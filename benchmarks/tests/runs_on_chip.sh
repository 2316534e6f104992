# A few runs in one call, each "cell:seed:seconds:trace":
#   chiprun --timeout 1800 -- bash benchmarks/tests/runs_on_chip.sh halo512.mcts:301:51:0 spmv16k.dfs:302:25:1
# Result lines go to chiprun_out/runs.jsonl (appended, tagged); the lines that say where
# a run's time went and what was compared are echoed.
mkdir -p chiprun_out
for spec in "$@"; do
  IFS=: read cell seed secs trace <<< "$spec"
  tag=$cell.$seed.t$trace
  python benchmarks/run.py --workload $cell --seed $seed --seconds $secs --trace $trace \
    > chiprun_out/$tag.out 2> chiprun_out/$tag.err
  rc=$?
  echo "== $tag rc=$rc"
  grep "^set-up\|^window\|^clock\|^compared\|^epilogue\|NOT CORRECT" chiprun_out/$tag.out
  [ $rc -ne 0 ] && tail -n 8 chiprun_out/$tag.err
  echo "{\"cell\": \"$cell\", \"seed\": $seed, \"seconds\": $secs, \"trace\": $trace, \"rc\": $rc, \"result\": $(tail -n 1 chiprun_out/$tag.out)}" >> chiprun_out/runs.jsonl
  tail -n 1 chiprun_out/$tag.out | cut -c1-1500
  cp benchmarks/out/$cell.seed$seed/record.trace$trace.json chiprun_out/$tag.record.json 2>/dev/null
done
