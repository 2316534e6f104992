# ISSUE 44's benchmark runs, parent against the tree git would commit, in one call:
#   mkdir -p .bench_checkout/parent .bench_checkout/change
#   git archive <parent commit> | tar -x -C .bench_checkout/parent
#   git add -A && git archive $(git write-tree) | tar -x -C .bench_checkout/change
#   chiprun --chips 4 --timeout 3000 -- bash experiments/halo_pack_runs.sh halo512-mesh4.mcts parent:S:0 change:S:0 ...
# Each spec is "side:seed:trace"; both sides of a pair share a seed.  Result lines go to
# chiprun_out/$PR_runs.jsonl, tagged; a traced run's record is kept beside them.  PR=pr47 in the
# environment names another PR's runs (ISSUE 47 ran the same pairs).
cell=$1; shift
PR=${PR:-pr44}
mkdir -p chiprun_out; out=$PWD/chiprun_out
for spec in "$@"; do
  IFS=: read side seed trace <<< "$spec"
  tag=$PR.$cell.$side.$seed.t$trace
  ( cd .bench_checkout/$side && python benchmarks/run.py --workload $cell --seed $seed --seconds 51 --trace $trace > $out/$tag.out 2> $out/$tag.err )
  rc=$?
  echo "== $tag rc=$rc"
  grep "^set-up\|^window\|NOT CORRECT" $out/$tag.out
  [ $rc -ne 0 ] && tail -n 8 $out/$tag.err
  echo "{\"cell\": \"$cell\", \"side\": \"$side\", \"seed\": $seed, \"trace\": $trace, \"rc\": $rc, \"result\": $(tail -n 1 $out/$tag.out)}" >> $out/${PR}_runs.jsonl
  tail -n 1 $out/$tag.out | cut -c1-600
  cp .bench_checkout/$side/benchmarks/out/$cell.seed$seed/record.trace$trace.json $out/$tag.record.json 2>/dev/null
  rm -rf .bench_checkout/$side/benchmarks/out
done
