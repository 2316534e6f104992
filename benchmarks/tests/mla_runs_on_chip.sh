# The measurements of PR 35's cell, in one call (PERF.md sections 2, 5 and 6):
#   git archive <parent> | tar -x -C .bench_checkout/parent   (then BENCHMARK.json and
#   benchmarks/ of this tree copied over it), git archive $(git write-tree) | tar -x -C
#   .bench_checkout/change
#   chiprun --timeout 3300 -- bash benchmarks/tests/mla_runs_on_chip.sh [seed:trace ...]
# (1) the new cell on the parent with this tree's benchmark files: it has to fail at once;
# (2) an old cell, traced, on the same parent-with-overlay; (3) the new cell from the files
# git would commit, every run on a seed of its own.  Results under chiprun_out/mla35/.
root=$(pwd); out=$root/chiprun_out/mla35; mkdir -p $out; : > $out/runs.jsonl
one() {  # cell seed trace tag: one run in the current directory
  t0=$(date +%s)
  python benchmarks/run.py --workload $1 --seed $2 --seconds 51 --trace $3 > $out/$4.out 2> $out/$4.err
  rc=$?; t1=$(date +%s)
  echo "== $4 rc=$rc wall=$((t1 - t0)) s"
  grep "^set-up\|^window\|^clock\|^epilogue\|NOT CORRECT" $out/$4.out
  [ $rc -ne 0 ] && tail -n 4 $out/$4.err
  last=$(tail -n 1 $out/$4.out); case "$last" in "{"*) ;; *) last=null ;; esac
  echo "{\"tag\": \"$4\", \"cell\": \"$1\", \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"wall_s\": $((t1 - t0)), \"result\": $last}" >> $out/runs.jsonl
  [ "$last" != null ] && echo "$last" | cut -c1-1800
}
cell=dsv3-mla-decode.climb
echo "== (1) the new cell on the parent"
( cd .bench_checkout/parent && one $cell 2147487001 0 parent.new )
echo "== (2) an old cell, traced, on the parent with this tree's benchmark files"
( cd .bench_checkout/parent && one trinity-attn32k.climb 2147487003 1 parent.trinity.t1 )
echo "== (3) the new cell from the committed files"
specs="$@"
[ -z "$specs" ] && specs="2147488001:0 2147489003:0 2147490007:1 2147491013:0 2147492017:0 2147493023:0 2147494029:0 2147495033:0 2147496037:0 2147497043:0 2147498047:0 2147499053:0"
cd .bench_checkout/change
for spec in $specs; do
  seed=${spec%%:*}; trace=${spec##*:}
  one $cell $seed $trace change.$seed.t$trace
  cp benchmarks/out/$cell.seed$seed/record.trace$trace.json $out/change.$seed.t$trace.record.json 2>/dev/null
  rm -rf benchmarks/out
done
