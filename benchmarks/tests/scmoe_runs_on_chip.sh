# The measurements of PR 46's cell (PERF.md sections 2, 4, 5 and 6), each part one call
# on four chips:
#   chiprun --chips 4 --timeout 1800 -- bash benchmarks/tests/scmoe_runs_on_chip.sh step1
#     step 1 as the configuration stands: three seeds; on the first naive, the start point
#     and the start point on the ring exchanges with first calls, clocks, fence gaps, the
#     start point's device ms by operation kind and the peak; on every seed check and the
#     reference's three controls
#   git archive <parent> | tar -x -C .bench_checkout/parent (then BENCHMARK.json and
#   benchmarks/ of this tree copied over it), git archive $(git write-tree) | tar -x -C
#   .bench_checkout/change
#   chiprun --chips 4 --timeout 3300 -- bash benchmarks/tests/scmoe_runs_on_chip.sh runs [seed:trace ...]
#     (1) the new cell on the parent with this tree's benchmark files: it has to fail at
#     once; (2) the new cell from the files git would commit, every run on a seed of its own
#   chiprun [--chips 4] --timeout 1500 -- bash benchmarks/tests/scmoe_runs_on_chip.sh pair <cell> <seed> <trace>
#     an old cell from both checkouts: parent, change, change, parent
#   chiprun --chips 4 --timeout 3500 -- bash benchmarks/tests/scmoe_runs_on_chip.sh all [seed:trace ...]
#     step 1, then moonlight-ep4.mcts from parent and change, then the runs, in one call
# Results under chiprun_out/scmoe46/ (step 1's JSON under chiprun_out/).  PR 46's own calls: `all`
# (29 minutes, 116 chip-minutes: step 1, the moonlight pair, the parent's refusal, six runs on
# the first draw of W_UK), then `runs 2147501009:0 2147502013:0 2147503021:0` in substance
# (three whole runs after the two cures of PERF.md section 6, without the parent's part).
root=$(pwd); out=$root/chiprun_out/scmoe46; mkdir -p $out
cell=longcat-lite-scmoe-decode.climb
one() {  # cell seed trace tag: one run in the current directory
  t0=$(date +%s)
  python benchmarks/run.py --workload $1 --seed $2 --seconds 51 --trace $3 > $out/$4.out 2> $out/$4.err
  rc=$?; t1=$(date +%s)
  echo "== $4 rc=$rc wall=$((t1 - t0)) s"
  grep "^set-up\|^window\|^clock\|^epilogue\|NOT CORRECT" $out/$4.out
  [ $rc -ne 0 ] && tail -n 6 $out/$4.err
  last=$(tail -n 1 $out/$4.out); case "$last" in "{"*) ;; *) last=null ;; esac
  echo "{\"tag\": \"$4\", \"cell\": \"$1\", \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"wall_s\": $((t1 - t0)), \"result\": $last}" >> $out/runs.jsonl
  [ "$last" != null ] && echo "$last" | cut -c1-2600
}
step1() {
  python benchmarks/tests/scmoe_step1_on_chip.py --workload $cell \
    --seeds 2147483659,2147483693,2147483713 --controls --ring 2>&1 | grep -v "^W0\|^I0\|^E0\|^E1" | cut -c1-3500
}
pair() {  # cell seed trace sides...: one run from each checkout named
  c=$1; s=$2; t=$3; shift 3
  for side in "$@"; do
    ( cd .bench_checkout/$side && one $c $s $t $side.$c.$s.t$t.$RANDOM; rm -rf benchmarks/out )
  done
}
case "$1" in
step1) step1; exit $? ;;
pair) pair $2 $3 $4 parent change change parent; exit 0 ;;
runs) shift ;;
all)  # where a four-chip machine is hard to come by: everything in one call
  shift; step1; echo "== step 1 returned $?"
  pair moonlight-ep4.mcts 2147495001 0 parent change ;;
*) echo "step1 | runs [seed:trace ...] | pair <cell> <seed> <trace> | all [seed:trace ...]"; exit 2 ;;
esac
echo "== (1) the new cell on the parent"
( cd .bench_checkout/parent && one $cell 2147487001 0 parent.new )
echo "== (2) the new cell from the committed files"
specs="$@"
[ -z "$specs" ] && specs="2147488001:0 2147489003:0 2147490007:1 2147491013:0 2147492017:0 2147493023:0"
cd .bench_checkout/change
for spec in $specs; do
  seed=${spec%%:*}; trace=${spec##*:}
  one $cell $seed $trace change.$seed.t$trace
  cp benchmarks/out/$cell.seed$seed/record.trace$trace.json $out/change.$seed.t$trace.record.json 2>/dev/null
  rm -rf benchmarks/out
done
