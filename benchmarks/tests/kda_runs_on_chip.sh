# The measurements of PR 42's cell (PERF.md sections 2, 4, 5 and 6), each part one call:
#   chiprun --timeout 1800 -- bash benchmarks/tests/kda_runs_on_chip.sh sweep
#     step 1's sweep at one seed: the kernel alone and the chain alone (--parts), naive,
#     control and peak at the configuration's shapes, then the start point at pages of 512
#     and 2048 and at 2 and 8 groups (what page_tokens, groups and kda_groups were set from)
#   chiprun --timeout 1800 -- bash benchmarks/tests/kda_runs_on_chip.sh step1
#     step 1 as the configuration stands: three seeds, naive, both controls, fence gap, peak
#   git archive <parent> | tar -x -C .bench_checkout/parent (then BENCHMARK.json and
#   benchmarks/ of this tree copied over it), git archive $(git write-tree) | tar -x -C
#   .bench_checkout/change
#   chiprun --timeout 3300 -- bash benchmarks/tests/kda_runs_on_chip.sh runs [seed:trace ...]
#     (1) the new cell on the parent with this tree's benchmark files: it has to fail at once;
#     (2) an old cell, traced, on the same parent-with-overlay; (3) the new cell from the
#     files git would commit, every run on a seed of its own; (4) naive and the start
#     point by vertex (tests/op_scopes_on_chip.py, PR 38's path) from the same files.
#   chiprun --timeout 3500 -- bash benchmarks/tests/kda_runs_on_chip.sh all [seed:trace ...]
#     where a machine is hard to come by, the three in one call: the sweep, then
#     tests/kda_choose.py sets page_tokens, groups, kda_groups (and the pre-declared cut to
#     64 sequences, if the peak at 128 leaves under 2 GB free) from the sweep's readings in
#     the configuration's file of this tree and of both checkouts, and leaves what it chose
#     and why in chiprun_out/kda42/chosen.json, to be applied to the committed file; then
#     step 1 and the whole runs at what it chose.
# Results under chiprun_out/kda42/ (step 1's JSON under chiprun_out/).
root=$(pwd); out=$root/chiprun_out/kda42; mkdir -p $out
cell=kimi-linear-kda-decode.climb
s1="python benchmarks/tests/kda_step1_on_chip.py --workload $cell"
sweep() {
  $s1 --seeds 2147483659 --parts --control 2>&1 | grep -v "^W0\|^I0" | cut -c1-3000
  for page in 512 2048; do
    $s1 --seeds 2147483659 --page $page --skip-naive 2>&1 | grep "^seed\|^{" | cut -c1-2500
  done
  for g in 2 8; do
    $s1 --seeds 2147483659 --groups $g --kda-groups $g --skip-naive 2>&1 | grep "^seed\|^{" | cut -c1-2500
  done
}
step1() {
  $s1 --seeds 2147483659,2147483693,2147483713 --control 2>&1 | grep -v "^W0\|^I0" | cut -c1-3000
}
case "$1" in
sweep) sweep; exit 0 ;;
step1) step1; exit $? ;;
runs) shift ;;
all) shift; sweep
  mkdir -p $out/sweep; cp chiprun_out/kda_step1.*.json $out/sweep/
  python benchmarks/tests/kda_choose.py . .bench_checkout/parent .bench_checkout/change || exit 1
  step1 || echo "== step 1 returned $?" ;;
*) echo "sweep | step1 | runs [seed:trace ...] | all [seed:trace ...]"; exit 2 ;;
esac
: > $out/runs.jsonl
one() {  # cell seed trace tag: one run in the current directory
  t0=$(date +%s)
  python benchmarks/run.py --workload $1 --seed $2 --seconds 51 --trace $3 > $out/$4.out 2> $out/$4.err
  rc=$?; t1=$(date +%s)
  echo "== $4 rc=$rc wall=$((t1 - t0)) s"
  grep "^set-up\|^window\|^clock\|^epilogue\|NOT CORRECT" $out/$4.out
  [ $rc -ne 0 ] && tail -n 4 $out/$4.err
  last=$(tail -n 1 $out/$4.out); case "$last" in "{"*) ;; *) last=null ;; esac
  echo "{\"tag\": \"$4\", \"cell\": \"$1\", \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"wall_s\": $((t1 - t0)), \"result\": $last}" >> $out/runs.jsonl
  [ "$last" != null ] && echo "$last" | cut -c1-2200
}
echo "== (1) the new cell on the parent"
( cd .bench_checkout/parent && one $cell 2147487001 0 parent.new )
echo "== (2) an old cell, traced, on the parent with this tree's benchmark files"
( cd .bench_checkout/parent && one dsv3-mla-decode.climb 2147487003 1 parent.dsv3.t1 )
echo "== (3) the new cell from the committed files"
specs="$@"
[ -z "$specs" ] && specs="2147488001:0 2147489003:0 2147490007:1 2147491013:0 2147492017:0 2147493023:0 2147494029:0"
cd .bench_checkout/change
for spec in $specs; do
  seed=${spec%%:*}; trace=${spec##*:}
  one $cell $seed $trace change.$seed.t$trace
  cp benchmarks/out/$cell.seed$seed/record.trace$trace.json $out/change.$seed.t$trace.record.json 2>/dev/null
  rm -rf benchmarks/out
done
echo "== (4) naive and the start point by vertex"
python benchmarks/tests/op_scopes_on_chip.py --workload $cell > $out/op_scopes.out 2> $out/op_scopes.err
echo "rc=$?"; grep -v "^W0\|^I0" $out/op_scopes.out | cut -c1-220 | tail -n 120
