# The measurements of PR 40's cell (PERF.md sections 2, 5 and 6), two calls:
#   git archive <parent> | tar -x -C .bench_checkout/parent   (then BENCHMARK.json and
#   benchmarks/ of this tree copied over it), git archive $(git write-tree) | tar -x -C
#   .bench_checkout/change
#   chiprun --timeout 1500 -- bash benchmarks/tests/dsa_runs_on_chip.sh step1 [seed,seed]
#   chiprun --timeout 1500 -- bash benchmarks/tests/dsa_runs_on_chip.sh old
#   chiprun --timeout 3300 -- bash benchmarks/tests/dsa_runs_on_chip.sh new [seed:trace ...]
#   (or all three in one call: ... dsa_runs_on_chip.sh all)
# old: (1) the new cell on the parent with this tree's benchmark files: it has to fail at
# once; (2) an old cell, traced, on the same parent-with-overlay (what this PR adds to the
# benchmark runs on a program that lacks what it adds to the program); (3) the two cells
# that share the changed code, traced, from the files git would commit.
# new: the new cell from the committed files, every run on a seed of its own.
# Results under chiprun_out/dsa40/.
root=$(pwd); out=$root/chiprun_out/dsa40; mkdir -p $out
one() {  # cell seed trace tag: one run in the current directory
  t0=$(date +%s)
  python benchmarks/run.py --workload $1 --seed $2 --seconds 51 --trace $3 > $out/$4.out 2> $out/$4.err
  rc=$?; t1=$(date +%s)
  echo "== $4 rc=$rc wall=$((t1 - t0)) s"
  grep "^set-up\|^window\|^clock\|^epilogue\|NOT CORRECT" $out/$4.out
  [ $rc -ne 0 ] && tail -n 4 $out/$4.err
  last=$(tail -n 1 $out/$4.out); case "$last" in "{"*) ;; *) last=null ;; esac
  echo "{\"tag\": \"$4\", \"cell\": \"$1\", \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"wall_s\": $((t1 - t0)), \"result\": $last}" >> $out/runs.$phase.jsonl
  [ "$last" != null ] && echo "$last" | cut -c1-2400
  rm -rf benchmarks/out
}
cell=dsv32-dsa-decode.climb
phase=$1; shift
: > $out/runs.$phase.jsonl
if [ "$phase" = all ]; then
  # one machine for everything (chips were scarce at PR 40)
  bash benchmarks/tests/dsa_runs_on_chip.sh step1 2147483659
  bash benchmarks/tests/dsa_runs_on_chip.sh old
  bash benchmarks/tests/dsa_runs_on_chip.sh new "$@"
elif [ "$phase" = vertex ]; then
  # the start point's device time by vertex alone (what dsa_index_roofline is held against)
  python benchmarks/tests/op_scopes_on_chip.py --workload $cell --only start > $out/op_scopes.start.out 2>&1
  grep -v "^mixed" $out/op_scopes.start.out | tail -n 60
elif [ "$phase" = step1 ]; then
  # step 1 from the tree as it stands: each part alone, the whole program on the seeds
  # named (three without) with the control, the start point's device time by vertex (what
  # dsa_index_roofline is held against); a whole run's length is phase new's to read
  python benchmarks/tests/dsa_step1_on_chip.py --workload $cell --parts 2>&1 | tail -n 45
  python benchmarks/tests/dsa_step1_on_chip.py --workload $cell --control ${1:+--seeds $1} 2>&1 | grep -v "^W0\\|^I0" | tail -n 12
  python benchmarks/tests/op_scopes_on_chip.py --workload $cell --only start > $out/op_scopes.start.out 2>&1
  grep -v "^mixed" $out/op_scopes.start.out | tail -n 60
elif [ "$phase" = old ]; then
  echo "== (1) the new cell on the parent"
  ( cd .bench_checkout/parent && one $cell 2147487001 0 parent.new )
  echo "== (2) an old cell, traced, on the parent with this tree's benchmark files"
  ( cd .bench_checkout/parent && one dsv3-mla-decode.climb 2147487003 1 parent.mla.t1 )
  echo "== (3) the cells that share the changed code, traced, from the committed files"
  cd .bench_checkout/change
  one dsv3-mla-decode.climb 2147487005 1 change.mla.t1
  one trinity-attn32k.climb 2147487007 1 change.trinity.t1
else
  specs="$@"
  [ -z "$specs" ] && specs="2147488001:0 2147489003:0 2147490007:1 2147491013:0 2147492017:0 2147493023:0 2147494029:0 2147495033:0 2147496037:0 2147497043:0 2147498047:0 2147499053:0"
  cd .bench_checkout/change
  for spec in $specs; do
    seed=${spec%%:*}; trace=${spec##*:}
    one $cell $seed $trace change.$seed.t$trace
  done
fi
