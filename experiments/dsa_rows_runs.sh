# ISSUE 41's chip calls, each one machine for everything it reads (chips were scarce):
#   mkdir -p .bench_checkout/parent .bench_checkout/change
#   git archive <parent commit> | tar -x -C .bench_checkout/parent
#   git add -A && git archive $(git write-tree) | tar -x -C .bench_checkout/change
#   chiprun --timeout 3000 -- bash experiments/dsa_rows_runs.sh step1 [seed]
#   chiprun --timeout 3000 -- bash experiments/dsa_rows_runs.sh vertex
#   chiprun --timeout 3000 -- bash experiments/dsa_rows_runs.sh runs <cell> <trace 0|1> <side:seed> ...
#   chiprun --timeout 3000 -- bash experiments/dsa_rows_runs.sh all <side:seed> ...   (the three, untraced pairs of the claimed cell)
# step1: experiments/dsa_rows_step1_on_chip.py from the tree as it stands.  vertex: the
# start point's device ms an iteration by vertex, from the change's checkout and from the
# parent's (benchmarks/tests/op_scopes_on_chip.py is the same file in both).  runs: whole
# benchmark runs from a side's checkout (experiments/attn_operands_runs.sh: the change's
# holds only the files git would commit; the two sides of a pair share a seed).
cell=dsv32-dsa-decode.climb
phase=$1; shift
mkdir -p chiprun_out; out=$PWD/chiprun_out
if [ "$phase" = step1 ]; then
  python experiments/dsa_rows_step1_on_chip.py ${1:+--seed $1} > $out/dsa_rows_step1.out 2>&1; rc=$?
  grep -v "^W0\|^I0" $out/dsa_rows_step1.out | tail -n 60
  rm -rf benchmarks/out
  exit $rc
elif [ "$phase" = vertex ]; then
  for side in change parent; do
    ( cd .bench_checkout/$side && python benchmarks/tests/op_scopes_on_chip.py --workload $cell --only start > $out/dsa_rows.op_scopes.$side.out 2>&1; rm -rf benchmarks/out )
    echo "== by vertex, $side"; grep -v "^mixed" $out/dsa_rows.op_scopes.$side.out | tail -n 45
  done
elif [ "$phase" = runs ]; then
  bash experiments/attn_operands_runs.sh "$@"
  for side in parent change; do rm -rf .bench_checkout/$side/benchmarks/out; done
else
  bash experiments/dsa_rows_runs.sh step1 || exit 1  # (b) is not (c): nothing else to read
  bash experiments/dsa_rows_runs.sh vertex
  bash experiments/dsa_rows_runs.sh runs $cell 0 "$@"
fi
