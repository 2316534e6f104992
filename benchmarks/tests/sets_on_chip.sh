# Two sets of six runs of one cell, the same seeds in both sets, in one call
# (the contract's measurement for a bound):
#   chiprun --timeout 2400 -- bash benchmarks/tests/sets_on_chip.sh <cell> [seconds]
# Result lines go to chiprun_out/<cell>.sets.jsonl, one per run, tagged.
cell=$1; secs=${2:-51}
mkdir -p chiprun_out
out=chiprun_out/$cell.sets.jsonl; : > $out
for set in 1 2; do
  for seed in 2147483659 2147483693 2147483713 2147483743 2147483777 2147483783; do
    python benchmarks/run.py --workload $cell --seed $seed --seconds $secs --trace 0 \
      > chiprun_out/$cell.run.out 2> chiprun_out/$cell.run.err
    rc=$?
    echo "{\"set\": $set, \"seed\": $seed, \"rc\": $rc, \"result\": $(tail -n 1 chiprun_out/$cell.run.out)}" >> $out
    grep "^set-up\|^window\|^epilogue\|NOT CORRECT" chiprun_out/$cell.run.out
    [ $rc -ne 0 ] && tail -n 5 chiprun_out/$cell.run.err
  done
done
cat $out
