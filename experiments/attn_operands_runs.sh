# ISSUE 37: whole benchmark runs of one cell, parent against change, in one call.
#   mkdir -p .bench_checkout/parent .bench_checkout/change
#   git archive <parent commit> | tar -x -C .bench_checkout/parent
#   git add -A && git archive $(git write-tree) | tar -x -C .bench_checkout/change
#   chiprun --timeout 3000 -- bash experiments/attn_operands_runs.sh <cell> <trace 0|1> <side:seed> ...
# Each run is `python benchmarks/run.py` from its side's checkout (the change's holds only the
# files git would commit), on the seed given: the two sides of a pair share one, every other
# run has its own.  Per run: the exit code, the whole run's seconds, the result line (tagged)
# appended to chiprun_out/attn_operands_runs.jsonl.
cell=$1; trace=$2; shift 2
mkdir -p chiprun_out; out=$PWD/chiprun_out
for spec in "$@"; do
  IFS=: read side seed <<< "$spec"
  tag=$cell.$side.$seed.t$trace
  t0=$(date +%s)
  ( cd .bench_checkout/$side && python benchmarks/run.py --workload $cell --seed $seed --seconds 51 --trace $trace > $out/$tag.out 2> $out/$tag.err )
  rc=$?
  secs=$(( $(date +%s) - t0 ))
  echo "== $tag rc=$rc seconds=$secs"
  grep "^set-up\|^window\|NOT CORRECT" $out/$tag.out
  [ $rc -ne 0 ] && tail -n 8 $out/$tag.err
  echo "{\"cell\": \"$cell\", \"side\": \"$side\", \"seed\": $seed, \"trace\": $trace, \"rc\": $rc, \"seconds\": $secs, \"result\": $(tail -n 1 $out/$tag.out)}" >> $out/attn_operands_runs.jsonl
  tail -n 1 $out/$tag.out | cut -c1-600
done
