# The accepted one-chip cells on a benchmark PR's final tree against the parent, in one call:
#   mkdir -p .bench_checkout/parent .bench_checkout/change
#   git archive <parent commit> | tar -x -C .bench_checkout/parent
#   git add -A && git archive $(git write-tree) | tar -x -C .bench_checkout/change
#   chiprun --timeout 3000 -- bash benchmarks/tests/pairs_on_chip.sh [cell ...]
# For each cell: parent, change, change, parent (two seeds, each on both sides).  The change
# runs from the files git would commit.  Last, the refusal in a directory that holds only the
# benchmark.  Result lines go to chiprun_out/pairs.jsonl, tagged.
cells=${@:-halo512.climb spmv16k.dfs}
mkdir -p chiprun_out; out=$PWD/chiprun_out
for cell in $cells; do
  for spec in parent:2147484101 change:2147484101 change:2147484102 parent:2147484102; do
    IFS=: read side seed <<< "$spec"
    tag=$cell.$side.$seed
    ( cd .bench_checkout/$side && python benchmarks/run.py --workload $cell --seed $seed --seconds 51 --trace 0 > $out/$tag.out 2> $out/$tag.err )
    rc=$?
    echo "== $tag rc=$rc"
    grep "^set-up\|^window\|timed_fence_gap\|NOT CORRECT" $out/$tag.out
    [ $rc -ne 0 ] && tail -n 8 $out/$tag.err
    echo "{\"cell\": \"$cell\", \"side\": \"$side\", \"seed\": $seed, \"rc\": $rc, \"result\": $(tail -n 1 $out/$tag.out)}" >> $out/pairs.jsonl
    tail -n 1 $out/$tag.out | cut -c1-420
  done
done
echo "== a directory with only the benchmark"
mkdir -p .bench_checkout/only && cp -r .bench_checkout/change/BENCHMARK.json .bench_checkout/change/benchmarks .bench_checkout/only/
( cd .bench_checkout/only && python benchmarks/run.py --workload spmv16k.dfs --seed 225 --seconds 5 --trace 0 > $out/only.out 2> $out/only.err; echo "only rc=$?"; echo "stdout lines: $(wc -l < $out/only.out)"; tail -n 2 $out/only.err )
