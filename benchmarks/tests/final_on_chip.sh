# The last call of a benchmark PR's session, on the final tree:
#   (make .bench_checkout/ first: git archive $(git write-tree) | tar -x -C .bench_checkout)
#   chiprun --timeout 3000 -- bash benchmarks/tests/final_on_chip.sh
# controls at the cells' own size, the timed path broken underneath, further seeds, one
# traced run a cell, a run from the files git would commit, and the refusal in a directory
# that holds only the benchmark.
mkdir -p chiprun_out
echo "== controls"
python benchmarks/tests/control_on_chip.py --workload halo512.climb --seeds 11,12,2147483801 --sound-seeds 13 2>&1 | grep -v "hugepages\|warnings.warn"
python benchmarks/tests/control_on_chip.py --workload spmv16k.dfs --seeds 11,12,13,14,15,2147483801 --sound-seeds 21,22,23,24,25,26,27,28,29,30,31,2147483803 2>&1 | grep -v "hugepages\|warnings.warn"
echo "== the timed program broken underneath"
for cell in halo512.climb spmv16k.dfs; do
  python benchmarks/tests/broken_on_chip.py --workload $cell --seed 2147483811 --seconds 10 2>&1 | grep "timed_fence_gap\|NOT CORRECT\|broken run"
done
echo "== traced runs and further seeds"
bash benchmarks/tests/runs_on_chip.sh halo512.climb:121:51:1 spmv16k.dfs:221:51:1 \
  halo512.climb:122:25:0 halo512.climb:123:25:0 halo512.climb:2147483807:25:0 \
  spmv16k.dfs:222:25:0 spmv16k.dfs:223:25:0 spmv16k.dfs:2147483807:25:0
echo "== from the committed files only"
( cd .bench_checkout && ls && python benchmarks/run.py --workload spmv16k.dfs --seed 224 --seconds 25 --trace 0 > ../chiprun_out/archive.out 2> ../chiprun_out/archive.err; echo "archive rc=$?"; grep "^set-up\|^window" ../chiprun_out/archive.out; tail -n 1 ../chiprun_out/archive.out | cut -c1-1500; tail -n 3 ../chiprun_out/archive.err )
echo "== a directory with only the benchmark"
mkdir -p .bench_checkout/only && cp -r .bench_checkout/BENCHMARK.json .bench_checkout/benchmarks .bench_checkout/only/
( cd .bench_checkout/only && python benchmarks/run.py --workload spmv16k.dfs --seed 225 --seconds 5 --trace 0 > ../../chiprun_out/only.out 2> ../../chiprun_out/only.err; echo "only rc=$?"; echo "stdout lines: $(wc -l < ../../chiprun_out/only.out)"; tail -n 2 ../../chiprun_out/only.err )
