# The first call of a sharded cell on its four chips, in ISSUE 27's order:
#   chiprun --chips 4 --timeout 2400 -- bash benchmarks/tests/mesh_steps_on_chip.sh <cell> <source's cells a shard>
# what a chip holds at the source's size and at the cell's, the control and sound readings at the
# cell's own size, then one whole run untraced and one traced.  Each step is bounded, so a hang
# costs its limit and not the call's.
cell=$1; full=$2
mkdir -p chiprun_out
quiet='hugepages\|warnings.warn'
echo "== memory at the source's size"
timeout 500 python benchmarks/tests/mesh_memory_on_chip.py --workload $cell --cells $full 2>&1 | grep -v "$quiet" | tail -n 14
echo "== memory at the cell's size"
timeout 500 python benchmarks/tests/mesh_memory_on_chip.py --workload $cell 2>&1 | grep -v "$quiet" | tail -n 14
echo "== control and sound readings"
timeout 600 python benchmarks/tests/control_on_chip.py --workload $cell --seeds 2147484011,2147484012,2147484013 --sound-seeds 2147484021,2147484022 2>&1 | grep -v "$quiet" | tail -n 14
echo "== one run untraced, one traced"
timeout 1500 bash benchmarks/tests/runs_on_chip.sh $cell:2147484001:51:0 $cell:2147484002:51:1
