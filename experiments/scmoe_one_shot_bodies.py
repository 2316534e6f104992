"""PR 46, cause 2 (PERF.md section 6): does the one-shot program share the
timed program's loop body?  No chip: compiles, for the described v5e 2x2
host at ``longcat-lite-scmoe-decode.climb``'s own shapes, the timed repeat-n
program of naive (or ``start``), the straight-line one-shot program and the
loop-form one-shot program (``TraceExecutor._looped_fn``), and counts the
computations reachable from the timed program's ``while`` body that the
loop-form one lacks, with and without layouts and memory-space annotations;
then the fusions of the straight-line program that differ by vertex.

    JAX_PLATFORMS=cpu python experiments/scmoe_one_shot_bodies.py [naive|start]

Read at PR 46 (naive): 452 computations in the body, 39 differ, none beyond
layout or memory space; the straight-line program differs in the fusions of
absorb, up-project, a chain's finaliser, the query path and the experts'
product.
"""

import collections
import hashlib
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))


def computations(text):
    """``{name: [lines]}`` of a compiled module's computations."""
    out, cur = {}, None
    for line in text.split("\n"):
        m = re.match(r"^(ENTRY )?%?([\w\.\-]+) .*\{$", line)
        if m and not line.startswith(" "):
            cur = m.group(2)
            out[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            out[cur].append(line.strip())
    return out


def normalize(lines, layouts=True):
    out = []
    for l in lines:
        l = re.sub(r"metadata=\{[^}]*\}", "", l)
        l = re.sub(r"%[\w\-]+(\.[\w\-]+)*", "%v", l)
        l = re.sub(r"(calls|to_apply|body|condition)=%v", r"\1=C", l)
        if not layouts:
            l = re.sub(r"\{[\d,]*:[^}]*\}|\{[\d,]*\}", "", l)
        out.append(l)
    return out


def body_of(text):
    """The computations reachable from the largest ``while`` body."""
    cs = computations(text)
    name = max(re.findall(r"body=%?([\w\.\-]+)", text),
               key=lambda k: len(cs.get(k, [])))
    seen = {}

    def walk(n):
        if n in seen or n not in cs:
            return
        seen[n] = cs[n]
        for l in cs[n]:
            for c in re.findall(
                    r"(?:calls|to_apply|body|condition)=%?([\w\.\-]+)", l):
                walk(c)

    walk(name)
    return seen


def digests(comps, layouts):
    return collections.Counter(
        hashlib.md5("\n".join(normalize(v, layouts)).encode()).hexdigest()
        for v in comps.values())


def fusions_by_vertex(text):
    """``{vertex: Counter((shape, body digest))}`` of the fusions under
    ``tz.<vertex>/apply``."""
    cs = computations(text)
    rows = collections.defaultdict(collections.Counter)
    for lines in cs.values():
        for l in lines:
            scope = re.search(r'op_name="[^"]*tz\.([\w\.\-]+)/apply', l)
            called = re.search(r"calls=%?([\w\.\-]+)", l)
            if scope and called and " fusion(" in l:
                shape = re.sub(r"\{.*$", "", l.split(" = ")[1].split(" ")[0])
                body = hashlib.md5("\n".join(normalize(
                    cs.get(called.group(1), []), False)).encode()).hexdigest()
                rows[scope.group(1)][(shape, body[:8])] += 1
    return rows


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"  # the kernels, not the interpreter
    import test_tpu_compile as cell

    from benchmarks.builders.scmoe_decode import NAIVE, START, prefer_of
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models import shortcut_moe
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy

    which = sys.argv[1] if len(sys.argv) > 1 else "naive"
    args, mesh, bufs, specs, graph = cell._scmoe_cell(topo)
    naive = which == "naive"
    plat = Platform.make_n_lanes(1 if naive else 2, mesh=mesh, specs=specs)
    order = shortcut_moe.WRITTEN if naive else shortcut_moe.SHORTCUT
    seq, _ = drive(graph, plat, phase_policy(
        plat, shortcut_moe.phases(args, order),
        prefer_of(NAIVE if naive else START)))
    ex = TraceExecutor(plat, bufs)
    n = jax.ShapeDtypeStruct((), jnp.int32)
    ops = seq.vector()
    timed = jax.jit(ex._stepped_fn(ops)).lower(bufs, n).compile().as_text()
    looped = jax.jit(ex._looped_fn(ops)).lower(bufs, n).compile().as_text()
    straight = jax.jit(ex._build(seq)).lower(bufs).compile().as_text()
    a, b = body_of(timed), body_of(looped)
    for layouts in (True, False):
        da, db = digests(a, layouts), digests(b, layouts)
        print(f"{which}: loop body, {len(a)} | {len(b)} computations; "
              f"{'as compiled' if layouts else 'layouts aside'}: "
              f"{sum((da - db).values())} only in the timed program, "
              f"{sum((db - da).values())} only in the loop-form one-shot")
    fa, fb = fusions_by_vertex(timed), fusions_by_vertex(straight)
    differ = sorted(v for v in set(fa) | set(fb) if fa[v] != fb[v])
    print(f"{which}: vertices whose fusions the straight-line program forms "
          f"otherwise ({len(differ)}): {differ}")


if __name__ == "__main__":
    main()
