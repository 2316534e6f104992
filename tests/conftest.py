"""Test environment: force CPU with 8 virtual devices so multi-chip sharding tests
run anywhere (SURVEY.md §4: the reference's CI runs the CPU-tagged subset only;
device tests are opt-in).

Env vars must be set before the CPU backend initializes; the platform must be
forced via jax.config as well, so that nothing an installation registers at
interpreter start-up can take priority over JAX_PLATFORMS: the tests are the
CPU way of running this program (the chip way is ``python chip_smoke.py``).

Capability-probed skips (ISSUE 9 satellite): some environments — notably the
pinned jax-0.4.37 CPU container — lack capabilities whole test families need
(``jax.shard_map``, a ``pinned_host`` memory space on the CPU backend, CPU
multiprocess collectives, ...).  Those tests used to FAIL there, burying real
regressions under a constant red count.  Each such family carries a
``needs_<capability>`` marker (registered in pytest.ini); the probes below run
lazily (once per session, only when a marked test is about to run) and a missing
capability turns the family into *skips* with the probe's reason — so a red
tier-1 line means a real regression, and on a fully-capable environment (CI's
current jax) every probe passes and nothing is skipped.
"""

import functools
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import pytest

jax.config.update("jax_platforms", "cpu")


# -- capability probes (lazy, cached, cheap-first) ---------------------------

@functools.lru_cache(maxsize=None)
def _has_shard_map() -> bool:
    """jax.shard_map moved out of jax.experimental after 0.4.x; the mesh
    lowering paths use the top-level name."""
    return hasattr(jax, "shard_map")


@functools.lru_cache(maxsize=None)
def _has_pinned_host() -> bool:
    """TraceExecutor.place_host_buffers needs a ``pinned_host`` memory
    space; old CPU backends expose only ``unpinned_host``."""
    try:
        kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
    except Exception:
        return False
    return "pinned_host" in kinds


@functools.lru_cache(maxsize=None)
def _has_profile_data() -> bool:
    """jax.profiler.ProfileData (the xplane parser) arrived after 0.4.37."""
    try:
        from jax.profiler import ProfileData  # noqa: F401
        return True
    except ImportError:
        return False


@functools.lru_cache(maxsize=None)
def _has_tie_hlo() -> bool:
    """Does this backend's *compiled* HLO preserve the executor's
    select-based ordering ties?  Old XLA CPU folds the whole token chain
    of a traced program away (the lowered HLO still has the selects), so
    schedule order is not physically represented and the compiled-text
    assertions cannot hold.  Probed on the smallest real program — a
    2-lane diamond through TraceExecutor — because no pure-jax repro
    folds the same way (the fold needs the full chain structure)."""
    try:
        import jax.numpy as jnp

        from tenzing_tpu.core.graph import Graph
        from tenzing_tpu.core.operation import DeviceOp
        from tenzing_tpu.core.platform import Platform
        from tenzing_tpu.core.state import State
        from tenzing_tpu.runtime.executor import TraceExecutor

        class _Add(DeviceOp):
            def __init__(self, name, src, dst):
                super().__init__(name)
                self._src, self._dst = src, dst

            def reads(self):
                return [self._src]

            def writes(self):
                return [self._dst]

            def apply(self, bufs, ctx):
                return {self._dst: bufs[self._src] + 1.0}

        g = Graph()
        a, b, c = _Add("a", "x", "u"), _Add("b", "u", "v"), _Add("c", "v", "w")
        g.start_then(a)
        g.then(a, b)
        g.then(b, c)
        g.then_finish(c)
        plat = Platform.make_n_lanes(2)
        st = State(g)
        while not st.is_terminal():
            st = st.apply(st.get_decisions(plat)[0])
        ex = TraceExecutor(plat, {k: jnp.zeros((4,), jnp.float32)
                                  for k in ("x", "u", "v", "w")})
        txt = ex.compiled_text(st.sequence)
        return ("select(" in txt) or ("select.s" in txt) or (" select" in txt)
    except Exception:
        return False


@functools.lru_cache(maxsize=None)
def _has_multiprocess_cpu() -> bool:
    """Can two CPU processes form a jax.distributed job and run a
    collective?  Old CPU backends answer 'Multiprocess computations
    aren't implemented'.  Probed with two tiny subprocesses (a few
    seconds, once per session, only when a marked test is about to run)."""
    import socket
    import subprocess
    import sys

    driver = (
        "import os, sys\n"
        "pid, port = int(sys.argv[1]), sys.argv[2]\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "os.environ.pop('XLA_FLAGS', None)\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "jax.distributed.initialize(\n"
        "    coordinator_address=f'localhost:{port}',\n"
        "    num_processes=2, process_id=pid)\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import multihost_utils\n"
        "v = multihost_utils.broadcast_one_to_all(jnp.float32(7.0))\n"
        "assert float(v) == 7.0\n"
    )
    try:
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = str(s.getsockname()[1])
        env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        env["JAX_PLATFORMS"] = "cpu"
        procs = [subprocess.Popen([sys.executable, "-c", driver,
                                   str(pid), port], env=env,
                                  stdout=subprocess.DEVNULL,
                                  stderr=subprocess.DEVNULL)
                 for pid in (0, 1)]
        ok = True
        for p in procs:
            try:
                ok = (p.wait(timeout=120) == 0) and ok
            except subprocess.TimeoutExpired:
                p.kill()
                ok = False
        return ok
    except OSError:
        return False


_CAPABILITIES = {
    "needs_shard_map": (
        _has_shard_map,
        "jax.shard_map is unavailable (mesh lowering paths cannot run)"),
    "needs_pinned_host": (
        _has_pinned_host,
        "the CPU backend has no pinned_host memory space "
        "(TraceExecutor.place_host_buffers cannot stage host buffers)"),
    "needs_multiprocess": (
        _has_multiprocess_cpu,
        "multiprocess computations are not implemented on this CPU backend"),
    "needs_profile_data": (
        _has_profile_data,
        "jax.profiler.ProfileData (xplane parser) is unavailable"),
    "needs_tie_hlo": (
        _has_tie_hlo,
        "this backend's compiled HLO folds the select-based ordering "
        "ties away (schedule order is not physically represented)"),
}


def pytest_runtest_setup(item):
    # per-test setup, not collection: a probe (the multiprocess one costs
    # two subprocesses) only ever runs when a marked test is actually
    # about to execute — `-k`, `-m` and --collect-only stay probe-free —
    # and the lru_cache makes it once per session regardless
    for marker, (probe, why) in _CAPABILITIES.items():
        if item.get_closest_marker(marker) is None:
            continue
        if not probe():
            pytest.skip(f"environment capability absent: {why}")
