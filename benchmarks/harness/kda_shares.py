"""What the hybrid-decode cell's two device-trace readers of the KDA layers
share (``layer_metrics/kda_state_*``): which operation kinds of the traced
slice are the KDA vertices', and their share of the slice's time.  Kept
beside ``harness/kda_costs.py`` and ``harness/dsa_shares.py``."""

from __future__ import annotations

#: a dispatch does these once, outside the repeat-n loop (the written
#: buffers' copies into the loop's carry, the fence's reductions); the loop's
#: own copies are a hundredth of an iteration
ONCE = ("copy", "reduce")


def is_kda(kind: str) -> bool:
    """An operation kind of a KDA vertex: the ``kda_step`` kernel, or an XLA
    fusion.  In this cell's programs the fusions are the XLA chain's (its
    state step's two passes, convolution step, gates, output norm): what
    else XLA fuses (the absorb, the up-projection, a latent chain's
    finaliser) is 0.012 ms of an iteration's 4.7 (PERF.md section 5)."""
    return kind.startswith("kda_step") or "fusion" in kind


def kda_seconds(record):
    """``(KDA vertices' seconds, the loop's seconds, busy seconds)`` of the
    first device in the traced slice of the window, from its ten longest
    operation kinds (``harness/trace.py``); the loop's are all but
    :data:`ONCE`.  ``None`` where the slice lists no ``kda_step``: a program
    without the kernel has nothing to read here."""
    w = (record.get("trace") or {}).get("window")
    if not w or not w.get("busy_s"):
        return None
    ops = w["device_ops"]
    if not any(name.startswith("kda_step") for name, _ in ops):
        return None
    kda = sum(s for name, s in ops if is_kda(name))
    loop = sum(s for name, s in ops if not name.startswith(ONCE))
    return kda, loop, w["busy_s"]
