"""ISSUE 37: one ``attn_fused`` call of ``trinity-attn32k`` alone in a loop, by
the form of its operands.

The start point's program read the full layer's four kernels 0.97 ms slower an
iteration once they took Q, K and V whole, and the window layers' twelve not
at all (``attn_operands_step0.json``).  Is it the index maps?  Each form here is
the kernel alone in a ``fori_loop`` (O carried in place, the token taken from
the last O onto the positions), timed at ``LO`` and ``HI`` calls: the slope,
ms a call.  Forms: ``parent`` (rows and keys sliced out before the loop),
``whole`` (the committed form), ``q_whole`` / ``kv_whole`` (one side each),
``whole_qpad`` / ``whole_kvpad`` / ``whole_vpad`` (the buffers padded, so
their strides and relative offsets differ), ``whole_fresh_o`` (O not aliased),
``whole_notok``.  Read (my chip run, PR 37, at the tree before ``walk_step``
moved the idle steps; ``attn_operand_forms.jsonl``):
every form within 0.06 ms of ``parent`` on every block, so nothing in the index
maps costs; what differed in the program was where XLA kept the operands and
which fetches the grid hid (PERF.md section 6, PR 37).  ``pl.Buffered(3)`` on
the K/V specs is refused by Mosaic here ("Only single (1) and double (2)
buffering are supported").

    chiprun -- python experiments/attn_operand_forms_on_chip.py [blocks]

One process, two minutes; ``KERN_AB_SHRINK=8 JAX_PLATFORMS=cpu`` rehearses it
in the interpreter.  Prints one JSON line a (block, form).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
from jax import lax

from tenzing_tpu.ops.attention_pallas import attn_fused_pallas

F = int(os.environ.get("KERN_AB_SHRINK", "1"))  # a CPU rehearsal: 8
H, HKV, N, D = 32 // F, 4, 16384 // F, 128
LO, HI, REPS = (4, 16, 3) if F == 1 else (1, 2, 1)
BKV = 1024 // F
SCALE = D ** -0.5

# (name, q0, rows, k0, keys, window): three blocks of the full layer, one of
# a window layer
BLOCKS = [("full.b3", 12288, 4096, 0, 16384, None),
          ("full.b0", 0, 4096, 0, 4096, None),
          ("full.b2", 8192, 4096, 0, 12288, None),
          ("win.b1", 4096, 4096, 2048, 6144, 2048)]
BLOCKS = [(n, a // F, b // F, c // F, d // F, w and w // F)
          for n, a, b, c, d, w in BLOCKS]
if len(sys.argv) > 1:
    BLOCKS = [b for b in BLOCKS if b[0] in sys.argv[1].split(",")]
FORMS = ["parent", "whole", "q_whole", "kv_whole", "whole_qpad",
         "whole_kvpad", "whole_vpad", "whole_fresh_o", "whole_notok",
         "parent", "whole"]  # the first two again, for the noise


def rows_of(x, r0, n):
    return lax.dynamic_slice_in_dim(x, r0, n, 1)


def make_call(form, q0, rows, k0, keys, window):
    """``f(Q, K, V, o, tok) -> o`` for one form."""
    kw = dict(scale=SCALE, bkv=BKV, q_pos=q0 - k0, causal=True, window=window,
              finish=True, o_row0=q0)

    def f(q, k, v, o, tok):
        at = {}
        if form in ("parent", "kv_whole"):
            q = rows_of(q, q0, rows)
        else:
            at.update(q_row0=q0, rows=rows)
        if form in ("parent", "q_whole"):
            k, v = rows_of(k, k0, keys), rows_of(v, k0, keys)
        else:
            at.update(k_row0=k0, keys=keys)
        if form == "whole_notok":
            tok = None
        if form == "whole_fresh_o":
            out = attn_fused_pallas(q, k, v, None, None, None, tok=tok,
                                    **{**kw, "o_row0": 0}, **at)
            return lax.dynamic_update_slice_in_dim(o, out[:, :8], q0, 1)
        return attn_fused_pallas(q, k, v, None, None, None, o=o, tok=tok,
                                 **kw, **at)

    return f


def timed(form, blk, q, k, v, o):
    _, q0, rows, k0, keys, window = blk
    f = make_call(form, q0, rows, k0, keys, window)

    @jax.jit
    def run(q, k, v, o, n):
        def body(_, c):
            o, s = c
            o = f(q, k, v, o, (s != s).astype(jnp.int32))
            return o, s + o[0, q0, 0].astype(jnp.float32)
        o, s = lax.fori_loop(0, n, body, (o, jnp.float32(0)))
        return s + o[1, q0, 1].astype(jnp.float32)

    t0 = time.perf_counter()
    float(run(q, k, v, o, 1))
    first = time.perf_counter() - t0
    slopes = []
    for _ in range(REPS):
        ts = []
        for n in (LO, HI):
            t0 = time.perf_counter()
            float(run(q, k, v, o, n))
            ts.append(time.perf_counter() - t0)
        slopes.append((ts[1] - ts[0]) / (HI - LO) * 1e3)
    return first, slopes


def main():
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    bf = jnp.bfloat16
    q = jax.random.normal(kq, (H, N, D), bf)
    k = jax.random.normal(kk, (HKV, N, D), bf)
    v = jax.random.normal(kv, (HKV, N, D), bf)
    o = jnp.zeros((H, N, D), bf)

    def pad(x, r):
        return jnp.pad(x, ((0, 0), (0, r), (0, 0)))

    qp, kp, vp = pad(q, 512), pad(k, BKV), pad(v, BKV)
    jax.block_until_ready((q, k, v, o, qp, kp, vp))
    print(jax.devices()[0].device_kind, flush=True)
    for blk in BLOCKS:
        for form in FORMS:
            try:
                first, slopes = timed(
                    form, blk, qp if form == "whole_qpad" else q,
                    kp if form == "whole_kvpad" else k,
                    vp if form in ("whole_kvpad", "whole_vpad") else v, o)
                row = {"block": blk[0], "form": form,
                       "ms": sorted(slopes)[len(slopes) // 2],
                       "slopes": slopes, "first_s": first}
            except Exception as e:  # a form Mosaic refuses: say so, go on
                row = {"block": blk[0], "form": form, "error": repr(e)[:300]}
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
