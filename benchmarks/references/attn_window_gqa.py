"""Plain reference of a run of attention layers with grouped heads, a causal
mask and, in some layers, a sliding window (Trinity-Mini's ``afmoe``: three
``sliding_attention`` layers and one ``full_attention`` layer a period).

Imports nothing of the program (a copy of ``tenzing_tpu/models/
attention_reference.py``'s equations; ``tests/test_attn_window_gqa.py`` holds
the two together).  For a layer's Q ``(heads, n, d)``, K and V ``(kv_heads,
n, d)``, head h reading key/value head ``h // (heads // kv_heads)``, float32
throughout at ``jax.default_matmul_precision("highest")``:

    S = Q K^T / sqrt(d)
    visible(i, j) = j <= i                  (full layer)
                    i - window < j <= i     (window layer)
    P = softmax over visible j;   O = P V

A dense mask, no key blocks, no online softmax.  So that it fits the chip,
the rows are computed ``ROWS`` at a time: a row's softmax is over all its
keys at once, ``(heads, ROWS, n)`` scores exist whole.  Departures from the
model's equations: P stays float32 for the second product (the model rounds
it to bfloat16) and O is kept float32 (the model stores bfloat16); the
comparison's limits carry both roundings.

Data from the seed, drawn on the device: each layer's Q, K and V standard
normal, rounded to the configuration's dtype, which is what both sides then
read (as the projected, rotated tensors would arrive: the projections, norms
and rotary embedding before them are not part of the configuration).

What is compared (:func:`check`), for every layer's O, the worst layer
reported:

* ``attn_o_rms_gap``: root of the summed squares of ``o - o_ref`` over that
  of ``o_ref``.  The program rounds P to bfloat16 before the second product
  and O to bfloat16 at the end: some 0.2%.  K and V carried as float8 (the
  control) give several per cent.  Sees a lower precision anywhere.
* ``attn_o_widest_row_gap``: the largest, over (head, position), of
  ``|o - o_ref| / max(|o_ref|, median |o_ref|)`` (Euclidean norms over the
  head's width).  A row folded against a wrong block or a wrong key/value
  head, or a window off by one key, reads near 1 (tests/test_reference.py:
  one key beyond the window let in reads 0.1 and more at the toy's 16 keys).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax

#: the output buffers of one iteration that :func:`check` compares are
#: ``OUTPUT.<layer>``, one a layer (:func:`outputs`)
OUTPUT = "O"
ROWS = 256  # query rows whose scores exist at once
#: limits of the comparison (PERF.md, section 2: each between the largest
#: sound reading and the control's smallest, at the cell's own size)
RMS_LIMIT = 0.01
ROW_LIMIT = 0.03
FLOAT8_E4M3 = (4, 3)  # exponent and mantissa bits of the control's K and V


def sizes(config: dict) -> dict:
    """The layers' sizes: the published widths (top-level keys, as the
    model's ``config.json`` names them) and the run's (``shapes``); a
    rehearsal's ``toy`` group stands in for the widths."""
    s = config["shapes"]
    toy = s.get("toy")
    src = toy if toy else config
    layers = int(config["layers"])
    kinds = tuple(config["layer_types"][:layers])
    window = int(src["sliding_window"])
    return {"heads": int(src["num_attention_heads"]),
            "kv_heads": int(src["num_key_value_heads"]),
            "d": int(src["head_dim"]),
            "windows": tuple(window if k == "sliding_attention" else None
                             for k in kinds),
            "n": int(s["prompt_tokens"]), "dtype": s["dtype"]}


def tags(config: dict) -> list:
    return [f"L{i}" for i in range(int(config["layers"]))]


def outputs(config: dict) -> list:
    return [f"{OUTPUT}.{t}" for t in tags(config)]


def _layer(q, k, v, window, via=None):
    """O float32 ``(heads, n, d)``.  ``window``: ``None`` for a full layer,
    or the keys a query sees, which may be a traced scalar (a window of
    ``n`` keys masks what the causal mask does: that is how
    :func:`_programs` runs every layer through one traced body).  ``via``
    (the control; exponent and mantissa bits): K and V are read rounded to
    that format by ``lax.reduce_precision`` (a cast there and back the TPU
    compiler takes out, PERF.md section 2)."""
    f32 = jnp.float32
    h, n, d = q.shape
    g = k.shape[0]
    rows = min(ROWS, n)
    pad = (-n) % rows
    with jax.default_matmul_precision("highest"):
        q, k, v = (t.astype(f32) for t in (q, k, v))
        if via is not None:
            k, v = (lax.reduce_precision(t, *via) for t in (k, v))
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0))).reshape(
            g, h // g, (n + pad) // rows, rows, d)
        j = jnp.arange(n)[None, :]

        def some_rows(c):
            i = c * rows + jnp.arange(rows)[:, None]
            visible = j <= i
            if window is not None:
                visible = visible & (j > i - window)
            s = jnp.einsum("ghrd,gjd->ghrj", q[:, :, c], k) / jnp.sqrt(f32(d))
            p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
            return jnp.einsum("ghrj,gjd->ghrd", p, v)

        o = lax.map(some_rows, jnp.arange((n + pad) // rows))
    # (chunks, g, h/g, rows, d) -> (heads, n, d)
    return jnp.moveaxis(o, 0, 2).reshape(h, n + pad, d)[:, :n]


def _median(x):
    """``jnp.median`` of non-negative float32 values (an even count: the mean
    of the two middle ones), by bisection on their bit patterns, which order
    as the values do.  ``jnp.median`` sorts, and a sort of the cell's 524 288
    row norms takes the TPU's compiler most of a minute (PERF.md, PR 33)."""
    bits = lax.bitcast_convert_type(x.ravel(), jnp.int32)

    def kth(k):
        """The smallest pattern with more than ``k`` values at or below it."""
        def halve(_, span):
            lo, hi = span
            mid = lo + (hi - lo) // 2
            enough = jnp.sum(bits <= mid) > k
            return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

        # 0 to +inf: 2**31 patterns, halved 31 times
        _, hi = lax.fori_loop(0, 31, halve,
                              (jnp.int32(0), jnp.int32(0x7F800000)))
        return lax.bitcast_convert_type(hi, jnp.float32)

    return 0.5 * (kth((bits.size - 1) // 2) + kth(bits.size // 2))


def _draw(z, seed):
    """``(q, k, v)`` of every layer stacked on a leading axis: one draw, by
    the device's own generator (``rbg``: the default ``threefry`` costs a
    checkout's first run 24 s of compiling here and as much again inside
    the reference's program, PERF.md section 6, PR 33).  The same seed gives
    the same tensors wherever the draw is traced: :func:`make_data` and the
    reference's program draw them alike."""
    layers, hq, hk = len(z["windows"]), z["heads"], z["kv_heads"]
    x = jax.random.normal(jax.random.key(seed, impl="rbg"),
                          (layers, hq + 2 * hk, z["n"], z["d"]), jnp.float32)
    return jnp.split(x.astype(jnp.dtype(z["dtype"])), [hq, hq + hk], axis=1)


@lru_cache(maxsize=None)
def _programs(frozen: tuple):
    """``(data, reference, gaps)``: the draw; every layer's reference O,
    stacked (``via`` static); the two gaps of one layer's O against its
    reference.  The layers go through one traced body, a full layer as a
    window of ``n`` keys, so that the reference compiles once and not once a
    layer."""
    z = dict(frozen)

    @jax.jit
    def data(seed):
        return {f"{name}.L{i}": t[i] for name, t in zip("QKV", _draw(z, seed))
                for i in range(len(z["windows"]))}

    @partial(jax.jit, static_argnums=1)
    def reference(seed, via):
        q, k, v = _draw(z, seed)
        windows = jnp.asarray(
            [z["n"] if w is None else w for w in z["windows"]], jnp.int32)
        return lax.map(lambda a: _layer(*a, via), (q, k, v, windows))

    @jax.jit
    def gaps(out, ref):
        err = out.astype(jnp.float32) - ref
        err2, ref2 = jnp.sum(err * err, axis=2), jnp.sum(ref * ref, axis=2)
        floor = _median(jnp.sqrt(ref2))
        return jnp.stack([
            jnp.sqrt(jnp.sum(err2) / jnp.sum(ref2)),
            jnp.max(jnp.sqrt(err2) / jnp.maximum(jnp.sqrt(ref2), floor))])

    return data, reference, gaps


def _of(config: dict):
    return _programs(tuple(sorted(sizes(config).items())))


def _seed(seed: int):
    return jnp.uint32(seed & 0xFFFFFFFF)


#: ``{sizes: (reference, gaps)}`` compiled by :func:`precompile`.  The
#: epilogue runs with the persistent cache off, and a jitted function's own
#: cache does not take what ``lower().compile()`` made: so the executables
#: are kept and called
_COMPILED = {}


@lru_cache(maxsize=1)
def _reference_of(frozen: tuple, seed: int):
    """Every layer's float32 reference O for one seed, computed once a run
    (each schedule compared reads the same)."""
    if frozen in _COMPILED:
        return _COMPILED[frozen][0](_seed(seed))
    return _programs(frozen)[1](_seed(seed), None)


def make_data(config: dict, seed: int) -> dict:
    """``{Q.L<i>, K.L<i>, V.L<i>}`` for every layer, drawn on the device."""
    return _of(config)[0](_seed(seed))


def precompile(config: dict, like) -> None:
    """Compile the reference and the comparison for an output shaped as
    ``like`` (set-up: the persistent cache keeps them, and no run of them
    is counted as set-up)."""
    z = sizes(config)
    frozen = tuple(sorted(z.items()))
    _, reference, gaps = _programs(frozen)
    ref = jax.ShapeDtypeStruct((z["heads"], z["n"], z["d"]), jnp.float32)
    _COMPILED[frozen] = (
        reference.lower(_seed(0), None).compile(),
        gaps.lower(jax.ShapeDtypeStruct(like.shape, like.dtype),
                   ref).compile())


def check(config: dict, seed: int, outputs_: dict) -> list:
    """The two numbers of the module's docstring, the worst layer's."""
    frozen = tuple(sorted(sizes(config).items()))
    refs = _reference_of(frozen, seed)
    gaps = _COMPILED[frozen][1] if frozen in _COMPILED else _programs(
        frozen)[2]
    both = jax.device_get([gaps(outputs_[name], ref)
                           for name, ref in zip(outputs(config), refs)])
    return [{"name": "attn_o_rms_gap",
             "value": float(max(g[0] for g in both)), "limit": RMS_LIMIT},
            {"name": "attn_o_widest_row_gap",
             "value": float(max(g[1] for g in both)), "limit": ROW_LIMIT}]


def _as_outputs(config: dict, seed: int, via) -> dict:
    dt = jnp.dtype(sizes(config)["dtype"])
    return {name: o.astype(dt) for name, o in zip(
        outputs(config), _of(config)[1](_seed(seed), via))}


def control(config: dict, seed: int) -> dict:
    """The reference in the program's place, one precision down: K and V
    read as float8 where the configuration states bfloat16.  :func:`check`
    has to refuse it."""
    return _as_outputs(config, seed, FLOAT8_E4M3)


def sound(config: dict, seed: int) -> dict:
    """The reference's own float32 layers, rounded once to the
    configuration's dtype (tests: :func:`check` passes it)."""
    return _as_outputs(config, seed, None)
