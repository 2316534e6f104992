"""Plain reference of one deepseek_v3 expert layer (Moonlight-16B-A3B),
expert-parallel: tokens by rank, routed experts by rank, the router and the
shared expert on every rank.

Imports nothing of the program (a copy of ``tenzing_tpu/models/
moe_reference.py``'s equations; ``tests/test_moe_topk.py`` holds the two
together).  For a rank's tokens ``x`` (T, d), float32 throughout at
``jax.default_matmul_precision("highest")``, no slots, no chunks, no
capacity: every expert over every token, weighted by a mask.

* scores ``s = sigmoid(x W_g)``; selected: the ``k`` largest of ``s + b``
  (``e_score_correction_bias``, zeros here);
* weights ``w_i = scale * s_i / (sum of the selected s + 1e-20)``;
* expert ``E_i(x) = (silu(x W1_i) * (x W3_i)) W2_i``; the shared expert
  ``S(x)`` the same form at its own width;
* ``y = S(x) + sum over the selected of w_i E_i(x)``.

Data from the seed, each rank's part drawn on its own device (the
generator's values do not depend on the sharding): ``X`` standard normal,
every weight matrix normal over the square root of its fan-in, the router's
columns of unit length, all rounded to the configuration's dtype, which is
what both sides then read.

What is compared (:func:`check`), per rank under ``shard_map``, one fetch:

* ``moe_y_rms_gap``: root of the summed squares of ``y - y_ref`` over that
  of ``y_ref``, all ranks.  The program rounds to bfloat16 three times on
  the routed path (hidden activation, expert output, ``y``) and twice on
  the shared one: some 0.3%.  Slots carried as float8 (the control) give
  some 3%.  Sees a lower precision anywhere.
* ``moe_y_widest_token_gap``: the largest, over tokens, of
  ``|y_t - y_ref_t| / max(|y_ref_t|, median |y_ref|)`` (Euclidean norms
  over the model width).  Sees one token that went to a wrong expert, took
  a wrong weight or came back to a wrong place: such a token reads near 1.
* ``moe_tokens_left_out``: tokens whose ``k``-th and ``k+1``-th scores lie
  closer than ``TIE`` = 1e-5.  Which of the two such a token goes to is
  rounding's to decide (two float32 sums of 2048 products in another
  order differ by some 1e-7 after the sigmoid), and the program selects
  at set-up with a product of its own: these tokens are left out of both
  numbers above, some 20 of 32 768, and the limit on their count (0.5% of
  the tokens) keeps the comparison from passing by leaving out.
* ``chips_without_a_shard``: ranks whose output lies on no device of its own.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: the output buffer of one iteration that :func:`check` compares
OUTPUT = "Y"
AXIS = "ep"
TIE = 1e-5
#: limits of the comparison (PERF.md, section 2: each between the largest
#: sound reading and the control's smallest, at the cell's own size)
RMS_LIMIT = 0.01
TOKEN_LIMIT = 0.02
LEFT_OUT_SHARE = 0.005
FLOAT8_E4M3 = (4, 3)  # exponent and mantissa bits of the control's slots

BY_RANK2, BY_RANK3 = P(AXIS, None), P(AXIS, None, None)
SPECS = {"X": BY_RANK2, "Wg": P(), "gate_bias": P(), "W1": BY_RANK3,
         "W3": BY_RANK3, "W2": BY_RANK3, "Ws1": P(), "Ws3": P(), "Ws2": P()}


def sizes(config: dict) -> dict:
    """The layer's sizes: the published widths (top-level keys, as the
    model's ``config.json`` names them) and the deployment (``shapes``); a
    rehearsal's ``toy`` group stands in for the widths."""
    s = config["shapes"]
    toy = s.get("toy")
    if toy:
        d, f, n_e, k, fs = (toy[n] for n in ("d_model", "d_ff", "experts",
                                             "top_k", "shared_ff"))
    else:
        d, f = config["hidden_size"], config["moe_intermediate_size"]
        n_e, k = config["n_routed_experts"], config["num_experts_per_tok"]
        fs = config["n_shared_experts"] * f
    ranks = int(s["ranks"])
    if ranks * int(s["experts_per_shard"]) != n_e:
        raise ValueError(f"{n_e} experts over {ranks} ranks of "
                         f"{s['experts_per_shard']}")
    return {"d": int(d), "f": int(f), "experts": int(n_e), "top_k": int(k),
            "fs": int(fs), "scale": float(config["routed_scaling_factor"]),
            "ranks": ranks, "tokens": int(s["tokens_per_chip"]),
            "dtype": s["dtype"]}


def mesh_of(config: dict) -> Mesh:
    """The reference's own ranks: the first ``ranks`` devices JAX has."""
    return Mesh(np.array(jax.devices()[:int(config["shapes"]["ranks"])]),
                (AXIS,))


def _mlp(x, w1, w3, w2):
    f32 = jnp.float32
    h = jax.nn.silu(jnp.dot(x, w1.astype(f32))) * jnp.dot(x, w3.astype(f32))
    return jnp.dot(h, w2.astype(f32))


def _layer(z, via, x, wg, bias, w1, w3, w2, ws1, ws3, ws2):
    """One rank's ``(y_ref (T, d) float32, clear (T,) bool)``: the layer
    over its tokens ``x`` with every rank's experts gathered here.  ``via``
    (the control; exponent and mantissa bits): tokens reach the routed
    experts, and their outputs come back, rounded to that format by
    ``lax.reduce_precision`` (a cast there and back the TPU compiler takes
    out: it may keep excess precision, and the control then read 0.0017,
    my chip run, PR 28)."""
    f32 = jnp.float32
    k, n_e = z["top_k"], z["experts"]
    w1, w3, w2 = (lax.all_gather(w, AXIS, axis=0, tiled=True)
                  for w in (w1, w3, w2))
    with jax.default_matmul_precision("highest"):
        x = x.astype(f32)
        s = jax.nn.sigmoid(jnp.dot(x, wg.astype(f32)))
        top, sel = lax.top_k(s + bias.astype(f32)[None, :], min(k + 1, n_e))
        clear = (top[:, k - 1] - top[:, -1] >= TIE) | (k == n_e)
        sel = sel[:, :k]
        picked = jnp.take_along_axis(s, sel, axis=1)
        w = z["scale"] * picked / (picked.sum(axis=1, keepdims=True) + 1e-20)
        # (E, T): each expert's weight for every token, 0 where not selected
        mask = jnp.sum(jnp.where(sel[None, :, :] == jnp.arange(
            n_e, dtype=sel.dtype)[:, None, None], w[None], 0.0), axis=2)
        sent = x if via is None else lax.reduce_precision(x, *via)

        def add_expert(y, e):
            we, w1e, w3e, w2e = e
            back = _mlp(sent, w1e, w3e, w2e)
            if via is not None:
                back = lax.reduce_precision(back, *via)
            return y + we[:, None] * back, None

        y, _ = lax.scan(add_expert, _mlp(x, ws1, ws3, ws2),
                        (mask, w1, w3, w2))
    return y, clear


def _draw(z, seed):
    """The data, global shapes (jit shards the drawing by ``SPECS``).  The
    router's columns are scaled to unit length before they are rounded: a
    trained router is balanced by its loss, and columns of unequal length
    make experts of unequal popularity (drawn unscaled, an expert's share
    of the tokens spread by 15% either way and the fullest slot table held
    259 of its 288 slots over forty seeds, *CPU*; unit columns: 6%, 246).
    Expert weights are drawn as matrices and reshaped: the three-dimensional
    draw takes the TPU compiler three times as long."""
    d, f, n_e, fs = z["d"], z["f"], z["experts"], z["fs"]
    dt = jnp.dtype(z["dtype"])
    keys = jax.random.split(jax.random.key(seed), 8)

    def normal(i, shape, fan_in=1):
        flat = (int(np.prod(shape[:-1])), shape[-1])
        return (jax.random.normal(keys[i], flat, jnp.float32)
                / np.sqrt(fan_in)).astype(dt).reshape(shape)

    wg = jax.random.normal(keys[1], (d, n_e), jnp.float32)
    return {"X": normal(0, (z["ranks"] * z["tokens"], d)),
            "Wg": (wg / jnp.linalg.norm(wg, axis=0, keepdims=True)).astype(dt),
            "gate_bias": jnp.zeros((n_e,), jnp.float32),
            "W1": normal(2, (n_e, d, f), d), "W3": normal(3, (n_e, d, f), d),
            "W2": normal(4, (n_e, f, d), f),
            "Ws1": normal(5, (d, fs), d), "Ws3": normal(6, (d, fs), d),
            "Ws2": normal(7, (fs, d), fs)}


@lru_cache(maxsize=None)
def _programs(mesh: Mesh, frozen: tuple):
    """``(data, gaps, layer)`` for one mesh and size, each one program over
    the whole mesh."""
    z = dict(frozen)
    names = sorted(SPECS)
    placed = {n: NamedSharding(mesh, SPECS[n]) for n in names}
    everywhere = NamedSharding(mesh, P())
    by_rank = NamedSharding(mesh, BY_RANK2)
    args = ("X", "Wg", "gate_bias", "W1", "W3", "W2", "Ws1", "Ws3", "Ws2")
    in_specs = tuple(SPECS[n] for n in args)

    data = jax.jit(partial(_draw, z), out_shardings=placed)

    def gaps_local(out, *a):
        ref, clear = _layer(z, None, *a)
        err = jnp.where(clear[:, None], out.astype(jnp.float32) - ref, 0.0)
        ref = jnp.where(clear[:, None], ref, 0.0)
        err2, ref2 = jnp.sum(err * err, axis=1), jnp.sum(ref * ref, axis=1)
        floor = jnp.median(jnp.sqrt(ref2))
        widest = jnp.max(jnp.sqrt(err2) / jnp.maximum(jnp.sqrt(ref2), floor))
        return jnp.stack([
            jnp.sqrt(lax.psum(jnp.sum(err2), AXIS)
                     / lax.psum(jnp.sum(ref2), AXIS)),
            lax.pmax(widest, AXIS),
            lax.psum(jnp.sum(~clear).astype(jnp.float32), AXIS)])

    @partial(jax.jit, in_shardings=(by_rank, everywhere),
             out_shardings=everywhere)
    def gaps(out, seed):
        d = _draw(z, seed)
        return jax.shard_map(gaps_local, mesh=mesh,
                             in_specs=(BY_RANK2,) + in_specs, out_specs=P())(
                                 out, *(d[n] for n in args))

    @partial(jax.jit, static_argnums=1, out_shardings=by_rank)
    def layer(seed, via):
        d = _draw(z, seed)
        return jax.shard_map(
            lambda *a: _layer(z, via, *a)[0].astype(jnp.dtype(z["dtype"])),
            mesh=mesh, in_specs=in_specs, out_specs=BY_RANK2)(
                *(d[n] for n in args))

    return data, gaps, layer


def _of(config: dict):
    return _programs(mesh_of(config), tuple(sorted(sizes(config).items())))


def _seed(seed: int):
    return jnp.uint32(seed & 0xFFFFFFFF)


def make_data(config: dict, seed: int) -> dict:
    """Tokens, router, routed and shared experts, each rank's part drawn on
    its own device."""
    return _of(config)[0](_seed(seed))


def precompile(config: dict, like) -> None:
    """Compile the comparison for an output shaped and placed as ``like``
    (set-up: the persistent cache keeps it, and no run of it is counted as
    set-up)."""
    like = jax.ShapeDtypeStruct(like.shape, like.dtype, sharding=like.sharding)
    _of(config)[1].lower(like, _seed(0)).compile()


def check(config: dict, seed: int, outputs: dict) -> list:
    """The four numbers of the module's docstring, one fetch."""
    out = outputs[OUTPUT]
    z = sizes(config)
    owners = {s.device for s in out.addressable_shards}
    want = NamedSharding(mesh_of(config), BY_RANK2)
    if not out.sharding.is_equivalent_to(want, out.ndim):
        out = jax.device_put(out, want)  # compared where it should have lain
    rms, widest, left_out = (float(v) for v in jax.device_get(
        _of(config)[1](out, _seed(seed))))
    n_tokens = z["ranks"] * z["tokens"]
    return [{"name": "moe_y_rms_gap", "value": rms, "limit": RMS_LIMIT},
            {"name": "moe_y_widest_token_gap", "value": widest,
             "limit": TOKEN_LIMIT},
            {"name": "moe_tokens_left_out", "value": int(left_out),
             "limit": int(np.ceil(LEFT_OUT_SHARE * n_tokens))},
            {"name": "chips_without_a_shard",
             "value": z["ranks"] - len(owners), "limit": 0}]


def control(config: dict, seed: int) -> dict:
    """The reference in the program's place, one precision down: the slots
    travel as float8 (tokens to the routed experts, their outputs back),
    where the configuration states bfloat16.  :func:`check` has to refuse
    it."""
    return {OUTPUT: _of(config)[2](_seed(seed), FLOAT8_E4M3)}


def sound(config: dict, seed: int) -> dict:
    """The reference's own float32 layer, rounded once to the
    configuration's dtype (tests: :func:`check` passes it)."""
    return {OUTPUT: _of(config)[2](_seed(seed), None)}
