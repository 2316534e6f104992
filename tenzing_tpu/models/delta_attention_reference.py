"""Plain reference of Kimi Delta Attention (KDA, Kimi-Linear's linear
attention layer), to hold ``models/delta_attention.py`` against: **a whole
sequence, token by token, from a zero state**.

Per head (``d`` channels; ``t`` the token), with the layer's projections
taken as done (the inputs are handed as they would arrive):

    short convolution   [q_t ; k_t ; v_t] = silu(sum_{i=0..taps-1} Wc[i] .
                        x_{t-taps+1+i}),   x_j = 0 for j < 0  (left padding)
    norms               q_t <- q_t / sqrt(|q_t|^2 + 1e-6) . d^(-1/2)
                        k_t <- k_t / sqrt(|k_t|^2 + 1e-6)
    decay               alpha_t = exp(-exp(A_log) softplus(f_t + dt_bias))
                        (a number a key channel, in (0, 1))
    beta                beta_t = sigmoid(b_t)
    state               S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1}
                              + beta_t k_t v_t^T,        S_{-1} = 0
    read-out            o_t = S_t^T q_t
    output              o_t <- RMSNorm_w(o_t) . sigmoid(go_t)

(the Kimi Linear report's equations for the recurrent form: the gated
delta rule with Diag(alpha) in the place of a scalar gate).  ``jax.numpy``
in float32 under ``jax.default_matmul_precision("highest")``, one sequence
at a time: no kernel, no cache, no batching, no chunking.  Departures from
the model, all of them: ``q_proj`` / ``k_proj`` / ``v_proj``, the low-rank
gate projections ``f_a f_b`` and ``g_a g_b``, ``b_proj``, the pre-norm and
``o_proj`` lie before the inputs and after ``o``.

The hybrid period's reference is this for the KDA layers and
``latent_attention_reference.py``'s ``published`` for the latent-attention
layer (``mla_use_nope``: the rotary columns arrive unrotated, which changes
nothing after the inputs), each layer on its own inputs.
"""

from __future__ import annotations

L2_EPS = 1e-6


def _f32(*xs):
    import jax.numpy as jnp

    return [jnp.asarray(x, jnp.float32) for x in xs]


def one_token(state, window, x, f, b, go, wc, dt_bias, a_log, w_norm,
              eps: float = 1e-5):
    """One token of one sequence: ``(o (H, d), S_t (H, d, d), the window
    moved on)`` from ``state`` ``S_{t-1}`` ``(H, d, d)``, ``window`` (the
    ``taps - 1`` rows before the token, ``(taps - 1, 3, H, d)``), the
    token's ``x`` ``(3, H, d)``, ``f`` / ``go`` ``(H, d)``, ``b`` ``(H,
    1)``."""
    import jax
    import jax.numpy as jnp

    (state, window, x, f, b, go, wc, dt_bias, a_log,
     w_norm) = _f32(state, window, x, f, b, go, wc, dt_bias, a_log, w_norm)
    with jax.default_matmul_precision("highest"):
        rows = jnp.concatenate([window, x[None]], axis=0)
        q, k, v = jax.nn.silu(jnp.einsum("tchd,tchd->chd", wc, rows))
        d = q.shape[-1]
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) \
            * d ** -0.5
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
        alpha = jnp.exp(-jnp.exp(a_log) * jax.nn.softplus(f + dt_bias))
        beta = jax.nn.sigmoid(b)
        decayed = alpha[:, :, None] * state
        erase = jnp.einsum("hk,hkv->hv", k, decayed)
        new = decayed - beta[:, :, None] * jnp.einsum("hk,hv->hkv", k, erase) \
            + beta[:, :, None] * jnp.einsum("hk,hv->hkv", k, v)
        o = jnp.einsum("hkv,hk->hv", new, q)
        o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * w_norm
        return o * jax.nn.sigmoid(go), new, rows[1:]


def forward(x, f, b, go, wc, dt_bias, a_log, w_norm, eps: float = 1e-5):
    """A whole sequence from a zero state: ``x`` ``(T, 3, H, d)``, ``f`` /
    ``go`` ``(T, H, d)``, ``b`` ``(T, H, 1)``.  Returns ``(o (T, H, d), the
    states after every token (T, H, d, d), the windows after every token
    (T, taps - 1, 3, H, d))``."""
    import jax.numpy as jnp

    x, = _f32(x)
    taps, _, heads, d = wc.shape
    state = jnp.zeros((heads, d, d), jnp.float32)
    window = jnp.zeros((taps - 1,) + x.shape[1:], jnp.float32)
    outs, states, windows = [], [], []
    for t in range(x.shape[0]):
        o, state, window = one_token(state, window, x[t], f[t], b[t], go[t],
                                     wc, dt_bias, a_log, w_norm, eps)
        outs.append(o)
        states.append(state)
        windows.append(window)
    return jnp.stack(outs), jnp.stack(states), jnp.stack(windows)
