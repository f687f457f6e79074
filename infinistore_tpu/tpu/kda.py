"""Kimi Delta Attention (KDA) on a recurrent state: the chunked program and
the one-token update.

Per head the layer keeps a float32 state ``S [K, V]`` and walks it a token
at a time (arXiv:2510.26692):

    S'  = diag(exp(g_t)) S_{t-1}                 g_t <= 0, per key channel
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

``kda_chunk`` is the same mathematics for many tokens at once, with an
initial state in and the state after the last token out. Within a chunk of
``CHUNK`` tokens, with ``G_t`` the running sum of ``g`` from the chunk's
start:

    A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])      s <  t
    B[t, s] = sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])      s <= t
    (I + diag(beta) A) U = diag(beta) (V - (K * exp(G)) S_0)
    O   = (Q * exp(G)) S_0 + B U
    S_C = diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

Every exponent is formed as a difference first and is <= 0, so nothing
overflows however strong the decay. ``A``, ``B`` and the inverse of the unit
lower-triangular ``I + diag(beta) A`` do not depend on the state and are
made for all chunks at once; the walk over chunks is four small products a
step. All of it is float32 at the highest matmul precision: the sums are
short, and the state is what a prefix leaves behind.

Plain XLA: the one-token update moves the state once each way (4 MiB a row
and layer at the published widths) and XLA does that in place on a donated
cache; the chunked program's products are small and many, and XLA batches
them over heads and chunks.
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

CHUNK = 32
_HI = jax.lax.Precision.HIGHEST


def kda_step(q, k, v, g, beta, state):
    """One token a row. q, k, g: [T, H, K]; v: [T, H, V]; beta: [T, H];
    state: [T, H, K, V] float32, each row's state before its token. Returns
    (o [T, H, V] float32, the rows' states after it)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    decayed = jnp.exp(g)[..., None] * state
    seen = jnp.sum(decayed * k[..., None], axis=2)  # S'^T k: [T, H, V]
    u = beta[..., None] * (v - seen)
    state = decayed + k[..., None] * u[:, :, None, :]
    return jnp.sum(state * q[..., None], axis=2), state


@functools.partial(jax.jit, static_argnames=("chunk",))
def kda_chunk(q, k, v, g, beta, state, *, chunk: int = CHUNK) -> Tuple[jax.Array, jax.Array]:
    """Many tokens of one request. q, k, g: [S, H, K]; v: [S, H, V]; beta:
    [S, H]; state: [H, K, V] float32, the state before the first token.
    Returns (o [S, H, V] float32, the state after the last token). Any S: the
    tail is padded with tokens that neither decay nor write (g 0, beta 0)."""
    f32 = jnp.float32
    s, h, dk = q.shape
    n = -(-s // chunk)
    pad = n * chunk - s

    def cut(a):  # [S, H, ...] -> [n, H, C, ...]
        a = a.astype(f32)
        if pad:
            a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        a = a.reshape(n, chunk, *a.shape[1:])
        return jnp.swapaxes(a, 1, 2)

    q, k, v, g, beta = cut(q), cut(k), cut(v), cut(g), cut(beta)
    G = jnp.cumsum(g, axis=2)  # [n, H, C, K]
    t_ge_s = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp(G_t - G_s) for s <= t, 0 above the diagonal: [n, H, C, C, K].
    decay = jnp.where(
        t_ge_s[:, :, None],
        jnp.exp(jnp.minimum(G[:, :, :, None, :] - G[:, :, None, :, :], 0.0)),
        0.0,
    )
    ks = k[:, :, None, :, :]
    a = jnp.sum(k[:, :, :, None, :] * ks * decay, axis=-1)  # [n, H, C, C]
    b = jnp.sum(q[:, :, :, None, :] * ks * decay, axis=-1)
    eye = jnp.eye(chunk, dtype=f32)
    m = eye + beta[..., None] * jnp.where(t_ge_s & ~eye.astype(bool), a, 0.0)  # s < t
    eye = jnp.broadcast_to(eye, m.shape)
    t = jax.scipy.linalg.solve_triangular(m, eye, lower=True, unit_diagonal=True)
    gamma = jnp.exp(G)
    mm = functools.partial(jnp.einsum, precision=_HI)
    w = mm("nhts,nhsk->nhtk", t, beta[..., None] * k * gamma)
    ub = mm("nhts,nhsv->nhtv", t, beta[..., None] * v)
    q_in = q * gamma
    g_end = G[:, :, -1:, :]  # [n, H, 1, K]
    k_out = k * jnp.exp(g_end - G)
    gamma_end = jnp.exp(g_end[:, :, 0, :])  # [n, H, K]

    def walk(state, c):
        w, ub, q_in, b, k_out, gamma_end = c
        u = ub - mm("htk,hkv->htv", w, state)
        o = mm("htk,hkv->htv", q_in, state) + mm("hts,hsv->htv", b, u)
        state = gamma_end[..., None] * state + mm("htk,htv->hkv", k_out, u)
        return state, o

    state, o = jax.lax.scan(walk, state.astype(f32), (w, ub, q_in, b, k_out, gamma_end))
    o = jnp.swapaxes(o, 1, 2).reshape(n * chunk, h, -1)
    return o[:s], state


def short_conv(x, tail, weight):
    """The causal depth-wise convolution over the last ``taps`` positions.
    x: [S, C] the rows before it; tail: [taps - 1, C] the rows that came
    before x (zeros at a prompt's start); weight: [taps, C]. Returns
    (y [S, C] float32, the new tail: the last ``taps - 1`` rows of the two).
    A ``tail`` of ``[T, taps - 1, C]`` is a wave's: one position a row, each a
    request with a tail of its own (:func:`short_conv_rows`)."""
    if tail.ndim == 3:
        return short_conv_rows(x, tail, weight)
    taps = weight.shape[0]
    s = x.shape[0]
    rows = jnp.concatenate([tail.astype(x.dtype), x], axis=0)  # [taps - 1 + S, C]
    w = weight.astype(jnp.float32)
    y = sum(rows[i : i + s].astype(jnp.float32) * w[i] for i in range(taps))
    return y, rows[s:]


def short_conv_rows(x, tails, weight):
    """:func:`short_conv` of a wave: one position a row, each a request with a
    tail of its own. x: [T, C]; tails: [T, taps - 1, C]; weight: [taps, C].
    Returns (y [T, C] float32, the new tails [T, taps - 1, C])."""
    rows = jnp.concatenate([tails.astype(x.dtype), x[:, None]], axis=1)
    y = jnp.sum(rows.astype(jnp.float32) * weight.astype(jnp.float32)[None], axis=1)
    return y, rows[:, 1:]
