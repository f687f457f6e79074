"""The Mamba-2 mixer's recurrence (state-space duality, arXiv:2405.21060) on a
carried state: the chunked program and the one-token update.

Per head the layer keeps a float32 state ``S [P, N]`` (``P`` the head's
channels, ``N`` the state size) and walks it a token at a time, with a scalar
decay a head and ``B``, ``C`` shared by a group of heads:

    a_t = exp(A dt_t)                    A = -exp(A_log) < 0, dt_t > 0
    S_t = a_t S_{t-1} + dt_t x_t B_t^T
    o_t = S_t C_t + D x_t

``ssd_chunk`` is the same mathematics for many tokens at once, with a state
in and the state after the last token out. Within a chunk of ``chunk``
tokens, with ``L_t`` the running sum of ``A dt`` from the chunk's start:

    o_t   = exp(L_t) (S_0 C_t) + sum_{s <= t} exp(L_t - L_s) (C_t . B_s) dt_s x_s + D x_t
    S_end = exp(L_end) S_0 + sum_s exp(L_end - L_s) dt_s x_s B_s^T

Every exponent is formed as a difference first and is <= 0. What does not
depend on the state (``C . B``, the decays, each chunk's own contribution to
the state) is made for all chunks at once; the walk over chunks is one
product and one scaled sum a step. All of it is float32 at the highest
matmul precision: the state is what a prefix leaves behind, and by FLOPs the
walk is 0.6% of a layer at the published widths.

Plain XLA, as ``kda.py`` and for its reasons: the one-token update moves the
state once each way (8 MiB a row and layer at the published widths, in place
on a donated cache), and the chunked program's products are small and many.
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

CHUNK = 128  # ``mamba_chunk_size`` as published
_HI = jax.lax.Precision.HIGHEST


def ssd_step(x, dt, a_log, b, c, d, state):
    """One token a row. x: [T, H, P]; dt: [T, H] (after the softplus); a_log,
    d: [H]; b, c: [T, G, N], head h reads group ``h // (H / G)``; state: [T,
    H, P, N] float32, each row's state before its token. Returns (o [T, H, P]
    float32, the rows' states after it)."""
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    per_group = x.shape[1] // b.shape[1]
    b, c = (jnp.repeat(v, per_group, axis=1) for v in (b, c))  # [T, H, N]
    decay = jnp.exp(-jnp.exp(a_log.astype(f32)) * dt)  # [T, H]
    state = decay[..., None, None] * state + (dt[..., None] * x)[..., None] * b[:, :, None, :]
    o = jnp.sum(state * c[:, :, None, :], axis=-1) + d.astype(f32)[None, :, None] * x
    return o, state


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_chunk(x, dt, a_log, b, c, d, state, *, chunk: int = CHUNK) -> Tuple[jax.Array, jax.Array]:
    """Many tokens of one request. x: [S, H, P]; dt: [S, H] (after the
    softplus); a_log, d: [H]; b, c: [S, G, N]; state: [H, P, N] float32, the
    state before the first token. Returns (o [S, H, P] float32, the state
    after the last token). Any S: the tail is padded with tokens that neither
    decay nor write (dt 0)."""
    f32 = jnp.float32
    s, h, p = x.shape
    g = b.shape[1]
    n = -(-s // chunk)
    pad = n * chunk - s

    def cut(v):  # [S, ...] -> [n, C, ...]
        v = v.astype(f32)
        if pad:
            v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        return v.reshape(n, chunk, *v.shape[1:])

    x, dt, b, c = cut(x), cut(dt), cut(b), cut(c)
    mm = functools.partial(jnp.einsum, precision=_HI)
    run = jnp.cumsum(-jnp.exp(a_log.astype(f32)) * dt, axis=1)  # L: [n, C, H]
    t_ge_s = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp(L_t - L_s) for s <= t, 0 above the diagonal: [n, H, C, C].
    lt = jnp.moveaxis(run, 2, 1)  # [n, H, C]
    decay = jnp.where(
        t_ge_s, jnp.exp(jnp.minimum(lt[:, :, :, None] - lt[:, :, None, :], 0.0)), 0.0
    )
    cb = mm("ntgk,nsgk->ngts", c, b)  # [n, G, C, C]
    mix = decay.reshape(n, g, h // g, chunk, chunk) * cb[:, :, None]
    xdt = x * dt[..., None]  # [n, C, H, P]
    inside = mm("nhts,nshp->nthp", mix.reshape(n, h, chunk, chunk), xdt)
    # Each chunk's own contribution to the state at its end: [n, H, P, N].
    to_end = jnp.exp(run[:, -1:, :] - run)  # [n, C, H]
    bh = jnp.repeat(b, h // g, axis=2)  # [n, C, H, N]
    ch = jnp.repeat(c, h // g, axis=2)
    wrote = mm("nshp,nshk->nhpk", xdt * to_end[..., None], bh)
    through = jnp.exp(run[:, -1, :])  # [n, H]
    carried = jnp.exp(run)  # [n, C, H]

    def walk(state, at):
        wrote, through, ch, carried = at
        seen = mm("thk,hpk->thp", ch, state) * carried[..., None]
        return through[:, None, None] * state + wrote, seen

    state, seen = jax.lax.scan(walk, state.astype(f32), (wrote, through, ch, carried))
    o = inside + seen + d.astype(f32)[None, None, :, None] * x
    return o.reshape(n * chunk, h, p)[:s], state
