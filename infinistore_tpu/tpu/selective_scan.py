"""The Mamba-1 selective scan: a recurrence whose decay is per (channel, state)
pair,

  h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] u_t[c] B_t[n]
  y_t[c]    = sum_n h_t[c, n] C_t[n] + D[c] u_t[c]

so no product on the matrix unit computes it (``ssd.py``'s chunked form needs
ONE decay a head). ``A = -exp(A_log)`` is ``[C, N]``, the state float32 and
kept N-MAJOR, ``[N, C]``: the channels lie on the lanes, a state row a state
index, which is how the cache holds it too.

``selective_scan_step``   one token a row (a decode wave), plain XLA.
``selective_scan_chunk``  many tokens of one request: the dispatcher, the
                          Pallas kernel on the chip and the XLA walk elsewhere.
``selective_scan_pallas`` the kernel. The channels are cut into tiles of 8 x
                          128 (one float32 register), the tokens into chunks;
                          the grid is (channel tiles, token chunks), the
                          tokens innermost. A tile's state, N registers, stays
                          on the chip across the whole piece (a VMEM scratch
                          between chunks, the loop's carry within one); ``u``,
                          ``dt`` stream in a chunk at a time, ``y`` streams
                          out, ``B_t[n]`` and ``C_t[n]`` are scalars read from
                          SMEM. Float32 inside. Every token costs a channel
                          tile N exponentials and about 7 N vector operations:
                          the vector unit binds, not HBM.
``selective_scan_xla``    the same walk as a ``lax.scan`` over the tokens.
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged

_SUBLANES, _LANES = 8, 128
_TILE = _SUBLANES * _LANES  # channels a grid step's state covers
_CHUNK = 256  # tokens a grid step streams


def selective_scan_step(u, dt, a_log, b, c, d, state):
    """One token a row. u, dt: [T, C] (dt after its softplus); a_log: [C, N];
    b, c: [T, N]; d: [C]; state: [T, N, C] float32, each row's state before
    its token. Returns (y [T, C] float32, the rows' states after it)."""
    f32 = jnp.float32
    u, dt, b, c = (v.astype(f32) for v in (u, dt, b, c))
    a = -jnp.exp(a_log.astype(f32)).T  # [N, C]
    state = jnp.exp(dt[:, None, :] * a[None]) * state + (dt * u)[:, None, :] * b[:, :, None]
    y = jnp.sum(state * c[:, :, None], axis=1) + d.astype(f32)[None] * u
    return y, state


@jax.jit
def selective_scan_xla(u, dt, a_log, b, c, d, state):
    """Many tokens of one request, a token at a time. u, dt: [S, C]; b, c:
    [S, N]; state: [N, C] float32 before the first token. Returns (y [S, C]
    float32, the state after the last)."""
    f32 = jnp.float32
    u, dt, b, c = (v.astype(f32) for v in (u, dt, b, c))
    a = -jnp.exp(a_log.astype(f32)).T

    def token(h, at):
        u_t, dt_t, b_t, c_t = at
        h = jnp.exp(dt_t[None] * a) * h + (dt_t * u_t)[None] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    state, y = jax.lax.scan(token, state.astype(f32), (u, dt, b, c))
    return y + d.astype(f32)[None] * u, state


def _scan_kernel(b_ref, c_ref, u_ref, dt_ref, a_ref, h0_ref, y_ref, h_out_ref, h_scr, *, n_state):
    chunk = pl.program_id(1)

    @pl.when(chunk == 0)
    def _load():
        h_scr[...] = h0_ref[...]

    a = tuple(a_ref[n] for n in range(n_state))

    def token(t, h):
        dt = dt_ref[t]  # [8, 128]
        dtu = dt * u_ref[t]
        y = jnp.zeros_like(dt)
        out = []
        for n in range(n_state):
            h_n = jnp.exp(dt * a[n]) * h[n] + dtu * b_ref[t * n_state + n]
            y = y + h_n * c_ref[t * n_state + n]
            out.append(h_n)
        y_ref[t] = y
        return tuple(out)

    h = jax.lax.fori_loop(0, u_ref.shape[0], token, tuple(h_scr[n] for n in range(n_state)))
    for n in range(n_state):
        h_scr[n] = h[n]

    @pl.when(chunk == pl.num_programs(1) - 1)
    def _store():
        for n in range(n_state):
            h_out_ref[n] = h[n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_pallas(u, dt, a_log, b, c, d, state, *, interpret: bool = False):
    """:func:`selective_scan_xla`'s contract through the kernel (module
    docstring). Any S and C: the tokens are padded with ones that neither
    decay nor write (dt 0), the channels with ones that stay zero."""
    f32 = jnp.float32
    s, ch = u.shape
    n_state = b.shape[1]
    chunk = min(_CHUNK, -(-s // _SUBLANES) * _SUBLANES)
    s_pad, c_pad = -(-s // chunk) * chunk, -(-ch // _TILE) * _TILE
    tiles = c_pad // _TILE

    def slabs(v, rows):  # [rows, C] -> [rows', tiles x 8, 128]: a tile's channels one register
        v = jnp.pad(v.astype(f32), ((0, rows - v.shape[0]), (0, c_pad - ch)))
        return v.reshape(rows, tiles * _SUBLANES, _LANES)

    a = slabs(-jnp.exp(a_log.astype(f32)).T, n_state)
    flat = lambda v: jnp.pad(v.astype(f32), ((0, s_pad - s), (0, 0))).reshape(-1)
    by_tile = lambda rows: pl.BlockSpec((rows, _SUBLANES, _LANES), lambda i, j: (0, i, 0))
    streamed = pl.BlockSpec((chunk, _SUBLANES, _LANES), lambda i, j: (j, i, 0))
    scalars = pl.BlockSpec((chunk * n_state,), lambda i, j: (j,), memory_space=pltpu.SMEM)
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, n_state=n_state),
        grid=(tiles, s_pad // chunk),
        in_specs=[scalars, scalars, streamed, streamed, by_tile(n_state), by_tile(n_state)],
        out_specs=[streamed, by_tile(n_state)],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, tiles * _SUBLANES, _LANES), f32),
            jax.ShapeDtypeStruct((n_state, tiles * _SUBLANES, _LANES), f32),
        ],
        scratch_shapes=[pltpu.VMEM((n_state, _SUBLANES, _LANES), f32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(flat(b), flat(c), slabs(u, s_pad), slabs(dt, s_pad), a, slabs(state, n_state))
    y = y.reshape(s_pad, c_pad)[:s, :ch] + d.astype(f32)[None] * u.astype(f32)
    return y, h.reshape(n_state, c_pad)[:, :ch]


def selective_scan_chunk(u, dt, a_log, b, c, d, state) -> Tuple[jax.Array, jax.Array]:
    """Many tokens of one request (:func:`selective_scan_xla`'s contract): the
    kernel on the chip, the XLA walk elsewhere."""
    if paged._use_pallas():
        return selective_scan_pallas(u, dt, a_log, b, c, d, state)
    return selective_scan_xla(u, dt, a_log, b, c, d, state)
