"""The mathematics two or more model files share: a leaf module.

A model file (``llama``, ``afmoe``, ``kimi_linear``, ``falcon_h1``,
``granite_hybrid``, ``mellum``, ``glm_dsa``, ``sambay``, ``pangu_mtp``) imports this module,
``serving`` and ``..tpu``, and no other model file. What only ONE file uses
stays in that file (``mellum.rotate``, ``llama._rms_norm``,
every ``_attn_inputs`` / ``_qkv`` / ``_ssm_inputs``); what two use is here,
written once. This module imports ``jax``, ``numpy`` and ``..tpu`` only.
"""

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..tpu.moe import _swiglu, expert_layer

Params = Dict[str, jax.Array]

# The two kinds of attention layer a stack may mix (``afmoe``, ``mellum``:
# the published ``layer_types`` entries).
SLIDING, FULL = "sliding_attention", "full_attention"


def rms(x: jax.Array, w: jax.Array, eps: float, dtype=None) -> jax.Array:
    """RMS norm in float32, rounded once, to ``dtype`` (x's own by default):
    a layer passes several of these, and each branch's output is normed to the
    residual stream's own size. The stream itself is carried in float32 within
    a step (none of a token's adds rounded to the served type); what the
    products take and the cache holds is the served type."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(dtype or x.dtype)


def layer_weights(params: Params, layer: int) -> Params:
    """Layer ``layer``'s weights without the layer prefix: the pytree every
    layer of one kind hands the jitted layer body, so one trace serves them."""
    pre = f"l{layer}."
    return {k[len(pre):]: w for k, w in params.items() if k.startswith(pre)}


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate-half by ``positions / theta^(2 i / d)``, the angles in float32.
    x: [..., seq, heads, head_dim], positions: [..., seq]."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# The rotation of a latent mixer's positional values (``glm_dsa`` and
# ``pangu_mtp``: the DeepSeek-V3 lineage's interleaved pairs).
# ---------------------------------------------------------------------------


def _pair_swap(width: int, first: int, rope: int) -> np.ndarray:
    """The signed permutation that takes each pair ``(a, b)`` of the ``rope``
    values from ``first`` on to ``(-b, a)`` and everything else to nought."""
    m = np.zeros((width, width), np.float32)
    for i in range(first, first + rope, 2):
        m[i + 1, i], m[i, i + 1] = -1.0, 1.0
    return m


def rotate_pairs(x, positions, first: int, config, dtype=None) -> jax.Array:
    """x: [T, ..., width] float32, positions: [T]. The ``qk_rope_head_dim``
    values from ``first`` on rotated as interleaved pairs, the rest as they
    are: ``x cos + swap(x) sin`` with cosine one and sine nought outside the
    rotated part, in float32, rounded once to ``dtype``. The pairs are swapped
    by a product with a signed permutation, exact in any type: a slice at a
    lane that is no multiple of 128 (192 of a head's 256) would be re-laid out
    (``mellum.rotate``, PERF.md PR 50). Of ``config``: ``qk_rope_head_dim``
    and ``rope_theta``."""
    rope, width = config.qk_rope_head_dim, x.shape[-1]
    inv_freq = (config.rope_theta ** (-np.arange(0, rope, 2) / rope)).astype(np.float32)
    angles = positions[:, None].astype(jnp.float32) * jnp.asarray(inv_freq)
    pad = ((0, 0), (first, width - first - rope))
    cos = jnp.pad(jnp.repeat(jnp.cos(angles), 2, axis=-1), pad, constant_values=1.0)
    sin = jnp.pad(jnp.repeat(jnp.sin(angles), 2, axis=-1), pad)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (width,)
    swapped = jnp.dot(
        x, jnp.asarray(_pair_swap(width, first, rope), x.dtype),
        precision=jax.lax.Precision.HIGHEST,
    )
    out = x.astype(jnp.float32) * cos.reshape(shape) + swapped.astype(jnp.float32) * sin.reshape(shape)
    return out.astype(dtype or x.dtype)


# ---------------------------------------------------------------------------
# A [T, dim] float32 stream between an embedding and a head, an MLP that is
# dense in the leading layers and routed behind them (``kimi_linear`` and
# ``glm_dsa``: the two published stacks are this one, ``first_k_dense_replace``
# dense layers, then experts with a shared one).
# ---------------------------------------------------------------------------


def embed(params: Params, tokens: jax.Array) -> jax.Array:
    # [T, dim] float32: the residual stream, carried unrounded within a step.
    return jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)


def head(params: Params, x: jax.Array, config) -> jax.Array:
    x = rms(x, params["final_norm"], config.rms_eps, config.dtype)
    return jnp.dot(x, params["lm_head"])


def mlp(w: Params, x, dense: bool, config):
    """The second half of a layer on x: [T, dim] float32. Returns (x_next,
    ids [T, k] or None, the expert layer's counts or None)."""
    m = rms(x, w["pre_mlp_norm"], config.rms_eps, config.dtype)
    if dense:
        f = _swiglu(m[None], w["w_gate_up"], w["w_down"])[0].astype(jnp.float32)
        return x + f, None, None
    f, ids, counts = expert_layer(w, m, config)
    return x + f, ids, counts


def choices(harness, rows) -> np.ndarray:
    """``[len(rows), sites, k]``: the experts the timed wave chose at every
    expert layer while it made the logits ``rows`` that
    ``harness.wave.step_chunk`` just handed this request (the benchmark's
    ``program.choices``, which a routed model file re-exports). Read off what
    the wave returned with those very logits; nothing is computed again."""
    return np.asarray(harness.wave.row_aux(rows))


# ---------------------------------------------------------------------------
# What a block keeps beside a recurrent state: a convolution's tail, folded;
# a row's slot of a per-block tensor; the route tail.
# ---------------------------------------------------------------------------


def folded_tail_shape(taps: int, width: int):
    """A convolution tail's ``[taps - 1, width]`` rows as a cache keeps them:
    folded to 128 lanes where they divide (the array then lies row-major on
    the chip and the block copies take it as it lies), the rows rounded up to
    four, so that a block's tail is whole KiB in the served type (the rows past
    the real ones stay zero)."""
    total = (taps - 1) * width
    if total % 128:
        return (taps - 1, width)
    rows = total // 128
    return (rows + -rows % 4, 128)


def routes_shape(route_tail: int, sites: int, experts_per_token: int):
    """``[route_tail, sites, k]`` ids as the cache keeps them (``split_routes``):
    folded to 128 lanes where they divide."""
    total = route_tail * sites * experts_per_token
    lanes = 128 if total % 128 == 0 else total
    return (total // lanes, lanes)


def tail_rows(tail, config):
    """The cache's folded tail(s) ``[..., rows, lanes]`` as ``[..., taps - 1,
    conv_width]``: the real rows of the fold (``config.conv_taps``,
    ``config.conv_width``)."""
    taps, width = config.conv_taps - 1, config.conv_width
    lead = tail.shape[:-2]
    return tail.reshape(*lead, -1)[..., : taps * width].reshape(*lead, taps, width)


def tail_folded(tail, like):
    """``[..., taps - 1, conv_width]`` as the cache keeps it (``like``: the
    cache's tail tensor), zeros in the fold's spare rows."""
    lead = tail.shape[:-2]
    flat = tail.astype(like.dtype).reshape(*lead, -1)
    spare = int(np.prod(like.shape[1:])) - flat.shape[-1]
    if spare:
        flat = jnp.pad(flat, [(0, 0)] * len(lead) + [(0, spare)])
    return flat.reshape(*lead, *like.shape[1:])


def slots_of(cache, ids):
    """``cache[ids]``, a row a slice, read in place: a gather by row makes
    XLA:TPU copy every block's tensor first (cut in two along a 256-wide minor
    axis: 2 ms a layer and wave on the chip, PERF.md, PR 43). A wave's rows
    are few."""
    return jnp.stack([
        jax.lax.dynamic_index_in_dim(cache, ids[t], 0, keepdims=False)
        for t in range(ids.shape[0])
    ])


def set_slots(cache, ids, values):
    """``cache.at[ids].set(values)``, a row a slice, written in place."""
    for t in range(ids.shape[0]):
        cache = jax.lax.dynamic_update_index_in_dim(cache, values[t].astype(cache.dtype), ids[t], 0)
    return cache


def split_routes(cache, layer: int, config):
    """(the layer's own tensors, the ``routes`` tensor or None): where the
    configuration asks (``route_tail`` tokens) the LAST layer's cache ends in
    a tensor ``[blocks, route_tail x sites x k]`` int32 (folded to 128 lanes):
    the expert ids a block's last ``route_tail`` tokens chose at every expert
    layer, shifted on by every chunk and wave as a convolution's tail is."""
    if config.route_tail and layer == config.n_layers - 1:
        return cache[:-1], cache[-1]
    return cache, None


def chunk_routes(routes, chosen, block, before, fresh, config):
    """``routes`` after a chunk whose rows chose ``chosen`` (a list, a site an
    entry, of [S_c, k]): block ``block`` keeps the last ``route_tail`` tokens'
    sets, the chunk's own the newest, the older ones block ``before``'s (-1
    before a prompt's start)."""
    old = jnp.where(fresh, -1, routes[before]).reshape(config.route_tail, -1)
    mine = jnp.stack(chosen, axis=1).reshape(chosen[0].shape[0], -1)
    kept = jnp.concatenate([old, mine])[-config.route_tail :]
    return routes.at[block].set(kept.reshape(routes.shape[1:]))


def wave_routes(routes, chosen, src, dst, fresh, config):
    """``routes`` after a wave: each row's tail moves on by its own sets, from
    block ``src`` to block ``dst``, as its state does. Returns (routes, the
    tails the rows FOUND [T, route_tail, sites x k], the oldest first)."""
    t = chosen[0].shape[0]
    found = jnp.where(fresh[:, None, None], -1, routes[src]).reshape(t, config.route_tail, -1)
    mine = jnp.stack(chosen, axis=1).reshape(t, 1, -1)
    kept = jnp.concatenate([found[:, 1:], mine], axis=1)
    return routes.at[dst].set(kept.reshape(t, *routes.shape[1:])), found


def rows_with_routes(rows, found, experts_per_token: int):
    """A wave's ``aux["rows"]`` [T, sites, k] followed by the sets of the
    tokens before each row, the nearest first (``wave_routes``'s ``found``):
    [T, sites x (1 + route_tail), k]."""
    return jnp.concatenate(
        [rows, found[:, ::-1].reshape(rows.shape[0], -1, experts_per_token)], axis=1
    )
