"""The expert layer of the routed model files: a router, and one expert product in
two shapes.

Five model files (``models/afmoe.py``, ``kimi_linear.py``, ``granite_hybrid.py``,
``mellum.py``, ``glm_dsa.py``) run this one code behind their mixers.
:func:`expert_layer` takes any configuration with the fields it reads
(``n_experts``, ``experts_per_token``, ``held``, ``n_shared_experts``,
``router`` and, for the sigmoid router, ``route_norm`` / ``route_scale``) and
a layer's weights under the names ``router``, ``router_bias``, ``w_gate``,
``w_up``, ``w_down_moe``, ``ws_gate_up``, ``ws_down``.

The layer is told which experts it holds (``config.held``, a ``(first,
count)`` span of the expert axis): it routes over ALL experts in float32 and
computes its own experts' part, the shared expert riding with the share that
holds expert 0, so the shares of a layer spread over chips add up to the
layer. Nothing here stands in for absent chips. Two shapes of the one
mathematics:

- many tokens (a prompt, a chunk): the (token, expert) pairs sorted by expert
  and ONE grouped matrix product a projection (``_grouped_ffn``: the Pallas
  grouped matmul on the chip, ``ragged_dot`` elsewhere); no token dropped, no
  capacity factor. Its tiles follow the product's widths (``_gmm_tiling``):
  128 rows, because a group pays for every row tile it touches whole, and K
  whole, so that a group's weights are fetched once however many row tiles it
  spans;
- few rows (a wave): the weights of the wave's DISTINCT chosen experts that
  are HELD HERE streamed once each through one kernel (``_moe_wave_pallas``:
  the scalar-prefetched expert ids drive the weight blocks' index maps, and
  a grid step past the real slots names the block before it, so nothing is
  copied for it), every row multiplied by its own combine weight for that
  expert (zero where it did not choose it); no dense pass over all experts,
  no read for an expert held elsewhere.

What a wave's layer counts (:func:`expert_counts`, under the names of
``EXPERT_COUNTERS``) rides back with its output; ``models/serving.py``
``ExpertTally`` sums it over a step's layers.
"""

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged

Params = Dict[str, jax.Array]

# Tokens of a prompt whose expert products run as one grouped matmul: a
# longer prompt is cut into equal chunks of at most this many (whole
# multiples of 128), one after the other, so the sorted copies of a 32k
# prompt's activations (8 a token) never stand in HBM at once.
_MOE_CHUNK_TOKENS = 8192
# Rows up to which the expert layer streams the rows' distinct experts
# (rows x k slots at most) instead of sorting pairs into a grouped matmul.
_MOE_WAVE_ROWS = 16
_VMEM_LIMIT = 64 << 20
# The wave kernel's tile along an expert's width, where the width is whole
# tiles of it; else the width whole (768 = 6 x 128: one contiguous block an
# expert and projection).
_MOE_WAVE_F_TILE = 512
# The grouped product's row tile and the most elements of a weight tile
# (``_gmm_tiling``): 4.5 MiB in bfloat16, twice over in VMEM's 16 MiB.
_GMM_ROW_TILE = 128
_GMM_WEIGHT_TILE = 2304 * 1024


def _swiglu(m, w_gate_up, w_down):
    gate_up = jnp.einsum("bsd,dcf->bscf", m, w_gate_up)
    return jnp.einsum(
        "bsf,fd->bsd", jax.nn.silu(gate_up[:, :, 0]) * gate_up[:, :, 1], w_down
    )


def _router_logits(m: jax.Array, router: jax.Array) -> jax.Array:
    return jnp.dot(
        m.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


def route(m: jax.Array, router: jax.Array, bias: Optional[jax.Array], config):
    """m: [T, dim]. The ids the top-k chose ([T, k] int32) and their combine
    weights ([T, k] float32), over ALL experts, in float32. Which router is
    the configuration's (``config.router``): ``"sigmoid"`` ranks by sigmoid
    score + selection bias and weighs by the scores alone; ``"softmax_topk"``
    takes the k largest LOGITS and a softmax over those k alone (no bias, no
    scale)."""
    if config.router == "softmax_topk":
        with jax.named_scope("softmax_topk_router"):
            top, ids = jax.lax.top_k(_router_logits(m, router), config.experts_per_token)
            return ids.astype(jnp.int32), jax.nn.softmax(top, axis=-1)
    with jax.named_scope("afmoe_router"):
        logits = _router_logits(m, router)
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), config.experts_per_token)
        chosen = jnp.take_along_axis(scores, ids, axis=1)
        if config.route_norm:
            chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
        return ids.astype(jnp.int32), chosen * config.route_scale


def _moe_wave_kernel(ids_ref, n_ref, x_ref, c_ref, wg_ref, wu_ref, wd_ref, out_ref):
    """Grid (slot, F tile): slot s is the s-th distinct HELD expert the wave's
    rows chose; its gate, up and down tiles come in by the block specs' index
    maps (``_wave_block``), every row meets them, and the row's combine
    weight for that expert (zero where it did not choose it) scales what it
    adds. A step past the ``n_ref[0]`` real slots names the block the last
    real step named, whatever the expert's width in tiles, so the pipeline
    copies nothing there, and skips the compute."""
    del ids_ref
    s, j = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(s == 0, j == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(s < n_ref[0])
    def _fold():
        x = x_ref[...]
        dot = functools.partial(
            jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        h = jax.nn.silu(dot(x, wg_ref[...])) * dot(x, wu_ref[...])  # [Tp, tf] f32
        h = h * c_ref[...][:, :1]
        out_ref[...] += dot(h.astype(x.dtype), wd_ref[...])


def _wave_f_tile(f: int) -> int:
    """The wave kernel's tile along an expert's width ``f``."""
    tf = min(f, _MOE_WAVE_F_TILE)
    return tf if f % tf == 0 else f


def _wave_block(s, j, ids, n, tiles: int):
    """(slot, expert, F tile) whose blocks grid step ``(s, j)`` of the wave
    kernel names: ``(s, ids[s], j)`` on the ``n[0]`` real slots, and past
    them what the last real step named, ``(n - 1, ids[n - 1], tiles - 1)``,
    in BOTH coordinates: a block index that stands still is not copied again,
    one that moves in ``j`` alone is (a whole expert a padded slot, where an
    expert is several tiles wide). With no real slot it is slot 0's last
    tile at every step: fetched once, never used."""
    last = jnp.maximum(n[0] - 1, 0)
    at = jnp.minimum(s, last)
    return at, ids[at], jnp.where(s < n[0], j, tiles - 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _moe_wave_pallas(x, slots, n_slots, combine, w_gate, w_up, w_down, *, interpret):
    """x: [Tp, D]; slots: [S] int32 expert ids (held-local), the first
    ``n_slots[0]`` real; combine: [S, Tp, 128] float32 (lanes equal); weights
    [E, D, F], [E, D, F], [E, F, D]. Returns [Tp, D] float32."""
    tp, d = x.shape
    f = w_gate.shape[2]
    tf = _wave_f_tile(f)
    block = functools.partial(_wave_block, tiles=f // tf)

    def in_cols(s, j, ids, n):  # gate, up: [E, D, F] by (expert, 0, tile)
        _, e, tile = block(s, j, ids, n)
        return e, 0, tile

    def in_rows(s, j, ids, n):  # down: [E, F, D] by (expert, tile, 0)
        _, e, tile = block(s, j, ids, n)
        return e, tile, 0

    return pl.pallas_call(
        _moe_wave_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots.shape[0], f // tf),
            in_specs=[
                pl.BlockSpec((tp, d), lambda s, j, ids, n: (0, 0)),
                pl.BlockSpec((None, tp, 128), lambda s, j, ids, n: (block(s, j, ids, n)[0], 0, 0)),
                pl.BlockSpec((None, d, tf), in_cols),
                pl.BlockSpec((None, d, tf), in_cols),
                pl.BlockSpec((None, tf, d), in_rows),
            ],
            out_specs=pl.BlockSpec((tp, d), lambda s, j, ids, n: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((tp, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(slots, n_slots, x, combine, w_gate, w_up, w_down)


def _wave_slots(ids, weights, config):
    """The wave's distinct chosen experts HELD HERE as kernel slots, in
    ascending order, compacted to the front. Returns (slots [S] held-local
    ids with ``S = min(T * k, count)``: a wave cannot choose more distinct
    held experts than are held; the count of real slots [1]; combine [S, T]
    float32, zero past the real slots; the number of distinct experts the
    rows chose among ALL experts). An expert held elsewhere gets no slot: it
    costs neither a read nor a product."""
    t, k = ids.shape
    first, count = config.held
    # Held experts sort first, in their own order, as ``_grouped_ffn`` has it.
    key = jnp.mod(ids - first, config.n_experts)
    flat = key.reshape(-1)
    uniq = jnp.unique(flat, size=min(t * k, config.n_experts), fill_value=jnp.max(flat))
    fresh = jnp.concatenate([jnp.ones((1,), bool), uniq[1:] != uniq[:-1]])
    n_slots = min(t * k, count)
    slots = uniq[:n_slots]
    mine = fresh[:n_slots] & (slots < count)
    hits = (key[None] == slots[:, None, None]) & mine[:, None, None]  # [S, T, k]
    combine = jnp.sum(jnp.where(hits, weights[None], 0.0), axis=-1)  # [S, T]
    n_held, distinct = jnp.sum(mine, dtype=jnp.int32), jnp.sum(fresh, dtype=jnp.int32)
    return jnp.minimum(slots, count - 1), n_held.reshape(1), combine, distinct


def _moe_wave(m, ids, weights, w: Params, config):
    """The few-rows form. m: [T, dim]; returns ([T, dim] float32, the
    layer's ``expert_counts``)."""
    t, d = m.shape
    slots, n_held, combine, distinct = _wave_slots(ids, weights, config)
    with jax.named_scope("afmoe_gathered_product"):
        if paged._use_pallas():
            tp = -(-t // 16) * 16
            x = jnp.pad(m, ((0, tp - t), (0, 0)))
            c = jnp.pad(combine, ((0, 0), (0, tp - t)))
            c = jnp.broadcast_to(c[:, :, None], (*c.shape, 128))
            out = _moe_wave_pallas(
                x, slots, n_held, c, w["w_gate"], w["w_up"], w["w_down_moe"],
                interpret=False,
            )[:t]
        else:
            out = moe_wave_xla(m, slots, combine, w["w_gate"], w["w_up"], w["w_down_moe"])
    return out, expert_counts(distinct, n_held[0])


@jax.jit
def moe_wave_xla(m, slots, combine, w_gate, w_up, w_down):
    """The wave kernel's mathematics in plain XLA (off the chip, and the
    tests' reference for the kernel): gathers the slots' weights."""
    f32 = jnp.float32
    g = jnp.einsum("td,sdf->stf", m, jnp.take(w_gate, slots, axis=0), preferred_element_type=f32)
    u = jnp.einsum("td,sdf->stf", m, jnp.take(w_up, slots, axis=0), preferred_element_type=f32)
    h = (jax.nn.silu(g) * u * combine[:, :, None]).astype(m.dtype)
    return jnp.einsum("stf,sfd->td", h, jnp.take(w_down, slots, axis=0), preferred_element_type=f32)


def _lane_tile(width: int, most: int) -> int:
    """A K or N tile of a grouped product over ``width``: the width whole
    where it is at most ``most``, else the largest multiple of 128 lanes that
    DIVIDES it (2,304 under 2,047: 1,152, no last tile a quarter full and
    masked), else 1,024 with a ragged last tile."""
    if width <= most:
        return width
    whole = [t for t in range(128, most + 1, 128) if width % t == 0]
    return whole[-1] if whole else 1024


def _gmm_tiling(k: int, n: int) -> Tuple[int, int, int]:
    """The ``(tm, tk, tn)`` handed to the Pallas grouped matmul, from the
    product's two widths alone (tools/gmm_tile_probe.py is the sweep behind
    it). The grid visits a group once a row tile it touches, one whole ``tm x
    tk x tn`` pass a step, and fetches an operand's tile only when its index
    moves. So: rows of ``_GMM_ROW_TILE``, the matrix unit's, because a group
    of 4 to 32 rows pays for the whole tile; K WHOLE, so that the steps of
    one group share one weight tile however many row tiles the group spans
    (cut K and every visit reads the weights again; a K past 9,216, which no
    configuration has, is cut as before the rule); N as wide as keeps the
    weight tile within ``_GMM_WEIGHT_TILE`` elements of VMEM."""
    tk = k if k <= _GMM_WEIGHT_TILE // 256 else _lane_tile(k, 1024)
    return _GMM_ROW_TILE, tk, _lane_tile(n, _GMM_WEIGHT_TILE // tk)


def _grouped_matmul(lhs, rhs, group_sizes, out_dtype):
    """lhs [M, K] sorted by group, M whole row tiles, rhs [G, K, N],
    group_sizes [G] (their sum may fall short of M: the rows past it are
    nobody's and cost nothing). One grouped matrix product: the Pallas
    grouped matmul (megablox ``gmm``) on the chip, in 128-row tiles with K
    whole (``_gmm_tiling``), ``ragged_dot`` elsewhere."""
    if paged._use_pallas():
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        tiling = _gmm_tiling(lhs.shape[1], rhs.shape[2])
        return gmm(lhs, rhs, group_sizes, preferred_element_type=out_dtype, tiling=tiling)
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes, preferred_element_type=out_dtype
    )


def _grouped_ffn(m, ids, weights, w: Params, config):
    """The many-tokens form for one chunk. m: [T, dim]; ids, weights: [T, k].
    The (token, expert) pairs sorted by expert, the held experts' first; a
    grouped product each for gate, up and down over the held experts' rows;
    the rest of the pairs (another share's) add nothing. [T, dim] float32."""
    t, d = m.shape
    k = ids.shape[1]
    first, count = config.held
    flat = ids.reshape(-1)
    # Held experts sort first, in their own order: (id - first) mod E.
    order_key = jnp.mod(flat - first, config.n_experts)
    order = jnp.argsort(order_key)
    token = order // k
    group_sizes = jnp.bincount(order_key, length=config.n_experts)[:count].astype(jnp.int32)
    rows = jnp.take(m, token, axis=0)  # [T * k, dim]
    pad = -rows.shape[0] % _GMM_ROW_TILE
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    with jax.named_scope("afmoe_grouped_product"):
        gate = _grouped_matmul(rows, w["w_gate"], group_sizes, jnp.float32)
        up = _grouped_matmul(rows, w["w_up"], group_sizes, jnp.float32)
        h = (jax.nn.silu(gate) * up).astype(m.dtype)
        out = _grouped_matmul(h, w["w_down_moe"], group_sizes, jnp.float32)
    mine = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
    out = jnp.where(mine[:, None], out, 0.0)[: t * k]
    out = out * jnp.take(weights.reshape(-1), order)[:, None]
    return jnp.zeros((t, d), jnp.float32).at[token].add(out)


def _chunks(tokens: int) -> Tuple[int, int]:
    """(chunks, tokens a chunk) for a prompt's expert products."""
    n = -(-tokens // _MOE_CHUNK_TOKENS)
    return n, -(-tokens // (n * 128)) * 128


# What one expert layer of a wave counts (``expert_counts``), by the names a
# routed configuration's ``step_counters`` reports them under.
EXPERT_COUNTERS = ("moe_distinct_experts", "moe_streamed_experts")


def expert_counts(distinct=0, streamed=0) -> Dict[str, jax.Array]:
    """What one expert layer of a wave adds to the step's counters
    (``EXPERT_COUNTERS``): ``moe_distinct_experts``, the different experts the
    rows chose among ALL the router's, and ``moe_streamed_experts``, those of
    them held here: the slots whose weights the wave kernel reads. Both zero
    for many rows (the grouped products) and as the sum a wave step starts
    from."""
    return {
        name: jnp.asarray(n, jnp.int32) for name, n in zip(EXPERT_COUNTERS, (distinct, streamed))
    }


def expert_layer(w: Params, m: jax.Array, config):
    """m: [T, dim], the normed input. Returns (f [T, dim] float32, ids
    [T, k] the experts each row chose among all, the layer's
    ``expert_counts``: counted for few rows only, else 0)."""
    t = m.shape[0]
    first, _ = config.held
    ids, weights = route(m, w["router"], w.get("router_bias"), config)
    if t <= _MOE_WAVE_ROWS:
        out, counts = _moe_wave(m, ids, weights, w, config)
    else:
        counts = expert_counts()
        n, size = _chunks(t)
        if n == 1:
            out = _grouped_ffn(m, ids, weights, w, config)
        else:
            pad = n * size - t
            cut = lambda x: jnp.pad(x, ((0, pad), (0, 0))).reshape(n, size, x.shape[1])
            out = jax.lax.map(
                lambda c: _grouped_ffn(c[0], c[1], c[2], w, config),
                (cut(m), cut(ids), cut(weights)),
            ).reshape(n * size, -1)[:t]
    if first == 0 and config.n_shared_experts:
        out = out + _swiglu(m[None], w["ws_gate_up"], w["ws_down"])[0].astype(jnp.float32)
    return out, ids, counts
