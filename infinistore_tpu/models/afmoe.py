"""A decoder of the ``afmoe`` family on the paged serving path.

What differs from ``llama.py``, layer by layer (the equations are the
published modelling code's; ``benchmarks/reference_afmoe.py`` writes the same
ones out in plain float32):

  h0      = E[token] * sqrt(dim)                              (mup)
  n       = rms(h; w_in)
  q, k    = rms_head(Wq n; w_q), rms_head(Wk n; w_k)   v = Wv n   g = Wg n
  sliding : q, k <- rope(q, k);  key j visible to query i iff 0 <= i - j < window
  full    : no rope;             key j visible to query i iff j <= i
  a       = softmax(q k^T / sqrt(head_dim)) v          a <- a * sigmoid(g)
  h       <- h + rms(Wo a; w_post_attn)
  m       = rms(h; w_pre_mlp)
  dense   : f = Wdown (silu(Wgate m) * Wup m)
  expert  : s = sigmoid(Wr m) in float32;  S = top-k of (s + b)
            w_e = route_scale * s_e / (sum_{e in S} s_e + 1e-20)
            f = Shared(m) + sum_{e in S} w_e Expert_e(m)
  h       <- h + rms(f; w_post_mlp)
  logits  = Whead rms(h_L; w_final)

The expert layer is told which experts it holds (``AfmoeConfig.experts_held``,
a ``(first, count)`` span of the expert axis; all of them by default): it
routes over ALL experts in float32 and computes its own experts' part, the
shared expert riding with the share that holds expert 0, so the shares of a
layer spread over chips add up to the layer. Nothing here stands in for
absent chips. Two shapes of the one mathematics:

- many tokens (``prefill``, ``resume_chunk``): the (token, expert) pairs
  sorted by expert and ONE grouped matrix product a projection
  (``_grouped_ffn``: the Pallas grouped matmul on the chip, ``ragged_dot``
  elsewhere); no token dropped, no capacity factor. Its tiles follow the
  product's widths (``_gmm_tiling``): 128 rows, because a group pays for
  every row tile it touches whole, and K whole, so that a group's weights
  are fetched once however many row tiles it spans;
- few rows (a wave): the weights of the wave's DISTINCT chosen experts that
  are HELD HERE streamed once each through one kernel (``_moe_wave_pallas``:
  the scalar-prefetched expert ids drive the weight blocks' index maps, and
  a grid step past the real slots names the block before it, so nothing is
  copied for it), every row multiplied by its own combine weight for that
  expert (zero where it did not choose it); no dense pass over all experts,
  no read for an expert held elsewhere.

The three serving entries keep the names the trace readers match
(``prefill``, ``resume_chunk``, ``verify_step_ragged``) and donate ``caches``
as ``llama.py``'s do. The wave returns, beside its logits, the ids every row's
every expert layer chose and three counters (``moe_pairs``,
``moe_distinct_experts``, ``moe_streamed_experts``), all from the timed step
itself (``serving.py``).
The layerwise disagg entries of ``llama.py`` have no twin here: no cell runs
them (ROADMAP).
"""

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tpu import paged
from ..tpu.chunk_attention import chunk_prefix_attention
from ..tpu.flash_prefill import flash_prefill_attention
from ..tpu.paged import PagedKVCacheSpec, scatter_blocks
from ..tpu.paged_attention import paged_decode_attention_rows
from .llama import _rope
from .serving import ServingSteps

Params = Dict[str, jax.Array]
Caches = List[Tuple[jax.Array, jax.Array]]

SLIDING, FULL = "sliding_attention", "full_attention"
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


@dataclass(frozen=True)
class AfmoeConfig:
    vocab: int = 512
    dim: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    ffn_dim: int = 128  # the leading dense layers' width
    moe_ffn_dim: int = 32  # one expert's width
    n_experts: int = 8
    experts_per_token: int = 2
    n_shared_experts: int = 1
    n_dense_layers: int = 1
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, SLIDING, FULL)
    sliding_window: int = 32
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    route_scale: float = 2.826
    route_norm: bool = True
    mup: bool = True
    block_tokens: int = 8
    dtype: jnp.dtype = jnp.bfloat16
    # (first, count) of the expert axis this instance computes; None: all.
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        # A configuration file hands a list; jit wants the config hashable.
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", tuple(self.experts_held))
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    def window_of(self, layer: int) -> Optional[int]:
        return self.sliding_window if self.layer_types[layer] == SLIDING else None

    def kv_spec(self, num_blocks: int) -> PagedKVCacheSpec:
        return PagedKVCacheSpec(
            num_layers=self.n_layers,
            num_blocks=num_blocks,
            block_tokens=self.block_tokens,
            num_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            dtype=self.dtype,
            windows=tuple(self.window_of(l) for l in range(self.n_layers)),
        )

    @property
    def steps(self) -> ServingSteps:
        return ServingSteps(prefill, prefill_continue, verify_step_ragged)

    # What the wave step counts and returns with its logits (serving.py).
    step_counters = ("moe_pairs", "moe_distinct_experts", "moe_streamed_experts")
    # How ``route`` turns the router's logits into ids and combine weights: a
    # property of the family, read off the configuration's class.
    router = "sigmoid"


def init_params(config: AfmoeConfig, key: jax.Array) -> Params:
    """Seeded 1/sqrt(fan_in) normal weights as a flat dict (layer-prefixed
    keys), norms at one, the router's selection bias at zero. The held
    experts only where the instance holds a share."""
    keys = iter(jax.random.split(key, 4 + 12 * config.n_layers))
    _, count = config.held

    def dense(k, shape, fan_in):
        w = jax.random.normal(k, shape, dtype=jnp.float32) / np.sqrt(fan_in)
        return w.astype(config.dtype)

    ones = lambda n: jnp.ones((n,), dtype=config.dtype)
    d, hd, f = config.dim, config.head_dim, config.moe_ffn_dim
    p: Params = {
        "embed": dense(next(keys), (config.vocab, d), config.vocab),
        "final_norm": ones(d),
        "lm_head": dense(next(keys), (d, config.vocab), d),
    }
    for layer in range(config.n_layers):
        pre = f"l{layer}."
        for norm in ("in_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm"):
            p[pre + norm] = ones(d)
        p[pre + "q_norm"], p[pre + "k_norm"] = ones(hd), ones(hd)
        p[pre + "wq"] = dense(next(keys), (d, config.n_heads, hd), d)
        p[pre + "wk"] = dense(next(keys), (d, config.n_kv_heads, hd), d)
        p[pre + "wv"] = dense(next(keys), (d, config.n_kv_heads, hd), d)
        p[pre + "wg"] = dense(next(keys), (d, config.n_heads, hd), d)
        p[pre + "wo"] = dense(next(keys), (config.n_heads, hd, d), config.n_heads * hd)
        if layer < config.n_dense_layers:
            p[pre + "w_gate_up"] = dense(next(keys), (d, 2, config.ffn_dim), d)
            p[pre + "w_down"] = dense(next(keys), (config.ffn_dim, d), config.ffn_dim)
            continue
        p[pre + "router"] = dense(next(keys), (d, config.n_experts), d)
        p[pre + "router_bias"] = jnp.zeros((config.n_experts,), jnp.float32)
        p[pre + "w_gate"] = dense(next(keys), (count, d, f), d)
        p[pre + "w_up"] = dense(next(keys), (count, d, f), d)
        p[pre + "w_down_moe"] = dense(next(keys), (count, f, d), f)
        fs = f * config.n_shared_experts
        p[pre + "ws_gate_up"] = dense(next(keys), (d, 2, fs), d)
        p[pre + "ws_down"] = dense(next(keys), (fs, d), fs)
    return p


def _rms(x: jax.Array, w: jax.Array, eps: float, dtype=None) -> jax.Array:
    """In float32, rounded once, to ``dtype`` (x's own by default): a layer
    passes six of these, and each branch's output is normed to the residual
    stream's own size. The stream itself is carried in float32 within a step
    (ten adds a token at these five layers, none of them rounded to the
    served type); what the products take and the cache holds is the served
    type."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(dtype or x.dtype)


def _layer_weights(params: Params, layer: int) -> Params:
    """Layer ``layer``'s weights without the layer prefix: the pytree every
    layer of one kind hands the jitted layer body, so one trace serves them."""
    pre = f"l{layer}."
    return {k[len(pre):]: w for k, w in params.items() if k.startswith(pre)}


def _embed(params: Params, tokens: jax.Array, config: AfmoeConfig) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    if config.mup:
        x = x * np.float32(np.sqrt(config.dim))
    return x[None]  # [1, T, dim] float32: the residual stream


def _head(params: Params, x: jax.Array, config: AfmoeConfig) -> jax.Array:
    x = _rms(x, params["final_norm"], config.rms_eps, config.dtype)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"])


def _attn_inputs(w: Params, x, positions, sliding: bool, config: AfmoeConfig):
    """q [1, T, H, D], k and v [1, T, KVH, D], and the output gate's
    pre-activation g [1, T, H, D], of the normed input."""
    n = _rms(x, w["in_norm"], config.rms_eps, config.dtype)
    q = _rms(jnp.einsum("bsd,dhk->bshk", n, w["wq"]), w["q_norm"], config.rms_eps)
    k = _rms(jnp.einsum("bsd,dhk->bshk", n, w["wk"]), w["k_norm"], config.rms_eps)
    v = jnp.einsum("bsd,dhk->bshk", n, w["wv"])
    g = jnp.einsum("bsd,dhk->bshk", n, w["wg"])
    if sliding:
        q = _rope(q, positions, config.rope_theta)
        k = _rope(k, positions, config.rope_theta)
    return q, k, v, g


def _attn_out(w: Params, x, attn, g, config: AfmoeConfig):
    gated = attn.astype(jnp.float32) * jax.nn.sigmoid(g.astype(jnp.float32))
    o = jnp.einsum("bshk,hkd->bsd", gated.astype(attn.dtype), w["wo"])
    return x + _rms(o, w["post_attn_norm"], config.rms_eps, jnp.float32)


def _swiglu(m, w_gate_up, w_down):
    gate_up = jnp.einsum("bsd,dcf->bscf", m, w_gate_up)
    return jnp.einsum(
        "bsf,fd->bsd", jax.nn.silu(gate_up[:, :, 0]) * gate_up[:, :, 1], w_down
    )


# ---------------------------------------------------------------------------
# The expert layer.
# ---------------------------------------------------------------------------


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


def _wave_slots(ids, weights, config: AfmoeConfig):
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


def _moe_wave(m, ids, weights, w: Params, config: AfmoeConfig):
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


def _grouped_ffn(m, ids, weights, w: Params, config: AfmoeConfig):
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


def expert_counts(distinct=0, streamed=0) -> Dict[str, jax.Array]:
    """What one expert layer of a wave adds to the step's counters, under the
    names ``step_counters`` reports them: ``moe_distinct_experts``, the
    different experts the rows chose among ALL the router's, and
    ``moe_streamed_experts``, those of them held here: the slots whose
    weights the wave kernel reads. Both zero for many rows (the grouped
    products) and as the sum a wave step starts from."""
    return {
        "moe_distinct_experts": jnp.asarray(distinct, jnp.int32),
        "moe_streamed_experts": jnp.asarray(streamed, jnp.int32),
    }


def expert_layer(w: Params, m: jax.Array, config: AfmoeConfig):
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


def _mlp(w: Params, x, dense: bool, config: AfmoeConfig):
    """The second half of a layer on x: [1, T, dim]. Returns (x_next, ids
    [T, k] or None, the expert layer's counts or None)."""
    m = _rms(x, w["pre_mlp_norm"], config.rms_eps, config.dtype)
    if dense:
        f, ids, counts = _swiglu(m, w["w_gate_up"], w["w_down"]), None, None
    else:
        f, ids, counts = expert_layer(w, m[0], config)
        f = f[None]
    return x + _rms(f, w["post_mlp_norm"], config.rms_eps, jnp.float32), ids, counts


# ---------------------------------------------------------------------------
# The three serving entries (serving.py). Each DONATES ``caches``.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("caches",))
def prefill(
    params: Params,
    tokens: jax.Array,  # [S] int32, S % block_tokens == 0
    caches: Caches,
    block_table: jax.Array,  # [S // block_tokens] int32
    config: AfmoeConfig,
) -> Tuple[jax.Array, Caches]:
    """A miss: the whole prompt, its K/V written to the table's blocks.
    Returns (last-token logits, caches); ``caches`` is donated."""
    s = tokens.shape[0]
    bt = config.block_tokens
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    x = _embed(params, tokens, config)
    new_caches: Caches = []
    for layer, (k_cache, v_cache) in enumerate(caches):
        w = _layer_weights(params, layer)
        window = config.window_of(layer)
        q, k, v, g = _attn_inputs(w, x, positions, window is not None, config)
        attn = flash_prefill_attention(q, k, v, causal=True, window=window)
        x = _attn_out(w, x, attn, g, config)
        x, _, _ = _mlp(w, x, layer < config.n_dense_layers, config)
        blocks = lambda a: a[0].reshape(s // bt, bt, config.n_kv_heads, config.head_dim)
        new_caches.append((
            scatter_blocks(k_cache, block_table, blocks(k)),
            scatter_blocks(v_cache, block_table, blocks(v)),
        ))
    return _head(params, x[:, -1:], config)[0, -1], new_caches


def _wave_layer(
    w: Params, x, positions, k_cache, v_cache, block_idx, slots, row_tables,
    seq_lens, pages, page_rows, page_starts, config: AfmoeConfig, sliding: bool,
    dense: bool,
):
    """ONE layer of the wave body on T flat rows (``llama._wave_layer``'s
    role): insert the rows' K/V, attend each row's pages (a sliding layer its
    windowed list), gate, residual, MLP. The layers of one kind share one
    traced and lowered function."""
    q, k, v, g = _attn_inputs(w, x, positions, sliding, config)
    k_cache = k_cache.at[block_idx, slots].set(k[0].astype(k_cache.dtype))
    v_cache = v_cache.at[block_idx, slots].set(v[0].astype(v_cache.dtype))
    attn = paged_decode_attention_rows(
        q[0], k_cache, v_cache, row_tables, seq_lens, pages, page_rows, page_starts,
        window=config.sliding_window if sliding else None,
    )[None]
    x = _attn_out(w, x, attn, g, config)
    x, ids, counts = _mlp(w, x, dense, config)
    return x, k_cache, v_cache, ids, counts


@functools.partial(
    jax.jit, static_argnames=("config", "max_blocks"), donate_argnames=("caches",)
)
def verify_step_ragged(
    params: Params,
    tokens: jax.Array,  # [T] int32, the wave's chunks concatenated
    positions: jax.Array,  # [T] int32
    row_of: jax.Array,  # [T] int32 owning request per flat token
    pages: jax.Array,  # [P] the wave's flat page list (RaggedWaveMeta)
    page_rows: jax.Array,  # [P + 1]
    page_starts: jax.Array,  # [T]
    caches: Caches,
    block_tables: jax.Array,  # [B, max_blocks]
    config: AfmoeConfig,
    max_blocks: int,
    window_pages=None,  # the same triple for the sliding layers
):
    """THE wave body (``llama.verify_step_ragged``'s contract and argument
    order), with the sliding layers on the wave's second page list. Returns
    ``(logits [T, vocab], caches, aux)``: ``aux["rows"]`` [T, sites, k] the
    experts every row chose at every expert layer IN THIS STEP, and
    ``aux["counters"]``: ``moe_pairs`` (row, expert) pairs of the wave's real
    rows over its expert layers (a tail row that repeats its predecessor is
    padding), ``moe_distinct_experts``, the distinct experts they touched, a
    layer at a time, and ``moe_streamed_experts``, those of them held here
    (``expert_counts``). ``caches`` is donated."""
    t = tokens.shape[0]
    if block_tables.ndim != 2 or block_tables.shape[1] != max_blocks:
        raise ValueError(f"block_tables must be [B, {max_blocks}], got {block_tables.shape}")
    if window_pages is None and config.sliding_window is not None and SLIDING in config.layer_types:
        raise ValueError("a model with sliding layers needs the wave's window_pages")
    bt = config.block_tokens
    x = _embed(params, tokens, config)
    pos2d = positions[None]
    row_tables = jnp.take(block_tables, row_of, axis=0)
    block_idx = jnp.take_along_axis(row_tables, (positions // bt)[:, None], axis=1)[:, 0]
    slots = positions % bt
    seq_lens = positions + 1

    layer_fn = jax.jit(_wave_layer, static_argnames=("config", "sliding", "dense"))
    new_caches: Caches = []
    chosen, counts = [], expert_counts()
    for layer, (k_cache, v_cache) in enumerate(caches):
        sliding = config.window_of(layer) is not None
        meta = window_pages if sliding else (pages, page_rows, page_starts)
        x, k_cache, v_cache, ids, n = layer_fn(
            _layer_weights(params, layer), x, pos2d, k_cache, v_cache, block_idx,
            slots, row_tables, seq_lens, *meta, config=config, sliding=sliding,
            dense=layer < config.n_dense_layers,
        )
        new_caches.append((k_cache, v_cache))
        if ids is not None:
            chosen.append(ids)
            counts = jax.tree.map(jnp.add, counts, n)
    logits = _head(params, x, config)[0]
    real = jnp.concatenate([
        jnp.ones((1,), bool),
        (positions[1:] != positions[:-1]) | (row_of[1:] != row_of[:-1]),
    ])
    aux = {
        "rows": jnp.stack(chosen, axis=1),  # [T, sites, k]
        "counters": {
            "moe_pairs": jnp.sum(real, dtype=jnp.int32)
            * (len(chosen) * config.experts_per_token),
            **counts,
        },
    }
    return logits, new_caches, aux


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("caches",))
def resume_chunk(
    params: Params,
    tokens: jax.Array,  # [S_c] int32, the suffix chunk
    start_pos: jax.Array,  # [] int32
    caches: Caches,
    block_table: jax.Array,  # [max_blocks] int32
    config: AfmoeConfig,
) -> Tuple[jax.Array, Caches]:
    """A prefix hit's question: ONE request's chunk at contiguous positions
    over the pages in the cache (``llama.resume_chunk``'s contract). A
    sliding layer reads no page behind its first row's window: those a hit
    left uninstalled. ``caches`` is donated."""
    s_c = tokens.shape[0]
    bt = config.block_tokens
    positions = start_pos + jnp.arange(s_c, dtype=jnp.int32)
    pos2d = positions[None]
    x = _embed(params, tokens, config)
    block_idx = jnp.take(block_table, positions // bt)
    slots = positions % bt
    new_caches: Caches = []
    for layer, (k_cache, v_cache) in enumerate(caches):
        w = _layer_weights(params, layer)
        window = config.window_of(layer)
        q, k, v, g = _attn_inputs(w, x, pos2d, window is not None, config)
        k_cache = k_cache.at[block_idx, slots].set(k[0].astype(k_cache.dtype))
        v_cache = v_cache.at[block_idx, slots].set(v[0].astype(v_cache.dtype))
        attn = chunk_prefix_attention(
            q[0], k_cache, v_cache, block_table, start_pos, window=window
        )[None]
        x = _attn_out(w, x, attn, g, config)
        x, _, _ = _mlp(w, x, layer < config.n_dense_layers, config)
        new_caches.append((k_cache, v_cache))
    return _head(params, x, config)[0], new_caches


def prefill_continue(params, tokens, start_pos, caches, block_table, config, max_blocks):
    """``llama.prefill_continue``'s signature over this file's
    ``resume_chunk``: the harness's resume step."""
    if block_table.shape[0] != max_blocks:
        raise ValueError(
            f"block_table has {block_table.shape[0]} entries, expected max_blocks={max_blocks}"
        )
    return resume_chunk(params, tokens, start_pos, caches, block_table, config)


def choices(harness, rows) -> np.ndarray:
    """``[len(rows), sites, k]``: the experts the timed wave chose at every
    expert layer while it made the logits ``rows`` that
    ``harness.wave.step_chunk`` just handed this request (the benchmark's
    ``program.choices``). Read off what the wave returned with those very
    logits; nothing is computed again."""
    return np.asarray(harness.wave.row_aux(rows))
