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

The expert layer is ``tpu/moe.py``'s (``expert_layer``: the router, the grouped
products of a prompt, the streamed experts of a wave), told which experts it
holds by ``AfmoeConfig.experts_held``, a ``(first, count)`` span of the expert
axis; all of them by default.

The three serving entries keep the names the trace readers match
(``prefill``, ``resume_chunk``, ``verify_step_ragged``) and donate ``caches``
(``serving.py`` has the contract). The wave returns, beside its logits, the
ids every row's every expert layer chose and the expert layer's counters
(``serving.ExpertTally``), all from the timed step itself.
The layerwise disagg entries of ``llama.py`` have no twin here: no cell runs
them (ROADMAP).
"""

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..tpu.chunk_attention import chunk_prefix_attention
from ..tpu.flash_prefill import flash_prefill_attention
from ..tpu.moe import _swiglu, expert_layer
from ..tpu.paged import PagedKVCacheSpec, scatter_blocks
from ..tpu.paged_attention import paged_decode_attention_rows
from .layers import FULL, SLIDING, layer_weights, rms, rope
from .layers import choices  # re-exported: this file's ``program.choices`` (benchmarks/configs/)
from .serving import ExpertTally, ServingSteps, real_rows, resume_step, wave_index

Params = Dict[str, jax.Array]
Caches = List[Tuple[jax.Array, jax.Array]]


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
    step_counters = ExpertTally.counters
    # How ``moe.route`` turns the router's logits into ids and combine weights:
    # a property of the family, read off the configuration's class.
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


def _embed(params: Params, tokens: jax.Array, config: AfmoeConfig) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    if config.mup:
        x = x * np.float32(np.sqrt(config.dim))
    return x[None]  # [1, T, dim] float32: the residual stream


def _head(params: Params, x: jax.Array, config: AfmoeConfig) -> jax.Array:
    x = rms(x, params["final_norm"], config.rms_eps, config.dtype)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"])


def _attn_inputs(w: Params, x, positions, sliding: bool, config: AfmoeConfig):
    """q [1, T, H, D], k and v [1, T, KVH, D], and the output gate's
    pre-activation g [1, T, H, D], of the normed input."""
    n = rms(x, w["in_norm"], config.rms_eps, config.dtype)
    q = rms(jnp.einsum("bsd,dhk->bshk", n, w["wq"]), w["q_norm"], config.rms_eps)
    k = rms(jnp.einsum("bsd,dhk->bshk", n, w["wk"]), w["k_norm"], config.rms_eps)
    v = jnp.einsum("bsd,dhk->bshk", n, w["wv"])
    g = jnp.einsum("bsd,dhk->bshk", n, w["wg"])
    if sliding:
        q = rope(q, positions, config.rope_theta)
        k = rope(k, positions, config.rope_theta)
    return q, k, v, g


def _attn_out(w: Params, x, attn, g, config: AfmoeConfig):
    gated = attn.astype(jnp.float32) * jax.nn.sigmoid(g.astype(jnp.float32))
    o = jnp.einsum("bshk,hkd->bsd", gated.astype(attn.dtype), w["wo"])
    return x + rms(o, w["post_attn_norm"], config.rms_eps, jnp.float32)


def _mlp(w: Params, x, dense: bool, config: AfmoeConfig):
    """The second half of a layer on x: [1, T, dim]. Returns (x_next, ids
    [T, k] or None, the expert layer's counts or None)."""
    m = rms(x, w["pre_mlp_norm"], config.rms_eps, config.dtype)
    if dense:
        f, ids, counts = _swiglu(m, w["w_gate_up"], w["w_down"]), None, None
    else:
        f, ids, counts = expert_layer(w, m[0], config)
        f = f[None]
    return x + rms(f, w["post_mlp_norm"], config.rms_eps, jnp.float32), ids, counts


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
        w = layer_weights(params, layer)
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
    """ONE layer of the wave body on T flat rows: insert the rows' K/V, attend each row's pages (a sliding layer its
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
    params: Params, tokens, positions, row_of, pages, page_rows, page_starts, caches: Caches,
    block_tables, config: AfmoeConfig, max_blocks: int, window_pages=None,
):
    """THE wave body (``serving.py``: ``wave``'s contract and argument order),
    with the sliding layers on the wave's second page list. Returns ``(logits
    [T, vocab], caches, aux)``: ``aux`` is ``serving.ExpertTally``'s, the
    experts every row chose at every expert layer IN THIS STEP and the
    ``moe_*`` counters. ``caches`` is donated."""
    if window_pages is None and config.sliding_window is not None and SLIDING in config.layer_types:
        raise ValueError("a model with sliding layers needs the wave's window_pages")
    x = _embed(params, tokens, config)
    pos2d = positions[None]
    row_tables, block_idx, slots = wave_index(
        positions, row_of, block_tables, max_blocks, config.block_tokens
    )
    seq_lens = positions + 1

    layer_fn = jax.jit(_wave_layer, static_argnames=("config", "sliding", "dense"))
    new_caches: Caches = []
    tally = ExpertTally()
    for layer, (k_cache, v_cache) in enumerate(caches):
        sliding = config.window_of(layer) is not None
        meta = window_pages if sliding else (pages, page_rows, page_starts)
        x, k_cache, v_cache, ids, n = layer_fn(
            layer_weights(params, layer), x, pos2d, k_cache, v_cache, block_idx,
            slots, row_tables, seq_lens, *meta, config=config, sliding=sliding,
            dense=layer < config.n_dense_layers,
        )
        new_caches.append((k_cache, v_cache))
        tally.add(ids, n)
    logits = _head(params, x, config)[0]
    aux = tally.aux(real_rows(positions, row_of), config.experts_per_token)
    return logits, new_caches, aux


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("caches",))
def resume_chunk(
    params: Params, tokens, start_pos, caches: Caches, block_table, config: AfmoeConfig
) -> Tuple[jax.Array, Caches]:
    """A prefix hit's question: ONE request's chunk at contiguous positions
    over the pages in the cache (``serving.py``: ``resume``'s contract). A
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
        w = layer_weights(params, layer)
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


prefill_continue = resume_step(resume_chunk)
