"""A decoder of the ``mellum`` family on the paged serving path.

A Qwen3-MoE-shaped stack whose layers are of two attention kinds, each with a
rotation of its own, and whose every MLP is an expert layer with no shared
expert (``benchmarks/reference_mellum.py`` writes the same equations out in
plain float32):

  h0      = E[token]
  n       = rms(h; w_in)
  q, k    = rms_head(Wq n; w_q), rms_head(Wk n; w_k)       v = Wv n   (no bias)
  sliding : q, k <- rot_plain(q, k);  key j visible to query i iff 0 <= i - j < window
  full    : q, k <- rot_yarn(q, k);   key j visible to query i iff j <= i
  h       <- h + Wo softmax(q k^T / sqrt(head_dim)) v
  m       = rms(h; w_post)
  l       = Wr m in float32;  S = the k largest;  g = softmax(l)_S / sum softmax(l)_S
  h       <- h + sum_{e in S} g_e Wdown_e (silu(Wgate_e m) * Wup_e m)
  logits  = Whead rms(h_L; w_final)

**Two rotations in one stack.** Both are rotate-half over a table of
``head_dim / 2`` inverse frequencies, cosine and sine times a scale; the table
and the scale are the layer kind's (``MellumConfig.rotation``), built once
from the published ``rope_parameters``: a sliding layer's is ``theta ^ (-2i /
d)`` at scale 1, a full layer's YaRN's (the low pairs as published, the high
ones divided by ``factor``, a linear blend between, cosine AND sine times
``attention_factor``). The K a full layer writes to its pages is rotated and
carries that factor once; a hit installs those bytes as they were saved, so
nothing downstream of the cache knows which rotation made them.

The expert layer is ``tpu/moe.py``'s ``expert_layer`` (``router =
"softmax_topk"``: softmax over all the experts, top-k, renormalised, which IS
the softmax over the k chosen logits; ``n_shared_experts = 0``), the cache's
shape ``afmoe``'s (per-layer ``windows``: a hit installs a sliding layer's
trailing ``window / block_tokens`` blocks). The three serving entries keep
the names the trace readers match and donate ``caches``; the wave returns the
ids every row chose at every layer and the expert layer's counters
(``serving.ExpertTally``).
"""

import functools
import math
from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..tpu.chunk_attention import chunk_prefix_attention
from ..tpu.flash_prefill import flash_prefill_attention
from ..tpu.paged import PagedKVCacheSpec, scatter_blocks
from ..tpu.paged_attention import paged_decode_attention_rows
from ..tpu.moe import expert_layer
from .layers import FULL, SLIDING, layer_weights, rms
from .layers import choices  # re-exported: this file's ``program.choices`` (benchmarks/configs/)
from .serving import ExpertTally, ServingSteps, real_rows, resume_step, wave_index

Params = Dict[str, jax.Array]
Caches = List[Tuple[jax.Array, jax.Array]]

# A small pair of rotations whose blend is live from position 32 on: of the
# eight pairs of a 16-wide head, pair 0 as published, 1-3 blended, 4-7 over 4.
_SMALL_ROPE = {
    FULL: {
        "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
        "original_max_position_embeddings": 32, "beta_fast": 2.0, "beta_slow": 0.125,
        "attention_factor": 0.1 * math.log(4.0) + 1.0,
    },
    SLIDING: {"rope_type": "default", "rope_theta": 10000.0},
}


def yarn_correction_range(p: Mapping, head_dim: int) -> Tuple[float, float]:
    """(low, high): the pairs between which YaRN blends. A pair that turns
    ``r`` times over the original context is pair ``c(r) = d ln(L0 / (2 pi
    r)) / (2 ln b)``; the blend runs from ``c(beta_fast)`` down-rounded to
    ``c(beta_slow)`` up-rounded (whole unless ``truncate`` is false)."""
    base, orig = float(p["rope_theta"]), float(p["original_max_position_embeddings"])
    c = lambda turns: head_dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(base))
    low, high = c(float(p.get("beta_fast", 32))), c(float(p.get("beta_slow", 1)))
    if p.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    return max(low, 0), min(high, head_dim - 1)


@functools.lru_cache(maxsize=None)
def _rotation(frozen: Tuple, head_dim: int) -> Tuple[np.ndarray, float]:
    """(inverse frequencies ``[head_dim / 2]`` float32, the scale of cosine
    and sine) of one layer kind's ``rope_parameters``, as frozen items."""
    p = dict(frozen)
    pairs = np.arange(head_dim // 2, dtype=np.float64)
    inv = float(p["rope_theta"]) ** (-2.0 * pairs / head_dim)
    kind = p.get("rope_type", "default")
    if kind == "default":
        return inv.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: this file writes out 'default' and 'yarn'")
    factor = float(p["factor"])
    low, high = yarn_correction_range(p, head_dim)
    if low == high:
        high += 0.001  # the published code's guard against a blend of no width
    ramp = np.clip((pairs - low) / (high - low), 0.0, 1.0)
    table = inv / factor * ramp + inv * (1.0 - ramp)
    scale = p.get("attention_factor")
    return table.astype(np.float32), float(0.1 * math.log(factor) + 1.0 if scale is None else scale)


@dataclass(frozen=True)
class MellumConfig:
    vocab: int = 512
    dim: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    moe_ffn_dim: int = 32  # one expert's width
    n_experts: int = 8
    experts_per_token: int = 2
    norm_topk_prob: bool = True
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    sliding_window: int = 32
    # One entry a layer kind, as the published ``rope_parameters`` has them.
    rope_parameters: Mapping = None
    rms_eps: float = 1e-6
    block_tokens: int = 8
    dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        # A configuration file hands lists and dicts; jit wants the config hashable.
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}")
        if not self.norm_topk_prob:
            raise ValueError(
                "norm_topk_prob is false: the router this family shares (tpu/moe.py route, "
                "'softmax_topk') renormalises the chosen probabilities"
            )
        rope = _SMALL_ROPE if self.rope_parameters is None else self.rope_parameters
        if isinstance(rope, Mapping):
            rope = tuple(sorted((kind, tuple(sorted(p.items()))) for kind, p in rope.items()))
        missing = set(self.layer_types) - {kind for kind, _ in rope}
        if missing:
            raise ValueError(f"rope_parameters has no entry for {sorted(missing)}")
        object.__setattr__(self, "rope_parameters", rope)
        for kind in set(self.layer_types):
            self.rotation(kind)  # a key this file cannot read stops here

    def rotation(self, kind: str) -> Tuple[np.ndarray, float]:
        """The layer kind's table of inverse frequencies and its scale."""
        return _rotation(dict(self.rope_parameters)[kind], self.head_dim)

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def held(self) -> Tuple[int, int]:
        return (0, self.n_experts)

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

    # What ``moe.expert_layer`` asks of a configuration beside the fields.
    router = "softmax_topk"
    n_shared_experts = 0
    # What the wave step counts and returns with its logits (serving.py).
    step_counters = ExpertTally.counters
    # What the published config says of the family and this file takes as
    # given (a configuration's file holds them to its own keys).
    attention_bias = False
    tie_word_embeddings = False
    hidden_act = "silu"


def init_params(config: MellumConfig, key: jax.Array) -> Params:
    """Seeded 1/sqrt(fan_in) normal weights as a flat dict (layer-prefixed
    keys, ``afmoe``'s names where the tensor is the same), norms at one."""
    keys = iter(jax.random.split(key, 2 + 8 * config.n_layers))

    def dense(k, shape, fan_in):
        w = jax.random.normal(k, shape, dtype=jnp.float32) / np.sqrt(fan_in)
        return w.astype(config.dtype)

    ones = lambda n: jnp.ones((n,), dtype=config.dtype)
    d, hd, f, e = config.dim, config.head_dim, config.moe_ffn_dim, config.n_experts
    p: Params = {
        "embed": dense(next(keys), (config.vocab, d), config.vocab),
        "final_norm": ones(d),
        "lm_head": dense(next(keys), (d, config.vocab), d),
    }
    for layer in range(config.n_layers):
        pre = f"l{layer}."
        p[pre + "in_norm"], p[pre + "post_norm"] = ones(d), ones(d)
        p[pre + "q_norm"], p[pre + "k_norm"] = ones(hd), ones(hd)
        p[pre + "wq"] = dense(next(keys), (d, config.n_heads, hd), d)
        p[pre + "wk"] = dense(next(keys), (d, config.n_kv_heads, hd), d)
        p[pre + "wv"] = dense(next(keys), (d, config.n_kv_heads, hd), d)
        p[pre + "wo"] = dense(next(keys), (config.n_heads, hd, d), config.n_heads * hd)
        p[pre + "router"] = dense(next(keys), (d, e), d)
        p[pre + "w_gate"] = dense(next(keys), (e, d, f), d)
        p[pre + "w_up"] = dense(next(keys), (e, d, f), d)
        p[pre + "w_down_moe"] = dense(next(keys), (e, f, d), f)
    return p


def _swap_halves(d: int) -> np.ndarray:
    """``[d, d]``: ``x @ P = concat(-x2, x1)`` of the halves x1, x2 of x."""
    p = np.zeros((d, d), np.float32)
    half = np.arange(d // 2)
    p[half + d // 2, half] = -1.0
    p[half, half + d // 2] = 1.0
    return p


def rotate(x, positions, inv_freq: np.ndarray, scale: float, dtype=None) -> jax.Array:
    """x: [..., seq, heads, head_dim], positions: [..., seq]. Rotate-half by
    ``positions x inv_freq``, cosine and sine times ``scale``: ``x cos +
    swap(x) sin`` in float32, rounded once, to ``dtype`` (x's own by default).
    The halves are swapped (and the first negated) by a product with a signed
    permutation, which is exact in any type: a half of a head is 64 lanes of
    the chip's 128, and as an array of its own it is padded to a whole head's
    size, so slicing q's halves out put three arrays of 514 MB beside a 32k
    miss's q (PERF.md, PR 50)."""
    angles = positions[..., :, None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(angles) * np.float32(scale), jnp.sin(angles) * np.float32(scale)
    cos = jnp.concatenate([cos, cos], axis=-1)[..., :, None, :]
    sin = jnp.concatenate([sin, sin], axis=-1)[..., :, None, :]
    swapped = jnp.dot(
        x, jnp.asarray(_swap_halves(x.shape[-1]), x.dtype), precision=jax.lax.Precision.HIGHEST
    )
    out = x.astype(jnp.float32) * cos + swapped.astype(jnp.float32) * sin
    return out.astype(dtype or x.dtype)


def _embed(params: Params, tokens: jax.Array) -> jax.Array:
    # [1, T, dim] float32: the residual stream, carried unrounded within a step.
    return jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)[None]


def _head(params: Params, x: jax.Array, config: MellumConfig) -> jax.Array:
    x = rms(x, params["final_norm"], config.rms_eps, config.dtype)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"], preferred_element_type=jnp.float32)


def _attn_inputs(w: Params, x, positions, kind: str, config: MellumConfig):
    """q [1, T, H, D], k and v [1, T, KVH, D] of the normed input, q and k
    normed per head and rotated by the layer kind's table. q and k stay in
    float32 from the projection's accumulator through the norm and the
    rotation and are rounded ONCE, to the served type: every rounding of a
    key is one every later token's attention carries."""
    f32 = jnp.float32
    n = rms(x, w["in_norm"], config.rms_eps, config.dtype)
    project = lambda name: jnp.einsum("bsd,dhk->bshk", n, w[name], preferred_element_type=f32)
    q = rms(project("wq"), w["q_norm"], config.rms_eps)
    k = rms(project("wk"), w["k_norm"], config.rms_eps)
    v = jnp.einsum("bsd,dhk->bshk", n, w["wv"])
    inv_freq, scale = config.rotation(kind)
    with jax.named_scope("yarn_rope" if kind == FULL else "plain_rope"):
        return (
            rotate(q, positions, inv_freq, scale, config.dtype),
            rotate(k, positions, inv_freq, scale, config.dtype),
            v,
        )


def _close(w: Params, x, attn, config: MellumConfig):
    """The residual adds of one layer on x: [1, T, dim] float32, attention's
    output projected and then the expert layer of the normed sum. Returns
    (x_next, ids [T, k], the expert layer's counts)."""
    x = x + jnp.einsum("bshk,hkd->bsd", attn, w["wo"], preferred_element_type=jnp.float32)
    m = rms(x, w["post_norm"], config.rms_eps, config.dtype)
    f, ids, counts = expert_layer(w, m[0], config)
    return x + f[None], ids, counts


# ---------------------------------------------------------------------------
# The three serving entries (serving.py). Each DONATES ``caches``.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("caches",))
def prefill(
    params: Params,
    tokens: jax.Array,  # [S] int32, S % block_tokens == 0
    caches: Caches,
    block_table: jax.Array,  # [S // block_tokens] int32
    config: MellumConfig,
) -> Tuple[jax.Array, Caches]:
    """A miss: the whole prompt, its K/V written to the table's blocks.
    Returns (last-token logits, caches); ``caches`` is donated."""
    s = tokens.shape[0]
    bt = config.block_tokens
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    x = _embed(params, tokens)
    new_caches: Caches = []
    for layer, (k_cache, v_cache) in enumerate(caches):
        w = layer_weights(params, layer)
        kind = config.layer_types[layer]
        q, k, v = _attn_inputs(w, x, positions, kind, config)
        with jax.named_scope(kind):
            attn = flash_prefill_attention(q, k, v, causal=True, window=config.window_of(layer))
        x, _, _ = _close(w, x, attn, config)
        blocks = lambda a: a[0].reshape(s // bt, bt, config.n_kv_heads, config.head_dim)
        k_cache = scatter_blocks(k_cache, block_table, blocks(k))
        v_cache = scatter_blocks(v_cache, block_table, blocks(v))
        # The layer's pages are written before the next layer starts: left to
        # itself the scheduler defers every layer's scatter to the program's
        # end and keeps (or recomputes K and V from) each layer's input
        # stream, 289 MiB at 32k tokens, until then (PERF.md, PR 50).
        x, k_cache, v_cache = jax.lax.optimization_barrier((x, k_cache, v_cache))
        new_caches.append((k_cache, v_cache))
    return _head(params, x[:, -1:], config)[0, -1], new_caches


def _wave_layer(
    w: Params, x, positions, k_cache, v_cache, block_idx, slots, row_tables,
    seq_lens, pages, page_rows, page_starts, config: MellumConfig, kind: str,
):
    """ONE layer of the wave body on T flat rows: insert the rows' K/V, attend
    each row's pages (a sliding layer its windowed list), residual, experts.
    The layers of one kind share one traced and lowered function."""
    q, k, v = _attn_inputs(w, x, positions, kind, config)
    k_cache = k_cache.at[block_idx, slots].set(k[0].astype(k_cache.dtype))
    v_cache = v_cache.at[block_idx, slots].set(v[0].astype(v_cache.dtype))
    with jax.named_scope(kind):
        attn = paged_decode_attention_rows(
            q[0], k_cache, v_cache, row_tables, seq_lens, pages, page_rows, page_starts,
            window=config.sliding_window if kind == SLIDING else None,
        )[None]
    x, ids, counts = _close(w, x, attn, config)
    return x, k_cache, v_cache, ids, counts


@functools.partial(
    jax.jit, static_argnames=("config", "max_blocks"), donate_argnames=("caches",)
)
def verify_step_ragged(
    params: Params, tokens, positions, row_of, pages, page_rows, page_starts, caches: Caches,
    block_tables, config: MellumConfig, max_blocks: int, window_pages=None,
):
    """THE wave body (``serving.py``: ``wave``'s contract and argument order,
    the sliding layers on the wave's second page list): ``(logits [T, vocab],
    caches, aux)`` with ``aux`` ``serving.ExpertTally``'s, the experts every
    row chose at every layer in this step and the ``moe_*`` counters, folded on
    the device. ``caches`` is donated."""
    if window_pages is None and SLIDING in config.layer_types:
        raise ValueError("a model with sliding layers needs the wave's window_pages")
    x = _embed(params, tokens)
    pos2d = positions[None]
    row_tables, block_idx, slots = wave_index(
        positions, row_of, block_tables, max_blocks, config.block_tokens
    )
    seq_lens = positions + 1

    layer_fn = jax.jit(_wave_layer, static_argnames=("config", "kind"))
    new_caches: Caches = []
    tally = ExpertTally()
    for layer, (k_cache, v_cache) in enumerate(caches):
        kind = config.layer_types[layer]
        meta = window_pages if kind == SLIDING else (pages, page_rows, page_starts)
        x, k_cache, v_cache, ids, n = layer_fn(
            layer_weights(params, layer), x, pos2d, k_cache, v_cache, block_idx,
            slots, row_tables, seq_lens, *meta, config=config, kind=kind,
        )
        new_caches.append((k_cache, v_cache))
        tally.add(ids, n)
    logits = _head(params, x, config)[0]
    aux = tally.aux(real_rows(positions, row_of), config.experts_per_token)
    return logits, new_caches, aux


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("caches",))
def resume_chunk(
    params: Params, tokens, start_pos, caches: Caches, block_table, config: MellumConfig
) -> Tuple[jax.Array, Caches]:
    """A prefix hit's question: ONE request's chunk at contiguous positions
    over the pages in the cache (``serving.py``: ``resume``'s contract). A
    sliding layer reads no page behind its first row's window: those a hit
    left uninstalled. ``caches`` is donated."""
    s_c = tokens.shape[0]
    bt = config.block_tokens
    positions = start_pos + jnp.arange(s_c, dtype=jnp.int32)
    pos2d = positions[None]
    x = _embed(params, tokens)
    block_idx = jnp.take(block_table, positions // bt)
    slots = positions % bt
    new_caches: Caches = []
    for layer, (k_cache, v_cache) in enumerate(caches):
        w = layer_weights(params, layer)
        kind = config.layer_types[layer]
        q, k, v = _attn_inputs(w, x, pos2d, kind, config)
        k_cache = k_cache.at[block_idx, slots].set(k[0].astype(k_cache.dtype))
        v_cache = v_cache.at[block_idx, slots].set(v[0].astype(v_cache.dtype))
        with jax.named_scope(kind):
            attn = chunk_prefix_attention(
                q[0], k_cache, v_cache, block_table, start_pos, window=config.window_of(layer)
            )[None]
        x, _, _ = _close(w, x, attn, config)
        new_caches.append((k_cache, v_cache))
    return _head(params, x, config)[0], new_caches


prefill_continue = resume_step(resume_chunk)
