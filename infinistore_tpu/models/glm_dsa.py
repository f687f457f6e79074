"""A decoder of the ``glm_moe_dsa`` family on the paged serving path: latent
attention (MLA) with a query latent and a rotated shared key in EVERY layer,
an indexer beside it that picks the ``index_topk`` positions a query reads
(DeepSeek sparse attention), leading dense layers, then expert layers.

``benchmarks/reference_glm_dsa.py`` writes the same equations out in plain
float32. Pre-norm; ``R`` rotates INTERLEAVED pairs ``(2i, 2i + 1)`` by ``pos x
theta ^ (-2i / rope)``, unscaled:

  h       = x + mixer(rms(x; w_in))          y = h + mlp(rms(h; w_pre_mlp))
  query   : c_q = rms(W_qa n; w_qn);  q = W_qb c_q = [q_n | q_r] a head;  q_r <- R(q_r)
  cache   : [c | k_r] = W_kva n;  c <- rms(c; w_kvn);  k_r <- R(k_r), one for all heads
            [k_n | v] = W_kvb c a head
  indexer : q_I = W_Iq c_q a head, k_I = LayerNorm(W_Ik n), the first ``rope``
            values of each rotated;  w = W_Iw n / sqrt(heads x width)
            I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])          (float32)
            S_t = the min(t + 1, index_topk) positions s <= t of largest I[t, s]
  mixer   : softmax over S_t of (q_n . k_n[s] + q_r . k_r[s]) / sqrt(nope + rope);  Wo (p v)
  dense   : Wdown (silu(Wgate m) * Wup m)
  expert  : ``tpu/moe.py`` ``expert_layer`` (sigmoid scores, top-k of s + b, route_scale
            x s_e / sum of the chosen, a shared expert, the held share)
  logits  = Whead rms(h_L; w_final)

The cache (``kv_spec``): per layer TWO tensors, both fetched in every block of
a hit: ``tpu/mla.py``'s latent ``[blocks, rank + rope, block_tokens]`` (the
normed latent beside the ROTATED shared key) and the index keys ``[blocks,
index_dim, block_tokens]`` (``CacheTensor.kind`` ``"index"``), the token the
minor axis of both. Nothing in it is a recurrent state, but ``resume_chunk``
lies inside one block, so the engine computes a prompt as it does Kimi's
(``ServingSteps.resume_in_block``): cut at block boundaries, the part-full
last block kept, the prompt's last token landed by the first wave. A chunk's rows and a wave's rows
score their whole context's index keys (``tpu/dsa.py``), select per row, and
attend over their own sets: every page is read under the selection's bias
(``dsa`` says why, and PERF.md what a gather would cost). q, k_r, q_I and k_I
stay in float32 from the projection's accumulator through norm and rotation
and are rounded ONCE.

The three serving entries keep the names the trace readers match:
``prefill`` (``serving.prefill_by_blocks``: a miss cut at block boundaries through ``resume_chunk``),
``resume_chunk`` and ``verify_step_ragged``; each donates ``caches``.
"""

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..tpu import dsa, mla
from ..tpu.paged import CacheTensor, PagedKVCacheSpec
from .layers import embed, head, layer_weights, mlp, rms, rotate_pairs
from .layers import choices  # re-exported: this file's ``program.choices`` (benchmarks/configs/)
from .serving import (
    ExpertTally, ServingSteps, chunk_index, prefill_by_blocks, real_rows, resume_step, wave_index,
)

Params = Dict[str, jax.Array]
Caches = List[Tuple[jax.Array, jax.Array]]

INDEX_NORM_EPS = 1e-6  # the indexer's LayerNorm (the published modelling code's default)


@dataclass(frozen=True)
class GlmDsaConfig:
    vocab: int = 512
    dim: int = 64
    n_layers: int = 3
    n_heads: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    # The indexer
    index_heads: int = 4
    index_head_dim: int = 16
    index_topk: int = 16
    # MLPs
    ffn_dim: int = 128  # the leading dense layers' width
    moe_ffn_dim: int = 32
    n_experts: int = 8  # the router's width
    experts_per_token: int = 2
    n_shared_experts: int = 1
    n_dense_layers: int = 1
    route_scale: float = 2.5
    route_norm: bool = True
    rms_eps: float = 1e-5
    rope_theta: float = 1e6
    block_tokens: int = 8
    dtype: jnp.dtype = jnp.bfloat16
    # (first, count) of the expert axis this instance computes; None: all.
    experts_held: Optional[Tuple[int, int]] = None
    # The published ``rope_parameters`` group, whole: read into ``rope_theta``
    # and dropped, so that the config stays hashable.
    rope: Optional[dict] = None

    def __post_init__(self):
        if self.rope is not None:
            if self.rope.get("rope_type", "default") != "default":
                raise ValueError(f"rope_type {self.rope['rope_type']!r}: only the unscaled rotation is written")
            object.__setattr__(self, "rope_theta", float(self.rope["rope_theta"]))
            object.__setattr__(self, "rope", None)
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", tuple(self.experts_held))
        if self.qk_rope_head_dim % 2 or self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError(
                f"a rotated part of {self.qk_rope_head_dim} values is no whole pairs within an "
                f"index key of {self.index_head_dim}"
            )

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def held_count(self) -> int:
        return self.held[1]

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def sites(self) -> int:
        """Expert layers: the model's discrete-choice sites, in its order."""
        return self.n_layers - self.n_dense_layers

    def layer_cache(self) -> Tuple[CacheTensor, CacheTensor]:
        bt = self.block_tokens
        return (
            CacheTensor("latent", (self.latent_width, bt), self.dtype, None, "latent"),
            CacheTensor("index", (self.index_head_dim, bt), self.dtype, None, "index"),
        )

    def kv_spec(self, num_blocks: int) -> PagedKVCacheSpec:
        return PagedKVCacheSpec.of_layers(
            num_blocks, self.block_tokens, [self.layer_cache()] * self.n_layers
        )

    @property
    def steps(self) -> ServingSteps:
        return ServingSteps(prefill, prefill_continue, verify_step_ragged, resume_in_block=True)

    # What the wave step counts and returns with its logits (serving.py): the
    # expert layer's three, over its real rows and layers the positions the
    # selection kept of those it could have (float32: a window's sum passes
    # 2^31), and over its layers the counting passes the selection's searches
    # made over their keys and the row tiles searched (``dsa.select``).
    step_counters = (
        *ExpertTally.counters, "dsa_keys_selected", "dsa_keys_in_context",
        "dsa_select_passes", "dsa_select_searches",
    )
    router = "sigmoid"  # ``moe.route``'s kind


def init_params(config: GlmDsaConfig, key: jax.Array) -> Params:
    """Seeded 1/sqrt(fan_in) normal weights as a flat dict (layer-prefixed
    keys), the embedding's rows 1 / sqrt(vocab) as the siblings', norms at one
    (the indexer's LayerNorm bias at zero), the router's selection bias at
    zero. The held experts only where the instance holds a share."""
    keys = iter(jax.random.split(key, 4 + 16 * config.n_layers))
    _, count = config.held
    f32 = jnp.float32

    def dense(k, shape, fan_in):
        w = jax.random.normal(k, shape, dtype=f32) / np.sqrt(fan_in)
        return w.astype(config.dtype)

    ones = lambda n: jnp.ones((n,), dtype=config.dtype)
    d, f, h = config.dim, config.moe_ffn_dim, config.n_heads
    qr, r = config.q_lora_rank, config.kv_lora_rank
    nope, rope, v = config.qk_nope_head_dim, config.qk_rope_head_dim, config.v_head_dim
    hi, di = config.index_heads, config.index_head_dim
    p: Params = {
        "embed": dense(next(keys), (config.vocab, d), config.vocab),
        "final_norm": ones(d),
        "lm_head": dense(next(keys), (d, config.vocab), d),
    }
    for layer in range(config.n_layers):
        pre = f"l{layer}."
        p[pre + "in_norm"], p[pre + "pre_mlp_norm"] = ones(d), ones(d)
        p[pre + "w_qa"] = dense(next(keys), (d, qr), d)
        p[pre + "q_norm"] = ones(qr)
        p[pre + "w_qb"] = dense(next(keys), (qr, h, nope + rope), qr)
        p[pre + "w_kva"] = dense(next(keys), (d, r + rope), d)
        p[pre + "kv_norm"] = ones(r)
        p[pre + "w_kvb"] = dense(next(keys), (r, h, nope + v), r)
        p[pre + "wo"] = dense(next(keys), (h * v, d), h * v)
        p[pre + "wi_q"] = dense(next(keys), (qr, hi, di), qr)
        p[pre + "wi_k"] = dense(next(keys), (d, di), d)
        p[pre + "wi_k_norm"], p[pre + "wi_k_bias"] = ones(di), jnp.zeros((di,), config.dtype)
        p[pre + "wi_w"] = dense(next(keys), (d, hi), d)
        if layer < config.n_dense_layers:
            p[pre + "w_gate_up"] = dense(next(keys), (d, 2, config.ffn_dim), d)
            p[pre + "w_down"] = dense(next(keys), (config.ffn_dim, d), config.ffn_dim)
            continue
        p[pre + "router"] = dense(next(keys), (d, config.n_experts), d)
        p[pre + "router_bias"] = jnp.zeros((config.n_experts,), f32)
        p[pre + "w_gate"] = dense(next(keys), (count, d, f), d)
        p[pre + "w_up"] = dense(next(keys), (count, d, f), d)
        p[pre + "w_down_moe"] = dense(next(keys), (count, f, d), f)
        fs = f * config.n_shared_experts
        p[pre + "ws_gate_up"] = dense(next(keys), (d, 2, fs), d)
        p[pre + "ws_down"] = dense(next(keys), (fs, d), fs)
    return p


# ---------------------------------------------------------------------------
# The mixer's inputs.
# ---------------------------------------------------------------------------


def _mixer_inputs(w: Params, n, positions, config: GlmDsaConfig):
    """n: [T, dim], the normed input. Returns q [T, H, nope + rope] (rotated),
    the latent cache's row [T, rank + rope] (the normed latent beside the
    rotated shared key), the indexer's queries [T, Hi, Di], its key [T, Di]
    (the index cache's row) and its head weights [T, Hi] float32."""
    f32, dt = jnp.float32, config.dtype
    r, nope = config.kv_lora_rank, config.qk_nope_head_dim
    c_q = rms(mla.einsum_f32("td,dr->tr", n, w["w_qa"]), w["q_norm"], config.rms_eps, dt)
    q = mla.einsum_f32("tr,rhk->thk", c_q, w["w_qb"])
    kva = mla.einsum_f32("td,dr->tr", n, w["w_kva"])
    c = rms(kva[:, :r], w["kv_norm"], config.rms_eps, dt)
    with jax.named_scope("mla_rope"):
        q = rotate_pairs(q, positions, nope, config, dt)
        k_r = rotate_pairs(kva[:, r:], positions, 0, config, dt)
    with jax.named_scope("dsa_index"):
        q_i = rotate_pairs(mla.einsum_f32("tr,rhk->thk", c_q, w["wi_q"]), positions, 0, config, dt)
        k = mla.einsum_f32("td,dk->tk", n, w["wi_k"])
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + INDEX_NORM_EPS)
        k = k * w["wi_k_norm"].astype(f32) + w["wi_k_bias"].astype(f32)
        k_i = rotate_pairs(k, positions, 0, config, dt)
        w_i = mla.einsum_f32("td,dh->th", n, w["wi_w"]) * np.float32(
            (config.index_heads * config.index_head_dim) ** -0.5
        )
    return q, jnp.concatenate([c, k_r], axis=-1), q_i, k_i, w_i


def _scale(config: GlmDsaConfig) -> float:
    return float((config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5)


def _mixer_out(w: Params, x, attn, config: GlmDsaConfig):
    """attn: [T, H, v] float32."""
    a = attn.astype(config.dtype).reshape(x.shape[0], -1)
    return x + mla.einsum_f32("tk,kd->td", a, w["wo"])


# ---------------------------------------------------------------------------
# The three serving entries (serving.py). Each DONATES ``caches``.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("caches",))
def resume_chunk(
    params: Params, tokens, start_pos, caches: Caches, block_table, config: GlmDsaConfig
) -> Tuple[jax.Array, Caches]:
    """ONE request's chunk at contiguous positions INSIDE ONE BLOCK (the
    caller cuts at block boundaries): a hit's question, and every piece of a
    miss's prefill. Each layer writes the chunk's latents and index keys into
    the block, scores the context so far, selects per row and attends over
    each row's own set. Returns (logits [S_c, vocab], caches); ``caches`` is
    donated."""
    bt = config.block_tokens
    block, _, _ = chunk_index(tokens, start_pos, block_table, bt)
    positions = start_pos + jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = embed(params, tokens)
    new_caches: Caches = []
    for layer, (latent, index) in enumerate(caches):
        w = layer_weights(params, layer)
        n = rms(x, w["in_norm"], config.rms_eps, config.dtype)
        q, rows, q_i, k_i, w_i = _mixer_inputs(w, n, positions, config)
        # The chunk lies in one block: one slice written in place (a scatter
        # by index makes XLA re-lay the whole cache out, twice).
        at = (block, 0, start_pos % bt)
        latent = jax.lax.dynamic_update_slice(latent, rows.T[None], at)
        index = jax.lax.dynamic_update_slice(index, k_i.T[None], at)
        with jax.named_scope("dsa_index"):
            scores = dsa.index_scores_chunk(q_i, w_i, index, block_table, start_pos)
        with jax.named_scope("dsa_select"):
            bias, _ = dsa.select(scores, positions + 1, config.index_topk)
        with jax.named_scope("mla_sparse_attention"):
            attn = mla.latent_chunk_attention(
                q, latent, block_table, start_pos, w["w_kvb"], rank=config.kv_lora_rank,
                nope=config.qk_nope_head_dim, scale=_scale(config), bias=bias,
            )
        x = _mixer_out(w, x, attn, config)
        x, _, _ = mlp(w, x, layer < config.n_dense_layers, config)
        new_caches.append((latent, index))
    return head(params, x, config), new_caches


prefill_continue = resume_step(resume_chunk)
prefill = prefill_by_blocks(resume_chunk)


def _wave_mixer(w: Params, x, latent, index, dst, slots, row_tables, positions,
                config: GlmDsaConfig):
    """One layer's mixer over a wave's rows. Returns (x_next, latent, index,
    the selection's bias [max_blocks, T, block_tokens], its passes a row
    tile [tiles])."""
    n = rms(x, w["in_norm"], config.rms_eps, config.dtype)
    q, rows, q_i, k_i, w_i = _mixer_inputs(w, n, positions, config)
    # A row a slice, in place: a scatter by (block, slot) makes XLA re-lay the
    # whole cache out and back every wave. A wave's rows are few.
    for t in range(rows.shape[0]):
        at = (dst[t], 0, slots[t])
        latent = jax.lax.dynamic_update_slice(latent, rows[t][None, :, None], at)
        index = jax.lax.dynamic_update_slice(index, k_i[t][None, :, None], at)
    seq_lens = positions + 1
    with jax.named_scope("dsa_index"):
        scores = dsa.index_scores_rows(q_i, w_i, index, row_tables, seq_lens)
    with jax.named_scope("dsa_select"):
        bias, passes = dsa.select(scores, seq_lens, config.index_topk)
    nope, r = config.qk_nope_head_dim, config.kv_lora_rank
    with jax.named_scope("mla_sparse_attention"):
        # Absorbed: the query through the keys' up-projection, the output
        # through the values'.
        q_abs = mla.einsum_f32("thd,rhd->thr", q[..., :nope], w["w_kvb"][..., :nope])
        q_lat = jnp.concatenate([q_abs.astype(q.dtype), q[..., nope:]], axis=-1)
        mix = dsa.sparse_latent_decode_rows(
            q_lat, latent, bias, row_tables, seq_lens, rank=r, scale=_scale(config)
        )
        attn = mla.einsum_f32("thr,rhd->thd", mix.astype(config.dtype), w["w_kvb"][..., nope:])
    return _mixer_out(w, x, attn, config), latent, index, bias, passes


def _packed_set(bias, k: int):
    """The selection a row as bits, 32 positions an int32 (position s is bit
    ``s % 32`` of word ``s // 32``), the words laid ``k`` a site and padded
    with empty ones to whole sites: [T, words / k, k]."""
    p, t, bt = bias.shape
    chosen = jnp.swapaxes(bias, 0, 1).reshape(t, p * bt) == 0.0
    words = -(-p * bt // (32 * k)) * k
    chosen = jnp.pad(chosen, ((0, 0), (0, words * 32 - p * bt))).reshape(t, words, 32)
    bit = jnp.left_shift(jnp.int32(1), jnp.arange(32, dtype=jnp.int32))
    return jnp.sum(jnp.where(chosen, bit, 0), axis=-1, dtype=jnp.int32).reshape(t, words // k, k)


@functools.partial(
    jax.jit, static_argnames=("config", "max_blocks"), donate_argnames=("caches",)
)
def verify_step_ragged(
    params: Params, tokens, positions, row_of, pages, page_rows, page_starts, caches: Caches,
    block_tables, config: GlmDsaConfig, max_blocks: int,
):
    """THE wave body (``serving.py``: ``wave``'s contract and argument order).
    Each row writes its latent and its index key in place, scores its whole
    context through its block table, selects, and attends in the absorbed
    form. Returns ``(logits [T, vocab], caches, aux)``: ``serving.ExpertTally``'s
    ``aux``, its ``rows`` [T, sites + layers x words / k, k] int32 followed, a
    layer, by the positions its selection kept as bits (``_packed_set``: the
    reference follows both, ``benchmarks/reference_glm_dsa.py``) and, among its
    counters, over the real rows and the layers, ``dsa_keys_selected`` of
    ``dsa_keys_in_context`` (float32), and over the layers
    ``dsa_select_passes`` in ``dsa_select_searches`` row tiles (int32).
    ``caches`` is donated."""
    del pages, page_rows, page_starts
    x = embed(params, tokens)
    row_tables, dst, slots = wave_index(
        positions, row_of, block_tables, max_blocks, config.block_tokens
    )
    real = real_rows(positions, row_of)
    new_caches: Caches = []
    tally, sets = ExpertTally(), []
    selected, passes, searches = jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32), 0
    for layer, (latent, index) in enumerate(caches):
        w = layer_weights(params, layer)
        x, latent, index, bias, made = _wave_mixer(
            w, x, latent, index, dst, slots, row_tables, positions, config
        )
        passes, searches = passes + jnp.sum(made), searches + made.shape[0]
        kept = jnp.sum(bias == 0.0, axis=(0, 2), dtype=jnp.float32)
        selected = selected + jnp.sum(jnp.where(real, kept, 0.0))
        sets.append(_packed_set(bias, config.experts_per_token))
        x, ids, n = mlp(w, x, layer < config.n_dense_layers, config)
        tally.add(ids, n)
        new_caches.append((latent, index))
    logits = head(params, x, config)
    aux = tally.aux(real, config.experts_per_token)
    aux["rows"] = jnp.concatenate([aux["rows"]] + sets, axis=1)
    aux["counters"]["dsa_keys_selected"] = selected
    aux["counters"]["dsa_keys_in_context"] = (
        jnp.sum(jnp.where(real, positions + 1, 0), dtype=jnp.float32) * config.n_layers
    )
    aux["counters"]["dsa_select_passes"] = passes
    aux["counters"]["dsa_select_searches"] = jnp.int32(searches)
    return logits, new_caches, aux
