"""Small Llama-style transformer with a paged KV cache, in plain JAX.

TPU-idiomatic by construction: einsum everywhere (MXU), bfloat16 activations,
static shapes, GQA attention, RoPE, RMSNorm, SwiGLU. The KV cache uses the
paged layout of infinistore_tpu.tpu.paged ([num_blocks, block_tokens,
n_kv_heads, head_dim] per layer), so prefill output can be streamed to the
store with LayerwiseKVWriter and decode can resume from fetched blocks — the
role vLLM plays for the reference store.

Sharding conventions (used by __graft_entry__.dryrun_multichip and the
train_step): logical axes are ("dp", "tp"[, "ep"]) — batch over dp, attention
heads / ffn hidden over tp, experts over ep (n_experts > 0 switches the FFN
to a soft mixture-of-experts whose expert-major weight tensors shard over the
ep axis; XLA computes local experts and inserts the combine collective), with
sequence-sharded activations where XLA chooses.
"""

import functools
from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..tpu.chunk_attention import chunk_prefix_attention
from ..tpu.flash_prefill import flash_prefill_attention
from ..tpu.paged import PagedKVCacheSpec, scatter_blocks
from ..tpu.paged_attention import (
    paged_decode_attention_rows,
    rectangle_as_ragged,
)
from .layers import rope
from .serving import ServingSteps, resume_step, wave_index

Params = Dict[str, jax.Array]
Caches = List[Tuple[jax.Array, jax.Array]]


@dataclass(frozen=True)
class LlamaConfig:
    vocab: int = 512
    dim: int = 128
    n_layers: int = 2
    n_heads: int = 8
    n_kv_heads: int = 4
    ffn_dim: int = 256
    # > 0 switches every FFN to a soft mixture of experts: expert-major
    # weights [n_experts, ...] shard over an "ep" mesh axis (expert
    # parallelism); a router picks per-token gates and the combine reduces
    # across experts (psum over ep under jit).
    n_experts: int = 0
    block_tokens: int = 8
    rope_theta: float = 10000.0
    dtype: jnp.dtype = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def steps(self) -> ServingSteps:
        """This file's serving steps, by role (serving.py): what the engine
        runs for a configuration of this class."""
        return ServingSteps(prefill, prefill_continue, verify_step_ragged)

    def kv_spec(self, num_blocks: int) -> PagedKVCacheSpec:
        """Paged-KV cache spec matching this model's layers/heads/dtype."""
        return PagedKVCacheSpec(
            num_layers=self.n_layers,
            num_blocks=num_blocks,
            block_tokens=self.block_tokens,
            num_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            dtype=self.dtype,
        )


def init_params(config: LlamaConfig, key: jax.Array) -> Params:
    """He-scaled dense params as a flat dict (layer-prefixed keys)."""
    keys = iter(jax.random.split(key, 4 + 8 * config.n_layers))

    def dense(k, shape):
        scale = 1.0 / np.sqrt(shape[0])
        return (jax.random.normal(k, shape, dtype=jnp.float32) * scale).astype(
            config.dtype
        )

    p: Params = {
        "embed": dense(next(keys), (config.vocab, config.dim)),
        "final_norm": jnp.ones((config.dim,), dtype=config.dtype),
        "lm_head": dense(next(keys), (config.dim, config.vocab)),
    }
    hd = config.head_dim
    for layer in range(config.n_layers):
        pre = f"l{layer}."
        p[pre + "attn_norm"] = jnp.ones((config.dim,), dtype=config.dtype)
        p[pre + "wq"] = dense(next(keys), (config.dim, config.n_heads, hd))
        p[pre + "wk"] = dense(next(keys), (config.dim, config.n_kv_heads, hd))
        p[pre + "wv"] = dense(next(keys), (config.dim, config.n_kv_heads, hd))
        p[pre + "wo"] = dense(next(keys), (config.n_heads, hd, config.dim))
        p[pre + "ffn_norm"] = jnp.ones((config.dim,), dtype=config.dtype)
        if config.n_experts > 0:
            p[pre + "router"] = dense(next(keys), (config.dim, config.n_experts))
            p[pre + "w_gate_up_moe"] = dense(
                next(keys), (config.n_experts, config.dim, 2, config.ffn_dim)
            )
            p[pre + "w_down_moe"] = dense(
                next(keys), (config.n_experts, config.ffn_dim, config.dim)
            )
        else:
            p[pre + "w_gate_up"] = dense(next(keys), (config.dim, 2, config.ffn_dim))
            p[pre + "w_down"] = dense(next(keys), (config.ffn_dim, config.dim))
    return p


def _rms_norm(x: jax.Array, w: jax.Array) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + 1e-6).astype(x.dtype)) * w


def _attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, T, KVH, D]
    v: jax.Array,  # [B, T, KVH, D]
    mask: jax.Array,  # [B, S, T] True = attend
) -> jax.Array:
    """Dense attention with the framework-wide numeric contract: logits and
    softmax statistics in float32 (preferred_element_type keeps the MXU's
    native f32 accumulation for bf16 operands; HIGHEST stops XLA from
    running f32 operands in reduced-precision passes), output cast back to
    the query dtype. The fused paged decode kernel
    (tpu/paged_attention.py) and the ring/Ulysses paths follow the same
    contract, so every attention implementation agrees to float32 rounding
    on every backend."""
    groups = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = (
        jnp.einsum(
            "bshd,bthd->bhst",
            q,
            k,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        * scale
    )
    logits = jnp.where(mask[:, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhst,bthd->bshd",
        probs,
        v.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    return out.astype(q.dtype)


def _block(params: Params, layer: int, x, k, v, q_positions, mask, config):
    """Shared transformer block math given already-materialized K/V context.

    x: [B, S, dim]; k/v: [B, T, KVH, D] (full attention context). ``mask``
    is [B, S, T] (True = attend), or None for plain causal — the None form
    routes through the flash prefill kernel on TPU (no S x T logits
    materialized; forward-only, so training losses pass an explicit mask
    and keep the differentiable dense path)."""
    pre = f"l{layer}."
    q = _q_proj(params, layer, x, q_positions, config)
    if mask is None:
        attn = flash_prefill_attention(q, k, v, causal=True)
    else:
        attn = _attention(q, k, v, mask)
    x = x + jnp.einsum("bshk,hkd->bsd", attn, params[pre + "wo"])
    return _ffn(params, layer, x, config)


# Rows a ONE-row dense FFN runs on (``_ffn``): the fewest at which the chip
# reads w_gate_up as fast as it does for any wave of several rows. 2, 8 and 16
# read alike (tools/ffn_rows_probe.py on a v5e: 0.24 ms a layer at ffn 11008
# against 0.68 for the one-row product; PERF.md section 6, PR 45).
ONE_ROW_FFN_ROWS = 2


def _ffn(params: Params, layer: int, x, config):
    """FFN half of the block (dense or soft-MoE), shared by the dense path
    and the fused-decode path."""
    pre = f"l{layer}."
    h = _rms_norm(x, params[pre + "ffn_norm"])
    if config.n_experts > 0:
        # Soft MoE, expert-major: every einsum keeps the expert axis e
        # outermost so weights sharded P("ep", ...) compute their local
        # experts and XLA reduces the combine across the ep axis. Dense
        # (all tokens x all experts) by design — compiler-friendly static
        # shapes; top-k routing sparsity is a serving optimization, not
        # needed to exercise the parallelism.
        gates = jax.nn.softmax(
            jnp.einsum("bsd,de->bse", h, params[pre + "router"]).astype(jnp.float32),
            axis=-1,
        ).astype(h.dtype)
        gate_up = jnp.einsum("bsd,edcf->bsecf", h, params[pre + "w_gate_up_moe"])
        ffn = jax.nn.silu(gate_up[:, :, :, 0]) * gate_up[:, :, :, 1]  # [B,S,E,F]
        out = jnp.einsum("bse,bsef,efd->bsd", gates, ffn, params[pre + "w_down_moe"])
        return x + out
    if h.shape[0] * h.shape[1] == 1:
        # ONE row (a lone request's decode wave): XLA lowers a one-row
        # product to a multiply-and-reduce on the vector unit, which reads
        # w_gate_up [dim, 2, ffn] at a third (ffn 11008) to two thirds
        # (14336) of the rate the matrix-unit fusion of a two-row wave reads
        # the same buffer at. So the row rides with zero rows beside it and
        # row 0 is kept: the same operands, the same float32 accumulation,
        # the weights as they lie (tools/ffn_rows_probe.py chose the count;
        # docs/design.md, "A one-row wave's FFN"). Zeros, not a broadcast of
        # the row: nothing here folds back into a one-row product.
        h = jnp.pad(h, ((0, 0), (0, ONE_ROW_FFN_ROWS - 1), (0, 0)))
    gate_up = jnp.einsum("bsd,dcf->bscf", h, params[pre + "w_gate_up"])
    ffn = jax.nn.silu(gate_up[:, :, 0]) * gate_up[:, :, 1]
    out = jnp.einsum("bsf,fd->bsd", ffn, params[pre + "w_down"])
    return x + out[:, : x.shape[1]]


def _q_proj(params: Params, layer: int, x, positions, config):
    pre = f"l{layer}."
    h = _rms_norm(x, params[pre + "attn_norm"])
    q = jnp.einsum("bsd,dhk->bshk", h, params[pre + "wq"])
    return rope(q, positions, config.rope_theta)


def _kv_proj(params: Params, layer: int, x, positions, config):
    pre = f"l{layer}."
    h = _rms_norm(x, params[pre + "attn_norm"])
    k = jnp.einsum("bsd,dhk->bshk", h, params[pre + "wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, params[pre + "wv"])
    k = rope(k, positions, config.rope_theta)
    return k, v


# ---------------------------------------------------------------------------
# Paged-cache inference. The serving entries are ``prefill`` (a miss),
# ``resume_chunk`` (a prefix hit's chunk) and ``verify_step_ragged`` (a decode
# wave); ``decode_step``, ``prefill_continue`` and ``speculative_verify`` are
# views over them. The cache is shared across sequences via the block
# tables, exactly the paged-attention model the store serves.
#
# Every jitted entry here DONATES ``caches``: XLA aliases each layer's K and V
# output to its input and rewrites the touched slots in place, where an
# undonated cache is copied whole, every layer, every step. The rule for a
# caller is the installs' (tpu/paged.py): the arrays handed in are deleted by
# the call, use the returned ones; hand a copy (``jax.tree.map(jnp.copy,
# caches)``) to keep the input. A donation declared on an inner jit is ignored
# under an outer trace, so a caller that wraps an entry in a jit of its own
# declares it again (``decode_step`` does).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("caches",))
def prefill(
    params: Params,
    tokens: jax.Array,  # [S] int32, S % block_tokens == 0
    caches: Caches,  # per layer (K, V) paged arrays
    block_table: jax.Array,  # [S // block_tokens] int32 cache block ids
    config: LlamaConfig,
) -> Tuple[jax.Array, Caches]:
    """Full prompt pass; writes K/V into the paged cache blocks listed in
    block_table. Returns (last-token logits, updated caches).

    ``caches`` is donated (updated in place, the input arrays deleted). A
    call that raises after dispatch leaves the caller without a cache, as a
    failed install does (connector.py): there is no recovery here."""
    s = tokens.shape[0]
    bt = config.block_tokens
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    x = jnp.take(params["embed"], tokens, axis=0)[None]  # [1, S, dim]
    mask = None  # plain causal -> flash prefill kernel on TPU (_block)

    new_caches: Caches = []
    for layer, (k_cache, v_cache) in enumerate(caches):
        k, v = _kv_proj(params, layer, x, positions, config)
        x = _block(params, layer, x, k, v, positions, mask, config)
        # Scatter this prompt's K/V into its cache blocks.
        k_blocks = k[0].reshape(s // bt, bt, config.n_kv_heads, config.head_dim)
        v_blocks = v[0].reshape(s // bt, bt, config.n_kv_heads, config.head_dim)
        new_caches.append(
            (
                scatter_blocks(k_cache, block_table, k_blocks),
                scatter_blocks(v_cache, block_table, v_blocks),
            )
        )
    x = _rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits[0, -1], new_caches


@functools.partial(
    jax.jit, static_argnames=("config", "max_blocks"), donate_argnames=("caches",)
)
def decode_step(
    params: Params,
    token: jax.Array,  # [] int32
    position: jax.Array,  # [] int32 absolute position of `token`
    caches: Caches,
    block_table: jax.Array,  # [max_blocks] int32 (padded with any valid id)
    config: LlamaConfig,
    max_blocks: int,
) -> Tuple[jax.Array, Caches]:
    """One decode token against the paged cache: append this token's K/V into
    its block slot, then fused paged attention over the context blocks
    (tpu/paged_attention.py: on TPU each context block crosses HBM exactly
    once — no materialized gather; gather+dense XLA elsewhere, same f32
    softmax contract). ``max_blocks`` must equal the padded block_table
    length (validated at trace time — a mismatch fails loudly). Returns
    (logits, caches).

    The one-row, one-token view of ``verify_step_ragged`` — one decode body
    to maintain. The table rides the wave as a rectangle of one row
    (``rectangle_as_ragged``): entries past the sequence are not walked.
    ``caches`` is donated, declared here again because the wave body's own
    declaration is ignored under this outer trace."""
    if block_table.shape[0] != max_blocks:
        raise ValueError(
            f"block_table has {block_table.shape[0]} entries, expected "
            f"max_blocks={max_blocks} (pad the table to the static bound)"
        )
    block_tables = block_table[None]
    logits, new_caches = verify_step_ragged(
        params,
        token[None],
        position[None],
        jnp.zeros((1,), jnp.int32),
        *rectangle_as_ragged(block_tables),
        caches,
        block_tables,
        config,
        max_blocks,
    )
    return logits[0], new_caches


def _layer_weights(params: Params, layer: int) -> Params:
    """Layer ``layer``'s weights under layer 0's names: the one pytree every
    layer hands :func:`_wave_layer`, so that one trace serves them all."""
    pre = f"l{layer}."
    return {"l0." + k[len(pre):]: w for k, w in params.items() if k.startswith(pre)}


def _wave_layer(
    weights: Params,  # ONE layer's weights, named as layer 0's (_layer_weights)
    x: jax.Array,  # [1, T, dim] activations entering the layer
    positions: jax.Array,  # [1, T] int32
    k_cache: jax.Array,  # this LAYER's paged K array
    v_cache: jax.Array,
    block_idx: jax.Array,  # [T] cache block of each flat token's own K/V
    slots: jax.Array,  # [T] slot within that block
    row_tables: jax.Array,  # [T, max_blocks]
    seq_lens: jax.Array,  # [T]
    pages: jax.Array,
    page_rows: jax.Array,
    page_starts: jax.Array,
    config: LlamaConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """ONE layer of the paged wave body on T flat rows: insert the rows' K/V,
    attend each row's pages through the one dispatcher, residual + FFN.
    Returns ``(x_next, k_cache, v_cache)``. ``verify_step_ragged`` runs it a
    layer under one ``jax.jit`` of its own, so the layers share one traced
    and one lowered function (every wave bucket a run warms traces and
    lowers its program again: PERF.md, PR 31); the disagg
    ``decode_wave_layer`` is this function on a rectangle."""
    k, v = _kv_proj(weights, 0, x, positions, config)  # [1, T, KVH, D]
    k_cache = k_cache.at[block_idx, slots].set(k[0].astype(k_cache.dtype))
    v_cache = v_cache.at[block_idx, slots].set(v[0].astype(v_cache.dtype))
    q = _q_proj(weights, 0, x, positions, config)  # [1, T, H, D]
    attn = paged_decode_attention_rows(
        q[0], k_cache, v_cache, row_tables, seq_lens,
        pages, page_rows, page_starts,
    )[None]  # [1, T, H, D]
    x = x + jnp.einsum("bshk,hkd->bsd", attn, weights["l0.wo"])
    return _ffn(weights, 0, x, config), k_cache, v_cache


@functools.partial(
    jax.jit, static_argnames=("config", "max_blocks"), donate_argnames=("caches",)
)
def verify_step_ragged(
    params: Params,
    tokens: jax.Array,  # [T] int32, the wave's chunks CONCATENATED row-major
    positions: jax.Array,  # [T] int32 absolute position of each flat token
    row_of: jax.Array,  # [T] int32 owning request per flat token (sorted)
    pages: jax.Array,  # [P] int32 flat attention page list (RaggedWaveMeta)
    page_rows: jax.Array,  # [P + 1] int32 owning flat token per page
    page_starts: jax.Array,  # [T] int32 first page per flat token
    caches: Caches,  # SHARED paged cache across the wave
    block_tables: jax.Array,  # [B, max_blocks] int32 (rows padded)
    config: LlamaConfig,
    max_blocks: int,
) -> Tuple[jax.Array, Caches]:
    """THE paged-inference wave body: a wave of requests, each advancing a
    chunk of its OWN length against the shared cache, in one launch per
    layer. The wave is one flat [T] token list (T = sum of chunk lengths)
    with per-token request/page metadata — no [B, K] rectangle padded to
    the widest chunk. The serving engine's decode waves run this; one
    decode token (``decode_step``) is its one-row view, and the disagg
    decode layer (``decode_wave_layer``) is one layer of the same math.
    A prefix hit's chunk is a program of its own (``resume_chunk``), which
    reads the request's pages once for all its rows.

    Each flat token inserts its K/V at (table[pos // bt], pos % bt), then
    one attention launch covers all T rows, each masked to its own
    position + 1 (tpu/paged_attention.py paged_decode_attention_rows; on
    TPU the ragged kernel walks the flat page list: sum(ceil((pos_t + 1) /
    bt)) page reads, no padding to the wave max). Requests own disjoint
    blocks (the engine's block-table manager guarantees it). Tail-bucket
    padding repeats the LAST flat row: a same-bytes scatter, value-safe.
    Rows may attend sibling rows' K/V within a chunk: inserts complete
    before attention, and per-row masking keeps causality.

    A mixed ragged wave equals per-request sequential decode on the cache
    and the logits to float32 rounding on a float32 model (the tolerance
    the engine and kernel tests write down; XLA is free to fuse a wave and
    a single row differently, so no bitwise claim across batch shapes).
    ``block_tables`` rows beyond the real requests (bucket padding) are
    never referenced by any flat token: a padded WAVE ROW neither scatters
    nor attends, it is simply absent. Returns ([T, vocab] logits, updated
    caches).

    ``caches`` is donated: every layer's scatter lands in the input's own
    buffers (the per-layer ``jax.jit`` below needs no donation of its own:
    XLA updates in place across that call once the entry's parameter may
    be aliased) and the input arrays are deleted. A call that raises after
    dispatch leaves the caller without a cache, as a failed install does
    (connector.py): there is no recovery here."""
    t = tokens.shape[0]
    if positions.shape != (t,) or row_of.shape != (t,):
        raise ValueError(
            f"positions/row_of must match tokens' [{t}], got "
            f"{positions.shape}/{row_of.shape}"
        )
    if page_starts.shape != (t,):
        raise ValueError(f"page_starts must be [{t}], got {page_starts.shape}")
    x = jnp.take(params["embed"], tokens, axis=0)[None]  # [1, T, dim]
    pos2d = positions[None]  # [1, T]
    row_tables, block_idx, slots = wave_index(
        positions, row_of, block_tables, max_blocks, config.block_tokens
    )
    seq_lens = positions + 1

    # One jit for this trace alone: the layers share its traced and lowered
    # function, and nothing outlives the trace.
    layer_fn = jax.jit(_wave_layer, static_argnames=("config",))
    new_caches: Caches = []
    for layer, (k_cache, v_cache) in enumerate(caches):
        x, k_cache, v_cache = layer_fn(
            _layer_weights(params, layer), x, pos2d, k_cache, v_cache,
            block_idx, slots, row_tables, seq_lens, pages, page_rows, page_starts,
            config=config,
        )
        new_caches.append((k_cache, v_cache))
    x = _rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits[0], new_caches


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("caches",))
def resume_chunk(
    params: Params,
    tokens: jax.Array,  # [S_c] int32, the suffix chunk
    start_pos: jax.Array,  # [] int32, absolute position of tokens[0]
    caches: Caches,
    block_table: jax.Array,  # [max_blocks] int32 (padded)
    config: LlamaConfig,
) -> Tuple[jax.Array, Caches]:
    """The program behind ``prefill_continue``: ONE request's chunk at
    contiguous positions. Each layer inserts the chunk's K/V at
    (table[pos // bt], pos % bt) and then attends the request's pages in
    place, once for the whole chunk, row r masked to ``start_pos + r + 1``
    (tpu/chunk_attention.py). A program of its own: a decode wave's rows
    belong to different requests and want a page walk each
    (``verify_step_ragged``), a miss has no pages yet (``prefill``), and a
    chunk of one request wants one walk for all its rows. The compile key
    is the chunk's length and the table's (``max_blocks``).

    ``caches`` is donated (updated in place, the input arrays deleted). A
    call that raises after dispatch leaves the caller without a cache, as a
    failed install does (connector.py): there is no recovery here."""
    s_c = tokens.shape[0]
    bt = config.block_tokens
    positions = start_pos + jnp.arange(s_c, dtype=jnp.int32)
    pos2d = positions[None]  # [1, S_c]
    x = jnp.take(params["embed"], tokens, axis=0)[None]  # [1, S_c, dim]
    block_idx = jnp.take(block_table, positions // bt)
    slots = positions % bt

    new_caches: Caches = []
    for layer, (k_cache, v_cache) in enumerate(caches):
        k, v = _kv_proj(params, layer, x, pos2d, config)  # [1, S_c, KVH, D]
        k_cache = k_cache.at[block_idx, slots].set(k[0].astype(k_cache.dtype))
        v_cache = v_cache.at[block_idx, slots].set(v[0].astype(v_cache.dtype))
        pre = f"l{layer}."
        q = _q_proj(params, layer, x, pos2d, config)  # [1, S_c, H, D]
        attn = chunk_prefix_attention(
            q[0], k_cache, v_cache, block_table, start_pos
        )[None]
        x = x + jnp.einsum("bshk,hkd->bsd", attn, params[pre + "wo"])
        x = _ffn(params, layer, x, config)
        new_caches.append((k_cache, v_cache))
    x = _rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits[0], new_caches


# Chunked continuation prefill (the harness's resume step, ``serving.py``): a
# multi-token suffix against an already-populated paged prefix in ONE call per
# layer, vLLM's treatment of a prefix-cache hit. Token-by-token ``decode_step``
# costs S_c launches per layer and GEMV matmuls; ``resume_chunk`` inserts the
# whole chunk's K/V and attends all its rows in one kernel launch that reads
# each context page once, with chunk-wide GEMMs for the projections and FFN.
# Semantically equal to the decode loop (tested). What selects that program is
# this signature (one request, contiguous positions, a chunk); its compile key
# is the chunk's length and ``max_blocks``.
prefill_continue = resume_step(resume_chunk)


def speculative_verify(
    params: Params,
    draft,  # [D] int sequence/array of draft tokens (draft[0] already
    #         validated by the caller against its previous step's logits)
    start_pos,  # int, absolute position of draft[0]
    caches: Caches,
    block_table: jax.Array,  # [max_blocks] int32 (padded)
    config: LlamaConfig,
    max_blocks: int,
    pad_to: int = 0,
):
    """Score a whole speculative draft in ONE chunked pass and accept its
    longest greedy-consistent prefix.

    ``prefill_continue`` processes all D draft tokens at once (each row
    attends its own prefix); row i's argmax is the target model's next
    token after ``draft[:i+1]``, so ``draft[i+1]`` is accepted iff it
    equals that argmax. Returns ``(n_accepted, next_token, caches)`` where
    ``next_token`` is the target model's continuation after the accepted
    prefix — the token the engine emits alongside the accepted draft.

    Rollback is free by construction: rejected draft positions DID insert
    K/V into their slots, but every later decode masks attention by
    ``position + 1`` (tpu/paged_attention.py), so stale slots beyond the
    accepted point are never attended and are overwritten when real tokens
    reach those positions. The caller only rewinds its position counter.
    Cites the reference's cache-semantics stance (SURVEY.md §5.3): wrong
    speculation costs recompute, never correctness.

    ``pad_to``: prefill_continue is jitted, so every DISTINCT draft length
    recompiles. Engines with variable-length drafts pass a fixed
    ``pad_to`` >= D: the draft is padded (with its last token — the pad
    rows' K/V land beyond the accepted point and are masked/overwritten
    like any rejection) and acceptance is computed over the true D only,
    so one compiled shape serves every round."""
    draft_host = np.asarray(draft, dtype=np.int32)
    d = int(draft_host.shape[0])
    if d == 0:
        raise ValueError("speculative_verify needs a non-empty draft")
    span = pad_to or d
    if int(start_pos) + span > max_blocks * config.block_tokens:
        # jnp.take would CLIP out-of-table block indices and silently
        # overwrite the last block's slots — fail loudly instead.
        raise ValueError(
            f"draft span [{int(start_pos)}, {int(start_pos) + span}) exceeds "
            f"the table's {max_blocks * config.block_tokens}-token capacity"
        )
    if pad_to:
        if pad_to < d:
            raise ValueError(f"pad_to={pad_to} < draft length {d}")
        draft_host = np.concatenate(
            [draft_host, np.full(pad_to - d, draft_host[-1], np.int32)]
        )
    logits, caches = prefill_continue(
        params, jnp.asarray(draft_host), jnp.int32(start_pos), caches,
        block_table, config, max_blocks,
    )
    # ONE device->host transfer per round (the [D]-sized argmaxes; the
    # draft comparison side stays host-resident) — this runs every
    # speculation round on the decode hot path.
    preds = np.asarray(jnp.argmax(logits, axis=-1))  # preds[i] follows draft[:i+1]
    ok = preds[: d - 1] == draft_host[1:d]  # draft[i+1] consistent?
    n_accepted = 1 + int(np.argmin(ok)) if not ok.all() else d
    next_token = int(preds[n_accepted - 1])
    return n_accepted, next_token, caches


# ---------------------------------------------------------------------------
# Layerwise inference entry points (disaggregated prefill -> decode handoff,
# docs/disaggregation.md). The monolithic ``prefill``/``verify_step_ragged``
# bodies are re-expressed one layer per jitted call so a prefill engine can
# SHIP layer l's KV while layer l+1 computes, and a decode engine can gate
# each layer's attention on that layer's install alone (the watermark rule).
# Both handoff directions — streamed prefill and the fallback recompute —
# use THESE functions, and the watermarked and blocking decode paths share
# ``decode_wave_layer``: the same compiled program a layer on both paths,
# so "overlapped equals blocking" holds by construction regardless of how
# XLA fuses across the per-layer boundaries.
# ---------------------------------------------------------------------------


@jax.jit
def embed_prompt(params: Params, tokens: jax.Array) -> jax.Array:
    """[S] prompt tokens -> [1, S, dim] activations (the layerwise prefill
    chain's entry)."""
    return jnp.take(params["embed"], tokens, axis=0)[None]


@functools.partial(jax.jit, static_argnames=("config", "layer"))
def prefill_layer(
    params: Params,
    x: jax.Array,  # [1, S, dim] activations entering this layer
    k_cache: jax.Array,  # this LAYER's paged K array
    v_cache: jax.Array,  # this LAYER's paged V array
    block_table: jax.Array,  # [S // block_tokens] int32 cache block ids
    config: LlamaConfig,
    layer: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One layer of the whole-prompt prefill: project this layer's K/V,
    scatter them into the layer's cache blocks, and run the block. Returns
    ``(x_next, k_cache, v_cache)`` — ``x_next`` feeds ``layer + 1`` while
    the caller ships the freshly scattered K/V (the streaming overlap).
    Chaining layers 0..L-1 then ``lm_logits`` is semantically equal to
    ``prefill`` (same per-layer math, pinned by tests)."""
    s = x.shape[1]
    bt = config.block_tokens
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    k, v = _kv_proj(params, layer, x, positions, config)
    x = _block(params, layer, x, k, v, positions, None, config)
    k_blocks = k[0].reshape(s // bt, bt, config.n_kv_heads, config.head_dim)
    v_blocks = v[0].reshape(s // bt, bt, config.n_kv_heads, config.head_dim)
    return (
        x,
        scatter_blocks(k_cache, block_table, k_blocks),
        scatter_blocks(v_cache, block_table, v_blocks),
    )


@jax.jit
def lm_logits(params: Params, x: jax.Array) -> jax.Array:
    """Final norm + LM head over [B, S, dim] activations (the layerwise
    chains' exit; [B, S, vocab] logits)."""
    x = _rms_norm(x, params["final_norm"])
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"])


@jax.jit
def embed_wave(params: Params, tokens: jax.Array) -> jax.Array:
    """[B, K] wave tokens -> [B, K, dim] activations (the layerwise decode
    chain's entry)."""
    return jnp.take(params["embed"], tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("config", "layer", "max_blocks"))
def decode_wave_layer(
    params: Params,
    x: jax.Array,  # [B, K, dim] activations entering this layer
    positions: jax.Array,  # [B, K] int32 absolute positions
    k_cache: jax.Array,  # this LAYER's paged K array
    v_cache: jax.Array,  # this LAYER's paged V array
    block_tables: jax.Array,  # [B, max_blocks] int32 (rows padded)
    config: LlamaConfig,
    layer: int,
    max_blocks: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One layer of ``verify_step_ragged``'s wave body, for a RECTANGULAR
    wave (B requests, K tokens each): insert the wave's K/V at
    (table[pos // bt], pos % bt), paged attention over this layer's cache
    through the same dispatcher (the [B, K] rows ride it as a rectangle,
    ``rectangle_as_ragged``), residual + FFN. Returns ``(x_next, k_cache,
    v_cache)``. Chained over the layers between ``embed_wave`` and
    ``lm_logits`` it equals ``verify_step_ragged`` on the same wave to
    float32 rounding (tested).

    The watermark-gated decode admission (disagg.py) calls this only after
    THIS layer's prefix KV installed — layer l's attention never reads
    bytes still in flight — and the blocking fetch-all path chains the same
    function, so the two paths run the same programs on the same inputs."""
    bsz, kk = positions.shape
    if block_tables.shape != (bsz, max_blocks):
        raise ValueError(
            f"block_tables must be [{bsz}, {max_blocks}] (one padded row per "
            f"request), got {block_tables.shape}"
        )
    bt = config.block_tokens
    flat_pos = positions.reshape(-1)
    block_idx = jnp.take_along_axis(
        block_tables, positions // bt, axis=1
    ).reshape(-1)
    slots = flat_pos % bt
    row_tables = jnp.repeat(block_tables, kk, axis=0)
    x, k_cache, v_cache = _wave_layer(
        _layer_weights(params, layer), x.reshape(1, bsz * kk, -1),
        flat_pos[None], k_cache, v_cache, block_idx, slots, row_tables,
        flat_pos + 1, *rectangle_as_ragged(row_tables), config,
    )
    return x.reshape(bsz, kk, -1), k_cache, v_cache


# ---------------------------------------------------------------------------
# Training step (dense attention, no cache) — exercised by the multichip
# dryrun with dp/tp shardings.
# ---------------------------------------------------------------------------


def loss_fn(params: Params, tokens: jax.Array, config: LlamaConfig) -> jax.Array:
    """Next-token cross entropy over [B, S] token batches."""
    b, s = tokens.shape
    positions = jnp.arange(s, dtype=jnp.int32)[None].repeat(b, axis=0)
    x = jnp.take(params["embed"], tokens, axis=0)
    mask = positions[:, :, None] >= positions[:, None, :]
    for layer in range(config.n_layers):
        k, v = _kv_proj(params, layer, x, positions, config)
        x = _block(params, layer, x, k, v, positions, mask, config)
    x = _rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits[:, :-1])
    tgt = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return nll.mean()


@functools.partial(jax.jit, static_argnames=("config",), donate_argnums=(0,))
def train_step(
    params: Params, tokens: jax.Array, config: LlamaConfig, lr: float = 1e-3
) -> Tuple[Params, jax.Array]:
    """One SGD step on next-token loss; returns (new_params, loss). Shards
    follow the inputs (pjit-compatible: used by the multichip dryrun)."""
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, config)
    new_params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), params, grads)
    return new_params, loss
