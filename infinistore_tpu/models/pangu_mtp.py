"""A decoder of the ``pangu_ultra_moe`` family on the paged serving path:
latent attention (MLA) with a query latent and a rotated shared key in EVERY
layer, attended whole; sandwich norms; leading dense layers, then expert
layers; and ONE multi-token-prediction (MTP) layer behind the stack that
drafts, on the device, the token after the one a wave row samples.

``benchmarks/reference_pangu_mtp.py`` writes the same equations out in plain
float32. ``R`` rotates INTERLEAVED pairs ``(2i, 2i + 1)`` by ``pos x theta ^
(-2i / rope)``, unscaled (``layers.rotate_pairs``):

  layer   : h = x + rms(mixer(rms(x; w_in)); w_post_attn)
            y = h + rms(mlp(rms(h; w_pre_mlp)); w_post_mlp)
  query   : c_q = rms(W_qa n; w_qn);  q = W_qb c_q = [q_n | q_r] a head;  q_r <- R(q_r)
  cache   : [c | k_r] = W_kva n;  c <- rms(c; w_kvn);  k_r <- R(k_r), one for all heads
            [k_n | v] = W_kvb c a head
  mixer   : Wo softmax over s <= t of (q_n . k_n[s] + q_r . k_r[s]) / sqrt(nope + rope) v[s]
  dense   : Wdown (silu(Wgate m) * Wup m)
  expert  : ``tpu/moe.py`` ``expert_layer`` (sigmoid scores, top-k, route_scale x s_e /
            sum of the chosen, a shared expert, the held share)
  logits  = Whead rms(y_L; w_final)
  MTP     : u_i = W_eh [rms(E[x_{i+1}]; w_e) ; rms(y_L[i]; w_h)]        (2 dim -> dim)
            z   = Layer(u), one more layer of the expert kind with its OWN latent
                  cache, causal over 0..i, rotated at i
            draft logits = Whead rms(z_i; w_f), the main model's embedding and head;
            their argmax is the draft for x_{i+2}

The cache (``kv_spec``): ``n_layers + 1`` layers of ``tpu/mla.py``'s latent
``[blocks, rank + rope, block_tokens]``, the last the MTP layer's, which also
keeps the BOUNDARY row ``[blocks, dim / 128, 128]`` (folded to 128 lanes, as
the siblings' tails: the block copies take a block whose last two axes are
whole): the main stack's last hidden row at each block's last position. The MTP layer's slot i is a function of token i +
1, so the last slot of a block depends on a token the block's chain of hashes
does not cover: a hit that ends at that block and goes on with another
question rewrites that ONE slot, from the boundary row the hit fetched (the
trailing block's alone: ``last_blocks`` 1, kind ``"state"`` but no recurrence)
and the question's first token. ``resume_chunk`` does so for every piece that
starts a block, a miss's too, so that a hit's slot is a miss's to the bit.

Nothing here is a recurrent state, but ``resume_chunk`` lies inside one block
(``ServingSteps.resume_in_block``). Its MTP part writes slots only (a latent is
a function of its own row: no attention, no experts, no head in a prompt); the
piece's last slot takes ``next_token``, the next piece's first token or the
prompt's last. The wave is ONE body, ``verify_step_ragged``: the main stack,
the id each row samples, then the MTP module on the rows' last hidden state
and those ids (under the scope ``mtp_draft``), which writes its slots and the
boundary rows and returns a draft a row (``aux["drafts"]``).

The serving entries keep the names the trace readers match: ``prefill``
(``serving.prefill_by_blocks`` over ``resume_chunk``), ``resume_chunk`` and
``verify_step_ragged``; each donates ``caches``.
"""

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..tpu import mla
from ..tpu.moe import _swiglu, expert_layer
from ..tpu.paged import CacheTensor, PagedKVCacheSpec
from .layers import choices  # re-exported: this file's ``program.choices`` (benchmarks/configs/)
from .layers import embed, head, layer_weights, rms, rotate_pairs
from .serving import (
    ExpertTally, ServingSteps, chunk_index, prefill_by_blocks, real_rows, resume_step, wave_index,
)

Params = Dict[str, jax.Array]
Caches = List[Tuple[jax.Array, ...]]


@dataclass(frozen=True)
class PanguMtpConfig:
    vocab: int = 512
    dim: int = 64
    n_layers: int = 3  # the main stack; the MTP layer is one more
    n_heads: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    ffn_dim: int = 128  # the leading dense layers' width
    moe_ffn_dim: int = 32
    n_experts: int = 8  # the router's width
    experts_per_token: int = 2
    n_shared_experts: int = 1
    n_dense_layers: int = 1
    route_scale: float = 2.5
    route_norm: bool = True
    rms_eps: float = 1e-5
    rope_theta: float = 25.6e6
    block_tokens: int = 8
    dtype: jnp.dtype = jnp.bfloat16
    # (first, count) of the expert axis this instance computes; None: all.
    experts_held: Optional[Tuple[int, int]] = None
    # Published keys this file writes ONE form of: a configuration that says
    # otherwise is refused, not run as something else.
    mtp_layers: int = 1
    sandwich_norm: bool = True

    def __post_init__(self):
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", tuple(self.experts_held))
        if self.mtp_layers != 1 or not self.sandwich_norm:
            raise ValueError(
                f"num_nextn_predict_layers {self.mtp_layers}, sandwich_norm {self.sandwich_norm}: "
                f"one MTP layer behind sandwich-normed layers is what is written"
            )
        if self.qk_rope_head_dim % 2 or self.experts_per_token < 2:
            raise ValueError("the rotated part is whole pairs, and a choice site holds two ids")

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
        """The discrete-choice sites a wave row reports, in order: the main
        stack's expert layers, the MTP layer's router, the (committed,
        drafted) pair."""
        return self.n_layers - self.n_dense_layers + 2

    def layer_cache(self, layer: int) -> Tuple[CacheTensor, ...]:
        latent = CacheTensor(
            "latent", (self.latent_width, self.block_tokens), self.dtype, None, "latent"
        )
        if layer < self.n_layers:
            return (latent,)
        return (
            latent,
            CacheTensor("boundary", self.boundary_shape, self.dtype, 1, "state", recurrent=False),
        )

    @property
    def boundary_shape(self) -> Tuple[int, int]:
        """A hidden row as the cache keeps it: folded to 128 lanes where they divide."""
        lanes = 128 if self.dim % 128 == 0 else self.dim
        return (self.dim // lanes, lanes)

    def kv_spec(self, num_blocks: int) -> PagedKVCacheSpec:
        return PagedKVCacheSpec.of_layers(
            num_blocks, self.block_tokens,
            [self.layer_cache(layer) for layer in range(self.n_layers + 1)],
        )

    @property
    def steps(self) -> ServingSteps:
        return ServingSteps(
            prefill, prefill_continue, verify_step_ragged, resume_in_block=True, drafts=True
        )

    # What the wave body counts and returns (serving.py): the expert layers'
    # three, the MTP layer's among them.
    step_counters = ExpertTally.counters
    router = "sigmoid"  # ``moe.route``'s kind


def init_params(config: PanguMtpConfig, key: jax.Array) -> Params:
    """Seeded 1/sqrt(fan_in) normal weights as a flat dict (layer-prefixed
    keys; the MTP module's layer is ``l{n_layers}.`` and its own four
    ``mtp.``), the embedding's rows 1 / sqrt(vocab) as the siblings', norms at
    one, the router's selection bias at zero (the family has none: the shared
    router adds nought). The held experts only where the instance holds a
    share."""
    keys = iter(jax.random.split(key, 8 + 12 * (config.n_layers + 1)))
    _, count = config.held
    f32 = jnp.float32

    def dense(k, shape, fan_in):
        w = jax.random.normal(k, shape, dtype=f32) / np.sqrt(fan_in)
        return w.astype(config.dtype)

    ones = lambda n: jnp.ones((n,), dtype=config.dtype)
    d, f, h = config.dim, config.moe_ffn_dim, config.n_heads
    qr, r = config.q_lora_rank, config.kv_lora_rank
    nope, rope, v = config.qk_nope_head_dim, config.qk_rope_head_dim, config.v_head_dim
    p: Params = {
        "embed": dense(next(keys), (config.vocab, d), config.vocab),
        "final_norm": ones(d),
        "lm_head": dense(next(keys), (d, config.vocab), d),
        "mtp.e_norm": ones(d), "mtp.h_norm": ones(d), "mtp.final_norm": ones(d),
        "mtp.w_eh": dense(next(keys), (2 * d, d), 2 * d),
    }
    for layer in range(config.n_layers + 1):
        pre = f"l{layer}."
        for norm in ("in_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm"):
            p[pre + norm] = ones(d)
        p[pre + "w_qa"] = dense(next(keys), (d, qr), d)
        p[pre + "q_norm"] = ones(qr)
        p[pre + "w_qb"] = dense(next(keys), (qr, h, nope + rope), qr)
        p[pre + "w_kva"] = dense(next(keys), (d, r + rope), d)
        p[pre + "kv_norm"] = ones(r)
        p[pre + "w_kvb"] = dense(next(keys), (r, h, nope + v), r)
        p[pre + "wo"] = dense(next(keys), (h * v, d), h * v)
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
# A layer's halves.
# ---------------------------------------------------------------------------


def _mixer_inputs(w: Params, x, positions, config: PanguMtpConfig):
    """x: [T, dim] float32, the stream. Returns q [T, H, nope + rope]
    (rotated) and the latent cache's row [T, rank + rope]: the normed latent
    beside the rotated shared key. q and k_r stay in float32 from the
    projection's accumulator through norm and rotation and are rounded ONCE."""
    dt, r = config.dtype, config.kv_lora_rank
    n = rms(x, w["in_norm"], config.rms_eps, dt)
    c_q = rms(mla.einsum_f32("td,dr->tr", n, w["w_qa"]), w["q_norm"], config.rms_eps, dt)
    q = mla.einsum_f32("tr,rhk->thk", c_q, w["w_qb"])
    kva = mla.einsum_f32("td,dr->tr", n, w["w_kva"])
    c = rms(kva[:, :r], w["kv_norm"], config.rms_eps, dt)
    with jax.named_scope("mla_rope"):
        q = rotate_pairs(q, positions, config.qk_nope_head_dim, config, dt)
        k_r = rotate_pairs(kva[:, r:], positions, 0, config, dt)
    return q, jnp.concatenate([c, k_r], axis=-1)


def _scale(config: PanguMtpConfig) -> float:
    return float((config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5)


def _mixer_out(w: Params, x, attn, config: PanguMtpConfig):
    """attn: [T, H, v] float32. The branch normed to the stream's own size."""
    a = attn.astype(config.dtype).reshape(x.shape[0], -1)
    o = mla.einsum_f32("tk,kd->td", a, w["wo"])
    return x + rms(o, w["post_attn_norm"], config.rms_eps, jnp.float32)


def _mlp(w: Params, x, dense: bool, config: PanguMtpConfig):
    """The second half of a layer on x: [T, dim] float32. Returns (x_next,
    ids [T, k] or None, the expert layer's counts or None)."""
    m = rms(x, w["pre_mlp_norm"], config.rms_eps, config.dtype)
    if dense:
        f, ids, counts = _swiglu(m[None], w["w_gate_up"], w["w_down"])[0], None, None
    else:
        f, ids, counts = expert_layer(w, m, config)
    return x + rms(f, w["post_mlp_norm"], config.rms_eps, jnp.float32), ids, counts


def _wave_layer(w: Params, x, latent, dst, slots, row_tables, positions, dense: bool,
                config: PanguMtpConfig):
    """One layer over a wave's rows: each writes its latent in place, then
    attends its whole context in the absorbed form (a chunk's later row reads
    the slot its earlier row just wrote)."""
    q, rows = _mixer_inputs(w, x, positions, config)
    # A row a slice, in place: a scatter by (block, slot) makes XLA re-lay the
    # whole cache out and back every wave. A wave's rows are few.
    for t in range(rows.shape[0]):
        latent = jax.lax.dynamic_update_slice(
            latent, rows[t][None, :, None], (dst[t], 0, slots[t])
        )
    nope, r = config.qk_nope_head_dim, config.kv_lora_rank
    q_abs = mla.einsum_f32("thd,rhd->thr", q[..., :nope], w["w_kvb"][..., :nope])
    q_lat = jnp.concatenate([q_abs.astype(q.dtype), q[..., nope:]], axis=-1)
    mix = mla.latent_decode_rows(
        q_lat, latent, row_tables, positions + 1, rank=r, scale=_scale(config)
    )
    attn = mla.einsum_f32("thr,rhd->thd", mix.astype(config.dtype), w["w_kvb"][..., nope:])
    x, ids, counts = _mlp(w, _mixer_out(w, x, attn, config), dense, config)
    return x, latent, ids, counts


def _mtp_input(params: Params, next_tokens, hidden, config: PanguMtpConfig):
    """u [T, dim] float32: the MTP layer's stream, of each position's NEXT
    token and the main stack's last hidden row there."""
    dt = config.dtype
    both = jnp.concatenate([
        rms(embed(params, next_tokens), params["mtp.e_norm"], config.rms_eps, dt),
        rms(hidden, params["mtp.h_norm"], config.rms_eps, dt),
    ], axis=-1)
    return mla.einsum_f32("tk,kd->td", both, params["mtp.w_eh"])


# ---------------------------------------------------------------------------
# The serving entries (serving.py). Each DONATES ``caches``.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("caches",))
def resume_chunk(
    params: Params, tokens, start_pos, caches: Caches, block_table, config: PanguMtpConfig,
    next_token=None,
) -> Tuple[jax.Array, Caches]:
    """ONE request's chunk at contiguous positions INSIDE ONE BLOCK (the
    caller cuts at block boundaries): a hit's question, and every piece of a
    miss's prefill. Each main layer writes the chunk's latents into the block
    and attends the table's pages; the MTP layer's slots are written from the
    rows' last hidden state and each row's NEXT token (the last row's is
    ``next_token``), the boundary row where the chunk completes its block, and,
    where the chunk STARTS a block behind another, that block's last slot over
    again from its boundary row and the chunk's first token. Returns (logits
    [S_c, vocab], caches); ``caches`` is donated."""
    if next_token is None:
        raise ValueError("a drafting model's chunk takes the token that follows it (next_token)")
    bt = config.block_tokens
    s = tokens.shape[0]
    block, before, fresh = chunk_index(tokens, start_pos, block_table, bt)
    positions = start_pos + jnp.arange(s, dtype=jnp.int32)
    at = (block, 0, start_pos % bt)
    x = embed(params, tokens)
    new_caches: Caches = []
    for layer in range(config.n_layers):
        (latent,) = caches[layer]
        w = layer_weights(params, layer)
        q, rows = _mixer_inputs(w, x, positions, config)
        # The chunk lies in one block: one slice written in place (a scatter
        # by index makes XLA re-lay the whole cache out, twice).
        latent = jax.lax.dynamic_update_slice(latent, rows.T[None], at)
        attn = mla.latent_chunk_attention(
            q, latent, block_table, start_pos, w["w_kvb"], rank=config.kv_lora_rank,
            nope=config.qk_nope_head_dim, scale=_scale(config),
        )
        x, _, _ = _mlp(w, _mixer_out(w, x, attn, config), layer < config.n_dense_layers, config)
        new_caches.append((latent,))
    with jax.named_scope("mtp_slots"):
        latent, boundary = caches[config.n_layers]
        # Row 0 of this pass is the slot BEFORE the chunk, from the boundary
        # row of the block behind (what a hit fetched, what a miss's last
        # piece left); rows 1.. are the chunk's own.
        behind = start_pos - 1
        kept = jax.lax.dynamic_index_in_dim(boundary, before, 0, keepdims=True)
        hidden = jnp.concatenate([kept.reshape(1, -1).astype(jnp.float32), x])
        nexts = jnp.concatenate([tokens, jnp.reshape(next_token, (1,)).astype(tokens.dtype)])
        u = _mtp_input(params, nexts, hidden, config)
        _, rows = _mixer_inputs(
            layer_weights(params, config.n_layers), u,
            jnp.concatenate([jnp.reshape(jnp.maximum(behind, 0), (1,)), positions]), config,
        )
        latent = jax.lax.dynamic_update_slice(latent, rows[1:].T[None], at)
        # The slot behind, where the chunk starts a block behind another; else
        # the chunk's own first slot over again (a write with no read of the
        # cache: a one-slot READ makes XLA lay the whole tensor out anew).
        rewrite = (start_pos % bt == 0) & ~fresh
        slot = jnp.where(rewrite, rows[0], rows[1])[None, :, None]
        latent = jax.lax.dynamic_update_slice(
            latent, slot,
            (jnp.where(rewrite, before, block), 0, jnp.where(rewrite, bt - 1, start_pos % bt)),
        )
        completes = start_pos % bt + s == bt
        last = jax.lax.dynamic_index_in_dim(boundary, block, 0, keepdims=True)
        mine = x[-1:].astype(boundary.dtype).reshape(last.shape)
        boundary = jax.lax.dynamic_update_index_in_dim(
            boundary, jnp.where(completes, mine, last), block, 0
        )
        new_caches.append((latent, boundary))
    return head(params, x, config), new_caches


prefill_continue = resume_step(resume_chunk)


prefill = prefill_by_blocks(resume_chunk, next_token=True)


@functools.partial(
    jax.jit, static_argnames=("config", "max_blocks"), donate_argnames=("caches",)
)
def verify_step_ragged(
    params: Params, tokens, positions, row_of, pages, page_rows, page_starts, caches: Caches,
    block_tables, config: PanguMtpConfig, max_blocks: int,
):
    """THE wave body (``serving.py``: ``wave``'s contract and argument order,
    of a model that drafts): the main stack on the wave's rows, the id each
    row samples, then the MTP module on the rows' last hidden state (before
    the final norm) and those ids. Writes every layer's slot at every row's
    position, the MTP layer's too, and the boundary row where a row stands at
    its block's last position. Returns ``(logits [T, vocab], caches, aux)``:
    ``serving.ExpertTally``'s ``aux`` over the main stack's expert layers and
    the MTP layer's, its ``rows`` closed by ``[ids[t], drafts[t], 0, ...]``,
    and ``aux["drafts"]`` [T] int32, row t's the token that would follow
    ``ids[t]``. ``caches`` is donated."""
    del pages, page_rows, page_starts
    bt, k = config.block_tokens, config.experts_per_token
    x = embed(params, tokens)
    row_tables, dst, slots = wave_index(positions, row_of, block_tables, max_blocks, bt)
    new_caches: Caches = []
    tally = ExpertTally()
    for layer in range(config.n_layers):
        (latent,) = caches[layer]
        x, latent, chosen, n = _wave_layer(
            layer_weights(params, layer), x, latent, dst, slots, row_tables, positions,
            layer < config.n_dense_layers, config,
        )
        tally.add(chosen, n)
        new_caches.append((latent,))
    logits = head(params, x, config)
    ids = jax.lax.argmax(logits, 1, jnp.int32)  # the wave program's own (serving.py)
    with jax.named_scope("mtp_draft"):
        latent, boundary = caches[config.n_layers]
        z, latent, chosen, n = _wave_layer(
            layer_weights(params, config.n_layers), _mtp_input(params, ids, x, config),
            latent, dst, slots, row_tables, positions, False, config,
        )
        tally.add(chosen, n)
        for t in range(ids.shape[0]):
            old = jax.lax.dynamic_index_in_dim(boundary, dst[t], 0, keepdims=True)
            row = jnp.where(slots[t] == bt - 1, x[t].astype(boundary.dtype).reshape(old.shape), old)
            boundary = jax.lax.dynamic_update_index_in_dim(boundary, row, dst[t], 0)
        new_caches.append((latent, boundary))
        z = rms(z, params["mtp.final_norm"], config.rms_eps, config.dtype)
        drafts = jax.lax.argmax(jnp.dot(z, params["lm_head"]), 1, jnp.int32)
    aux = tally.aux(real_rows(positions, row_of), k)
    pair = jnp.pad(jnp.stack([ids, drafts], axis=1), ((0, 0), (0, k - 2)))[:, None, :]
    aux["rows"] = jnp.concatenate([aux["rows"], pair], axis=1)
    aux["drafts"] = drafts
    return logits, new_caches, aux
