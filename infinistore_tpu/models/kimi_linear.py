"""A decoder of the ``kimi_linear`` family on the paged serving path: linear
attention on a recurrent state (KDA) three layers in four, latent attention
(MLA) the fourth, an expert layer after the leading dense one.

The equations are the published modelling code's (arXiv:2510.26692);
``benchmarks/reference_kimi_linear.py`` writes the same ones out in plain
float32, the recurrence a token at a time. Pre-norm, no positional rotation:

  h       = x + mixer(rms(x; w_in))          y = h + mlp(rms(h; w_pre_mlp))
  KDA     : [q, k, v] = silu(conv4([Wq n, Wk n, Wv n]))   (causal, depth-wise)
            q <- l2norm(q) / sqrt(K)   k <- l2norm(k)            per head
            g = -exp(A_log) softplus(W_fb (W_fa n) + dt_bias)    per key channel
            beta = sigmoid(W_b n)                                per head
            S' = diag(exp(g)) S;  S = S' + beta k (v - S'^T k)^T;  o = S^T q
            mixer = Wo (rms_head(o; w_o) * sigmoid(W_gb (W_ga n)))
  MLA     : q = Wq n;  [c, k_r] = W_kva n;  c <- rms(c; w_kv)
            [k_n, v] = W_kvb c;  k = [k_n, k_r];  causal softmax(q k^T / sqrt(192)) v
            mixer = Wo a
  dense   : Wdown (silu(Wgate m) * Wup m)
  expert  : ``tpu/moe.py`` ``expert_layer``, the one code every routed family runs: sigmoid
            scores in float32, top-k of (s + b), route_scale x s_e / sum of the
            chosen, one shared expert added unweighted, the held share
            (``experts_held``) of the routed ones
  logits  = Whead rms(h_L; w_final)

The cache (``kv_spec``): per layer a tuple of named per-block tensors whose
leading axis is the block. A KDA layer's is its state ``[blocks, H, K, V]``
float32 and its convolution tail ``[blocks, 3, 3 H K]``: block b holds both as
they stand after b's last token, the RUNNING ones while b is a request's
last. The MLA layer's is ONE tensor, ``[blocks, rank + rope, block_tokens]``:
the normed latent and the shared key of every token, the token the minor
axis (``tpu/mla.py`` says why). A hit installs every
latent block and the LAST block's state and tail; every block saves all.

Where the configuration asks (``route_tail`` tokens, the last layer's third
tensor ``routes``: ``[blocks, route_tail x sites x k]`` int32, folded to 128
lanes) a block also keeps the expert ids its last ``route_tail`` tokens chose
at every expert layer, shifted on by every chunk and wave like the
convolution's tail, and a wave hands them back beside each row's own
(``aux["rows"]``: ``[T, sites x (1 + route_tail), k]``, the row's own sets
first, then the tokens' before it, the nearest first; -1 before a prompt's
start). A recurrent state is a sum over the context in which the last few
hundred tokens weigh most, so a row's logits depend on the DISCRETE choices
those tokens made, and a reference that is to be held to a few percent has to
follow them too (``benchmarks/reference_kimi_linear.py``; PERF.md, PR 41).

A token is absorbed into a state once, so nothing here may compute a
position twice: the engine lands a prompt's last token in the first wave
alone (``PagedKVCacheSpec.has_state``), a chunk lies inside one block, and a
wave's row reads its state from the block of position p - 1 and writes the
block of p (the two differ where the row crosses into a new block: the
running state moves on and the block behind keeps its end state; rows that
repeat their predecessor, a wave's padding, read and write the same bytes).

The three serving entries keep the names the trace readers match: ``prefill``
(a miss: ``serving.prefill_by_blocks``, the prompt cut at block boundaries
through ``resume_chunk``), ``resume_chunk`` and ``verify_step_ragged``; each
donates ``caches``.
"""

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..tpu import kda, mla
from ..tpu.paged import CacheTensor, PagedKVCacheSpec
from .layers import (
    chunk_routes, embed, head, layer_weights, mlp, rms, routes_shape, rows_with_routes,
    split_routes, wave_routes,
)
from .layers import choices  # re-exported: this file's ``program.choices`` (benchmarks/configs/)
from .serving import (
    ExpertTally, ServingSteps, chunk_index, prefill_by_blocks, real_rows, resume_step,
    wave_index, wave_sources,
)

Params = Dict[str, jax.Array]
Caches = List[Tuple[jax.Array, ...]]

KDA, MLA = "kda", "mla"


@dataclass(frozen=True)
class KimiLinearConfig:
    vocab: int = 512
    dim: int = 64
    n_layers: int = 5
    kda_layers: Tuple[int, ...] = (1, 2, 3, 5)  # 1-based, as published
    full_attn_layers: Tuple[int, ...] = (4,)
    # KDA (linear_attn_config)
    kda_heads: int = 4
    kda_head_dim: int = 16
    conv_taps: int = 4
    gate_rank: int = 16  # the low-rank pairs' inner width (the head size, published)
    # MLA
    n_heads: int = 4
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    # MLPs
    ffn_dim: int = 128  # the leading dense layers' width
    moe_ffn_dim: int = 32
    n_experts: int = 8
    experts_per_token: int = 2
    n_shared_experts: int = 1
    n_dense_layers: int = 1
    route_scale: float = 2.446
    route_norm: bool = True
    rms_eps: float = 1e-5
    block_tokens: int = 8
    dtype: jnp.dtype = jnp.bfloat16
    # (first, count) of the expert axis this instance computes; None: all.
    experts_held: Optional[Tuple[int, int]] = None
    # The published ``linear_attn_config`` group, whole: read into the six
    # KDA fields above (the low-rank pairs' inner width is its head size)
    # and dropped, so that the config stays hashable.
    linear_attn: Optional[dict] = None
    # Tokens whose chosen expert ids a block keeps beside its state (module
    # docstring); 0: none, and the last layer's cache has no third tensor.
    route_tail: int = 0

    def __post_init__(self):
        if self.linear_attn is not None:
            group = self.linear_attn
            for name, value in (
                ("kda_layers", group["kda_layers"]),
                ("full_attn_layers", group["full_attn_layers"]),
                ("kda_heads", group["num_heads"]), ("kda_head_dim", group["head_dim"]),
                ("gate_rank", group["head_dim"]),
                ("conv_taps", group["short_conv_kernel_size"]),
            ):
                object.__setattr__(self, name, value)
            object.__setattr__(self, "linear_attn", None)
        for name in ("kda_layers", "full_attn_layers"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", tuple(self.experts_held))
        named = sorted(self.kda_layers + self.full_attn_layers)
        if named != list(range(1, self.n_layers + 1)):
            raise ValueError(
                f"kda_layers {self.kda_layers} and full_attn_layers {self.full_attn_layers} "
                f"do not name layers 1..{self.n_layers} once each"
            )

    def kind_of(self, layer: int) -> str:
        """0-based ``layer``'s mixer."""
        return KDA if layer + 1 in self.kda_layers else MLA

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def held_count(self) -> int:
        return self.held[1]

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    def layer_cache(self, layer: int) -> Tuple[CacheTensor, ...]:
        if self.kind_of(layer) == MLA:
            tensors = (CacheTensor(
                "latent", (self.latent_width, self.block_tokens), self.dtype, None, "latent"
            ),)
        else:
            h, d = self.kda_heads, self.kda_head_dim
            tensors = (
                CacheTensor("state", (h, d, d), jnp.float32, 1, "state"),
                CacheTensor("tail", self.tail_shape, self.dtype, 1, "state"),
            )
        if self.route_tail and layer == self.n_layers - 1:
            tensors += (CacheTensor("routes", self.routes_shape, jnp.int32, 1, "state"),)
        return tensors

    @property
    def sites(self) -> int:
        """Expert layers: the model's discrete-choice sites, in its order."""
        return self.n_layers - self.n_dense_layers

    @property
    def routes_shape(self) -> Tuple[int, int]:
        return routes_shape(self.route_tail, self.sites, self.experts_per_token)

    @property
    def tail_shape(self) -> Tuple[int, int]:
        """The convolution tail's ``[taps - 1, 3 H K]`` rows as the cache keeps
        them: folded to 128 lanes where they divide, so that the array lies
        row-major on the chip and the block copies take it as it lies."""
        width = 3 * self.kda_heads * self.kda_head_dim
        lanes = 128 if width % 128 == 0 else width
        return ((self.conv_taps - 1) * width // lanes, lanes)

    def kv_spec(self, num_blocks: int) -> PagedKVCacheSpec:
        return PagedKVCacheSpec.of_layers(
            num_blocks, self.block_tokens,
            [self.layer_cache(layer) for layer in range(self.n_layers)],
        )

    @property
    def steps(self) -> ServingSteps:
        return ServingSteps(prefill, prefill_continue, verify_step_ragged)

    # What the wave step counts and returns with its logits (serving.py):
    # the expert layer's three, and the rows whose state crossed into a new
    # block (engine metrics: ``state_carries``).
    step_counters = (*ExpertTally.counters, "state_carries")
    router = "sigmoid"  # ``moe.route``'s kind


def init_params(config: KimiLinearConfig, key: jax.Array) -> Params:
    """Seeded 1/sqrt(fan_in) normal weights as a flat dict (layer-prefixed
    keys), norms at one, the router's selection bias at zero; ``A_log`` the
    log of a uniform draw from [1, 16] and ``dt_bias`` the inverse softplus
    of a log-uniform draw from [0.001, 0.1], the published initialisation.
    The held experts only where the instance holds a share."""
    keys = iter(jax.random.split(key, 4 + 16 * config.n_layers))
    _, count = config.held
    f32 = jnp.float32

    def dense(k, shape, fan_in):
        w = jax.random.normal(k, shape, dtype=f32) / np.sqrt(fan_in)
        return w.astype(config.dtype)

    ones = lambda n: jnp.ones((n,), dtype=config.dtype)
    d, f = config.dim, config.moe_ffn_dim
    p: Params = {
        "embed": dense(next(keys), (config.vocab, d), config.vocab),
        "final_norm": ones(d),
        "lm_head": dense(next(keys), (d, config.vocab), d),
    }
    for layer in range(config.n_layers):
        pre = f"l{layer}."
        p[pre + "in_norm"], p[pre + "pre_mlp_norm"] = ones(d), ones(d)
        if config.kind_of(layer) == KDA:
            h, hd, r = config.kda_heads, config.kda_head_dim, config.gate_rank
            p[pre + "w_qkv"] = dense(next(keys), (d, 3 * h * hd), d)
            p[pre + "conv_w"] = dense(next(keys), (config.conv_taps, 3 * h * hd), config.conv_taps)
            p[pre + "A_log"] = jnp.log(jax.random.uniform(next(keys), (h,), f32, 1.0, 16.0))
            dt = jnp.exp(jax.random.uniform(
                next(keys), (h * hd,), f32, np.log(1e-3), np.log(1e-1)
            ))
            p[pre + "dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
            p[pre + "w_fa"] = dense(next(keys), (d, r), d)
            p[pre + "w_fb"] = dense(next(keys), (r, h * hd), r)
            p[pre + "w_b"] = dense(next(keys), (d, h), d)
            p[pre + "w_ga"] = dense(next(keys), (d, r), d)
            p[pre + "w_gb"] = dense(next(keys), (r, h * hd), r)
            p[pre + "o_norm"] = ones(hd)
            p[pre + "wo"] = dense(next(keys), (h * hd, d), h * hd)
        else:
            h, qk = config.n_heads, config.qk_nope_head_dim + config.qk_rope_head_dim
            p[pre + "wq"] = dense(next(keys), (d, h, qk), d)
            p[pre + "w_kva"] = dense(next(keys), (d, config.latent_width), d)
            p[pre + "kv_norm"] = ones(config.kv_lora_rank)
            p[pre + "w_kvb"] = dense(
                next(keys),
                (config.kv_lora_rank, h, config.qk_nope_head_dim + config.v_head_dim),
                config.kv_lora_rank,
            )
            p[pre + "wo"] = dense(
                next(keys), (h * config.v_head_dim, d), h * config.v_head_dim
            )
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
# The KDA mixer.
# ---------------------------------------------------------------------------


def _l2norm(x):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda_inputs(w: Params, n, tail, config: KimiLinearConfig):
    """n: [T, dim] the normed input; tail: [taps - 1, 3 H K] the rows before
    the convolution that came before n's (per ROW where ``tail`` is [T, taps -
    1, 3 H K]: a wave, each row a request of its own). Returns q, k, v [T,
    H, K] in the served type, g [T, H, K] and beta [T, H] float32, the gate's
    pre-activation [T, H, K], and the new tail(s)."""
    t = n.shape[0]
    h, hd = config.kda_heads, config.kda_head_dim
    pre = jnp.dot(n, w["w_qkv"])  # [T, 3 H K]
    y, new_tail = kda.short_conv(pre, tail, w["conv_w"])  # a wave's tails: one a row
    y = jax.nn.silu(y).reshape(t, 3, h, hd)
    q = (_l2norm(y[:, 0]) * np.float32(hd ** -0.5)).astype(config.dtype)
    k = _l2norm(y[:, 1]).astype(config.dtype)
    v = y[:, 2].astype(config.dtype)
    f32 = jnp.float32
    decay_in = jnp.dot(jnp.dot(n, w["w_fa"]), w["w_fb"]).astype(f32) + w["dt_bias"]
    g = -jnp.exp(w["A_log"])[None, :, None] * jax.nn.softplus(decay_in).reshape(t, h, hd)
    beta = jax.nn.sigmoid(jnp.dot(n, w["w_b"]).astype(f32))
    gate = jnp.dot(jnp.dot(n, w["w_ga"]), w["w_gb"]).reshape(t, h, hd)
    return q, k, v, g, beta, gate, new_tail


def _kda_out(w: Params, x, o, gate, config: KimiLinearConfig):
    """o: [T, H, V] float32. Per-head norm, the sigmoid gate, Wo, residual."""
    normed = rms(o, w["o_norm"], config.rms_eps, jnp.float32)
    gated = (normed * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(config.dtype)
    return x + jnp.dot(gated.reshape(x.shape[0], -1), w["wo"]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# The MLA mixer.
# ---------------------------------------------------------------------------


def _mla_inputs(w: Params, n, config: KimiLinearConfig):
    """q [T, H, nope + rope] and the cache's row [T, rank + rope]: the normed
    latent beside the shared key."""
    q = jnp.einsum("td,dhk->thk", n, w["wq"])
    kva = jnp.dot(n, w["w_kva"])
    r = config.kv_lora_rank
    c = rms(kva[:, :r], w["kv_norm"], config.rms_eps)
    return q, jnp.concatenate([c, kva[:, r:]], axis=-1)


def _mla_scale(config: KimiLinearConfig) -> float:
    return float((config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5)


def _mla_out(w: Params, x, attn, config: KimiLinearConfig):
    """attn: [T, H, v] float32."""
    a = attn.astype(config.dtype).reshape(x.shape[0], -1)
    return x + jnp.dot(a, w["wo"]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# The three serving entries (serving.py). Each DONATES ``caches``.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("caches",))
def resume_chunk(
    params: Params, tokens, start_pos, caches: Caches, block_table, config: KimiLinearConfig
) -> Tuple[jax.Array, Caches]:
    """ONE request's chunk at contiguous positions INSIDE ONE BLOCK (the
    caller cuts at block boundaries): a hit's question, and every piece of a
    miss's prefill. A KDA layer takes the state and the tail of the block of
    position ``start_pos - 1`` (zeros at a prompt's start) and leaves the
    ones after its last token in the chunk's own block; the MLA layer writes
    its latents there and attends the table's pages. Returns (logits [S_c,
    vocab], caches); ``caches`` is donated."""
    bt = config.block_tokens
    block, before, fresh = chunk_index(tokens, start_pos, block_table, bt)
    x = embed(params, tokens)
    new_caches: Caches = []
    chosen = []
    for layer, cache in enumerate(caches):
        w = layer_weights(params, layer)
        n = rms(x, w["in_norm"], config.rms_eps, config.dtype)
        cache, routes = split_routes(cache, layer, config)
        if config.kind_of(layer) == KDA:
            states, tails = cache
            state = jnp.where(fresh, 0.0, states[before])
            tail = jnp.where(fresh, jnp.zeros((), tails.dtype), tails[before])
            tail = tail.reshape(config.conv_taps - 1, -1)
            q, k, v, g, beta, gate, tail = _kda_inputs(w, n, tail, config)
            o, state = kda.kda_chunk(q, k, v, g, beta, state)
            tail = tail.astype(tails.dtype).reshape(tails.shape[1:])
            cache = (states.at[block].set(state), tails.at[block].set(tail))
            x = _kda_out(w, x, o, gate, config)
        else:
            (latent,) = cache
            q, rows = _mla_inputs(w, n, config)
            # The chunk lies in one block: one slice written in place (a
            # scatter by index makes XLA re-lay the whole cache out, twice).
            latent = jax.lax.dynamic_update_slice(
                latent, rows.astype(latent.dtype).T[None], (block, 0, start_pos % bt)
            )
            attn = mla.latent_chunk_attention(
                q, latent, block_table, start_pos, w["w_kvb"], rank=config.kv_lora_rank,
                nope=config.qk_nope_head_dim, scale=_mla_scale(config),
            )
            cache = (latent,)
            x = _mla_out(w, x, attn, config)
        x, ids, _ = mlp(w, x, layer < config.n_dense_layers, config)
        if ids is not None:
            chosen.append(ids)
        if routes is not None:
            cache += (chunk_routes(routes, chosen, block, before, fresh, config),)
        new_caches.append(cache)
    return head(params, x, config), new_caches


prefill_continue = resume_step(resume_chunk)
prefill = prefill_by_blocks(resume_chunk)


def _wave_kda(w: Params, x, states, tails, src, dst, fresh, config: KimiLinearConfig):
    n = rms(x, w["in_norm"], config.rms_eps, config.dtype)
    state = jnp.where(fresh[:, None, None, None], 0.0, states[src])
    tail = jnp.where(fresh[:, None, None], jnp.zeros((), tails.dtype), tails[src])
    tail = tail.reshape(x.shape[0], config.conv_taps - 1, -1)
    q, k, v, g, beta, gate, tail = _kda_inputs(w, n, tail, config)
    o, state = kda.kda_step(q, k, v, g, beta, state)
    states = states.at[dst].set(state)
    tails = tails.at[dst].set(tail.astype(tails.dtype).reshape(-1, *tails.shape[1:]))
    return _kda_out(w, x, o, gate, config), states, tails


def _wave_mla(w: Params, x, latent, dst, slots, row_tables, seq_lens, config: KimiLinearConfig):
    n = rms(x, w["in_norm"], config.rms_eps, config.dtype)
    q, rows = _mla_inputs(w, n, config)
    # A row a slice, in place: a scatter by (block, slot) makes XLA re-lay the
    # whole cache out and back every wave. A wave's rows are few.
    rows = rows.astype(latent.dtype)
    for t in range(rows.shape[0]):
        latent = jax.lax.dynamic_update_slice(
            latent, rows[t][None, :, None], (dst[t], 0, slots[t])
        )
    nope, r = config.qk_nope_head_dim, config.kv_lora_rank
    # Absorbed: the query through the keys' up-projection, the output through
    # the values'.
    q_abs = jnp.einsum("thd,rhd->thr", q[..., :nope], w["w_kvb"][..., :nope])
    q_lat = jnp.concatenate([q_abs.astype(q.dtype), q[..., nope:]], axis=-1)
    mix = mla.latent_decode_rows(
        q_lat, latent, row_tables, seq_lens, rank=r, scale=_mla_scale(config)
    )
    attn = mla.einsum_f32("thr,rhd->thd", mix.astype(config.dtype), w["w_kvb"][..., nope:])
    return _mla_out(w, x, attn, config), latent


@functools.partial(
    jax.jit, static_argnames=("config", "max_blocks"), donate_argnames=("caches",)
)
def verify_step_ragged(
    params: Params, tokens, positions, row_of, pages, page_rows, page_starts, caches: Caches,
    block_tables, config: KimiLinearConfig, max_blocks: int,
):
    """THE wave body (``serving.py``: ``wave``'s contract and argument order).
    Each row names, by its position and its table, the block its state comes
    from (position p - 1's) and the block it goes to (p's): a row that crosses
    a block boundary carries its running state into the new block's slot.
    Returns ``(logits [T, vocab], caches, aux)``: ``serving.ExpertTally``'s
    ``aux`` (with ``route_tail`` its ``rows`` are followed by the sets the
    tokens before each row chose in theirs, as the cache kept them) and, among
    its counters, ``state_carries``, the real rows that crossed into a new
    block. ``caches`` is donated."""
    del pages, page_rows, page_starts
    bt = config.block_tokens
    x = embed(params, tokens)
    row_tables, dst, slots = wave_index(positions, row_of, block_tables, max_blocks, bt)
    src, fresh = wave_sources(positions, row_tables, bt)
    seq_lens = positions + 1

    kda_fn = jax.jit(_wave_kda, static_argnames=("config",))
    new_caches: Caches = []
    tally = ExpertTally()
    found = None
    for layer, cache in enumerate(caches):
        w = layer_weights(params, layer)
        cache, routes = split_routes(cache, layer, config)
        if config.kind_of(layer) == KDA:
            x, states, tails = kda_fn(w, x, *cache, src, dst, fresh, config=config)
            cache = (states, tails)
        else:
            x, latent = _wave_mla(w, x, *cache, dst, slots, row_tables, seq_lens, config)
            cache = (latent,)
        x, ids, n = mlp(w, x, layer < config.n_dense_layers, config)
        tally.add(ids, n)
        if routes is not None:
            routes, found = wave_routes(routes, tally.chosen, src, dst, fresh, config)
            cache += (routes,)
        new_caches.append(cache)
    logits = head(params, x, config)
    real = real_rows(positions, row_of)
    aux = tally.aux(real, config.experts_per_token)
    if found is not None:
        aux["rows"] = rows_with_routes(aux["rows"], found, config.experts_per_token)
    aux["counters"]["state_carries"] = jnp.sum(real & (slots == 0) & ~fresh, dtype=jnp.int32)
    return logits, new_caches, aux
