"""A decoder of the ``granitemoehybrid`` family on the paged serving path: a
SERIAL hybrid, most layers a Mamba-2 mixer on a recurrent state and a few a
grouped-query attention over K/V (nine to one as published), no positional
encoding at all, an expert layer behind EVERY mixer, four scalars on the
stream.

The equations are the published configuration's and modelling code's;
``benchmarks/reference_granite_hybrid.py`` writes the same ones out in plain
float32, the recurrence a token at a time. Pre-norm:

  x_0     = embedding_multiplier E[token]
  h       = x + residual_multiplier mixer(rms(x; w_in))
  y       = h + residual_multiplier (sum_{e in top-k} g_e Expert_e(m) + Shared(m)),  m = rms(h; w_post)
  router  : l = W_r m in float32; the k largest logits; g = softmax over those
            k alone (``moe.route``, ``router = "softmax_topk"``)
  Expert  : W_out (silu(a) b), [a, b] = W_in m; ``Shared`` the same at its own
            width; ``tpu/moe.py`` ``expert_layer``, every routed family's one code,
            told the share it holds (``experts_held``)
  Attn    : q = Wq n, k = Wk n, v = Wv n, NO rotation; causal softmax(
            attention_multiplier q k^T) v, H / KVH query heads a KV head; Wo.
            The two K/V kernels scale by 1 / sqrt(D), so q carries
            attention_multiplier sqrt(D), multiplied in float32 before its one
            rounding
  Mamba   : [z | xBC | dt] = W_in n; xBC <- silu(conv4(xBC) + b) (causal,
            depth-wise); [x, B, C]; dt = softplus(dt + dt_bias);
            S_t = exp(-exp(A_log) dt) S_{t-1} + dt x_t B_t^T, o_t = S_t C_t + D x_t
            per head (``tpu/ssd.py``; one group of B and C as published);
            W_out (rms(o silu(z)) w): the gate first, then ONE norm over all
            the mixer's channels
  logits  = E^T rms(x_L; w_final) / logits_scaling        (the embedding, tied)

The cache (``kv_spec``): a layer is state OR K/V. A Mamba layer's tuple is
``(state, tail)``: ``state`` ``[blocks, H_s, P, N]`` float32 and ``tail`` (the
last ``taps - 1`` rows before the convolution, folded to 128 lanes) are what
the mixer holds after the block's last token, the RUNNING ones while the block
is a request's last; it has no page list at all. An attention layer's is
``(k, v)`` ``[blocks, block_tokens, KVH, D]``, the pages the two attention
kernels walk (the block is the state's snapshot interval, so a page is as long
as that). A hit installs every K and V block of the attention layers and the
LAST block's state and tail of each Mamba layer; every block saves all.

Every layer routes, so where the configuration asks (``route_tail`` tokens)
the LAST layer's tuple carries a further tensor ``routes``: the expert ids the
block's last ``route_tail`` tokens chose at every layer, shifted on by every
chunk and wave like the convolution's tail and handed back beside each row's
own (``kimi_linear.py`` says why, and the layout of ``aux["rows"]``).

A token is absorbed into a state once, so nothing here may compute a position
twice: the engine lands a prompt's last token in the first wave alone
(``PagedKVCacheSpec.has_state``), a chunk lies inside one block, and a wave's
row reads its state from the block of position p - 1 and writes the block of
p (rows that repeat their predecessor, a wave's padding, read and write the
same bytes).

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

from ..tpu import kda, ssd
from ..tpu.chunk_attention import chunk_prefix_attention
from ..tpu.paged import CacheTensor, PagedKVCacheSpec
from ..tpu.paged_attention import paged_decode_attention_rows
from ..tpu.moe import expert_layer
from .layers import (
    chunk_routes, folded_tail_shape, layer_weights, rms, routes_shape, rows_with_routes, set_slots,
    slots_of, split_routes, tail_folded, tail_rows, wave_routes,
)
from .layers import choices  # re-exported: this file's ``program.choices`` (benchmarks/configs/)
from .serving import (
    ExpertTally, ServingSteps, chunk_index, prefill_by_blocks, real_rows, resume_step,
    wave_index, wave_sources,
)

Params = Dict[str, jax.Array]
Caches = List[Tuple[jax.Array, ...]]

MAMBA, ATTENTION = "mamba", "attention"


@dataclass(frozen=True)
class GraniteHybridConfig:
    vocab: int = 512
    dim: int = 64
    layer_types: Tuple[str, ...] = (MAMBA, ATTENTION, MAMBA)
    # attention (a head is dim / n_heads wide, as the family derives it)
    n_heads: int = 4
    n_kv_heads: int = 2
    # the Mamba-2 mixer: ssm_heads x ssm_head_dim channels
    ssm_heads: int = 8
    ssm_head_dim: int = 16
    ssm_state: int = 32
    ssm_groups: int = 1
    conv_taps: int = 4
    ssm_chunk: int = 16
    # the expert layer
    moe_ffn_dim: int = 32  # one routed expert's width
    shared_ffn_dim: int = 64  # the shared expert's
    n_experts: int = 8  # the router's width
    experts_per_token: int = 3
    # (first, count) of the expert axis this instance computes; None: all.
    experts_held: Optional[Tuple[int, int]] = None
    rms_eps: float = 1e-5
    # the family's four scalars, each applied where the module docstring says
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.25
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # Tokens whose chosen expert ids a block keeps beside its state (module
    # docstring); 0: none, and the last layer's cache has no further tensor.
    route_tail: int = 0
    block_tokens: int = 32
    dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        # A configuration file hands lists; jit wants the config hashable.
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", tuple(self.experts_held))
        unknown = set(self.layer_types) - {MAMBA, ATTENTION}
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}")
        if self.dim % self.n_heads or self.n_heads % self.n_kv_heads or self.ssm_heads % self.ssm_groups:
            raise ValueError("heads are shared out in whole groups, and the width in whole heads")
        if self.block_tokens % self.ssm_chunk:
            raise ValueError(
                f"a block of {self.block_tokens} tokens is no whole number of {self.ssm_chunk}-token chunks"
            )

    # What ``moe.expert_layer`` asks of a configuration beside the fields.
    router = "softmax_topk"
    n_shared_experts = 1

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def held_count(self) -> int:
        return self.held[1]

    @property
    def ssm_width(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_expand(self) -> int:
        """``mamba_expand``: the mixer's channels over the hidden size."""
        return self.ssm_width // self.dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution passes over: x, B and C side by side."""
        return self.ssm_width + 2 * self.ssm_groups * self.ssm_state

    @property
    def in_width(self) -> int:
        """The in-projection's outputs: z | xBC | dt."""
        return self.ssm_width + self.conv_width + self.ssm_heads

    @property
    def tail_shape(self) -> Tuple[int, int]:
        """The convolution tail's rows as the cache keeps them."""
        return folded_tail_shape(self.conv_taps, self.conv_width)

    @property
    def sites(self) -> int:
        """Expert layers, the model's discrete-choice sites: every layer."""
        return self.n_layers

    @property
    def routes_shape(self) -> Tuple[int, int]:
        return routes_shape(self.route_tail, self.sites, self.experts_per_token)

    def layer_cache(self, layer: int) -> Tuple[CacheTensor, ...]:
        if self.layer_types[layer] == ATTENTION:
            page = (self.block_tokens, self.n_kv_heads, self.head_dim)
            tensors = (
                CacheTensor("k", page, self.dtype, None, "kv"),
                CacheTensor("v", page, self.dtype, None, "kv"),
            )
        else:
            tensors = (
                CacheTensor(
                    "state", (self.ssm_heads, self.ssm_head_dim, self.ssm_state), jnp.float32, 1, "state"
                ),
                CacheTensor("tail", self.tail_shape, self.dtype, 1, "state"),
            )
        if self.route_tail and layer == self.n_layers - 1:
            tensors += (CacheTensor("routes", self.routes_shape, jnp.int32, 1, "state"),)
        return tensors

    def kv_spec(self, num_blocks: int) -> PagedKVCacheSpec:
        return PagedKVCacheSpec.of_layers(
            num_blocks, self.block_tokens,
            [self.layer_cache(layer) for layer in range(self.n_layers)],
        )

    @property
    def steps(self) -> ServingSteps:
        return ServingSteps(prefill, prefill_continue, verify_step_ragged)

    # What the wave step counts and returns with its logits (serving.py): the
    # expert layers' (``ExpertTally``), the pairs among them that fall on the
    # experts held here, and the rows whose state crossed into a new block.
    step_counters = (*ExpertTally.counters, "moe_held_pairs", "state_carries")


def init_params(config: GraniteHybridConfig, key: jax.Array) -> Params:
    """Seeded 1/sqrt(fan_in) normal weights as a flat dict (layer-prefixed
    keys), norms at one, the convolution's bias at zero; ``A_log`` the log of
    a uniform draw from [1, 16] a head, ``dt_bias`` the inverse softplus of a
    log-uniform draw from [0.001, 0.1] a head and ``D`` ones: the Mamba-2
    modelling code's initialisation. The held experts only where the instance
    holds a share; the head is the embedding (tied)."""
    keys = iter(jax.random.split(key, 1 + 12 * config.n_layers))
    _, count = config.held
    f32 = jnp.float32

    def dense(k, shape, fan_in):
        w = jax.random.normal(k, shape, dtype=f32) / np.sqrt(fan_in)
        return w.astype(config.dtype)

    ones = lambda n: jnp.ones((n,), dtype=config.dtype)
    d, h, kvh, hd = config.dim, config.n_heads, config.n_kv_heads, config.head_dim
    f, fs = config.moe_ffn_dim, config.shared_ffn_dim
    p: Params = {
        "embed": dense(next(keys), (config.vocab, d), config.vocab),
        "final_norm": ones(d),
    }
    for layer in range(config.n_layers):
        pre = f"l{layer}."
        p[pre + "in_norm"], p[pre + "pre_mlp_norm"] = ones(d), ones(d)
        if config.layer_types[layer] == ATTENTION:
            p[pre + "wq"] = dense(next(keys), (d, h, hd), d)
            p[pre + "wk"] = dense(next(keys), (d, kvh, hd), d)
            p[pre + "wv"] = dense(next(keys), (d, kvh, hd), d)
            p[pre + "wo"] = dense(next(keys), (h * hd, d), h * hd)
        else:
            heads = config.ssm_heads
            p[pre + "w_in"] = dense(next(keys), (d, config.in_width), d)
            p[pre + "conv_w"] = dense(next(keys), (config.conv_taps, config.conv_width), config.conv_taps)
            p[pre + "conv_b"] = jnp.zeros((config.conv_width,), config.dtype)
            p[pre + "A_log"] = jnp.log(jax.random.uniform(next(keys), (heads,), f32, 1.0, 16.0))
            dt = jnp.exp(jax.random.uniform(next(keys), (heads,), f32, np.log(1e-3), np.log(1e-1)))
            p[pre + "dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
            p[pre + "D"] = jnp.ones((heads,), f32)
            p[pre + "ssm_norm"] = ones(config.ssm_width)
            p[pre + "w_out"] = dense(next(keys), (config.ssm_width, d), config.ssm_width)
        p[pre + "router"] = dense(next(keys), (d, config.n_experts), d)
        p[pre + "w_gate"] = dense(next(keys), (count, d, f), d)
        p[pre + "w_up"] = dense(next(keys), (count, d, f), d)
        p[pre + "w_down_moe"] = dense(next(keys), (count, f, d), f)
        p[pre + "ws_gate_up"] = dense(next(keys), (d, 2, fs), d)
        p[pre + "ws_down"] = dense(next(keys), (fs, d), fs)
    return p


def _embed(params: Params, tokens: jax.Array, config: GraniteHybridConfig) -> jax.Array:
    # [T, dim] float32: the residual stream, carried unrounded within a step.
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    return x * np.float32(config.embedding_multiplier)


def _head(params: Params, x: jax.Array, config: GraniteHybridConfig) -> jax.Array:
    """The tied head: the embedding's rows against the normed stream."""
    x = rms(x, params["final_norm"], config.rms_eps, config.dtype)
    logits = jnp.einsum("td,vd->tv", x, params["embed"], preferred_element_type=jnp.float32)
    return (logits / np.float32(config.logits_scaling)).astype(config.dtype)


def _experts(w: Params, h, config: GraniteHybridConfig):
    """The second half of a layer on h: [T, dim] float32. Returns (y, ids [T,
    k] the experts each row chose among all, the expert layer's counts)."""
    m = rms(h, w["pre_mlp_norm"], config.rms_eps, config.dtype)
    with jax.named_scope("granite_expert_layer"):
        f, ids, counts = expert_layer(w, m, config)
    return h + np.float32(config.residual_multiplier) * f, ids, counts


def _qkv(w: Params, n, config: GraniteHybridConfig):
    """q [T, H, D] and the cache's rows k, v [T, KVH, D] of the normed n: no
    rotation; q carries the published logit scale over the kernels' own 1 /
    sqrt(D), multiplied in float32 and rounded once."""
    project = lambda name: jnp.einsum("td,dhk->thk", n, w[name], preferred_element_type=jnp.float32)
    scale = np.float32(config.attention_multiplier * np.sqrt(config.head_dim))
    q = (project("wq") * scale).astype(config.dtype)
    return q, project("wk").astype(config.dtype), project("wv").astype(config.dtype)


def _attn_out(w: Params, x, attn, config: GraniteHybridConfig):
    a = jnp.dot(attn.reshape(x.shape[0], -1), w["wo"]).astype(jnp.float32)
    return x + np.float32(config.residual_multiplier) * a


def _ssm_inputs(w: Params, n, tail, config: GraniteHybridConfig):
    """The mixer's inputs from the normed n: [T, dim]. ``tail``: [taps - 1,
    conv_width] the rows before the convolution that came before n's (per ROW
    where it is [T, taps - 1, conv_width]: a wave, each row a request of its
    own). Returns x [T, H_s, P], B and C [T, G, N] in the served type, dt [T,
    H_s] float32 after its softplus, the gate z [T, ssm_width] float32 and the
    new tail(s)."""
    f32 = jnp.float32
    t = n.shape[0]
    width, conv = config.ssm_width, config.conv_width
    u = jnp.dot(n, w["w_in"], preferred_element_type=f32)
    z, pre, dt = u[:, :width], u[:, width : width + conv].astype(config.dtype), u[:, width + conv :]
    y, new_tail = kda.short_conv(pre, tail, w["conv_w"])  # a wave's tails: one a row
    y = jax.nn.silu(y + w["conv_b"].astype(f32)).astype(config.dtype)
    group = config.ssm_groups * config.ssm_state
    x = y[:, :width].reshape(t, config.ssm_heads, config.ssm_head_dim)
    b = y[:, width : width + group].reshape(t, config.ssm_groups, config.ssm_state)
    c = y[:, width + group :].reshape(t, config.ssm_groups, config.ssm_state)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    return x, b, c, dt, z, new_tail


def _ssm_out(w: Params, x, o, z, config: GraniteHybridConfig):
    """x + residual_multiplier W_out (rms(o silu(z)) w) on o: [T, H_s, P]
    float32: the gate first, then one norm over all the mixer's channels."""
    t = o.shape[0]
    y = o.reshape(t, -1) * jax.nn.silu(z)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + config.rms_eps)
    y = (y * w["ssm_norm"].astype(jnp.float32)).astype(config.dtype)
    out = jnp.dot(y, w["w_out"]).astype(jnp.float32)
    return x + np.float32(config.residual_multiplier) * out


# ---------------------------------------------------------------------------
# The three serving entries (serving.py). Each DONATES ``caches``.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("caches",))
def resume_chunk(
    params: Params, tokens, start_pos, caches: Caches, block_table, config: GraniteHybridConfig
) -> Tuple[jax.Array, Caches]:
    """ONE request's chunk at contiguous positions INSIDE ONE BLOCK (the
    caller cuts at block boundaries): a hit's question, and every piece of a
    miss's prefill. A Mamba layer takes the state and the tail of the block of
    position ``start_pos - 1`` (zeros at a prompt's start) and leaves the ones
    after its last token in the chunk's own block; an attention layer writes
    the chunk's K and V into the block's page and attends the table's pages
    (``chunk_prefix_attention``). Returns (the LAST row's logits [1, vocab],
    caches): the engine takes a first token from the first wave, never from a
    chunk. ``caches`` is donated."""
    bt = config.block_tokens
    block, before, fresh = chunk_index(tokens, start_pos, block_table, bt)
    x = _embed(params, tokens, config)
    new_caches: Caches = []
    chosen = []
    for layer, cache in enumerate(caches):
        w = layer_weights(params, layer)
        n = rms(x, w["in_norm"], config.rms_eps, config.dtype)
        cache, routes = split_routes(cache, layer, config)
        if config.layer_types[layer] == ATTENTION:
            with jax.named_scope("granite_attention_mixer"):
                k_cache, v_cache = cache
                q, k, v = _qkv(w, n, config)
                # The chunk lies in one block: one slice written in place.
                at = (block, start_pos % bt, 0, 0)
                k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype)[None], at)
                v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype)[None], at)
                attn = chunk_prefix_attention(q, k_cache, v_cache, block_table, start_pos)
                x = _attn_out(w, x, attn, config)
                cache = (k_cache, v_cache)
        else:
            with jax.named_scope("granite_mamba_mixer"):
                states, tails = cache
                state = jnp.where(fresh, 0.0, states[before])
                tail = jnp.where(fresh, jnp.zeros((), tails.dtype), tails[before])
                xs, b, c, dt, z, tail = _ssm_inputs(w, n, tail_rows(tail, config), config)
                o, state = ssd.ssd_chunk(xs, dt, w["A_log"], b, c, w["D"], state, chunk=config.ssm_chunk)
                x = _ssm_out(w, x, o, z, config)
                cache = (
                    states.at[block].set(state),
                    tails.at[block].set(tail_folded(tail, tails)),
                )
        x, ids, _ = _experts(w, x, config)
        chosen.append(ids)
        if routes is not None:
            cache += (chunk_routes(routes, chosen, block, before, fresh, config),)
        new_caches.append(cache)
    return _head(params, x[-1:], config), new_caches


prefill_continue = resume_step(resume_chunk)
prefill = prefill_by_blocks(resume_chunk)


def _wave_mamba(w: Params, x, states, tails, src, dst, fresh, config: GraniteHybridConfig):
    """ONE Mamba layer of the wave body on T flat rows, each a request of its
    own: move each row's state on by its token (from block ``src`` to block
    ``dst``), then the expert layer. The layers of one kind share one traced
    and lowered function."""
    n = rms(x, w["in_norm"], config.rms_eps, config.dtype)
    with jax.named_scope("granite_mamba_mixer"):
        # A row a slice, read and written in place (``layers.slots_of``).
        state = jnp.where(fresh[:, None, None, None], 0.0, slots_of(states, src))
        tail = jnp.where(fresh[:, None, None], jnp.zeros((), tails.dtype), slots_of(tails, src))
        xs, b, c, dt, z, tail = _ssm_inputs(w, n, tail_rows(tail, config), config)
        o, state = ssd.ssd_step(xs, dt, w["A_log"], b, c, w["D"], state)
        states = set_slots(states, dst, state)
        tails = set_slots(tails, dst, tail_folded(tail, tails))
        x = _ssm_out(w, x, o, z, config)
    x, ids, counts = _experts(w, x, config)
    return x, states, tails, ids, counts


def _wave_attention(
    w: Params, x, k_cache, v_cache, dst, slots, row_tables, seq_lens, pages, page_rows,
    page_starts, config: GraniteHybridConfig,
):
    """ONE attention layer of the wave body: insert the rows' K and V, attend
    each row's pages (the ragged decode kernel), then the expert layer."""
    n = rms(x, w["in_norm"], config.rms_eps, config.dtype)
    with jax.named_scope("granite_attention_mixer"):
        q, k, v = _qkv(w, n, config)
        k_cache = k_cache.at[dst, slots].set(k.astype(k_cache.dtype))
        v_cache = v_cache.at[dst, slots].set(v.astype(v_cache.dtype))
        attn = paged_decode_attention_rows(
            q, k_cache, v_cache, row_tables, seq_lens, pages, page_rows, page_starts
        )
        x = _attn_out(w, x, attn, config)
    x, ids, counts = _experts(w, x, config)
    return x, k_cache, v_cache, ids, counts


@functools.partial(
    jax.jit, static_argnames=("config", "max_blocks"), donate_argnames=("caches",)
)
def verify_step_ragged(
    params: Params, tokens, positions, row_of, pages, page_rows, page_starts, caches: Caches,
    block_tables, config: GraniteHybridConfig, max_blocks: int,
):
    """THE wave body (``serving.py``: ``wave``'s contract and argument
    order). ONE table serves both kinds of layer: a row's flat page list
    (built from the table on the host) is what an attention layer walks, and
    by its position the table names the block a Mamba layer's state comes from
    (position p - 1's) and the block it goes to (p's, where the row's K and V
    land too): a row that crosses a block boundary carries its running state
    into the new block's slot. Returns ``(logits [T, vocab], caches, aux)``:
    ``serving.ExpertTally``'s ``aux`` (with ``route_tail`` its ``rows`` are
    followed by the sets the tokens before each row chose in theirs, as the
    cache kept them: ``layers.wave_routes``) and, among its counters,
    ``moe_held_pairs`` (the real rows' (row, choice) pairs that fall on the
    experts held here) and ``state_carries``, the real rows that crossed into
    a new block. ``caches`` is donated."""
    bt = config.block_tokens
    x = _embed(params, tokens, config)
    row_tables, dst, slots = wave_index(positions, row_of, block_tables, max_blocks, bt)
    src, fresh = wave_sources(positions, row_tables, bt)

    mamba_fn = jax.jit(_wave_mamba, static_argnames=("config",))
    attention_fn = jax.jit(_wave_attention, static_argnames=("config",))
    new_caches: Caches = []
    tally = ExpertTally()
    found = None
    for layer, cache in enumerate(caches):
        w = layer_weights(params, layer)
        cache, routes = split_routes(cache, layer, config)
        if config.layer_types[layer] == ATTENTION:
            x, *cache, ids, n = attention_fn(
                w, x, *cache, dst, slots, row_tables, positions + 1, pages, page_rows,
                page_starts, config=config,
            )
        else:
            x, *cache, ids, n = mamba_fn(w, x, *cache, src, dst, fresh, config=config)
        cache = tuple(cache)
        tally.add(ids, n)
        if routes is not None:
            routes, found = wave_routes(routes, tally.chosen, src, dst, fresh, config)
            cache += (routes,)
        new_caches.append(cache)
    logits = _head(params, x, config)
    real = real_rows(positions, row_of)
    aux = tally.aux(real, config.experts_per_token)
    first, count = config.held
    held = (aux["rows"] >= first) & (aux["rows"] < first + count) & real[:, None, None]
    aux["counters"]["moe_held_pairs"] = jnp.sum(held, dtype=jnp.int32)
    aux["counters"]["state_carries"] = jnp.sum(real & (slots == 0) & ~fresh, dtype=jnp.int32)
    if found is not None:
        aux["rows"] = rows_with_routes(aux["rows"], found, config.experts_per_token)
    return logits, new_caches, aux
