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
            k alone (``afmoe.route``, ``router = "softmax_topk"``)
  Expert  : W_out (silu(a) b), [a, b] = W_in m; ``Shared`` the same at its own
            width; ``afmoe.expert_layer``, the one code three families run,
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
(a miss: the prompt cut at block boundaries through ``resume_chunk``, the very
programs a hit's resume runs, so a full hit's first token equals the miss's to
the bit), ``resume_chunk`` and ``verify_step_ragged``; each donates ``caches``.
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
from .afmoe import _layer_weights, _rms, choices, expert_counts, expert_layer  # noqa: F401 - ``choices``: the file's ``program.choices``
from .kimi_linear import _split_routes
from .serving import ServingSteps

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

    # What ``afmoe.expert_layer`` asks of a configuration beside the fields.
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
        """The convolution tail's ``[taps - 1, conv_width]`` rows as the cache
        keeps them: folded to 128 lanes where they divide (``kimi_linear``'s
        reason: the array then lies row-major on the chip), the rows rounded
        up to four, so that a block's tail is whole KiB in the served type
        (the rows past the real ones stay zero)."""
        total = (self.conv_taps - 1) * self.conv_width
        if total % 128:
            return (self.conv_taps - 1, self.conv_width)
        rows = total // 128
        return (rows + -rows % 4, 128)

    @property
    def sites(self) -> int:
        """Expert layers, the model's discrete-choice sites: every layer."""
        return self.n_layers

    @property
    def routes_shape(self) -> Tuple[int, int]:
        """``[route_tail, sites, k]`` ids as the cache keeps them: folded to
        128 lanes where they divide."""
        total = self.route_tail * self.sites * self.experts_per_token
        lanes = 128 if total % 128 == 0 else total
        return (total // lanes, lanes)

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
    # expert layer's three (``afmoe.verify_step_ragged``'s), the pairs among them
    # that fall on the experts held here, and the rows whose state crossed
    # into a new block.
    step_counters = (
        "moe_pairs", "moe_distinct_experts", "moe_streamed_experts", "moe_held_pairs",
        "state_carries",
    )


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
    x = _rms(x, params["final_norm"], config.rms_eps, config.dtype)
    logits = jnp.einsum("td,vd->tv", x, params["embed"], preferred_element_type=jnp.float32)
    return (logits / np.float32(config.logits_scaling)).astype(config.dtype)


def _experts(w: Params, h, config: GraniteHybridConfig):
    """The second half of a layer on h: [T, dim] float32. Returns (y, ids [T,
    k] the experts each row chose among all, the expert layer's counts)."""
    m = _rms(h, w["pre_mlp_norm"], config.rms_eps, config.dtype)
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


def _tail_rows(tail, config: GraniteHybridConfig):
    """The cache's folded tail(s) ``[..., rows, lanes]`` as ``[..., taps - 1,
    conv_width]``: the real rows of the fold."""
    taps, width = config.conv_taps - 1, config.conv_width
    lead = tail.shape[:-2]
    return tail.reshape(*lead, -1)[..., : taps * width].reshape(*lead, taps, width)


def _tail_folded(tail, like, config: GraniteHybridConfig):
    """``[..., taps - 1, conv_width]`` as the cache keeps it (``like``: the
    cache's tail tensor), zeros in the fold's spare rows."""
    lead = tail.shape[:-2]
    flat = tail.astype(like.dtype).reshape(*lead, -1)
    spare = int(np.prod(like.shape[1:])) - flat.shape[-1]
    if spare:
        flat = jnp.pad(flat, [(0, 0)] * len(lead) + [(0, spare)])
    return flat.reshape(*lead, *like.shape[1:])


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
    if tail.ndim == 3:  # a wave: one position a row, each with its own tail
        rows = jnp.concatenate([tail.astype(pre.dtype), pre[:, None]], axis=1)
        y = jnp.sum(rows.astype(f32) * w["conv_w"].astype(f32)[None], axis=1)
        new_tail = rows[:, 1:]
    else:
        y, new_tail = kda.short_conv(pre, tail, w["conv_w"])
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
    params: Params,
    tokens: jax.Array,  # [S_c] int32, S_c <= block_tokens
    start_pos: jax.Array,  # [] int32
    caches: Caches,
    block_table: jax.Array,  # [max_blocks] int32
    config: GraniteHybridConfig,
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
    s_c = tokens.shape[0]
    bt = config.block_tokens
    if s_c > bt:
        raise ValueError(f"a chunk of {s_c} tokens does not lie in one {bt}-token block")
    block = block_table[start_pos // bt]
    before = block_table[jnp.maximum(start_pos - 1, 0) // bt]
    fresh = start_pos == 0
    x = _embed(params, tokens, config)
    new_caches: Caches = []
    chosen = []
    for layer, cache in enumerate(caches):
        w = _layer_weights(params, layer)
        n = _rms(x, w["in_norm"], config.rms_eps, config.dtype)
        cache, routes = _split_routes(cache, layer, config)
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
                xs, b, c, dt, z, tail = _ssm_inputs(w, n, _tail_rows(tail, config), config)
                o, state = ssd.ssd_chunk(xs, dt, w["A_log"], b, c, w["D"], state, chunk=config.ssm_chunk)
                x = _ssm_out(w, x, o, z, config)
                cache = (
                    states.at[block].set(state),
                    tails.at[block].set(_tail_folded(tail, tails, config)),
                )
        x, ids, _ = _experts(w, x, config)
        chosen.append(ids)
        if routes is not None:
            # The last ``route_tail`` tokens' sets, the chunk's own the newest.
            old = jnp.where(fresh, -1, routes[before]).reshape(config.route_tail, -1)
            mine = jnp.stack(chosen, axis=1).reshape(s_c, -1)
            kept = jnp.concatenate([old, mine])[-config.route_tail :]
            cache += (routes.at[block].set(kept.reshape(routes.shape[1:])),)
        new_caches.append(cache)
    return _head(params, x[-1:], config), new_caches


def prefill_continue(params, tokens, start_pos, caches, block_table, config, max_blocks):
    """The harness's resume step (``llama.prefill_continue``'s signature)."""
    if block_table.shape[0] != max_blocks:
        raise ValueError(
            f"block_table has {block_table.shape[0]} entries, expected max_blocks={max_blocks}"
        )
    return resume_chunk(params, tokens, start_pos, caches, block_table, config)


def prefill(params, tokens, caches, block_table, config: GraniteHybridConfig):
    """A miss: every token given, cut at block boundaries through the chunk
    program a hit's resume runs, so that each block's slot holds the state at
    its end. ``block_table`` covers the tokens (a last block may be part
    full). Returns (last-token logits, caches); ``caches`` is donated."""
    bt = config.block_tokens
    tokens = jnp.asarray(tokens, jnp.int32)
    table = jnp.asarray(block_table, jnp.int32)
    logits = None
    for start in range(0, tokens.shape[0], bt):
        logits, caches = resume_chunk(
            params, tokens[start : start + bt], jnp.int32(start), caches, table, config
        )
    return logits[-1], caches


def _wave_mamba(w: Params, x, states, tails, src, dst, fresh, config: GraniteHybridConfig):
    """ONE Mamba layer of the wave body on T flat rows, each a request of its
    own: move each row's state on by its token (from block ``src`` to block
    ``dst``), then the expert layer. The layers of one kind share one traced
    and lowered function."""
    n = _rms(x, w["in_norm"], config.rms_eps, config.dtype)
    with jax.named_scope("granite_mamba_mixer"):
        # A row a slice, read and written in place: a gather by row makes
        # XLA:TPU copy every block's state first (``falcon_h1.py``; PERF.md,
        # PR 43). A wave's rows are few.
        rows = range(x.shape[0])
        slots_of = lambda cache, ids: jnp.stack(
            [jax.lax.dynamic_index_in_dim(cache, ids[t], 0, keepdims=False) for t in rows]
        )
        state = jnp.where(fresh[:, None, None, None], 0.0, slots_of(states, src))
        tail = jnp.where(fresh[:, None, None], jnp.zeros((), tails.dtype), slots_of(tails, src))
        xs, b, c, dt, z, tail = _ssm_inputs(w, n, _tail_rows(tail, config), config)
        o, state = ssd.ssd_step(xs, dt, w["A_log"], b, c, w["D"], state)
        tail = _tail_folded(tail, tails, config)
        for t in rows:
            states = jax.lax.dynamic_update_index_in_dim(states, state[t].astype(states.dtype), dst[t], 0)
            tails = jax.lax.dynamic_update_index_in_dim(tails, tail[t], dst[t], 0)
        x = _ssm_out(w, x, o, z, config)
    x, ids, counts = _experts(w, x, config)
    return x, states, tails, ids, counts


def _wave_attention(
    w: Params, x, k_cache, v_cache, dst, slots, row_tables, seq_lens, pages, page_rows,
    page_starts, config: GraniteHybridConfig,
):
    """ONE attention layer of the wave body: insert the rows' K and V, attend
    each row's pages (the ragged decode kernel), then the expert layer."""
    n = _rms(x, w["in_norm"], config.rms_eps, config.dtype)
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
    params: Params,
    tokens: jax.Array,  # [T] int32: one token a request (a state absorbs a token once)
    positions: jax.Array,  # [T] int32
    row_of: jax.Array,  # [T] int32 owning request per flat token
    pages: jax.Array,  # [P] int32 the wave's flat page list (RaggedWaveMeta)
    page_rows: jax.Array,  # [P + 1]
    page_starts: jax.Array,  # [T]
    caches: Caches,
    block_tables: jax.Array,  # [B, max_blocks]
    config: GraniteHybridConfig,
    max_blocks: int,
):
    """THE wave body (``llama.verify_step_ragged``'s contract and argument
    order). ONE table serves both kinds of layer: a row's flat page list
    (built from the table on the host) is what an attention layer walks, and
    by its position the table names the block a Mamba layer's state comes from
    (position p - 1's) and the block it goes to (p's, where the row's K and V
    land too): a row that crosses a block boundary carries its running state
    into the new block's slot. Returns ``(logits [T, vocab], caches, aux)``:
    ``aux["rows"]`` [T, sites, k] the experts every row chose at every layer IN
    THIS STEP (with ``route_tail``, followed by the sets the tokens before it
    chose in theirs, as the cache kept them: ``kimi_linear.py``), and
    ``aux["counters"]``: ``moe_pairs``, ``moe_distinct_experts``,
    ``moe_streamed_experts`` (``afmoe.verify_step_ragged``'s),
    ``moe_held_pairs`` (the real rows' (row, choice) pairs that fall on the
    experts held here) and ``state_carries``, the real rows that crossed into
    a new block. ``caches`` is donated."""
    if block_tables.ndim != 2 or block_tables.shape[1] != max_blocks:
        raise ValueError(f"block_tables must be [B, {max_blocks}], got {block_tables.shape}")
    bt = config.block_tokens
    x = _embed(params, tokens, config)
    row_tables = jnp.take(block_tables, row_of, axis=0)
    at = lambda pos: jnp.take_along_axis(row_tables, (pos // bt)[:, None], axis=1)[:, 0]
    dst = at(positions)
    src = at(jnp.maximum(positions - 1, 0))
    fresh = positions == 0
    slots = positions % bt

    mamba_fn = jax.jit(_wave_mamba, static_argnames=("config",))
    attention_fn = jax.jit(_wave_attention, static_argnames=("config",))
    new_caches: Caches = []
    chosen, counts = [], expert_counts()
    before = None
    for layer, cache in enumerate(caches):
        w = _layer_weights(params, layer)
        cache, routes = _split_routes(cache, layer, config)
        if config.layer_types[layer] == ATTENTION:
            x, *cache, ids, n = attention_fn(
                w, x, *cache, dst, slots, row_tables, positions + 1, pages, page_rows,
                page_starts, config=config,
            )
        else:
            x, *cache, ids, n = mamba_fn(w, x, *cache, src, dst, fresh, config=config)
        cache = tuple(cache)
        chosen.append(ids)
        counts = jax.tree.map(jnp.add, counts, n)
        if routes is not None:
            # Each row's tail moves on by its own sets, as its state does.
            t, tail = tokens.shape[0], config.route_tail
            before = jnp.where(fresh[:, None, None], -1, routes[src]).reshape(t, tail, -1)
            mine = jnp.stack(chosen, axis=1).reshape(t, 1, -1)
            kept = jnp.concatenate([before[:, 1:], mine], axis=1)
            cache += (routes.at[dst].set(kept.reshape(t, *routes.shape[1:])),)
        new_caches.append(cache)
    logits = _head(params, x, config)
    real = jnp.concatenate([
        jnp.ones((1,), bool),
        (positions[1:] != positions[:-1]) | (row_of[1:] != row_of[:-1]),
    ])
    rows = jnp.stack(chosen, axis=1)  # [T, sites, k]
    first, count = config.held
    held = (rows >= first) & (rows < first + count) & real[:, None, None]
    k = config.experts_per_token
    if before is not None:
        # ... and the sets of the tokens before each row, the nearest first.
        rows = jnp.concatenate([rows, before[:, ::-1].reshape(rows.shape[0], -1, k)], axis=1)
    aux = {
        "rows": rows,  # [T, sites x (1 + route_tail), k]
        "counters": {
            "moe_pairs": jnp.sum(real, dtype=jnp.int32) * (len(chosen) * k),
            **counts,
            "moe_held_pairs": jnp.sum(held, dtype=jnp.int32),
            "state_carries": jnp.sum(real & (slots == 0) & ~fresh, dtype=jnp.int32),
        },
    }
    return logits, new_caches, aux
