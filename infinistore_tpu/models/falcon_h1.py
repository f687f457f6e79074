"""A decoder of the ``falcon_h1`` family on the paged serving path: a Mamba-2
mixer BESIDE grouped-query attention in every layer, one norm feeding both,
then a SwiGLU MLP; a scalar multiplier at every projection.

The equations are the published configuration's and modelling code's;
``benchmarks/reference_falcon_h1.py`` writes the same ones out in plain
float32, the recurrence a token at a time. Pre-norm:

  x_0     = embedding_multiplier E[token]
  n       = rms(x; w_in)
  h       = x + ssm_out_multiplier Mamba(n) + attention_out_multiplier Attn(attention_in_multiplier n)
  y       = h + MLP(rms(h; w_pre_mlp))
  Attn    : q = Wq a, k = key_multiplier Wk a, v = Wv a; rotary on q and k (angles
            in float32); causal softmax(q k^T / sqrt(D)) v, H / KVH query heads a
            KV head; Wo. The cache holds the rotated, multiplied k, and v.
  Mamba   : u = W_in (ssm_in_multiplier n) = [z | x | B | C | dt], each segment
            times its ``ssm_multipliers`` entry; [x, B, C] <- silu(conv4([x, B,
            C]) + b) (causal, depth-wise); dt = softplus(dt + dt_bias);
            S_t = exp(-exp(A_log) dt) S_{t-1} + dt x_t B_t^T, o_t = S_t C_t + D x_t
            per head (``tpu/ssd.py``; B and C shared by a group of heads);
            W_out (rms_group(o silu(z)) w): the gate first, then a norm over
            each group's channels
  MLP     : mlp_multipliers[1] Wdown (silu(mlp_multipliers[0] Wgate m) Wup m)
  logits  = lm_head_multiplier Whead rms(x_L; w_final)

The cache (``kv_spec``): EVERY layer's tuple is ``(k, v, state, tail)``, four
named per-block tensors whose leading axis is the block. ``k``, ``v``
``[blocks, block_tokens, KVH, D]`` are the pages the two attention kernels
walk (``tpu/paged_attention.py``, ``tpu/chunk_attention.py``: the block is
the state's snapshot interval, so a page is 1,024 tokens, and one page is one
grid step of either); ``state`` ``[blocks, H_s, P, N]`` float32 and ``tail``
(the last ``taps - 1`` rows before the convolution, folded to 128 lanes) are
what the mixer holds after the block's last token, the RUNNING ones while the
block is a request's last. A hit installs every block's K and V and the LAST
block's state and tail; every block saves all four.

A token is absorbed into a state once, so nothing here may compute a position
twice: the engine lands a prompt's last token in the first wave alone
(``PagedKVCacheSpec.has_state``), a chunk lies inside one block, and a wave's
row reads its state from the block of position p - 1 and writes the block of
p (``kimi_linear.py`` says the same of its state; rows that repeat their
predecessor, a wave's padding, read and write the same bytes).

The three serving entries keep the names the trace readers match: ``prefill``
(a miss: ``serving.prefill_by_blocks``, the prompt cut at block boundaries
through ``resume_chunk``), ``resume_chunk`` and ``verify_step_ragged``; each
donates ``caches``.
"""

import functools
from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..tpu import kda, ssd
from ..tpu.chunk_attention import chunk_prefix_attention
from ..tpu.paged import CacheTensor, PagedKVCacheSpec
from ..tpu.paged_attention import paged_decode_attention_rows
from .layers import layer_weights, rms, rope, set_slots, slots_of
from .serving import (
    ServingSteps, chunk_index, prefill_by_blocks, real_rows, resume_step, wave_index, wave_sources,
)

Params = Dict[str, jax.Array]
Caches = List[Tuple[jax.Array, ...]]


@dataclass(frozen=True)
class FalconH1Config:
    vocab: int = 512
    dim: int = 64
    n_layers: int = 2
    # attention
    n_heads: int = 10
    n_kv_heads: int = 2
    head_dim: int = 16
    rope_theta: float = 1e11
    # the Mamba-2 mixer
    ssm_width: int = 64  # mamba_d_ssm = ssm_heads x ssm_head_dim
    ssm_heads: int = 4
    ssm_head_dim: int = 16
    ssm_state: int = 32
    ssm_groups: int = 2
    conv_taps: int = 4
    ssm_chunk: int = 16
    ffn_dim: int = 160
    rms_eps: float = 1e-5
    # the family's scalars, each applied where the module docstring says
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5  # z, x, B, C, dt
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)  # gate, down
    block_tokens: int = 32
    dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        # The published 1e11 is written as an integer past 32 bits.
        object.__setattr__(self, "rope_theta", float(self.rope_theta))
        for name in ("ssm_multipliers", "mlp_multipliers"):
            object.__setattr__(self, name, tuple(float(m) for m in getattr(self, name)))
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has five entries (z, x, B, C, dt), mlp_multipliers two")
        if self.ssm_width != self.ssm_heads * self.ssm_head_dim:
            raise ValueError(
                f"mamba_d_ssm {self.ssm_width} is not {self.ssm_heads} heads of {self.ssm_head_dim}"
            )
        if self.ssm_heads % self.ssm_groups or self.n_heads % self.n_kv_heads:
            raise ValueError("heads are shared out in whole groups")
        if self.block_tokens % self.ssm_chunk:
            raise ValueError(
                f"a block of {self.block_tokens} tokens is no whole number of {self.ssm_chunk}-token chunks"
            )

    @property
    def conv_width(self) -> int:
        """Channels the convolution passes over: x, B and C side by side."""
        return self.ssm_width + 2 * self.ssm_groups * self.ssm_state

    @property
    def in_width(self) -> int:
        """The in-projection's outputs: z | x | B | C | dt."""
        return self.ssm_width + self.conv_width + self.ssm_heads

    @property
    def tail_shape(self) -> Tuple[int, int]:
        """The convolution tail's ``[taps - 1, conv_width]`` rows as the cache
        keeps them: folded to 128 lanes where they divide (``kimi_linear``'s
        reason: the array then lies row-major on the chip)."""
        total = (self.conv_taps - 1) * self.conv_width
        lanes = 128 if total % 128 == 0 else self.conv_width
        return (total // lanes, lanes)

    def layer_cache(self) -> Tuple[CacheTensor, ...]:
        page = (self.block_tokens, self.n_kv_heads, self.head_dim)
        return (
            CacheTensor("k", page, self.dtype, None, "kv"),
            CacheTensor("v", page, self.dtype, None, "kv"),
            CacheTensor(
                "state", (self.ssm_heads, self.ssm_head_dim, self.ssm_state), jnp.float32, 1, "state"
            ),
            CacheTensor("tail", self.tail_shape, self.dtype, 1, "state"),
        )

    def kv_spec(self, num_blocks: int) -> PagedKVCacheSpec:
        return PagedKVCacheSpec.of_layers(
            num_blocks, self.block_tokens, [self.layer_cache()] * self.n_layers
        )

    @property
    def steps(self) -> ServingSteps:
        return ServingSteps(prefill, prefill_continue, verify_step_ragged)

    # What the wave step counts and returns with its logits (serving.py): the
    # rows whose state crossed into a new block (engine metrics).
    step_counters = ("state_carries",)


def init_params(config: FalconH1Config, key: jax.Array) -> Params:
    """Seeded 1/sqrt(fan_in) normal weights as a flat dict (layer-prefixed
    keys), norms at one, the convolution's bias at zero; ``A_log`` the log of a
    uniform draw from [1, 16] a head, ``dt_bias`` the inverse softplus of a
    log-uniform draw from [0.001, 0.1] a head and ``D`` ones: the Mamba-2
    modelling code's initialisation."""
    keys = iter(jax.random.split(key, 2 + 12 * config.n_layers))
    f32 = jnp.float32

    def dense(k, shape, fan_in):
        w = jax.random.normal(k, shape, dtype=f32) / np.sqrt(fan_in)
        return w.astype(config.dtype)

    ones = lambda n: jnp.ones((n,), dtype=config.dtype)
    d, h, kvh, hd = config.dim, config.n_heads, config.n_kv_heads, config.head_dim
    p: Params = {
        "embed": dense(next(keys), (config.vocab, d), config.vocab),
        "final_norm": ones(d),
        "lm_head": dense(next(keys), (d, config.vocab), d),
    }
    for layer in range(config.n_layers):
        pre = f"l{layer}."
        p[pre + "in_norm"], p[pre + "pre_mlp_norm"] = ones(d), ones(d)
        p[pre + "wq"] = dense(next(keys), (d, h, hd), d)
        p[pre + "wk"] = dense(next(keys), (d, kvh, hd), d)
        p[pre + "wv"] = dense(next(keys), (d, kvh, hd), d)
        p[pre + "wo"] = dense(next(keys), (h * hd, d), h * hd)
        p[pre + "w_in"] = dense(next(keys), (d, config.in_width), d)
        p[pre + "conv_w"] = dense(next(keys), (config.conv_taps, config.conv_width), config.conv_taps)
        p[pre + "conv_b"] = jnp.zeros((config.conv_width,), config.dtype)
        p[pre + "A_log"] = jnp.log(jax.random.uniform(next(keys), (config.ssm_heads,), f32, 1.0, 16.0))
        dt = jnp.exp(jax.random.uniform(
            next(keys), (config.ssm_heads,), f32, np.log(1e-3), np.log(1e-1)
        ))
        p[pre + "dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
        p[pre + "D"] = jnp.ones((config.ssm_heads,), f32)
        p[pre + "ssm_norm"] = ones(config.ssm_width)
        p[pre + "w_out"] = dense(next(keys), (config.ssm_width, d), config.ssm_width)
        p[pre + "w_gate_up"] = dense(next(keys), (d, 2, config.ffn_dim), d)
        p[pre + "w_down"] = dense(next(keys), (config.ffn_dim, d), config.ffn_dim)
    return p


def _scaled(x, multiplier: float):
    """x times a configuration's scalar, in float32, rounded once to x's type."""
    if multiplier == 1.0:
        return x
    return (x.astype(jnp.float32) * np.float32(multiplier)).astype(x.dtype)


def _embed(params: Params, tokens: jax.Array, config: FalconH1Config) -> jax.Array:
    # [T, dim] float32: the residual stream, carried unrounded within a step.
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    return x * np.float32(config.embedding_multiplier)


def _head(params: Params, x: jax.Array, config: FalconH1Config) -> jax.Array:
    x = rms(x, params["final_norm"], config.rms_eps, config.dtype)
    return _scaled(jnp.dot(x, params["lm_head"]), config.lm_head_multiplier)


def _mlp(w: Params, h, config: FalconH1Config):
    """h + MLP(rms(h)) on h: [T, dim] float32."""
    m = rms(h, w["pre_mlp_norm"], config.rms_eps, config.dtype)
    gate_up = jnp.einsum("td,dcf->tcf", m, w["w_gate_up"])
    gate, down = config.mlp_multipliers
    act = jax.nn.silu(_scaled(gate_up[:, 0], gate)) * gate_up[:, 1]
    return h + jnp.dot(act, w["w_down"]).astype(jnp.float32) * np.float32(down)


def _qkv(w: Params, n, positions, config: FalconH1Config):
    """The attention's inputs from the normed n: [T, dim]: q [T, H, D] and
    the cache's rows k, v [T, KVH, D], q and k rotated (``layers.rope``: the
    angles in float32), k multiplied; rounded once, after the rotation."""
    f32 = jnp.float32
    a = _scaled(n, config.attention_in_multiplier)
    project = lambda name: jnp.einsum("td,dhk->thk", a, w[name], preferred_element_type=f32)
    q = rope(project("wq"), positions, config.rope_theta)
    k = rope(project("wk") * np.float32(config.key_multiplier), positions, config.rope_theta)
    return q.astype(config.dtype), k.astype(config.dtype), project("wv").astype(config.dtype)


def _ssm_inputs(w: Params, n, tail, config: FalconH1Config):
    """The mixer's inputs from the normed n: [T, dim]. ``tail``: [taps - 1,
    conv_width] the rows before the convolution that came before n's (per ROW
    where it is [T, taps - 1, conv_width]: a wave, each row a request of its
    own). Returns x [T, H_s, P], B and C [T, G, N] in the served type, dt [T,
    H_s] float32 after its softplus, the gate z [T, ssm_width] float32 and the
    new tail(s)."""
    f32 = jnp.float32
    t = n.shape[0]
    width, conv, heads = config.ssm_width, config.conv_width, config.ssm_heads
    z_m, x_m, b_m, c_m, dt_m = config.ssm_multipliers
    group = config.ssm_groups * config.ssm_state
    scale = np.concatenate([
        np.full(width, z_m), np.full(width, x_m), np.full(group, b_m), np.full(group, c_m),
        np.full(heads, dt_m),
    ]).astype(np.float32)
    u = jnp.dot(_scaled(n, config.ssm_in_multiplier), w["w_in"]).astype(f32) * scale
    z, pre, dt = u[:, :width], u[:, width : width + conv].astype(config.dtype), u[:, width + conv :]
    y, new_tail = kda.short_conv(pre, tail, w["conv_w"])  # a wave's tails: one a row
    y = jax.nn.silu(y + w["conv_b"].astype(f32)).astype(config.dtype)
    x = y[:, :width].reshape(t, heads, config.ssm_head_dim)
    b = y[:, width : width + group].reshape(t, config.ssm_groups, config.ssm_state)
    c = y[:, width + group :].reshape(t, config.ssm_groups, config.ssm_state)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    return x, b, c, dt, z, new_tail


def _ssm_out(w: Params, o, z, config: FalconH1Config):
    """W_out (rms_group(o silu(z)) w) on o: [T, H_s, P] float32: the gate
    first, then the norm over each group's channels. [T, dim] float32."""
    t = o.shape[0]
    y = o.reshape(t, config.ssm_groups, -1) * jax.nn.silu(z).reshape(t, config.ssm_groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + config.rms_eps)
    y = (y.reshape(t, -1) * w["ssm_norm"].astype(jnp.float32)).astype(config.dtype)
    return jnp.dot(y, w["w_out"]).astype(jnp.float32)


def _mix(w: Params, x, mamba, attn, config: FalconH1Config):
    """The layer's first half closed: both mixers' outputs scaled and added to
    the stream unnormed. attn: [T, H, D]."""
    a = jnp.dot(attn.reshape(x.shape[0], -1), w["wo"]).astype(jnp.float32)
    return (
        x + np.float32(config.ssm_out_multiplier) * mamba
        + np.float32(config.attention_out_multiplier) * a
    )


# ---------------------------------------------------------------------------
# The three serving entries (serving.py). Each DONATES ``caches``.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("caches",))
def resume_chunk(
    params: Params, tokens, start_pos, caches: Caches, block_table, config: FalconH1Config
) -> Tuple[jax.Array, Caches]:
    """ONE request's chunk at contiguous positions INSIDE ONE BLOCK (the
    caller cuts at block boundaries): a hit's question, and every piece of a
    miss's prefill. Each layer writes the chunk's K and V into the block's page
    and attends the table's pages (``chunk_prefix_attention``), takes the state
    and the tail of the block of position ``start_pos - 1`` (zeros at a
    prompt's start) and leaves the ones after its last token in the chunk's
    own block. Returns (the LAST row's logits [1, vocab], caches): the engine
    takes a first token from the first wave, never from a chunk, and the head
    over every row of a 1,024-token piece would be two fifths of its work at
    the published widths. ``caches`` is donated."""
    bt = config.block_tokens
    block, before, fresh = chunk_index(tokens, start_pos, block_table, bt)
    positions = start_pos + jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = _embed(params, tokens, config)
    new_caches: Caches = []
    for layer, (k_cache, v_cache, states, tails) in enumerate(caches):
        w = layer_weights(params, layer)
        n = rms(x, w["in_norm"], config.rms_eps, config.dtype)
        q, k, v = _qkv(w, n, positions, config)
        # The chunk lies in one block: one slice written in place.
        at = (block, start_pos % bt, 0, 0)
        k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype)[None], at)
        v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype)[None], at)
        attn = chunk_prefix_attention(q, k_cache, v_cache, block_table, start_pos)
        state = jnp.where(fresh, 0.0, states[before])
        tail = jnp.where(fresh, jnp.zeros((), tails.dtype), tails[before])
        xs, b, c, dt, z, tail = _ssm_inputs(
            w, n, tail.reshape(config.conv_taps - 1, -1), config
        )
        o, state = ssd.ssd_chunk(xs, dt, w["A_log"], b, c, w["D"], state, chunk=config.ssm_chunk)
        states = states.at[block].set(state)
        tails = tails.at[block].set(tail.astype(tails.dtype).reshape(tails.shape[1:]))
        x = _mlp(w, _mix(w, x, _ssm_out(w, o, z, config), attn, config), config)
        new_caches.append((k_cache, v_cache, states, tails))
    return _head(params, x[-1:], config), new_caches


prefill_continue = resume_step(resume_chunk)
prefill = prefill_by_blocks(resume_chunk)


def _wave_layer(
    w: Params,  # ONE layer's weights (_layer_weights)
    x: jax.Array,  # [T, dim] float32 activations entering the layer
    positions, k_cache, v_cache, states, tails,
    src, dst, fresh, slots, row_tables, seq_lens, pages, page_rows, page_starts,
    config: FalconH1Config,
):
    """ONE layer of the wave body on T flat rows, each a request of its own:
    insert the rows' K and V and attend each row's pages (the ragged decode
    kernel), move each row's state on by its token (from block ``src`` to
    block ``dst``), add both, then the MLP. ``verify_step_ragged`` runs it a
    layer under one ``jax.jit`` of its own, so the layers share one traced and
    one lowered function."""
    n = rms(x, w["in_norm"], config.rms_eps, config.dtype)
    q, k, v = _qkv(w, n, positions, config)
    k_cache = k_cache.at[dst, slots].set(k.astype(k_cache.dtype))
    v_cache = v_cache.at[dst, slots].set(v.astype(v_cache.dtype))
    attn = paged_decode_attention_rows(
        q, k_cache, v_cache, row_tables, seq_lens, pages, page_rows, page_starts
    )
    # A row a slice, read and written in place (``layers.slots_of``).
    state = jnp.where(fresh[:, None, None, None], 0.0, slots_of(states, src))
    tail = jnp.where(fresh[:, None, None], jnp.zeros((), tails.dtype), slots_of(tails, src))
    xs, b, c, dt, z, tail = _ssm_inputs(
        w, n, tail.reshape(x.shape[0], config.conv_taps - 1, -1), config
    )
    o, state = ssd.ssd_step(xs, dt, w["A_log"], b, c, w["D"], state)
    states = set_slots(states, dst, state)
    tails = set_slots(tails, dst, tail.reshape(-1, *tails.shape[1:]))
    x = _mlp(w, _mix(w, x, _ssm_out(w, o, z, config), attn, config), config)
    return x, k_cache, v_cache, states, tails


@functools.partial(
    jax.jit, static_argnames=("config", "max_blocks"), donate_argnames=("caches",)
)
def verify_step_ragged(
    params: Params, tokens, positions, row_of, pages, page_rows, page_starts, caches: Caches,
    block_tables, config: FalconH1Config, max_blocks: int,
):
    """THE wave body (``serving.py``: ``wave``'s contract and argument
    order). ONE table serves both halves of a row: its flat page list (built
    from the table on the host) is what its attention walks, and by its
    position the table names the block its state comes from (position p - 1's)
    and the block it goes to (p's, where its K and V land too): a row that
    crosses a block boundary carries its running state into the new block's
    slot. Returns ``(logits [T, vocab], caches, aux)``; ``aux["counters"]``:
    ``state_carries``, the real rows that crossed into a new block. ``caches``
    is donated."""
    bt = config.block_tokens
    x = _embed(params, tokens, config)
    row_tables, dst, slots = wave_index(positions, row_of, block_tables, max_blocks, bt)
    src, fresh = wave_sources(positions, row_tables, bt)

    layer_fn = jax.jit(_wave_layer, static_argnames=("config",))
    new_caches: Caches = []
    for layer, cache in enumerate(caches):
        x, *cache = layer_fn(
            layer_weights(params, layer), x, positions, *cache, src, dst, fresh, slots,
            row_tables, positions + 1, pages, page_rows, page_starts, config=config,
        )
        new_caches.append(tuple(cache))
    real = real_rows(positions, row_of)
    aux = {"counters": {
        "state_carries": jnp.sum(real & (slots == 0) & ~fresh, dtype=jnp.int32),
    }}
    return _head(params, x, config), new_caches, aux
