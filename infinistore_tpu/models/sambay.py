"""A decoder of the ``phi4flash`` family ("SambaY", a decoder-hybrid-decoder)
on the paged serving path: a SELF-DECODER whose layers alternate a Mamba-1
mixer on a recurrent state and a sliding-window attention, ONE full-attention
layer whose K and V are the model's only growing cache, and a CROSS-DECODER
whose layers alternate a gated memory unit (no cache at all) and an attention
with a query projection alone, which reads that one layer's K and V. No
position encoding anywhere; every attention is DIFFERENTIAL (two softmaxes a
head pair, one subtracted from the other); a dense MLP behind every mixer.

The equations are the published modelling code's and the paper's
(arXiv:2507.06607); ``benchmarks/reference_sambay.py`` writes the same ones
out in plain float32, the recurrence a token at a time, the four softmaxes of
a head pair one by one. With L layers and ``full = L / 2 + 1``:

  x_0     = E[token]
  x       = x + Mixer_l(LN(x; w, b));  x = x + MLP_l(LN(x; w, b))
  MLP     : W_2 (u silu(g)), [g, u] = W_1 h
  kinds   : l < full  even: Mamba-1, odd: sliding differential attention;
            l = full  full differential attention: its K, V are THE cache;
            l > full  even (l % mb_per_layer == 0): gated memory unit,
                      odd: cross differential attention (Wq alone)
  Mamba-1 : [u, z] = W_in h; u <- silu(conv4(u) + b) (causal, depth-wise);
            [r, B, C] = W_x u; dt = softplus(W_dt r + b_dt); the selective
            scan of ``tpu/selective_scan.py`` (A = -exp(A_log), + D u) gives
            y; out = W_out (y silu(z)). The LAST Mamba layer (l = full - 1)
            also hands on m = y, before the gate: the cross-decoder's memory
  GMU     : W_out (m silu(W_in h)): token t reads m_t of its own position
  DiffAttn: heads pair up, (q1, q2) = heads (2p, 2p + 1), K/V heads (2g, 2g
            + 1) = (k1, k2), (v1, v2), pair p reads group g = p // (pairs /
            groups); V_g = [v1 | v2]; a1 = softmax(q1 k1^T / sqrt(D)) V_g, a2
            the same of q2, k2; o_p = (1 - L0) rms(a1 - lam a2; w),
            lam = exp(lq1 . lk1) - exp(lq2 . lk2) + L0,
            L0 = 0.8 - 0.6 exp(-0.3 l); the o_p side by side into W_o (+ b).
            Causal; a sliding layer's row at t reads keys t - window < s <= t
  logits  = LN(x_L; w, b) E^T                      (the embedding, tied)

**The pair layout.** A K/V pair is kept as ONE head of 2 D: ``[k1 | k2]``,
``[v1 | v2]``, and ``q1`` goes in as ``[q1 | 0]``, ``q2`` as ``[0 | q2]``: 2 P
query heads of 2 D over G K/V heads, whole groups, the shape every K/V kernel
here takes. ``[q1 | 0] . [k1 | k2] = q1 . k1``, and the value read is V_g whole,
so the kernels return ``a1`` and ``a2`` of every pair as they are and read the
model's bytes exactly. They scale by 1 / sqrt(2 D), so q carries sqrt(2),
multiplied in float32 before its one rounding.

**What a prompt step leaves out.** Rows of the layers past ``full`` do not
interact (a cross layer reads layer ``full``'s K and V, a memory unit its own
``m_t``), and layer ``full``'s own attention output feeds only them. So a row
whose logits nobody reads stops after layer ``full - 1`` and ``K, V`` of layer
``full``: ``resume_chunk`` / ``prefill`` compute ``full`` layers and one K/V
projection of L, no attention over the prefix at all, and return ``(None,
caches)`` (``serving.py``: a prompt step may). The wave's logits are those of
the full forward pass: that is mathematics, not a tolerance.

The cache (``kv_spec``) has ``full + 1`` layers, fewer than the model:
  Mamba layer    ``(state, tail)``: ``state`` ``[N, C]`` float32 (N-major, the
                 channels on the lanes) and ``tail``, the last ``taps - 1``
                 rows before the convolution folded to 128 lanes, as the mixer
                 holds them after the block's last token; kind ``state``,
                 ``last_blocks 1``.
  sliding layer  ``(k_tail, v_tail)``: the last ``window`` positions' K and V,
                 ``[window, G x 2 D]``, position p at row ``p % window``: a
                 TAIL, kept a block as a state is kept (kind ``state``,
                 ``last_blocks 1``), not pages: the layer's memory does not
                 grow with the context. A block ends on a multiple of the
                 window, so its slot's rows are in position order and a saved
                 value does not depend on the path that made it.
  layer ``full`` ``(k, v)`` pages ``[block_tokens, G, 2 D]`` (kept with the
                 first two axes folded, ``[block_tokens x G, 2 D]``: the same
                 bytes), every block, kind ``kv``: what the wave's ``L / 4``
                 reading layers walk.
  the layers past it: nothing.

A token is absorbed into a state once: the engine lands a prompt's last token
in the first wave alone (``PagedKVCacheSpec.has_state``), a chunk lies inside
one block and starts on a multiple of the window (block boundaries are), and a
wave's row reads state and tails from the block of position p - 1 and writes
the block of p. The serving entries keep the names the trace readers match:
``prefill``, ``resume_chunk`` and ``verify_step_ragged``; each donates
``caches``.
"""

import functools
from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..tpu import kda, paged
from ..tpu.flash_prefill import flash_prefill_attention
from ..tpu.paged import CacheTensor, PagedKVCacheSpec
from ..tpu.paged_attention import paged_decode_attention_rows
from ..tpu.selective_scan import selective_scan_chunk, selective_scan_step
from .layers import (
    embed, folded_tail_shape, layer_weights, set_slots, slots_of, tail_folded, tail_rows,
)
from .serving import (
    ServingSteps, chunk_index, prefill_by_blocks, real_rows, resume_step, wave_index, wave_sources,
)

Params = Dict[str, jax.Array]
Caches = List[Tuple[jax.Array, ...]]

MAMBA, SLIDING, FULL, GMU, CROSS = "mamba", "sliding", "full", "gmu", "cross"
_NEG = -1e30


@dataclass(frozen=True)
class SambaYConfig:
    vocab: int = 512
    dim: int = 64
    n_layers: int = 8
    n_heads: int = 8
    n_kv_heads: int = 4
    ffn_dim: int = 128
    sliding_window: int = 8
    mb_per_layer: int = 2
    # the Mamba-1 mixer: ``ssm_expand`` x dim channels
    ssm_state: int = 4
    conv_taps: int = 4
    ssm_expand: int = 2
    dt_rank: int = 4
    norm_eps: float = 1e-5
    block_tokens: int = 16
    dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        if self.n_layers % 4 or self.mb_per_layer != 2:
            raise ValueError("the stack is whole periods of four layers, a scan or memory layer every second")
        if self.dim % self.n_heads or self.n_heads % 2 or self.n_kv_heads % 2 or self.n_heads % self.n_kv_heads:
            raise ValueError("heads pair up, and the pairs are shared out in whole groups")
        if self.block_tokens % self.sliding_window:
            raise ValueError(
                f"a block of {self.block_tokens} tokens is no whole number of {self.sliding_window}-token windows"
            )

    @property
    def full_layer(self) -> int:
        """The one full-attention layer, whose K and V the cross-decoder reads."""
        return self.n_layers // 2 + 1

    def kind(self, layer: int) -> str:
        scan = layer % self.mb_per_layer == 0
        if layer > self.full_layer:
            return GMU if scan else CROSS
        if layer == self.full_layer:
            return FULL
        return MAMBA if scan else SLIDING

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(self.kind(layer) for layer in range(self.n_layers))

    @property
    def cache_layers(self) -> int:
        """Layers that keep anything: the self-decoder and the full layer."""
        return self.full_layer + 1

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def pair_dim(self) -> int:
        """A head pair's width, the kernels' head: [k1 | k2]."""
        return 2 * self.head_dim

    @property
    def kv_pairs(self) -> int:
        return self.n_kv_heads // 2

    @property
    def kv_width(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def ssm_width(self) -> int:
        return self.ssm_expand * self.dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution passes over: the mixer's own (B and C are
        projected behind it)."""
        return self.ssm_width

    @property
    def tail_shape(self) -> Tuple[int, int]:
        """The convolution tail's rows as the cache keeps them."""
        return folded_tail_shape(self.conv_taps, self.conv_width)

    def lambda_init(self, layer: int) -> float:
        return 0.8 - 0.6 * float(np.exp(-0.3 * layer))

    def layer_cache(self, layer: int) -> Tuple[CacheTensor, ...]:
        kind = self.kind(layer)
        if kind == MAMBA:
            return (
                CacheTensor("state", (self.ssm_state, self.ssm_width), jnp.float32, 1, "state"),
                CacheTensor("tail", self.tail_shape, self.dtype, 1, "state"),
            )
        if kind == SLIDING:
            tail = (self.sliding_window, self.kv_width)
            return (
                CacheTensor("k_tail", tail, self.dtype, 1, "state"),
                CacheTensor("v_tail", tail, self.dtype, 1, "state"),
            )
        if kind == FULL:
            # [block_tokens, G, 2 D] with its first two axes folded: the bytes
            # the K/V kernels walk ([bt x KVH, D] a page), in a shape whose
            # layout on the chip is its own order (XLA:TPU lays a [.., bt, 10,
            # 128] array out KV-head-major and copies it whole for the kernel).
            page = (self.block_tokens * self.kv_pairs, self.pair_dim)
            return (CacheTensor("k", page, self.dtype, None, "kv"), CacheTensor("v", page, self.dtype, None, "kv"))
        raise ValueError(f"layer {layer} ({kind}) keeps nothing")

    def kv_spec(self, num_blocks: int) -> PagedKVCacheSpec:
        return PagedKVCacheSpec.of_layers(
            num_blocks, self.block_tokens,
            [self.layer_cache(layer) for layer in range(self.cache_layers)],
        )

    @property
    def steps(self) -> ServingSteps:
        return ServingSteps(prefill, prefill_continue, verify_step_ragged, resume_in_block=True)

    # What the wave step counts and returns with its logits (serving.py): the
    # real rows that ran the cross-decoder, and the real rows any program of
    # the model computed. A prompt piece's rows run the self-decoder alone:
    # the engine adds them to the second on the host (``prompt_rows_counter``).
    step_counters = ("cross_decoder_rows", "stack_rows")
    prompt_rows_counter = "stack_rows"


def init_params(config: SambaYConfig, key: jax.Array) -> Params:
    """Seeded 1/sqrt(fan_in) normal weights as a flat dict (layer-prefixed
    keys), the embedding 1/sqrt(vocab), norm weights one, biases zero;
    ``A_log = log(1 .. N)`` a channel, ``D`` ones and ``b_dt`` the inverse
    softplus of a log-uniform draw from [0.001, 0.1] a channel (the Mamba
    reference implementation's recurrence initialisation: memories of many
    lengths); the four lambda vectors normal(0, 0.1). The head is the
    embedding (tied)."""
    keys = iter(jax.random.split(key, 1 + 12 * config.n_layers))
    f32 = jnp.float32

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, dtype=f32) / np.sqrt(fan_in)).astype(config.dtype)

    ones = lambda *n: jnp.ones(n, dtype=config.dtype)
    zeros = lambda *n: jnp.zeros(n, dtype=config.dtype)
    d, h, kvh, hd = config.dim, config.n_heads, config.n_kv_heads, config.head_dim
    c, n, r, f = config.ssm_width, config.ssm_state, config.dt_rank, config.ffn_dim
    p: Params = {
        "embed": dense(next(keys), (config.vocab, d), config.vocab),
        "final_norm": ones(d),
        "final_norm_b": zeros(d),
    }
    for layer in range(config.n_layers):
        pre, kind = f"l{layer}.", config.kind(layer)
        for name in ("in_norm", "mlp_norm"):
            p[pre + name], p[pre + name + "_b"] = ones(d), zeros(d)
        if kind == MAMBA:
            p[pre + "w_in"] = dense(next(keys), (d, 2, c), d)
            p[pre + "conv_w"] = dense(next(keys), (config.conv_taps, c), config.conv_taps)
            p[pre + "conv_b"] = zeros(c)
            p[pre + "w_x"] = dense(next(keys), (c, r + 2 * n), c)
            p[pre + "w_dt"] = dense(next(keys), (r, c), r)
            dt = jnp.exp(jax.random.uniform(next(keys), (c,), f32, np.log(1e-3), np.log(1e-1)))
            p[pre + "b_dt"] = dt + jnp.log(-jnp.expm1(-dt))
            p[pre + "A_log"] = jnp.log(jnp.broadcast_to(jnp.arange(1, n + 1, dtype=f32), (c, n)))
            p[pre + "D"] = jnp.ones((c,), f32)
            p[pre + "w_out"] = dense(next(keys), (c, d), c)
        elif kind == GMU:
            p[pre + "w_in"] = dense(next(keys), (d, c), d)
            p[pre + "w_out"] = dense(next(keys), (c, d), c)
        else:
            p[pre + "wq"], p[pre + "bq"] = dense(next(keys), (d, h, hd), d), zeros(h, hd)
            if kind != CROSS:
                p[pre + "wk"], p[pre + "bk"] = dense(next(keys), (d, kvh, hd), d), zeros(kvh, hd)
                p[pre + "wv"], p[pre + "bv"] = dense(next(keys), (d, kvh, hd), d), zeros(kvh, hd)
            p[pre + "wo"], p[pre + "bo"] = dense(next(keys), (h * hd, d), h * hd), zeros(d)
            p[pre + "lambdas"] = 0.1 * jax.random.normal(next(keys), (4, hd), f32)  # lq1, lk1, lq2, lk2
            p[pre + "subln"] = jnp.ones((2 * hd,), f32)
        p[pre + "w1"] = dense(next(keys), (d, 2, f), d)
        p[pre + "w2"] = dense(next(keys), (f, d), f)
    return p


# Where the served type's rounding goes. bf16 keeps 8 bits, and at 32 layers
# the roundings of a float32 activation to it, before each product, part the
# program's logits from the float32 reference by 3.6% of the logits' rms where
# every product takes bf16 rows, against 2.5% allowed (my chip runs, PR 58).
# The weights are exactly bf16, so a float32 row can meet them EXACTLY as bf16
# pieces, one under the other, the results added (``_proj``). So no activation
# is rounded before a product, in a wave or in a piece; what the served type
# still rounds is what the cache keeps (K, V, their tails, the convolution's
# rows) and what the attention kernels are handed (q, k, v) and hand back:
# - a wave's rows are few and its products read the weights once whatever the
#   rows: three pieces a row, the whole float32 row, for nothing;
# - a piece's products are bound by the matrix unit: two pieces a row (16
#   bits), a second pass over the weights, which is what the 2.5% costs a miss
#   at this depth (PERF.md section 6, PR 58, has the readings).
_FEW_ROWS = 8


def _ln(x, w, b, config: SambaYConfig):
    """LayerNorm with weight and bias, in float32 and out."""
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + config.norm_eps)
    return x * w.astype(jnp.float32) + b.astype(jnp.float32)


def _proj(spec: str, x, w):
    """``einsum(spec, x, w)`` of rows x: [T, ...] in float32. Float32 rows
    against bf16 weights go in as bf16 PIECES of each row, one under the other
    (the row's rounding, then the rounding of what that left, ...), and the
    pieces' results are added: three pieces, the whole float32 row, where the
    rows are few (a wave), two (16 bits) where they are many (a piece). Rows
    already in the weights' type take one pass. A wave's lone row never goes
    alone: the vector unit reads the weights at a fraction of the matrix
    unit's rate (``llama._ffn``; PERF.md, PR 45)."""
    rows, f32 = x.shape[0], jnp.float32
    if x.dtype != f32 or w.dtype != jnp.bfloat16:
        if rows == 1:
            x = jnp.pad(x, ((0, 1),) + ((0, 0),) * (x.ndim - 1))
        return jnp.einsum(spec, x, w, preferred_element_type=f32)[:rows]
    pieces, rest = [], x
    for _ in range(3 if rows <= _FEW_ROWS else 2):
        # ``reduce_precision``, not a cast there and back: XLA:TPU keeps the
        # float32 value through such a pair (excess precision), what is left
        # is then zero, and the pieces are one bf16 pass (my chip run, PR 58).
        piece = jax.lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
        pieces.append(piece.astype(w.dtype))
        rest = rest - piece
    out = jnp.einsum(spec, jnp.concatenate(pieces), w, preferred_element_type=f32)
    return sum(out[i * rows : (i + 1) * rows] for i in range(len(pieces)))


def _head(params: Params, x: jax.Array, config: SambaYConfig) -> jax.Array:
    """The tied head: the embedding's rows against the normed stream."""
    x = _ln(x, params["final_norm"], params["final_norm_b"], config)
    return _proj("td,vd->tv", x, params["embed"]).astype(config.dtype)


def _mlp(w: Params, x, config: SambaYConfig):
    h = _ln(x, w["mlp_norm"], w["mlp_norm_b"], config)
    gate_up = _proj("td,dcf->tcf", h, w["w1"])
    return x + _proj("tf,fd->td", jax.nn.silu(gate_up[:, 0]) * gate_up[:, 1], w["w2"])


def _pair_queries(w: Params, n, config: SambaYConfig):
    """The normed n's queries in the pair layout: [T, H, 2 D], head 2 p
    ``[q1_p | 0]`` and head 2 p + 1 ``[0 | q2_p]``, carrying sqrt(2)."""
    t, hd = n.shape[0], config.head_dim
    q = (_proj("td,dhk->thk", n, w["wq"]) + w["bq"].astype(jnp.float32)) * np.float32(np.sqrt(2.0))
    q = q.astype(config.dtype).reshape(t, -1, 2, hd)
    zero = jnp.zeros_like(q[:, :, 0])
    halves = [jnp.concatenate([q[:, :, 0], zero], axis=-1), jnp.concatenate([zero, q[:, :, 1]], axis=-1)]
    return jnp.stack(halves, axis=2).reshape(t, config.n_heads, 2 * hd)


def _pair_keys_values(w: Params, n, config: SambaYConfig):
    """k, v [T, G, 2 D] of the normed n: heads (2 g, 2 g + 1) side by side."""
    shape = (n.shape[0], config.kv_pairs, config.pair_dim)
    k = _proj("td,dhk->thk", n, w["wk"]) + w["bk"].astype(jnp.float32)
    v = _proj("td,dhk->thk", n, w["wv"]) + w["bv"].astype(jnp.float32)
    return k.astype(config.dtype).reshape(shape), v.astype(config.dtype).reshape(shape)


def _diff_out(w: Params, x, attn, lambda_init, config: SambaYConfig):
    """x + W_o of the pairs' (1 - L0) rms(a1 - lam a2; w): attn [T, H, 2 D],
    head 2 p the pair's ``a1`` and head 2 p + 1 its ``a2``."""
    f32 = jnp.float32
    t = attn.shape[0]
    a = attn.astype(f32).reshape(t, -1, 2, config.pair_dim)
    lq1, lk1, lq2, lk2 = w["lambdas"].astype(f32)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lambda_init
    o = a[:, :, 0] - lam * a[:, :, 1]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + config.norm_eps)
    o = (o * w["subln"].astype(f32) * (1.0 - lambda_init)).reshape(t, -1)
    return x + _proj("tf,fd->td", o, w["wo"]) + w["bo"].astype(f32)


def _masked_attention(q, k, v, seen, config: SambaYConfig):
    """Plain attention of q [T, H, 2 D] over k, v [T or 1, S, G, 2 D] under
    ``seen`` [T, S], float32 throughout and out: the sliding tails' (a wave's
    rows, and a piece's off the chip)."""
    f32 = jnp.float32
    t, h, d = q.shape
    g = k.shape[2]
    mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST, preferred_element_type=f32)
    qg = q.reshape(t, g, h // g, d)
    scores = mm("tgjd,tsgd->tgjs", qg, jnp.broadcast_to(k, (t, *k.shape[1:])))
    scores = jnp.where(seen[:, None, None, :], scores / np.float32(np.sqrt(d)), _NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    out = mm("tgjs,tsgd->tgjd", probs, jnp.broadcast_to(v, (t, *v.shape[1:])).astype(f32))
    return out.reshape(t, h, d)


def _scan_inputs(w: Params, n, tail, config: SambaYConfig):
    """The scan's inputs from the normed n: [T, dim]. ``tail``: [taps - 1, C]
    the rows before the convolution that came before n's (per ROW where it is
    [T, taps - 1, C]: a wave). Returns u [T, C] float32, dt [T, C]
    float32 after its softplus, B and C [T, N] float32, the gate z [T, C]
    float32 and the new tail(s)."""
    f32 = jnp.float32
    uz = _proj("td,dcf->tcf", n, w["w_in"])
    # Float32 rows into the convolution; the cache rounds the tail it keeps.
    pre, z = uz[:, 0], uz[:, 1]
    y, new_tail = kda.short_conv(pre, tail, w["conv_w"])  # a wave's tails: one a row
    u = jax.nn.silu(y + w["conv_b"].astype(f32))
    rbc = _proj("tc,cr->tr", u, w["w_x"])
    r, st = config.dt_rank, config.ssm_state
    dt = jax.nn.softplus(_proj("tr,rc->tc", rbc[:, :r], w["w_dt"]) + w["b_dt"])
    return u, dt, rbc[:, r : r + st], rbc[:, r + st :], z, new_tail


def _scan_out(w: Params, x, y, z, config: SambaYConfig):
    return x + _proj("tc,cd->td", y * jax.nn.silu(z), w["w_out"])


def _gmu(w: Params, x, memory, config: SambaYConfig):
    """x + W_out (m silu(W_in LN(x))), then the MLP: each row reads ``memory``
    [T, C] float32 of its own position."""
    n = _ln(x, w["in_norm"], w["in_norm_b"], config)
    with jax.named_scope("sambay_memory_unit"):
        gate = jax.nn.silu(_proj("td,dc->tc", n, w["w_in"]))
        x = x + _proj("tc,cd->td", memory * gate, w["w_out"])
    return _mlp(w, x, config)


def _band_attention(q, k_old, v_old, k, v, start_pos, config: SambaYConfig):
    """A piece's rows q [S, H, 2 D] at positions ``start_pos ..`` over the
    window behind each: the ``window`` keys before the piece, k_old / v_old
    [window, G, 2 D] in position order (the newest last), and the piece's own
    k / v [S, G, 2 D]. On the chip the flash kernel's band (queries of zeros
    stand before the piece's so that rows and keys count from one origin; a
    prompt's first piece has no keys before it and runs alone); elsewhere
    plain masked attention."""
    s, win = q.shape[0], config.sliding_window
    if paged._use_pallas():
        flash = lambda q, k, v: flash_prefill_attention(q[None], k[None], v[None], causal=True, window=win)[0]

        def after(q):
            lead = jnp.zeros((win, *q.shape[1:]), q.dtype)
            keys, values = jnp.concatenate([k_old, k]), jnp.concatenate([v_old, v])
            return flash(jnp.concatenate([lead, q]), keys, values)[win:]

        return jax.lax.cond(start_pos == 0, lambda q: flash(q, k, v), after, q)
    keys, values = jnp.concatenate([k_old, k]), jnp.concatenate([v_old, v])
    key_pos = start_pos - win + jnp.arange(win + s)
    row_pos = start_pos + jnp.arange(s)
    seen = (key_pos[None] <= row_pos[:, None]) & (key_pos[None] > row_pos[:, None] - win) & (key_pos[None] >= 0)
    return _masked_attention(q, keys[None], values[None], seen, config)


def _tail_after(old, new, start_pos, config: SambaYConfig):
    """The K or V tail after a piece: row r the newest position p <= end - 1
    with p % window == r, the piece's where it holds p, else ``old``'s.
    old: [window, W]; new: [S, W] the piece's rows at ``start_pos ..``."""
    s, win = new.shape[0], config.sliding_window
    last = start_pos + s - 1
    p = last - (last - jnp.arange(win)) % win
    mine = jnp.take(new, jnp.clip(p - start_pos, 0, s - 1), axis=0)
    return jnp.where((p >= start_pos)[:, None], mine, old)


# ---------------------------------------------------------------------------
# The three serving entries (serving.py). Each DONATES ``caches``.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("caches",))
def resume_chunk(
    params: Params, tokens, start_pos, caches: Caches, block_table, config: SambaYConfig
) -> Tuple[None, Caches]:
    """ONE request's chunk at contiguous positions INSIDE ONE BLOCK: a hit's
    question, and every piece of a miss's prefill. The self-decoder and layer
    ``full``'s K/V projection, nothing else (module docstring): a Mamba layer
    takes the state and the tail of the block of position ``start_pos - 1``
    (zeros at a prompt's start) and leaves the ones after its last token in
    the chunk's own block; a sliding layer attends the tail it finds there and
    the chunk's own keys, and leaves the tail after its last token; layer
    ``full`` writes the chunk's K and V into the block's page and attends
    nothing. Returns ``(None, caches)``: a prompt step has no logits
    (``serving.py``). ``caches`` is donated."""
    s_c = tokens.shape[0]
    bt, win = config.block_tokens, config.sliding_window
    block, before, fresh = chunk_index(tokens, start_pos, block_table, bt)
    x = embed(params, tokens)
    new_caches: Caches = []
    for layer, cache in enumerate(caches):
        w = layer_weights(params, layer)
        n = _ln(x, w["in_norm"], w["in_norm_b"], config)
        kind = config.kind(layer)
        if kind == MAMBA:
            with jax.named_scope("sambay_mamba_mixer"):
                states, tails = cache
                state = jnp.where(fresh, 0.0, states[before])
                tail = jnp.where(fresh, jnp.zeros((), tails.dtype), tails[before])
                u, dt, b, c, z, tail = _scan_inputs(w, n, tail_rows(tail, config), config)
                y, state = selective_scan_chunk(u, dt, w["A_log"], b, c, w["D"], state)
                x = _scan_out(w, x, y, z, config)
                cache = (states.at[block].set(state), tails.at[block].set(tail_folded(tail, tails)))
        elif kind == SLIDING:
            with jax.named_scope("sambay_sliding_mixer"):
                k_tails, v_tails = cache
                k, v = _pair_keys_values(w, n, config)
                # Position order: the row of position start_pos - window first.
                ordered = lambda tail: jnp.roll(tail, -(start_pos % win), axis=0).reshape(win, *k.shape[1:])
                k_old, v_old = k_tails[before], v_tails[before]
                attn = _band_attention(
                    _pair_queries(w, n, config), ordered(k_old), ordered(v_old), k, v, start_pos, config
                )
                x = _diff_out(w, x, attn, config.lambda_init(layer), config)
                flat = lambda rows: rows.reshape(s_c, -1)
                cache = (
                    k_tails.at[block].set(_tail_after(k_old, flat(k), start_pos, config)),
                    v_tails.at[block].set(_tail_after(v_old, flat(v), start_pos, config)),
                )
        else:  # the full layer: its K and V, and the step is over
            with jax.named_scope("sambay_shared_kv"):
                k_cache, v_cache = cache
                k, v = _pair_keys_values(w, n, config)
                # The chunk lies in one block: one slice of the folded page.
                at = (block, start_pos % bt * config.kv_pairs, 0)
                fold = lambda rows: rows.reshape(1, -1, config.pair_dim)
                new_caches.append((
                    jax.lax.dynamic_update_slice(k_cache, fold(k), at),
                    jax.lax.dynamic_update_slice(v_cache, fold(v), at),
                ))
            break
        x = _mlp(w, x, config)
        new_caches.append(cache)
    return None, new_caches


prefill_continue = resume_step(resume_chunk)
prefill = prefill_by_blocks(resume_chunk)


def _wave_mamba(w: Params, x, states, tails, src, dst, fresh, config: SambaYConfig):
    """ONE Mamba layer of the wave body on T flat rows, each a request of its
    own: move each row's state on by its token (from block ``src`` to block
    ``dst``), then the MLP. Returns the scan's output before the gate too (the
    last Mamba layer's is the cross-decoder's memory)."""
    n = _ln(x, w["in_norm"], w["in_norm_b"], config)
    with jax.named_scope("sambay_mamba_mixer"):
        state = jnp.where(fresh[:, None, None], 0.0, slots_of(states, src))
        tail = jnp.where(fresh[:, None, None], jnp.zeros((), tails.dtype), slots_of(tails, src))
        u, dt, b, c, z, tail = _scan_inputs(w, n, tail_rows(tail, config), config)
        y, state = selective_scan_step(u, dt, w["A_log"], b, c, w["D"], state)
        states = set_slots(states, dst, state)
        tails = set_slots(tails, dst, tail_folded(tail, tails))
        x = _scan_out(w, x, y, z, config)
    return _mlp(w, x, config), states, tails, y


def _wave_sliding(w: Params, x, k_tails, v_tails, src, dst, positions, lambda_init, config: SambaYConfig):
    """ONE sliding layer of the wave body: each row's K and V go into its tail
    at ``position % window`` (the tail of block ``src`` moved to block
    ``dst``), the row attends the tail's rows that hold a position, then the
    MLP."""
    n = _ln(x, w["in_norm"], w["in_norm_b"], config)
    win, t = config.sliding_window, x.shape[0]
    with jax.named_scope("sambay_sliding_mixer"):
        k, v = _pair_keys_values(w, n, config)
        at = (jnp.arange(t), positions % win)
        k_tail = slots_of(k_tails, src).at[at].set(k.reshape(t, -1))
        v_tail = slots_of(v_tails, src).at[at].set(v.reshape(t, -1))
        k_tails, v_tails = set_slots(k_tails, dst, k_tail), set_slots(v_tails, dst, v_tail)
        # Row r holds the newest position <= the row's own with p % window == r.
        held = positions[:, None] - (positions[:, None] - jnp.arange(win)[None]) % win
        shape = (t, win, config.kv_pairs, config.pair_dim)
        attn = _masked_attention(
            _pair_queries(w, n, config), k_tail.reshape(shape), v_tail.reshape(shape), held >= 0, config
        )
        x = _diff_out(w, x, attn, lambda_init, config)
    return _mlp(w, x, config), k_tails, v_tails


def _wave_shared(w: Params, x, k_cache, v_cache, write, row_tables, seq_lens, pages, page_rows, page_starts,
                 lambda_init, config: SambaYConfig):
    """ONE layer of the wave body that attends layer ``full``'s pages: the
    full layer itself (``write``: (dst, slots), the rows' K and V inserted
    first) or a cross layer (``write`` None: a query projection alone), the
    ragged decode kernel over the SAME pages, then the MLP."""
    n = _ln(x, w["in_norm"], w["in_norm_b"], config)
    with jax.named_scope("sambay_shared_kv_attention"):
        if write is not None:
            k, v = _pair_keys_values(w, n, config)
            for t in range(x.shape[0]):  # a row's G heads lie side by side in the folded page
                at = (write[0][t], write[1][t] * config.kv_pairs, 0)
                k_cache = jax.lax.dynamic_update_slice(k_cache, k[t][None], at)
                v_cache = jax.lax.dynamic_update_slice(v_cache, v[t][None], at)
        unfold = lambda c: c.reshape(c.shape[0], config.block_tokens, config.kv_pairs, config.pair_dim)
        attn = paged_decode_attention_rows(
            _pair_queries(w, n, config), unfold(k_cache), unfold(v_cache), row_tables, seq_lens, pages,
            page_rows, page_starts,
        )
        x = _diff_out(w, x, attn, lambda_init, config)
    return _mlp(w, x, config), k_cache, v_cache


@functools.partial(
    jax.jit, static_argnames=("config", "max_blocks"), donate_argnames=("caches",)
)
def verify_step_ragged(
    params: Params, tokens, positions, row_of, pages, page_rows, page_starts, caches: Caches,
    block_tables, config: SambaYConfig, max_blocks: int,
):
    """THE wave body (``serving.py``: ``wave``'s contract and argument
    order): all the model's layers for every row. ONE table serves every
    kind: a row's flat page list is what layer ``full`` and the cross layers
    walk (the SAME pages, each with its own queries), and by its position the
    table names the block a state or a tail comes from (position p - 1's) and
    the block it goes to (p's, where the row's K and V land too). The memory
    units read the scan output of the wave's own last Mamba layer. Returns
    ``(logits [T, vocab], caches, aux)``; ``aux["counters"]``:
    ``cross_decoder_rows`` and ``stack_rows``, the wave's real rows (each ran
    the whole stack). ``caches`` is donated."""
    bt = config.block_tokens
    x = embed(params, tokens)
    # The slot is layer ``full``'s alone and is traced where it is used: traced
    # here it reorders the optimised program (tools/program_fingerprints.py).
    row_tables, dst, _ = wave_index(positions, row_of, block_tables, max_blocks, bt)
    src, fresh = wave_sources(positions, row_tables, bt)
    walk = (row_tables, positions + 1, pages, page_rows, page_starts)

    mamba_fn = jax.jit(_wave_mamba, static_argnames=("config",))
    sliding_fn = jax.jit(_wave_sliding, static_argnames=("config",))
    shared_fn = jax.jit(_wave_shared, static_argnames=("config",))
    gmu_fn = jax.jit(_gmu, static_argnames=("config",))
    new_caches: Caches = []
    memory = shared = None
    for layer, kind in enumerate(config.layer_kinds):
        w = layer_weights(params, layer)
        l0 = jnp.float32(config.lambda_init(layer))
        if kind == MAMBA:
            x, *cache, memory = mamba_fn(w, x, *caches[layer], src, dst, fresh, config=config)
        elif kind == SLIDING:
            x, *cache = sliding_fn(w, x, *caches[layer], src, dst, positions, l0, config=config)
        elif kind == FULL:
            x, *shared = shared_fn(w, x, *caches[layer], (dst, positions % bt), *walk, l0, config=config)
            cache = shared
        elif kind == CROSS:
            x, *_ = shared_fn(w, x, *shared, None, *walk, l0, config=config)
            continue
        else:
            x = gmu_fn(w, x, memory, config=config)
            continue
        new_caches.append(tuple(cache))
    logits = _head(params, x, config)
    rows = jnp.sum(real_rows(positions, row_of), dtype=jnp.int32)
    return logits, new_caches, {"counters": {"cross_decoder_rows": rows, "stack_rows": rows}}
