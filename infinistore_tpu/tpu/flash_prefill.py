"""Blocked causal (flash) attention for prefill: no S x S materialization.

Dense prefill attention materializes the full [H, S, T] float32 logits; at
long-context lengths that tensor alone exceeds HBM (32k tokens, 8 heads:
32GB). This kernel streams K/V block by block with the same online-softmax
(max, denominator, accumulator) recurrence the decode kernel uses
(paged_attention.py), so peak memory is O(BQ x BK) per grid step and every
K/V byte crosses HBM once per query block below the causal diagonal —
above-diagonal steps clamp their index map to the diagonal block (no fresh
fetch) and skip their compute entirely. It is the within-shard
complement of ring attention: ring shards the sequence across devices and
rotates K/V chunks (models/ring_attention.py); this kernel keeps each
shard's local attention from materializing its own S_loc^2 logits.

Layout: the grid is (B*H, S//BQ, T//BK) with the K index innermost, so the
scratch accumulators carry one query block's statistics across its K blocks
and reset when the K index wraps. GQA maps query row b*H + h to KV row
b*KVH + h//(H//KVH) inside the BlockSpec index maps — queries of one group
re-read their shared KV block from HBM (per-group dedup is a further
optimization; the asymptotics are already right).

Numeric contract as everywhere in this framework (models/llama.py
_attention): f32 softmax statistics, HIGHEST-precision dots, output cast to
the query dtype. Causal masking is by global position; fully-masked K
blocks contribute nothing (their probabilities are explicitly zeroed).
Forward-only: prefill/inference paths — the training loss keeps the dense
differentiable path (pallas_call is not autodifferentiated).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged

_NEG_INF = -1e30


def _block_and_padded(n: int, limit: int, align: int):
    """(block, padded length) for a sequence of ``n`` rows: the fewest
    blocks of at most ~``limit`` rows, each a multiple of ``align`` (the
    dtype's sublane tile — the TPU lowering rejects a second-to-last block
    dim that is not a multiple of 8, and 16-bit dtypes pack 16 rows per
    tile), with the sequence padded up to a whole number of blocks."""
    blocks = -(-n // limit)
    rows = -(-n // blocks)  # per block, before alignment
    block = -(-rows // align) * align
    return block, -(-n // block) * block


def _band_first_block(qb, bq: int, bk: int, window):
    """First K block a query block of a sliding layer still sees: the block
    of the oldest key of its first row (``qb * bq - window + 1``). The K axis
    of a windowed grid counts from here, so blocks wholly behind the band are
    no grid step at all."""
    return jnp.maximum(qb * bq - (window - 1), 0) // bk


def _flash_kernel(
    q_ref, k_ref, v_ref, out_ref, m_scr, l_scr, acc_scr, *, causal, kv_len,
    window=None,
):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    _, bq, d = q_ref.shape
    bk = k_ref.shape[1]
    step = kb  # the K axis's own count: the band's first block is step 0
    if window is not None:
        kb = kb + _band_first_block(qb, bq, bk, window)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if causal:
        # Steps strictly above the diagonal contribute nothing: their K/V
        # index maps are clamped to the diagonal block (so the pipeline
        # re-serves the resident block instead of a fresh HBM fetch) and
        # the whole update is skipped — without the skip the clamped block
        # would be double-counted.
        kb_max = (qb * bq + bq - 1) // bk

        @pl.when(kb <= kb_max)
        def _update():
            _flash_update(
                qb, kb, q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, causal, kv_len,
                window,
            )
    else:
        _flash_update(
            qb, kb, q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, causal, kv_len
        )

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        # Row 0 attends to at least itself under causal, so l >= 1; the
        # guard only matters for hypothetical fully-masked rows.
        out_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
        ).astype(out_ref.dtype)


def _flash_update(
    qb, kb, q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, causal, kv_len,
    window=None,
):
    _, bq, d = q_ref.shape
    bk = k_ref.shape[1]
    scale = 1.0 / np.sqrt(d)
    q = q_ref[0]  # [BQ, D] native dtype
    k = k_ref[0]  # [BK, D]
    v = v_ref[0]

    # Native-dtype operands with f32 accumulation: for bf16 models this is
    # ONE exact MXU pass per dot (casting to f32 first forces multi-pass
    # f32 matmuls — measured 6.5x slower end to end at 4k tokens); for f32
    # models HIGHEST keeps full f32 precision. Softmax statistics stay f32
    # either way. Mosaic rejects HIGHEST on bf16 operands ("Bad lhs type"),
    # so the precision is chosen by dtype — DEFAULT is already exact for
    # bf16 x bf16 -> f32.
    prec = (
        jax.lax.Precision.HIGHEST
        if q.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    logits = (
        jax.lax.dot_general(
            q,
            k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec,
        )
        * scale
    )  # [BQ, BK] f32
    # ``kv_len`` is set only when the wrapper padded K/V to a whole number
    # of blocks: the padded tail rows are zeros, not context.
    valid = None
    if causal or kv_len is not None:
        kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            qpos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            valid = qpos >= kpos
            if window is not None:
                valid = jnp.logical_and(valid, qpos - kpos < window)
        if kv_len is not None:
            in_ctx = kpos < kv_len
            valid = in_ctx if valid is None else jnp.logical_and(valid, in_ctx)
        logits = jnp.where(valid, logits, _NEG_INF)

    m_prev = m_scr[...]  # [BQ, 128]
    m_curr = jnp.max(logits, axis=1, keepdims=True)  # [BQ, 1]
    m_next = jnp.maximum(m_prev, m_curr)
    alpha = jnp.exp(m_prev[:, :1] - m_next[:, :1])
    p = jnp.exp(logits - m_next[:, :1])
    if valid is not None:
        # A fully-masked block leaves m_next at _NEG_INF and exp(0)=1 would
        # leak weight onto masked positions; zero those probabilities.
        p = jnp.where(valid, p, 0.0)
    l_next = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    # Probabilities ride in V's dtype for the PV pass (exact for f32
    # models; for bf16 models this is the standard flash-on-TPU choice —
    # one MXU pass, error at the model's own dtype scale).
    pv = jax.lax.dot_general(
        p.astype(v.dtype),
        v,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=prec,
    )  # [BQ, D] f32
    m_scr[...] = m_next
    l_scr[...] = jax.lax.broadcast_in_dim(l_next, l_scr.shape, (0, 1))
    acc_scr[...] = acc_scr[...] * alpha + pv


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret", "window")
)
def _flash_prefill_pallas(q, k, v, *, causal, block_q, block_k, interpret,
                          window=None):
    b, s, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    groups = h // kvh
    # Any length works: the sequence is padded with zero rows up to a whole
    # number of tile-aligned blocks (a 264-token bf16 prompt runs as two
    # 144-row blocks over 288 rows), padded keys are masked in the kernel,
    # and padded query rows are sliced off below. Lengths that already are
    # a whole number of aligned blocks (every multiple of 256, and every
    # multiple of 16 up to 256) pad nothing.
    align = 32 // jnp.dtype(q.dtype).itemsize
    bq, s_pad = _block_and_padded(s, block_q, align)
    bk, t_pad = _block_and_padded(t, block_k, align)
    # Head-major rows: [B*H, S, D] queries against [B*KVH, T, D] keys.
    qr = jnp.swapaxes(q, 1, 2).reshape(b * h, s, d)
    kr = jnp.swapaxes(k, 1, 2).reshape(b * kvh, t, d)
    vr = jnp.swapaxes(v, 1, 2).reshape(b * kvh, t, d)
    if s_pad != s:
        qr = jnp.pad(qr, ((0, 0), (0, s_pad - s), (0, 0)))
    if t_pad != t:
        kr = jnp.pad(kr, ((0, 0), (0, t_pad - t), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, t_pad - t), (0, 0)))

    def kv_row(bh):
        return (bh // h) * kvh + (bh % h) // groups

    k_steps = t_pad // bk
    if window is not None:
        # A sliding layer: the K axis counts from the band's first block, and
        # is as long as the widest band (a query block's rows and the window
        # behind its first), so blocks wholly outside the band cost nothing.
        k_steps = min(k_steps, (window + bq - 2) // bk + 2)

        def kv_block(qb, kb):
            return jnp.minimum(
                kb + _band_first_block(qb, bq, bk, window), (qb * bq + bq - 1) // bk
            )
    elif causal:
        # Clamp above-diagonal steps to the diagonal block: the pipeline
        # sees the same block index as the previous step and skips the HBM
        # fetch; the kernel skips their compute (see _flash_kernel).
        def kv_block(qb, kb):
            return jnp.minimum(kb, (qb * bq + bq - 1) // bk)
    else:
        def kv_block(qb, kb):
            return kb

    grid = (b * h, s_pad // bq, k_steps)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, causal=causal, kv_len=t if t_pad != t else None,
            **({} if window is None else {"window": window}),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qb, kb: (bh, qb, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qb, kb: (kv_row(bh), kv_block(qb, kb), 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qb, kb: (kv_row(bh), kv_block(qb, kb), 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qb, kb: (bh, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return jnp.swapaxes(out[:, :s].reshape(b, h, s, d), 1, 2)  # [B, S, H, D]


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_prefill_xla(q, k, v, *, causal=True, window=None):
    """Dense reference semantics on any backend (f32 softmax, HIGHEST)."""
    groups = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = (
        jnp.einsum(
            "bshd,bthd->bhst",
            q.astype(jnp.float32),
            k.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        * scale
    )
    if causal:
        s, t = q.shape[1], k.shape[1]
        cm = jnp.arange(s)[:, None] >= jnp.arange(t)[None, :]
        if window is not None:
            cm &= jnp.arange(s)[:, None] - jnp.arange(t)[None, :] < window
        logits = jnp.where(cm[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhst,bthd->bshd",
        probs,
        v.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    return out.astype(q.dtype)


def flash_prefill_attention(q, k, v, *, causal=True, block_q=256, block_k=256,
                            window=None):
    """Prefill attention without materializing S x T logits.

    q: [B, S, H, D]; k/v: [B, T, KVH, D] with KVH dividing H (GQA); any S/T
    work (``block_q``/``block_k`` are block-size targets; odd lengths are
    padded to whole tile-aligned blocks and masked inside the kernel).
    Pallas flash kernel on TPU, dense XLA elsewhere. Softmax statistics are
    f32 on both paths; for f32 inputs the outputs agree to f32 rounding.
    For bf16 inputs the TPU kernel runs native-dtype MXU dots and rounds
    the probabilities to bf16 for the PV pass (one exact-accumulation pass
    per dot — the standard flash-on-TPU choice), so TPU and CPU outputs
    agree at the model dtype's rounding scale, not f32's. Forward-only
    (use the dense path for differentiable training losses).

    ``causal=True`` masks by GLOBAL position assuming q and k both start at
    position 0, so it requires S == T; a suffix chunk attending a longer
    context (S < T with q offset T-S) would be silently over-masked —
    rejected loudly instead (a chunk at an offset over a paged context is
    tpu/chunk_attention.py's, behind models/llama.py prefill_continue).

    ``window`` (static, causal only): a sliding layer, key j visible to query
    i iff ``0 <= i - j < window``; K blocks wholly outside the band are no
    grid step. None lowers the program it always did."""
    if window is not None and not causal:
        raise ValueError("a sliding window is causal")
    if causal and q.shape[1] != k.shape[1]:
        raise ValueError(
            f"causal=True assumes q and k start at position 0, so S must "
            f"equal T (got S={q.shape[1]}, T={k.shape[1]}); offset suffix "
            "chunks would be over-masked"
        )
    if paged._use_pallas():
        return _flash_prefill_pallas(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=False, **({} if window is None else {"window": window}),
        )
    if window is None:
        return flash_prefill_xla(q, k, v, causal=causal)
    return flash_prefill_xla(q, k, v, causal=causal, window=window)
