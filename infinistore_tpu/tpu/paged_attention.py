"""Fused paged decode attention: a wave of query rows, each attending its own
pages of the paged KV cache, computed block-by-block with an online softmax —
no materialized context.

This is the hot op on the consumer side of the store. The engine resumes a
request from fetched cache blocks and then decodes token-by-token; every
decode step attends over the whole context. The unfused path (gather_blocks
then dense attention) moves each context block HBM->HBM into a contiguous
buffer and then reads it again for attention — every cached byte crosses HBM
three times per token. Decode attention does O(1) FLOPs per byte, so it is
purely HBM-bandwidth-bound and that 3x is the whole cost. The fused kernel
reads each block exactly once: the scalar-prefetched flat page list drives
the BlockSpec index maps (the pipeline DMAs cache[pages[i]] directly into
VMEM, double-buffering consecutive blocks), and a flash-style running
(max, sum, acc) in VMEM scratch folds each block into the softmax as it
arrives. The reference never needed this op — CUDA engines bring their own
paged attention (vLLM) and the store hands them raw pointers; on TPU the
engine-side kernel is part of the framework's job.

ONE kernel family: the ragged one (a flat grid over the wave's concatenated
page lists, with a raw-statistics twin for context sharded over a mesh).
How a decode row attends its pages is decided in this module alone: the
model's wave body, its one-token view and the disagg decode layer all call
:func:`paged_decode_attention_rows`; a caller that holds a rectangle
(``[B, M]`` tables) describes it as a ragged wave with
:func:`rectangle_as_ragged`. The two XLA bodies are the fallback off the
chip and the tests' reference.

GQA layout: each query row is [n_heads, head_dim] against caches of
n_kv_heads; the kernel unrolls over kv heads and issues one MXU dot per
(kv head, block) — no batched dot_general, which Mosaic handles unevenly at
small shapes.

Numerical contract (shared with the XLA fallback and the dense oracle in
models/llama.py): logits and softmax statistics in float32, output cast to
the query dtype. Positions >= seq_len are masked out; padded block-table
entries past the sequence contribute nothing (their probabilities are
explicitly zeroed, so a whole-block mask cannot poison the running max).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged

_NEG_INF = -1e30


def _varying_like(shape, dtype, *operands):
    """``out_shape`` entry for a pallas_call that also runs inside
    ``shard_map`` (the stats kernel, under the sharded decode entry): the
    output varies over every mesh axis an operand varies over, and jax's
    varying-axes typing requires the kernel to say so. Outside shard_map the
    set is empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _attn_block_fold(first, j, seq_len, q, k, v, m_scr, l_scr, acc_scr):
    """Fold ONE cache block into the running (max, denominator, accumulator)
    scratch — the single copy of the online-softmax numeric contract every
    decode kernel shares (ragged, its stats twin, and kv_quant.py's int8
    kernel, which dequantizes in VMEM first).

    ``first``: traced bool — this is the request's first block, reset the
    accumulators. ``j``: block index WITHIN the request (the ragged grid is
    flat, so the grid step is not the block index). ``seq_len``: traced
    scalar count of the request's valid context tokens.

    q: [H, D] f32; k/v: [bt, KVH, D] f32 (already loaded from refs — all
    dots request f32 accumulation at HIGHEST precision: XLA's DEFAULT runs
    f32 matmuls in bf16 passes, which would quantize the statistics).

    A fully-masked block is a BITWISE no-op on the scratch (alpha = exp(0)
    = 1, every p zeroed, l and acc multiplied by 1.0 and incremented by
    0.0), which is what lets the ragged layout pad its flat page list, and
    a rectangle ride it with every row's table at full width
    (rectangle_as_ragged), without changing a single output bit."""
    h, d = q.shape
    bt, kvh = k.shape[0], k.shape[1]
    groups = h // kvh

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    scale = 1.0 / np.sqrt(d)

    # Per-kv-head MXU dots, stacked head-major: logits[H, bt].
    logits = (
        jnp.concatenate(
            [
                jax.lax.dot_general(
                    q[g * groups : (g + 1) * groups],  # [G, D]
                    k[:, g, :],  # [bt, D]
                    (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                )
                for g in range(kvh)
            ],
            axis=0,
        )
        * scale
    )

    pos = j * bt + jax.lax.broadcasted_iota(jnp.int32, (h, bt), 1)
    valid = pos < seq_len
    logits = jnp.where(valid, logits, _NEG_INF)

    m_prev = m_scr[...]  # [H, 128] (all lanes equal)
    m_curr = jnp.max(logits, axis=1, keepdims=True)  # [H, 1]
    m_next = jnp.maximum(m_prev, m_curr)  # [H, 128]
    alpha = jnp.exp(m_prev[:, :1] - m_next[:, :1])  # [H, 1]
    p = jnp.exp(logits - m_next[:, :1])  # [H, bt]
    # A fully-masked block leaves m_next at _NEG_INF and exp(0)=1 would leak
    # weight onto padded slots; zero them unconditionally instead.
    p = jnp.where(valid, p, 0.0)

    l_next = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)  # [H, 1]
    pv = jnp.concatenate(
        [
            jax.lax.dot_general(
                p[g * groups : (g + 1) * groups],  # [G, bt]
                v[:, g, :],  # [bt, D]
                (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
            )
            for g in range(kvh)
        ],
        axis=0,
    )  # [H, D]
    m_scr[...] = m_next
    l_scr[...] = jax.lax.broadcast_in_dim(l_next, l_scr.shape, (0, 1))
    acc_scr[...] = acc_scr[...] * alpha + pv


@jax.jit
def _decode_attention_stats_xla(q, k_cache, v_cache, block_tables, seq_lens):
    """XLA form of the raw per-row statistics over RECTANGULAR tables
    ([B, M] ``block_tables``, [B] ``seq_lens``): acc [B, H, D] f32
    unnormalized, m / l [B, H, 1] f32. The fallback off the chip and the
    reference the kernels are tested against."""
    _, bt, kvh, d = k_cache.shape
    h = q.shape[1]
    groups = h // kvh
    scale = 1.0 / np.sqrt(d)

    def one(qb, tbl, sl):
        k = jnp.take(k_cache, tbl, axis=0).reshape(-1, kvh, d)
        v = jnp.take(v_cache, tbl, axis=0).reshape(-1, kvh, d)
        k = jnp.repeat(k, groups, axis=1)
        v = jnp.repeat(v, groups, axis=1)
        logits = (
            jnp.einsum(
                "hd,thd->ht",
                qb.astype(jnp.float32),
                k.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            * scale
        )
        t = k.shape[0]
        valid = jnp.arange(t, dtype=jnp.int32) < sl
        logits = jnp.where(valid[None, :], logits, _NEG_INF)
        m = jnp.max(logits, axis=1, keepdims=True)  # [H, 1]
        p = jnp.exp(logits - m)
        # An all-masked shard (sl == 0) leaves m at _NEG_INF and exp(0)=1;
        # zero those weights so its (acc, l) contribute nothing.
        p = jnp.where(valid[None, :], p, 0.0)
        l = jnp.sum(p, axis=1, keepdims=True)  # [H, 1]
        acc = jnp.einsum(
            "ht,thd->hd", p, v.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        return acc, m, l

    return jax.vmap(one)(q, block_tables, seq_lens)


@jax.jit
def paged_decode_attention_xla_batched(q, k_cache, v_cache, block_tables, seq_lens):
    """Batched reference semantics, derived from the stats body (one copy of
    the numeric contract). Zero-length rows yield zeros."""
    acc, _, l = _decode_attention_stats_xla(
        q, k_cache, v_cache, block_tables, seq_lens
    )
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Ragged decode attention: one flat grid over the wave's CONCATENATED page
# lists — a length-skewed wave costs sum(ceil(len_i / bt)) block folds
# instead of a rectangular layout's B * max_blocks (Ragged Paged
# Attention, PAPERS.md). The kernel never materializes gathered KV: the
# scalar-prefetched flat page list drives the K/V BlockSpec index maps, and
# the per-page row map decides when the online-softmax scratch resets and
# when a row's output is finalized.
# ---------------------------------------------------------------------------


class RaggedWaveMeta:
    """Host-assembled metadata for one ragged decode wave of R rows.

    Layout contract (all int32 numpy arrays, built by
    :func:`build_ragged_wave`):

    - ``pages`` [P]: the wave's page lists concatenated in row order; row
      r's pages are ``pages[page_starts[r] : page_starts[r] + nb_r]`` with
      ``nb_r = max(1, ceil(seq_lens[r] / block_tokens))`` (a zero-length
      row carries ONE fully-masked page so its output block is still
      written — as zeros, the framework-wide empty-row contract). The tail
      may be padded with copies of the last page to a static bucket; padded
      entries belong to the last row and fold as fully-masked blocks, a
      bitwise no-op (see _attn_block_fold).
    - ``page_rows`` [P + 1]: owning row of each flat page, non-decreasing,
      with sentinel ``page_rows[P] == R`` so ``page_rows[i + 1] != row``
      detects a row's last page without branching.
    - ``page_starts`` [R]: index of each row's first page in ``pages``.
    - ``seq_lens`` [R]: valid context tokens per row.
    - ``pad_pages``: how many tail entries are padding (the pad-fraction
      accounting the engine exports as ``engine_wave_pad_fraction``).
    """

    __slots__ = ("pages", "page_rows", "page_starts", "seq_lens", "pad_pages")

    def __init__(self, pages, page_rows, page_starts, seq_lens, pad_pages):
        self.pages = pages
        self.page_rows = page_rows
        self.page_starts = page_starts
        self.seq_lens = seq_lens
        self.pad_pages = pad_pages

    @property
    def num_pages(self) -> int:
        return int(self.pages.shape[0])

    @property
    def num_rows(self) -> int:
        return int(self.seq_lens.shape[0])


def build_ragged_wave(
    tables, seq_lens, block_tokens: int, pad_to: int = 0,
    pad_to_pow2: bool = False,
):
    """Assemble :class:`RaggedWaveMeta` from per-row page tables.

    ``tables``: sequence of R 1-D int arrays/lists — row r's block table
    (entries past its sequence are ignored; the table must cover
    ``ceil(seq_lens[r] / block_tokens)`` entries). ``pad_to``: pad the flat
    page list to this static length (0 = exact). ``pad_to_pow2``: let the
    BUILDER pick the power-of-two bucket from its own page count — the
    form jit-bucketing callers (engine, bench legs) should use, so the
    per-row page-count rule lives in exactly one place."""
    seq_lens = np.asarray(seq_lens, dtype=np.int32)
    r = len(tables)
    if r == 0 or seq_lens.shape != (r,):
        raise ValueError(f"need >= 1 rows with one seq_len each, got {r} "
                         f"tables / seq_lens {seq_lens.shape}")
    chunks, starts, total = [], [], 0
    for row, table in enumerate(tables):
        table = np.asarray(table, dtype=np.int32).reshape(-1)
        nb = max(1, -(-int(seq_lens[row]) // block_tokens))
        if table.shape[0] < nb:
            raise ValueError(
                f"row {row}: table has {table.shape[0]} pages, needs {nb} "
                f"for seq_len {int(seq_lens[row])}"
            )
        chunks.append(table[:nb])
        starts.append(total)
        total += nb
    if pad_to and pad_to < total:
        raise ValueError(f"pad_to={pad_to} < {total} real pages")
    if pad_to_pow2 and not pad_to:
        pad_to = 1 << (total - 1).bit_length()
    p = pad_to or total
    pages = np.empty(p, dtype=np.int32)
    pages[:total] = np.concatenate(chunks)
    pages[total:] = pages[total - 1]  # valid id; folds fully masked
    page_rows = np.empty(p + 1, dtype=np.int32)
    for row, start in enumerate(starts):
        end = starts[row + 1] if row + 1 < r else total
        page_rows[start:end] = row
    page_rows[total:p] = r - 1  # padding rides the last row, masked
    page_rows[p] = r  # sentinel: no real row, terminates the last row
    return RaggedWaveMeta(
        pages=pages,
        page_rows=page_rows,
        page_starts=np.asarray(starts, dtype=np.int32),
        seq_lens=seq_lens,
        pad_pages=p - total,
    )


def _ragged_fold(rows_ref, starts_ref, seqlen_ref, q_ref, k_ref, v_ref,
                 m_scr, l_scr, acc_scr):
    """Shared body of the ragged kernels: fold flat page ``i`` into its
    row's scratch; returns (row, is_last_page_of_row)."""
    i = pl.program_id(0)
    b = rows_ref[i]
    # First page of a row: flat index 0, or the row changed. The i == 0 arm
    # keeps the clamped rows_ref[-1] read from aliasing row 0's own id.
    first = jnp.logical_or(i == 0, rows_ref[jnp.maximum(i - 1, 0)] != b)
    _attn_block_fold(
        first,
        i - starts_ref[b],
        seqlen_ref[b],
        q_ref[0].astype(jnp.float32),
        k_ref[0].astype(jnp.float32),
        v_ref[0].astype(jnp.float32),
        m_scr,
        l_scr,
        acc_scr,
    )
    # rows_ref is [P + 1] with sentinel R, so i + 1 never reads past the end
    # and the wave's very last page (padding included) finalizes its row.
    return b, rows_ref[i + 1] != b


def _ragged_attn_kernel(
    rows_ref,  # scalar-prefetch: [P + 1] int32 owning row per page
    pages_ref,  # scalar-prefetch: [P] int32 flat page list (drives DMA)
    starts_ref,  # scalar-prefetch: [R] int32 first flat index per row
    seqlen_ref,  # scalar-prefetch: [R] int32 valid context lengths
    q_ref,  # [1, H, D] this row's query
    k_ref,  # [1, bt, KVH, D] one cache page
    v_ref,  # [1, bt, KVH, D]
    out_ref,  # [1, H, D]
    m_scr,  # VMEM [H, 128] f32
    l_scr,  # VMEM [H, 128] f32
    acc_scr,  # VMEM [H, D] f32
):
    del pages_ref
    _, last = _ragged_fold(
        rows_ref, starts_ref, seqlen_ref, q_ref, k_ref, v_ref,
        m_scr, l_scr, acc_scr,
    )

    @pl.when(last)
    def _finish():
        out_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
        ).astype(out_ref.dtype)


def _ragged_attn_stats_kernel(
    rows_ref, pages_ref, starts_ref, seqlen_ref,
    q_ref, k_ref, v_ref,
    acc_ref,  # [1, H, D] f32 unnormalized numerator
    m_ref,  # [1, H, 128] f32
    l_ref,  # [1, H, 128] f32
    m_scr, l_scr, acc_scr,
):
    """Ragged online softmax emitting raw (acc, m, l) — the shard-local
    half of ragged sharded decode (combined with one pmax and two psum)."""
    del pages_ref
    _, last = _ragged_fold(
        rows_ref, starts_ref, seqlen_ref, q_ref, k_ref, v_ref,
        m_scr, l_scr, acc_scr,
    )

    @pl.when(last)
    def _finish():
        acc_ref[0] = acc_scr[...]
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]


def _ragged_grid_spec(h, d, bt, kvh, p, out_specs):
    block = (1, bt, kvh, d)
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(p,),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i, rows, pages, st, sl: (rows[i], 0, 0)),
            pl.BlockSpec(block, lambda i, rows, pages, st, sl: (pages[i], 0, 0, 0)),
            pl.BlockSpec(block, lambda i, rows, pages, st, sl: (pages[i], 0, 0, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_decode_attention_pallas_ragged(
    q, k_cache, v_cache, pages, page_rows, page_starts, seq_lens, *, interpret
):
    """q: [R, H, D]; flat metadata per RaggedWaveMeta's layout contract."""
    r, h, d = q.shape
    _, bt, kvh, _ = k_cache.shape
    p = pages.shape[0]
    grid_spec = _ragged_grid_spec(
        h, d, bt, kvh, p,
        pl.BlockSpec((1, h, d), lambda i, rows, pages, st, sl: (rows[i], 0, 0)),
    )
    return pl.pallas_call(
        _ragged_attn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, h, d), q.dtype),
        interpret=interpret,
    )(page_rows, pages, page_starts, seq_lens, q, k_cache, v_cache)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_decode_attention_pallas_ragged_stats(
    q, k_cache, v_cache, pages, page_rows, page_starts, seq_lens, *, interpret
):
    """Raw ragged (acc, m, l): acc [R,H,D] f32, m/l [R,H,1] f32."""
    r, h, d = q.shape
    _, bt, kvh, _ = k_cache.shape
    p = pages.shape[0]
    out = lambda i, rows, pages, st, sl: (rows[i], 0, 0)
    grid_spec = _ragged_grid_spec(
        h, d, bt, kvh, p,
        [
            pl.BlockSpec((1, h, d), out),
            pl.BlockSpec((1, h, 128), out),
            pl.BlockSpec((1, h, 128), out),
        ],
    )
    operands = (page_rows, pages, page_starts, seq_lens, q, k_cache, v_cache)
    acc, m, l = pl.pallas_call(
        _ragged_attn_stats_kernel,
        grid_spec=grid_spec,
        out_shape=[
            _varying_like((r, h, d), jnp.float32, *operands),
            _varying_like((r, h, 128), jnp.float32, *operands),
            _varying_like((r, h, 128), jnp.float32, *operands),
        ],
        interpret=interpret,
    )(*operands)
    return acc, m[:, :, :1], l[:, :, :1]


def _ragged_row_tables(pages, page_starts, table_width: int):
    """Reconstruct [R, table_width] per-row tables from the flat page list
    for the XLA fallback (which gathers per row). Entries past a row's real
    pages alias LATER pages in the flat list (clamped in range) — valid ids
    whose contents are masked by seq_len, the padded-table contract of the
    XLA body (tested: test_padded_table_entries_are_ignored)."""
    idx = page_starts[:, None] + jnp.arange(table_width, dtype=jnp.int32)[None, :]
    return jnp.take(pages, jnp.minimum(idx, pages.shape[0] - 1), axis=0)


@functools.partial(jax.jit, static_argnames=("table_width",))
def _paged_decode_attention_ragged_xla(
    q, k_cache, v_cache, pages, page_starts, seq_lens, *, table_width
):
    """XLA fallback for the ragged entry, jitted as ONE unit so the table
    reconstruction fuses with the gather instead of dispatching eagerly
    (measured ~20% per-call overhead unfused on the CPU backend)."""
    tables = _ragged_row_tables(pages, page_starts, table_width)
    return paged_decode_attention_xla_batched(
        q, k_cache, v_cache, tables, seq_lens
    )


def paged_decode_attention_ragged(
    q, k_cache, v_cache, pages, page_rows, page_starts, seq_lens,
    *, table_width: int
):
    """Decode attention for a RAGGED wave: R rows over one shared paged
    cache with per-row context lengths, no padding to the wave max.

    q: [R, n_heads, head_dim]; the flat metadata follows
    :class:`RaggedWaveMeta` (use :func:`build_ragged_wave`). ``table_width``
    (static): max pages any row spans — only the XLA fallback uses it, to
    reconstruct rectangular tables for its gather. On TPU one fused kernel
    walks the flat page list: sum(ceil(len_i / bt)) block folds total, so
    an 8:1 length-skewed wave costs ~the mean length, not B x max. Rows
    with seq_len 0 return zeros on every backend."""
    if paged._use_pallas():
        return _paged_decode_attention_pallas_ragged(
            q, k_cache, v_cache,
            jnp.asarray(pages, jnp.int32),
            jnp.asarray(page_rows, jnp.int32),
            jnp.asarray(page_starts, jnp.int32),
            jnp.asarray(seq_lens, jnp.int32),
            interpret=False,
        )
    return _paged_decode_attention_ragged_xla(
        q, k_cache, v_cache,
        jnp.asarray(pages, jnp.int32),
        jnp.asarray(page_starts, jnp.int32),
        jnp.asarray(seq_lens, jnp.int32),
        table_width=table_width,
    )


def rectangle_as_ragged(block_tables):
    """Ragged metadata ``(pages, page_rows, page_starts)`` of a RECTANGULAR
    wave: ``block_tables`` [B, M], one full-width table a row. Row b owns
    flat pages ``[b * M, (b + 1) * M)``; those past its sequence fold fully
    masked, a bitwise no-op (_attn_block_fold), and a zero-length row still
    writes zeros. Static given the shape, so it traces inside a jit: what
    the callers that hold a rectangle (one decode token, the disagg decode
    layer) hand :func:`paged_decode_attention_rows`, at the B x M grid steps
    a rectangular kernel would take."""
    b, m = block_tables.shape
    rows = jnp.arange(b + 1, dtype=jnp.int32)
    return (
        block_tables.reshape(-1).astype(jnp.int32),
        jnp.repeat(rows, m)[: b * m + 1],  # [B*M + 1], sentinel B last
        rows[:b] * m,
    )


def paged_decode_attention_rows(
    q, k_cache, v_cache, row_tables, seq_lens, pages, page_rows, page_starts
):
    """Per-row decode attention with BOTH layouts in hand: THE way a decode
    row attends its pages. q: [R, n_heads, head_dim]; row r attends the
    first ``seq_lens[r]`` tokens of its table ``row_tables[r]`` ([R,
    max_blocks], padded with any valid id), which the flat ragged metadata
    (:class:`RaggedWaveMeta`'s layout, from :func:`build_ragged_wave` on
    the host or :func:`rectangle_as_ragged` in a jit) describes a second
    time. A row with ``seq_lens[r] == 0`` returns zeros. On TPU the flat
    metadata routes to the ragged kernel (one grid step a page, no
    B x max_blocks grid); elsewhere the XLA body gathers ``row_tables``.
    Every caller in models/llama.py comes through here (the wave body
    verify_step_ragged, decode_step as its one-row view, the disagg
    decode_wave_layer), so a wave and the same tokens decoded one at a time
    agree to float32 rounding on a float32 model (the tests' written
    tolerance), whatever the batch shape."""
    if paged._use_pallas():
        return _paged_decode_attention_pallas_ragged(
            q, k_cache, v_cache, pages, page_rows, page_starts, seq_lens,
            interpret=False,
        )
    return paged_decode_attention_xla_batched(
        q, k_cache, v_cache, row_tables, seq_lens
    )


def _decode_attention_stats_ragged(
    q, k_cache, v_cache, pages, page_rows, page_starts, seq_lens,
    table_width: int,
):
    """Raw ragged (acc, m, l) dispatcher (Pallas on TPU, XLA off) — the
    shard-local half of ragged sharded decode."""
    if paged._use_pallas():
        return _paged_decode_attention_pallas_ragged_stats(
            q, k_cache, v_cache, pages, page_rows, page_starts, seq_lens,
            interpret=False,
        )
    tables = _ragged_row_tables(pages, page_starts, table_width)
    return _decode_attention_stats_xla(q, k_cache, v_cache, tables, seq_lens)


def build_ragged_wave_sharded(local_tables, local_lens, block_tokens: int):
    """Per-shard :func:`build_ragged_wave` metadata for a ragged wave whose
    KV pages are SHARDED over a mesh axis, stacked into the [P, ...]
    leading-axis arrays ``shard_map`` splits.

    ``local_tables``: P sequences of R per-row SHARD-LOCAL page tables
    (each row indexes within its shard's cache rows); ``local_lens``:
    [P, R] valid token counts per (shard, row) — 0 is fine: the row gets
    one fully-masked page on that shard, whose (acc=0, m=-inf, l=0) stats
    carry zero combine weight. Every shard's flat list pads to the fleet
    max so the stacked arrays are rectangular.

    Returns (pages [P, maxP], page_rows [P, maxP+1], page_starts [P, R],
    seq_lens [P, R], table_width) — table_width sized for the XLA
    fallback's per-row reconstruction."""
    local_lens = np.asarray(local_lens, dtype=np.int32)
    p = len(local_tables)
    if p == 0 or local_lens.shape[0] != p:
        raise ValueError("need one table list + len row per shard")
    # Per-(shard, row) page counts — same rule as build_ragged_wave's loop
    # (a zero-length row still carries one masked page) — give the fleet
    # max without building each shard's metadata twice.
    counts = np.maximum(1, -(-local_lens // block_tokens))
    max_p = int(counts.sum(axis=1).max())
    padded = [
        build_ragged_wave(tables, lens, block_tokens, pad_to=max_p)
        for tables, lens in zip(local_tables, local_lens)
    ]
    width = int(counts.max())
    return (
        np.stack([m.pages for m in padded]),
        np.stack([m.page_rows for m in padded]),
        np.stack([m.page_starts for m in padded]),
        local_lens,
        width,
    )


def paged_decode_attention_ragged_sharded(
    q, k_cache, v_cache, local_pages, local_rows, local_starts, local_lens,
    *, mesh, axis: str = "sp", table_width: int,
):
    """Ragged decode attention for a WAVE of R rows whose paged KV is
    sharded over ``mesh``'s ``axis`` — the multi-chip serving shape where
    one engine step advances every live request and the wave's contexts
    together exceed a single device's HBM.

    Layout contract: ``k_cache``/``v_cache`` are [P * blocks_per_shard, bt,
    KVH, D] sharded over ``axis`` on the block dimension. The per-shard
    ragged metadata comes from :func:`build_ragged_wave_sharded`:
    ``local_pages`` [P, maxP] flat SHARD-LOCAL page lists, ``local_rows``
    [P, maxP + 1] owning-row maps, ``local_starts`` [P, R], ``local_lens``
    [P, R] valid tokens per (shard, row). ``q`` is [R, H, D], replicated.

    Each shard folds its local pages with the RAGGED stats kernel (flat
    grid, no padding to the wave max) and the per-row (acc, m, l) combine
    with one ``pmax`` and two ``psum`` over ``axis``, exactly (softmax is
    permutation-invariant, so shard order does not matter):

        out = sum_p(acc_p * e^(m_p - m)) / sum_p(l_p * e^(m_p - m)),
        m = max_p(m_p)

    Cached bytes never cross the interconnect; only [R, H, D]-sized
    statistics do. Returns [R, H, D], replicated. A single long request is
    the R = 1 wave. The shard_map is built once per (mesh, axis, width)
    (_sharded_ragged_decode_fn is lru_cached): this is a per-decode-token
    entry point, and a fresh closure a call would retrace every token."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    fn, cache_spec = _sharded_ragged_decode_fn(mesh, axis, int(table_width))
    put = lambda x, spec: jax.device_put(
        jnp.asarray(x), NamedSharding(mesh, spec)
    )
    meta_put = lambda x: jax.device_put(
        jnp.asarray(x, jnp.int32), NamedSharding(mesh, P(axis, None))
    )
    return fn(
        put(q, P(None, None, None)),
        put(k_cache, cache_spec),
        put(v_cache, cache_spec),
        meta_put(local_pages),
        meta_put(local_rows),
        meta_put(local_starts),
        meta_put(local_lens),
    )


@functools.lru_cache(maxsize=None)
def _sharded_ragged_decode_fn(mesh, axis: str, table_width: int):
    """Build (once per mesh/axis/width) the shard_map'd ragged local-stats
    + per-row combine. The jit around the shard_map matters: without it
    every call re-traces and re-lowers."""
    from jax.sharding import PartitionSpec as P

    def local_fn(q_rep, kc, vc, pages, rows, starts, lens):
        acc, m, l = _decode_attention_stats_ragged(
            q_rep, kc, vc, pages[0], rows[0], starts[0], lens[0], table_width
        )  # [R, H, D], [R, H, 1], [R, H, 1]
        m_g = jax.lax.pmax(m, axis)
        w = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * w, axis)
        acc_g = jax.lax.psum(acc * w, axis)
        # max(l, tiny): only the "row empty on EVERY shard" case (seq_len
        # 0), which must read as zeros, not 0/0 NaN.
        return (acc_g / jnp.maximum(l_g, 1e-30)).astype(q_rep.dtype)

    cache_spec = P(axis, None, None, None)
    meta_spec = P(axis, None)
    fn = jax.jit(
        jax.shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(
                P(None, None, None), cache_spec, cache_spec,
                meta_spec, meta_spec, meta_spec, meta_spec,
            ),
            out_specs=P(None, None, None),
        )
    )
    return fn, cache_spec
