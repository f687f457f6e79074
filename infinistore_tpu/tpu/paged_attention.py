"""Fused paged decode attention: a wave of query rows, each attending its own
pages of the paged KV cache, computed step by step with an online softmax —
no materialized context.

This is the hot op on the consumer side of the store. The engine resumes a
request from fetched cache blocks and then decodes token-by-token; every
decode step attends over the whole context. The unfused path (gather_blocks
then dense attention) moves each context block HBM->HBM into a contiguous
buffer and then reads it again for attention — every cached byte crosses HBM
three times per token. Decode attention does O(1) FLOPs per byte, so it is
purely HBM-bandwidth-bound and that 3x is the whole cost. The fused kernel
reads each block exactly once: the scalar-prefetched step table says which
cache pages a grid step needs, the kernel copies cache[page] from HBM
directly into VMEM (its own copies, double-buffered across consecutive
steps), and a flash-style running (max, sum, acc) in VMEM scratch folds
each step into the softmax as it arrives. The reference never needed this op — CUDA engines bring their own
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

What a grid step is (``_ragged_steps``, ``_ragged_walk``, ``_attn_fold``):

- *What it folds.* Up to ``_STEP_TOKENS`` keys — eight 16-token pages, one
  v5e MXU tile along the key axis — of ONE row, copied one under the other
  as they lie in the cache ([bt * KVH, D] a page: a row a (key, KV head)
  pair). A row's last step is partial and masked by its
  ``seq_len``; steps never span rows. Two dots a step, whatever the heads:
  all query heads against all of the step's (key, KV head) rows, the
  columns of the KV heads that do not serve a query head masked like keys
  past the sequence. K and V pass the MXU once as its stationary operand,
  which is what bounds decode attention there; the query rows that meet
  them ride free, no head is cut out of a page tile, and the program does
  not grow with the head count.
- *What is skipped.* The step count is static (the wave's bucket decides
  it), the number of REAL steps rides in as a prefetched scalar: steps past
  it start no copy and skip their compute (the query and output blocks do
  not move either: their index maps repeat the last real step's). A wave's
  power-of-two page padding, and whatever a row's span of the flat list
  holds past its sequence (a rectangle's full-width table), costs neither
  bytes nor dots.
- *Which precision each dtype gets.* Chosen from the operands' dtype, with
  no switch. bf16 queries on a bf16 cache: Q.K on the native operands with
  f32 accumulation (bf16 x bf16 products are exact in f32: the float32
  result at one MXU pass instead of six); P.V with the probabilities'
  float32 value, cut into three bf16 pieces stacked as rows against V
  (exact in bf16), so V still passes once. A float32 cache (and the int8
  kernel's dequantized pages): both dots at ``Precision.HIGHEST``.

Numerical contract (shared with the XLA fallback and the dense oracle in
models/llama.py): logits and softmax statistics in float32, output cast to
the query dtype. Positions >= seq_len are masked out; padded block-table
entries past the sequence contribute nothing (their probabilities are
explicitly zeroed, so a whole-step mask cannot poison the running max).
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


# Keys a grid step of the ragged kernels folds: eight 16-token pages, one
# v5e MXU tile along the key axis, a copy a page into the step's half of a
# double buffer; 64 keys a step ran 12-18% slower on 2k-8k contexts (PERF.md,
# PR 31). A page LONGER than this (1,024 tokens where the block is a state's
# snapshot interval) is a step of its own: slices of it ran slower (PR 43).
# A module constant: the pages a step follow the cache's block size alone,
# never the wave's bucket (that would mint programs).
_STEP_TOKENS = 128


def _key_positions(h: int, kvh: int, keys: int):
    """[H, keys * KVH] int32, in the program: for query head ``h`` and column
    ``c`` of a step's K (or V) tile — the step's pages as they lie in the
    cache, one row a (key, KV head) pair, key-major — the key's index within
    the step where column ``c`` holds the KV head that serves ``h``, and
    2**30 (past any sequence) where it holds another's."""
    lax, shape = jax.lax, (h, keys * kvh)
    col = lax.broadcasted_iota(jnp.int32, shape, 1)
    row = lax.broadcasted_iota(jnp.int32, shape, 0)
    serves = lax.eq(lax.rem(col, np.int32(kvh)), lax.div(row, np.int32(h // kvh)))
    return lax.select(
        serves, lax.div(col, np.int32(kvh)), lax.full(shape, np.int32(1 << 30))
    )


def _attn_fold(first, kpos0, seq_len, scale, q, k, v, key_pos, m_scr, l_scr,
               acc_scr, low=None):
    """Fold ONE grid step's keys into the running (max, denominator,
    accumulator) scratch — the single copy of the online-softmax numeric
    contract every decode kernel shares (ragged, its stats twin, and
    kv_quant.py's int8 kernel, which dequantizes in VMEM first).

    ``first``: traced bool — this is the row's first step, reset the
    accumulators. ``kpos0``: position WITHIN the row's context of the
    step's first key (the ragged grid is flat, so the grid step says
    nothing). ``seq_len``: traced scalar count of the row's valid tokens.
    ``scale``: 1 / sqrt(head_dim), the caller's (D may be padded). ``low``
    (a sliding layer's rows only; None elsewhere, and then nothing is traced
    for it): keys at positions under it lie behind the row's window and are
    masked like keys past the sequence.

    q: [H, D]; k/v: [T * KVH, D], the step's pages as they lie in the cache
    (a row a (key, KV head) pair, key-major: a page is [bt * KVH, D] without
    moving a byte), T = 16 keys for the int8 kernel's one page,
    ``_STEP_TOKENS`` for the ragged kernels; key_pos: [H, T * KVH] from
    :func:`_key_positions`. TWO dots a step, whatever the heads: every query
    head meets every KV head's keys in one ``[H, D] x [D, T * KVH]`` product
    and the columns of the other KV heads are masked like keys past the
    sequence (probability exactly 0), so ``[H, T * KVH] x [T * KVH, D]`` sums
    a head's own keys only. Decode attention streams K and V through the MXU
    once as its stationary operand; the rows that meet them there (32 for 4
    of use under GQA) ride free, and no head is cut out of a page tile (a
    sublane gather a key) or unrolled in the program.

    The MXU passes follow the operands' dtype, which is all that is looked
    at. bf16 queries on a bf16 cache: Q.K takes them as they are with f32
    accumulation (a bf16 x bf16 product is exact in f32, so this is the
    float32 result at one pass instead of six). P.V keeps the
    probabilities' float32 value: p is cut into three bf16 pieces — p
    rounded, the remainder rounded, what is then left; each remainder is
    exact in f32 and three 8-bit significands hold f32's 24, so the pieces
    sum to p — stacked as ROWS against V (exact in bf16 already), so V
    still passes the MXU once, and the three partial products add up in
    f32. Anything else (a float32 cache, the dequantized int8 pages) is cast
    to f32 and both dots ask ``Precision.HIGHEST``: XLA's DEFAULT runs f32
    matmuls in bf16 passes, which would quantize the statistics. Logits,
    softmax statistics and the accumulator are f32 either way.

    A fully-masked step is a BITWISE no-op on the scratch (alpha = exp(0)
    = 1, every p zeroed, l and acc multiplied by 1.0 and incremented by
    0.0): a rectangle's empty row and the int8 kernel's padded table
    entries rely on it. A ragged wave's padding does not run at all."""
    h = q.shape[0]
    native = q.dtype == k.dtype == v.dtype == jnp.bfloat16
    if native:
        precision = jax.lax.Precision.DEFAULT
    else:
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        precision = jax.lax.Precision.HIGHEST

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    ) * scale  # [H, T * KVH]
    valid = kpos0 + key_pos < seq_len
    if low is not None:
        valid = jnp.logical_and(valid, kpos0 + key_pos >= low)
    logits = jax.lax.select(valid, logits, jnp.full_like(logits, _NEG_INF))

    m_prev = m_scr[...]  # [H, 128] (all lanes equal)
    m_curr = jnp.max(logits, axis=1, keepdims=True)  # [H, 1]
    m_next = jnp.maximum(m_prev, m_curr)  # [H, 128]
    alpha = jnp.exp(m_prev[:, :1] - m_next[:, :1])  # [H, 1]
    p = jnp.exp(logits - m_next[:, :1])  # [H, T * KVH]
    # A fully-masked step leaves m_next at _NEG_INF and exp(0)=1 would leak
    # weight onto padded slots; zero them unconditionally instead.
    p = jax.lax.select(valid, p, jnp.zeros_like(p))

    l_next = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)  # [H, 1]
    if native:
        hi = p.astype(v.dtype)
        rest = p - hi.astype(jnp.float32)
        mid = rest.astype(v.dtype)
        lo = (rest - mid.astype(jnp.float32)).astype(v.dtype)
        p = jnp.concatenate([hi, mid, lo], axis=0)  # [3H, T * KVH]
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    )
    if native:
        pv = pv[:h] + pv[h : 2 * h] + pv[2 * h :]
    m_scr[...] = m_next
    l_scr[...] = jax.lax.broadcast_in_dim(l_next, l_scr.shape, (0, 1))
    acc_scr[...] = acc_scr[...] * alpha + pv  # [H, D]


@functools.partial(jax.jit, static_argnames=("window",))
def _decode_attention_stats_xla(q, k_cache, v_cache, block_tables, seq_lens,
                                window=None):
    """XLA form of the raw per-row statistics over RECTANGULAR tables
    ([B, M] ``block_tables``, [B] ``seq_lens``): acc [B, H, D] f32
    unnormalized, m / l [B, H, 1] f32. The fallback off the chip and the
    reference the kernels are tested against. ``window``: a row attends its
    last ``window`` tokens only, and what lies behind is not even multiplied
    by zero (a hit leaves those blocks of a sliding layer uninstalled)."""
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
        if window is not None:
            valid = valid & (jnp.arange(t, dtype=jnp.int32) >= sl - window)
            v = jnp.where(valid[:, None, None], v, 0)
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


@functools.partial(jax.jit, static_argnames=("window",))
def paged_decode_attention_xla_batched(q, k_cache, v_cache, block_tables, seq_lens,
                                       window=None):
    """Batched reference semantics, derived from the stats body (one copy of
    the numeric contract). Zero-length rows yield zeros."""
    acc, _, l = _decode_attention_stats_xla(
        q, k_cache, v_cache, block_tables, seq_lens, window=window
    )
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Ragged decode attention: one flat grid over the wave's CONCATENATED page
# lists — a length-skewed wave costs sum(ceil(len_i / bt)) page reads
# instead of a rectangular layout's B * max_blocks (Ragged Paged
# Attention, PAPERS.md). The kernel never materializes gathered KV: the
# step table cut from the flat page list (_ragged_steps) names the pages the
# kernel copies for a step and says which row the step belongs to, so when
# the online-softmax scratch resets and when a row's output is finalized.
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
      entries belong to the last row; the kernel walks each row's real
      pages only, so they are neither fetched nor folded.
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


def window_first_page(seq_len: int, block_tokens: int, window) -> int:
    """Index within a row's table of the first page a row of ``seq_len``
    tokens attends: 0 without a window, else the page of the oldest token its
    last query still sees (position ``seq_len - window``)."""
    return 0 if window is None else max(0, int(seq_len) - window) // block_tokens


def build_ragged_wave(
    tables, seq_lens, block_tokens: int, pad_to: int = 0,
    pad_to_pow2: bool = False, window=None,
):
    """Assemble :class:`RaggedWaveMeta` from per-row page tables.

    ``tables``: sequence of R 1-D int arrays/lists — row r's block table
    (entries past its sequence are ignored; the table must cover
    ``ceil(seq_lens[r] / block_tokens)`` entries). ``pad_to``: pad the flat
    page list to this static length (0 = exact). ``pad_to_pow2``: let the
    BUILDER pick the power-of-two bucket from its own page count — the
    form jit-bucketing callers (engine, bench legs) should use, so the
    per-row page-count rule lives in exactly one place. ``window``: the
    page list of a wave's SLIDING layers: row r carries only the pages from
    :func:`window_first_page` on (at most ``window / block_tokens + 1``), and
    the kernel, told the same ``window``, counts its keys from there; the
    pages behind cost no entry, no copy and no compute."""
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
        first = window_first_page(seq_lens[row], block_tokens, window)
        chunks.append(table[first:nb])
        starts.append(total)
        total += nb - first
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


def _ragged_steps(pages, page_starts, seq_lens, bt: int, step_pages: int):
    """The wave's flat page list cut into grid steps, in the program: a step
    is up to ``step_pages`` consecutive pages of ONE row, a row's last step
    may be partial, steps never span rows. Row r has ``nb_r = max(1,
    ceil(seq_lens[r] / bt))`` real pages (:class:`RaggedWaveMeta`'s rule;
    whatever else its span of the flat list holds — a rectangle's full-width
    table, the wave's bucket padding — is never walked) and so
    ``ceil(nb_r / step_pages)`` steps.

    The step COUNT is static: ``R + (P - R) // step_pages`` bounds the sum
    for any rows that fit P flat pages, so the program's shape is the
    bucket's. Returns ``(n_real [1], step_row [S], step_page0 [S],
    step_ids [S * step_pages])``: the real step count; per step its row and
    the index within the row of its first page; and the cache page each of
    its ``step_pages`` slots copies, slots past the row's last page repeating
    that page (masked by position). Steps past ``n_real`` repeat the last
    real step entry for entry: the query and output blocks stay where they
    are, and the kernel starts no copy and skips the compute.

    Every wave bucket's program traces and lowers this once (the layers
    share the jitted function around the kernel), so it is written in
    ``lax`` primitives on non-negative int32 — about thirty equations, no
    nested function: through ``jnp``'s wrappers it cost as much set-up as
    the kernel's body."""
    p, r = pages.shape[0], seq_lens.shape[0]
    n_steps = r + max(p - r, 0) // step_pages
    lax, i32 = jax.lax, np.int32
    over_steps = lambda x: lax.broadcast_in_dim(x, (n_steps, r), (1,))
    per_row = lambda x: lax.broadcast_in_dim(x, (n_steps, r), (0,))
    nb = lax.max(lax.div(seq_lens + i32(bt - 1), i32(bt)), i32(1))  # [R]
    steps = lax.div(nb + i32(step_pages - 1), i32(step_pages))
    ends = lax.cumsum(steps, axis=0)
    n_real = lax.min(lax.slice(ends, (r - 1,), (r,)), i32(n_steps))  # [1]
    s = lax.min(
        lax.iota(jnp.int32, n_steps),
        lax.broadcast_in_dim(n_real - i32(1), (n_steps,), (0,)),
    )
    # Rows that end at or before step s: their count is the step's row, the
    # sum of their steps its row's first step. The row's own numbers come
    # through the same [S, R] compare (R is a wave's rows: small).
    done = lax.le(over_steps(ends), per_row(s))
    zeros = lax.full((n_steps, r), i32(0))
    total = lambda x: lax.reduce_sum(x, (1,))
    row = total(lax.convert_element_type(done, jnp.int32))
    page0 = (s - total(lax.select(done, over_steps(steps), zeros))) * i32(
        step_pages
    )
    own = lax.eq(per_row(row), lax.broadcasted_iota(jnp.int32, (n_steps, r), 1))
    first = total(lax.select(own, over_steps(page_starts), zeros))
    last = total(lax.select(own, over_steps(nb), zeros)) - i32(1)
    cols = lambda x: lax.broadcast_in_dim(x, (n_steps, step_pages), (0,))
    slot = lax.min(
        cols(page0) + lax.broadcasted_iota(jnp.int32, (n_steps, step_pages), 1),
        cols(last),
    )
    flat = lax.reshape(cols(first) + slot, (n_steps * step_pages, 1))
    ids = lax.gather(
        pages, flat,
        lax.GatherDimensionNumbers(
            offset_dims=(), collapsed_slice_dims=(0,), start_index_map=(0,)
        ),
        slice_sizes=(1,), mode=lax.GatherScatterMode.CLIP,
    )
    return n_real, row, page0, ids


def _ragged_walk(refs, n_out: int, step_pages: int, bt: int, scale: float,
                 windowed: bool = False):
    """Shared body of the ragged kernels: fold this grid step's pages into
    its row's scratch, or do nothing past the wave's real steps. ``refs``
    are the kernel's: five scalar-prefetch refs — [1] real steps of the
    wave, [S] row of each step, [S] first page of each step counted within
    its row, [S * PG] cache page of each of a step's PG slots, [R] valid
    context lengths — then the row's query [1, H, D], the key positions
    [H, PG * bt * KVH] (_key_positions), the K and the V cache whole and
    where they are ([blocks, bt * KVH, D], no block: HBM), ``n_out`` outputs,
    and the scratch: the three softmax refs, two [2, PG * bt * KVH, D]
    buffers a cache and a [2, 2] DMA semaphore.

    The pages come in by the kernel's own copies, one a page, double
    buffered across grid steps: step i waits for its own (started by step
    i - 1; step 0 starts its own first) after starting step i + 1's into the
    other half. Against one BlockSpec a page this keeps the program small —
    two copies in a loop, not sixteen operands each with an index map of its
    own to trace, lower and load for every wave bucket a run warms (PERF.md,
    PR 31) — and steps past the real ones start nothing.

    Returns (is the step its row's last, outputs, softmax scratch)."""
    nreal_ref, row_ref, page0_ref, ids_ref, seqlen_ref = refs[:5]
    # A sliding layer's walk carries a sixth prefetched array: per row, the
    # first position inside its window, counted like ``seq_lens`` from the
    # row's first listed page.
    low_ref, refs = (refs[5], refs[1:]) if windowed else (None, refs)
    q_ref, kpos_ref, k_hbm, v_hbm = refs[5:9]
    outs = refs[9 : 9 + n_out]
    m_scr, l_scr, acc_scr, k_buf, v_buf, sem = refs[9 + n_out :]
    i = pl.program_id(0)
    n_real = nreal_ref[0]
    seq_len = seqlen_ref[row_ref[i]]
    low = low_ref[row_ref[i]] if windowed else None
    j0 = page0_ref[i]
    rows = k_hbm.shape[1]  # bt * KVH: a page

    def pages_of(step, do):
        slot = jax.lax.rem(step, 2)

        def page(j, carry):
            pid = ids_ref[step * step_pages + j]
            at = pl.ds(j * rows, rows)
            do(pltpu.make_async_copy(k_hbm.at[pid], k_buf.at[slot, at], sem.at[slot, 0]))
            do(pltpu.make_async_copy(v_hbm.at[pid], v_buf.at[slot, at], sem.at[slot, 1]))
            return carry

        jax.lax.fori_loop(0, step_pages, page, 0)
        return slot

    @pl.when(i == 0)
    def _first():
        pages_of(i, lambda copy: copy.start())

    @pl.when(i + 1 < n_real)
    def _ahead():
        pages_of(i + 1, lambda copy: copy.start())

    @pl.when(i < n_real)
    def _fold():
        slot = pages_of(i, lambda copy: copy.wait())
        _attn_fold(
            j0 == 0, j0 * bt, seq_len, scale, q_ref[0], k_buf[slot],
            v_buf[slot], kpos_ref[...], m_scr, l_scr, acc_scr, low,
        )

    n_pages = jnp.maximum(1, jax.lax.div(seq_len + (bt - 1), bt))
    last = jnp.logical_and(i < n_real, j0 + step_pages >= n_pages)
    return last, outs, (m_scr, l_scr, acc_scr)


def _ragged_attn_kernel(*refs, **walk):
    last, (out_ref,), (_, l_scr, acc_scr) = _ragged_walk(refs, 1, **walk)

    @pl.when(last)
    def _finish():
        out_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
        ).astype(out_ref.dtype)


def _ragged_attn_stats_kernel(*refs, **walk):
    """Ragged online softmax emitting raw (acc [1, H, D] f32 unnormalized,
    m, l [1, H, 128] f32) — the shard-local half of ragged sharded decode
    (combined with one pmax and two psum). The same walk, another last
    step."""
    last, (acc_ref, m_ref, l_ref), (m_scr, l_scr, acc_scr) = _ragged_walk(
        refs, 3, **walk
    )

    @pl.when(last)
    def _finish():
        acc_ref[0] = acc_scr[...]
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]


# Above the compiler's default scoped limit, far under a core's VMEM: at 32
# KV heads a step's logits are [32, 4096] f32 beside 4 MiB of page buffers.
_VMEM_LIMIT = 64 << 20


def _ragged_call(kernel, outs, q, k_cache, v_cache, pages, page_starts,
                 seq_lens, interpret, window=None):
    """One ``pallas_call`` of a ragged kernel over the wave's steps; every
    output is [R, H, width] (``outs``: a (width, dtype) each, width None for
    the head dim) and its block its row's, indexed like the query's. The cache goes in as [blocks, bt * KVH, D] — the same
    bytes, a page a [bt * KVH, D] tile."""
    r, h, d = q.shape
    n, bt, kvh, _ = k_cache.shape
    step_pages = max(1, _STEP_TOKENS // bt)
    lows = ()
    if window is not None:
        # The flat list starts each row at its first page inside the window
        # (build_ragged_wave): lengths and the window's edge count from there.
        behind = jnp.maximum(seq_lens - window, 0)
        skipped = behind // bt * bt
        seq_lens, lows = seq_lens - skipped, (behind - skipped,)
    n_real, step_row, step_page0, step_ids = _ragged_steps(
        pages, page_starts, seq_lens, bt, step_pages
    )
    # The kernel's copies take whole lane tiles: a head_dim under one (the
    # demo geometries' 16 and 32; no serving configuration) rides zero-padded
    # — a copy of the cache a call, where nothing is timed.
    lanes = -(-d // 128) * 128
    if lanes != d:
        pad = lambda x: jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, lanes - d)])
        q, k_cache, v_cache = pad(q), pad(k_cache), pad(v_cache)
    by_row = lambda i, n, rows, *_: (rows[i], 0, 0)
    key_pos = _key_positions(h, kvh, step_pages * bt)
    page_buffer = pltpu.VMEM((2, step_pages * bt * kvh, lanes), k_cache.dtype)
    widths = [lanes if w is None else w for w, _ in outs]
    got = pl.pallas_call(
        functools.partial(
            kernel, step_pages=step_pages, bt=bt, scale=1.0 / np.sqrt(d),
            **({} if window is None else {"windowed": True}),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5 + len(lows),
            grid=(step_row.shape[0],),
            in_specs=[
                pl.BlockSpec((1, h, lanes), by_row),
                pl.BlockSpec(key_pos.shape, lambda i, *_: (0, 0)),
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            out_specs=[pl.BlockSpec((1, h, w), by_row) for w in widths],
            scratch_shapes=[
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, lanes), jnp.float32),
                page_buffer,
                page_buffer,
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=[
            _varying_like((r, h, w), dt, q, k_cache, v_cache, pages, seq_lens)
            for w, (_, dt) in zip(widths, outs)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=interpret,
    )(
        n_real, step_row, step_page0, step_ids, seq_lens, *lows, q, key_pos,
        k_cache.reshape(n, bt * kvh, lanes), v_cache.reshape(n, bt * kvh, lanes),
    )
    return [o if w is not None else o[..., :d] for o, (w, _) in zip(got, outs)]


@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def _paged_decode_attention_pallas_ragged(
    q, k_cache, v_cache, pages, page_rows, page_starts, seq_lens, *, interpret,
    window=None,
):
    """q: [R, H, D]; flat metadata per RaggedWaveMeta's layout contract
    (``page_rows`` is not read: the steps follow ``page_starts`` and
    ``seq_lens``, _ragged_steps). ``window``: the list is a sliding layer's
    (``build_ragged_wave(window=)``); None lowers the program it always did."""
    del page_rows
    (out,) = _ragged_call(
        _ragged_attn_kernel, [(None, q.dtype)],
        q, k_cache, v_cache, pages, page_starts, seq_lens, interpret, window,
    )
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_decode_attention_pallas_ragged_stats(
    q, k_cache, v_cache, pages, page_rows, page_starts, seq_lens, *, interpret
):
    """Raw ragged (acc, m, l): acc [R,H,D] f32, m/l [R,H,1] f32."""
    del page_rows
    acc, m, l = _ragged_call(
        _ragged_attn_stats_kernel,
        [(None, jnp.float32), (128, jnp.float32), (128, jnp.float32)],
        q, k_cache, v_cache, pages, page_starts, seq_lens, interpret,
    )
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
    walks the flat page list: sum(ceil(len_i / bt)) page reads total, so
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
    flat pages ``[b * M, (b + 1) * M)``; those past its sequence are not
    walked (the steps follow ``seq_lens``, _ragged_steps), and a zero-length
    row still writes zeros. Static given the shape, so it traces inside a
    jit: what the callers that hold a rectangle (one decode token, the
    disagg decode layer) hand :func:`paged_decode_attention_rows`."""
    b, m = block_tables.shape
    rows = jnp.arange(b + 1, dtype=jnp.int32)
    return (
        block_tables.reshape(-1).astype(jnp.int32),
        jnp.repeat(rows, m)[: b * m + 1],  # [B*M + 1], sentinel B last
        rows[:b] * m,
    )


def paged_decode_attention_rows(
    q, k_cache, v_cache, row_tables, seq_lens, pages, page_rows, page_starts,
    window=None,
):
    """Per-row decode attention with BOTH layouts in hand: THE way a decode
    row attends its pages. q: [R, n_heads, head_dim]; row r attends the
    first ``seq_lens[r]`` tokens of its table ``row_tables[r]`` ([R,
    max_blocks], padded with any valid id), which the flat ragged metadata
    (:class:`RaggedWaveMeta`'s layout, from :func:`build_ragged_wave` on
    the host or :func:`rectangle_as_ragged` in a jit) describes a second
    time. A row with ``seq_lens[r] == 0`` returns zeros. On TPU the flat
    metadata routes to the ragged kernel (a grid step folds up to
    ``_STEP_TOKENS`` keys of one row, no B x max_blocks grid); elsewhere the
    XLA body gathers ``row_tables``. ``window`` (static): a sliding layer,
    whose rows attend their last ``window`` tokens; the flat metadata is then
    the wave's windowed list (``build_ragged_wave(window=)``) while
    ``row_tables`` stay whole, and nothing behind the window is read.
    Every caller in models/llama.py comes through here (the wave body
    verify_step_ragged, decode_step as its one-row view, the disagg
    decode_wave_layer), so a wave and the same tokens decoded one at a time
    agree to float32 rounding on a float32 model (the tests' written
    tolerance), whatever the batch shape."""
    if paged._use_pallas():
        return _paged_decode_attention_pallas_ragged(
            q, k_cache, v_cache, pages, page_rows, page_starts, seq_lens,
            interpret=False, **({} if window is None else {"window": window}),
        )
    if window is None:
        return paged_decode_attention_xla_batched(
            q, k_cache, v_cache, row_tables, seq_lens
        )
    return paged_decode_attention_xla_batched(
        q, k_cache, v_cache, row_tables, seq_lens, window=window
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
