"""A learned sparse selection over a paged latent cache (DeepSeek sparse
attention, as ``glm_moe_dsa`` publishes it): an index key a token beside the
latent, a scoring pass over a row's whole context, the ``k`` best positions,
and latent attention over THOSE alone.

The cache holds a second tensor a layer beside ``tpu/mla.py``'s latent, in the
same layout: ``[blocks, index_dim, block_tokens]``, the TOKEN the minor axis
(an index key is 128 values: a page is one clean ``[128, block_tokens]`` tile
that the matrix unit takes as it lies). Three steps, each in two shapes (a
decode wave: a few rows, each over its own block table; a chunk: many rows of
ONE request at contiguous positions over one table):

scores  ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`` in float32 over
        every position of the row's table. Pallas on the chip
        (``dsa_index_decode_pallas``: a page a grid step, the bytes bind;
        ``dsa_index_chunk_pallas``: 128 rows x a page a step, a product a
        head), plain XLA elsewhere.
select  per row the ``min(len, k)`` positions ``s < len`` of largest score,
        a tie broken towards the lower position: the SET ``lax.top_k`` gives,
        found without a sort. A float32 maps to an integer that orders the
        same way; the k-th largest of those is found a bit at a time (a
        count of the keys at or above a candidate a bit) and the search
        STOPS at the first threshold whose count is exactly ``min(len, k)``:
        the keys at or above it are the set, about 24 counts of 32 over
        distinct scores, none for a row that chooses all it has. Only where
        every bit was tried and more than k keys still stand (a tie across
        the k-th place) the ties at that value are cut by position the same
        way. ``dsa_select_pallas`` on the chip, a row tile's keys resident in
        VMEM, its second result the counting passes each tile made;
        ``select_xla`` (a sort, so a second opinion) elsewhere. The result is
        a BIAS, not ids: 0 where the position is selected, -1e30 elsewhere.
attend  ``tpu/mla.py``'s two latent attentions with that bias added to the
        scores, so every page is still READ and a position outside the set
        weighs nothing: ``mla_sparse_decode_pallas`` (the absorbed form) and
        ``mla.latent_chunk_attention(..., bias=)`` (on the chip ONE kernel,
        ``mla_chunk_attention_pallas``, the bias a block fetched once a head
        group and the scores in VMEM; the page loop in plain XLA elsewhere).
        Reading only the selected latents wants a gather of 1,152-byte rows
        out of a cache whose token axis is the minor one; PERF.md (PR 56, and
        PR 57 for what it would add now) says what that would cost.

Scores and biases travel PAGE-MAJOR, ``[max_blocks, rows, block_tokens]``: a
page of a row tile is then a leading-axis index for every kernel here (no
dynamic slice along lanes), and the chunk's attention (its kernel's page axis,
its XLA twin's loop) indexes it the same way.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged
from .mla import _NEG, einsum_f32

_INT_MIN = np.int32(-(2**31))
_ROW_TILE = 32  # the selection's rows a grid step at most (``_row_tile``)
_PAGE_UNROLL = 2  # pages a step of a counting pass's loop
_BITS_A_CHECK = 2  # bits the search tries between two looks at whether it is done
_NO_BOUND = np.int32(2**31 - 1)  # a tie cut that cuts nothing
CHUNK_ROW_TILE = 128  # the chunk scoring pass's rows a grid step
_VMEM_LIMIT = 64 << 20


def _dot(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


# ---------------------------------------------------------------------------
# Scores.
# ---------------------------------------------------------------------------


def _index_decode_kernel(tables_ref, lens_ref, q_ref, w_ref, k_ref, o_ref, *, bt: int):
    del tables_ref
    r, j = pl.program_id(0), pl.program_id(1)
    n_pages = (lens_ref[r] + bt - 1) // bt

    @pl.when(j < n_pages)
    def _score():
        s = _dot(q_ref[0], k_ref[0])  # [Hi, bt]
        s = jnp.maximum(s, 0.0) * w_ref[0][:, :1]
        o_ref[0, 0] = jnp.sum(s, axis=0, keepdims=True)

    @pl.when(j >= n_pages)
    def _none():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dsa_index_decode_pallas(q, w, index, row_tables, seq_lens, *, interpret: bool = False):
    """q: [T, Hi, Di]; w: [T, Hi] float32; index: [blocks, Di, bt];
    row_tables: [T, P] int32; seq_lens: [T] int32. Returns [P, T, bt] float32
    scores (zeros on pages past a row's length)."""
    t, hi, di = q.shape
    bt, p = index.shape[2], row_tables.shape[1]

    def page_of(r, j, tables, lens):
        last = jnp.maximum((lens[r] + bt - 1) // bt - 1, 0)
        return tables[r * p + jnp.minimum(j, last)], 0, 0

    w = jnp.broadcast_to(w.astype(jnp.float32)[:, :, None], (t, hi, 128))
    out = pl.pallas_call(
        functools.partial(_index_decode_kernel, bt=bt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(t, p),
            in_specs=[
                pl.BlockSpec((1, hi, di), lambda r, j, tables, lens: (r, 0, 0)),
                pl.BlockSpec((1, hi, 128), lambda r, j, tables, lens: (r, 0, 0)),
                pl.BlockSpec((1, di, bt), page_of),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, bt), lambda r, j, tables, lens: (j, r, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((p, t, 1, bt), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(row_tables.reshape(-1), seq_lens, q, w, index)
    return out.reshape(p, t, bt)


def _index_chunk_kernel(table_ref, n_ref, q_ref, w_ref, k_ref, o_ref, *, heads: int):
    del table_ref
    j = pl.program_id(1)

    @pl.when(j < n_ref[0])
    def _score():
        page, w = k_ref[0], w_ref[...]
        acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
        for h in range(heads):
            acc = acc + jnp.maximum(_dot(q_ref[h], page), 0.0) * w[:, h : h + 1]
        o_ref[0] = acc

    @pl.when(j >= n_ref[0])
    def _none():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dsa_index_chunk_pallas(q, w, index, block_table, n_pages, *, interpret: bool = False):
    """q: [Hi, S, Di] (head-major, S whole row tiles); w: [S, Hi] float32;
    index: [blocks, Di, bt]; block_table: [P] int32; n_pages: [1] int32, the
    pages the chunk's context spans. Returns [P, S, bt] float32 scores."""
    hi, s, di = q.shape
    bt, p = index.shape[2], block_table.shape[0]
    rows = CHUNK_ROW_TILE

    def page_of(i, j, table, n):
        return table[jnp.minimum(j, n[0] - 1)], 0, 0

    return pl.pallas_call(
        functools.partial(_index_chunk_kernel, heads=hi),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s // rows, p),
            in_specs=[
                pl.BlockSpec((hi, rows, di), lambda i, j, table, n: (0, i, 0)),
                pl.BlockSpec((rows, hi), lambda i, j, table, n: (i, 0)),
                pl.BlockSpec((1, di, bt), page_of),
            ],
            out_specs=pl.BlockSpec((1, rows, bt), lambda i, j, table, n: (j, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((p, s, bt), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(block_table, n_pages, q, w.astype(jnp.float32), index)


@jax.jit
def index_scores_xla(q, w, index, tables):
    """The scoring kernels' mathematics in plain XLA. q: [R, Hi, Di]; w: [R,
    Hi]; tables: [R, P] (a wave: a table a row) or [P] (a chunk: one for all).
    Returns [P, R, bt] float32; pages past a row's length hold what their
    table entries point at (the selection looks at ``len`` alone)."""
    pages = jnp.take(index, tables, axis=0)  # [(R,) P, Di, bt]
    spec = "rhd,rpdc->prhc" if tables.ndim == 2 else "rhd,pdc->prhc"
    s = jnp.maximum(einsum_f32(spec, q, pages), 0.0)
    return jnp.sum(s * w.astype(jnp.float32)[None, :, :, None], axis=2)


def index_scores_rows(q, w, index, row_tables, seq_lens):
    """A wave's scoring pass: [P, T, bt] float32."""
    if paged._use_pallas():
        return dsa_index_decode_pallas(q, w, index, row_tables, seq_lens)
    return index_scores_xla(q, w, index, row_tables)


def index_scores_chunk(q, w, index, block_table, start_pos):
    """A chunk's scoring pass. q: [S, Hi, Di] at positions ``start_pos ..``;
    returns [P, S, bt] float32."""
    if not paged._use_pallas():
        return index_scores_xla(q, w, index, block_table)
    s, bt = q.shape[0], index.shape[2]
    pad = -s % CHUNK_ROW_TILE
    q = jnp.pad(jnp.swapaxes(q, 0, 1), ((0, 0), (0, pad), (0, 0)))
    n_pages = ((start_pos + s + bt - 1) // bt).astype(jnp.int32).reshape(1)
    out = dsa_index_chunk_pallas(q, jnp.pad(w, ((0, pad), (0, 0))), index, block_table, n_pages)
    return out[:, :s]


# ---------------------------------------------------------------------------
# The selection.
# ---------------------------------------------------------------------------


def _ordered(x):
    """float32 -> int32 that orders the same way (NaNs aside)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & np.int32(0x7FFFFFFF))


def _select_kernel(pages_ref, lens_ref, s_ref, o_ref, passes_ref, key_sc, *, k: int, bt: int):
    p = s_ref.shape[0]
    n = pages_ref[pl.program_id(0)]  # pages the tile's longest row spans
    lens = lens_ref[...]  # [rows, 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape[1:], 1)
    steps = (n + _PAGE_UNROLL - 1) // _PAGE_UNROLL

    # A page's hits are added into ``[rows, bt]`` (a register a lane tile, none
    # waiting on another) and the lanes summed ONCE a pass: with a cross-lane
    # sum a page a piece's selection took 26.5 ms a layer on the chip, with
    # one register for a page's eight lane tiles 5.4 (PERF.md, PRs 56 and 61).
    # What a pass compares a key with is a value a ROW: spread over the lanes
    # (``wide``) before the page loop and not inside it, where the spreading
    # was two thirds of a pass (PERF.md, PR 61's probe).
    wide = lambda x: jnp.broadcast_to(x, s_ref.shape[1:])

    def count(hit):
        """[rows, 1] int32: per row, the keys of its first ``n`` pages that
        ``hit(key, position)`` holds for."""
        def step(i, acc):
            for c in range(_PAGE_UNROLL):
                page = i * _PAGE_UNROLL + c
                acc = acc + jnp.where(hit(key_sc[page], page * bt + lane), 1, 0)
            return acc
        acc = jax.lax.fori_loop(0, steps, step, jnp.zeros(s_ref.shape[1:], jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    valid = wide(lens)

    def write(c, _):
        key_sc[c] = jnp.where(c * bt + lane < valid, _ordered(s_ref[c]), _INT_MIN)
        return 0

    jax.lax.fori_loop(0, n, write, 0)

    def pad(c, _):  # the page loop's last step reads whole: keys nothing counts
        key_sc[c] = jnp.full(s_ref.shape[1:], _INT_MIN, jnp.int32)
        return 0

    jax.lax.fori_loop(n, steps * _PAGE_UNROLL, pad, 0)
    want = jnp.minimum(lens, k)
    # The want-th largest key, in the order of unsigned integers (a key with
    # its sign bit flipped), the highest bit first: the largest threshold at
    # or above which ``want`` keys still stand, and how many stand there. A
    # row whose count IS ``want`` is done: the keys at or above its threshold
    # are its set, no lower bit and no tie can change it. A row that may
    # choose everything (``len <= k``) is done before the first pass. The
    # search ends when every row of the tile is done; it looks (a vector
    # reduced to a scalar: 0.2 us) once ``_BITS_A_CHECK`` bits.
    def open_rows(at):
        return jnp.max(jnp.where(at == want, 0, 1))

    def try_bit(state):
        bit, found, at, _ = state
        for _ in range(_BITS_A_CHECK):
            cand = found | jnp.left_shift(jnp.int32(1), bit)
            least = wide(cand ^ _INT_MIN)
            stand = count(lambda key, _: key >= least)
            take = (stand >= want) & (at != want)
            found, at = jnp.where(take, cand, found), jnp.where(take, stand, at)
            bit = bit - 1
        return bit, found, at, open_rows(at)

    at = jnp.where(lens <= k, lens, _INT_MIN)  # unknown: no count is negative
    bit, found, at, tied = jax.lax.while_loop(
        lambda state: (state[0] >= 0) & (state[3] > 0), try_bit,
        (jnp.int32(31), jnp.zeros(lens.shape, jnp.int32), at, open_rows(at)),
    )
    kth = wide(found ^ _INT_MIN)
    position_bits = (p * bt).bit_length()

    def cut_ties():
        """Of the keys AT the threshold, the first ``ties`` by position: the
        largest bound under which no more than ``ties`` of them lie. Only in
        a tile with a row that tried every bit and still counts more than
        ``want``; its done rows ride along."""
        ties = want - count(lambda key, _: key > kth)

        def try_position(i, bound):
            cand = bound | jnp.left_shift(jnp.int32(1), position_bits - 1 - i)
            under = wide(cand)
            lie = count(lambda key, pos: (key == kth) & (pos < under))
            return jnp.where(lie <= ties, cand, bound)

        bound = jax.lax.fori_loop(0, position_bits, try_position, jnp.zeros(lens.shape, jnp.int32))
        return jnp.where(at == want, _NO_BOUND, bound)

    bound = jax.lax.cond(tied > 0, cut_ties, lambda: jnp.full(lens.shape, _NO_BOUND, jnp.int32))
    bound = wide(bound)
    passes_ref[pl.program_id(0), 0] = 31 - bit + jnp.where(tied > 0, 1 + position_bits, 0)

    def emit(c, _):
        key, pos = key_sc[c], c * bt + lane
        chosen = (key > kth) | ((key == kth) & (pos < bound))
        o_ref[c] = jnp.where(chosen & (pos < valid), 0.0, _NEG)
        return 0

    jax.lax.fori_loop(0, n, emit, 0)

    def blank(c, _):
        o_ref[c] = jnp.full(o_ref.shape[1:], _NEG, jnp.float32)
        return 0

    jax.lax.fori_loop(n, p, blank, 0)


def _row_tile(rows: int) -> int:
    """The selection's rows a grid step: 32 (a search's passes are latency,
    the same for 8 rows' registers as for 32's; PERF.md, PR 61), a wave's few
    rows one tile of whole sublanes."""
    return min(_ROW_TILE, -(-rows // 8) * 8)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def dsa_select_pallas(scores, lens, *, k: int, interpret: bool = False):
    """scores: [P, R, bt] float32, R whole tiles of ``_row_tile(R)`` rows;
    lens: [R] int32, the positions a row may choose among (its own included).
    Returns the bias [P, R, bt] float32 (0 on the row's ``min(len, k)`` best
    positions under ``len``, -1e30 elsewhere) and, [tiles, 1] int32, the
    counting passes each row tile's search made over its keys."""
    p, r, bt = scores.shape
    rows = _row_tile(r)
    tiles = r // rows
    longest = jnp.max(lens.reshape(tiles, rows), axis=1)
    pages = jnp.minimum((longest + bt - 1) // bt, p).astype(jnp.int32)
    block = pl.BlockSpec((p, rows, bt), lambda i, pages: (0, i, 0))
    return pl.pallas_call(
        functools.partial(_select_kernel, k=k, bt=bt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[pl.BlockSpec((rows, 1), lambda i, pages: (i, 0)), block],
            out_specs=[block, pl.BlockSpec(memory_space=pltpu.SMEM)],  # whole: a word a step
            scratch_shapes=[pltpu.VMEM((-(-p // _PAGE_UNROLL) * _PAGE_UNROLL, rows, bt), jnp.int32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(scores.shape, jnp.float32),
            jax.ShapeDtypeStruct((tiles, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(pages, lens.astype(jnp.int32).reshape(r, 1), scores)


@functools.partial(jax.jit, static_argnames=("k",))
def select_xla(scores, lens, *, k: int):
    """``dsa_select_pallas``'s contract by a sort: the k-th largest value a
    row, everything above it, and of the values AT it the first by position
    (what a stable ``lax.top_k`` keeps)."""
    p, r, bt = scores.shape
    flat = jnp.swapaxes(scores, 0, 1).reshape(r, p * bt)
    valid = jnp.arange(p * bt, dtype=jnp.int32)[None, :] < lens[:, None]
    keys = jnp.where(valid, _ordered(flat), _INT_MIN)
    want = jnp.minimum(lens, k)
    ranked = jnp.sort(keys, axis=1)[:, ::-1]
    kth = jnp.take_along_axis(ranked, jnp.maximum(want - 1, 0)[:, None], axis=1)
    above = keys > kth
    at = keys == kth
    ties = want[:, None] - jnp.sum(above, axis=1, keepdims=True)
    chosen = (above | (at & (jnp.cumsum(at, axis=1) <= ties))) & valid
    bias = jnp.where(chosen, 0.0, _NEG).astype(jnp.float32)
    return jnp.swapaxes(bias.reshape(r, p, bt), 0, 1)


def select(scores, lens, k: int):
    """The selection as a bias, [P, R, bt] float32 (module docstring), and
    the counting passes each row tile made, [tiles] int32 (the sort makes
    none)."""
    r = scores.shape[1]
    tile = _row_tile(r)
    pad = -r % tile
    if not paged._use_pallas():
        return select_xla(scores, lens, k=k), jnp.zeros(((r + pad) // tile,), jnp.int32)
    if pad:
        scores = jnp.pad(scores, ((0, 0), (0, pad), (0, 0)))
        lens = jnp.pad(lens, (0, pad))
    bias, passes = dsa_select_pallas(scores, lens, k=k)
    return bias[:, :r], passes[:, 0]


# ---------------------------------------------------------------------------
# The wave's latent attention over the selected positions.
# ---------------------------------------------------------------------------


def _sparse_decode_kernel(tables_ref, lens_ref, q_ref, lat_ref, bias_ref, o_ref, m_sc, l_sc,
                          acc_sc, *, bt: int, max_blocks: int, rank: int, scale: float):
    """``mla._decode_kernel`` with the selection's bias on the scores."""
    del tables_ref
    r, j = pl.program_id(0), pl.program_id(1)
    length = lens_ref[r]
    n_pages = (length + bt - 1) // bt

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(j < n_pages)
    def _fold():
        page = lat_ref[0]  # [rank + rope, bt]
        s = _dot(q_ref[0], page) * scale + bias_ref[0, 0]  # [H, bt]
        pos = j * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, _NEG)
        m_old = m_sc[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        # A page with no selected position leaves m at -1e30 and s - m at 0:
        # its weights are cut here, not by a later page's alpha.
        p = jnp.where(s > 0.5 * _NEG, jnp.exp(s - m_new), 0.0)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jax.lax.dot_general(
            p.astype(page.dtype), page[:rank], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[...] = m_new

    @pl.when(j == max_blocks - 1)
    def _done():
        o_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def mla_sparse_decode_pallas(q, latent, bias, row_tables, seq_lens, *, rank: int, scale: float,
                             interpret: bool = False):
    """q: [T, H, rank + rope] (absorbed); latent: [blocks, rank + rope, bt];
    bias: [P, T, bt] float32 (``select``'s); row_tables: [T, P] int32;
    seq_lens: [T] int32. Returns [T, H, rank] float32."""
    t, h, width = q.shape
    bt, p = latent.shape[2], row_tables.shape[1]

    def last_page(r, j, lens):
        return jnp.minimum(j, jnp.maximum((lens[r] + bt - 1) // bt - 1, 0))

    return pl.pallas_call(
        functools.partial(_sparse_decode_kernel, bt=bt, max_blocks=p, rank=rank, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(t, p),
            in_specs=[
                pl.BlockSpec((1, h, width), lambda r, j, tables, lens: (r, 0, 0)),
                pl.BlockSpec(
                    (1, width, bt),
                    lambda r, j, tables, lens: (tables[r * p + last_page(r, j, lens)], 0, 0),
                ),
                pl.BlockSpec(
                    (1, 1, 1, bt), lambda r, j, tables, lens: (last_page(r, j, lens), r, 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec((1, h, rank), lambda r, j, tables, lens: (r, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((t, h, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(row_tables.reshape(-1), seq_lens, q, latent, bias.reshape(p, t, 1, bt))


@functools.partial(jax.jit, static_argnames=("rank", "scale"))
def mla_sparse_decode_xla(q, latent, bias, row_tables, seq_lens, *, rank: int, scale: float):
    """The kernel's mathematics in plain XLA: gathers every row's table."""
    t, h, width = q.shape
    pages = jnp.take(latent, row_tables, axis=0)  # [T, P, width, bt]
    ctx = jnp.swapaxes(pages, 2, 3).reshape(t, -1, width)
    s = einsum_f32("thw,tcw->thc", q, ctx) * scale
    s = s + jnp.swapaxes(bias, 0, 1).reshape(t, 1, -1)
    pos = jnp.arange(ctx.shape[1], dtype=jnp.int32)
    s = jnp.where(pos[None, None, :] < seq_lens[:, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return einsum_f32("thc,tcr->thr", p.astype(latent.dtype), ctx[:, :, :rank])


def sparse_latent_decode_rows(q, latent, bias, row_tables, seq_lens, *, rank: int, scale: float):
    """The wave's latent decode over the selected positions."""
    fn = mla_sparse_decode_pallas if paged._use_pallas() else mla_sparse_decode_xla
    return fn(q, latent, bias, row_tables, seq_lens, rank=rank, scale=scale)
