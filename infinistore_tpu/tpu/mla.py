"""Latent attention (MLA) over a paged cache of ONE tensor a block.

The cache holds, a token, the normed latent ``c`` (``rank`` values) and the
shared positional key ``k_r`` side by side, the TOKEN the minor axis:
``[blocks, rank + rope, block_tokens]``. (Token-major, ``[.., block_tokens,
576]``, is not how the chip would keep it: 576 is no multiple of the 128
lanes, so XLA lays such an array out with the tokens minor anyway, and a
kernel that wants it row-major costs a copy of the whole cache each way,
every wave.) Two shapes of the same attention:

``latent_decode_rows``
    a decode wave, in the ABSORBED form: the query arrives already multiplied
    through the keys' up-projection (``[T, H, rank + rope]``), the scores are
    taken against the latent rows as they lie in the cache, and the output is
    the probabilities' mix of the latents (``[T, H, rank]``), which the caller
    takes through the values' up-projection. Every page is read once for all
    heads: the bytes bind (a row of 32k tokens reads 36 MiB and computes 60
    FLOP a byte). A Pallas kernel on the chip (the row's pages by its block
    table, scalar-prefetched; pages past the row's length start no copy and
    no compute), plain XLA elsewhere.
``latent_chunk_attention``
    a chunk of one request at contiguous positions (a miss's prefill cut at
    block boundaries, a hit's resume) against the pages of its table, in the
    UNABSORBED form: each page's latents are expanded to keys and values once
    for the whole chunk, a third of the absorbed form's operations at these
    head sizes. A Pallas kernel on the chip (``mla_chunk_attention_pallas``: a
    grid of head groups x the table's pages, the pages by the scalar-
    prefetched block table; a head's page is expanded once for all of the
    chunk's rows and the scores, the probabilities, the running max / sum and
    the accumulator stay in VMEM; with ``tpu/dsa.py``'s selection a bias on
    the scores, fetched once a head GROUP), the page loop in plain XLA
    elsewhere (``latent_chunk_attention_xla``: the kernel's twin, whose score
    tensors of heads x rows x page tokens go through memory several times a
    page: 2.67 ms a page-step at GLM-5's widths where the kernel takes 0.64,
    PERF.md, PR 57).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged

_NEG = -1e30
_VMEM_LIMIT = 64 << 20
_CHUNK_ROW_TILE = 256  # the chunk kernel's rows a pass over a head's page
_CHUNK_VMEM_BUDGET = 40 << 20  # a grid step's blocks and scratch, under the limit


def einsum_f32(spec: str, a, b):
    """``einsum`` with a float32 result: the operands as they are on the chip
    (bf16 to the MXU, accumulated in float32); off it, where the CPU's dot
    takes no bf16 x bf16 -> float32 of these shapes, cast first."""
    if jax.default_backend() == "tpu":
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))


def _decode_kernel(tables_ref, lens_ref, q_ref, lat_ref, o_ref, m_sc, l_sc, acc_sc,
                   *, bt: int, max_blocks: int, rank: int, scale: float):
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
        q = q_ref[0]  # [H, rank + rope]
        page = lat_ref[0]  # [rank + rope, bt]
        s = jax.lax.dot_general(
            q, page, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [H, bt]
        pos = j * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, _NEG)
        m_old = m_sc[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
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
def mla_decode_pallas(q, latent, row_tables, seq_lens, *, rank: int, scale: float,
                      interpret: bool = False):
    """q: [T, H, rank + rope]; latent: [blocks, rank + rope, bt]; row_tables:
    [T, max_blocks] int32; seq_lens: [T] int32 (tokens a row attends, its own
    included). Returns [T, H, rank] float32."""
    t, h, width = q.shape
    bt = latent.shape[2]
    max_blocks = row_tables.shape[1]

    def page_of(r, j, tables, lens):
        last = jnp.maximum((lens[r] + bt - 1) // bt - 1, 0)
        return tables[r * max_blocks + jnp.minimum(j, last)], 0, 0

    return pl.pallas_call(
        functools.partial(
            _decode_kernel, bt=bt, max_blocks=max_blocks, rank=rank, scale=scale
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(t, max_blocks),
            in_specs=[
                pl.BlockSpec((1, h, width), lambda r, j, tables, lens: (r, 0, 0)),
                pl.BlockSpec((1, width, bt), page_of),
            ],
            out_specs=pl.BlockSpec((1, h, rank), lambda r, j, tables, lens: (r, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((t, h, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(row_tables.reshape(-1), seq_lens, q, latent)


@functools.partial(jax.jit, static_argnames=("rank", "scale"))
def mla_decode_xla(q, latent, row_tables, seq_lens, *, rank: int, scale: float):
    """The kernel's mathematics in plain XLA (off the chip, and the tests'
    reference for the kernel): gathers every row's whole table."""
    t, h, width = q.shape
    pages = jnp.take(latent, row_tables, axis=0)  # [T, max_blocks, width, bt]
    ctx = jnp.swapaxes(pages, 2, 3).reshape(t, -1, width)
    s = einsum_f32("thw,tcw->thc", q, ctx) * scale
    pos = jnp.arange(ctx.shape[1], dtype=jnp.int32)
    s = jnp.where(pos[None, None, :] < seq_lens[:, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return einsum_f32("thc,tcr->thr", p.astype(latent.dtype), ctx[:, :, :rank])


def latent_decode_rows(q, latent, row_tables, seq_lens, *, rank: int, scale: float):
    """The wave's latent decode: Pallas on the chip, XLA elsewhere."""
    if paged._use_pallas():
        return mla_decode_pallas(q, latent, row_tables, seq_lens, rank=rank, scale=scale)
    return mla_decode_xla(q, latent, row_tables, seq_lens, rank=rank, scale=scale)


def latent_chunk_attention_xla(q, latent, block_table, start_pos, w_kvb, *, rank: int,
                           nope: int, scale: float, bias=None):
    """q: [S, H, nope + rope] (the chunk's queries, unabsorbed); latent:
    [blocks, rank + rope, bt] with the chunk's own rows already written;
    block_table: [max_blocks] int32; start_pos: [] int32, the chunk's first
    position; w_kvb: [rank, H, nope + v] the latents' up-projection to each
    head's keys and values. Query i attends positions <= start_pos + i, and
    where ``bias`` is given ([max_blocks, S, bt] float32, page-major:
    ``tpu/dsa.py``'s selection, 0 or -1e30) those of them it leaves at 0.
    Returns [S, H, v] float32. The kernel's mathematics in plain XLA (off the
    chip, and the tests' reference for the kernel): a loop over the request's
    real pages, the score tensors through memory."""
    s, h, _ = q.shape
    bt = latent.shape[2]
    vdim = w_kvb.shape[2] - nope
    f32 = jnp.float32
    q_pos = start_pos + jnp.arange(s, dtype=jnp.int32)
    n_pages = (start_pos + s + bt - 1) // bt
    q_n, q_r = q[..., :nope], q[..., nope:]

    def fold(j, carry):
        m, l, acc = carry
        page = jnp.take(latent, block_table[j], axis=0)  # [rank + rope, bt]
        kv = jnp.einsum("rc,rhd->chd", page[:rank], w_kvb)  # [bt, H, nope + v]
        sc = einsum_f32("shd,chd->hsc", q_n, kv[..., :nope])
        sc = sc + einsum_f32("shd,dc->hsc", q_r, page[rank:])
        k_pos = j * bt + jnp.arange(bt, dtype=jnp.int32)
        seen = k_pos[None, None, :] <= q_pos[None, :, None]
        sc = sc * scale
        if bias is not None:
            sc = sc + jnp.take(bias, j, axis=0)[None]
        sc = jnp.where(seen, sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + einsum_f32("hsc,chd->hsd", p.astype(kv.dtype), kv[..., nope:])
        return m_new, l, acc

    init = (
        jnp.full((h, s, 1), _NEG, f32), jnp.zeros((h, s, 1), f32), jnp.zeros((h, s, vdim), f32),
    )
    _, l, acc = jax.lax.fori_loop(0, n_pages, fold, init)
    return jnp.swapaxes(acc / l, 0, 1)


def _chunk_kernel(table_ref, meta_ref, q_ref, w_ref, lat_ref, *rest, bt: int, rank: int,
                  nope: int, scale: float, rows: int):
    """One page against a group of heads: a head's keys and values expanded
    from the page ONCE for all of the chunk's rows, then the rows a tile at a
    time, the scores and the probabilities never leaving VMEM. ``rest`` is
    ``(bias_ref,) o_ref, m_sc, l_sc, k_sc, v_sc``; the output block stays
    across a group's pages and is the accumulator."""
    del table_ref
    bias_ref = rest[0] if len(rest) == 6 else None
    o_ref, m_sc, l_sc, k_sc, v_sc = rest[-5:]
    j = pl.program_id(1)
    n_pages, start = meta_ref[0], meta_ref[1]
    heads, s = q_ref.shape[0], q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < n_pages)
    def _fold():
        k_sc[nope:] = lat_ref[0, rank:]  # the positional key every head shares

        def head(g, _):
            kv = jax.lax.dot_general(
                w_ref[g], lat_ref[0, :rank], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(k_sc.dtype)  # [nope + v, bt], rounded once as the loop's ``kv``
            k_sc[:nope] = kv[:nope]
            v_sc[...] = kv[nope:]

            def tile(r, _):
                at = pl.ds(pl.multiple_of(r * rows, rows), rows)
                sc = jax.lax.dot_general(
                    q_ref[g, at], k_sc[...], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale  # [rows, bt]
                if bias_ref is not None:
                    sc = sc + bias_ref[0, at]
                q_pos = start + r * rows + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
                k_pos = j * bt + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
                sc = jnp.where(k_pos <= q_pos, sc, _NEG)
                m_old = m_sc[g, at]
                m_new = jnp.maximum(m_old, jnp.max(sc, axis=1, keepdims=True))
                alpha = jnp.exp(m_old - m_new)
                p = jnp.exp(sc - m_new)
                l_sc[g, at] = alpha * l_sc[g, at] + jnp.sum(p, axis=1, keepdims=True)
                o_ref[g, at] = alpha * o_ref[g, at] + jax.lax.dot_general(
                    p.astype(v_sc.dtype), v_sc[...], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                m_sc[g, at] = m_new
                return 0

            jax.lax.fori_loop(0, s // rows, tile, 0)
            return 0

        jax.lax.fori_loop(0, heads, head, 0)

    @pl.when(j == n_pages - 1)
    def _done():
        o_ref[...] = o_ref[...] / l_sc[...]


def _chunk_tiling(s: int, h: int, bt: int, rank: int, rope: int, nope: int, vdim: int,
                  itemsize: int, biased: bool):
    """(rows a tile, heads a grid step), read off the operands' shapes. The
    rows: ``_CHUNK_ROW_TILE``, or the whole of a shorter chunk rounded up to a
    packed tile's sublanes. The heads: the most that divide ``h`` and keep a
    step's blocks (each double-buffered by the pipeline) and scratch inside
    ``_CHUNK_VMEM_BUDGET``. A page and its bias are fetched once a GROUP, not
    once a head: at GLM-5's widths four heads a step, so a page-step reads the
    float32 bias 16 times (67 MB) where a head a step would read it 64 times
    (268 MB, the score tensor's bytes again)."""
    rows = min(_CHUNK_ROW_TILE, -(-s // 16) * 16)
    s += -s % rows
    shared = 2 * (rank + rope) * bt * itemsize + (2 * s * bt * 4 if biased else 0)
    shared += (nope + rope + vdim) * bt * itemsize + 6 * rows * bt * 4  # k / v scratch, a tile's scores
    a_head = 2 * (s * (nope + rope) + (nope + vdim) * rank) * itemsize  # queries, w_kvb's slice
    a_head += 2 * s * vdim * 4 + 2 * s * 128 * 4  # the result; max and sum, a lane tile each
    fit = max(1, (_CHUNK_VMEM_BUDGET - shared) // a_head)
    return rows, max(g for g in range(1, h + 1) if h % g == 0 and g <= fit)


@functools.partial(jax.jit, static_argnames=("rank", "nope", "scale", "interpret"))
def mla_chunk_attention_pallas(q, latent, block_table, start_pos, w_kvb, *, rank: int,
                               nope: int, scale: float, bias=None, interpret: bool = False):
    """``latent_chunk_attention_xla``'s contract as ONE kernel: a grid of
    (head group, page of the table), the pages by the scalar-prefetched block
    table (a page past the context starts no copy and no compute), each page
    expanded once a head for all of the chunk's rows, scores, probabilities,
    the running max / sum and the accumulator in VMEM. The rows are padded to
    the tile here and cut from the result."""
    s, h, dq = q.shape
    _, width, bt = latent.shape
    p, dkv = block_table.shape[0], w_kvb.shape[2]
    vdim = dkv - nope
    rows, heads = _chunk_tiling(
        s, h, bt, rank, width - rank, nope, vdim, latent.dtype.itemsize, bias is not None
    )
    pad = -s % rows
    q = jnp.pad(jnp.swapaxes(q, 0, 1), ((0, 0), (0, pad), (0, 0)))  # [H, S, dq]
    meta = jnp.stack([(start_pos + s + bt - 1) // bt, start_pos]).astype(jnp.int32)

    def group(i, j, table, meta):
        return i, 0, 0

    def page(j, meta):  # a step past the context stays on its last page
        return jnp.minimum(j, meta[0] - 1)

    in_specs = [
        pl.BlockSpec((heads, s + pad, dq), group),
        pl.BlockSpec((heads, dkv, rank), group),
        pl.BlockSpec((1, width, bt), lambda i, j, table, meta: (table[page(j, meta)], 0, 0)),
    ]
    operands = [q, jnp.transpose(w_kvb, (1, 2, 0)), latent]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, s + pad, bt), lambda i, j, table, meta: (page(j, meta), 0, 0)))
        operands.append(jnp.pad(bias, ((0, 0), (0, pad), (0, 0))))
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, bt=bt, rank=rank, nope=nope, scale=scale, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h // heads, p),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((heads, s + pad, vdim), group),
            scratch_shapes=[
                pltpu.VMEM((heads, s + pad, 1), jnp.float32),
                pltpu.VMEM((heads, s + pad, 1), jnp.float32),
                pltpu.VMEM((dq, bt), latent.dtype),
                pltpu.VMEM((vdim, bt), latent.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((h, s + pad, vdim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(block_table, meta, *operands)
    return jnp.swapaxes(out[:, :s], 0, 1)


def latent_chunk_attention(q, latent, block_table, start_pos, w_kvb, *, rank: int,
                           nope: int, scale: float, bias=None):
    """A chunk's latent attention (``latent_chunk_attention_xla`` has the
    contract): Pallas on the chip, XLA elsewhere."""
    fn = mla_chunk_attention_pallas if paged._use_pallas() else latent_chunk_attention_xla
    return fn(q, latent, block_table, start_pos, w_kvb, rank=rank, nope=nope, scale=scale, bias=bias)
