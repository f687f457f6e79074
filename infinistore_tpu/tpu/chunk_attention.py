"""Chunk-against-paged-prefix attention: the resume of a prefix hit.

A prefix hit leaves the request's context in the paged cache and a suffix
chunk (the question) to compute: S_c queries at positions ``start_pos ..
start_pos + S_c - 1`` of ONE request, each attending that request's pages up
to its own position (the chunk's K/V are inserted before attention). Run as
S_c decode rows (the decode kernel of ``paged_attention.py``, one row a
token) every row walks the request's pages again: S_c walks a layer, each
grid step a DMA of one page and two dots a few rows tall — bound by
step overhead, not by bytes or FLOPs. Here the chunk walks the request's
pages ONCE: the scalar-prefetched block table drives the K/V index maps as in
the decode kernels (pages are read in place, nothing is gathered), several
pages fold per grid step (a 16-token page is a small tile), and per KV head
one ``[S_c x G, D] x [D, tokens]`` dot serves all rows and all query heads of
the group. Steps past the request's real pages re-serve the resident pages
(clamped index maps: no DMA) and skip their compute, so a short document does
not pay for the table's padding. A chunk longer than ``_TILE_ROWS`` is cut
into row tiles along a second grid axis, each with its own walk up to its own
last row (the causal triangle of a long suffix is skipped tile by tile), so
the kernel's VMEM and compile time are those of one tile whatever the chunk's
length: a long fresh remainder after a short shared prefix costs a walk a
tile, not a larger program.

Numeric contract: ``flash_prefill.py``'s, which the same tokens get on a miss
— native-dtype operands with f32 accumulation for Q.K, f32 softmax
statistics, probabilities in V's dtype for P.V, output cast to the query
dtype. The XLA form (every other backend) is the dense float32 computation
over the gathered table.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged

_NEG_INF = -1e30
# Tokens folded per grid step: 8 pages of 16 tokens, a page per operand (a
# longer page, 1,024 tokens, is a step of its own: PERF.md, PR 43), so the
# DMAs overlap the previous step's compute. On a v5e 256 ran 10-16% under 128
# (level with 512) at twice the Mosaic compile time: a millisecond of a resume.
_STEP_TOKENS = 128
# Chunk rows a tile: a longer chunk is cut into tiles along a grid axis of
# its own, each with its own walk up to its own last row, so what is resident
# (queries, output, statistics and accumulators of every KV head: 10 MiB at 32
# query heads x 128 rows x 128) and the Mosaic compile time do not grow with
# the chunk. The benchmark's 128-token question is one tile.
_TILE_ROWS = 128
# Above the compiler's default scoped limit, far under a core's VMEM.
_VMEM_LIMIT = 64 << 20
# The longest page a grid step takes whole: a page of more tokens (2,048 where
# the block is a state's snapshot interval and the K/V layer one in ten) is
# walked in slices of this many, each a block of its own of the page's array,
# so what is resident stays what a 1,024-token page asks (at 2,048 tokens x 8
# KV heads a whole page a step wants 73 MiB of VMEM: PERF.md, PR 47).
_PAGE_SLICE_TOKENS = 1024


def _fold_pages(q, k, v, qpos, kpos0, m_scr, l_scr, acc_scr, g, masked,
                window=None):
    """Fold one KV head's ``[T, D]`` keys and values into the running
    (max, denominator, accumulator) of its ``[R, D]`` query rows."""
    r, d = q.shape
    t = k.shape[0]
    prec = (
        jax.lax.Precision.HIGHEST
        if q.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec,
    ) * (1.0 / np.sqrt(d))  # [R, T] f32
    if masked:
        kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, (r, t), 1)
        valid = kpos <= qpos
        if window is not None:
            valid = jnp.logical_and(valid, qpos - kpos < window)
        logits = jnp.where(valid, logits, _NEG_INF)
    m_prev = m_scr[g]  # [R, 128], all lanes equal
    m_next = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev[:, :1] - m_next[:, :1])
    p = jnp.exp(logits - m_next[:, :1])
    if masked:
        p = jnp.where(valid, p, 0.0)
    l_next = alpha * l_scr[g][:, :1] + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec,
    )  # [R, D] f32
    m_scr[g] = m_next
    l_scr[g] = jax.lax.broadcast_in_dim(l_next, m_prev.shape, (0, 1))
    acc_scr[g] = acc_scr[g] * alpha + pv


def _chunk_attn_kernel(
    table_ref,  # scalar-prefetch: [max_blocks] int32 (drives the DMAs)
    start_ref,  # scalar-prefetch: [1] int32, position of the chunk's row 0
    q_ref,  # [KVH, R, D], R = tile x G rows ordered (chunk row, group head)
    *refs,  # PG key pages, PG value pages [1, bt, KVH, D]; out; 3 scratch
    pages, s_real, groups, window=None,
):
    del table_ref
    k_refs, v_refs = refs[:pages], refs[pages : 2 * pages]
    out_ref, m_scr, l_scr, acc_scr = refs[2 * pages :]
    kvh, r, _ = q_ref.shape
    bt = k_refs[0].shape[1]
    step_tokens = pages * bt
    tile = r // groups
    t, i = pl.program_id(0), pl.program_id(1)
    row0 = t * tile  # the tile's first chunk row
    start = start_ref[0]
    # The tile's walk ends at the page of its own last real row.
    n_pages = (start + jnp.minimum(row0 + tile, s_real) + bt - 1) // bt
    kpos0 = i * step_tokens
    if window is not None:
        # A sliding layer: the tile's walk starts at the step that holds the
        # oldest key its first row sees, and its slots under that key's page
        # re-serve that page (_window_walk): by their nominal positions they
        # lie behind every row's window, so the mask drops them.
        kpos0 = kpos0 + _window_walk(start, row0, window, bt, pages)[0] * step_tokens

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def fold(masked):
        qpos = None
        if masked:
            # Rows past the real chunk (tile padding) repeat its last row.
            row = row0 + jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0) // groups
            qpos = start + jnp.minimum(row, s_real - 1)
        # The step's pages as one [T, KVH, D] tile, then one head's [T, D]
        # at a time (a slice along the sublane axis, in the cache's dtype).
        k = jnp.concatenate([ref[0] for ref in k_refs], axis=0)
        v = jnp.concatenate([ref[0] for ref in v_refs], axis=0)
        for g in range(kvh):
            _fold_pages(
                q_ref[g], k[:, g, :], v[:, g, :],
                qpos, kpos0, m_scr, l_scr, acc_scr, g, masked, window,
            )

    # Steps wholly under the tile's first position need no mask; steps past
    # the tile's last real page do nothing (their index maps re-serve the
    # last real step's pages, so nothing is fetched for them either). The
    # last real step's clamped duplicate pages sit above every row's
    # position by their nominal index, so causality masks them.
    whole = kpos0 + step_tokens - 1 <= start + row0
    if window is not None:
        # ... and wholly inside the window of the tile's LAST row.
        whole = jnp.logical_and(
            whole, kpos0 > start + jnp.minimum(row0 + tile, s_real) - 1 - window
        )
    live = kpos0 < n_pages * bt

    @pl.when(jnp.logical_and(live, whole))
    def _below():
        fold(masked=False)

    @pl.when(jnp.logical_and(live, jnp.logical_not(whole)))
    def _diagonal():
        fold(masked=True)

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        # Every row attends at least position 0, so l >= 1.
        for g in range(kvh):
            out_ref[g] = (
                acc_scr[g] / jnp.maximum(l_scr[g][:, :1], 1e-30)
            ).astype(out_ref.dtype)


def _window_walk(start, row0, window: int, bt: int, pages: int):
    """(first step, first page) of a sliding layer's walk for the tile whose
    first row is chunk row ``row0``: the page of the oldest key that row sees
    (position ``start + row0 - window + 1``) and the grid step holding it."""
    first_page = jnp.maximum(start + row0 - (window - 1), 0) // bt
    return first_page // pages, first_page


@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def _chunk_prefix_attention_pallas(
    q, k_cache, v_cache, block_table, start_pos, *, interpret, window=None
):
    """q: [S_c, H, D]; block_table: [max_blocks]; start_pos: [] int32."""
    s, h, d = q.shape
    _, page_tokens, kvh, _ = k_cache.shape
    groups = h // kvh
    # ``bt``, ``n`` and ``pages`` count what a grid step's operand is: a page,
    # or one of the ``per`` slices a long page is walked in.
    per = page_tokens // _PAGE_SLICE_TOKENS if page_tokens % _PAGE_SLICE_TOKENS == 0 else 1
    bt = page_tokens // per
    n = block_table.shape[0] * per
    pages = max(1, min(n, _STEP_TOKENS // bt))
    steps = -(-n // pages)
    # Rows of one KV head together, (chunk row, group head) row-major; the
    # chunk padded to the dtype's sublane tile, a long one to whole row tiles.
    align = 32 // jnp.dtype(q.dtype).itemsize
    tile = min(-(-s // align) * align, _TILE_ROWS)
    tiles = -(-s // tile)
    s_pad = tiles * tile
    qr = jnp.swapaxes(q.reshape(s, kvh, groups, d), 0, 1)  # [KVH, S, G, D]
    if s_pad != s:
        qr = jnp.pad(qr, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    rows = tile * groups
    qr = qr.reshape(kvh, s_pad * groups, d)
    if window is not None:
        # A tile's walk is its rows and the window behind the first: the
        # steps count from the walk's first, and the table's earlier pages
        # (a hit leaves them uninstalled) are never an operand.
        steps = min(steps, (window + tile - 2) // (pages * bt) + 2)

    def page(j):
        def block(tbl, at):
            if per == 1:
                return (tbl[at], 0, 0, 0)
            return (tbl[at // per], at % per, 0, 0)

        def index(t, i, tbl, st):
            last = jnp.minimum((t + 1) * tile, s)  # rows up to the tile's end
            n_pages = jnp.minimum((st[0] + last + bt - 1) // bt, n)
            if window is None:
                step = jnp.minimum(i, (n_pages - 1) // pages)
                return block(tbl, jnp.minimum(step * pages + j, n_pages - 1))
            step0, page0 = _window_walk(st[0], t * tile, window, bt, pages)
            step = jnp.minimum(i + step0, (n_pages - 1) // pages)
            return block(tbl, jnp.clip(step * pages + j, page0, n_pages - 1))

        return pl.BlockSpec((1, bt, kvh, d), index)

    row_tile = pl.BlockSpec((kvh, rows, d), lambda t, i, tbl, st: (0, t, 0))
    page_specs = [page(j) for j in range(pages)]
    out = pl.pallas_call(
        functools.partial(
            _chunk_attn_kernel, pages=pages, s_real=s, groups=groups,
            **({} if window is None else {"window": window}),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles, steps),
            in_specs=[row_tile] + page_specs + page_specs,
            out_specs=row_tile,
            scratch_shapes=[
                pltpu.VMEM((kvh, rows, 128), jnp.float32),
                pltpu.VMEM((kvh, rows, 128), jnp.float32),
                pltpu.VMEM((kvh, rows, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((kvh, s_pad * groups, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(
        block_table.astype(jnp.int32),
        jnp.asarray(start_pos, jnp.int32).reshape(1),
        qr,
        *([k_cache] * pages),
        *([v_cache] * pages),
    )
    out = out.reshape(kvh, s_pad, groups, d)[:, :s]
    return jnp.swapaxes(out, 0, 1).reshape(s, h, d)


@functools.partial(jax.jit, static_argnames=("window",))
def chunk_prefix_attention_xla(q, k_cache, v_cache, block_table, start_pos,
                               window=None):
    """Dense semantics on any backend: gather the table's pages, mask row r
    to positions <= start_pos + r (and, under ``window``, to its last
    ``window`` positions: what lies behind every row's window is zeroed before
    it is multiplied), float32 softmax at HIGHEST precision."""
    s, h, d = q.shape
    _, bt, kvh, _ = k_cache.shape
    groups = h // kvh
    k = jnp.take(k_cache, block_table, axis=0).reshape(-1, kvh, d)
    v = jnp.take(v_cache, block_table, axis=0).reshape(-1, kvh, d)
    qg = q.reshape(s, kvh, groups, d).astype(jnp.float32)
    logits = jnp.einsum(
        "skgd,tkd->kgst", qg, k.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ) * (1.0 / np.sqrt(d))
    qpos = start_pos + jnp.arange(s, dtype=jnp.int32)
    kpos = jnp.arange(k.shape[0], dtype=jnp.int32)
    valid = kpos[None, :] <= qpos[:, None]
    if window is not None:
        valid &= qpos[:, None] - kpos[None, :] < window
        seen = (kpos > start_pos - window)[:, None, None]
        k, v = jnp.where(seen, k, 0), jnp.where(seen, v, 0)
    logits = jnp.where(valid[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "kgst,tkd->skgd", probs, v.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    return out.reshape(s, h, d).astype(q.dtype)


def chunk_prefix_attention(q, k_cache, v_cache, block_table, start_pos,
                           window=None):
    """Attention of ONE request's suffix chunk over its paged context.

    q: [S_c, n_heads, head_dim], row r at position ``start_pos + r``;
    k_cache/v_cache: [num_blocks, block_tokens, n_kv_heads, head_dim] with
    the chunk's own K/V already inserted; block_table: [max_blocks] int32,
    entries past ``ceil((start_pos + S_c) / block_tokens)`` may be any valid
    block id (they are neither read nor attended); start_pos: scalar int32.
    Row r attends positions ``0 .. start_pos + r``, under ``window`` (static;
    a sliding layer) the last ``window`` of them, and no page wholly behind
    the first row's window is read; None lowers the program it always did.
    Returns [S_c, n_heads,
    head_dim] in q's dtype. One walk over the request's pages on TPU, gather
    + dense XLA elsewhere."""
    if paged._use_pallas():
        return _chunk_prefix_attention_pallas(
            q, k_cache, v_cache, block_table, start_pos, interpret=False,
            **({} if window is None else {"window": window}),
        )
    if window is None:
        return chunk_prefix_attention_xla(q, k_cache, v_cache, block_table, start_pos)
    return chunk_prefix_attention_xla(
        q, k_cache, v_cache, block_table, start_pos, window=window
    )
