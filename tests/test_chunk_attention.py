"""Chunk-against-paged-prefix attention (tpu/chunk_attention.py): the
resume's kernel in Pallas interpret mode and its XLA form, against a dense
float64 oracle computed from the same (already rounded) operands.

Written tolerance. float32: both forms accumulate in float32 at HIGHEST
precision over at most 336 keys, so they sit within 2e-5 of the oracle for
unit-normal operands (observed 2e-7 .. 2e-6). bfloat16: the kernel rounds
each probability to bfloat16 for P.V (relative 2**-9) and the output to
bfloat16 (half an ulp: 2**-9 relative, outputs stay under 4 in magnitude),
the XLA form rounds the output only: 2e-2 absolute covers both (observed
4e-3 .. 5e-3). A leak of block 0 (filled with 1e3) or a wrong mask moves an
output by far more than either bound.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu.tpu import chunk_attention as ca
from infinistore_tpu.tpu import paged

# (name, q heads, kv heads, head_dim, block_tokens, dtype)
GEOMETRIES = [
    ("gqa_32_8", 32, 8, 128, 16, jnp.bfloat16),
    ("mha_32_32", 32, 32, 128, 16, jnp.bfloat16),
    ("engine_demo", 4, 2, 32, 16, jnp.float32),
    ("disagg_demo", 4, 2, 16, 8, jnp.float32),
]
NUM_BLOCKS = 48
TOL = {jnp.dtype(jnp.float32): 2e-5, jnp.dtype(jnp.bfloat16): 2e-2}


def _oracle(q, k_cache, v_cache, table, start):
    s, h, d = q.shape
    _, _, kvh, _ = k_cache.shape
    groups = h // kvh
    q = np.asarray(q, np.float64)
    k = np.asarray(k_cache, np.float64)[table].reshape(-1, kvh, d)
    v = np.asarray(v_cache, np.float64)[table].reshape(-1, kvh, d)
    out = np.zeros((s, h, d))
    for r in range(s):
        n = start + r + 1
        for head in range(h):
            logits = k[:n, head // groups] @ q[r, head] / np.sqrt(d)
            p = np.exp(logits - logits.max())
            out[r, head] = (p / p.sum()) @ v[:n, head // groups]
    return out


def _case(geom, s, start, table_len, seed):
    """A request whose real pages are scattered over the cache, its table
    padded with zeros, while block 0 holds another request's (loud) data."""
    _, h, kvh, d, bt, dtype = geom
    rng = np.random.default_rng(seed)
    shape = (NUM_BLOCKS, bt, kvh, d)
    k_cache = rng.standard_normal(shape).astype(np.float32)
    v_cache = rng.standard_normal(shape).astype(np.float32)
    k_cache[0], v_cache[0] = 1e3, 1e3
    n_pages = -(-(start + s) // bt)
    assert n_pages <= table_len
    table = np.zeros(table_len, np.int32)
    table[:n_pages] = rng.permutation(np.arange(1, NUM_BLOCKS))[:n_pages]
    # Slots of the last page past the chunk's end: another loud value the
    # causal mask must keep out.
    tail = (start + s) % bt
    if tail:
        k_cache[table[n_pages - 1], tail:] = 1e3
        v_cache[table[n_pages - 1], tail:] = 1e3
    q = rng.standard_normal((s, h, d)).astype(np.float32)
    return (
        jnp.asarray(q, dtype), jnp.asarray(k_cache, dtype),
        jnp.asarray(v_cache, dtype), table,
    )


def _run(form, q, k_cache, v_cache, table, start):
    table, start = jnp.asarray(table), jnp.int32(start)
    if form == "pallas":
        return ca._chunk_prefix_attention_pallas(
            q, k_cache, v_cache, table, start, interpret=True
        )
    return ca.chunk_prefix_attention_xla(q, k_cache, v_cache, table, start)


def _check(geom, form, s, start, table_len, seed=0):
    q, k_cache, v_cache, table = _case(geom, s, start, table_len, seed)
    got = _run(form, q, k_cache, v_cache, table, start)
    assert got.shape == q.shape and got.dtype == q.dtype
    want = _oracle(q, k_cache, v_cache, table, start)
    np.testing.assert_allclose(
        np.asarray(got, np.float64), want, rtol=0, atol=TOL[jnp.dtype(q.dtype)]
    )


@pytest.mark.parametrize("form", ["pallas", "xla"])
@pytest.mark.parametrize("prefix", ["one_page", "whole_groups", "one_page_more"])
@pytest.mark.parametrize("chunk_blocks", [1, 8])
@pytest.mark.parametrize("geom", GEOMETRIES, ids=[g[0] for g in GEOMETRIES])
def test_chunk_attends_its_prefix_once(geom, chunk_blocks, prefix, form):
    """Chunks of 1 and 8 blocks over prefixes of one page, of a whole
    number of the kernel's page groups, and of one page more; the table is
    longer than the request (padded with zeros, block 0 is loud), so the
    kernel's dead steps and clamped pages are exercised in every case."""
    bt = geom[4]
    group = ca._STEP_TOKENS // bt  # pages the kernel folds a grid step
    pages = {"one_page": 1, "whole_groups": group, "one_page_more": group + 1}[prefix]
    # One table length for every prefix: the position is a runtime value, so
    # the three prefixes of a (geometry, chunk) share one compiled program.
    _check(geom, form, chunk_blocks * bt, pages * bt, 3 * group + 3)


@pytest.mark.parametrize("form", ["pallas", "xla"])
@pytest.mark.parametrize("geom", GEOMETRIES, ids=[g[0] for g in GEOMETRIES])
def test_chunk_rows_cross_a_page_and_a_group(geom, form):
    """A one-block chunk that starts mid-page just under a page-group
    boundary: its rows cross a page and the kernel's unmasked / masked step
    boundary (the shapes of the one-block cases above, so nothing new
    compiles)."""
    bt = geom[4]
    group = ca._STEP_TOKENS // bt
    _check(geom, form, bt, ca._STEP_TOKENS - bt // 2 - 1, 3 * group + 3, seed=1)


@pytest.mark.parametrize("form", ["pallas", "xla"])
@pytest.mark.parametrize("rows", ["two_tiles", "two_tiles_and_a_part"])
@pytest.mark.parametrize("geom", GEOMETRIES, ids=[g[0] for g in GEOMETRIES])
def test_chunk_longer_than_a_row_tile(geom, rows, form):
    """A long fresh remainder after a short prefix: the kernel cuts the
    chunk into row tiles, each walking the pages up to its own last row
    (an early tile's walk stops pages before the request's end, a late
    tile's unmasked steps reach into the chunk's own earlier rows), the
    last tile padded. The prefix starts mid-page, so no tile boundary falls
    on a page boundary."""
    bt = geom[4]
    s = {"two_tiles": 2, "two_tiles_and_a_part": 2.3}[rows] * ca._TILE_ROWS
    start = 2 * bt + bt // 2 + 1
    _check(geom, form, int(s), start, NUM_BLOCKS - 1, seed=5)


@pytest.mark.parametrize("form", ["pallas", "xla"])
def test_table_exactly_full_single_row_and_a_draft_of_five(form):
    """The request fills its table to the last entry (no padding to clamp
    into); a chunk of one row (a decode step seen as a chunk); and a length
    that is no multiple of the sublane tile (a speculative draft), mid-page."""
    geom = GEOMETRIES[2]
    bt = geom[4]
    _check(geom, form, bt, 9 * bt, 10, seed=2)
    _check(geom, form, 1, 3 * bt + 5, 6, seed=3)
    _check(geom, form, 5, 2 * bt + 13, 6, seed=4)


def test_dispatcher_takes_the_xla_form_off_the_chip():
    geom = GEOMETRIES[3]
    q, k_cache, v_cache, table = _case(geom, 8, 16, 8, seed=4)
    assert jax.default_backend() != "tpu" and not paged._use_pallas()
    got = ca.chunk_prefix_attention(q, k_cache, v_cache, jnp.asarray(table), jnp.int32(16))
    want = ca.chunk_prefix_attention_xla(q, k_cache, v_cache, jnp.asarray(table), jnp.int32(16))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
