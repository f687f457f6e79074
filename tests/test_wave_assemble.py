"""What a flush hands the device, without a device or an event loop.

``WaveDecoder._assemble`` turns the taken queue entries into one wave's
operands (flat tokens, positions and owners padded at the tail to the row
bucket, tables padded to the table bucket, the page lists) and moves the
decoder's pad and page ledgers. Every number below is worked out by hand
from the entries, at 8-token blocks.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu.engine import ContinuousBatchingHarness, WaveDecoder
from infinistore_tpu.models import AfmoeConfig, LlamaConfig

MAX_REQ_BLOCKS = 16
# Two full layers, no window.
LLAMA = LlamaConfig(
    vocab=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128,
    block_tokens=8, dtype=jnp.float32,
)
# Five layers, four of them sliding over a window of 32 tokens (4 blocks).
AFMOE = AfmoeConfig(dtype=jnp.float32)


def table(first):
    return np.arange(first, first + MAX_REQ_BLOCKS, dtype=np.int32)


def entry(tokens, positions, first):
    """A queue entry as ``step_chunk`` appends it; ``_assemble`` never
    touches the future."""
    return (list(tokens), list(positions), table(first), None)


CASES = {
    # T = B = 1, one page, nothing padded.
    "one_token": dict(
        cfg=LLAMA, batch=[entry([5], [0], 0)],
        bucket=(1, 1, 1), real_rows=1, pad_pages=0, row_pages=[1],
    ),
    # 5 real rows -> 8, the tail the last row repeated; 3 tables -> 4, the
    # last repeated; every flat row (the repeats too) attends 3 pages.
    "one_three_one": dict(
        cfg=LLAMA,
        batch=[
            entry([5], [16], 0), entry([9, 11, 12], [16, 17, 18], 20), entry([13], [16], 40),
        ],
        bucket=(4, 8, 32), real_rows=5, pad_pages=8, row_pages=[3] * 8,
    ),
    # 5 entries -> B = T = 8, one page a row: the page bucket is full.
    "five_one_token_rows": dict(
        cfg=LLAMA, batch=[entry([r], [7], 20 * r) for r in range(5)],
        bucket=(8, 8, 8), real_rows=5, pad_pages=0, row_pages=[1] * 8,
    ),
    # Position 15 is the last slot of block 1 (2 pages), position 16 the
    # first of block 2 (3 pages).
    "block_edge": dict(
        cfg=LLAMA, batch=[entry([1], [15], 0), entry([2], [16], 20)],
        bucket=(2, 2, 8), real_rows=2, pad_pages=3, row_pages=[2, 3],
    ),
    # A row 81 tokens deep (11 pages, 5 of them from its window's first on)
    # beside a chunk at 25 and 26 tokens (4 pages, all inside the window);
    # the repeated tail row attends 4 more. 23 real pages of 32; the second
    # list holds 17 of min(32, 4 * (32 // 8 + 1)) = 20; the four sliding
    # layers skip 4 * (23 - 17) pages, five layers walk 5 * 23.
    "window": dict(
        cfg=AFMOE, batch=[entry([7], [80], 0), entry([3, 4], [24, 25], 20)],
        bucket=(2, 4, 32), real_rows=3, pad_pages=9, row_pages=[11, 4, 4, 4],
        window_pages=20, window_pad_pages=3, window_row_pages=[5, 4, 4, 4], skipped=24,
    ),
    # An 8:1 skew is ONE wave of 8 + 1 rows -> 16; every row attends 3 pages.
    "eight_to_one": dict(
        cfg=LLAMA,
        batch=[entry(range(8), range(16, 24), 0), entry([13], [16], 20)],
        bucket=(2, 16, 64), real_rows=9, pad_pages=16, row_pages=[3] * 16,
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_assemble(name):
    case = CASES[name]
    cfg, batch = case["cfg"], case["batch"]
    h = ContinuousBatchingHarness.__new__(ContinuousBatchingHarness)
    h.config, h.max_req_blocks = cfg, MAX_REQ_BLOCKS
    decoder = WaveDecoder(h)

    wave = decoder._assemble(batch)

    b_bucket, t_bucket, p_bucket = case["bucket"]
    real = case["real_rows"]
    flat = [(t, p, r) for r, (toks, pos, _, _) in enumerate(batch) for t, p in zip(toks, pos)]
    assert wave.real_rows == real == len(flat)
    # The flat lists: the entries in order, then the last real row repeated.
    got = list(zip(wave.tokens, wave.positions, wave.row_of))
    assert got == flat + [flat[-1]] * (t_bucket - real)
    # The tables: the entries' own, then the last one repeated; no flat
    # token names a padded table row.
    assert len(wave.tables) == b_bucket
    for r, tbl in enumerate(wave.tables):
        np.testing.assert_array_equal(tbl, batch[min(r, len(batch) - 1)][2])
    assert max(wave.row_of) == len(batch) - 1

    def row_pages(meta):
        starts = list(meta.page_starts) + [meta.num_pages - meta.pad_pages]
        return [b - a for a, b in zip(starts, starts[1:])]

    assert wave.meta.num_pages == p_bucket
    assert wave.meta.pad_pages == case["pad_pages"]
    assert row_pages(wave.meta) == case["row_pages"]
    real_pages = p_bucket - case["pad_pages"]
    if "window_pages" in case:
        assert wave.wmeta.num_pages == case["window_pages"]
        assert wave.wmeta.pad_pages == case["window_pad_pages"]
        assert row_pages(wave.wmeta) == case["window_row_pages"]
    else:
        assert wave.wmeta is None

    # The ledgers, moved once by this one wave and by nothing after it
    # (`waves`, `max_wave` and `one_row_waves` count launches: `_resolve`).
    assert decoder.bucket_sizes == {case["bucket"]}
    assert (decoder.launched_rows, decoder.pad_rows) == (t_bucket, t_bucket - real)
    assert (decoder.wave_pages, decoder.wave_pad_pages) == (p_bucket, case["pad_pages"])
    assert decoder.wave_layer_pages == cfg.kv_spec(1).num_layers * real_pages
    assert decoder.wave_window_pages_skipped == case.get("skipped", 0)
    assert (decoder.waves, decoder.max_wave, decoder.one_row_waves) == (0, 0, 0)
