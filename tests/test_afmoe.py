"""The ``afmoe`` model on the serving path, at a small size on the CPU: hidden
64, 4 / 2 heads x 16, window 32, blocks of 8 (window / block = 4), 8 experts
top-2, one dense layer and four expert layers (three sliding, one full),
seeded float32 weights.

- the program through the harness (a miss and its decode through the cache, a
  full hit, a partial hit) against ``benchmarks/reference_afmoe.py`` following
  the choices the timed waves reported;
- the share test: the expert layer run as 4 shares of 2 experts each, the
  shared expert counted once, adds up to the uncut layer;
- each windowed kernel against plain ``jnp`` at a window that cuts pages
  mid-row, and ``window=None`` bit-equal to the outputs of the parent commit;
- a hit of n < 4, = 4 and > 4 blocks fetches exactly what the per-layer policy
  names and never reads an uninstalled block (they are poisoned with NaN);
- the harness built from ``LlamaConfig`` and from ``AfmoeConfig`` while
  ``engine.py`` names neither model file.
"""

import asyncio
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import infinistore_tpu as its
from infinistore_tpu.connector import KVConnector
from infinistore_tpu.engine import ContinuousBatchingHarness, EngineKVAdapter
from infinistore_tpu.models import LlamaConfig, afmoe
from infinistore_tpu.models import init_params as llama_init
from infinistore_tpu.models.afmoe import FULL, SLIDING, AfmoeConfig
from infinistore_tpu.tpu import chunk_attention as ca
from infinistore_tpu.tpu import flash_prefill as fp
from infinistore_tpu.tpu import moe
from infinistore_tpu.tpu import paged_attention as pa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
import reference_afmoe  # noqa: E402 - the benchmark's plain reference

CFG = AfmoeConfig(dtype=jnp.float32)  # the defaults are the small size above
FILE = {  # the same size as the configuration file's published keys
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 32, "layer_types": [SLIDING] * 4 + [FULL], "num_dense_layers": 1,
    "num_hidden_layers": 5, "num_experts": 8, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "moe_intermediate_size": 32, "intermediate_size": 128, "score_func": "sigmoid",
    "route_norm": True, "route_scale": 2.826, "n_group": 1, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "mup_enabled": True,
}
BT = CFG.block_tokens
WINDOW_BLOCKS = CFG.sliding_window // BT  # 4
NUM_BLOCKS, MAX_REQ_BLOCKS = 64, 16
GEN = 5


@pytest.fixture(scope="module")
def params():
    return afmoe.init_params(CFG, jax.random.key(35))


@pytest.fixture()
def conn():
    srv = its.start_local_server(prealloc_bytes=64 << 20, block_bytes=16 << 10, enable_shm=True)
    c = its.InfinityConnection(
        its.ClientConfig(host_addr="127.0.0.1", service_port=srv.port, log_level="error")
    )
    c.connect()
    yield c
    c.close()
    srv.stop()


def fetched_values(n: int) -> int:
    """What the per-layer policy names for a hit of n blocks: K and V of
    every block of the full layer and of the last 4 of each sliding one."""
    return 2 * (n + 4 * min(n, WINDOW_BLOCKS))


class Tapped:
    """A harness whose ``step_chunk`` keeps, per call, the logits rows and the
    choices the program reports for them (as the benchmark's taps do), and
    whose installs poison the prefix's blocks with NaN first: what a hit does
    not install must never be read."""

    def __init__(self, conn, params, name):
        kvc = KVConnector(conn, CFG.kv_spec(NUM_BLOCKS), name, max_blocks=MAX_REQ_BLOCKS)
        self.h = ContinuousBatchingHarness(
            EngineKVAdapter(kvc), params, CFG, NUM_BLOCKS, MAX_REQ_BLOCKS
        )
        self.calls = []
        step_chunk, install = self.h.wave.step_chunk, self.h.adapter.install_kv

        async def tapped(tokens, positions, table, priority=0):
            rows = await step_chunk(tokens, positions, table, priority=priority)
            self.calls.append((np.asarray(rows, np.float32), afmoe.choices(self.h, rows)))
            return rows

        async def poisoned(prefetch, caches, block_table):
            ids = jnp.asarray(np.asarray(block_table), jnp.int32)
            caches = [tuple(t.at[ids].set(jnp.nan) for t in layer) for layer in caches]
            return await install(prefetch, caches, block_table)

        self.h.wave.step_chunk = tapped
        self.h.adapter.install_kv = poisoned

    async def ask(self, tokens):
        self.calls.clear()
        stats = await self.h.run_request(tokens, gen_tokens=GEN)
        return stats, list(self.calls)


def against_reference(params, tokens, stats, calls):
    """Round j decodes position len - 1 + j, teacher-forced on the tokens it
    chose; the reference follows row 0's choices of each round."""
    got = np.concatenate([rows[:1] for rows, _ in calls[:GEN]])
    chosen = np.stack([c[0] for _, c in calls[:GEN]])
    assert chosen.shape == (GEN, 4, 2)
    ref, gaps = reference_afmoe.logits_following(
        params, FILE, list(tokens) + stats.generated[: GEN - 1], GEN, chosen
    )
    ref = np.asarray(ref)
    scale = np.sqrt(np.mean(ref * ref))
    assert np.all(np.isfinite(got))
    assert np.max(np.abs(got - ref)) / scale < 2e-4, np.max(np.abs(got - ref)) / scale
    # The sets are the reference's own top-2, or a tie's other side.
    assert float(np.max(np.asarray(gaps))) < 1e-3, np.asarray(gaps)


@pytest.mark.parametrize("path", ["miss", "full-hit", "partial-hit"])
def test_the_program_through_the_harness_against_the_reference(conn, params, path):
    rng = np.random.default_rng(351)
    prefix = rng.integers(0, CFG.vocab, size=10 * BT).tolist()

    async def drive():
        t = Tapped(conn, params, f"afmoe-{path}")
        miss, miss_calls = await t.ask(prefix)
        assert miss.loaded_blocks == 0 and miss.computed_blocks == 10
        if path == "miss":
            return prefix, miss, miss_calls, None
        if path == "full-hit":
            hit, calls = await t.ask(prefix)
            assert hit.loaded_blocks == 10 and hit.computed_blocks == 0
            assert hit.prefetched_blocks == fetched_values(10)
            return prefix, hit, calls, (miss, miss_calls)
        tokens = prefix + rng.integers(0, CFG.vocab, size=2 * BT).tolist()
        part, calls = await t.ask(tokens)
        assert part.loaded_blocks == 10 and part.computed_blocks == 2
        assert part.prefetched_blocks == fetched_values(10)
        return tokens, part, calls, None

    tokens, stats, calls, miss = asyncio.run(drive())
    against_reference(params, tokens, stats, calls)
    if miss is not None:
        # The standing demand: a full hit's logits are the miss's EXACTLY,
        # and its tokens too, while the sliding layers hold their last four
        # blocks only (the rest of the prefix is NaN in the cache).
        assert stats.generated == miss[0].generated
        for (got, _), (want, _) in zip(calls, miss[1]):
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n", [2, 4, 10], ids=["under-window", "window", "over-window"])
def test_a_hit_fetches_what_the_policy_names_and_reads_nothing_else(conn, params, n):
    rng = np.random.default_rng(352 + n)
    prefix = rng.integers(0, CFG.vocab, size=n * BT).tolist()
    question = rng.integers(0, CFG.vocab, size=BT).tolist()

    async def drive():
        t = Tapped(conn, params, f"afmoe-fetch-{n}")
        await t.ask(prefix)
        before = dict(t.h.adapter.connector.hit_counters)
        part, calls = await t.ask(prefix + question)
        return part, calls, before, dict(t.h.adapter.connector.hit_counters)

    part, calls, before, after = asyncio.run(drive())
    assert part.loaded_blocks == n and part.hit_blocks == n
    assert part.prefetched_blocks == fetched_values(n)
    assert after["hit_values_fetched"] - before["hit_values_fetched"] == fetched_values(n)
    assert after["hit_values_whole_prefix"] - before["hit_values_whole_prefix"] == 2 * 5 * n
    assert all(np.all(np.isfinite(rows)) for rows, _ in calls)
    against_reference(params, prefix + question, part, calls)


def test_the_wave_counts_its_pages_and_experts(conn, params):
    rng = np.random.default_rng(353)
    prompts = [rng.integers(0, CFG.vocab, size=n * BT).tolist() for n in (10, 6, 3)]

    async def drive():
        t = Tapped(conn, params, "afmoe-counters")
        await asyncio.gather(*(t.h.run_request(p, gen_tokens=GEN) for p in prompts))
        return t.h.metrics()

    m = asyncio.run(drive())
    # Every (row, layer) pair of 4 expert layers chose 2 experts; a wave of
    # several rows shares some, a wave of one shares none.
    assert m["moe_pairs"] > 0 and m["moe_pairs"] % 8 == 0
    assert m["moe_pairs"] / 2 <= m["moe_distinct_experts"] <= m["moe_pairs"]
    # The 10-block request's rows attend 5 pages in four of five layers.
    assert 0 < m["wave_window_pages_skipped"] < m["wave_layer_pages"]
    assert m["wave_layer_pages"] % 5 == 0 and m["wave_window_pages_skipped"] % 4 == 0


# ---------------------------------------------------------------------------
# The share test.
# ---------------------------------------------------------------------------


def _layer(params, layer=1):
    pre = f"l{layer}."
    return {k[len(pre):]: w for k, w in params.items() if k.startswith(pre)}


def _family(name, params):
    """(the family's small configuration class and its keywords, one expert
    layer's weights, the published route scale): both families run the ONE
    expert layer, ``moe.expert_layer``."""
    if name == "afmoe":
        return AfmoeConfig, {"dtype": jnp.float32}, _layer(params), 2.826
    from infinistore_tpu.models import kimi_linear

    kw = {"dtype": jnp.float32}
    cfg = kimi_linear.KimiLinearConfig(**kw)
    return (
        kimi_linear.KimiLinearConfig, kw,
        _layer(kimi_linear.init_params(cfg, jax.random.key(41))), 2.446,
    )


@pytest.mark.parametrize("family", ["afmoe", "kimi_linear"])
@pytest.mark.parametrize("rows", [3, 40], ids=["few-rows", "many-tokens"])
def test_four_shares_of_two_experts_add_up_to_the_uncut_layer(params, rows, family):
    """The expert layer told it holds experts 2i and 2i + 1, four times over:
    every share routes over all 8, computes its own two, the share with
    expert 0 adds the shared expert, and the sum is the uncut layer's output,
    which is the plain float32 computation of the published rule. Under both
    configurations that run it (the second's is a held share on the chip)."""
    config_class, kw, w, route_scale = _family(family, params)
    cfg = config_class(**kw)
    m = jax.random.normal(jax.random.key(rows), (rows, cfg.dim), jnp.float32)
    whole, ids, _ = moe.expert_layer(w, m, cfg)
    total = jnp.zeros_like(whole)
    for first in range(0, 8, 2):
        share = dict(w, **{k: w[k][first : first + 2] for k in ("w_gate", "w_up", "w_down_moe")})
        part, share_ids, _ = moe.expert_layer(
            share, m, config_class(experts_held=(first, 2), **kw)
        )
        np.testing.assert_array_equal(share_ids, ids)  # every share routes over all
        total = total + part
    np.testing.assert_allclose(total, whole, atol=2e-5, rtol=0)
    # By hand: sigmoid scores, top-2, normalised and scaled weights.
    scores = jax.nn.sigmoid(m @ w["router"])
    want = np.zeros((rows, cfg.dim), np.float32)
    for t in range(rows):
        top = np.argsort(-np.asarray(scores[t]))[:2]
        assert set(top.tolist()) == set(np.asarray(ids[t]).tolist())
        for e in top:
            h = jax.nn.silu(m[t] @ w["w_gate"][e]) * (m[t] @ w["w_up"][e])
            want[t] += np.asarray(
                route_scale * scores[t, e] / (scores[t, top].sum() + 1e-20) * (h @ w["w_down_moe"][e])
            )
        su = jnp.einsum("d,dcf->cf", m[t], w["ws_gate_up"])
        want[t] += np.asarray((jax.nn.silu(su[0]) * su[1]) @ w["ws_down"])
    np.testing.assert_allclose(whole, want, atol=2e-5, rtol=0)


# (the share of 16 experts held, the experts the router may not choose): all;
# the first half; a sixteenth; a share the rows choose NOTHING of.
HELD_SHARES = {
    "all": (None, ()),
    "the-first-half": ((0, 8), ()),
    "a-sixteenth": ((5, 1), tuple(range(0, 5)) + tuple(range(6, 14))),
    "none-chosen": ((12, 4), tuple(range(12, 16))),
}


@pytest.mark.parametrize("share", HELD_SHARES)
def test_the_waves_slots_are_the_distinct_held_choices(share):
    """``_wave_slots`` hands the kernel the distinct chosen experts that are
    HELD, ascending, compacted to the front, ``S = min(T * k, count)`` of
    them; ``distinct`` still counts among ALL experts; and the layer over
    those slots is the loop over the held experts the rows chose."""
    held, barred = HELD_SHARES[share]
    kw = dict(n_experts=16, experts_per_token=2, dtype=jnp.float32)
    whole, cfg = AfmoeConfig(**kw), AfmoeConfig(experts_held=held, **kw)
    first, count = cfg.held
    w = _layer(afmoe.init_params(whole, jax.random.key(59)))
    # A barred expert's selection bias keeps it out of every row's top-k.
    w["router_bias"] = w["router_bias"].at[jnp.asarray(barred, jnp.int32)].set(-10.0)
    w.update({k: w[k][first : first + count] for k in ("w_gate", "w_up", "w_down_moe")})
    rows, k = 4, 2
    m = jax.random.normal(jax.random.key(4), (rows, cfg.dim), jnp.float32)
    ids, weights = moe.route(m, w["router"], w["router_bias"], cfg)
    chosen = np.asarray(ids)
    assert not set(chosen.reshape(-1).tolist()) & set(barred)
    want = sorted({e - first for e in chosen.reshape(-1).tolist() if first <= e < first + count})
    assert (share == "none-chosen") == (not want) and (share != "a-sixteenth" or want == [0])

    slots, n, combine, distinct = moe._wave_slots(ids, weights, cfg)
    assert slots.shape == (min(rows * k, count),) and combine.shape == (slots.shape[0], rows)
    assert n.shape == (1,) and int(n[0]) == len(want)
    assert np.asarray(slots)[: len(want)].tolist() == want
    assert 0 <= int(jnp.min(slots)) and int(jnp.max(slots)) < count
    assert int(distinct) == len(set(chosen.reshape(-1).tolist()))
    by_hand = np.zeros(combine.shape, np.float32)
    for s, e in enumerate(want):
        by_hand[s] = np.where(chosen == first + e, np.asarray(weights), 0.0).sum(-1)
    np.testing.assert_array_equal(combine, by_hand)  # zero past the real slots

    got, got_ids, counts = moe.expert_layer(w, m, cfg)
    np.testing.assert_array_equal(got_ids, ids)
    assert {name: int(v) for name, v in counts.items()} == {
        "moe_distinct_experts": int(distinct), "moe_streamed_experts": len(want),
    }
    loop = np.zeros((rows, cfg.dim), np.float32)
    for t in range(rows):
        for e, weight in zip(chosen[t].tolist(), np.asarray(weights[t]).tolist()):
            if first <= e < first + count:
                h = jax.nn.silu(m[t] @ w["w_gate"][e - first]) * (m[t] @ w["w_up"][e - first])
                loop[t] += weight * np.asarray(h @ w["w_down_moe"][e - first])
        if first == 0:
            su = jnp.einsum("d,dcf->cf", m[t], w["ws_gate_up"])
            loop[t] += np.asarray((jax.nn.silu(su[0]) * su[1]) @ w["ws_down"])
    np.testing.assert_allclose(got, loop, atol=2e-5, rtol=0)


@pytest.mark.parametrize("tiles", [1, 2, 4])
@pytest.mark.parametrize("n", [0, 1, 5], ids=["no-slot", "one-slot", "every-slot"])
def test_a_step_past_the_real_slots_names_the_block_before_it(n, tiles):
    """The index maps' rule as a plain function of the grid step (what
    interpret mode cannot show: it computes the same with or without the
    copies): a real step names ``(s, ids[s], j)``; every step past the ``n``
    real slots names what the last real step named, in BOTH coordinates, so
    the walk of the whole grid changes block ``n * tiles`` times and the
    pipeline copies that many tiles, not ``S * tiles``."""
    ids = jnp.asarray([3, 0, 7, 2, 5], jnp.int32)
    walk = [
        tuple(int(v) for v in moe._wave_block(s, j, ids, jnp.asarray([n], jnp.int32), tiles))
        for s in range(ids.shape[0]) for j in range(tiles)
    ]
    real = [(s, int(ids[s]), j) for s in range(n) for j in range(tiles)]
    assert walk[: n * tiles] == real
    parked = real[-1] if real else (0, 3, tiles - 1)
    assert set(walk[n * tiles :]) <= {parked}
    assert 1 + sum(a != b for a, b in zip(walk, walk[1:])) == max(n * tiles, 1)


@pytest.mark.parametrize("width", [256, 1024, 2048], ids=["one-tile", "two-tiles", "four-tiles"])
@pytest.mark.parametrize("n", [0, 3, 6], ids=["no-slot", "padded", "every-slot"])
def test_the_wave_kernel_streams_the_distinct_experts(n, width):
    """``_moe_wave_pallas`` (interpret mode) against the gathered XLA form,
    with ``n`` of six slots real (the combine weights past them are zero, and
    whatever ids stand there are never read)."""
    assert width // moe._wave_f_tile(width) == {256: 1, 1024: 2, 2048: 4}[width]
    rng = np.random.default_rng(354)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) / 8, jnp.float32)
    e, d, t = 8, 128, 16
    x, wg, wu, wd = f(t, d), f(e, d, width), f(e, d, width), f(e, width, d)
    slots = jnp.asarray([1, 4, 6, 7, 7, 7], jnp.int32)
    combine = jnp.asarray(rng.random((6, t)), jnp.float32).at[n:].set(0.0)
    got = moe._moe_wave_pallas(
        x, slots, jnp.asarray([n], jnp.int32),
        jnp.broadcast_to(combine[:, :, None], (6, t, 128)), wg, wu, wd, interpret=True,
    )
    want = moe.moe_wave_xla(x, slots, combine, wg, wu, wd)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    assert n or not np.asarray(got).any()


# ---------------------------------------------------------------------------
# The grouped product's tiles.
# ---------------------------------------------------------------------------

# (a product of the benchmark's traffic, its (token, expert) pairs, the rows a
# group can expect of them, the model's width, an expert's width, the tiles of
# gate / up and of down)
GROUPED_PRODUCTS = [
    ("kimi-miss-piece", 8192, 32, 2304, 1024, (128, 2304, 1024), (128, 1024, 2304)),
    ("kimi-hit-question", 1024, 4, 2304, 1024, (128, 2304, 1024), (128, 1024, 2304)),
    ("mellum-hit-question", 1024, 16, 2304, 896, (128, 2304, 896), (128, 896, 2304)),
    ("mellum-miss-chunk", 33792, 528, 2304, 896, (128, 2304, 896), (128, 896, 2304)),
    ("granite-miss-piece", 20480, 284, 4096, 768, (128, 4096, 384), (128, 768, 2048)),
    ("trinity-hit-question", 1024, 8, 2048, 1024, (128, 2048, 1024), (128, 1024, 2048)),
    ("trinity-miss-chunk", 44032, 344, 2048, 1024, (128, 2048, 1024), (128, 1024, 2048)),
]


@pytest.mark.parametrize("case", GROUPED_PRODUCTS, ids=[c[0] for c in GROUPED_PRODUCTS])
def test_the_grouped_products_tiles_are_a_function_of_its_widths(case):
    """``_gmm_tiling`` at the seven products of the benchmark's routed cells:
    128 rows whatever a group can expect (4 to 528: the sweep found no shape
    a larger tile serves once K is whole; ``_grouped_ffn`` pads the pairs to
    it); K whole; the N tile a whole divisor of its width (a multiple of 128
    lanes) that keeps the weight tile within 2,304 x 1,024 elements."""
    _, _pairs, _rows_a_group, dim, width, up, down = case
    assert moe._gmm_tiling(dim, width) == up and moe._gmm_tiling(width, dim) == down
    for (tm, tk, tn), (k, n) in ((up, (dim, width)), (down, (width, dim))):
        assert tm == 128 and tk == k and n % tn == 0 and tn % 128 == 0
        assert tk * tn <= 2304 * 1024 and (tn == n or tk * tn * 2 > 2304 * 1024)


def test_the_tile_rule_keeps_a_small_width_whole_and_cuts_an_odd_one_at_1024():
    """Widths of the tests' size stay one tile; a K no multiple of 128
    divides stays whole up to 9,216 and beyond it is cut in 1,024s, the last
    tile ragged where they do not divide it, as before the rule; 2,304 is
    cut in whole halves or thirds, never at 1,024."""
    assert moe._gmm_tiling(64, 32) == (128, 64, 32)
    assert moe._gmm_tiling(1100, 2304) == (128, 1100, 1152)
    assert moe._gmm_tiling(9216, 2048) == (128, 9216, 256)
    assert moe._gmm_tiling(18432, 1024) == moe._gmm_tiling(20000, 1024) == (128, 1024, 1024)
    assert moe._lane_tile(2304, 1024) == 768 and moe._lane_tile(2304, 2303) == 1152


@pytest.mark.parametrize(
    "sizes,k,n",
    [
        ((40, 0, 7, 100), 2304, 256),  # short of M, an empty group, 7 rows of a 128-row tile
        ((3, 130, 0, 120), 256, 2304),  # the down product's widths; a group over a tile's edge
        ((300, 0, 150, 61), 4096, 768),  # the N tile half the width; a group of three tiles
    ],
    ids=["gate-up", "down", "two-n-tiles"],
)
def test_gmm_under_the_rules_tiles_against_ragged_dot(sizes, k, n):
    """The Pallas grouped matmul (interpret mode) under ``_gmm_tiling``'s
    triple against ``ragged_dot``: group sizes that sum short of M (the rows
    past them are nobody's), an empty group, groups smaller than the tile."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    rng = np.random.default_rng(51)
    real = sum(sizes)
    tiling = moe._gmm_tiling(k, n)
    m = real + -real % tiling[0] + tiling[0]  # a whole tile of nobody's rows
    assert tiling[1] == k and n % tiling[2] == 0
    f = lambda *s: jnp.asarray(rng.standard_normal(s) / 8, jnp.float32).astype(jnp.bfloat16)
    lhs, rhs, group_sizes = f(m, k), f(len(sizes), k, n), jnp.asarray(sizes, jnp.int32)
    got = gmm(lhs, rhs, group_sizes, preferred_element_type=jnp.float32, tiling=tiling, interpret=True)
    want = jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=jnp.float32)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got[:real], want[:real], atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("tokens", [40, 200])
def test_the_grouped_ffn_pads_its_pairs_to_the_row_tile(monkeypatch, tokens):
    """``_grouped_ffn`` on its Pallas branch (the grouped matmul in interpret
    mode) equals its ``ragged_dot`` branch where the pairs are no whole row
    tiles: 80 pairs (one tile, most of it nobody's) and 400 (four)."""
    from jax.experimental.pallas.ops.tpu import megablox

    from infinistore_tpu.tpu import paged

    w = _layer(afmoe.init_params(CFG, jax.random.key(7)))
    m = jax.random.normal(jax.random.key(tokens), (tokens, CFG.dim), jnp.float32)
    ids, weights = moe.route(m, w["router"], w.get("router_bias"), CFG)
    want = moe._grouped_ffn(m, ids, weights, w, CFG)
    seen, gmm = [], megablox.gmm

    def interpreted(lhs, rhs, group_sizes, **kw):
        seen.append((lhs.shape[0], kw["tiling"]))
        return gmm(lhs, rhs, group_sizes, interpret=True, **kw)

    monkeypatch.setattr(megablox, "gmm", interpreted)
    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    got = moe._grouped_ffn(m, ids, weights, w, CFG)
    assert len(seen) == 3 and all(t[0] == 128 and rows % 128 == 0 for rows, t in seen), seen
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# ---------------------------------------------------------------------------
# The windowed kernels.
# ---------------------------------------------------------------------------


def kernel_inputs():
    rng = np.random.default_rng(20260929)
    h, kvh, d, bt, nb = 4, 2, 128, 8, 24
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return dict(
        h=h, kvh=kvh, d=d, bt=bt, nb=nb, k_cache=f(nb, bt, kvh, d), v_cache=f(nb, bt, kvh, d),
        q_rows=f(3, h, d), q_seq=f(1, 40, h, d), k_seq=f(1, 40, kvh, d), v_seq=f(1, 40, kvh, d),
        q_chunk=f(12, h, d), perm=rng.permutation(nb).astype(np.int32),
    )


TABLES = lambda x: [x["perm"][:8], x["perm"][8:16], x["perm"][16:24]]
LENS = [61, 17, 40]


def run_kernel(kind, x, window):
    kw = {} if window is None else {"window": window}
    if kind == "ragged":
        meta = pa.build_ragged_wave(TABLES(x), LENS, x["bt"], pad_to_pow2=True, **kw)
        return pa._paged_decode_attention_pallas_ragged(
            x["q_rows"], x["k_cache"], x["v_cache"], jnp.asarray(meta.pages),
            jnp.asarray(meta.page_rows), jnp.asarray(meta.page_starts),
            jnp.asarray(meta.seq_lens), interpret=True, **kw,
        )
    if kind == "flash":
        return fp._flash_prefill_pallas(
            x["q_seq"], x["k_seq"], x["v_seq"], causal=True, block_q=16, block_k=16,
            interpret=True, **kw,
        )
    return ca._chunk_prefix_attention_pallas(
        x["q_chunk"], x["k_cache"], x["v_cache"], jnp.asarray(x["perm"][:10]), jnp.int32(52),
        interpret=True, **kw,
    )


def dense(q, k, v, qpos, window):
    """Plain softmax attention: q [R, H, D] at positions qpos over k, v
    [T, KVH, D] at positions 0..T-1, under the window."""
    groups = q.shape[1] // k.shape[1]
    k, v = np.repeat(np.asarray(k), groups, 1), np.repeat(np.asarray(v), groups, 1)
    logits = np.einsum("rhd,thd->hrt", np.asarray(q), k) / np.sqrt(q.shape[-1])
    kpos = np.arange(k.shape[0])
    seen = (kpos[None] <= qpos[:, None]) & (qpos[:, None] - kpos[None] < window)
    logits = np.where(seen[None], logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    return np.einsum("hrt,thd->rhd", p / p.sum(-1, keepdims=True), v)


# Outputs of the three kernels on ``kernel_inputs`` at the parent commit
# (a81a522), interpret mode on the CPU: ``window=None`` must still give them.
PARENT = {
    "ragged": "58e99606e5a053a292b510b9fffe00a4b8b1d509312e949cb475e8cea116b653",
    "flash": "47c81604ce957c0b121f9c03f1fe4ef1a45ca2784235bd6aa05c5ce5bd2b0fdb",
    "chunk": "8938b78080123188de84f44a8a83753ae80327414f072df90611ee306d07c900",
}


@pytest.mark.parametrize("kind", ["ragged", "flash", "chunk"])
def test_no_window_is_bit_equal_to_the_parents_output(kind):
    out = run_kernel(kind, kernel_inputs(), None)
    assert hashlib.sha256(np.asarray(out).tobytes()).hexdigest() == PARENT[kind]


@pytest.mark.parametrize("window", [12, 16, 27], ids=lambda w: f"window{w}")
@pytest.mark.parametrize("kind", ["ragged", "flash", "chunk"])
def test_windowed_kernel_against_plain_attention(kind, window):
    """Windows of 12, 16 and 27 tokens over pages of 8: the window's edge
    falls inside a page, on a page's edge, and mid-page again. Blocks wholly
    behind a row's window are NaN: a kernel that read one would show it."""
    x = kernel_inputs()
    got = np.asarray(run_kernel(kind, poisoned_behind(kind, x, window), window))
    assert np.all(np.isfinite(got))
    gather = lambda c, t, n: np.asarray(c)[np.asarray(t)].reshape(-1, x["kvh"], x["d"])[:n]
    if kind == "ragged":
        want = np.stack([
            dense(x["q_rows"][r : r + 1], gather(x["k_cache"], t, n), gather(x["v_cache"], t, n),
                  np.asarray([n - 1]), window)[0]
            for r, (t, n) in enumerate(zip(TABLES(x), LENS))
        ])
    elif kind == "flash":
        want = dense(x["q_seq"][0], x["k_seq"][0], x["v_seq"][0], np.arange(40), window)[None]
    else:
        table = x["perm"][:10]
        want = dense(x["q_chunk"], gather(x["k_cache"], table, 64), gather(x["v_cache"], table, 64),
                     52 + np.arange(12), window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def poisoned_behind(kind, x, window):
    """The inputs with every cache block NaN that lies wholly behind the
    window of every row that attends it."""
    if kind == "flash":
        return x
    bt = x["bt"]
    if kind == "ragged":
        spans = [(t, (n - window) // bt) for t, n in zip(TABLES(x), LENS)]
    else:
        spans = [(x["perm"][:10], (52 - window + 1) // bt)]
    dead = np.concatenate([np.asarray(t)[: max(0, first)] for t, first in spans]).astype(np.int32)
    poison = lambda c: c.at[jnp.asarray(dead)].set(jnp.nan) if len(dead) else c
    return dict(x, k_cache=poison(x["k_cache"]), v_cache=poison(x["v_cache"]))


@pytest.mark.parametrize("kind", ["rows", "flash", "chunk"])
def test_the_fallbacks_off_the_chip_agree_with_the_kernels(kind):
    """What the CPU runs for a sliding layer (the XLA bodies) against the
    windowed kernels in interpret mode, uninstalled blocks poisoned."""
    x, window = kernel_inputs(), 27
    if kind == "rows":
        y = poisoned_behind("ragged", x, window)
        tables = jnp.asarray(np.stack(TABLES(x)))
        got = pa.paged_decode_attention_rows(
            y["q_rows"], y["k_cache"], y["v_cache"], tables, jnp.asarray(LENS, jnp.int32),
            None, None, None, window=window,
        )
        want = run_kernel("ragged", y, window)
    elif kind == "flash":
        got = fp.flash_prefill_attention(x["q_seq"], x["k_seq"], x["v_seq"], window=window)
        want = run_kernel("flash", x, window)
    else:
        y = poisoned_behind("chunk", x, window)
        got = ca.chunk_prefix_attention(
            y["q_chunk"], y["k_cache"], y["v_cache"], jnp.asarray(x["perm"][:10]), jnp.int32(52),
            window=window,
        )
        want = run_kernel("chunk", y, window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_a_windowed_wave_lists_the_pages_inside_the_window_only():
    tables = [np.arange(10, dtype=np.int32), 100 + np.arange(10, dtype=np.int32)]
    meta = pa.build_ragged_wave(tables, [77, 9], 8, pad_to=16, window=32)
    # Row 0: 77 tokens, the oldest seen is position 45, page 5; pages 5..9.
    assert meta.pages[:5].tolist() == [5, 6, 7, 8, 9] and meta.page_starts.tolist() == [0, 5]
    assert meta.pages[5:7].tolist() == [100, 101] and meta.pad_pages == 9
    assert pa.window_first_page(77, 8, 32) == 5 and pa.window_first_page(77, 8, None) == 0


# ---------------------------------------------------------------------------
# The engine names no model file.
# ---------------------------------------------------------------------------


def test_engine_names_no_model_file():
    with open(os.path.join(REPO, "infinistore_tpu", "engine.py")) as f:
        source = f.read()
    imports = re.findall(r"^\s*(?:from|import)\s+(\S+)", source, flags=re.M)
    # The contract itself (the roles, the packed wave entry) is no model file.
    assert [m for m in imports if "models" in m] == [".models.serving"], imports


def _package_imports(module: str):
    """What ``infinistore_tpu/models/<module>.py`` imports from this package,
    as written: ``.serving``, ``..tpu.paged``, ``..tpu`` (of ``from ..tpu
    import kda``), ..."""
    import ast

    with open(os.path.join(REPO, "infinistore_tpu", "models", f"{module}.py")) as f:
        tree = ast.parse(f.read())
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.level:
            found.append("." * node.level + (node.module or ""))
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            names = [node.module] if isinstance(node, ast.ImportFrom) else [a.name for a in node.names]
            found += [n for n in names if n.split(".")[0] == "infinistore_tpu"]
    return found


# The nine model files that serve a cell. ``long_context``, ``pipeline``,
# ``ring_attention`` and ``ulysses`` are not in the list: they serve nothing and
# go with ROADMAP D13.
MODEL_FILES = [
    "llama", "afmoe", "kimi_linear", "falcon_h1", "granite_hybrid", "mellum", "glm_dsa", "sambay",
    "pangu_mtp",
]


@pytest.mark.parametrize("model", MODEL_FILES)
def test_a_model_file_imports_no_other_model_file(model):
    """The arrows point one way: ``tpu/*`` <- ``models/layers.py`` <-
    ``models/serving.py`` <- a model file <- nothing under ``models/``."""
    imports = _package_imports(model)
    assert imports, model
    allowed = lambda m: m in (".serving", ".layers", "..tpu") or m.startswith("..tpu.")
    assert [m for m in imports if not allowed(m)] == [], imports


def test_the_shared_math_imports_no_models_module():
    imports = _package_imports("layers")
    assert imports and all(m == "..tpu" or m.startswith("..tpu.") for m in imports), imports


def test_the_contract_imports_no_model_file():
    imports = _package_imports("serving")
    assert [m for m in imports if m.startswith(".") and not m.startswith("..tpu")] == [], imports
    assert not any(m.lstrip(".").split(".")[-1] in MODEL_FILES for m in imports), imports


@pytest.mark.parametrize("model", ["llama", "afmoe"])
def test_the_harness_takes_its_steps_from_the_configuration(conn, params, model):
    if model == "llama":
        cfg = LlamaConfig(vocab=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128,
                          block_tokens=8, dtype=jnp.float32)
        weights = llama_init(cfg, jax.random.PRNGKey(0))
    else:
        cfg, weights = CFG, params
    steps = cfg.steps
    assert steps.prefill.__module__ == steps.wave.__module__ == f"infinistore_tpu.models.{model}"
    assert (cfg.kv_spec(4).window is None) == (model == "llama")
    kvc = KVConnector(conn, cfg.kv_spec(NUM_BLOCKS), f"steps-{model}", max_blocks=MAX_REQ_BLOCKS)
    h = ContinuousBatchingHarness(EngineKVAdapter(kvc), weights, cfg, NUM_BLOCKS, MAX_REQ_BLOCKS)
    tokens = np.random.default_rng(355).integers(0, 128, size=3 * 8).tolist()

    async def drive():
        first = await h.run_request(tokens, gen_tokens=3)
        again = await h.run_request(tokens, gen_tokens=3)
        return first, again

    first, again = asyncio.run(drive())
    assert first.computed_blocks == 3 and again.loaded_blocks == 3
    assert again.generated == first.generated and len(first.generated) == 3
