"""The ``mellum`` model on the serving path, at a small size on the CPU: hidden
64, 4 / 2 heads x 16, window 32, blocks of 8 (window / block = 4), 8 experts
top-2 of width 32 behind every layer, no shared expert, four layers (three
sliding, one full), two rotations (the full layers' YaRN over an original
context of 32 positions, so every prompt here lies past it and the blend is
live), seeded float32 weights.

- the program through the harness (a miss and its decode through the cache, a
  full hit, a partial hit) against ``benchmarks/reference_mellum.py`` following
  the choices the timed waves reported, and NOT against that reference with the
  cosine unscaled, with the plain rotation on the full layer, or with YaRN's
  table on every layer: the limits are tight enough that each fails;
- a hit of n < 4, = 4 and > 4 blocks fetches exactly what the per-layer policy
  names, counts its window values apart, and never reads an uninstalled block
  (they are poisoned with NaN);
- YaRN's table against a direct transcription of the configuration file's
  equations; ``softmax_topk`` against softmax-over-all, top-k, renormalise (and
  a router in bf16 against the same: outside the limit);
- ``moe.expert_layer`` with no shared expert and no dense layer against a
  loop over the experts, in both its shapes, and the wave kernel (interpret
  mode) at a width that is whole tiles and at one that is not;
- the configuration's file: the published keys, ``reduced``, the cache's
  geometry, and its ``hit_arithmetic`` against ``hit_values``, ``_layer_plan``
  and the benchmark's ``CacheGeometry``.

The entries compiled for a v5e at the published widths are in
``tests/test_tpu_aot_compile.py`` (one file loads the chip's compiler).
"""

import asyncio
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import infinistore_tpu as its
from infinistore_tpu.connector import KVConnector
from infinistore_tpu.engine import ContinuousBatchingHarness, EngineKVAdapter
from infinistore_tpu.models import layers, mellum
from infinistore_tpu.models.mellum import FULL, SLIDING, MellumConfig
from infinistore_tpu.tpu import layerwise, moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
import cache_geometry  # noqa: E402
import reference_mellum  # noqa: E402 - the benchmark's plain reference

CFG = MellumConfig(dtype=jnp.float32)  # the defaults are the small size above
ROPE = {kind: dict(p) for kind, p in CFG.rope_parameters}
FILE = {  # the same size as the configuration file's published keys
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 32, "layer_types": [SLIDING] * 3 + [FULL], "mlp_layer_types": ["sparse"] * 4,
    "num_hidden_layers": 4, "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "moe_intermediate_size": 32, "rope_parameters": ROPE, "rms_norm_eps": 1e-6,
    "hidden_act": "silu", "attention_bias": False, "tie_word_embeddings": False,
}
with open(os.path.join(REPO, "benchmarks", "configs", "mellum2-12b-a2.5b.json")) as f:
    REAL = json.load(f)
BT = CFG.block_tokens
WINDOW_BLOCKS = CFG.sliding_window // BT  # 4
NUM_BLOCKS, MAX_REQ_BLOCKS = 64, 16
GEN = 5
# Float32 on both sides: what is left is the order of the sums (the program
# multiplies a wave's rows through gathered experts, the reference sorts
# pairs), some 1e-5 of the logits' rms at four layers. A wrong rotation or an
# unscaled cosine moves the logits by 1e-2 and more (the cases below).
LOGITS_TOL = 2e-4
GAP_TOL = 1e-3  # the sets are the reference's own top-2, or a tie's other side


@pytest.fixture(scope="module")
def params():
    return mellum.init_params(CFG, jax.random.key(50))


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


def window_values(n: int) -> int:
    """K and V of the last 4 blocks of each of the three sliding layers."""
    return 2 * 3 * min(n, WINDOW_BLOCKS)


def fetched_values(n: int) -> int:
    """... and of every block of the full layer."""
    return window_values(n) + 2 * n


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
            self.calls.append((np.asarray(rows, np.float32), mellum.choices(self.h, rows)))
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


def off_reference(params, tokens, stats, calls, file=FILE):
    """(worst logit difference over the reference logits' rms, widest choice
    gap). Round j decodes position len - 1 + j, teacher-forced on the tokens
    it chose; the reference follows row 0's choices of each round."""
    got = np.concatenate([rows[:1] for rows, _ in calls[:GEN]])
    chosen = np.stack([c[0] for _, c in calls[:GEN]])
    assert chosen.shape == (GEN, 4, 2) and np.all(np.isfinite(got))
    ref, gaps = reference_mellum.logits_following(
        params, file, list(tokens) + stats.generated[: GEN - 1], GEN, chosen
    )
    ref = np.asarray(ref)
    return float(np.max(np.abs(got - ref)) / np.sqrt(np.mean(ref * ref))), float(np.max(np.asarray(gaps)))


def against_reference(params, tokens, stats, calls):
    worst, gap = off_reference(params, tokens, stats, calls)
    assert worst < LOGITS_TOL and gap < GAP_TOL, (worst, gap)


@pytest.mark.parametrize("path", ["miss", "full-hit", "partial-hit"])
def test_the_program_through_the_harness_against_the_reference(conn, params, path):
    rng = np.random.default_rng(501)
    prefix = rng.integers(0, CFG.vocab, size=10 * BT).tolist()  # positions to 80 and on: past 32

    async def drive():
        t = Tapped(conn, params, f"mellum-{path}")
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
        # blocks only (the rest of the prefix is NaN in the cache) and the
        # full layer's installed K carries YaRN's factor as it was saved.
        assert stats.generated == miss[0].generated
        for (got, _), (want, _) in zip(calls, miss[1]):
            np.testing.assert_array_equal(got, want)


WRONG = {
    "unscaled-cosine": {**ROPE, FULL: {**ROPE[FULL], "attention_factor": 1.0}},
    "plain-rotation-on-the-full-layer": {**ROPE, FULL: ROPE[SLIDING]},
    "yarn-on-every-layer": {**ROPE, SLIDING: ROPE[FULL]},
}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_the_limit_tells_a_wrong_rotation(conn, params, wrong):
    """The same comparison with a reference that rotates otherwise: the
    program's logits lie fifty limits off it and more, so a program that
    rotated that way would not pass against the right one."""
    rng = np.random.default_rng(502)
    tokens = rng.integers(0, CFG.vocab, size=10 * BT).tolist()

    async def drive():
        t = Tapped(conn, params, f"mellum-wrong-{wrong}")
        return await t.ask(tokens)

    stats, calls = asyncio.run(drive())
    worst, _ = off_reference(params, tokens, stats, calls)
    assert worst < LOGITS_TOL
    worst, _ = off_reference(params, tokens, stats, calls, dict(FILE, rope_parameters=WRONG[wrong]))
    assert worst > 50 * LOGITS_TOL, worst


@pytest.mark.parametrize("n", [2, 4, 10], ids=["under-window", "window", "over-window"])
def test_a_hit_fetches_what_the_policy_names_and_reads_nothing_else(conn, params, n):
    rng = np.random.default_rng(503 + n)
    prefix = rng.integers(0, CFG.vocab, size=n * BT).tolist()
    question = rng.integers(0, CFG.vocab, size=BT).tolist()

    async def drive():
        t = Tapped(conn, params, f"mellum-fetch-{n}")
        await t.ask(prefix)
        before = t.h.adapter.connector.get_stats()
        part, calls = await t.ask(prefix + question)
        return part, calls, before, t.h.adapter.connector.get_stats()

    part, calls, before, after = asyncio.run(drive())
    delta = lambda key: after[key] - before[key]
    assert part.loaded_blocks == n and part.hit_blocks == n
    assert part.prefetched_blocks == fetched_values(n)
    assert delta("hit_values_fetched") == fetched_values(n)
    assert delta("hit_window_values_fetched") == window_values(n)
    assert delta("hit_values_whole_prefix") == 2 * 4 * n
    assert delta("install_layers") == 4 and delta("install_dispatches") == 1
    assert all(np.all(np.isfinite(rows)) for rows, _ in calls)
    against_reference(params, prefix + question, part, calls)


def test_the_wave_counts_its_pages_and_experts_at_every_layer(conn, params):
    rng = np.random.default_rng(507)
    prompts = [rng.integers(0, CFG.vocab, size=n * BT).tolist() for n in (10, 6, 3)]
    steps = CFG.steps
    assert steps.prefill.__module__ == steps.wave.__module__ == "infinistore_tpu.models.mellum"
    assert CFG.kv_spec(4).windows == (32, 32, 32, None) and CFG.kv_spec(4).window == 32

    async def drive():
        t = Tapped(conn, params, "mellum-counters")
        await asyncio.gather(*(t.h.run_request(p, gen_tokens=GEN) for p in prompts))
        return t.h.metrics()

    m = asyncio.run(drive())
    # Every (row, layer) pair of 4 expert layers chose 2 experts: no layer is dense.
    assert m["moe_pairs"] > 0 and m["moe_pairs"] % 8 == 0
    assert m["moe_pairs"] / 2 <= m["moe_distinct_experts"] <= m["moe_pairs"]
    # The 10-block request's rows attend 5 pages in three of four layers.
    assert 0 < m["wave_window_pages_skipped"] < m["wave_layer_pages"]
    assert m["wave_layer_pages"] % 4 == 0 and m["wave_window_pages_skipped"] % 3 == 0


# ---------------------------------------------------------------------------
# The two rotations.
# ---------------------------------------------------------------------------


def transcribed(p: dict, d: int):
    """The configuration file's ``rotation_full`` / ``rotation_sliding``, pair
    by pair in plain Python: (table, scale, low, high)."""
    f = [float(p["rope_theta"]) ** (-2 * i / d) for i in range(d // 2)]
    if p["rope_type"] == "default":
        return f, 1.0, None, None
    c = lambda r: d * math.log(p["original_max_position_embeddings"] / (2 * math.pi * r)) / (
        2 * math.log(p["rope_theta"])
    )
    low, high = max(math.floor(c(p["beta_fast"])), 0), min(math.ceil(c(p["beta_slow"])), d - 1)
    ramp = [min(max((i - low) / (high - low), 0.0), 1.0) for i in range(d // 2)]
    table = [f[i] / p["factor"] * ramp[i] + f[i] * (1 - ramp[i]) for i in range(d // 2)]
    return table, p["attention_factor"], low, high


@pytest.mark.parametrize("size", ["published", "small"])
@pytest.mark.parametrize("kind", [FULL, SLIDING])
def test_rotation_tables_against_the_files_equations(size, kind):
    rope, d = (REAL["rope_parameters"], REAL["head_dim"]) if size == "published" else (ROPE, 16)
    cfg = MellumConfig(head_dim=d, rope_parameters=rope)
    table, scale = cfg.rotation(kind)
    want, want_scale, low, high = transcribed(rope[kind], d)
    np.testing.assert_allclose(table, want, rtol=1e-6)
    assert table.dtype == np.float32 and scale == want_scale
    # The reference builds its own, after the published code.
    ref_table, ref_scale = reference_mellum.rotation(rope[kind], d)
    np.testing.assert_allclose(ref_table, want, rtol=1e-6)
    assert ref_scale == want_scale
    if kind == SLIDING:
        return
    plain, _ = cfg.rotation(SLIDING)
    assert (low, high) == ((18, 35) if size == "published" else (0, 4))
    assert (low, high) == mellum.yarn_correction_range(rope[FULL], d)
    factor = rope[FULL]["factor"]
    # Three ranges: as published up to low, divided by the factor from high
    # on, strictly between the two in between.
    np.testing.assert_array_equal(table[: low + 1], plain[: low + 1])
    np.testing.assert_allclose(table[high:], plain[high:] / factor, rtol=1e-6)
    between = table[low + 1 : high] / plain[low + 1 : high]
    assert np.all(np.diff(between) < 0) and between[0] < 1 and between[-1] > 1 / factor
    assert scale == pytest.approx(0.1 * math.log(factor) + 1)
    if size == "published":
        assert scale == 1.2772588722239782


def test_a_full_layers_saved_key_carries_the_factor_once():
    """What a full layer writes to its pages is the rotated K: its norm is
    the factor times the unrotated key's, a sliding layer's the key's own."""
    x = jax.random.normal(jax.random.key(3), (1, 6, 2, 16), jnp.float32)
    positions = jnp.arange(40, 46, dtype=jnp.int32)[None]
    norm = lambda a: np.asarray(jnp.linalg.norm(a, axis=-1))
    for kind in (FULL, SLIDING):
        table, scale = CFG.rotation(kind)
        np.testing.assert_allclose(
            norm(mellum.rotate(x, positions, table, scale)), scale * norm(x), rtol=1e-5
        )


# ---------------------------------------------------------------------------
# The router and the expert layer.
# ---------------------------------------------------------------------------


def published_route(logits, k):
    """softmax over ALL, the k largest, divided by their sum."""
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ids = np.argsort(-p, axis=-1, kind="stable")[:, :k]
    chosen = np.take_along_axis(p, ids, axis=-1)
    return ids, chosen / chosen.sum(-1, keepdims=True)


@pytest.mark.parametrize("experts,k", [(8, 2), (64, 8)])
def test_softmax_topk_is_softmax_over_all_topk_renormalised(experts, k):
    cfg = MellumConfig(n_experts=experts, experts_per_token=k, dtype=jnp.float32)
    rng = np.random.default_rng(experts)
    m = jnp.asarray(rng.standard_normal((40, cfg.dim)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((cfg.dim, experts)) / 8, jnp.float32)
    ids, weights = moe.route(m, router, None, cfg)
    logits = np.asarray(m, np.float64) @ np.asarray(router, np.float64)
    want_ids, want = published_route(logits, k)
    np.testing.assert_array_equal(np.sort(np.asarray(ids), -1), np.sort(want_ids, -1))
    order = np.argsort(np.asarray(ids), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(weights), order, -1),
        np.take_along_axis(want, np.argsort(want_ids, -1), -1), atol=2e-6,
    )
    # A router in bf16 is outside this limit a hundred times over.
    rough = (m.astype(jnp.bfloat16) @ router.astype(jnp.bfloat16)).astype(jnp.float32)
    _, rough = published_route(np.asarray(rough, np.float64), k)
    assert np.max(np.abs(np.sort(rough, -1) - np.sort(want, -1))) > 100 * 2e-6


def experts_by_loop(w, m, k):
    """sum_e g_e Wdown_e (silu(Wgate_e m) * Wup_e m), a token and an expert
    at a time, in float64: nothing shared, nothing dense."""
    f64 = lambda a: np.asarray(a, np.float64)
    m64 = f64(m)
    ids, g = published_route(m64 @ f64(w["router"]), k)
    out = np.zeros_like(m64)
    for t in range(m64.shape[0]):
        for e, weight in zip(ids[t], g[t]):
            gate, up = m64[t] @ f64(w["w_gate"][e]), m64[t] @ f64(w["w_up"][e])
            out[t] += weight * ((gate / (1 + np.exp(-gate)) * up) @ f64(w["w_down_moe"][e]))
    return ids, out


@pytest.mark.parametrize("rows", [3, 40], ids=["few-rows", "many-tokens"])
def test_expert_layer_without_shared_or_dense_against_a_loop_over_experts(params, rows):
    w = layers.layer_weights(params, 1)
    assert "ws_gate_up" not in w and "router_bias" not in w and "w_gate_up" not in w
    m = jax.random.normal(jax.random.key(rows), (rows, CFG.dim), jnp.float32)
    got, ids, counts = moe.expert_layer(w, m, CFG)
    want_ids, want = experts_by_loop(w, m, CFG.experts_per_token)
    np.testing.assert_array_equal(np.sort(np.asarray(ids), -1), np.sort(want_ids, -1))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # Every expert is held: what a wave streams is what its rows chose.
    distinct = len(set(want_ids.reshape(-1).tolist())) if rows <= 16 else 0
    assert {name: int(v) for name, v in counts.items()} == {
        "moe_distinct_experts": distinct, "moe_streamed_experts": distinct,
    }


@pytest.mark.parametrize("width", [640, 1024], ids=["whole-width", "two-tiles"])
@pytest.mark.parametrize("n", [4, 0], ids=["padded", "no-slot"])
def test_the_wave_kernel_at_a_width_of_whole_tiles_and_at_one_that_is_not(width, n):
    """``_moe_wave_pallas`` (interpret mode) against the gathered XLA form:
    640 = 5 x 128 is no multiple of the kernel's 512-wide tile and is taken
    whole, as the configuration's 896 = 7 x 128 is; 1,024 is two tiles. With
    ``n`` of the six slots real: four and two padded, and none."""
    assert moe._wave_f_tile(width) == (640 if width == 640 else 512)
    assert moe._wave_f_tile(REAL["moe_intermediate_size"]) == 896
    rng = np.random.default_rng(width)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) / 8, jnp.float32)
    e, d, t = 8, 128, 16
    x, wg, wu, wd = f(t, d), f(e, d, width), f(e, d, width), f(e, width, d)
    slots = jnp.asarray([0, 3, 5, 7, 7, 7], jnp.int32)
    combine = jnp.asarray(rng.random((6, t)), jnp.float32).at[n:].set(0.0)
    got = moe._moe_wave_pallas(
        x, slots, jnp.asarray([n], jnp.int32),
        jnp.broadcast_to(combine[:, :, None], (6, t, 128)), wg, wu, wd, interpret=True,
    )
    want = moe.moe_wave_xla(x, slots, combine, wg, wu, wd)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# The configuration's file.
# ---------------------------------------------------------------------------


def real_config(**kw):
    fields = {k: REAL[v] for k, v in REAL["program"]["fields"].items()}
    return MellumConfig(block_tokens=REAL["serving"]["block_tokens"], **{**fields, **kw})


def test_the_file_gives_the_published_widths_and_builds_the_programs_config():
    want = {
        "hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
        "num_experts": 64, "num_experts_per_tok": 8, "moe_intermediate_size": 896,
        "sliding_window": 1024, "vocab_size": 98304, "rms_norm_eps": 1e-6, "num_hidden_layers": 8,
        "intermediate_size": 7168, "max_position_embeddings": 131072, "model_type": "mellum",
        "norm_topk_prob": True, "attention_bias": False, "tie_word_embeddings": False,
    }
    assert {k: REAL[k] for k in want} == want
    assert REAL["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
            "attention_factor": 1.2772588722239782,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    }
    assert REAL["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types"]
    assert REAL["layer_types"] == [SLIDING, SLIDING, SLIDING, FULL] * 2
    assert REAL["mlp_layer_types"] == ["sparse"] * 8 and REAL["published"]["num_hidden_layers"] == 28
    assert {"qk_norm", "mtp", "weights", "torch_dtype", "unused_keys"} <= set(REAL["assumed"])
    assert REAL["deployment"] and len(REAL["guarantees"]) == 5
    cfg = real_config()
    for attr, key in REAL["program"]["equals"].items():
        assert getattr(cfg, attr) == REAL[key], (attr, key)
    assert cfg.router == "softmax_topk" and cfg.n_shared_experts == 0 and cfg.held == (0, 64)
    hash(cfg)  # a jit's static argument
    with pytest.raises(ValueError, match="norm_topk_prob"):
        real_config(norm_topk_prob=False)
    with pytest.raises(ValueError, match="rope_type"):
        real_config(rope_parameters={**REAL["rope_parameters"], FULL: {"rope_type": "llama3", "rope_theta": 1e4}})
    # The parameters the program makes are the file's arithmetic.
    shapes = jax.eval_shape(lambda k: mellum.init_params(cfg, k), jax.random.key(0))
    count = sum(int(np.prod(a.shape)) for a in shapes.values())
    assert count == 3_794_968_832 and "3,794,968,832" in REAL["serving"]["arithmetic"]


@pytest.mark.parametrize("tokens", [8192, 16384, 32768])
def test_the_cache_spec_and_the_layer_plan_against_the_files_hit_arithmetic(tokens):
    serving = REAL["serving"]
    cfg = real_config()
    spec = cfg.kv_spec(4)
    n = tokens // serving["block_tokens"]
    sliding = [l for l, kind in enumerate(cfg.layer_types) if kind == SLIDING]
    assert spec.windows == tuple(1024 if l in sliding else None for l in range(8))
    assert [e["layers"] for e in serving["hit_installs"]] == [sliding, sliding]
    assert all(e["last_blocks"] == 64 for e in serving["hit_installs"])
    trailing, whole = spec.hit_values(n)
    assert (trailing, whole) == (2 * 6 * 64, 2 * 2 * n)
    values = trailing + whole
    assert values == 2 * (2 * n + 384)
    assert f"{values:,}" in serving["hit_arithmetic"]
    for layer in range(8):
        first = n - 64 if layer in sliding else 0
        assert spec.hit_first_block(layer, n) == first
        plan = layerwise._layer_plan(spec, layer, n, hit=True)
        assert [(t.name, at, m) for t, at, m, _ in plan] == [("k", first, n - first), ("v", first, n - first)]
        assert all(at == 0 and m == n for _, at, m, _ in layerwise._layer_plan(spec, layer, n, hit=False))
    # The benchmark's own reading of the file agrees, and the file's numbers
    # with the cache the program builds.
    geometry = cache_geometry.CacheGeometry.of(spec.make_caches(), serving["hit_installs"])
    geometry.check(serving)
    assert geometry.values_per_block == 16 and geometry.block_nbytes == 16 * serving["kv_bytes_per_token"]
    assert geometry.fetched_values(n) == values
    mib = geometry.installed_nbytes(n) / 2**20
    assert mib == {8192: 44, 16384: 76, 32768: 140}[tokens]
    share = 100 * values / (16 * n)
    assert f"{share:.1f}" in serving["hit_arithmetic"]
