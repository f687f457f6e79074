"""The ``trinity-mini`` configuration as files: its file against the cache
the program builds (``cache_geometry``, ``store_layout``), its cost module's
counts of window-inside work, its reference's refusal of a set that is not k
distinct ids, and the new ``BENCHMARK.json`` entries against their files."""

import json
import os

import numpy as np
import pytest

import accepted
import cache_geometry
import costs
import costs_afmoe
import reference_afmoe
import run
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)
CELL = "trinity-mini-long-prefix-reuse"
with open(os.path.join(BENCH_DIR, "configs", "trinity-mini.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NEW_METRICS = [
    "moe_wave_roofline.reuse", "moe_prefill_roofline.reuse", "hit_fetch_share.reuse",
    "window_pages_skipped_share.reuse", "moe_distinct_experts_share.reuse",
]


def built():
    """The program's config object and a two-block cache of its shape."""
    import jax.numpy as jnp

    prog, serving = CONFIG["program"], CONFIG["serving"]
    cfg = run.resolve(prog["config_class"])(
        block_tokens=serving["block_tokens"], dtype=jnp.bfloat16,
        **{k: CONFIG[v] for k, v in prog["fields"].items()},
    )
    return cfg, cfg.kv_spec(2).make_caches()


def test_the_file_agrees_with_the_cache_the_program_builds():
    cfg, caches = built()
    serving = CONFIG["serving"]
    for attr, key in CONFIG["program"]["equals"].items():
        assert getattr(cfg, attr) == CONFIG[key]
    geometry = cache_geometry.CacheGeometry.of(caches, serving["hit_installs"])
    geometry.check(serving)  # 10,240 B a token, ten values of 16 KiB a block
    assert geometry.block_nbytes == 16 * 10240 and geometry.values_per_block == 10
    # A 32k + 128-token prompt: every block of the full layer, the last 128 of each sliding one.
    assert geometry.fetched_values(2056) == 2 * (2056 + 4 * 128) == 5136
    assert geometry.fetched_values(100) == 2 * 5 * 100
    assert geometry.fetched_nbytes(5136, 2056) == 5136 * 16384 == 84_148_224
    # What the program's own spec derives from its layers' windows is what the file names.
    spec = cfg.kv_spec(2)
    for n in (1, 127, 128, 129, 520, 2056):
        assert sum(spec.hit_values(n)) == geometry.fetched_values(n)
        firsts = [spec.hit_first_block(layer, n) for layer in range(5)]
        assert firsts == [max(0, n - 128)] * 4 + [0]


def test_the_server_needs_no_unit_of_its_own_and_the_pool_holds_the_plan():
    layout = cache_geometry.store_layout(CONFIG["serving"])
    assert (layout.unit_kib, layout.block_kib, layout.pool_units_per_block) == (16, 16, 10)
    assert layout.pool_bytes_per_token == 10240
    plan = traffic.build_plan("reuse-sessions-8k-32k")
    assert len(plan.requests) == 333 and plan.clients == 3
    assert cache_geometry.pool_gib(traffic.store_bytes(plan, layout.pool_bytes_per_token)) == 18


def test_published_keys_stand_except_the_reduced_ones():
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types"]
    assert CONFIG["published"]["num_hidden_layers"] == 32 and CONFIG["published"]["num_dense_layers"] == 2
    for key, value in {
        "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
        "intermediate_size": 6144, "moe_intermediate_size": 1024, "num_experts": 128,
        "num_experts_per_tok": 8, "num_shared_experts": 1, "vocab_size": 200192,
        "sliding_window": 2048, "route_scale": 2.826, "rms_norm_eps": 1e-5, "rope_theta": 10000,
        "score_func": "sigmoid", "route_norm": True, "mup_enabled": True, "tie_word_embeddings": False,
    }.items():
        assert CONFIG[key] == value, key
    assert CONFIG["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    entry = next(c for c in BENCH["configs"] if c["name"] == "trinity-mini")
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"]
    assert not {"hidden_size", "head_dim", "num_experts_per_tok"} & set(entry["reduced"])
    for told in ("four_norms_a_layer", "qk_norm", "output_gate", "no_rope_on_full_layers",
                 "router_selection_bias", "embedding_scale", "weights"):
        assert told in CONFIG["assumed"], told


# -- the cost module ---------------------------------------------------------


def test_a_sliding_layer_counts_the_pages_inside_its_window():
    one = lambda pages: costs.ragged_decode_bytes(pages, 1, 16, 32, 4, 128, 2)
    # 8k, 16k and 32k contexts: four layers read 129 pages, one all of them.
    for pages in (520, 1032, 2056):
        got = costs_afmoe.wave_work(CONFIG, pages, 1)["ragged_decode_bytes"]
        assert got == one(pages) + 4 * one(129)
    # Under the window every layer reads every page.
    assert costs_afmoe.wave_work(CONFIG, 100, 1)["ragged_decode_bytes"] == 5 * one(100)
    assert costs_afmoe.window_pages(2056, 1, 16, 2048) == 129


def test_a_prompts_flash_attention_counts_the_bands_pairs():
    heads, d, s, w = 32, 128, 32896, 2048
    band = sum(min(i + 1, w) for i in range(s))
    assert costs_afmoe.band_pairs(s, w) == band
    work = costs_afmoe.prefill_work(CONFIG, s)
    assert work["flash_prefill_flops"] == 4 * heads * d * (s * (s + 1) // 2 + 4 * band)
    assert work["moe_prefill_flops"] == s * 8 * 6 * 2048 * 1024 * 4


def test_a_resume_counts_the_keys_its_rows_see():
    rows, pages, w = 128, 2056, 2048
    context = pages * 16
    work = costs_afmoe.resume_work(CONFIG, pages, rows)
    full = costs.chunk_attn_flops(context, rows, 32, 128)
    assert work["chunk_attn_flops"] == full + 4 * 4 * 32 * 128 * rows * w
    seen = w + rows - 1
    assert work["chunk_attn_bytes"] == (
        costs.chunk_attn_bytes(context, rows, 32, 4, 128, 2) + 4 * costs.chunk_attn_bytes(seen, rows, 32, 4, 128, 2)
    )
    assert work["moe_prefill_flops"] == rows * 8 * 6 * 2048 * 1024 * 4
    # A hit under the window: every layer is a full one.
    short = costs_afmoe.resume_work(CONFIG, 64, rows)
    assert short["chunk_attn_flops"] == 5 * costs.chunk_attn_flops(1024, rows, 32, 128)
    assert set(costs_afmoe.WORK_KEYS) == set(costs.WORK_KEYS) | {"moe_prefill_flops", "moe_wave_bytes"}


@pytest.mark.parametrize("width", [1, 2, 3, 4])
def test_the_waves_expert_bytes_are_under_the_distinct_experts_on_average(width):
    """1,000 seeded waves of ``width`` rows under uniform routing (8 distinct
    experts of 128 a row and layer): what ``moe_wave_bytes`` counts for the
    wave's entries is not above the bytes of the distinct experts the wave
    streams, on average; at four rows, the width it assumes, it is the
    expectation itself (held to 1%)."""
    rng = np.random.default_rng([35, width])
    layers, waves = 4, 1000
    distinct = sum(
        len(np.unique(np.concatenate([rng.choice(128, size=8, replace=False) for _ in range(width)])))
        for _ in range(waves * layers)
    ) / waves
    counted = width * costs_afmoe.wave_work(CONFIG, 600, 1)["moe_wave_bytes"]
    streamed = distinct * costs_afmoe.expert_bytes(CONFIG)
    assert costs_afmoe.expert_bytes(CONFIG) == 3 * 2048 * 1024 * 2
    assert abs(costs_afmoe.wave_distinct_share(CONFIG) - 128 * (1 - (15 / 16) ** 4) / 32) < 1e-12
    assert counted <= streamed * (1.01 if width == 4 else 1.0), (counted, streamed)
    assert counted >= 0.90 * streamed


# -- the reference -----------------------------------------------------------


@pytest.mark.parametrize("spoil,says", [
    (lambda c: c.__setitem__((1, 2, 0), c[1, 2, 1]), r"row 1 site 2 .* not 2 distinct ids"),
    (lambda c: c.__setitem__((0, 0, 1), 8), r"row 0 site 0 .* range\(8\)"),
    (lambda c: None, None),
], ids=["repeated", "out-of-range", "sound"])
def test_the_reference_refuses_a_set_that_is_not_k_distinct_ids(spoil, says):
    import jax
    import jax.numpy as jnp

    from infinistore_tpu.models import afmoe

    cfg = afmoe.AfmoeConfig(dtype=jnp.float32)
    params = afmoe.init_params(cfg, jax.random.key(0))
    file = {
        "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "sliding_window": 32, "layer_types": list(cfg.layer_types), "num_dense_layers": 1,
        "num_hidden_layers": 5, "num_experts": 8, "num_experts_per_tok": 2, "score_func": "sigmoid",
        "route_norm": True, "route_scale": 2.826, "rope_theta": 10000, "rms_norm_eps": 1e-5,
        "mup_enabled": True,
    }
    tokens = list(range(40))
    chosen = np.tile(np.asarray([0, 1], np.int32), (2, 4, 1))
    spoil(chosen)
    if says:
        with pytest.raises(ValueError, match=says):
            reference_afmoe.logits_following(params, file, tokens, 2, chosen)
        return
    logits, gaps = reference_afmoe.logits_following(params, file, tokens, 2, chosen)
    assert logits.shape == (2, 512) and gaps.shape == (2, 4)
    # Followed, its own top-2 read a negative gap and the same logits as the plain pass.
    own, own_gaps = reference_afmoe.logits_following(
        params, file, tokens, 2, own_choices(params, file, tokens, 2)
    )
    np.testing.assert_allclose(own, reference_afmoe.logits(params, file, tokens, 2), atol=1e-5)
    assert float(np.max(np.asarray(own_gaps))) < 0


def own_choices(params, file, tokens, rounds):
    """The reference's own top-2 on the last rows, site by site: follow what
    it chose so far and read the next site's scores."""
    chosen = np.tile(np.asarray([0, 1], np.int32), (rounds, 4, 1))
    for site in range(4):
        _, gaps = reference_afmoe.logits_following(params, file, tokens, rounds, chosen)
        for row in range(rounds):
            best = None
            for a in range(8):
                for b in range(a + 1, 8):
                    trial = chosen.copy()
                    trial[row, site] = (a, b)
                    g = float(reference_afmoe.logits_following(params, file, tokens, rounds, trial)[1][row, site])
                    if best is None or g < best[0]:
                        best = (g, (a, b))
            chosen[row, site] = best[1]
    return chosen


# -- BENCHMARK.json ----------------------------------------------------------


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metrics_file_agrees_with_its_entry_and_lists_no_cells(name):
    spec, entry = accepted.agreed(name)
    # The cell it was brought for is on its list; which cells joined it since is not pinned.
    assert CELL in entry["workloads"] and entry["moves"] == "tokens_per_s"
    assert spec["reader"]["kind"] in ("trace_roofline", "counter")
    if spec["reader"]["kind"] == "trace_roofline":
        assert spec["reader"]["cost"] in costs_afmoe.WORK_KEYS and name.endswith("_roofline.reuse")


def test_the_cell_is_listed_as_it_was_brought_and_reports_what_the_reuse_cells_report():
    """By name: where the cell and its five metrics stand in their lists, and
    how many metrics the reuse cells report by now, is not pinned."""
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert {k: cell[k] for k in ("config", "traffic", "chips")} == {
        "config": "trinity-mini", "traffic": "reuse-sessions-8k-32k", "chips": 1,
    }
    listed = [m["name"] for m in run.metrics_for(BENCH, "per_layer", CELL)]
    assert set(NEW_METRICS) <= set(listed)
    assert [m["name"] for m in run.metrics_for(BENCH, "end_to_end", CELL)] == ["tokens_per_s", "setup_s"]
    # Whatever every cell that reports tokens_per_s lists, this one lists too.
    reuse = accepted.cells_reporting("tokens_per_s")
    shared = [m["name"] for m in BENCH["per_layer"] if m.get("workloads") == reuse]
    assert CELL in reuse and shared and set(shared) <= set(listed)
