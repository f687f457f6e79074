"""The ``mellum2-12b-a2.5b`` files at a toy size on the CPU, through
``run.execute`` with the file's own ``program`` (config class, reference,
costs, choices) and the new traffic file's shape (twelve asks a document, a
32-token answer): two rotations in one stack, an expert layer behind every
layer, sliding layers that install their last blocks only, the counter the
new metric reads. Control flow, counts and checks; no number from here is a
device metric."""

import argparse
import json
import os

import pytest

import cache_geometry
import readers
import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmarks", "configs", "mellum2-12b-a2.5b.json")) as f:
    REAL = json.load(f)
CELL = "mellum2-completion-prefix-reuse"

# The published keys at a toy size: window 32 tokens = 2 blocks of 16, four
# layers (one period), YaRN over an original context of 32 positions so that
# every prompt lies past it.
KINDS = REAL["layer_types"][:4]
TOY = dict(
    REAL, name="toy-mellum", hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, vocab_size=512,
    sliding_window=32, num_hidden_layers=4, layer_types=KINDS, mlp_layer_types=["sparse"] * 4,
    rope_parameters={
        "full_attention": dict(
            REAL["rope_parameters"]["full_attention"], rope_theta=10000, factor=4,
            original_max_position_embeddings=32, beta_fast=2, beta_slow=0.125,
            attention_factor=1.138629436111989,
        ),
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000},
    },
    serving={
        "block_tokens": 16, "cache_blocks": 96, "kv_bytes_per_token": 4 * 2 * 2 * 16 * 2,
        "store_block_kib": 1,  # 16 tokens x 2 heads x 16 x 2 B
        "hit_installs": [
            {"layers": [0, 1, 2], "tensor": 0, "last_blocks": 2},
            {"layers": [0, 1, 2], "tensor": 1, "last_blocks": 2},
        ],
    },
)
CLOSED = dict(
    traffic.load_params("reuse-completions-8k-32k"), schedule_seed=5, documents_per_client=21,
    prefix_tokens={"64": 2, "128": 1}, question_tokens=16, answer_tokens=32,
)


def test_the_cells_files_are_the_ones_the_issue_names():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("mellum2-12b-a2.5b", "reuse-completions-8k-32k", 1)
    p = traffic.load_params(cell["traffic"])
    want = {
        "loop": "closed", "clients": 3, "documents_per_client": 14, "asks_per_document": 12,
        "open_documents": 3, "prefix_tokens": {"8192": 4, "16384": 2, "32768": 1},
        "question_tokens": 128, "answer_tokens": 32, "schedule_seed": 20261050,
    }
    assert {k: p[k] for k in want} == want and set(p) == set(want) | {"why", "who"}
    plan = traffic.build_plan(cell["traffic"])
    # 42 documents; a list's last document loses the asks that would follow
    # their own save at once, so 497 of the 504.
    assert len({r.doc for r in plan.requests}) == 42 and len(plan.requests) == 497
    assert min(len(plan.client_list(c)) for c in range(3)) == 165
    assert sum(r.expect_hit for r in plan.requests) / len(plan.requests) > 0.91
    layout = cache_geometry.store_layout(REAL["serving"])
    assert (layout.unit_kib, layout.block_kib, layout.pool_units_per_block) == (16, 16, 16)
    assert cache_geometry.pool_gib(traffic.store_bytes(plan, layout.pool_bytes_per_token)) == 16
    # The one new metric is a data file over a reader kind the harness has.
    on = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert "hit_window_share.reuse" in on  # by name: neither its place nor the cell's count is pinned
    spec = readers.load_layer_metric("hit_window_share.reuse")["reader"]
    assert spec == {"kind": "counter", "key": "hit_window_values_fetched", "per": "hit_values_fetched", "scale": 100.0}
    assert CELL in [m for m in bench["end_to_end"] if m["name"] == "tokens_per_s"][0]["workloads"]


# What may make the toy run not ``correct`` on the CPU, and says nothing of the
# chip: (1) at contexts of 80-144 tokens and a window of 32 a compared row
# attends a few dozen keys, so the context tokens whose top-2 of 8 flipped
# under bf16 (the reference routes them by its own float32 scores) are not
# averaged away as they are behind a 1,024-token window, and the logits read
# 3-11% off where the chip's cell reads about 1%; (2) on the CPU backend
# ``device_put`` is zero-copy, and the install's region release waits on the
# scattered caches in a thread (layerwise.py ``wait_and_mark``): where the
# resume has donated them first the wait raises, the lease is never returned,
# and later hits fall back to the one-phase load, which counts no fetched
# values. The chip copies on upload and waits on the uploads alone.
TOY_SIZE_ONLY = (
    "logits off the float32 reference", "the program's choice at row", "fetched 0 store values",
    "installed blocks: read back 0 layers",
)


def test_toy_mellum_cell_runs_and_checks(capfd):
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("a rehearsal for the sandbox; the chip runs the real cell")
    import run

    plan = traffic._closed_plan("toy", CLOSED)
    # ``install_layers`` over ``install_dispatches`` had a file until PR 55 (it read each
    # configuration's depth in every cell); the program still counts both, so they are named here.
    counters = (readers.counter_keys([
        "hit_window_share.reuse", "hit_fetch_share.reuse", "moe_distinct_experts_share.reuse",
        "window_pages_skipped_share.reuse",
    ]) | {"install_layers", "install_dispatches"}) - run.OWN_COUNTERS
    args = argparse.Namespace(workload="toy", seed=2**31 + 50, seconds=4.0, trace=0)
    line, res, _ = run.execute(
        args, {"name": "toy", "chips": 1}, TOY, plan, run.device_line(jax), counters
    )
    said = [l for l in capfd.readouterr().err.splitlines() if l.startswith("not correct: ")]
    assert all(any(kind in l for kind in TOY_SIZE_ONLY) for l in said), said
    assert line["failed"] == 0 and line["attempted"] >= 8, line
    assert res["counters"]["window_compiles"] == 0, res["counters"]
    # Two prompt classes x (miss, partial hit), every one with its choices followed.
    assert len(line["compared"]) == 4 and all("max_gap" in c for c in line["compared"]), line["compared"]
    c = res["counters"]
    hits = [r for r in res["rows"] if r["hit"] and r["fetched_values"]]
    assert hits and all(r["fetched_values"] == 2 * (r["hit_blocks"] + 3 * min(r["hit_blocks"], 2)) for r in hits)
    # 4- and 8-block hits: (n + 3 x 2) / 4n = 62.5% and 43.75%; of a hit's
    # values 6 / (n + 6) = 60% and 43% are the sliding layers'.
    assert 0.4375 <= c["hit_values_fetched"] / c["hit_values_whole_prefix"] <= 0.625, c
    assert 3 / 7 <= c["hit_window_values_fetched"] / c["hit_values_fetched"] <= 0.6, c
    # One dispatch a hit hands the device all four layers where every layer was staged before
    # the gate; after race (2) above a later hit's dispatch carries fewer (one run in four of
    # this test alone read 272 layers in 70 dispatches), so the CPU holds the bound, not the equality.
    assert 0 < c["install_layers"] <= 4 * c["install_dispatches"], c
    assert 0 < c["wave_window_pages_skipped"] < c["wave_layer_pages"], c
    assert 0 < c["moe_distinct_experts"] <= c["moe_pairs"] and c["moe_pairs"] % 8 == 0, c
    view = readers.Run(res["rows"], c, None, {})
    share = readers.read_layer_metric("hit_window_share.reuse", view)
    assert 100 * 3 / 7 <= share <= 60.0
    # On a program without the counter (the parent) the metric is left out.
    bare = readers.Run(res["rows"], {k: v for k, v in c.items() if k != "hit_window_values_fetched"}, None, {})
    assert readers.read_layer_metric("hit_window_share.reuse", bare) is None
