"""The new configuration's whole run at a toy size on the CPU: a model whose
layers choose experts and whose sliding layers install their last blocks
only, through ``run.execute`` with the files' own ``program`` (config class,
choices, reference, costs). Control flow, counts and checks; no number from
here is a device metric."""

import argparse
import json
import os

import pytest

import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmarks", "configs", "trinity-mini.json")) as f:
    REAL = json.load(f)

# The published keys at a toy size: window 32 tokens = 2 blocks of 16.
TOY = dict(
    REAL, name="toy-afmoe", hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, intermediate_size=128, moe_intermediate_size=32, num_experts=8,
    num_experts_per_tok=2, vocab_size=512, sliding_window=32,
    serving={
        "block_tokens": 16, "cache_blocks": 96, "kv_bytes_per_token": 5 * 2 * 2 * 16 * 2,
        "store_block_kib": 1,  # 16 tokens x 2 heads x 16 x 2 B
        "hit_installs": [
            {"layers": [0, 1, 2, 3], "tensor": 0, "last_blocks": 2},
            {"layers": [0, 1, 2, 3], "tensor": 1, "last_blocks": 2},
        ],
    },
)
CLOSED = {
    "loop": "closed", "clients": 3, "schedule_seed": 5, "documents_per_client": 60,
    "asks_per_document": 4, "prefix_tokens": {"64": 2, "128": 1}, "question_tokens": 16,
    "answer_tokens": 32,
}
COUNTERS = (
    "hit_values_fetched", "hit_values_whole_prefix", "wave_layer_pages",
    "wave_window_pages_skipped", "moe_pairs", "moe_distinct_experts",
)


def test_toy_afmoe_cell_runs_and_checks():
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("a rehearsal for the sandbox; the chip runs the real cell")
    import run

    plan = traffic._closed_plan("toy", CLOSED)
    args = argparse.Namespace(workload="toy", seed=2**31 + 35, seconds=4.0, trace=0)
    line, res, _ = run.execute(
        args, {"name": "toy", "chips": 1}, TOY, plan, run.device_line(jax), COUNTERS
    )
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 8, line
    assert res["counters"]["window_compiles"] == 0, res["counters"]
    # Every comparison followed the program's choices and read a gap.
    assert len(line["compared"]) == 4 and all("max_gap" in c for c in line["compared"]), line["compared"]
    c = res["counters"]
    hits = [r for r in res["rows"] if r["hit"]]
    assert hits and all(r["fetched_values"] == 2 * (r["hit_blocks"] + 4 * min(r["hit_blocks"], 2)) for r in hits)
    # 4- and 8-block hits: (n + 4 x 2) / 5n = 60% and 40%.
    assert 0.40 <= c["hit_values_fetched"] / c["hit_values_whole_prefix"] <= 0.60, c
    assert 0 < c["wave_window_pages_skipped"] < c["wave_layer_pages"], c
    assert 0 < c["moe_distinct_experts"] <= c["moe_pairs"], c
