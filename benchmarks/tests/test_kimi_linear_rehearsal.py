"""The ``kimi-linear-48b-a3b`` files at a toy size on the CPU, through
``run.execute`` with the file's own ``program`` (config class, choices,
reference, costs): a cache of per-layer kinds (a float32 state and a tail a
KDA layer, one latent tensor the MLA layer), a hit that installs every latent
block and the last block's state, prompts whose last block is part full.
Control flow, counts and checks; no number from here is a device metric."""

import argparse
import importlib
import json
import os

import pytest

import cache_geometry
import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmarks", "configs", "kimi-linear-48b-a3b.json")) as f:
    REAL = json.load(f)

# The file's keys at a toy size whose values are whole KiB: a state of 4 x
# 128 x 128 float32 = 256 KiB, a tail of 3 x 1,536 bf16 = 9 KiB, a latent
# block of 16 tokens x (24 + 8) bf16 = 1 KiB.
STATE_KIB, TAIL_KIB, LATENT_KIB, ROUTES_KIB = 256, 9, 1, 4  # 128 tokens x 4 layers x 2 ids x 4 B
TOY = dict(
    REAL, name="toy-kimi", hidden_size=64, num_attention_heads=4, kv_lora_rank=24,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
    moe_intermediate_size=32, num_experts=4, router_experts=8, experts_held=[0, 4],
    num_experts_per_token=2, vocab_size=512,
    linear_attn_config=dict(REAL["linear_attn_config"], num_heads=4, head_dim=128),
    serving={
        "block_tokens": 16, "cache_blocks": 64,
        "kv_bytes_per_token": (4 * (STATE_KIB + TAIL_KIB) + LATENT_KIB + ROUTES_KIB) * 1024 // 16,
        "store_block_kib": STATE_KIB, "store_unit_kib": 16,
        "store_values_kib": [[4, STATE_KIB], [4, TAIL_KIB], [1, LATENT_KIB], [1, ROUTES_KIB]],
        "hit_installs": REAL["serving"]["hit_installs"],
    },
)
CLOSED = {
    "loop": "closed", "clients": 2, "schedule_seed": 7, "documents_per_client": 24,
    "asks_per_document": 4, "prefix_tokens": {"64": 2, "128": 1}, "question_tokens": 5,
    "answer_tokens": 20,
}
COUNTERS = (
    "hit_bytes_fetched", "hit_bytes_whole_prefix", "hit_state_bytes_fetched",
    "save_state_bytes", "save_latent_bytes", "state_carries", "moe_pairs",
)


def test_the_real_files_serving_numbers_agree_with_themselves():
    """What ``run.py`` sizes the server from, before anything is built."""
    layout = cache_geometry.store_layout(REAL["serving"])
    assert (layout.unit_kib, layout.block_kib, layout.pool_units_per_block) == (16, 2048, 605)
    assert sum(c * k for c, k in layout.values_kib) * 1024 == REAL["serving"]["kv_bytes_per_token"] * 1024
    costs = importlib.import_module(REAL["program"]["costs"])
    assert set(costs.WORK_KEYS) == {
        "mla_decode_bytes", "kda_step_bytes", "moe_wave_bytes", "moe_prefill_flops",
    }
    # A row over 9 pages: 9 x 1,024 x 1,152 B of latents, its query and its mix.
    wave = costs.wave_work(REAL, 9, 1)
    assert wave["mla_decode_bytes"] == 9 * 1024 * 1152 + 32 * (1152 + 2048)
    assert wave["kda_step_bytes"] == 4 * 2 * (2048 + 72) * 1024
    assert abs(costs.held_choices(REAL) - 4.0) < 1e-9
    assert set(costs.prefill_work(REAL, 8319)) == set(costs.resume_work(REAL, 9, 127)) == {"moe_prefill_flops"}


def test_toy_kimi_cell_runs_and_checks():
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("a rehearsal for the sandbox; the chip runs the real cell")
    import run

    plan = traffic._closed_plan("toy", CLOSED)
    args = argparse.Namespace(workload="toy", seed=2**31 + 41, seconds=4.0, trace=0)
    line, res, _ = run.execute(
        args, {"name": "toy", "chips": 1}, TOY, plan, run.device_line(jax), COUNTERS
    )
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 4, line
    assert res["counters"]["window_compiles"] == 0, res["counters"]
    # Two prompt classes x (miss, partial hit), each following the program's choices.
    assert len(line["compared"]) == 4 and all("max_gap" in c for c in line["compared"]), line["compared"]
    c = res["counters"]
    hits = [r for r in res["rows"] if r["hit"]]
    # n latent values and a state and a tail for each of the four KDA layers.
    # ... and the last layer's chosen ids.
    assert hits and all(r["fetched_values"] == r["hit_blocks"] + 9 for r in hits)
    state = (4 * (STATE_KIB + TAIL_KIB) + ROUTES_KIB) * 1024
    assert c["hit_state_bytes_fetched"] == len(hits) * state or c["hit_state_bytes_fetched"] % state == 0
    assert 0 < c["hit_bytes_fetched"] < c["hit_bytes_whole_prefix"], c
    assert c["moe_pairs"] > 0 and c["state_carries"] >= 0, c
