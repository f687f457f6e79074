"""The ``glm-5`` files at a toy size on the CPU, through ``run.execute`` with
the file's own ``program`` (config class, choices, reference, costs): a cache
of two tensors a layer (a latent and an index key a token), a hit that
installs both in every block, prompts whose last block is part full. The toy
``index_topk`` (256) is over every context, so the selection runs, its sets go
to the reference as bits and come back agreed, and it drops nothing: at a toy
width a swapped near-tie is 1 / 32 of a mixer's output, and the CONTEXT's
rows, whose sets the reference does not follow, then carry a bf16 program 3-4%
off it (``index_topk`` 32 here; at the published widths a swap is 1 / 2,048
and the logits read 1.5-2.0% with the compared rows' sets followed: PERF.md,
PR 56). Selections that DROP keys are held to the reference in float32,
``tests/test_glm_dsa.py``. Control flow, counts and
checks; no number from here is a device metric."""

import argparse
import importlib
import json
import os

import pytest

import cache_geometry
import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmarks", "configs", "glm-5.json")) as f:
    REAL = json.load(f)

# The file's keys at a toy size whose values are whole KiB: a latent block of
# 16 tokens x (24 + 8) bf16 and an index block of 16 x 32 bf16, 1 KiB each.
LAYERS = 3
TOY = dict(
    REAL, name="toy-glm", hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=24,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, index_n_heads=2, index_head_dim=32,
    index_topk=256, intermediate_size=128, moe_intermediate_size=32, n_routed_experts=4,
    router_experts=8, experts_held=[0, 4], num_experts_per_tok=2, vocab_size=512,
    num_hidden_layers=LAYERS,
    serving={
        "block_tokens": 16, "cache_blocks": 64, "kv_bytes_per_token": LAYERS * 2 * 1024 // 16,
        "store_block_kib": 1,
    },
)
CLOSED = {
    "loop": "closed", "clients": 2, "schedule_seed": 7, "documents_per_client": 24,
    "asks_per_document": 4, "prefix_tokens": {"64": 2, "128": 1}, "question_tokens": 5,
    "answer_tokens": 20,
}
COUNTERS = (
    "hit_bytes_fetched", "hit_bytes_whole_prefix", "hit_index_bytes_fetched", "save_index_bytes",
    "save_latent_bytes", "dsa_keys_selected", "dsa_keys_in_context", "moe_pairs",
)


def test_the_real_files_serving_numbers_agree_with_themselves():
    """What ``run.py`` sizes the server from, before anything is built."""
    layout = cache_geometry.store_layout(REAL["serving"])
    assert (layout.unit_kib, layout.block_kib, layout.pool_units_per_block) == (16, 1152, 440)
    assert layout.pool_bytes_per_block == REAL["serving"]["kv_bytes_per_token"] * 1024
    plan = traffic.build_plan("reuse-sessions-8k-32k")
    assert cache_geometry.pool_gib(traffic.store_bytes(plan, layout.pool_bytes_per_token)) == 13
    costs = importlib.import_module(REAL["program"]["costs"])
    assert set(costs.WORK_KEYS) == {
        "dsa_index_decode_bytes", "mla_sparse_decode_bytes", "moe_wave_bytes", "moe_prefill_flops",
    }
    # A row over 9 pages, five layers: the pages' index keys, and of the
    # latents the 2,048 selected alone.
    wave = costs.wave_work(REAL, 9, 1)
    assert wave["dsa_index_decode_bytes"] == 5 * (9 * 1024 * 256 + 32 * (256 + 4))
    assert wave["mla_sparse_decode_bytes"] == 5 * (2048 * 1152 + 64 * (1152 + 2048))
    assert abs(costs.held_choices(REAL) - 0.5) < 1e-9
    # A chunk counts its expert products alone: 0.5 held choices a token,
    # three products of 6,144 x 2,048, four expert layers.
    resume, miss = costs.resume_work(REAL, 9, 127), costs.prefill_work(REAL, 8319)
    assert set(miss) == set(resume) == {"moe_prefill_flops"}
    assert resume["moe_prefill_flops"] == 127 * 0.5 * 3 * 2 * 6144 * 2048 * 4
    assert miss["moe_prefill_flops"] == 8319 * 0.5 * 3 * 2 * 6144 * 2048 * 4


def test_toy_glm_cell_runs_and_checks():
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("a rehearsal for the sandbox; the chip runs the real cell")
    import run

    plan = traffic._closed_plan("toy", CLOSED)
    args = argparse.Namespace(workload="toy", seed=2**31 + 56, seconds=4.0, trace=0)
    line, res, _ = run.execute(
        args, {"name": "toy", "chips": 1}, TOY, plan, run.device_line(jax), COUNTERS
    )
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 4, line
    assert res["counters"]["window_compiles"] == 0, res["counters"]
    # Two prompt classes x (miss, partial hit), each following the program's choices.
    assert len(line["compared"]) == 4 and all("max_gap" in c for c in line["compared"]), line["compared"]
    c = res["counters"]
    hits = [r for r in res["rows"] if r["hit"]]
    # Both tensors of every layer, every block.
    assert hits and all(r["fetched_values"] == r["hit_blocks"] * 2 * LAYERS for r in hits)
    assert c["hit_bytes_fetched"] == c["hit_bytes_whole_prefix"] == 2 * c["hit_index_bytes_fetched"] > 0, c
    assert c["save_index_bytes"] == c["save_latent_bytes"] > 0, c
    # Every context is under the toy index_topk: the selection keeps all.
    assert 0 < c["dsa_keys_selected"] == c["dsa_keys_in_context"] and c["moe_pairs"] > 0, c
