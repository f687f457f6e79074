"""The ``granite-4.0-h-small`` files at a toy size on the CPU, through
``run.execute`` with the file's own ``program`` (config class, reference,
costs, choices): a cache whose layers are state OR K/V, a hit that installs
every K and V block of the attention layer and the last block's state and
tail of each Mamba layer (and the followed ids), prompts whose last block is
part full, a reference that follows the program's choices at every layer.
Control flow, counts and checks; no number from here is a device metric."""

import argparse
import json
import os

import pytest

import cache_geometry
import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmarks", "configs", "granite-4.0-h-small.json")) as f:
    REAL = json.load(f)

# The file's keys at a toy size whose values are whole KiB: a page of 16 tokens
# x 2 KV heads x 16 bf16 = 1 KiB, a state of 8 x 32 x 64 float32 = 64 KiB, a
# tail of 3 x 384 bf16 (9 rows of 128 lanes, 2.25 KiB, kept as 12 rows: 3 KiB) and
# the ids of 64 tokens x 3 layers x 4 choices (768 int32: 3 KiB).
PAGE_KIB, STATE_KIB, TAIL_KIB, ROUTES_KIB = 1, 64, 3, 3
KINDS = ["mamba", "attention", "mamba"]
TOY = dict(
    REAL, name="toy-granite", hidden_size=128, num_attention_heads=8, num_key_value_heads=2,
    vocab_size=512, layer_types=KINDS, num_hidden_layers=3, mamba_n_heads=8, mamba_d_head=32,
    mamba_d_state=64, mamba_chunk_size=16, intermediate_size=32, shared_intermediate_size=64,
    router_experts=8, num_local_experts=4, experts_held=[0, 4], num_experts_per_tok=4,
    route_tail_tokens=64,
    serving={
        "block_tokens": 16, "cache_blocks": 64,
        "kv_bytes_per_token": (2 * (STATE_KIB + TAIL_KIB) + 2 * PAGE_KIB + ROUTES_KIB) * 1024 // 16,
        "store_block_kib": STATE_KIB, "store_unit_kib": 16,
        "store_values_kib": [[2, PAGE_KIB], [2, STATE_KIB], [3, TAIL_KIB]],
        "hit_installs": [
            {"layers": [0, 2], "tensor": 0, "last_blocks": 1},
            {"layers": [0, 2], "tensor": 1, "last_blocks": 1},
            {"layers": [2], "tensor": 2, "last_blocks": 1},
        ],
    },
)
CLOSED = {
    "loop": "closed", "clients": 2, "schedule_seed": 7, "documents_per_client": 36,
    "asks_per_document": 4, "prefix_tokens": {"64": 2, "128": 1}, "question_tokens": 5,
    "answer_tokens": 20,
}
COUNTERS = (
    "hit_bytes_fetched", "hit_bytes_whole_prefix", "hit_state_bytes_fetched", "save_state_bytes",
    "save_kv_bytes", "save_bytes", "state_carries", "moe_pairs", "moe_held_pairs",
    "moe_distinct_experts", "wave_pages",
)


def test_the_cells_traffic_is_falcons_and_its_pool_fits_the_host():
    layout = cache_geometry.store_layout(REAL["serving"])
    assert (layout.unit_kib, layout.block_kib, layout.pool_units_per_block) == (16, 4096, 2856)
    plan = traffic.build_plan("reuse-sessions-8k-32k-16doc")
    assert cache_geometry.pool_gib(traffic.store_bytes(plan, layout.pool_bytes_per_token)) == 22
    # ... and at 1,024-token blocks it would not: what settled the block.
    assert cache_geometry.pool_gib(traffic.store_bytes(plan, 2600 * 16 * 1024 / 1024)) == 39


# What may make the toy run not ``correct`` on the CPU and says nothing of the
# chip (``test_mellum_rehearsal.py`` has the whole of it): on the CPU backend
# ``device_put`` is zero-copy and the install's region release waits on the
# scattered caches in a thread; where the resume has donated them first the wait
# raises, the lease is never returned, and later hits take the one-phase load,
# which counts no fetched values. Under several test workers it happens.
CPU_ONLY = ("fetched 0 store values", "installed blocks: read back 0 layers")


def test_toy_granite_cell_runs_and_checks(capfd):
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("a rehearsal for the sandbox; the chip runs the real cell")
    import run

    plan = traffic._closed_plan("toy", CLOSED)
    args = argparse.Namespace(workload="toy", seed=2**31 + 47, seconds=4.0, trace=0)
    line, res, _ = run.execute(
        args, {"name": "toy", "chips": 1}, TOY, plan, run.device_line(jax), COUNTERS
    )
    said = [l for l in capfd.readouterr().err.splitlines() if l.startswith("not correct: ")]
    assert all(any(kind in l for kind in CPU_ONLY) for l in said), said
    assert line["correct"] == (not said) and line["failed"] == 0 and line["attempted"] >= 4, line
    assert res["counters"]["window_compiles"] == 0, res["counters"]
    # Two prompt classes x (miss, partial hit), every one with its choices followed.
    assert len(line["compared"]) == 4 and all("max_gap" in c for c in line["compared"])
    c = res["counters"]
    hits = [r for r in res["rows"] if r["hit"] and r["fetched_values"]]
    # n K and n V values of ONE layer, a state and a tail of two, and the ids.
    assert hits and all(r["fetched_values"] == 2 * r["hit_blocks"] + 5 for r in hits)
    state = (2 * (STATE_KIB + TAIL_KIB) + ROUTES_KIB) * 1024
    assert c["hit_state_bytes_fetched"] and c["hit_state_bytes_fetched"] % state == 0
    assert 0 < c["hit_bytes_fetched"] < c["hit_bytes_whole_prefix"], c
    # Every block writes every tensor: the state's share of a save is the block's.
    assert c["save_bytes"] == c["save_state_bytes"] + c["save_kv_bytes"]
    assert c["save_state_bytes"] * (2 * PAGE_KIB) == c["save_kv_bytes"] * (state // 1024)
    # Every layer routes: 3 sites x 4 choices a real row, about half of them held.
    assert c["moe_pairs"] > 0 and c["moe_pairs"] % 12 == 0
    assert 0 < c["moe_held_pairs"] < c["moe_pairs"] and c["wave_pages"] > 0, c
