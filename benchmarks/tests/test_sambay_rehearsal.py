"""The ``phi-4-mini-flash-reasoning`` files at a toy size on the CPU, through
``run.execute`` with the file's own ``program`` (config class, reference,
costs; no choices: the model makes no discrete choice): a cache of six layers
for a model of eight, prompt steps that hand back no logits, a hit that
installs every K and V block of ONE layer and the last block's state,
convolution tail and K/V tails of the others, prompts whose last block is part
full. Control flow, counts and checks; no number from here is a device metric."""

import argparse
import json
import os

import pytest

import cache_geometry
import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmarks", "configs", "phi-4-mini-flash-reasoning.json")) as f:
    REAL = json.load(f)

# The file's keys at a toy size whose values are whole KiB: a state of 16 x 256
# float32 = 16 KiB, a convolution tail of 3 x 256 bf16 (6 rows of 128 lanes,
# kept as 8: 2 KiB), a K or V tail of 16 positions x 4 heads x 16 bf16 = 2 KiB,
# a page of 16 tokens x 2 pairs x 32 bf16 = 2 KiB. Eight layers, m s m s m F g c:
# three scan layers, two sliding, the full one; the last two keep nothing.
STATE_KIB, SMALL_KIB = 16, 2
TOY = dict(
    REAL, name="toy-sambay", hidden_size=128, num_attention_heads=8, num_key_value_heads=4,
    intermediate_size=256, vocab_size=512, num_hidden_layers=8, sliding_window=16, mamba_d_state=16,
    mamba_dt_rank=8,
    serving={
        "block_tokens": 16, "cache_blocks": 64,
        "kv_bytes_per_token": (3 * STATE_KIB + 9 * SMALL_KIB) * 1024 // 16,
        "store_block_kib": STATE_KIB, "store_unit_kib": 16,
        "store_values_kib": [[3, STATE_KIB], [9, SMALL_KIB]],
        "hit_installs": [
            {"layers": [0, 1, 2, 3, 4], "tensor": 0, "last_blocks": 1},
            {"layers": [0, 1, 2, 3, 4], "tensor": 1, "last_blocks": 1},
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
    "save_kv_bytes", "save_bytes", "cross_decoder_rows", "stack_rows", "wave_pages",
)


def test_the_cells_traffic_is_falcons_and_its_pool_fits_the_host():
    layout = cache_geometry.store_layout(REAL["serving"])
    assert (layout.unit_kib, layout.block_kib, layout.pool_units_per_block) == (16, 5120, 2118)
    plan = traffic.build_plan("reuse-sessions-8k-32k-16doc")
    assert cache_geometry.pool_gib(traffic.store_bytes(plan, layout.pool_bytes_per_token)) == 17
    # ... at 1,024-token blocks (23,630 + 5,120 KiB a block) it would ask 27:
    # the fallback, had the 5 MiB page not passed the chip and the store.
    units = 9 * 20 + 9 * 2 + 16 * 80 + 2 * 160
    assert cache_geometry.pool_gib(traffic.store_bytes(plan, units * 16 * 1024 / 1024)) == 27


# What may make the toy run not ``correct`` on the CPU and says nothing of the
# chip (``test_mellum_rehearsal.py`` has the whole of it): on the CPU backend
# ``device_put`` is zero-copy and the install's region release waits on the
# scattered caches in a thread; where the resume has donated them first the wait
# raises, the lease is never returned, and later hits take the one-phase load,
# which counts no fetched values. Under several test workers it happens.
CPU_ONLY = ("fetched 0 store values", "installed blocks: read back 0 layers")


def test_toy_sambay_cell_runs_and_checks(capfd):
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("a rehearsal for the sandbox; the chip runs the real cell")
    import run

    plan = traffic._closed_plan("toy", CLOSED)
    args = argparse.Namespace(workload="toy", seed=2**31 + 58, seconds=4.0, trace=0)
    line, res, _ = run.execute(
        args, {"name": "toy", "chips": 1}, TOY, plan, run.device_line(jax), COUNTERS
    )
    said = [l for l in capfd.readouterr().err.splitlines() if l.startswith("not correct: ")]
    assert all(any(kind in l for kind in CPU_ONLY) for l in said), said
    assert line["correct"] == (not said) and line["failed"] == 0 and line["attempted"] >= 4, line
    assert res["counters"]["window_compiles"] == 0, res["counters"]
    # Two prompt classes x (miss, partial hit), no choices to follow.
    assert len(line["compared"]) == 4 and not any("max_gap" in c for c in line["compared"])
    c = res["counters"]
    hits = [r for r in res["rows"] if r["hit"] and r["fetched_values"]]
    # n K and n V values of ONE layer, two tensors of each of the other five.
    assert hits and all(r["fetched_values"] == 2 * r["hit_blocks"] + 10 for r in hits)
    state = (3 * STATE_KIB + 7 * SMALL_KIB) * 1024
    assert c["hit_state_bytes_fetched"] and c["hit_state_bytes_fetched"] % state == 0
    assert 0 < c["hit_bytes_fetched"] < c["hit_bytes_whole_prefix"], c
    # Every block writes every tensor: the state's share of a save is the block's.
    assert c["save_bytes"] == c["save_state_bytes"] + c["save_kv_bytes"]
    assert c["save_state_bytes"] * (2 * SMALL_KIB) == c["save_kv_bytes"] * (state // 1024)
    # The waves' rows ran the whole stack; the prompt pieces' rows are the rest.
    assert 0 < c["cross_decoder_rows"] < c["stack_rows"] and c["wave_pages"] > 0, c
