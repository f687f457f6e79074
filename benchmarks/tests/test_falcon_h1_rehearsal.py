"""The ``falcon-h1-34b`` files at a toy size on the CPU, through
``run.execute`` with the file's own ``program`` (config class, reference,
costs): a cache whose EVERY layer is ``(k, v, state, tail)``, a hit that
installs every K and V block and the last block's state and tail, prompts
whose last block is part full, a reference without ``logits_following``.
Control flow, counts and checks; no number from here is a device metric."""

import argparse
import json
import os

import pytest

import cache_geometry
import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmarks", "configs", "falcon-h1-34b.json")) as f:
    REAL = json.load(f)

# The file's keys at a toy size whose values are whole KiB: a page of 16 tokens
# x 2 KV heads x 16 bf16 = 1 KiB, a state of 4 x 64 x 64 float32 = 64 KiB, a
# tail of 3 x 512 bf16 = 3 KiB.
PAGE_KIB, STATE_KIB, TAIL_KIB, LAYERS = 1, 64, 3, 4
TOY = dict(
    REAL, name="toy-falcon", hidden_size=64, num_attention_heads=10, num_key_value_heads=2,
    head_dim=16, intermediate_size=160, vocab_size=512, rope_theta=1e4,
    mamba_d_ssm=256, mamba_n_heads=4, mamba_d_head=64, mamba_d_state=64, mamba_n_groups=2,
    mamba_chunk_size=16,
    serving={
        "block_tokens": 16, "cache_blocks": 64,
        "kv_bytes_per_token": LAYERS * (2 * PAGE_KIB + STATE_KIB + TAIL_KIB) * 1024 // 16,
        "store_block_kib": STATE_KIB, "store_unit_kib": 16,
        "store_values_kib": [[2 * LAYERS, PAGE_KIB], [LAYERS, STATE_KIB], [LAYERS, TAIL_KIB]],
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
    "save_state_bytes", "save_bytes", "state_carries", "wave_pages", "wave_pad_pages",
)


def test_the_twin_traffic_differs_from_the_accepted_file_in_its_documents_alone():
    """The cell's traffic: ``reuse-sessions-8k-32k`` in every key but
    ``documents_per_client`` (and the ``why`` that says why), so that the
    server's pool, sized for the whole plan, fits the chip's host."""
    was = traffic.load_params("reuse-sessions-8k-32k")
    now = traffic.load_params("reuse-sessions-8k-32k-16doc")
    assert {k for k in was if was[k] != now.get(k)} == {"documents_per_client", "why"}
    assert set(was) == set(now) and (was["documents_per_client"], now["documents_per_client"]) == (28, 16)
    layout = cache_geometry.store_layout(REAL["serving"])
    pool = lambda name: cache_geometry.pool_gib(
        traffic.store_bytes(traffic.build_plan(name), layout.pool_bytes_per_token)
    )
    assert (pool("reuse-sessions-8k-32k"), pool("reuse-sessions-8k-32k-16doc")) == (42, 24)
    plan = traffic.build_plan("reuse-sessions-8k-32k-16doc")
    assert min(len(plan.client_list(c)) for c in range(plan.clients)) >= 63


def test_toy_falcon_cell_runs_and_checks():
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("a rehearsal for the sandbox; the chip runs the real cell")
    import run

    plan = traffic._closed_plan("toy", CLOSED)
    args = argparse.Namespace(workload="toy", seed=2**31 + 43, seconds=4.0, trace=0)
    line, res, _ = run.execute(
        args, {"name": "toy", "chips": 1}, TOY, plan, run.device_line(jax), COUNTERS
    )
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 4, line
    assert res["counters"]["window_compiles"] == 0, res["counters"]
    # Two prompt classes x (miss, partial hit), no choices followed.
    assert len(line["compared"]) == 4 and not any("max_gap" in c for c in line["compared"])
    c = res["counters"]
    hits = [r for r in res["rows"] if r["hit"]]
    # n K and n V values and a state and a tail, for each of the four layers.
    assert hits and all(r["fetched_values"] == LAYERS * (2 * r["hit_blocks"] + 2) for r in hits)
    state = LAYERS * (STATE_KIB + TAIL_KIB) * 1024
    assert c["hit_state_bytes_fetched"] and c["hit_state_bytes_fetched"] % state == 0
    assert 0 < c["hit_bytes_fetched"] < c["hit_bytes_whole_prefix"], c
    # Every block writes every tensor: the state's share of a save is the block's.
    assert c["save_bytes"] and c["save_state_bytes"] * (2 * PAGE_KIB + STATE_KIB + TAIL_KIB) == c[
        "save_bytes"
    ] * (STATE_KIB + TAIL_KIB)
    assert c["wave_pages"] > 0 and c["state_carries"] >= 0, c
