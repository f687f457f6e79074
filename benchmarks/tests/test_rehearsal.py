"""The whole run at a toy size on the CPU: control flow, counts and checks.
No number from here is a device metric; the line is never printed."""

import argparse
import json
import os

import pytest

import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOY = {
    "name": "toy", "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "serving": {"block_tokens": 16, "cache_blocks": 64, "kv_bytes_per_token": 2 * 2 * 4 * 16 * 2},
}
CLOSED = {
    "loop": "closed", "clients": 3, "schedule_seed": 1, "documents_per_client": 60,
    "asks_per_document": 4, "prefix_tokens": {"32": 2, "64": 1}, "question_tokens": 16,
    "answer_tokens": 32,
}
OPEN = {
    "loop": "open", "rate_rps": 4.0, "max_live": 4, "schedule_seed": 2, "horizon_s": 20,
    "lead_in_s": 1, "prompt_tokens": {"16": 2, "32": 1}, "answer_tokens": {"16": 1, "32": 1},
}


@pytest.mark.parametrize("params", [CLOSED, OPEN], ids=["closed", "open"])
def test_toy_cell_runs_and_checks(params):
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("a rehearsal for the sandbox; the chip runs the real cells")
    import run

    with open(os.path.join(REPO, "benchmarks", "configs", "mistral-7b-v0.3.json")) as f:
        config = dict(TOY, program=json.load(f)["program"])
    plan = (traffic._closed_plan if params["loop"] == "closed" else traffic._open_plan)("toy", params)
    args = argparse.Namespace(workload="toy", seed=2**31 + 7, seconds=4.0, trace=0)
    line, res, trace = run.execute(args, {"name": "toy", "chips": 1}, config, plan, run.device_line(jax))
    assert trace is None and res["counters"]["window_compiles"] == 0, res["counters"]
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 8, line
    e2e = res["end_to_end"]
    assert e2e["ttft_p50_ms"] > 0 and e2e["tpot_mean_ms"] > 0 and e2e["tokens_per_s"] > 0
    hits = [r for r in res["rows"] if r["hit"]]
    if params["loop"] == "closed":
        # Which asks hit is fixed by the lists: three of four, to the request.
        assert abs(len(hits) / len(res["rows"]) - 0.75) < 0.15, (len(hits), len(res["rows"]))
        assert all(r["loaded_blocks"] == r["prompt_blocks"] - 1 for r in hits)
    else:
        assert not hits and all(r["late_ms"] >= 0 for r in res["rows"])
