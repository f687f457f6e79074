"""The whole run at a toy size on the CPU: control flow, counts and checks,
with the program's recorder off (an end-to-end run) and on (what a traced
run reads besides the profile). No number from here is a device metric; the
line is never printed."""

import argparse
import json
import os

import pytest

import readers
import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOY = {
    "name": "toy", "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "torch_dtype": "bfloat16",
    "serving": {
        "block_tokens": 16, "cache_blocks": 64, "kv_bytes_per_token": 2 * 2 * 4 * 16 * 2,
        "store_block_kib": 2,  # 16 tokens x 4 heads x 16 x 2 B
    },
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
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def toy_run(params, seed, program_counters=()):
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("a rehearsal for the sandbox; the chip runs the real cells")
    import run

    with open(os.path.join(REPO, "benchmarks", "configs", "mistral-7b-v0.3.json")) as f:
        config = dict(TOY, program=json.load(f)["program"])
    plan = (traffic._closed_plan if params["loop"] == "closed" else traffic._open_plan)("toy", params)
    args = argparse.Namespace(workload="toy", seed=seed, seconds=4.0, trace=0)
    return run.execute(
        args, {"name": "toy", "chips": 1}, config, plan, run.device_line(jax), program_counters
    )


@pytest.mark.parametrize("params", [CLOSED, OPEN], ids=["closed", "open"])
def test_toy_cell_runs_and_checks(params):
    # Two of the program's own counters by name: one of harness.metrics(),
    # one of the connector's get_stats().
    line, res, trace = toy_run(params, 2**31 + 7, ("generated_tokens", "kvmap_len"))
    assert trace is None and res["spans"] is None and res["counters"]["window_compiles"] == 0, res["counters"]
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 8, line
    assert line["server"] == {"block_kib": 16, "pool_gib": 2}
    e2e = res["end_to_end"]
    assert e2e["ttft_p50_ms"] > 0 and e2e["tpot_mean_ms"] > 0 and e2e["tokens_per_s"] > 0
    assert res["counters"]["generated_tokens"] > 0 and res["counters"]["kvmap_len"] > 0
    hits = [r for r in res["rows"] if r["hit"]]
    if params["loop"] == "closed":
        # Which asks hit is fixed by the lists: three of four, to the request.
        assert abs(len(hits) / len(res["rows"]) - 0.75) < 0.15, (len(hits), len(res["rows"]))
        assert all(r["loaded_blocks"] == r["prompt_blocks"] - 1 for r in hits)
    else:
        assert not hits and all(r["late_ms"] >= 0 for r in res["rows"])


@pytest.mark.parametrize("params,suffix", [(CLOSED, ".reuse"), (OPEN, ".chat")], ids=["closed", "open"])
def test_toy_cell_with_spans_on(params, suffix):
    """Recorder on, as ``--trace 1`` turns it on: every span metric that
    BENCHMARK.json lists for a cell of that loop kind has a sample, and the
    run stays correct."""
    import run
    from infinistore_tpu import tracing

    rec = tracing.configure(enabled=True, capacity=run.SPAN_CAPACITY)
    try:
        line, res, trace = toy_run(params, 2**31 + 11)
    finally:
        tracing.configure(enabled=False)
    assert line["correct"] and line["failed"] == 0 and res["counters"]["window_compiles"] == 0, line
    assert rec.dropped == 0 and rec.recorded > 10 * line["attempted"]
    assert res["spans"]["dropped"] == 0 and res["spans"]["profile"] is None
    cell = "mistral7b-prefix-reuse" if suffix == ".reuse" else "mistral7b-unshared-chat"
    specs = [
        spec for spec in (readers.load_layer_metric(m["name"]) for m in run.metrics_for(BENCH, "per_layer", cell))
        if spec["reader"]["kind"] in ("spans", "trace_idle_in")
    ]
    assert len(specs) == 12 and all(s["name"].endswith(suffix) for s in specs)
    view = readers.Run(res["rows"], res["counters"], trace, {}, spans=res["spans"])
    values = {s["name"]: readers.read_layer_metric(s["name"], view) for s in specs}
    for spec in specs:
        name, value = spec["name"], values[spec["name"]]
        if spec["reader"]["kind"] == "trace_idle_in":
            assert value is None, name  # no profile on the CPU
        else:
            assert value is not None and value >= 0.0, name
    # The program's emit stamps and the benchmark's patch tell the same time
    # (a loose bound: this is a shared CPU), and the four parts cover the
    # time from the prefix being ready to the first token.
    assert values["emit_stamp_skew_p95_ms" + suffix] < 20.0
    assert 50.0 < values["after_ready_accounted_pct" + suffix] <= 100.5, values
    assert values["first_wave_wait_p50_ms" + suffix] > 0
