"""The toy cells of ``test_rehearsal.py`` through ``run_spans.py``'s pieces,
recorder on: every span metric a cell of that loop kind lists has a sample,
and the run stays correct. No number from here is a device metric."""

import argparse
import json
import os

import pytest

import traffic
from test_rehearsal import CLOSED, OPEN, REPO, TOY


@pytest.mark.parametrize("params,suffix", [(CLOSED, ".reuse"), (OPEN, ".chat")], ids=["closed", "open"])
def test_toy_cell_with_spans_on(params, suffix, monkeypatch):
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("a rehearsal for the sandbox; the chip runs the real cells")
    import run
    import run_spans
    from infinistore_tpu import tracing

    with open(os.path.join(REPO, "benchmarks", "configs", "mistral-7b-v0.3.json")) as f:
        config = dict(TOY, program=json.load(f)["program"])
    plan = (traffic._closed_plan if params["loop"] == "closed" else traffic._open_plan)("toy", params)
    args = argparse.Namespace(workload="toy", seed=2**31 + 11, seconds=4.0, trace=0)
    monkeypatch.setattr(run, "CellRun", run_spans.SpanCellRun)
    rec = tracing.configure(enabled=True, capacity=run_spans.CAPACITY)
    try:
        line, res, trace = run.execute(args, {"name": "toy", "chips": 1}, config, plan, run.device_line(jax))
    finally:
        tracing.configure(enabled=False)
    assert line["correct"] and line["failed"] == 0 and res["counters"]["window_compiles"] == 0, line
    assert rec.dropped == 0 and rec.recorded > 10 * line["attempted"]
    cell = "mistral7b-prefix-reuse" if suffix == ".reuse" else "mistral7b-unshared-chat"
    specs = run_spans.span_metrics(cell)
    assert len(specs) == 13 and all(s["name"].endswith(suffix) for s in specs)
    values, view = run_spans.layer_values([s["name"] for s in specs], res, trace, {})
    assert "spans" not in res and view["dropped"] == 0 and "emit_s" not in res["rows"][0]
    for spec in specs:
        name, value = spec["name"], values[spec["name"]]
        if spec["reader"]["kind"] == "trace_idle_in":
            assert value is None, name  # no profile on the CPU
        else:
            assert value is not None and value >= 0.0, name
    # The program's emit stamps and the benchmark's patch tell the same time
    # (a loose bound: this is a shared CPU), and the five parts cover the
    # time from the prefix being ready to the first token.
    assert values["emit_stamp_skew_p95_ms" + suffix] < 20.0
    assert 50.0 < values["after_ready_accounted_pct" + suffix] <= 100.5, values
    assert values["first_wave_wait_p50_ms" + suffix] > 0 and values["save_io_p50_ms" + suffix] > 0
