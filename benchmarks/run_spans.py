#!/usr/bin/env python3
"""TO BE DELETED, and not an entry point: ``BENCHMARK.json``'s command is
``run.py``, and the driver never runs this file. It stands in for five
additions to ``run.py`` and ``readers.py`` that only a ``benchmark`` PR may
make (PERF.md section 7 (m) lists them line by line). That PR folds them in,
lists the span metrics in ``BENCHMARK.json``, and deletes this file and
``run.Instruments``' ``step_chunk`` / ``alloc`` patches in the same change, so
that one thing times each layer. Until then this is the builder's tool for
the numbers in PERF.md section 5.

``run.py`` with the program's own spans on: the same cell, the same line,
plus the per-layer metrics that read the engine's spans.

    python3 benchmarks/run_spans.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The recorder (``infinistore_tpu.tracing``) is on for the whole run whatever
``--trace`` says (unlike what ``run.py`` is to do: off with ``--trace 0``),
so what this prints carries its cost, and ``--trace 0`` here against
``run.py --trace 0`` is that cost; ``--trace 1`` adds the profiler as ``run.py``
does, puts the two clock marks around it and lays the spans over the device's
timeline. The metrics are the files under ``layer_metrics/`` whose reader is
one of ``span_readers.KINDS`` and whose ``workloads`` name the cell; they are
not in ``BENCHMARK.json``, because ``run.py`` and ``readers.py`` cannot read
them as they stand (PERF.md section 7 (m) says which lines each needs). This
file adds nothing to the measurement itself: it subclasses ``run.CellRun`` at
the four places those lines would go, and prints the end-to-end metrics and
every per-layer metric it can read in one line, whatever ``--trace`` says.
"""

import argparse
import functools
import glob
import json
import os
import sys

import run  # noqa: E402 - sets sys.path for the rest

import readers  # noqa: E402
import span_readers  # noqa: E402
import trace_reduce  # noqa: E402
from infinistore_tpu import tracing  # noqa: E402

# Enough for a run: ~15 spans a request and its store ops, two a wave.
CAPACITY = 1 << 18


class SpanCellRun(run.CellRun):
    def trace_start(self):
        super().trace_start()
        tracing.profile_clock_mark()

    def trace_stop(self):
        tracing.profile_clock_mark()
        super().trace_stop()

    def request_row(self, rec):
        row = super().request_row(rec)
        if rec.stats is not None:
            row.update(
                trace_id=rec.stats.trace_id, emit_s=list(rec.stats.token_emit_s),
                bench_emit_s=rec.emits(),
            )
        return row

    def results(self, setup_s, peak_bytes):
        """The window's results, and under ``spans`` what the recorder
        holds and where the profile puts it."""
        res = super().results(setup_s, peak_bytes)
        rec = tracing.recorder()
        spans = rec.snapshot()
        profile = None
        if self.trace_t0 is not None:
            raw = trace_reduce.load(trace_reduce.find_xplane(self.trace_dir))
            profile = span_readers.reduce_profile(raw, spans)
        res["spans"] = {
            "spans": spans, "recorded": rec.recorded, "dropped": rec.dropped,
            "window_us": [self.t_open * 1e6, self.t_close * 1e6], "profile": profile,
        }
        return res


def span_metrics(workload: str):
    """The layer-metric files only this runner can read, for this cell."""
    out = []
    for path in sorted(glob.glob(os.path.join(run.HERE, "layer_metrics", "*.json"))):
        spec = run.load_json(path)
        if spec["reader"]["kind"] in span_readers.KINDS and workload in spec["workloads"]:
            out.append(spec)
    return out


def layer_values(names, res, trace, peaks):
    """Every named per-layer metric, the accepted kinds and the span kinds,
    from one view of the run; takes ``res["spans"]`` out of ``res``."""
    readers.KINDS.update(span_readers.KINDS)
    seen = span_readers.SpanRun(
        res["rows"], res["counters"], trace, peaks, spans=res.pop("spans")
    )
    values = {name: readers.read_layer_metric(name, seen) for name in names}
    for row in res["rows"]:  # the stamp lists have served; a detail file has no use for them
        row.pop("emit_s", None), row.pop("bench_emit_s", None)
    return values, seen.spans


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    bench = run.load_json(os.path.join(run.REPO, "BENCHMARK.json"))
    cell, config = run.cell_of(bench, args.workload)
    import jax

    device = run.device_line(jax)
    if device["platform"] != "tpu" or device["count"] < cell["chips"]:
        run.fail(f"JAX found platform {device['platform']!r}; nothing was measured.")
    peaks = run.load_json(os.path.join(run.HERE, "peaks.json"))[device["kind"]]

    from infinistore_tpu import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run.build_native_if_missing()
    plan = run.traffic.build_plan(cell["traffic"])

    tracing.configure(enabled=True, capacity=CAPACITY)
    # One read of the .xplane.pb serves both reductions.
    trace_reduce.load = functools.lru_cache(maxsize=1)(trace_reduce.load)
    run.CellRun = SpanCellRun
    line, res, trace = run.execute(args, cell, config, plan, device)
    listed = run.metrics_for(bench, "per_layer", cell["name"]) + span_metrics(cell["name"])
    layer, view = layer_values([m["name"] for m in listed], res, trace, peaks)
    if view["dropped"]:
        line["correct"] = False
        print(f"not correct: the recorder dropped {view['dropped']} spans", file=sys.stderr)
    for m in run.metrics_for(bench, "end_to_end", cell["name"]):
        if m["name"] in res["end_to_end"]:
            line["metrics"][m["name"]] = {"value": res["end_to_end"][m["name"]], "unit": m["unit"]}
    for m in listed:
        if layer[m["name"]] is not None:
            line["metrics"][m["name"]] = {"value": layer[m["name"]], "unit": m["unit"]}
    line["spans"] = {"recorded": view["recorded"], "dropped": view["dropped"]}
    if view["profile"]:
        line["spans"].update(view["profile"])
        line["device"].update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["breakdown"] = {"idle_gaps": trace_reduce.top(trace["idle_gaps"])}
    run.detail(args, dict(cell, name="spans." + cell["name"]), line, res, layer)
    # Beside the detail file: every span the recorder held, and the idle
    # table by phase (install, save_snapshot, compute and the rest).
    view.pop("index", None)
    with open(os.path.join(
        run.REPO, ".bench_out", f"recorder.{cell['name']}.seed{args.seed}.trace{args.trace}.json"
    ), "w") as f:
        json.dump(view, f)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
