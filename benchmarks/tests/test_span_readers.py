"""The span readers and the profile reduction on a hand-made fixture
(``data/spans_fixture.json``): two requests' span trees, one wave, a request
outside the window's rows, and a trace slice with two clock marks 4 us
apart in drift. Every expected value below is worked out by hand from the
numbers in that file."""

import glob
import json
import os

import pytest

import accepted
import readers
import span_readers

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "spans_fixture.json")) as f:
    FIX = json.load(f)
with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")) as f:
    BENCH = json.load(f)
SPAN_FILES = [
    p for p in sorted(glob.glob(os.path.join(os.path.dirname(HERE), "layer_metrics", "*.json")))
    if json.load(open(p))["reader"]["kind"] in span_readers.KINDS
]


def view(profile=True, rows=None):
    spans = {
        "spans": FIX["spans"], "recorded": len(FIX["spans"]), "dropped": 0,
        "window_us": FIX["window_us"],
        "profile": span_readers.reduce_profile(FIX["trace"], FIX["spans"]) if profile else None,
    }
    return readers.Run(FIX["rows"] if rows is None else rows, {}, None, {}, spans=spans)


# Three of the twenty-four went with PR 55 (a constant on the chip in every cell that listed
# them); their readers stand in ``accepted.py`` as the files stood.
RETIRED = ("alloc_wait_p95_ms.chat", "emit_stamp_skew_p95_ms.reuse", "emit_stamp_skew_p95_ms.chat")


def read(name, run):
    reader = accepted.RETIRED_READERS[name] if name in RETIRED else readers.load_layer_metric(name)["reader"]
    return span_readers.KINDS[reader["kind"]](run, reader)


# ms or %; the arithmetic is in the comments of the fixture's generator
# (PR 24) and repeated here: request A is trace 11, request B trace 12.
EXPECTED = {
    "alloc_wait_p95_ms": 0.2855,  # 10 us and 300 us: 10 + 0.95 * 290; trace 13's 90 ms is not in rows
    "save_gate_wait_p50_ms": 1.0,  # A waited 2000 us, B found the gate free: 0
    "save_snapshot_p50_ms": 4.0,  # A 7000 - 2000, B 3000
    "first_wave_wait_p50_ms": 10.0,
    "first_readback_p50_ms": 1.5,  # 2000 and 1000
    "after_ready_accounted_pct": 73.0,  # A 19 of 25 ms, B 14 of 20 ms: their save_io (4 and 2 ms) is no part
    "decode_wave_wait_mean_ms": 27.5,  # A's rounds 1 and 2; B's round 1 ends after the window
    "decode_readback_mean_ms": 2.0,
    "emit_stamp_skew_p95_ms": 0.1,  # 0.05, 0.1, 0.1 ms
    "idle_in_wave_dispatch_pct": 100 * 5.102 / 47.0,  # the wave between `gate` and `dispatched`
    "idle_in_readback_pct": 100 * 2.298 / 47.0,
    "idle_outside_spans_pct": 100 * 18.898 / 47.0,
}


@pytest.mark.parametrize("suffix", [".reuse", ".chat"])
@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_each_reader_by_hand(metric, suffix):
    assert read(metric + suffix, view()) == pytest.approx(EXPECTED[metric], rel=1e-6)


def test_profile_clock_idle_table_and_agreement():
    p = span_readers.reduce_profile(FIX["trace"], FIX["spans"])
    assert p["marks"] == 2 and p["drift_ns"] == 4000 and p["offset_ns"] == 7_000_000_000 + 2000
    assert p["window_s"] == pytest.approx(0.047)
    # Four gaps, 5 + 1.5 + 6 + 23 ms. B's prefill (20 ms long) covers the first two, but the
    # wave and A's read-back are shorter, so they name their pieces; it keeps the 0.1 ms
    # between them and 5.002 ms of the third gap. The wave is cut at its stamps: the second
    # gap opens 0.102 ms before `dispatched` and 0.1 ms more pass until `resolved`.
    want = {"wave_dispatch": 5.102, "wave_resolve": 0.1, "readback": 2.298, "compute": 5.102, "save_snapshot": 2.0,
            "save_io": 2.0, "outside": 18.898}
    assert {k: round(v * 1e3, 6) for k, v in p["idle_s"].items()} == want
    # The parts are the device's idle time: the window less the busy 11.5 ms.
    assert sum(p["idle_s"].values()) == pytest.approx(0.047 - 0.0115)
    # The wave's annotation sits 30 us after its span entry; the offset (mean of two marks
    # 4 us apart) puts the span 2 us late: 28 us. The read-backs were placed 2 us late too: 0.
    assert p["agreement"]["its.wave_dispatch"] == {
        "n": 1, "start_p50_us": 28.0, "end_p50_us": 28.0, "end_p95_us": 28.0,
    }
    assert p["agreement"]["its.readback"]["n"] == 2
    assert p["agreement"]["its.readback"]["end_p50_us"] == 0.0
    assert "its.compute" not in p["agreement"]  # recorded, but no annotation in this slice


def test_zero_where_nothing_waited_and_none_where_there_is_no_span():
    miss = [r for r in FIX["rows"] if not r["hit"]]
    assert read("save_gate_wait_p50_ms.reuse", view(rows=miss)) == 0.0  # a save, no wait
    assert read("decode_wave_wait_mean_ms.chat", view(rows=miss)) == 0.0  # rounds, none in the window
    reader = {"kind": "spans", "span": "no_such_span", "aggregate": "p50"}
    assert span_readers.KINDS["spans"](view(), reader) is None
    assert read("alloc_wait_p95_ms.chat", readers.Run(FIX["rows"], {}, None, {})) is None  # no recorder
    assert read("alloc_wait_p95_ms.chat", view(rows=[{"hit": False}])) is None  # a parent's rows: no trace_id
    assert read("idle_in_readback_pct.reuse", view(profile=False)) is None  # no profile
    assert read("emit_stamp_skew_p95_ms.reuse", view(rows=[{"hit": True, "trace_id": 11}])) is None
    no_marks = {"planes": [p for p in FIX["trace"]["planes"] if p["name"].startswith("/device")]}
    assert span_readers.reduce_profile(no_marks, FIX["spans"]) is None  # a program without the marks


@pytest.mark.parametrize("path", SPAN_FILES, ids=[os.path.basename(p) for p in SPAN_FILES])
def test_span_metric_file_is_listed_in_benchmark_json(path):
    """Every span metric's file is an entry of BENCHMARK.json (PR 26): the
    entry's keys and letters, a layer it names, an end-to-end metric its
    cells report."""
    spec = json.load(open(path))
    assert os.path.basename(path) == spec["name"] + ".json"
    # Its cells are listed in BENCHMARK.json alone (PR 33).
    assert set(spec) == {"name", "unit", "better", "source", "layer", "moves", "what", "reader"}
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    entry = listed[spec["name"]]
    assert all(spec[k] == entry[k] for k in set(entry) - {"workloads"})
    assert spec["source"] in ("program_span", "device_trace") and spec["better"] in ("lower", "higher")
    assert spec["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    (moved,) = [m for m in BENCH["end_to_end"] if m["name"] == spec["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])
    for part in spec["reader"].get("parts", ()):
        assert listed[part]["workloads"] == entry["workloads"]


def test_each_of_the_twelve_has_a_file_under_both_suffixes_or_is_named_as_retired():
    """By name: the span metrics that came after PR 24 are other tests'."""
    names = {os.path.basename(p)[: -len(".json")] for p in SPAN_FILES}
    wanted = {m + s for m in EXPECTED for s in (".reuse", ".chat")}
    assert wanted - set(RETIRED) <= names and not set(RETIRED) & names and set(RETIRED) <= wanted
