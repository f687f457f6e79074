"""The metrics of the store's hop (PR 39 brought twenty: ISSUE 39's nineteen and
`idle_in_save_layer_pct`, the phase that held most idle time on the chip; PR 55 retired the four
that read a constant on the chip or a rate that was none, whose readers are kept inline in
`accepted.py`, since the reader kinds stay), read off a hand-made
recorder with the readers that are there: a hit of two layers over one
staging region (so layer 1 waits for the region and the install waits for
layer 1), a miss with its save of two layers, and a device that idles under
each new phase. Every expected value is worked out in the comments.

All times in us on the spans' clock; the profile's clock is the same one
(its single mark reads its own timestamp), in ns.
"""

import pytest

import accepted
import readers
import span_readers

HIT, MISS = 21, 22  # the two requests' traces
NEW_SPANS = {"fetch_layer", "install_upload", "install_staged_wait", "save_layer", "save_d2h_wait"}
NEW_STAMPS = {"alloc_done", "primed"}


def span(sid, name, parent, trace, start, end, stages=(), **attrs):
    return {
        "name": name, "trace_id": trace, "span_id": sid, "parent_id": parent, "start_us": start,
        "end_us": end, "duration_us": end - start, "status": "ok",
        "stages": [list(s) for s in stages], "attrs": attrs,
    }


SPANS = [
    # The hit: probe 2 ms, alloc 1, the wait for the store 20, the gate 5, the install 60,
    # the resume's gate 2 and its dispatch 8: 98 of a prefix_ready of 100 ms.
    span(1, "engine_request", 0, HIT, 1000, 200000, [
        ("enqueue", 1000), ("fetch_start", 3000), ("alloc_done", 4000), ("primed", 24000),
        ("install", 89000),
    ]),
    span(2, "pool_alloc", 1, HIT, 3000, 4000),
    # Layer 0 has the region from the start and reads for 20 ms; layer 1 waits 37 ms for
    # it (until layer 0's upload ended) and reads for 30.
    span(3, "fetch_layer", 1, HIT, 3000, 23000,
         [("queued", 3000), ("region_free", 3000), ("submit", 3100), ("landed", 23000)],
         layer=0, region=0, values=2, bytes=100_000_000),
    span(4, "fetch_layer", 1, HIT, 3000, 70000,
         [("queued", 3000), ("region_free", 40000), ("submit", 40100), ("landed", 70000)],
         layer=1, region=0, values=2, bytes=100_000_000),
    span(5, "gate_wait", 1, HIT, 24000, 29000, mode="expedite"),
    span(6, "install", 1, HIT, 29000, 89000, blocks=4),
    span(7, "install_upload", 6, HIT, 29500, 39500, [("started", 29700), ("h2d", 35000)],
         layer=0, bytes=100_000_000, fused=False, device_calls=[["its.install", 29700, 39400]]),
    span(8, "install_staged_wait", 6, HIT, 39600, 70100, layer=1),
    span(9, "install_upload", 6, HIT, 70200, 88200, [("started", 70300), ("h2d", 80000)],
         layer=1, bytes=100_000_000, fused=False, device_calls=[["its.install", 70300, 88100]]),
    span(10, "gate_wait", 1, HIT, 89000, 91000, mode="exclusive"),
    span(11, "compute", 1, HIT, 91000, 99000, kind="chunked_resume"),
    # The miss: alloc 0.5 ms, gate 3, prefill 44: 47.5 of 50 ms. Then its save: layer 1's
    # D2H is waited for 4 ms, layer 0's 2.
    span(20, "engine_request", 0, MISS, 200000, 310000, [("enqueue", 200000), ("alloc_done", 201500)]),
    span(21, "pool_alloc", 20, MISS, 201000, 201500),
    span(22, "gate_wait", 20, MISS, 201500, 204500, mode="exclusive"),
    span(23, "compute", 20, MISS, 204500, 248500, kind="prefill_full"),
    span(24, "save_io", 20, MISS, 250000, 300000, blocks=4),
    span(25, "save_layer", 24, MISS, 250100, 280000, layer=1, bytes=60_000_000),
    span(26, "save_d2h_wait", 25, MISS, 251000, 255000, device_calls=[["its.save_d2h", 251000, 255000]]),
    span(27, "save_layer", 24, MISS, 250200, 299000, layer=0, bytes=60_000_000),
    span(28, "save_d2h_wait", 27, MISS, 281000, 283000, device_calls=[["its.save_d2h", 281000, 283000]]),
]
ROWS = [
    {"hit": True, "trace_id": HIT, "prefix_ready_ms": 100.0},
    {"hit": False, "trace_id": MISS, "prefix_ready_ms": 50.0},
]
COUNTERS = {
    "hit_read_bytes": 200_000_000, "hit_read_busy_us": 40_000.0,  # the two reads, 20 + 30 ms, 10 of them together
    "install_upload_bytes": 200_000_000, "install_upload_us": 25_000.0,
    "save_d2h_bytes": 120_000_000, "save_d2h_wait_us": 6_000.0,
}


def op(start_us, end_us):
    return ["%fusion.1 = bf16[4,32]{1,0} fusion(%a)", start_us * 1000, (end_us - start_us) * 1000]


# The device is busy in seven short ops; the six gaps between them:
#   6-28 ms     fetch_layer 0 until it ends at 23 (17), then layer 1's, the only phase left (5)
#   30-34       install_upload 0 (4)
#   36-50       install_upload 0 to 39.5 (3.5), the install alone to 39.6 (0.1), the staged wait (10.4)
#   60-75       the staged wait to 70.1 (10.1), the install (0.1), install_upload 1 (4.8)
#   76-252      install_upload 1 to 88.2 (12.2), the install to 89 (0.8), the resume 91-99 (8), the
#               prefill 204.5-248.5 (44), save_io 250-250.1 (0.1), save_layer 1 to 251 (0.9),
#               its D2H wait (1); the rest under containers only: outside (109)
#   253-282     that D2H wait to 255 (2), save_layer 1 to 280 (25), layer 0's to 281 (1), its D2H wait (1)
TRACE = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            op(5000, 6000), op(28000, 30000), op(34000, 36000), op(50000, 60000), op(75000, 76000),
            op(252000, 253000), op(282000, 283000),
        ]},
        {"name": "XLA Modules", "events": []},
    ]},
    {"name": "/host:CPU", "lines": [{"name": "main/1", "events": [
        ["its.clock:1000000", 1000000, 0],
        ["its.install", 29_700_000, 9_700_000], ["its.install", 70_300_000, 17_800_000],
        ["its.save_d2h", 251_010_000, 3_990_000], ["its.save_d2h", 281_010_000, 1_990_000],
    ]}]},
]}
WINDOW_MS = 283.0 - 5.0

EXPECTED = {
    "hit_probe_p50_ms": 2.0,
    "hit_store_wait_p50_ms": 20.0,
    "hit_gate_wait_p50_ms": 5.0,
    "install_hold_p50_ms": 60.0,
    "install_upload_p50_ms": 28.0,  # 10 + 18
    "ready_compute_gate_wait_p50_ms": 2.5,  # the hit's 2 and the miss's 3
    "ready_compute_p50_ms": 26.0,  # 8 and 44
    "prefix_ready_accounted_pct": 96.5,  # 98 and 95
    "fetch_layer_read_p50_ms": 25.0,  # 20 and 30
    "store_read_gbps": 5.0,  # 200 MB in 40 ms
    "save_d2h_gbps": 20.0,  # 120 MB in 6 ms
    "save_d2h_wait_mean_ms": 3.0,  # 4 and 2
    "idle_in_install_upload_pct": 100 * 24.5 / WINDOW_MS,  # 4 + 3.5 + 4.8 + 12.2
    "idle_in_save_d2h_wait_pct": 100 * 4.0 / WINDOW_MS,  # 1 + 2 + 1
    "idle_in_save_layer_pct": 100 * 26.9 / WINDOW_MS,  # 0.9 + 25 + 1: puts in flight, no D2H wait
    "idle_in_fetch_layer_pct": 100 * 22.0 / WINDOW_MS,  # 17 + 5
}
# The four whose files went with PR 55 (their readers: ``accepted.RETIRED_READERS``): the value here.
RETIRED = {
    "install_staged_wait_p50_ms": 30.5,
    "fetch_region_wait_mean_ms": 18.5,  # 0 and 37
    "install_upload_gbps": 8.0,  # 200 MB in 25 ms
    "idle_in_install_staged_wait_pct": 100 * 20.5 / WINDOW_MS,  # 10.4 + 10.1
}
SPAN_KINDS = sorted(m for m in EXPECTED if readers.load_layer_metric(m)["reader"]["kind"] == "spans")
IDLE_KINDS = sorted(m for m in EXPECTED if m.startswith("idle_in_"))


def view(spans=SPANS, counters=COUNTERS):
    held = {
        "spans": spans, "recorded": len(spans), "dropped": 0, "window_us": [0, 400000],
        "profile": span_readers.reduce_profile(TRACE, spans),
    }
    return readers.Run(ROWS, counters, None, {}, spans=held)


def parent_spans():
    """What the parent's tree records of the same two requests: no span and
    no stamp that this PR adds."""
    return [
        dict(s, stages=[st for st in s["stages"] if st[0] not in NEW_STAMPS])
        for s in SPANS if s["name"] not in NEW_SPANS
    ]


def test_each_is_a_reader_kind_that_was_there():
    assert SPAN_KINDS and IDLE_KINDS and not set(RETIRED) & set(EXPECTED)
    kinds = {readers.load_layer_metric(m)["reader"]["kind"] for m in EXPECTED}
    assert kinds == {"spans", "counter", "trace_idle_in"} and kinds <= set(readers.KINDS)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_file_agrees_with_its_benchmark_json_entry(metric):
    spec, entry = accepted.agreed(metric)
    # Every cell that reports tokens_per_s, whichever have joined since PR 39.
    assert entry["workloads"] == accepted.cells_reporting("tokens_per_s") and entry["moves"] == "tokens_per_s"
    rate_or_share = metric.endswith("_gbps") or metric == "prefix_ready_accounted_pct"
    assert spec["better"] == ("higher" if rate_or_share else "lower")
    assert spec["source"] == {
        "spans": "program_span", "counter": "program_counter", "trace_idle_in": "device_trace",
    }[spec["reader"]["kind"]]
    assert (spec["layer"] == "Device") == (metric in IDLE_KINDS)
    for part in spec["reader"].get("parts", ()):
        assert accepted.entry(part)["workloads"] == entry["workloads"]
        assert "span" in readers.load_layer_metric(part)["reader"]  # `_parts` reads spans only


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_each_reads_its_value_off_the_recorder(metric):
    assert readers.read_layer_metric(metric, view()) == pytest.approx(EXPECTED[metric], rel=1e-9)


@pytest.mark.parametrize("metric", sorted(RETIRED))
def test_a_retired_metrics_reader_still_reads_its_value_off_the_recorder(metric):
    """The file went (PR 55); the reader kind and shape it used are still
    the program's to be read with, by whoever brings a metric that moves."""
    reader = accepted.RETIRED_READERS[metric]
    assert readers.KINDS[reader["kind"]](view(), reader) == pytest.approx(RETIRED[metric], rel=1e-9)


def test_idle_parts_still_add_up_and_the_sixth_region_agrees():
    p = view().spans["profile"]
    want = {
        "fetch_layer": 22.0, "install_upload": 24.5, "install_staged_wait": 20.5, "install": 1.0,
        "compute": 52.0, "save_io": 0.1, "save_layer": 26.9, "save_d2h_wait": 4.0, "outside": 109.0,
    }
    assert {k: round(v * 1e3, 6) for k, v in p["idle_s"].items()} == want
    busy_ms = 1 + 2 + 2 + 10 + 1 + 1 + 1
    assert sum(p["idle_s"].values()) == pytest.approx((WINDOW_MS - busy_ms) / 1e3)
    # its.install moved onto install_upload and its.save_d2h is new: both are found on
    # their spans and lie as close to their annotations as the fixture put them.
    assert p["agreement"]["its.install"] == {"n": 2, "start_p50_us": 0.0, "end_p50_us": 0.0, "end_p95_us": 0.0}
    assert p["agreement"]["its.save_d2h"] == {"n": 2, "start_p50_us": 10.0, "end_p50_us": 0.0, "end_p95_us": 0.0}


def test_a_run_without_a_recorder_or_without_counters_leaves_them_all_out():
    bare = readers.Run(ROWS, {}, None, {})
    assert [readers.read_layer_metric(m, bare) for m in sorted(EXPECTED)] == [None] * len(EXPECTED)
    empty = readers.Run(ROWS, {}, None, {}, spans={
        "spans": [], "recorded": 0, "dropped": 0, "window_us": [0, 400000], "profile": None,
    })
    assert [readers.read_layer_metric(m, empty) for m in sorted(EXPECTED)] == [None] * len(EXPECTED)


def test_the_parent_side_reads_what_it_has_and_raises_nowhere():
    """The driver lays these files over the parent's tree, whose recorder has
    the old spans and whose connector has none of the six counters."""
    got = {m: readers.read_layer_metric(m, view(parent_spans(), counters={})) for m in EXPECTED}
    # No span of the name: nothing to read, and `run.py` leaves the metric out.
    absent = ["fetch_layer_read_p50_ms", "save_d2h_wait_mean_ms", "store_read_gbps", "save_d2h_gbps"]
    assert [got[m] for m in absent] == [None] * len(absent)
    # The span is there, the stamps or the children are not: nothing waited, as far as it says.
    for m in ("hit_store_wait_p50_ms", "install_upload_p50_ms"):
        assert got[m] == 0.0
    # The five that read spans the parent records read what they read here.
    for m in ("hit_probe_p50_ms", "hit_gate_wait_p50_ms", "install_hold_p50_ms",
              "ready_compute_gate_wait_p50_ms", "ready_compute_p50_ms"):
        assert got[m] == EXPECTED[m]
    # The sum lacks the wait for the store: the hit's 78 of 100, the miss's 95 of 100.
    assert got["prefix_ready_accounted_pct"] == pytest.approx(86.5)
    # A profile without the phases: no idle time under them, and the install has it all.
    assert [got[m] for m in IDLE_KINDS] == [0.0] * len(IDLE_KINDS)
    idle = view(parent_spans()).spans["profile"]["idle_s"]
    assert not NEW_SPANS & set(idle) and idle["install"] == pytest.approx(0.046)  # 24.5 + 20.5 + 1
    assert idle["save_io"] == pytest.approx(0.031)  # 0.1 + the 26.9 and the 4 of its layers
