"""The request's tail (PR 40), read off a hand-made recorder with the
`spans` reader that is there: two requests' tails on `engine_request`
(`generated` -> `acknowledged`). A parent's recorder (no stamp, no
`acknowledged` attr) gives None.

All times in us on the spans' clock.
"""

import pytest

import accepted
import readers

A, B, FAILED = 31, 32, 33


def span(sid, name, trace, start, end, stages=(), **attrs):
    return {
        "name": name, "trace_id": trace, "span_id": sid, "parent_id": 0, "start_us": start,
        "end_us": end, "duration_us": end - start, "status": "ok",
        "stages": [list(s) for s in stages], "attrs": attrs,
    }


SPANS = [
    # A hit: last token at 900 ms, acknowledged 30 ms later.
    span(1, "engine_request", A, 1000, 931000, [
        ("enqueue", 1000), ("alloc_done", 4000), ("generated", 900000), ("acknowledged", 930000),
    ], acknowledged=True),
    # A miss whose prompt write outlasted its generation: 90 ms of tail.
    span(2, "engine_request", B, 2000, 1291000, [
        ("enqueue", 2000), ("alloc_done", 5000), ("generated", 1200000), ("acknowledged", 1290000),
    ], acknowledged=True),
    # A request whose answer's save failed: generated, never acknowledged; no sample.
    span(3, "engine_request", FAILED, 3000, 700000, [("enqueue", 3000), ("generated", 650000)]),
]
ROWS = [{"hit": True, "trace_id": A}, {"hit": False, "trace_id": B}, {"hit": True, "trace_id": FAILED}]
METRIC = "ack_tail_mean_ms.reuse"


def view(spans=SPANS, rows=ROWS):
    held = {
        "spans": spans, "recorded": len(spans), "dropped": 0, "window_us": [0, 2000000],
        "profile": None,
    }
    return readers.Run(rows, {}, None, {}, spans=held)


def parent_spans():
    """The same requests as the parent's tree records them."""
    return [
        dict(s, stages=[st for st in s["stages"] if st[0] not in ("generated", "acknowledged")],
             attrs={})
        for s in SPANS
    ]


def test_file_agrees_with_its_benchmark_json_entry():
    spec, entry = accepted.agreed(METRIC)
    assert entry["workloads"] == accepted.cells_reporting("tokens_per_s") and entry["moves"] == "tokens_per_s"
    assert spec["layer"] == "Traffic / scheduler"
    assert spec["reader"]["kind"] == "spans" and spec["source"] == "program_span"


def test_it_reads_the_mean_of_the_tails_that_reached_their_acknowledgement():
    assert readers.read_layer_metric(METRIC, view()) == pytest.approx(60.0, rel=1e-12)  # 30 and 90


def test_it_needs_no_counter():
    assert readers.counter_keys({METRIC: None}) == set()


def test_a_parents_recorder_gives_none():
    """The driver lays this file over the parent's tree: its
    `engine_request` has neither stamp nor the attr. The metric is left out
    of the line; nothing raises."""
    assert readers.read_layer_metric(METRIC, view(parent_spans())) is None
    assert readers.read_layer_metric(METRIC, readers.Run(ROWS, {}, None, {})) is None


def test_a_window_in_which_every_answers_save_failed_has_no_sample():
    assert readers.read_layer_metric(METRIC, view([SPANS[2]])) is None
