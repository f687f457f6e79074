"""``BENCHMARK.json`` as these tests ask it: by NAME, never by position, glob
or count, so that the next cell and the next metric file trip none of them.

A metric's cells are listed in ``BENCHMARK.json`` alone (PR 33); its file
holds every other key of the entry, and ``what`` and ``reader`` beside them.
"""

import json
import os

import readers

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
FILE_KEYS = {"name", "unit", "better", "source", "layer", "moves", "what", "reader"}
ENTRY_KEYS = {"name", "unit", "better", "source", "layer", "moves", "workloads"}


# The readers of the seven metric files PR 55 retired whose by-hand cases stay (the reader
# kinds and shapes stay in readers.py and span_readers.py; ``skew`` has no other user), as
# the files stood.
_SKEW = {"kind": "spans", "skew": ["emit_s", "bench_emit_s"], "aggregate": "p95", "scale": 1000.0}
RETIRED_READERS = {
    "alloc_wait_p95_ms.chat": {"kind": "spans", "span": "pool_alloc", "aggregate": "p95", "scale": 0.001},
    "emit_stamp_skew_p95_ms.reuse": _SKEW,
    "emit_stamp_skew_p95_ms.chat": _SKEW,
    "install_staged_wait_p50_ms": {
        "kind": "spans", "span": "install", "child": "install_staged_wait", "where": "hit",
        "aggregate": "p50", "scale": 0.001,
    },
    "fetch_region_wait_mean_ms": {
        "kind": "spans", "span": "fetch_layer", "from": "queued", "to": "region_free", "where": "hit",
        "aggregate": "mean", "scale": 0.001,
    },
    "install_upload_gbps": {
        "kind": "counter", "key": "install_upload_bytes", "per": "install_upload_us", "scale": 0.001,
    },
    "idle_in_install_staged_wait_pct": {"kind": "trace_idle_in", "pattern": "^install_staged_wait$"},
}


def entry(name):
    (found,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    return found


def cells_reporting(metric):
    """The cells that report that end-to-end metric: its list, or all."""
    (moved,) = [m for m in BENCH["end_to_end"] if m["name"] == metric]
    return moved.get("workloads", CELLS)


def agreed(name):
    """The metric's file and its entry, once they agree in every shared key
    and the entry's cells all report the end-to-end metric it moves."""
    spec, listed = readers.load_layer_metric(name), entry(name)
    assert set(spec) == FILE_KEYS and set(listed) == ENTRY_KEYS
    assert all(spec[k] == listed[k] for k in ENTRY_KEYS - {"workloads"})
    assert listed["workloads"] and set(listed["workloads"]) <= set(cells_reporting(listed["moves"]))
    return spec, listed
