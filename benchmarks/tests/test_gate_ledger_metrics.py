"""PR 52's nineteen metric files over the device gate's ledger
(``infinistore_tpu/engine.py`` ``DeviceGate``) and the idle phases that had no
file: each loads, names a reader kind that exists and agrees with its
``BENCHMARK.json`` entry (found by name); the counters they name are keys a
harness's ``metrics()`` really returns; a wave's parts make its whole; a tree
without the ledger (the parent, which the driver lays these files over) leaves
the counter metrics out and reads 0 under a phase it does not record."""

import json
import os

import jax.numpy as jnp
import pytest

import readers
import run
import span_readers
from infinistore_tpu.engine import GATE_HOLDERS, ContinuousBatchingHarness
from infinistore_tpu.models import LlamaConfig

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
REUSE = [
    "mistral7b-prefix-reuse", "deepseek7b-prefix-reuse", "trinity-mini-long-prefix-reuse",
    "kimi-linear-long-prefix-reuse", "falcon-h1-long-prefix-reuse",
    "granite-h-small-long-prefix-reuse", "mellum2-completion-prefix-reuse",
]
CHAT = ["mistral7b-unshared-chat"]
WAVE_PARTS = ("prefill", "resume", "install", "snapshot", "free")
# name -> (counter key, per) or the idle phase's pattern
COUNTERS = {
    "wave_gate_wait_mean_ms.reuse": ("gate_wait_us_wave", "waves"),
    "wave_gate_wait_mean_ms.chat": ("gate_wait_us_wave", "waves"),
    **{
        f"wave_gate_behind_{kind}_mean_ms.reuse": (f"gate_wait_us_wave_behind_{kind}", "waves")
        for kind in WAVE_PARTS
    },
    **{
        f"wave_gate_behind_{kind}_mean_ms.chat": (f"gate_wait_us_wave_behind_{kind}", "waves")
        for kind in ("prefill", "snapshot", "free")
    },
    "gate_hold_prefill_mean_ms.reuse": ("gate_held_us_prefill", "gate_holds_prefill"),
    "gate_hold_prefill_mean_ms.chat": ("gate_held_us_prefill", "gate_holds_prefill"),
    "gate_hold_resume_mean_ms.reuse": ("gate_held_us_resume", "gate_holds_resume"),
    "snapshot_gate_behind_wave_mean_ms.reuse": (
        "gate_wait_us_snapshot_behind_wave", "gate_waits_snapshot",
    ),
}
IDLE = {
    "idle_in_compute_pct.reuse": "^compute$", "idle_in_compute_pct.chat": "^compute$",
    "idle_in_save_snapshot_pct.reuse": "^save_snapshot$",
    "idle_in_save_snapshot_pct.chat": "^save_snapshot$",
    "idle_no_request_live_pct.chat": "^no_request_live$",
}
NEW = {**COUNTERS, **IDLE}


@pytest.mark.parametrize("name", sorted(NEW))
def test_file_loads_and_agrees_with_its_entry(name):
    spec = readers.load_layer_metric(name)
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    chat = name.endswith(".chat")
    assert entry["workloads"] == (CHAT if chat else REUSE)
    assert entry["moves"] == ("tpot_mean_ms" if chat else "tokens_per_s")
    assert entry["better"] == "lower" and spec["reader"]["kind"] in readers.KINDS
    assert all(spec[k] == entry[k] for k in set(entry) - {"workloads"})
    assert len(spec["what"]) > 80 and "\n" not in spec["what"]
    if name in COUNTERS:
        key, per = COUNTERS[name]
        assert spec["reader"] == {"kind": "counter", "key": key, "per": per, "scale": 0.001}
        assert (entry["unit"], entry["source"], entry["layer"]) == (
            "ms", "program_counter", "Traffic / scheduler",
        )
        assert "engine.py" in spec["what"] and "DeviceGate" in spec["what"]
    else:
        assert spec["reader"] == {"kind": "trace_idle_in", "pattern": IDLE[name]}
        assert (entry["unit"], entry["source"], entry["layer"]) == ("%", "device_trace", "Device")


def test_the_files_stay_within_the_contract_and_add_at_the_end():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(names) <= 128 and len(set(names)) == len(names)
    first = min(names.index(n) for n in NEW)
    assert set(names[first:]) == set(NEW)  # appended in one run, nothing between them
    # The one file of ISSUE 52's table that the count of 128 leaves no room for.
    assert "idle_no_request_live_pct.reuse" not in names


def toy_harness():
    cfg = LlamaConfig(
        vocab=64, dim=32, n_layers=1, n_heads=2, n_kv_heads=1, ffn_dim=64,
        block_tokens=8, dtype=jnp.float32,
    )
    return ContinuousBatchingHarness(object(), None, cfg, 8, 2)


def test_the_counters_the_files_name_are_keys_of_metrics():
    keys = readers.counter_keys(COUNTERS) - run.OWN_COUNTERS
    assert keys == {
        "gate_wait_us_wave", *(f"gate_wait_us_wave_behind_{k}" for k in WAVE_PARTS),
        "gate_held_us_prefill", "gate_holds_prefill", "gate_held_us_resume", "gate_holds_resume",
        "gate_wait_us_snapshot_behind_wave", "gate_waits_snapshot",
    }
    assert readers.counter_keys(COUNTERS) - keys == {"waves"}
    metrics = toy_harness().metrics()
    assert keys <= set(metrics)
    assert all(metrics[k] == 0 and not isinstance(metrics[k], bool) for k in keys)
    # What has no file is there all the same, for whoever scrapes the program.
    for waiter in GATE_HOLDERS:
        assert {f"gate_wait_us_{waiter}_behind_{k}" for k in (*GATE_HOLDERS, "free")} <= set(metrics)


LEDGER = {  # window deltas, us: 200 waves waited 1.5 s in all
    "waves": 200, "gate_wait_us_wave": 1_500_000,
    "gate_wait_us_wave_behind_prefill": 900_000, "gate_wait_us_wave_behind_resume": 100_000,
    "gate_wait_us_wave_behind_install": 250_000, "gate_wait_us_wave_behind_snapshot": 50_000,
    "gate_wait_us_wave_behind_free": 200_000,
    "gate_held_us_prefill": 4_000_000, "gate_holds_prefill": 8,
    "gate_held_us_resume": 60_000, "gate_holds_resume": 24,
    "gate_wait_us_snapshot_behind_wave": 16_000, "gate_waits_snapshot": 32,
}


def view(counters, idle_s=None):
    spans = None if idle_s is None else {
        "spans": [], "recorded": 0, "dropped": 0, "window_us": [0, 8_000_000],
        "profile": {"window_s": 8.0, "idle_s": idle_s},
    }
    return readers.Run([], counters, None, {}, spans=spans)


def test_a_waves_parts_make_its_whole_and_a_hold_is_a_mean():
    got = {name: readers.read_layer_metric(name, view(LEDGER)) for name in COUNTERS}
    assert got["wave_gate_wait_mean_ms.reuse"] == got["wave_gate_wait_mean_ms.chat"] == 7.5
    parts = [got[f"wave_gate_behind_{k}_mean_ms.reuse"] for k in WAVE_PARTS]
    assert parts == [4.5, 0.5, 1.25, 0.25, 1.0] and sum(parts) == 7.5
    assert got["gate_hold_prefill_mean_ms.reuse"] == got["gate_hold_prefill_mean_ms.chat"] == 500.0
    assert got["gate_hold_resume_mean_ms.reuse"] == 2.5
    assert got["snapshot_gate_behind_wave_mean_ms.reuse"] == 0.5


def test_a_tree_without_the_ledger_leaves_them_out_and_raises_nowhere():
    parent = view({"waves": 200}, idle_s={"outside": 2.0, "compute": 1.2, "save_snapshot": 0.4})
    assert [readers.read_layer_metric(n, parent) for n in sorted(COUNTERS)] == [None] * len(COUNTERS)
    # A window in which no miss was admitted: the wave parts read, a hold has no mean.
    quiet = dict(LEDGER, gate_holds_prefill=0, gate_held_us_prefill=0)
    assert readers.read_layer_metric("gate_hold_prefill_mean_ms.reuse", view(quiet)) is None
    # The idle files read the phases a parent records, and 0 under the one it does not.
    assert readers.read_layer_metric("idle_in_compute_pct.chat", parent) == pytest.approx(15.0)
    assert readers.read_layer_metric("idle_in_save_snapshot_pct.reuse", parent) == pytest.approx(5.0)
    assert readers.read_layer_metric("idle_no_request_live_pct.chat", parent) == 0.0
    assert readers.read_layer_metric("idle_outside_spans_pct.chat", parent) == pytest.approx(25.0)
    assert [readers.read_layer_metric(n, view({})) for n in sorted(IDLE)] == [None] * len(IDLE)


def test_no_request_live_is_a_phase_and_takes_its_gaps_from_outside():
    """A stretch with nobody live lies between two requests' spans: with the
    span recorded the gap goes to it, without it to ``outside``; ``gate_wait``
    stays a container and names none."""

    def span(sid, name, start, end, parent=0, trace=None):
        return {
            "name": name, "trace_id": trace or sid, "span_id": sid, "parent_id": parent,
            "start_us": start, "end_us": end, "duration_us": end - start, "status": "ok",
            "stages": [], "attrs": {},
        }

    spans = [
        span(1, "engine_request", 0, 1000), span(2, "compute", 100, 900, parent=1, trace=1),
        span(3, "gate_wait", 50, 100, parent=1, trace=1),
        span(4, "no_request_live", 1000, 3000),
        span(5, "engine_request", 3000, 4000),
    ]
    gaps = [(60_000, 90_000), (1_200_000, 2_900_000), (3_100_000, 3_200_000)]  # ns
    with_span = span_readers._attribute(gaps, span_readers.phases(spans, 0.0))
    assert with_span == pytest.approx({"outside": 0.00013, "no_request_live": 0.0017})
    without = span_readers._attribute(
        gaps, span_readers.phases([s for s in spans if s["name"] != "no_request_live"], 0.0)
    )
    assert without == pytest.approx({"outside": 0.00183})
