"""tools/bench_check.py: the data-plane regression gate.

The gate exists so the BENCH_r05 striping inversion (striped_4 < striped_1)
can never silently return; these tests pin its verdicts against an inline
copy of that receipt and synthetic ones, including the driver's truncated
``tail`` format (the receipt's head is routinely clipped mid-JSON).
"""

import importlib.util
import json
import os
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench_check():
    path = os.path.join(_REPO, "tools", "bench_check.py")
    spec = importlib.util.spec_from_file_location("bench_check", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench_check = _load_bench_check()


# The round-5 driver receipt the gate was founded on, as the driver wrote
# it: a wrapper whose ``tail`` is the bench line with its head clipped
# mid-object and ``parsed`` null. Inline, cut to the host data-plane keys
# (the record file itself is gone; its device-side keys measured a
# transport that is no longer installed).
_R05_CLIPPED_TAIL = (
    'extra": {"memcpy_ceiling_gbps": 10.469, "p50_fetch_4k_us": 30.2, '
    '"p99_fetch_4k_us": 107.5, "p50_fetch_64k_us": 39.8, '
    '"p99_fetch_64k_us": 98.2, "sync_p50_fetch_4k_us": 23.8, '
    '"sync_p99_fetch_4k_us": 98.9, "sync_p50_fetch_64k_us": 19.7, '
    '"sync_p99_fetch_64k_us": 61.0, "asyncio_efd_floor_us": 14.7, '
    '"lookup_256chain_p50_us": 26.1, "striped_1_gbps": 5.031, '
    '"striped_4_gbps": 3.138, "shaped_cap_mbps": 50, '
    '"shaped_striped_1_mbps": 51.5, "shaped_striped_4_mbps": 215.6, '
    '"shaped_speedup_4_over_1": 4.19, "spill_cold_read_gbps": 1.693, '
    '"spill_hot_read_gbps": 3.4, "spill_promotions": 192, '
    '"uncontended_hot_p99_us": 45.3, "contended_ram_hot_p50_us": 50.5, '
    '"contended_ram_hot_p99_us": 640.1, "contended_spill_hot_p50_us": 38.0, '
    '"contended_spill_hot_p99_us": 658.8, "spill_vs_ram_contended_p99": 1.03}}\n'
)


def test_fails_on_the_r05_inversion_receipt(tmp_path):
    """The founding requirement: the round-5 receipt (striped_4 3.14 <
    striped_1 5.03) must fail the gate."""
    p = tmp_path / "r05.json"
    p.write_text(json.dumps({
        "n": 5,
        "cmd": "if [ -f bench.py ]; then python bench.py; else exit 0; fi",
        "rc": 0,
        "tail": _R05_CLIPPED_TAIL,
        "parsed": None,
    }))
    assert bench_check.main([str(p)]) == 1


def test_passes_on_a_healthy_receipt(tmp_path):
    doc = {
        "metric": "kv_batched_write_read_throughput",
        "value": 5.5,
        "extra": {
            "striped_1_gbps": 5.4,
            "striped_4_gbps": 5.5,
            "shaped_striped_1_mbps": 51.0,
            "shaped_striped_4_mbps": 205.0,
            "p50_fetch_4k_us": 28.0,
            "sync_p50_fetch_4k_us": 23.0,
        },
    }
    p = tmp_path / "good.json"
    p.write_text(json.dumps(doc))
    assert bench_check.main([str(p)]) == 0


def test_fails_on_inverted_striping(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"striped_1_gbps": 5.0, "striped_4_gbps": 3.0}))
    assert bench_check.main([str(p)]) == 1


def test_fails_on_pathological_async_bridge(tmp_path):
    """The async gate is calibrated for pathological bridges (a per-op
    call_soon_threadsafe hop lands 3-5x over sync), not host weather
    (honest history swings 1.27-2.64x). The measured asyncio eventfd wake
    floor is subtracted first — that cost is asyncio's, not the bridge's,
    and billing it to the bridge made the gate trip whenever the SYNC path
    got faster."""
    p = tmp_path / "slow_bridge.json"
    p.write_text(json.dumps({
        "p50_fetch_4k_us": 100.0,
        "sync_p50_fetch_4k_us": 20.0,
        "asyncio_efd_floor_us": 18.0,
    }))
    assert bench_check.main([str(p)]) == 1
    p.write_text(json.dumps({
        "p50_fetch_4k_us": 47.0,
        "sync_p50_fetch_4k_us": 14.0,
        "asyncio_efd_floor_us": 18.0,
    }))
    assert bench_check.main([str(p)]) == 0


def _ring_receipt(**over):
    """A healthy descriptor-ring receipt slice; override keys to break it."""
    doc = {
        "ring_ceiling_fraction": 0.93,
        "ring_vs_socket_speedup": 1.01,
        "ring_posted": 84,
        "ring_completions": 84,
        "ring_full_fallbacks": 0,
        "ring_meta_fallbacks": 0,
        "ring_doorbell_ratio": 10.5,
        "trace_frac_first_slice_to_last_slice": 0.764,
    }
    doc.update(over)
    return doc


def test_ring_gates_pass_on_healthy_receipt(tmp_path):
    p = tmp_path / "ring_ok.json"
    p.write_text(json.dumps(_ring_receipt()))
    assert bench_check.main([str(p)]) == 0


def test_ring_ceiling_fraction_gate(tmp_path):
    """The ROADMAP-2 target: the ring-backed batched leg must reach 0.75
    of the paired memcpy ceiling — 0.54 is the pre-ring r05 state."""
    p = tmp_path / "ring_slow.json"
    p.write_text(json.dumps(_ring_receipt(ring_ceiling_fraction=0.54)))
    assert bench_check.main([str(p)]) == 1


def test_ring_never_loses_to_socket(tmp_path):
    p = tmp_path / "ring_loses.json"
    p.write_text(json.dumps(_ring_receipt(ring_vs_socket_speedup=0.80)))
    assert bench_check.main([str(p)]) == 1


def test_ring_mechanism_gate(tmp_path):
    """Silent fallbacks would A/B the socket against itself; a 1.0
    doorbell ratio means every post paid the syscall the ring removes; a
    completion deficit means ring ops vanished."""
    for over in (
        {"ring_full_fallbacks": 3},
        {"ring_meta_fallbacks": 1},
        {"ring_doorbell_ratio": 1.0},
        {"ring_completions": 80},
        {"ring_posted": 0, "ring_completions": 0},
    ):
        p = tmp_path / "ring_mech.json"
        p.write_text(json.dumps(_ring_receipt(**over)))
        assert bench_check.main([str(p)]) == 1, over


def test_ring_stage_shift_gate(tmp_path):
    """first_slice->last_slice must stay visibly below the PR 7 receipt's
    ~0.80 — and the check binds only on ring-era receipts (a PR 7 receipt
    without ring keys skips instead of failing retroactively)."""
    p = tmp_path / "ring_frac.json"
    p.write_text(json.dumps(
        _ring_receipt(trace_frac_first_slice_to_last_slice=0.81)
    ))
    assert bench_check.main([str(p)]) == 1
    # Pre-ring receipt: same fraction, no ring keys -> not applicable.
    p.write_text(json.dumps({
        "trace_frac_first_slice_to_last_slice": 0.81,
        "striped_1_gbps": 5.0, "striped_4_gbps": 5.1,
    }))
    assert bench_check.main([str(p)]) == 0


def test_parses_truncated_driver_tail(tmp_path):
    """Driver receipts wrap the bench line and clip its head; metrics must
    still be recovered by key-value scan from the tail string."""
    # The way the driver writes it: a JSON wrapper whose "tail" value is a
    # string holding the CLIPPED bench line (starts mid-object; its quotes
    # are escaped inside the wrapper file, so only the tail-aware path can
    # recover the metrics).
    tail = (
        'extra": {"striped_1_gbps": 5.031, "striped_4_gbps": 3.138, '
        '"shaped_striped_1_mbps": 51.5}}'
    )
    doc = {"n": 5, "cmd": "python bench.py", "rc": 0, "tail": tail,
           "parsed": None}
    p = tmp_path / "driver.json"
    p.write_text(json.dumps(doc))
    m = bench_check.extract_metrics(p.read_text())
    assert m["striped_1_gbps"] == 5.031 and m["striped_4_gbps"] == 3.138
    assert bench_check.main([str(p)]) == 1  # the inversion is in the tail


def test_empty_receipt_is_not_a_pass(tmp_path):
    p = tmp_path / "empty.json"
    p.write_text(json.dumps({"rc": 0, "tail": "no metrics here"}))
    assert bench_check.main([str(p)]) == 2


def test_tiering_gates_pass_on_healthy_receipt(tmp_path):
    doc = {
        "tiering_hot_p99_ratio": 1.01,
        "tiering_cold_vs_spill_floor": 2.1,
        "tiering_demotions": 120,
        "tiering_promotions": 4,
        "tiering_admit_rejects": 32,
        "tiering_wrong_reads": 0,
        "tiering_misses": 0,
    }
    p = tmp_path / "tier.json"
    p.write_text(json.dumps(doc))
    assert bench_check.main([str(p)]) == 0


def test_tiering_hot_isolation_gate(tmp_path):
    # A tier plane stalling the hot path (policy hooks / fall-through
    # probing on serving hits) fails the paired-ratio gate.
    p = tmp_path / "tier.json"
    p.write_text(json.dumps({"tiering_hot_p99_ratio": 1.6}))
    assert bench_check.main([str(p)]) == 1


def test_tiering_cold_floor_and_mechanism_gates(tmp_path):
    # Cold reads far below the spill floor (a per-key fallback storm).
    p = tmp_path / "tier.json"
    p.write_text(json.dumps({"tiering_cold_vs_spill_floor": 0.2}))
    assert bench_check.main([str(p)]) == 1
    # Movement must run BOTH directions; one wrong read fails outright.
    p.write_text(json.dumps({
        "tiering_hot_p99_ratio": 1.0,
        "tiering_cold_vs_spill_floor": 2.0,
        "tiering_demotions": 120,
        "tiering_promotions": 0,
        "tiering_admit_rejects": 32,
        "tiering_wrong_reads": 0,
        "tiering_misses": 0,
    }))
    assert bench_check.main([str(p)]) == 1
    p.write_text(json.dumps({
        "tiering_hot_p99_ratio": 1.0,
        "tiering_cold_vs_spill_floor": 2.0,
        "tiering_demotions": 120,
        "tiering_promotions": 4,
        "tiering_admit_rejects": 32,
        "tiering_wrong_reads": 1,
        "tiering_misses": 0,
    }))
    assert bench_check.main([str(p)]) == 1


def _prof_receipt(**over):
    """A healthy profiling/timeseries receipt slice; override to break."""
    doc = {
        "prof_overhead_cost": 0.004,
        "prof_stage_tag_fraction": 0.97,
        "prof_completion_ring_samples": 41,
        "timeseries_anomaly_faulty": 1,
        "timeseries_anomaly_clean": 0,
    }
    doc.update(over)
    return doc


def test_profiling_gates_pass_on_healthy_receipt(tmp_path):
    p = tmp_path / "prof.json"
    p.write_text(json.dumps(_prof_receipt()))
    assert bench_check.main([str(p)]) == 0


def test_prof_overhead_gate(tmp_path):
    # A sampler whose frame walks eat >3% of op wall time is too heavy
    # for an always-on production instrument.
    p = tmp_path / "prof.json"
    p.write_text(json.dumps(_prof_receipt(prof_overhead_cost=0.06)))
    assert bench_check.main([str(p)]) == 1


def test_prof_stage_attribution_gate(tmp_path):
    # Untagged samples mean the thread->span feed broke; a completion_ring
    # interval with no samples means the ROADMAP-5 receipt is empty.
    p = tmp_path / "prof.json"
    p.write_text(json.dumps(_prof_receipt(prof_stage_tag_fraction=0.5)))
    assert bench_check.main([str(p)]) == 1
    p.write_text(json.dumps(_prof_receipt(prof_completion_ring_samples=0)))
    assert bench_check.main([str(p)]) == 1


def test_timeseries_anomaly_gate(tmp_path):
    # The step must fire exactly once (edge-triggering) and never on the
    # clean run (a false positive teaches operators to delete the alert).
    p = tmp_path / "prof.json"
    p.write_text(json.dumps(_prof_receipt(timeseries_anomaly_faulty=0)))
    assert bench_check.main([str(p)]) == 1
    p.write_text(json.dumps(_prof_receipt(timeseries_anomaly_faulty=3)))
    assert bench_check.main([str(p)]) == 1
    p.write_text(json.dumps(_prof_receipt(timeseries_anomaly_clean=1)))
    assert bench_check.main([str(p)]) == 1
