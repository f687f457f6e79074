"""The harness on a cache that is not a K and a V of one shape: what a block
weighs, the bytes and evictions it accounts, the useful work of a cost module
it has never seen and a counter of the program's named by a metric file, all
from made-up objects and all against numbers worked out by hand."""

import argparse
import os
import types

import numpy as np
import pytest

import readers
import run
import trace_reduce
import traffic
from cache_geometry import CacheGeometry

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BLOCKS = 4


def tensor(*block_shape):
    return np.zeros((BLOCKS, *block_shape), np.float16)  # two bytes a value, as bf16


# A latent of 512 and a rope key of 64 per token: 16 x 512 x 2 = 16,384 B and
# 16 x 64 x 2 = 2,048 B a block; three such layers.
LATENT = [(tensor(16, 1, 512), tensor(16, 1, 64)) for _ in range(3)]
# Layers of two kinds: two latent layers and one with a K and a V of 4 heads
# x 32: 16 x 4 x 32 x 2 = 4,096 B each.
MIXED = LATENT[:2] + [(tensor(16, 4, 32), tensor(16, 4, 32))]
BY_HAND = {
    "latent": dict(caches=LATENT, block=3 * 18432, values=6, largest=16384, per_token=3456, kib=16),
    "mixed": dict(caches=MIXED, block=2 * 18432 + 8192, values=6, largest=16384, per_token=2816, kib=16),
}


def serving(want):
    return {"block_tokens": 16, "kv_bytes_per_token": want["per_token"], "store_block_kib": want["kib"]}


@pytest.mark.parametrize("case", sorted(BY_HAND))
def test_geometry_from_the_caches_alone(case):
    want = BY_HAND[case]
    g = CacheGeometry.of(want["caches"])
    assert g.block_nbytes == want["block"] and g.values_per_block == want["values"]
    assert g.largest_value_nbytes == want["largest"] == want["kib"] * 1024
    assert g.mean_value_nbytes * g.values_per_block == want["block"]
    g.check(serving(want))  # the file agrees: nothing raised


@pytest.mark.parametrize("case", sorted(BY_HAND))
def test_a_file_that_misstates_its_cache_is_refused_with_both_numbers(case):
    want = BY_HAND[case]
    g = CacheGeometry.of(want["caches"])
    wrong = dict(serving(want), kv_bytes_per_token=65536)
    with pytest.raises(ValueError, match=rf"65536.*\b{want['per_token']}\b"):
        g.check(wrong)
    wrong = dict(serving(want), store_block_kib=32)
    with pytest.raises(ValueError, match=r"store_block_kib is 32 .*\b16384\b"):
        g.check(wrong)


def stub_cell_run(caches, program_counters=()):
    """A ``CellRun`` with nothing behind it but what the accounting reads."""
    args = argparse.Namespace(seed=1, seconds=4.0, trace=0)
    cell_run = run.CellRun(args, {"name": "made-up"}, {"name": "made-up"}, None, program_counters)
    cell_run.cfg = types.SimpleNamespace(block_tokens=16)
    cell_run.geometry = CacheGeometry.of(caches)
    return cell_run


@pytest.mark.parametrize("case", sorted(BY_HAND))
def test_installed_and_fetched_bytes_of_a_request(case):
    want = BY_HAND[case]
    cell_run = stub_cell_run(want["caches"])
    stats = types.SimpleNamespace(
        loaded_blocks=3, prefetched_blocks=3 * want["values"], gate_stall_us=0.0, prefix_ready_us=1e3,
        ttft_us=2e3, gate_hold_us=0.0, fetch_us=5e3, trace_id=7, token_emit_s=[1.5],
    )
    req = traffic.Request(0, 0, None, 0, 1, 48, 16, 4)
    rec = run.Record(req=req, t_start=1.0, t_dispatch=1.0, t_sent=1.0, stamps=[1.2, 1.5, 1.6], stats=stats)
    row = cell_run.request_row(rec)
    assert row["installed_bytes"] == 3 * want["block"]
    # A fetch takes every value of a block, so count x mean is exact.
    assert row["fetched_bytes"] == 3 * want["block"]
    assert row["trace_id"] == 7 and row["emit_s"] == [1.5] and row["bench_emit_s"] == [1.5, 1.6]


@pytest.mark.parametrize("case", sorted(BY_HAND))
def test_store_evictions_and_the_programs_counters_by_name(case):
    want = BY_HAND[case]
    seen = {"metrics": {"saves_overlapped": 3, "hit_rate": 0.5}, "stats": {"kvmap_len": 10, "spill": {"dropped": 1}}}
    cell_run = stub_cell_run(want["caches"], ("saves_overlapped", "spill.dropped"))
    wave = types.SimpleNamespace(waves=0, launched_rows=0, pad_rows=0)
    cell_run.h = types.SimpleNamespace(wave=wave, metrics=lambda: dict(seen["metrics"]))
    connector = types.SimpleNamespace(get_stats=lambda: dict(seen["stats"]))
    cell_run.adapter = types.SimpleNamespace(connector=connector, chains_saved={"a", "b", "c", "d", "e"})
    cell_run.conn = connector
    cell_run.compiles = types.SimpleNamespace(count=0)
    cell_run.t_open, cell_run.t_close = 10.0, 14.0
    cell_run.at_open = cell_run.snapshot()
    # In the window: 6 waves of 9 real rows, 4 more overlapped saves, 2 more
    # entries dropped from the spill tier; the server ends with 28 keys.
    wave.waves, wave.launched_rows, wave.pad_rows = 6, 12, 3
    seen["metrics"]["saves_overlapped"] = 7
    seen["stats"] = {"kvmap_len": 28, "spill": {"dropped": 3}}
    cell_run.at_close = cell_run.snapshot()
    counters = cell_run.results(setup_s=1.0, peak_bytes=0)["counters"]
    assert counters["store_evictions"] == 5 * want["values"] - 28 == 2
    assert counters["saves_overlapped"] == 4 and counters["spill.dropped"] == 2
    assert (counters["waves"], counters["real_rows"]) == (6, 9)
    view = readers.Run([], counters, None, {})
    assert readers.KINDS["counter"](view, {"kind": "counter", "key": "spill.dropped"}) == 2
    assert readers.KINDS["counter"](view, {"kind": "counter", "key": "real_rows", "per": "waves"}) == 1.5


def test_a_counter_that_is_nowhere_stops_the_run():
    cell_run = stub_cell_run(LATENT, ("no_such_counter",))
    cell_run.h = types.SimpleNamespace(metrics=lambda: {"wave_buckets": []})
    cell_run.adapter = types.SimpleNamespace(connector=types.SimpleNamespace(get_stats=lambda: {}))
    with pytest.raises(ValueError, match="no_such_counter"):
        cell_run.read_program_counters()


def test_work_of_a_cost_module_the_harness_has_never_seen(monkeypatch):
    """A latent is read once for scores and values: its module says so under
    a key of its own, and a ``trace_roofline`` reader finds it there."""
    made_up = types.ModuleType("costs_made_up")
    made_up.WORK_KEYS = ("latent_decode_bytes", "mla_prefill_flops")
    made_up.wave_work = lambda config, pages, rows: {
        "latent_decode_bytes": config["num_hidden_layers"] * (pages * 16 * 576 * 2 + rows * 100)
    }
    made_up.prefill_work = lambda config, tokens: {"mla_prefill_flops": config["num_hidden_layers"] * tokens**2}
    cell_run = stub_cell_run(LATENT)
    cell_run.config = {"num_hidden_layers": 3}
    cell_run.costs = made_up
    cell_run.trace_t0, cell_run.trace_t1, cell_run.trace_dir = 100.0, 108.0, "unused"
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda _dir: os.path.join(DATA, "small_trace.json"))
    req = traffic.Request(0, 0, None, 0, 0, 0, 16, 4)
    cell_run.records = [run.Record(
        req=req, t_start=99.0, t_dispatch=99.0, t_sent=99.0,
        stamps=[99.5, 101.0, 107.9, 108.0], calls=[(5, 1), (5, 1), (6, 2), (6, 1)],  # two inside
    )]
    cell_run.taps = types.SimpleNamespace(prefills=[(99.0, 512), (103.0, 1000), (104.0, 24)])
    trace = cell_run.trace_results(None)
    assert trace["work"] == {
        "latent_decode_bytes": 3 * (5 * 18432 + 100) + 3 * (6 * 18432 + 200),
        "mla_prefill_flops": 3 * (1000**2 + 24**2),
        "prefill_ktok": 1.024,
    }
    reader = {"kind": "trace_roofline", "pattern": "paged_decode_attention_pallas_ragged",
              "cost": "latent_decode_bytes", "peak": "hbm_bytes_per_s"}
    view = readers.Run([], {}, trace, {"hbm_bytes_per_s": 819e9})
    seconds = 0.002156872  # the ragged kernel's device time in the recorded trace
    assert readers.KINDS["trace_roofline"](view, reader) == pytest.approx(
        100.0 * trace["work"]["latent_decode_bytes"] / (819e9 * seconds)
    )
