"""The put path's per-layer metrics (PR 42; ``put_file_share`` PR 44) and
its probe.

``save_put_gbps``, ``put_touched_share`` and ``put_file_share`` are
``counter`` readers over keys of ``KVConnector.get_stats()``; a tree
without the keys (the parent) leaves them out of the line. ``tools/putfault_probe.py`` is smoked at a tiny
size for the SHAPE of its line: a rate belongs to the host it was read on.
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REUSE_CELLS = [
    "mistral7b-prefix-reuse", "deepseek7b-prefix-reuse", "trinity-mini-long-prefix-reuse",
    "kimi-linear-long-prefix-reuse", "falcon-h1-long-prefix-reuse",
    "granite-h-small-long-prefix-reuse", "mellum2-completion-prefix-reuse",
    "glm5-long-prefix-reuse", "phi4-flash-long-prefix-reuse", "openpangu-mtp-long-prefix-reuse",
]
METRICS = {
    "save_put_gbps": ("GB/s", "save_put_bytes", "save_put_busy_us"),
    "put_touched_share": ("%", "put_touched_bytes", "put_copy_bytes"),
    "put_file_share": ("%", "put_file_bytes", "put_copy_bytes"),
}


@pytest.fixture
def readers(monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmarks"))
    return importlib.import_module("readers")


@pytest.mark.parametrize("name", sorted(METRICS))
def test_file_agrees_with_its_benchmark_json_entry(readers, name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    unit, key, per = METRICS[name]
    spec = readers.load_layer_metric(name)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert all(spec[k] == entry[k] for k in set(entry) - {"workloads"})
    assert entry["workloads"] == REUSE_CELLS and entry["moves"] == "tokens_per_s"
    assert entry["unit"] == unit and entry["better"] == "higher"
    assert entry["layer"] == "Store client / wire / server"
    assert spec["reader"]["kind"] == "counter" and spec["source"] == "program_counter"
    assert (spec["reader"]["key"], spec["reader"]["per"]) == (key, per)
    assert readers.counter_keys([name]) == {key, per}


def test_the_readers_on_a_window_and_on_a_parent(readers):
    window = {
        "save_put_bytes": 600_000_000, "save_put_busy_us": 750_000.0,  # 0.8 GB/s
        "put_touched_bytes": 570_000_000, "put_copy_bytes": 600_000_000,  # 95%
        "put_file_bytes": 600_000_000,  # every copy through the descriptor
    }
    run = readers.Run([], window, None, {})
    assert readers.read_layer_metric("save_put_gbps", run) == pytest.approx(0.8)
    assert readers.read_layer_metric("put_touched_share", run) == pytest.approx(95.0)
    assert readers.read_layer_metric("put_file_share", run) == pytest.approx(100.0)
    # The parent has no such keys; a window without a put has no rate.
    for counters in ({}, dict(window, save_put_busy_us=0.0, put_copy_bytes=0)):
        run = readers.Run([], counters, None, {})
        assert all(readers.read_layer_metric(name, run) is None for name in METRICS)
    # PR 43's tree: the put's ledger without the descriptor's key.
    del window["put_file_bytes"]
    run = readers.Run([], window, None, {})
    assert readers.read_layer_metric("put_file_share", run) is None
    assert readers.read_layer_metric("put_touched_share", run) == pytest.approx(95.0)


def test_probe_prints_one_line_of_the_documented_shape():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "putfault_probe.py"), "--pool-gib", "1",
         "--puts", "4", "--blocks", "4", "--block-kib", "64", "--big-pool-gib", "1",
         "--value-kib", "64", "512", "--put-mib", "2", "--stall-mib", "8",
         "--stall-read-mib", "1", "--stall-value-kib", "64", "--stall-tail-s", "0.3"],
        capture_output=True, text=True, timeout=300, check=True,
    )
    (text,) = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    line = json.loads(text)
    assert line["probe"] == "putfault" and line["put_bytes"] == 4 * (64 << 10)
    assert line["page_bytes"] > 0 and isinstance(line["ru_minflt_counts"], bool)
    assert line["shm"] is True and isinstance(line["pinned"], bool)
    puts = {"untouched", "client_touched", "socket", "server_touched", "server_touched_pinned"}
    assert set(line["put_gbps"]) == puts
    assert all(len(v) == 4 and min(v) > 0 for v in line["put_gbps"].values())
    gets = line["get_gbps"]
    assert gets["loc_exact"] is True and gets["into_exact"] is True
    for key in ("loc_first", "loc_second", "into_first", "into_second"):
        assert len(gets[key]) == 2 and min(gets[key]) > 0
    for key in ("pread_first", "pread_second"):  # beside them: the pool file, no mapping
        assert len(gets[key]) == 4 and min(gets[key]) > 0
    seg = line["segment"]
    assert {"untouched", "touched_again", "after_read_touch", "after_write_touch",
            "read_touch_us_per_page", "write_touch_us_per_page", "madv_populate_write",
            "pwrite_untouched", "pwrite_again", "pread"} <= set(seg)
    assert (seg["madv_populate_write"] == "ok") == ("after_populate" in seg)
    # The descriptor's put by value size: cold and warm, one writer and two.
    by_value = line["put_by_value_gbps"]
    assert set(by_value) == {"64", "512"}
    for rates in by_value.values():
        assert set(rates) == {"one_cold", "one_warm", "one_file_share",
                              "two_cold", "two_warm", "two_file_share"}
        assert len(rates["one_cold"]) == len(rates["one_warm"]) == 4
        assert len(rates["two_cold"]) == len(rates["two_warm"]) == 1
        assert min(rates["one_cold"] + rates["one_warm"] + rates["two_cold"]) > 0
        assert rates["one_file_share"] == rates["two_file_share"] == 100.0
    # The read that stood still: its latencies during the puts and after them.
    stall = line["stall"]
    assert stall["exact"] is True and stall["file_share"] == 100.0
    assert stall["read_bytes"] == 1 << 20 and stall["put_bytes"] == 8 << 20
    assert stall["put_gbps"] > 0 and stall["put_gbps_min"] > 0
    assert set(stall["read_ms"]) == {"during", "after"}
    after = stall["read_ms"]["after"]
    assert after["n"] > 0 and 0 < after["p50"] <= after["max"]
    for at in ("at_ack", "at_end"):
        ledger = stall["counters"][at]
        assert ledger["put_file_bytes"] == ledger["put_copy_bytes"] == (1 << 20) + (8 << 20)
        assert ledger["pretouch_bytes"] == 0
