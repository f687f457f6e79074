"""BENCHMARK.json keeps to the contract's letters, and every name in it
finds its file."""

import importlib
import json
import os
import re

import pytest

import readers
import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert BENCH["paths"] == ["benchmarks"] and BENCH["command"][1].startswith("benchmarks/")


def test_names_and_units_are_made_of_the_allowed_characters():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(set(names)) == len(names)
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and all(NAME.match(k) for k in c["reduced"])


def test_end_to_end_metrics_have_bounds_and_cells():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        listed = [m for m in e2e.values() if cell in m.get("workloads", cells)]
        assert len(listed) >= 2


def test_loop_kinds_decide_where_rate_and_tail_are_reported():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    loops = {w["name"]: traffic.load_params(w["traffic"])["loop"] for w in BENCH["workloads"]}
    if "tokens_per_s" in e2e:
        assert all(loops[c] == "closed" for c in e2e["tokens_per_s"]["workloads"])
    if "ttft_p90_ms" in e2e:
        assert all(loops[c] == "open" for c in e2e["ttft_p90_ms"]["workloads"])


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_layer_metric_file_agrees_with_benchmark_json(metric):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == metric]
    assert set(entry) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    spec = readers.load_layer_metric(metric)
    # A metric's cells are listed once, in BENCHMARK.json: a PR that adds a
    # cell appends its name there and edits no file under benchmarks/.
    assert "workloads" not in spec, metric
    for key in set(entry) - {"workloads"}:
        assert spec[key] == entry[key], (metric, key)
    assert set(spec) == (set(entry) - {"workloads"}) | {"what", "reader"}, metric
    assert spec["reader"]["kind"] in readers.KINDS
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    moved = e2e[entry["moves"]]
    # The metric it moves is reported wherever this one is.
    assert set(entry.get("workloads", cells)) <= set(moved.get("workloads", cells))


def test_a_fourth_cell_gets_its_metrics_by_being_listed_in_benchmark_json_alone():
    """What listing a metric's cells once buys: a new closed-loop reuse cell
    that reports ``tokens_per_s`` has its name appended to that metric's list
    and to the lists of the per-layer metrics the reuse cells report, in a
    copy of BENCHMARK.json and nowhere else; ``metrics_for`` then hands it
    those metrics, and every one still finds its file and agrees with it."""
    import copy

    import run

    bench, new, like = copy.deepcopy(BENCH), "made-up-long-prefix-reuse", "mistral7b-prefix-reuse"
    appended = []
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(new)
            appended.append(m["name"])
    # However many the reuse cells report by now: no count is pinned here.
    assert appended[0] == "tokens_per_s" and len(appended) > 1
    got = run.metrics_for(bench, "per_layer", new)
    assert [m["name"] for m in got] == appended[1:]
    assert [m["name"] for m in got] == [m["name"] for m in run.metrics_for(BENCH, "per_layer", like)]
    assert [m["name"] for m in run.metrics_for(bench, "end_to_end", new)] == ["tokens_per_s", "setup_s"]
    assert run.metrics_for(BENCH, "per_layer", new) == []  # unlisted, it reports nothing
    for m in got:
        spec = readers.load_layer_metric(m["name"])
        assert all(spec[key] == m[key] for key in set(m) - {"workloads"}), m["name"]
        assert spec["reader"]["kind"] in readers.KINDS and m["moves"] == "tokens_per_s"
    # The counters that cell's run would snapshot come with the files too.
    assert {"wave_pad_pages", "wave_pages"} <= readers.counter_keys(m["name"] for m in got) - run.OWN_COUNTERS


def resolves(dotted: str) -> bool:
    module, _, attr = dotted.partition(":")
    return hasattr(importlib.import_module(module), attr)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    """Its configuration, traffic and every listed per-layer metric's file
    exist and parse; what the configuration's ``program`` names is there; a
    ``cost`` a metric names is one the configuration's cost module fills.
    Nothing here knows a model's shape: ``run.py`` holds ``serving`` to the
    caches the program builds (``cache_geometry.py``)."""
    (w,) = [w for w in BENCH["workloads"] if w["name"] == cell]
    (cfg,) = [c for c in BENCH["configs"] if c["name"] == w["config"]]
    with open(os.path.join(REPO, cfg["file"])) as f:
        config = json.load(f)
    assert config["source"] == cfg["source"] and config["reduced"] == cfg["reduced"]
    prog, serving = config["program"], config["serving"]
    for key in list(prog["fields"].values()) + list(prog.get("equals", {}).values()):
        assert key in config
    assert resolves(prog["config_class"]) and resolves(prog["init_params"])
    assert callable(importlib.import_module(prog["reference"]).logits)
    costs = importlib.import_module(prog["costs"])
    assert callable(costs.wave_work) and callable(costs.prefill_work) and costs.WORK_KEYS
    for key in ("block_tokens", "cache_blocks", "kv_bytes_per_token", "store_block_kib"):
        assert serving[key] > 0, key
    assert config["guarantees"]
    assert traffic.build_plan(w["traffic"]).requests
    for m in BENCH["per_layer"]:
        if cell not in m.get("workloads", [cell]):
            continue
        reader = readers.load_layer_metric(m["name"])["reader"]
        assert reader["kind"] in readers.KINDS
        for key in ("cost", "or_cost"):
            assert reader.get(key, costs.WORK_KEYS[0]) in costs.WORK_KEYS, (m["name"], reader[key])
        if reader["kind"] == "trace_time" and reader.get("per") not in (None, "event"):
            assert reader["per"] in (*costs.WORK_KEYS, "prefill_ktok"), m["name"]


def test_the_lists_are_under_their_caps_and_a_readers_parts_are_listed_with_it():
    """The contract's caps (PR 52 filled ``per_layer``'s, PR 55 made room);
    and a metric whose reader sums other metrics' (``parts``) loads each of
    them BY FILE NAME, so each is an entry, in the same cells: retiring a
    part takes an edit of that reader with it."""
    assert 1 <= len(BENCH["per_layer"]) <= 128 and 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["workloads"]) <= 24 and 1 <= len(BENCH["configs"]) <= 24
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    for m in BENCH["per_layer"]:
        for part in readers.load_layer_metric(m["name"])["reader"].get("parts", ()):
            assert listed[part].get("workloads") == m.get("workloads"), (m["name"], part)


def test_no_file_under_layer_metrics_is_left_unlisted():
    """A metric file that BENCHMARK.json does not list is read by nothing."""
    listed = {m["name"] + ".json" for m in BENCH["per_layer"]}
    assert set(os.listdir(os.path.join(REPO, "benchmarks", "layer_metrics"))) == listed
