"""The harness on a cache that is not a K and a V of one shape: what a block
weighs, what a hit installs of it where some tensors are checkpoints, the
bytes and evictions it accounts, the useful work of a cost module it has never
seen and a counter of the program's named by a metric file, all from made-up
objects and all against numbers worked out by hand."""

import argparse
import json
import os
import re
import types

import numpy as np
import pytest

import readers
import run
import trace_reduce
import traffic
from cache_geometry import CacheGeometry, hit_mismatch

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BLOCKS = 4


def tensor(*block_shape, dtype=np.float16):  # float16: two bytes a value, as bf16
    return np.zeros((BLOCKS, *block_shape), dtype)


# A latent of 512 and a rope key of 64 per token: 16 x 512 x 2 = 16,384 B and
# 16 x 64 x 2 = 2,048 B a block; three such layers.
LATENT = [(tensor(16, 1, 512), tensor(16, 1, 64)) for _ in range(3)]
# Layers of two kinds: two latent layers and one with a K and a V of 4 heads
# x 32: 16 x 4 x 32 x 2 = 4,096 B each.
MIXED = LATENT[:2] + [(tensor(16, 4, 32), tensor(16, 4, 32))]
BY_HAND = {
    "latent": dict(caches=LATENT, block=3 * 18432, values=6, largest=16384, per_token=3456, kib=16,
                   listed=[[3, 16], [3, 2]]),
    "mixed": dict(caches=MIXED, block=2 * 18432 + 8192, values=6, largest=16384, per_token=2816, kib=16,
                  listed=[[2, 16], [2, 2], [2, 4]]),
}


def serving(want):
    """Values of unlike sizes: the file lists them, and names its unit."""
    return {"block_tokens": 16, "kv_bytes_per_token": want["per_token"], "store_block_kib": want["kib"],
            "store_unit_kib": 16, "store_values_kib": want["listed"]}


@pytest.mark.parametrize("case", sorted(BY_HAND))
def test_geometry_from_the_caches_alone(case):
    want = BY_HAND[case]
    g = CacheGeometry.of(want["caches"])
    assert g.block_nbytes == want["block"] and g.values_per_block == want["values"]
    assert g.largest_value_nbytes == want["largest"] == want["kib"] * 1024
    g.check(serving(want))  # the file agrees: nothing raised
    # No tensor is a checkpoint: a hit installs and fetches whole blocks.
    assert g.installed_nbytes(5) == g.fetched_nbytes(5 * want["values"], 5) == 5 * want["block"]
    assert g.fetched_values(5) == 5 * want["values"] == len(g.compared(5))


@pytest.mark.parametrize("case", sorted(BY_HAND))
def test_a_file_that_misstates_its_cache_is_refused_with_both_numbers(case):
    want = BY_HAND[case]
    g = CacheGeometry.of(want["caches"])
    wrong = dict(serving(want), kv_bytes_per_token=65536)
    with pytest.raises(ValueError, match=rf"65536.*\b{want['per_token']}\b"):
        g.check(wrong)
    wrong = dict(serving(want), store_block_kib=32, store_values_kib=[[6, 32]])
    with pytest.raises(ValueError, match=r"store_block_kib is 32 .*\b16384\b"):
        g.check(wrong)
    # The list of values misstates the caches: both lists are said, by size.
    built = sorted(want["listed"], key=lambda v: v[1])
    listed = [[n + (kib == 2), kib] for n, kib in built]
    says = re.escape(f"store_values_kib is {listed} ") + ".*" + re.escape(f" puts {built} in the store")
    with pytest.raises(ValueError, match=says):
        g.check(dict(serving(want), store_values_kib=listed))
    # Without the list every value is held to be of store_block_kib: these caches' are not.
    unlisted = {k: v for k, v in serving(want).items() if k != "store_values_kib"}
    with pytest.raises(ValueError, match="no whole number of values of serving.store_block_kib"):
        g.check(unlisted)


def stub_cell_run(caches, program_counters=(), hit_installs=()):
    """A ``CellRun`` with nothing behind it but what the accounting reads."""
    args = argparse.Namespace(seed=1, seconds=4.0, trace=0)
    cell_run = run.CellRun(args, {"name": "made-up"}, {"name": "made-up"}, None, program_counters)
    cell_run.cfg = types.SimpleNamespace(block_tokens=16)
    cell_run.geometry = CacheGeometry.of(caches, hit_installs)
    return cell_run


def hit_row(cell_run, blocks, values):
    """The row of a request that hit ``blocks`` blocks and fetched ``values`` store values."""
    stats = types.SimpleNamespace(
        loaded_blocks=blocks, hit_blocks=blocks, prefetched_blocks=values, gate_stall_us=0.0,
        prefix_ready_us=1e3, ttft_us=2e3, gate_hold_us=0.0, fetch_us=5e3, trace_id=7, token_emit_s=[1.5],
    )
    req = traffic.Request(0, 0, None, 0, 1, 48, 16, 4)
    rec = run.Record(req=req, t_start=1.0, t_dispatch=1.0, t_sent=1.0, stamps=[1.2, 1.5, 1.6], stats=stats)
    return cell_run.request_row(rec)


@pytest.mark.parametrize("case", sorted(BY_HAND))
def test_installed_and_fetched_bytes_of_a_request(case):
    want = BY_HAND[case]
    row = hit_row(stub_cell_run(want["caches"]), 3, 3 * want["values"])
    assert row["installed_bytes"] == 3 * want["block"]
    # A fetch takes every value of a block, so count x mean is exact.
    assert row["fetched_bytes"] == 3 * want["block"]
    assert row["trace_id"] == 7 and row["emit_s"] == [1.5] and row["bench_emit_s"] == [1.5, 1.6]


# A hybrid of paged attention and recurrent state at 1,024-token blocks: three
# layers of a K and a V of 2 heads x 128 and the keys pooled to 64 a block
# (2 x 524,288 + 32,768 B), beside nine layers of ONE tensor, a state of 32
# heads x 128 x 128 float32 = 2 MiB saved with every block, of which a hit
# installs the last block's alone.
ATTN = (1024 * 2 * 128 * 2, 1024 * 2 * 128 * 2, 64 * 2 * 128 * 2)
STATE = 32 * 128 * 128 * 4
HYBRID_LAYERS = "ASSSSSSAASSS"  # which of the twelve layers is which
STATE_LAYERS = [i for i, kind in enumerate(HYBRID_LAYERS) if kind == "S"]
HYBRID_POLICY = [{"layers": STATE_LAYERS, "tensor": 0, "last_blocks": 1}]


# What its file's ``serving`` says of the store: a unit of 64 KiB, the six K and
# V of 512 KiB, the three pooled keys of 32 KiB, the nine states of 2 MiB.
HYBRID_SERVING = {
    "block_tokens": 1024, "kv_bytes_per_token": 21_600, "store_block_kib": 2048, "store_unit_kib": 64,
    "store_values_kib": [[6, 512], [3, 32], [9, 2048]], "hit_installs": HYBRID_POLICY,
}


def hybrid_caches(scale=1):
    """The hybrid's caches; ``scale`` divides every tensor's last axis, for
    the cases that fill them with bytes."""
    attn = (tensor(1024, 2, 128 // scale), tensor(1024, 2, 128 // scale), tensor(64, 2, 128 // scale))
    state = (tensor(32, 128, 128 // scale, dtype=np.float32),)
    return [attn if kind == "A" else state for kind in HYBRID_LAYERS]


def test_a_hit_installs_every_kv_block_and_the_last_blocks_state():
    g = CacheGeometry.of(hybrid_caches(), HYBRID_POLICY)
    # Every block still WRITES every tensor: the pool and the evictions see all of it.
    assert g.block_nbytes == 3 * sum(ATTN) + 9 * STATE == 22_118_400 == 21_600 * 1024
    assert g.values_per_block == 3 * 3 + 9 and g.largest_value_nbytes == STATE == 2048 * 1024
    g.check(HYBRID_SERVING)
    # A hit of 32 blocks: 32 x the attention layers' values, one state a layer.
    assert g.fetched_values(32) == 32 * 9 + 9
    assert g.installed_nbytes(32) == 32 * 3_244_032 + 18_874_368 == 122_683_392
    assert g.installed_nbytes(32) != 32 * g.block_nbytes == 707_788_800
    assert g.installed_nbytes(1) == g.block_nbytes and g.installed_nbytes(0) == 0
    triples = g.compared(32)
    assert len(triples) == 32 * 9 + 9
    assert {(layer, block) for layer, _, block in triples if layer in STATE_LAYERS} == {
        (layer, 31) for layer in STATE_LAYERS
    }
    assert [(t, b) for layer, t, b in triples if layer == 0] == [(t, b) for t in range(3) for b in range(32)]
    # The request's row: what the program counted as fetched, in bytes.
    row = hit_row(stub_cell_run(hybrid_caches(), hit_installs=HYBRID_POLICY), 32, 32 * 9 + 9)
    assert row["installed_bytes"] == row["fetched_bytes"] == 122_683_392


def test_a_window_of_two_blocks():
    """A sliding layer whose window is two blocks: a hit installs its last two."""
    caches = [(tensor(16, 4, 32), tensor(16, 4, 32)), (tensor(16, 4, 32), tensor(16, 4, 32))]
    policy = [{"layers": [1], "tensor": t, "last_blocks": 2} for t in (0, 1)]
    g = CacheGeometry.of(caches, policy)
    assert g.block_nbytes == 4 * 4096 and g.values_per_block == 4
    assert g.installed_nbytes(5) == 2 * 5 * 4096 + 2 * 2 * 4096 and g.fetched_values(5) == 10 + 4
    assert g.installed_nbytes(1) == g.block_nbytes  # a hit shorter than the window: all of it
    assert [(t, b) for layer, t, b in g.compared(5) if layer == 1] == [(0, 3), (0, 4), (1, 3), (1, 4)]
    assert g.fetched_nbytes(14, 5) == g.installed_nbytes(5)


def test_four_sliding_layers_and_a_full_one():
    """Dry run 4 as shapes: ten tensors of 16 KiB a block over five layers, the
    K and V of layers 0-3 checkpoints of the window's 128 blocks. The byte
    comparison and a request's row follow the same policy."""
    kv = np.broadcast_to(np.float16(0), (BLOCKS, 16, 4, 128))
    policy = [{"layers": [0, 1, 2, 3], "tensor": t, "last_blocks": 128} for t in (0, 1)]
    cell_run = stub_cell_run([(kv, kv)] * 5, hit_installs=policy)
    g = cell_run.geometry
    assert g.block_nbytes / 16 == 10_240 and g.largest_value_nbytes == 16 * 1024
    triples = g.compared(2056)
    assert len(triples) == g.fetched_values(2056) == 5136
    assert {b for layer, _, b in triples if layer < 4} == set(range(1928, 2056))
    assert {b for layer, _, b in triples if layer == 4} == set(range(2056))
    row = hit_row(cell_run, 2056, 5136)
    assert row["installed_bytes"] == row["fetched_bytes"] == 84_148_224
    # A program that fetched every block of every layer is off the policy by 15,424 values.
    assert 10 * 2056 - g.fetched_values(2056) == 15_424
    assert len(g.compared(100)) == g.fetched_values(100) == 1000


@pytest.mark.parametrize("policy,says", [
    ([{"layers": [3, 12], "tensor": 0, "last_blocks": 1}], r"tensor 0 of layer 12 .* 12 layers of \[3, 1, 1, "),
    ([{"layers": [1], "tensor": 1, "last_blocks": 1}], r"tensor 1 of layer 1 .* 12 layers of \[3, 1, 1, "),
    ([{"layers": [1], "tensor": 0, "last_blocks": 0}], "last_blocks of 1 or more"),
    ([{"layers": [1, 1], "tensor": 0, "last_blocks": 1}], "twice"),
], ids=["layer", "tensor", "count", "twice"])
def test_a_policy_the_caches_do_not_have_stops_with_both_shapes(policy, says):
    with pytest.raises(ValueError, match=says):
        CacheGeometry.of(hybrid_caches(), policy)


def filled_hit(n, seed=5):
    """A hit of ``n`` blocks of the hybrid, at an eighth of its widths: the
    bytes every block's save was handed, and what a program that follows the
    policy leaves on the device and the check reads back: every attention
    block, and the state of block n - 1 alone (the states before it were
    never installed, and are not read back)."""
    rng = np.random.default_rng(seed)
    caches = hybrid_caches(scale=8)
    g = CacheGeometry.of(caches, HYBRID_POLICY)
    chains = [f"chain-{i}" for i in range(n)]
    saved = {c: [[rng.bytes(t[0].nbytes) for t in layer] for layer in caches] for c in chains}
    installed = [
        [
            np.frombuffer(b"".join(saved[c][layer][i] for c in chains), t.dtype).reshape(n, *t.shape[1:]).copy()
            for i, t in enumerate(tensors)
        ]
        for layer, tensors in enumerate(caches)
    ]
    for layer in STATE_LAYERS:
        installed[layer][0] = installed[layer][0][n - 1 :]
    return g, installed, saved, chains


def flip_a_byte(array, block):
    array[block].view(np.uint8).reshape(-1)[-1] ^= 1


def test_the_full_hits_comparison_follows_the_policy():
    g, installed, saved, chains = filled_hit(32)
    assert hit_mismatch(installed, saved, chains, g) is None  # only block 31's states are there
    assert [[len(t) for t in layer] for layer in installed] == [
        [len(blocks) for blocks in layer] for layer in g.installed_blocks(32)
    ] == [[32, 32, 32] if kind == "A" else [1] for kind in HYBRID_LAYERS]
    # The same read-back under a file WITHOUT the key: every state block is held to its save.
    every = CacheGeometry.of(hybrid_caches(scale=8))
    assert "layer 1 tensor 0: read back 1 blocks, a hit of 32 installs 32" in hit_mismatch(installed, saved, chains, every)
    whole = [[np.concatenate([np.zeros((32 - len(t), *t.shape[1:]), t.dtype), t]) for t in layer] for layer in installed]
    assert "layer 1 tensor 0 block 0 of 32" in hit_mismatch(whole, saved, chains, every)
    # Block 31's state, the one read back, differs by one byte.
    flip_a_byte(installed[STATE_LAYERS[-1]][0], 0)
    assert "layer 11 tensor 0 block 31 of 32 is not the bytes that were saved" in hit_mismatch(installed, saved, chains, g)
    flip_a_byte(installed[STATE_LAYERS[-1]][0], 0)
    assert hit_mismatch(installed, saved, chains, g) is None
    # Any K block differs; so for a V and for the pooled keys.
    for layer, t, block in ((0, 0, 0), (7, 1, 17), (8, 2, 31)):
        flip_a_byte(installed[layer][t], block)
        assert f"layer {layer} tensor {t} block {block} of 32" in hit_mismatch(installed, saved, chains, g)
        flip_a_byte(installed[layer][t], block)
    # A read-back that is short of blocks, of layers, or a chain no save was seen for.
    assert "layer 0 tensor 0: read back 30 blocks, a hit of 32 installs 32" in hit_mismatch(
        [[t[:30] for t in layer] for layer in installed], saved, chains, g)
    assert "0 layers" in hit_mismatch((), saved, chains, g)
    assert "block 4 of 32: no save" in hit_mismatch(
        installed, {c: v for c, v in saved.items() if c != chains[4]}, chains, g)


def accepted_files():
    """The configurations WITHOUT ``serving.hit_installs``, chosen by what
    the file holds: one whose layers install unlike shares of a hit states
    the list, is no case, and trips nothing."""
    bench = run.load_json(os.path.join(run.REPO, "BENCHMARK.json"))
    return [
        pytest.param(c["file"], id=c["name"]) for c in bench["configs"]
        if "hit_installs" not in run.load_json(os.path.join(run.REPO, c["file"]))["serving"]
    ]


def test_the_two_dense_files_are_among_the_cases():
    assert {"mistral-7b-v0.3", "deepseek-llm-7b"} <= {p.id for p in accepted_files()}


@pytest.mark.parametrize("path", accepted_files())
def test_an_accepted_file_without_the_key_counts_what_it_counted(path):
    """For a file without the key the bytes of a hit and
    the compared triples are what ``run.py`` computed before the key existed
    (``d292d7e``: ``loaded_blocks * block_nbytes``, ``prefetched_blocks *
    mean_value_nbytes``, every tensor of every layer in all n blocks)."""
    with open(os.path.join(run.REPO, path)) as f:
        config = json.load(f)
    kv = tensor(config["serving"]["block_tokens"], config["num_key_value_heads"], config["head_dim"])
    caches = [(kv, kv)] * config["num_hidden_layers"]
    g = CacheGeometry.of(caches)
    g.check(config["serving"])
    block_nbytes = sum(t.nbytes // BLOCKS for layer in caches for t in layer)
    values_per_block = 2 * len(caches)
    cell_run = stub_cell_run(caches)
    for n in (0, 1, 64, 136, 520):
        row = hit_row(cell_run, n, n * values_per_block)
        assert row["installed_bytes"] == g.installed_nbytes(n) == n * block_nbytes
        for values in (n * values_per_block, n * values_per_block // 2, 7):  # whole, cut short
            assert g.fetched_nbytes(values, n) == values * (block_nbytes / values_per_block)
        assert row["fetched_bytes"] == n * block_nbytes
        assert g.compared(n) == [
            (layer, kind, i) for layer, tensors in enumerate(caches)
            for kind in range(len(tensors)) for i in range(n)
        ]


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
    """Since PR 32 it stops nothing: a metric file may come in the same PR
    as the counter it reads, and the driver lays the new files over the
    parent, which has no such counter. The key is left out, so its metric is
    left out of that side's line; a key that IS there and is no number still
    stops the run."""
    cell_run = stub_cell_run(LATENT, ("no_such_counter", "waves_launched", "spill.dropped", "spill.no_such"))
    cell_run.h = types.SimpleNamespace(metrics=lambda: {"wave_buckets": [], "waves_launched": 7})
    stats = {"spill": {"dropped": 2}}
    cell_run.adapter = types.SimpleNamespace(connector=types.SimpleNamespace(get_stats=lambda: stats))
    assert cell_run.read_program_counters() == {"waves_launched": 7, "spill.dropped": 2}
    view = readers.Run([], {"waves_launched": 3}, None, {})
    assert readers.KINDS["counter"](view, {"kind": "counter", "key": "no_such_counter"}) is None
    assert readers.KINDS["counter"](view, {"kind": "counter", "key": "no_such_counter", "per": "waves_launched"}) is None
    assert readers.KINDS["counter"](view, {"kind": "counter", "key": "waves_launched", "per": "no_such_counter"}) is None
    for key in ("wave_buckets", "spill"):  # a list, a table: there, and no number
        cell_run.program_counters = [key]
        with pytest.raises(ValueError, match=key):
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


def test_the_traced_resumes_are_priced_by_a_cost_module_that_has_resume_work(monkeypatch):
    """The resumes the taps saw inside the traced seconds (dispatch time,
    context pages, chunk rows), summed by ``resume_work`` under its own key;
    ``chunk_attn_roofline.reuse`` reads it over the kernel's device time."""
    import costs

    config = run.load_json(os.path.join(run.HERE, "configs", "mistral-7b-v0.3.json"))
    cell_run = stub_cell_run(LATENT)
    cell_run.config, cell_run.costs = config, costs
    cell_run.trace_t0, cell_run.trace_t1, cell_run.trace_dir = 100.0, 108.0, "unused"
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda _dir: os.path.join(DATA, "small_trace.json"))
    cell_run.records = []
    cell_run.taps = types.SimpleNamespace(
        prefills=[], resumes=[(99.9, 520, 128), (100.0, 136, 128), (104.0, 264, 128), (108.0, 520, 128)],
    )
    trace = cell_run.trace_results(None)
    assert trace["work"]["chunk_attn_flops"] == 70_883_737_600 + 139_603_214_336  # the two inside
    assert trace["work"]["chunk_attn_bytes"] == 16 * 2 * ((2176 + 4224) * 8 + 2 * 128 * 32) * 128 * 2
    assert trace["work"]["ragged_decode_bytes"] == trace["work"]["flash_prefill_flops"] == 0
    spec = readers.load_layer_metric("chunk_attn_roofline.reuse")
    trace["ops"] = {"chunk_prefix_attention_pallas_bf16_8_512_128": [0.012, 32], "fusion": [1.0, 9]}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    view = readers.Run([], {}, trace, peaks)
    # Four query heads a KV head: the operations bind (1.07 ms against 0.58 of bytes).
    assert readers.KINDS[spec["reader"]["kind"]](view, spec["reader"]) == pytest.approx(
        100.0 * 210_486_951_936 / (197e12 * 0.012)
    )
    # One query head a KV head: the bytes bind, and the share is theirs.
    mha = dict(trace, work=dict(trace["work"], chunk_attn_bytes=4 * trace["work"]["chunk_attn_bytes"]))
    assert readers.read_layer_metric("chunk_attn_roofline.reuse", readers.Run([], {}, mha, peaks)) == pytest.approx(
        100.0 * mha["work"]["chunk_attn_bytes"] / (819e9 * 0.012)
    )
    # No resume in the traced seconds, or none of its kernel's events: no sample, never 0.
    trace["ops"] = {"fusion": [1.0, 9]}
    assert readers.read_layer_metric("chunk_attn_roofline.reuse", view) is None
    cell_run.taps.resumes = []
    idle = cell_run.trace_results(None)
    idle["ops"] = {"chunk_prefix_attention_pallas_bf16_8_512_128": [0.012, 32]}
    assert readers.read_layer_metric("chunk_attn_roofline.reuse", readers.Run([], {}, idle, peaks)) is None
