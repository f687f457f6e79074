"""The store's allocation unit is a number of its own (PR 32): a file's
``serving`` may name ``store_unit_kib`` and list what a block puts in the
store (``store_values_kib``), and the harness sizes the server's pool in
whole units of it. Three geometries against numbers worked out by hand, the
refusals that come before the server starts, the two accepted files against
what the parent computed, and one live server at a 64 KiB unit."""

import argparse
import os

import numpy as np
import pytest

import run
import traffic
from cache_geometry import (
    POOL_EVICTS_FROM, CacheGeometry, pool_bytes_per_block, pool_gib, store_layout,
)

KIB = 1024
# A K and a V of 16 tokens x 8 heads x 128 x 2 B in each of 16 layers.
LLAMA = {"block_tokens": 16, "kv_bytes_per_token": 65536, "store_block_kib": 32}
# Dry run 2: three attention layers (a K and a V of 512 KiB; the pooled keys
# it also has are left out here as PERF.md's entry leaves them out) beside
# nine states of 2 MiB, 1,024-token blocks.
DRY_RUN_2 = {
    "block_tokens": 1024, "kv_bytes_per_token": 21_504, "store_block_kib": 2048, "store_unit_kib": 64,
    "store_values_kib": [[6, 512], [9, 2048]],
}
# Dry run 3: every layer a recurrent state of 8 heads x 8256 x 128 float32
# (33,024 KiB) and its normaliser of 8 x 8256 float32 (258 KiB), 8 layers,
# a checkpoint every 8,192 tokens.
DRY_RUN_3 = {
    "block_tokens": 8192, "kv_bytes_per_token": 33_282, "store_block_kib": 33_024, "store_unit_kib": 64,
    "store_values_kib": [[8, 33_024], [8, 258]],
    "hit_installs": [
        {"layers": list(range(8)), "tensor": 0, "last_blocks": 1},
        {"layers": list(range(8)), "tensor": 1, "last_blocks": 1},
    ],
}
# Dry run 4 (PR 33): five layers of a K and a V of 16 tokens x 4 KV heads x 128
# x 2 B = 16 KiB; layers 0-3 slide over a window of 2,048 tokens = 128 blocks,
# layer 4 attends to everything. Values of one size, a power of two that is the
# server's smallest unit: the file needs neither a unit nor a list.
DRY_RUN_4 = {
    "block_tokens": 16, "kv_bytes_per_token": 10_240, "store_block_kib": 16,
    "hit_installs": [{"layers": [0, 1, 2, 3], "tensor": t, "last_blocks": 128} for t in (0, 1)],
}
# Documents of 8k/16k/32k tokens 4:2:1 asked four times, as dry run 2's traffic.
LONG_DOCUMENTS = {
    "loop": "closed", "clients": 3, "schedule_seed": 3, "documents_per_client": 14,
    "asks_per_document": 4, "prefix_tokens": {"8192": 4, "16384": 2, "32768": 1},
    "question_tokens": 128, "answer_tokens": 64,
}


def state_caches(blocks=3):
    """Dry run 3's caches as shapes: no byte of them is ever touched."""
    state = np.broadcast_to(np.float32(0), (blocks, 8, 8256, 128))
    norm = np.broadcast_to(np.float32(0), (blocks, 8, 8256))
    return [(state, norm)] * 8


@pytest.mark.parametrize("serving,data_kib,pool_kib,units", [
    # Values of the unit's own size: the pool holds the data and nothing more.
    (LLAMA, 32 * 32, 32 * 32, 32),
    # 21 MiB of data in whole units of 64 KiB: nothing is rounded.
    (DRY_RUN_2, 6 * 512 + 9 * 2048, 6 * 512 + 9 * 2048, 336),
    # A layer's state takes 516 units and its normaliser 5 (4.03 of them):
    # 33,344 KiB against 33,282 of data, 0.19% over.
    (DRY_RUN_3, 8 * 33_282, 8 * (516 + 5) * 64, 8 * 521),
    # Ten values of the unit's own size: 160 KiB a block, 10,240 B a token.
    (DRY_RUN_4, 10 * 16, 10 * 16, 10),
], ids=["llama-kv", "dry-run-2", "dry-run-3", "dry-run-4"])
def test_pool_bytes_per_block(serving, data_kib, pool_kib, units):
    layout = store_layout(serving)
    assert layout.pool_bytes_per_block == pool_kib * KIB
    assert layout.pool_bytes_per_block == pool_bytes_per_block(layout.values_kib, layout.unit_kib)
    assert layout.pool_units_per_block == units
    assert data_kib * KIB == serving["kv_bytes_per_token"] * serving["block_tokens"]
    assert layout.pool_bytes_per_token == pool_kib * KIB / serving["block_tokens"]
    assert 1.0 <= pool_kib / data_kib < 1.002


def test_a_layer_of_dry_run_3_by_hand():
    assert pool_bytes_per_block([[1, 33_024], [1, 258]], 64) == (516 + 5) * 64 * KIB == 33_344 * KIB
    assert (33_024 + 258) == 33_282 and 33_344 / 33_282 - 1 < 0.002
    # The unit the harness used to give such a cache, had the server taken it
    # (32 MiB, the power of two under the state): 2 + 1 units a layer, 2.95x.
    assert pool_bytes_per_block([[1, 33_024], [1, 258]], 32_768) == 3 * 32_768 * KIB
    # Dry run 2 at the old coupling (the unit is the 2 MiB state): 1.42x.
    assert pool_bytes_per_block(DRY_RUN_2["store_values_kib"], 2048) == 30 * 1024 * KIB
    assert 30 * 1024 / (6 * 512 + 9 * 2048) == pytest.approx(1.4286, abs=1e-4)


def without(serving, *keys):
    return {k: v for k, v in serving.items() if k not in keys}


@pytest.mark.parametrize("serving,says", [
    # Today's coupling: the unit is the largest value, and 33024 is no power of two.
    (without(DRY_RUN_3, "store_unit_kib"), r"store_block_kib is 33024, which is no power of two"),
    (dict(DRY_RUN_3, store_unit_kib=65_536), r"store_unit_kib is 65536 beside a serving.store_block_kib of 33024"),
    (dict(DRY_RUN_3, store_unit_kib=96), r"store_unit_kib is 96 beside .* 33024: the unit is a power of two"),
    (dict(DRY_RUN_3, store_unit_kib=8), r"store_unit_kib is 8 beside .* at least 16"),
    (dict(DRY_RUN_3, store_unit_kib=64.0), r"store_unit_kib is 64.0 beside"),
    # Values of unlike sizes and no list of them.
    (without(DRY_RUN_3, "store_values_kib"), r"272646144 bytes a block, no whole number of values .* \(33024 KiB\)"),
    (dict(DRY_RUN_3, store_values_kib=[[8, 33_024], [8, 40_000]]), r"the largest KiB serving.store_block_kib \(33024\)"),
    (dict(DRY_RUN_3, store_values_kib=[[8, 33_024], [0, 258]]), r"a list of \[count, KiB\] pairs"),
    (dict(DRY_RUN_3, store_values_kib=[]), r"a list of \[count, KiB\] pairs"),
], ids=["no-unit", "unit-over-value", "unit-96", "unit-8", "unit-float", "no-list", "list-over-block",
        "zero-count", "empty-list"])
def test_a_file_that_cannot_start_a_server_stops_before_it(monkeypatch, serving, says):
    with pytest.raises(ValueError, match=says):
        store_layout(serving)
    # And ``execute`` stops there: no server is started, nothing is built.
    monkeypatch.setattr(run, "start_server", lambda *a: pytest.fail("the server was started"))
    args = argparse.Namespace(workload="made-up", seed=1, seconds=1.0, trace=0)
    plan = traffic._closed_plan("made-up", LONG_DOCUMENTS)
    with pytest.raises(ValueError, match=says):
        run.execute(args, {"name": "made-up", "chips": 1}, {"name": "made-up", "serving": serving}, plan, {})


class ServerStarted(Exception):
    pass


def test_dry_run_3_gets_a_unit_of_64_kib_and_a_pool_that_holds_its_checkpoints(monkeypatch):
    """From the harness alone: ``execute`` asks for a server at a 64 KiB
    unit, and every checkpoint the plan saves, at what it takes of the pool,
    stays under the share from which the server evicts."""
    asked = {}

    def start_server(pool, unit_kib):
        asked.update(pool_gib=pool, unit_kib=unit_kib)
        raise ServerStarted

    monkeypatch.setattr(run, "start_server", start_server)
    args = argparse.Namespace(workload="made-up", seed=1, seconds=1.0, trace=0)
    plan = traffic._closed_plan("made-up", LONG_DOCUMENTS)
    with pytest.raises(ServerStarted):
        run.execute(
            args, {"name": "made-up", "chips": 1},
            {"name": "made-up", "serving": DRY_RUN_3, "program": {"reference": "reference"}}, plan, {},
        )
    assert asked["unit_kib"] == 64
    # Whole blocks saved: each document's prefix once, and the block a
    # request's own question and answer complete, if any.
    bt = DRY_RUN_3["block_tokens"]
    docs = {r.doc: r.prefix_tokens // bt for r in plan.requests}
    own = sum((r.prompt_tokens + r.answer_tokens) // bt - r.prefix_tokens // bt for r in plan.requests)
    checkpoints = sum(docs.values()) + own
    assert checkpoints == 72 and own == 0
    held = checkpoints * store_layout(DRY_RUN_3).pool_bytes_per_block
    assert held / (asked["pool_gib"] * 2**30) < POOL_EVICTS_FROM
    assert asked["pool_gib"] == pool_gib(traffic.store_bytes(plan, 8 * 33_344 * KIB / bt)) == 29
    # At the old coupling's weight (three units of 32 MiB a layer) the same
    # pool would have been over the threshold: finding 2 of ISSUE 32.
    assert checkpoints * 8 * 3 * 32_768 * KIB / (asked["pool_gib"] * 2**30) > POOL_EVICTS_FROM


def test_dry_run_4_needs_no_unit_and_a_long_hit_fetches_its_windows_alone():
    layout = store_layout(DRY_RUN_4)
    assert (layout.unit_kib, layout.block_kib, layout.values_kib) == (16, 16, ((10, 16),))
    kv = np.broadcast_to(np.float16(0), (3, 16, 4, 128))  # as shapes: 16 KiB a block
    g = CacheGeometry.of([(kv, kv)] * 5, DRY_RUN_4["hit_installs"])
    g.check(DRY_RUN_4)
    assert g.block_nbytes == 10 * 16 * KIB and g.values_per_block == 10
    # A hit of a 32k + 128-token prompt, 2,056 blocks: the full layer's every
    # block and the sliding layers' last 128, 80.25 MiB where all would be 321.
    assert g.fetched_values(2056) == 2 * (2056 + 4 * 128) == 5136
    assert g.installed_nbytes(2056) == 5136 * 16 * KIB == 84_148_224
    assert 2056 * g.block_nbytes == 336_855_040 and g.installed_nbytes(2056) / 2**20 == 80.25
    assert g.installed_blocks(2056) == [[range(1928, 2056)] * 2] * 4 + [[range(0, 2056)] * 2]
    # A hit shorter than the window installs all of every tensor.
    assert g.fetched_values(100) == 1000 and g.installed_nbytes(100) == 100 * g.block_nbytes


def test_the_list_is_held_to_the_caches_the_program_built():
    g = CacheGeometry.of(state_caches(), DRY_RUN_3["hit_installs"])
    g.check(DRY_RUN_3)
    assert g.block_nbytes == 8 * 34_080_768 and g.values_per_block == 16
    assert g.largest_value_nbytes == 33_816_576 == 33_024 * KIB
    # A hit of n blocks fetches and installs the last block's state and normaliser alone.
    assert g.fetched_values(4) == 16 and g.installed_nbytes(4) == g.block_nbytes
    assert g.installed_blocks(4) == [[range(3, 4), range(3, 4)]] * 8
    wrong = dict(DRY_RUN_3, store_values_kib=[[8, 33_024], [4, 258], [4, 128]])
    with pytest.raises(ValueError, match=(
        r"store_values_kib is \[\[4, 128\], \[4, 258\], \[8, 33024\]\] .* puts \[\[8, 258\], \[8, 33024\]\] in the store"
    )):
        g.check(wrong)


NEW_KEYS = {"store_unit_kib", "store_values_kib"}


def serving_of(path):
    return run.load_json(os.path.join(run.REPO, path))["serving"]


def accepted():
    """Every configuration WITHOUT the keys PR 32 brought, under every
    traffic file: chosen by what the file holds, not by where it stands in
    ``BENCHMARK.json``, so a configuration that states its unit (every one
    with a state or a latent since PR 41) is no case and trips nothing."""
    bench = run.load_json(os.path.join(run.REPO, "BENCHMARK.json"))
    files = {c["name"]: c["file"] for c in bench["configs"] if not NEW_KEYS & set(serving_of(c["file"]))}
    plans = sorted(f[: -len(".json")] for f in os.listdir(os.path.join(run.HERE, "traffic")))
    return [pytest.param(files[c], t, id=f"{c}-{t}") for c in sorted(files) for t in plans]


def test_the_two_files_of_pr_31_are_among_the_cases():
    assert {"mistral-7b-v0.3-reuse-sessions-2k-8k", "deepseek-llm-7b-reuse-sessions-1k-2k"} <= {
        p.id for p in accepted()
    }


@pytest.mark.parametrize("path,traffic_name", accepted())
def test_an_accepted_file_gets_the_server_the_parent_gave_it(path, traffic_name):
    """No such file has the new keys, and under every traffic file
    the unit and the pool are what ``run.execute`` computed at PR 31
    (``2e7dce5``), written out here as it stood there."""
    serving = serving_of(path)
    plan = traffic.build_plan(traffic_name)
    need = traffic.store_bytes(plan, serving["kv_bytes_per_token"])
    parent = {"pool_gib": max(2, int(need / 0.7 / 2**30) + 2), "block_kib": max(16, int(serving["store_block_kib"]))}
    layout = store_layout(serving)
    assert layout.pool_bytes_per_token == serving["kv_bytes_per_token"]
    assert traffic.store_bytes(plan, layout.pool_bytes_per_token) == need
    assert {"pool_gib": pool_gib(need), "block_kib": layout.block_kib} == parent
    assert layout.unit_kib == layout.block_kib == serving["store_block_kib"]
    assert layout.pool_units_per_block * layout.unit_kib * KIB == serving["kv_bytes_per_token"] * serving["block_tokens"]


SERVERS = {  # by cell: the ``server`` of its result lines on the chip (the last five: my chip runs, PR 54)
    "mistral7b-prefix-reuse": {"block_kib": 32, "pool_gib": 17, "unit_kib": 32, "pool_units_per_block": 32},
    "deepseek7b-prefix-reuse": {"block_kib": 128, "pool_gib": 18, "unit_kib": 128, "pool_units_per_block": 30},
    "mistral7b-unshared-chat": {"block_kib": 32, "pool_gib": 4, "unit_kib": 32, "pool_units_per_block": 32},
    "trinity-mini-long-prefix-reuse": {"block_kib": 16, "pool_gib": 18, "unit_kib": 16, "pool_units_per_block": 10},
    "kimi-linear-long-prefix-reuse": {"block_kib": 2048, "pool_gib": 18, "unit_kib": 16, "pool_units_per_block": 605},
    "falcon-h1-long-prefix-reuse": {"block_kib": 4096, "pool_gib": 24, "unit_kib": 16, "pool_units_per_block": 1544},
    "granite-h-small-long-prefix-reuse": {"block_kib": 4096, "pool_gib": 22, "unit_kib": 16, "pool_units_per_block": 2856},
    "mellum2-completion-prefix-reuse": {"block_kib": 16, "pool_gib": 16, "unit_kib": 16, "pool_units_per_block": 16},
}


@pytest.mark.parametrize("cell", sorted(SERVERS))
def test_a_named_cells_server_line(cell):
    """A cell that is not named here is no case: the next cell adds its line
    or leaves it out, and trips nothing either way."""
    bench = run.load_json(os.path.join(run.REPO, "BENCHMARK.json"))
    w, config = run.cell_of(bench, cell)
    layout = store_layout(config["serving"])
    pool = pool_gib(traffic.store_bytes(traffic.build_plan(w["traffic"]), layout.pool_bytes_per_token))
    assert {
        "block_kib": layout.block_kib, "pool_gib": pool, "unit_kib": layout.unit_kib,
        "pool_units_per_block": layout.pool_units_per_block,
    } == SERVERS[cell]


def test_a_live_server_at_a_64_kib_unit_holds_a_state_and_its_normaliser_in_521_units():
    """The real server (its own process, no JAX, as ``run.start_server``
    starts it for a cell) at a 64 KiB unit: a value of 33,024 KiB and one of
    258 KiB go in through ``InfinityConnection``, come back byte-identical,
    and take (516 + 5) x 64 KiB of the pool."""
    import infinistore_tpu as its

    run.build_native_if_missing()
    server = run.start_server(2, 64)
    conn = None
    try:
        conn = its.InfinityConnection(its.ClientConfig(
            host_addr="127.0.0.1", service_port=server["service_port"], log_level="error",
        ))
        conn.connect()
        assert conn.get_stats()["used_bytes"] == 0
        rng = np.random.default_rng(32)
        sizes = {"state": 33_024 * KIB, "normaliser": 258 * KIB}
        src = {k: rng.integers(0, 256, size=n, dtype=np.uint8) for k, n in sizes.items()}
        for key, buf in src.items():
            conn.register_mr(buf)
            conn.write_cache([(key, 0)], buf.nbytes, buf.ctypes.data)
        stats = conn.get_stats()
        assert stats["kvmap_len"] == 2 and stats["used_bytes"] == (516 + 5) * 64 * KIB
        assert stats["usage"] == pytest.approx(521 * 64 * KIB / 2**31, abs=1e-5)  # six digits on the wire
        for key, buf in src.items():
            back = np.zeros_like(buf)
            conn.register_mr(back)
            conn.read_cache([(key, 0)], back.nbytes, back.ctypes.data)
            assert np.array_equal(back, buf), key
    finally:
        if conn is not None:
            conn.close()
        run.stop_server(server)
