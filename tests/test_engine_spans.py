"""The engine's own spans (docs/observability.md, "Inside the engine"): a
request's phases from alloc to each emitted token under one trace id, the
gate's and the pool's waits, the always-on emit stamps, and the clock marks
that lay all of it over a ``jax.profiler`` trace."""

import asyncio
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu import tracing
from infinistore_tpu.connector import KVConnector
from infinistore_tpu.engine import (
    BlockPool,
    ContinuousBatchingHarness,
    DeviceGate,
    EngineKVAdapter,
)
from infinistore_tpu.models import LlamaConfig, init_params
from infinistore_tpu.tpu.staging import HostStagingPool

CFG = LlamaConfig(
    vocab=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128,
    block_tokens=8, dtype=jnp.float32,
)
NUM_BLOCKS, MAX_REQ_BLOCKS, GEN = 32, 4, 5
# The request's own phases, children of `engine_request`. A miss has no
# install; a full hit computes and saves nothing before it generates.
MISS_PHASES = {"pool_alloc", "gate_wait", "compute", "save_snapshot", "save_io", "generate"}
HIT_PHASES = {"pool_alloc", "gate_wait", "install", "generate"}


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture()
def traced():
    rec = tracing.configure(enabled=True, capacity=4096, slow_op_us=0)
    rec.clear()
    yield rec
    tracing.configure(enabled=False)


def _harness(conn, params, model_id):
    kvc = KVConnector(conn, CFG.kv_spec(NUM_BLOCKS), model_id, max_blocks=MAX_REQ_BLOCKS)
    return ContinuousBatchingHarness(
        EngineKVAdapter(kvc), params, CFG, NUM_BLOCKS, MAX_REQ_BLOCKS
    )


def _prompt(seed, blocks=3):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab, size=blocks * CFG.block_tokens).tolist()


def _miss_then_hit(h, prompt):
    async def drive():
        miss = await h.run_request(prompt, gen_tokens=GEN)
        hit = await h.run_request(prompt, gen_tokens=GEN)
        return miss, hit

    return asyncio.run(asyncio.wait_for(drive(), 60))


@pytest.mark.parametrize("which", ["miss", "hit"])
def test_request_span_tree(conn, params, traced, which):
    h = _harness(conn, params, f"spans-{which}-{conn.shm_active}")
    miss, hit = _miss_then_hit(h, _prompt(1))
    assert miss.loaded_blocks == 0 and hit.loaded_blocks == 3 and hit.computed_blocks == 0
    stats = miss if which == "miss" else hit
    assert stats.trace_id and miss.trace_id != hit.trace_id
    spans = [s for s in traced.snapshot() if s["trace_id"] == stats.trace_id]
    (root,) = [s for s in spans if s["name"] == "engine_request"]
    assert root["parent_id"] == 0 and root["attrs"]["gen_tokens"] == GEN
    by_id = {s["span_id"]: s for s in spans}
    for s in spans:
        assert s["status"] == "ok", s
        assert root["start_us"] <= s["start_us"] <= s["end_us"] <= root["end_us"], s
        if s is not root:
            assert s["parent_id"] in by_id, s  # one tree: every parent is in the trace
    children = {s["name"] for s in spans if s["parent_id"] == root["span_id"]}
    want = MISS_PHASES if which == "miss" else HIT_PHASES
    assert want <= children, (which, children)
    assert not ({"install"} if which == "miss" else {"compute", "save_io"}) & children

    (gen,) = [s for s in spans if s["name"] == "generate"]
    stages = [name for name, _ in gen["stages"]]
    assert stages == ["wave_enqueue", "wave_result", "token"] * GEN
    stamps = [t for _, t in gen["stages"]]
    assert stamps == sorted(stamps)
    assert len([c for c in gen["attrs"]["device_calls"] if c[0] == "its.readback"]) == GEN

    if which == "miss":
        (comp,) = [s for s in spans if s["name"] == "compute"]
        assert comp["attrs"]["kind"] == "prefill_full" and comp["attrs"]["waits_for_device"]
        assert comp["attrs"]["tokens"] == 3 * CFG.block_tokens
        (call,) = comp["attrs"]["device_calls"]
        assert call[0] == "its.compute" and comp["start_us"] <= call[1] <= call[2] <= comp["end_us"]
        snaps = [s for s in spans if s["name"] == "save_snapshot"]
        assert [s["attrs"]["before_first_token"] for s in snaps] == [True]
        (wait,) = [s for s in spans if s["parent_id"] == snaps[0]["span_id"]]
        assert wait["name"] == "gate_wait" and wait["attrs"]["mode"] == "shared"
        (io,) = [s for s in spans if s["name"] == "save_io"]
        # The store's write ops stamp and annotate the span they run under
        # (one op's `blocks` among them); the save's own attrs are set last.
        assert io["attrs"]["blocks"] == 3 and io["attrs"]["before_first_token"] is False
        assert io["attrs"]["overlaps_generate"] is True
        assert io["attrs"]["op"] == "write_cache" and "submit" in [n for n, _ in io["stages"]]
        # The snapshot precedes the first wave; the write runs beside the
        # generation, a child of the request like it.
        assert io["start_us"] >= snaps[0]["end_us"] and gen["start_us"] >= snaps[0]["end_us"]
        assert gen["start_us"] < io["end_us"] and io["parent_id"] == root["span_id"]
        assert miss.save_overlap_us > 0 and hit.save_overlap_us == hit.save_tail_us == 0.0
    else:
        (inst,) = [s for s in spans if s["name"] == "install"]
        assert inst["attrs"]["blocks"] == 3 and "device_calls" not in inst["attrs"]
        # The call into the device is the upload's, a child of the install.
        uploads = [s for s in spans if s["name"] == "install_upload"]
        assert uploads and all(u["parent_id"] == inst["span_id"] for u in uploads)
        assert all([c[0] for c in u["attrs"]["device_calls"]] == ["its.install"] for u in uploads)
        modes = {s["attrs"]["mode"] for s in spans if s["name"] == "gate_wait"}
        assert "expedite" in modes

    # The waves these rounds rode: traces of their own, five stages each.
    waves = [s for s in traced.snapshot() if s["name"] == "wave"]
    assert len(waves) >= 2 * GEN and all(w["parent_id"] == 0 for w in waves)
    assert not {w["trace_id"] for w in waves} & {miss.trace_id, hit.trace_id}
    for w in waves:
        assert [n for n, _ in w["stages"]] == ["taken", "assembled", "gate", "dispatched", "resolved"]
        assert w["attrs"]["entries"] == 1 and w["attrs"]["rows"] >= w["attrs"]["real_rows"] == 1
        (call,) = w["attrs"]["device_calls"]
        gate, dispatched = w["stages"][2][1], w["stages"][3][1]
        assert call[0] == "its.wave_dispatch" and gate <= call[1] <= call[2] <= dispatched
    wave_ids = {w["span_id"] for w in waves}
    wave_waits = [
        s for s in traced.snapshot() if s["name"] == "gate_wait" and s["parent_id"] in wave_ids
    ]
    assert len(wave_waits) == len(waves)  # the wave's wait is its own child, not a request's


def test_prompt_write_ends_before_the_answer_save_starts(conn, params, traced):
    h = _harness(conn, params, f"spans-answer-{conn.shm_active}")
    gen = CFG.block_tokens  # the answer fills one block: a second save
    stats = asyncio.run(asyncio.wait_for(h.run_request(_prompt(3), gen_tokens=gen), 60))
    spans = [s for s in traced.snapshot() if s["trace_id"] == stats.trace_id]
    prompt_snap, answer_snap = [s for s in spans if s["name"] == "save_snapshot"]
    prompt_io, answer_io = [s for s in spans if s["name"] == "save_io"]
    (generate,) = [s for s in spans if s["name"] == "generate"]
    assert [s["attrs"]["before_first_token"] for s in (prompt_snap, answer_snap)] == [True, False]
    assert [s["attrs"]["blocks"] for s in (prompt_io, answer_io)] == [3, 1]
    assert [s["attrs"]["overlaps_generate"] for s in (prompt_io, answer_io)] == [True, False]
    assert not prompt_io["attrs"]["before_first_token"] and not answer_io["attrs"]["before_first_token"]
    assert prompt_snap["end_us"] <= generate["start_us"] < prompt_io["end_us"]
    assert prompt_io["end_us"] <= answer_snap["start_us"] and generate["end_us"] <= answer_snap["start_us"]
    assert answer_snap["end_us"] <= answer_io["start_us"]
    assert h.metrics()["saves_overlapped"] == 1


def test_tracing_off_records_nothing_and_still_stamps_emits(conn, params, monkeypatch):
    tracing.configure(enabled=False)
    rec = tracing.configure(capacity=64)  # a fresh, empty recorder; still off
    made = []

    class NoSpan(tracing.Span):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    def no_annotation(*a, **kw):
        made.append(a)
        raise AssertionError("TraceAnnotation made with tracing off")

    monkeypatch.setattr(tracing, "Span", NoSpan)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", no_annotation)
    h = _harness(conn, params, f"spans-off-{conn.shm_active}")
    t_before = time.perf_counter()
    miss, hit = _miss_then_hit(h, _prompt(2))
    assert not made and rec.recorded == 0 and rec.snapshot() == []
    for stats in (miss, hit):
        assert stats.trace_id == 0 and len(stats.token_emit_s) == GEN == len(stats.generated)
        assert all(a < b for a, b in zip(stats.token_emit_s, stats.token_emit_s[1:]))
        assert t_before < stats.token_emit_s[0] and stats.token_emit_s[-1] < time.perf_counter()
    # ttft_us precedes the first read-back; token_emit_s[0] follows it.
    assert miss.ttft_us > 0 and hit.ttft_us > 0
    # The hop's counters need no recorder: the miss saved, the hit read and
    # installed, and all eight moved.
    moved = h.adapter.connector.get_stats()
    assert all(moved[key] > 0 for key in HOP_COUNTERS), moved
    assert moved["hit_read_bytes"] == moved["install_upload_bytes"] == _hit_bytes(hit)
    assert moved["save_d2h_bytes"] == _hit_bytes(hit) and moved["hit_reads_in_flight"] == 0
    # The put's ledger (PR 42): every saved byte acknowledged over some busy
    # time, and beside it the connection's own: the two-phase shm put copied
    # those bytes itself, through the pool file's descriptor (PR 44: nothing
    # walks the pool any more), and the socket path copied none.
    assert moved["save_put_bytes"] == moved["save_d2h_bytes"] and moved["save_put_busy_us"] > 0
    assert moved["save_puts_in_flight"] == 0
    assert moved["put_copy_bytes"] == (moved["save_put_bytes"] if conn.shm_active else 0)
    assert moved["put_file_bytes"] == moved["put_touched_bytes"] == moved["put_copy_bytes"]
    assert (moved["put_file_calls"] > 0) == conn.shm_active and moved["pretouch_bytes"] == 0


# The connector's ledger of the hop (docs/observability.md), always on.
HOP_COUNTERS = (
    "hit_read_bytes", "hit_read_busy_us", "install_upload_bytes", "install_upload_us",
    "install_layers", "install_dispatches", "save_d2h_bytes", "save_d2h_wait_us",
)


def _hit_bytes(stats):
    """What a hit's prefetch read: its store values (a K or a V of one block
    of one layer: 8 tokens x 2 KV heads x 16 x float32 under this file's
    configurations)."""
    return stats.prefetched_blocks * 8 * 2 * 16 * 4


# -- the store's hop, cut where the work happens ------------------------------

CFG3 = LlamaConfig(
    vocab=128, dim=64, n_layers=3, n_heads=4, n_kv_heads=2, ffn_dim=128,
    block_tokens=8, dtype=jnp.float32,
)
READ_DELAY_S = 0.1


class SlowReads:
    """The connection with every batched read held back, so that whatever
    waits for the store has something to wait for."""

    def __init__(self, conn, delay_s=READ_DELAY_S):
        self._conn, self._delay_s = conn, delay_s

    def __getattr__(self, name):
        return getattr(self._conn, name)

    async def read_cache_async(self, *args, **kwargs):
        await asyncio.sleep(self._delay_s)
        return await self._conn.read_cache_async(*args, **kwargs)


@pytest.fixture(scope="module")
def params3():
    return init_params(CFG3, jax.random.PRNGKey(1))


def _harness3(conn, params3, model_id, regions=None):
    """Three layers behind a slow store; ``regions=2`` leaves the prefetch
    arena room for two staging regions of a 3-block hit, so layer 2 reads
    into the region layer 0's install hands on."""
    spec = CFG3.kv_spec(NUM_BLOCKS)
    kvc = KVConnector(SlowReads(conn), spec, model_id, max_blocks=MAX_REQ_BLOCKS)
    if regions is not None:
        kvc._prefetch_pool = HostStagingPool(
            regions * 2 * 3 * spec.block_nbytes, spec.block_nbytes, conn=kvc.conn
        )
    return ContinuousBatchingHarness(
        EngineKVAdapter(kvc), params3, CFG3, NUM_BLOCKS, MAX_REQ_BLOCKS
    )


def _stamp(span, name):
    (t,) = [t for stage, t in span["stages"] if stage == name]
    return t


def _trace(rec, stats):
    spans = [s for s in rec.snapshot() if s["trace_id"] == stats.trace_id]
    (root,) = [s for s in spans if s["name"] == "engine_request"]
    return spans, root


def test_hit_over_two_regions_is_cut_where_it_waits(conn, params3, traced):
    h = _harness3(conn, params3, f"hop-2r-{conn.shm_active}", regions=2)
    t_before = time.perf_counter()
    miss, hit = _miss_then_hit(h, _prompt(11))
    wall_us = (time.perf_counter() - t_before) * 1e6
    assert hit.loaded_blocks == 3 and hit.computed_blocks == 0
    spans, root = _trace(traced, hit)
    assert all(s["status"] == "ok" for s in spans)

    # Admission in order: probe, alloc, the wait for the store, gate, install;
    # then the tail: last token on the host, every save acknowledged.
    tail = ["generated", "acknowledged"]
    order = ["enqueue", "fetch_start", "alloc_done", "primed", "install"] + tail
    assert [n for n, _ in root["stages"]] == order
    assert [t for _, t in root["stages"]] == sorted(t for _, t in root["stages"])
    _, miss_root = _trace(traced, miss)
    assert [n for n, _ in miss_root["stages"]] == ["enqueue", "alloc_done"] + tail

    # One fetch_layer a layer, under the request; the store's op stamps the
    # layer that asked, and the request's own span carries none of it.
    layers = sorted(
        (s for s in spans if s["name"] == "fetch_layer"), key=lambda s: s["attrs"]["layer"]
    )
    assert [s["attrs"]["layer"] for s in layers] == [0, 1, 2]
    assert [s["attrs"]["region"] for s in layers] == [0, 1, 0]
    value = CFG3.kv_spec(NUM_BLOCKS).block_nbytes
    for s in layers:
        assert s["parent_id"] == root["span_id"]
        assert s["attrs"]["values"] == 6 and s["attrs"]["bytes"] == 6 * value
        assert _stamp(s, "queued") <= _stamp(s, "region_free") <= _stamp(s, "landed")
        assert _stamp(s, "landed") - _stamp(s, "region_free") >= READ_DELAY_S * 1e6 * 0.9
        assert "coalesce" in [n for n, _ in s["stages"]]
    submitted = [s for s in layers if "submit" in [n for n, _ in s["stages"]]]
    assert submitted and all(s["attrs"]["op"] == "read_cache" for s in submitted)
    assert not {"submit", "coalesce", "completion_ring"} & {n for n, _ in root["stages"]}
    assert "op" not in root["attrs"]

    # The install: an upload a RUN of staged layers (layers 0 and 1, each in
    # a region of its own, go up in one executor call), and the gate waited
    # for the network exactly where a layer had not landed (layer 2, behind
    # layer 0's region: the run the wrap forces).
    (inst,) = [s for s in spans if s["name"] == "install"]
    uploads = sorted(
        (s for s in spans if s["name"] == "install_upload"), key=lambda s: s["attrs"]["layer"]
    )
    assert [(u["attrs"]["layer"], u["attrs"]["layers"]) for u in uploads] == [(0, 2), (2, 1)]
    for u in uploads:
        assert u["parent_id"] == inst["span_id"]
        assert u["attrs"]["bytes"] == u["attrs"]["layers"] * 6 * value
        assert u["start_us"] <= _stamp(u, "started") <= _stamp(u, "h2d") <= u["end_us"]
        ((name, t0, t1),) = u["attrs"]["device_calls"]
        assert name == "its.install" and _stamp(u, "started") <= t0 <= t1 <= u["end_us"]
    assert _stamp(layers[2], "region_free") >= uploads[0]["end_us"]
    assert _stamp(layers[2], "region_free") - _stamp(layers[2], "queued") >= READ_DELAY_S * 1e6 * 0.9
    for early in layers[:2]:  # a region of their own from the start
        assert _stamp(early, "region_free") - _stamp(early, "queued") < READ_DELAY_S * 1e6 * 0.5
    (wait,) = [s for s in spans if s["name"] == "install_staged_wait"]
    assert wait["parent_id"] == inst["span_id"] and wait["attrs"] == {"layer": 2}
    assert wait["duration_us"] >= READ_DELAY_S * 1e6 * 0.25
    assert uploads[0]["end_us"] <= wait["start_us"] <= wait["end_us"] <= uploads[1]["start_us"]
    assert _stamp(root, "primed") >= _stamp(layers[1], "landed")

    # The counters, beside the spans: what was read is what the hit fetched,
    # the reads' union is inside the wall time, and the dispatches and their
    # layers are the spans'.
    c = h.adapter.connector.hit_counters
    assert c["hit_read_bytes"] == _hit_bytes(hit) == 18 * value
    assert 2 * READ_DELAY_S * 1e6 * 0.9 <= c["hit_read_busy_us"] <= wall_us
    assert c["install_upload_bytes"] == 18 * value and c["hit_reads_in_flight"] == 0
    assert 0 < c["install_upload_us"] <= sum(u["duration_us"] for u in uploads) + 1000
    assert (c["install_layers"], c["install_dispatches"]) == (3, 2)


def test_no_staged_wait_where_every_layer_had_landed(conn, params3, traced):
    h = _harness3(conn, params3, f"hop-one-{conn.shm_active}")  # a region a layer
    _, hit = _miss_then_hit(h, _prompt(12))
    spans, root = _trace(traced, hit)
    fetches = sorted(
        (s for s in spans if s["name"] == "fetch_layer"), key=lambda s: s["attrs"]["layer"]
    )
    assert [s["attrs"]["region"] for s in fetches] == [0, 1, 2]
    assert not [s for s in spans if s["name"] == "install_staged_wait"]
    (inst,) = [s for s in spans if s["name"] == "install"]
    # Every layer staged before the gate (`primed` waits for the last): ONE
    # run, one executor call, one span.
    assert _stamp(root, "primed") >= _stamp(fetches[-1], "landed")
    (upload,) = [s for s in spans if s["name"] == "install_upload"]
    assert upload["parent_id"] == inst["span_id"]
    assert (upload["attrs"]["layer"], upload["attrs"]["layers"]) == (0, 3)
    c = h.adapter.connector.hit_counters
    assert upload["attrs"]["bytes"] == c["install_upload_bytes"]
    assert (c["install_layers"], c["install_dispatches"]) == (3, 1)
    assert [n for n, _ in upload["stages"]] == ["started", "h2d"]
    assert [c[0] for c in upload["attrs"]["device_calls"]] == ["its.install"]


@pytest.mark.parametrize("which", ["miss", "hit"])
def test_seven_parts_account_for_prefix_ready(conn, params3, traced, which):
    """What `prefix_ready_accounted_pct` sums (benchmarks/layer_metrics): the
    probe, the alloc, the wait for the store, the expedited gate's wait, the
    install's hold, the compute's gate wait and the compute."""
    h = _harness3(conn, params3, f"hop-parts-{which}-{conn.shm_active}", regions=2)
    prefill = h._prefill_full

    def slow_prefill(*args):
        # A miss is its prefill: give it a length beside which its probe's
        # executor hop (enqueue -> pool_alloc: under no part, a millisecond
        # or two unless the machine is loaded) stays small.
        time.sleep(4 * READ_DELAY_S)
        return prefill(*args)

    h._prefill_full = slow_prefill
    miss, hit = _miss_then_hit(h, _prompt(13))
    stats = miss if which == "miss" else hit
    spans, root = _trace(traced, stats)

    def durations(name, **attrs):
        return sum(
            s["duration_us"] for s in spans
            if s["name"] == name and all(s["attrs"].get(k) == v for k, v in attrs.items())
        )

    def between(a, b):
        stamps = dict(root["stages"])
        return stamps[b] - stamps[a] if a in stamps and b in stamps else 0

    parts = {
        "hit_probe": between("enqueue", "fetch_start"),
        "alloc_wait": durations("pool_alloc"),
        "hit_store_wait": between("alloc_done", "primed"),
        "hit_gate_wait": durations("gate_wait", mode="expedite"),
        "install_hold": durations("install"),
        "ready_compute_gate_wait": durations("gate_wait", mode="exclusive"),
        "ready_compute": durations("compute"),
    }
    if which == "miss":
        assert not parts["hit_probe"] and not parts["hit_store_wait"] and not parts["install_hold"]
        assert parts["ready_compute"] >= 4 * READ_DELAY_S * 1e6
    else:
        assert parts["hit_store_wait"] and parts["install_hold"] and not parts["ready_compute"]
    assert 0.9 <= sum(parts.values()) / stats.prefix_ready_us <= 1.001, (parts, stats.prefix_ready_us)


def test_save_layers_and_their_d2h_waits(conn, params3, traced):
    h = _harness3(conn, params3, f"hop-save-{conn.shm_active}")
    miss = asyncio.run(asyncio.wait_for(h.run_request(_prompt(14), gen_tokens=GEN), 60))
    spans, _ = _trace(traced, miss)
    (io,) = [s for s in spans if s["name"] == "save_io"]
    layers = [s for s in spans if s["name"] == "save_layer"]
    value = CFG3.kv_spec(NUM_BLOCKS).block_nbytes
    assert all(s["parent_id"] == io["span_id"] and s["status"] == "ok" for s in layers)
    # Layer 0 holds the sentinel keys: gathered last, acknowledged last.
    assert [s["attrs"]["layer"] for s in sorted(layers, key=lambda s: s["start_us"])] == [1, 2, 0]
    assert max(layers, key=lambda s: s["end_us"])["attrs"]["layer"] == 0
    deeper_acked = max(s["end_us"] for s in layers if s["attrs"]["layer"])
    for s in layers:
        assert s["attrs"]["bytes"] == 6 * value and io["start_us"] <= s["start_us"] <= s["end_us"] <= io["end_us"]
        (wait,) = [w for w in spans if w["parent_id"] == s["span_id"]]
        assert wait["name"] == "save_d2h_wait" and s["start_us"] <= wait["start_us"] <= wait["end_us"] <= s["end_us"]
        ((name, t0, t1),) = wait["attrs"]["device_calls"]
        assert name == "its.save_d2h" and wait["start_us"] <= t0 <= t1 <= wait["end_us"]
        if s["attrs"]["layer"] == 0:  # the barrier: every deeper layer committed first
            assert wait["start_us"] >= deeper_acked
    # The write ops still stamp the save_io they run under (test_request_span_tree).
    assert io["attrs"]["op"] == "write_cache"
    c = h.adapter.connector.hit_counters
    assert c["save_d2h_bytes"] == 18 * value
    waits = sum(w["duration_us"] for w in spans if w["name"] == "save_d2h_wait")
    assert 0 < c["save_d2h_wait_us"] <= waits + 1000


def test_gate_wait_spans_under_contention(traced):
    gate = DeviceGate()
    hold_s = 0.05

    async def drive():
        acquired = asyncio.Event()

        async def writer():
            async with gate.exclusive(holder="prefill"):
                acquired.set()
                await asyncio.sleep(hold_s)

        async def reader():
            await acquired.wait()
            with tracing.trace_op("reader") as span:
                async with gate.shared(holder="snapshot"):
                    pass
            return span

        async def installer():
            await acquired.wait()
            async with gate.exclusive(holder="install", expedite=True):
                pass

        _, span, _ = await asyncio.gather(writer(), reader(), installer())
        return span

    reader_span = asyncio.run(asyncio.wait_for(drive(), 10))
    waits = {s["attrs"]["mode"]: s for s in traced.snapshot() if s["name"] == "gate_wait"}
    assert set(waits) == {"exclusive", "expedite", "shared"}
    # Both waiters sat behind the held writer for (nearly) its whole hold.
    assert waits["shared"]["duration_us"] >= hold_s * 1e6 * 0.8
    assert waits["expedite"]["duration_us"] >= hold_s * 1e6 * 0.8
    assert waits["exclusive"]["duration_us"] < hold_s * 1e6 * 0.5
    assert waits["shared"]["parent_id"] == reader_span.span_id
    # The writer asked from inside no span: its wait is a root.
    assert waits["exclusive"]["parent_id"] == 0


def test_gate_makes_no_span_with_tracing_off(monkeypatch):
    tracing.configure(enabled=False)

    class NoSpan:
        def __init__(self, *a, **kw):
            raise AssertionError("Span built with tracing off")

    monkeypatch.setattr(tracing, "Span", NoSpan)
    gate = DeviceGate()
    order = []

    async def drive():
        async def writer():
            async with gate.exclusive(holder="prefill"):
                order.append("writer in")
                await asyncio.sleep(0.01)
                order.append("writer out")

        async def reader():
            await asyncio.sleep(0)
            async with gate.shared(holder="snapshot"):
                order.append("reader in")

        await asyncio.gather(writer(), reader())

    asyncio.run(asyncio.wait_for(drive(), 10))
    assert order == ["writer in", "writer out", "reader in"]


def test_pool_alloc_span_records_the_wait_on_an_empty_pool(traced):
    pool = BlockPool(4)
    wait_s = 0.05

    async def drive():
        held = await pool.alloc(4)  # the pool is now empty

        async def release():
            await asyncio.sleep(wait_s)
            await pool.free(held)

        got, _ = await asyncio.gather(pool.alloc(3), release())
        return got

    got = asyncio.run(asyncio.wait_for(drive(), 10))
    assert len(got) == 3
    first, second = [s for s in traced.snapshot() if s["name"] == "pool_alloc"]
    assert first["attrs"] == {"blocks": 4, "free_at_entry": 4}
    assert first["duration_us"] < wait_s * 1e6 * 0.5
    assert second["attrs"] == {"blocks": 3, "free_at_entry": 0}
    assert second["duration_us"] >= wait_s * 1e6 * 0.9


def test_clock_mark_roundtrips_and_offset_places_a_span(monkeypatch):
    names = []

    class Annotation:
        def __init__(self, name):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    before = time.monotonic_ns()
    first = tracing.profile_clock_mark()
    second = tracing.profile_clock_mark()
    assert before <= first <= second <= time.monotonic_ns()
    assert names == [f"its.clock:{first}", f"its.clock:{second}"]
    assert [tracing.clock_mark_ns(n) for n in names] == [first, second]
    assert tracing.clock_mark_ns("its.readback") is None
    assert tracing.clock_mark_ns("its.clock:soon") is None
    # A profile whose clock runs 5 s ahead of CLOCK_MONOTONIC and gains
    # 40 ns between the two marks.
    ahead = 5_000_000_000
    marks = [(first + ahead, names[0]), (second + ahead + 40, names[1]), (123.0, "PjitFunction")]
    offset_ns, drift_ns = tracing.profile_clock_offset(marks)
    assert offset_ns == ahead + 20 and drift_ns == 40
    assert tracing.profile_clock_offset([(1.0, "np.asarray")]) is None
    span_start_us = first // 1000 + 250
    placed = tracing.to_profile_ns(span_start_us, offset_ns)
    assert placed == span_start_us * 1000 + ahead + 20


def test_tracing_imports_without_jax():
    code = (
        "import sys, importlib.util as u\n"
        "spec = u.spec_from_file_location('its_tracing', sys.argv[1])\n"
        "m = u.module_from_spec(spec); spec.loader.exec_module(m)\n"
        "m.configure(enabled=True)\n"
        "with m.trace_op('op', stage='enqueue') as s:\n"
        "    with m.device_call('its.compute', None):\n"
        "        pass\n"
        "assert m.recorder().recorded == 1\n"
        "assert not [n for n in sys.modules if n == 'jax' or n.startswith('jax.')], 'jax imported'\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code, tracing.__file__], capture_output=True, text=True
    )
    assert out.returncode == 0, out.stderr
