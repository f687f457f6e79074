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
        assert inst["attrs"]["blocks"] == 3
        assert [c[0] for c in inst["attrs"]["device_calls"]] == ["its.install"] * len(
            inst["attrs"]["device_calls"]
        )
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


def test_gate_wait_spans_under_contention(traced):
    gate = DeviceGate()
    hold_s = 0.05

    async def drive():
        acquired = asyncio.Event()

        async def writer():
            async with gate.exclusive():
                acquired.set()
                await asyncio.sleep(hold_s)

        async def reader():
            await acquired.wait()
            with tracing.trace_op("reader") as span:
                async with gate.shared():
                    pass
            return span

        async def installer():
            await acquired.wait()
            async with gate.exclusive(expedite=True):
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
            async with gate.exclusive():
                order.append("writer in")
                await asyncio.sleep(0.01)
                order.append("writer out")

        async def reader():
            await asyncio.sleep(0)
            async with gate.shared():
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
