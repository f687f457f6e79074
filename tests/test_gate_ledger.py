"""The device gate's ledger (``engine.DeviceGate``): who held the gate for how
long, every wait cut by the kind of holder it stood behind, the keys it puts
into ``harness.metrics()``, what the ``gate_wait`` span says of one wait, and
the ``no_request_live`` span the harness records between requests.

The gate-only tests run on a clock the test advances itself, so every
assertion is exact and none compares against the machine's speed."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu import engine, tracing
from infinistore_tpu.connector import KVConnector
from infinistore_tpu.engine import (
    GATE_FREE,
    GATE_HOLDERS,
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
BEHIND = GATE_HOLDERS + (GATE_FREE,)


class Clock:
    """The gate's clock in the test's hand (whole microseconds)."""

    def __init__(self):
        self.us = 1000

    def __call__(self) -> int:
        return self.us

    def advance(self, us: int):
        self.us += us


@pytest.fixture()
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(engine, "_gate_now_us", c)
    return c


@pytest.fixture()
def traced():
    rec = tracing.configure(enabled=True, capacity=4096, slow_op_us=0)
    rec.clear()
    yield rec
    tracing.configure(enabled=False)


async def _until(cond):
    for _ in range(1000):
        if cond():
            return
        await asyncio.sleep(0)
    raise AssertionError("the gate never reached the state waited for")


class Holder:
    """A task that asks for the gate and holds it until told to let go."""

    def __init__(self, gate, holder, shared=False, expedite=False):
        self.release = asyncio.Event()
        self.hold = None
        ctx = (
            gate.shared(holder=holder) if shared
            else gate.exclusive(holder=holder, expedite=expedite)
        )

        async def run():
            async with ctx as hold:
                self.hold = hold
                await self.release.wait()
            return hold

        self.task = asyncio.ensure_future(run())

    async def acquired(self):
        await _until(lambda: self.hold is not None)
        return self.hold

    async def let_go(self, clock=None, then_us=0):
        """Leave the body. ``then_us``: advance ``clock`` after the release is
        stamped and before any waiter it wakes runs (the loop runs callbacks
        in the order they were scheduled: this holder's wake-up, the advance,
        then the waiters its ``notify_all`` wakes), the stretch in which the
        gate is nobody's."""
        self.release.set()
        if then_us:
            asyncio.get_running_loop().call_soon(clock.advance, then_us)
        return await self.task


def _row(gate, waiter):
    return {k: v for k, v in gate.wait_us[waiter].items() if v}


def _consistent(gate):
    """Nothing held, nobody waiting, every waiter's parts a whole."""
    assert gate.idle and gate._holding is None
    assert gate._exclusive_waiting == gate._expedite_waiting == 0
    c = gate.counters()
    for w in GATE_HOLDERS:
        assert c[f"gate_wait_us_{w}"] == sum(c[f"gate_wait_us_{w}_behind_{b}"] for b in BEHIND)


@pytest.mark.parametrize("first, shared", [("prefill", False), ("snapshot", True), ("resume", False)])
def test_a_wait_is_cut_by_every_holder_it_stood_behind(clock, traced, first, shared):
    """A wave asks while ``first`` holds; an install (the expedite lane) asks
    after it and goes ahead of it: the wave stood behind both, and its parts
    and ``free`` are its wait to the microsecond."""

    async def drive():
        gate = DeviceGate()
        a = Holder(gate, first, shared=shared)
        await a.acquired()
        clock.advance(7)  # a's hold before anybody waits: nobody's wait
        with tracing.trace_op("flush") as parent:
            wave = Holder(gate, "wave")
        await _until(lambda: gate._exclusive_waiting == 1)
        clock.advance(40)
        install = Holder(gate, "install", expedite=True)
        await _until(lambda: gate._expedite_waiting == 1)
        clock.advance(30)
        # 7 + 40 + 30 held, the wave stood behind 70 of them; then the gate
        # is nobody's for 5 until the install wakes.
        await a.let_go(clock, then_us=5)
        install_hold = await install.acquired()
        assert wave.hold is None  # the expedite lane went first
        clock.advance(25)
        await install.let_go(clock, then_us=3)
        wave_hold = await wave.acquired()
        clock.advance(11)
        await wave.let_go()
        return gate, a.hold, install_hold, wave_hold, parent

    gate, a_hold, install_hold, wave_hold, parent = asyncio.run(asyncio.wait_for(drive(), 10))
    assert _row(gate, "wave") == {first: 70, "install": 25, GATE_FREE: 8}
    assert wave_hold.waited_us == 103 == sum(gate.wait_us["wave"].values())
    assert _row(gate, "install") == {first: 30, GATE_FREE: 5} and install_hold.waited_us == 35
    assert _row(gate, first) == {}  # granted at once: a wait of 0 is counted, and adds nothing
    assert (a_hold.waited_us, a_hold.held_us) == (0, 77)
    assert (install_hold.held_us, wave_hold.held_us) == (25, 11)
    assert {k: v for k, v in gate.held_us.items() if v} == {first: 77, "install": 25, "wave": 11}
    assert {k: v for k, v in gate.holds.items() if v} == {first: 1, "install": 1, "wave": 1}
    assert {k: v for k, v in gate.waits.items() if v} == {first: 1, "install": 1, "wave": 1}
    assert wave_hold.asked_us == 1007 and wave_hold.asked_s == pytest.approx(1007e-6)
    _consistent(gate)
    # The span says the same of its one wait, and the hold's length at release.
    waits = {s["attrs"]["holder"]: s for s in traced.snapshot() if s["name"] == "gate_wait"}
    assert waits["wave"]["attrs"] == {
        "mode": "exclusive", "holder": "wave", "held_us": 11,
        "behind_us": {first: 70, "install": 25, GATE_FREE: 8},
    }
    assert waits["install"]["attrs"]["behind_us"] == {first: 30, GATE_FREE: 5}
    assert waits[first]["attrs"] == {
        "mode": "shared" if shared else "exclusive", "holder": first,
        "behind_us": {}, "held_us": 77,
    }
    assert waits["wave"]["parent_id"] == parent.span_id


def test_overlapping_shared_holds_are_one_stretch(clock):
    """Two snapshots and a verify overlap: the time the gate was shared counts
    once, under the kind that opened the stretch; every hold is counted."""

    async def drive():
        gate = DeviceGate()
        s1 = Holder(gate, "snapshot", shared=True)
        await s1.acquired()
        clock.advance(10)
        wave = Holder(gate, "wave")  # asks 10 into the stretch...
        await _until(lambda: gate._exclusive_waiting == 1)
        late = Holder(gate, "snapshot", shared=True)  # ...and keeps new shared holders out
        for _ in range(5):
            await asyncio.sleep(0)
        assert late.hold is None
        wave.task.cancel()
        await asyncio.gather(wave.task, return_exceptions=True)
        s2 = await late.acquired()  # the cancelled writer's notify_all freed it
        v = Holder(gate, "verify", shared=True)
        await v.acquired()
        clock.advance(20)
        await s1.let_go()
        clock.advance(20)
        await late.let_go()
        clock.advance(15)
        await v.let_go()
        return gate, s1.hold, s2, v.hold

    gate, s1, s2, v = asyncio.run(asyncio.wait_for(drive(), 10))
    assert gate.held_us["snapshot"] == 65 and gate.held_us["verify"] == 0
    assert gate.holds["snapshot"] == 2 and gate.holds["verify"] == 1
    assert (s1.held_us, s2.held_us, v.held_us) == (30, 40, 55)
    # The late snapshot waited behind nobody's hold but the stretch itself.
    assert _row(gate, "snapshot") == {} and s2.waited_us == 0
    # The cancelled wave's wait is not in the ledger.
    assert gate.waits["wave"] == 0 and _row(gate, "wave") == {}
    _consistent(gate)


def test_a_cancelled_waiter_leaves_the_ledger_whole(clock):
    """A waiting wave is cancelled: its wait is not added, no hold is left
    open, and the reader that queued beside it has its own wait cut as any
    other. (That the cancelled wait's ``notify_all`` frees a reader the writer
    alone kept out: ``test_overlapping_shared_holds_are_one_stretch``.)"""

    async def drive():
        gate = DeviceGate()
        prefill = Holder(gate, "prefill")
        await prefill.acquired()
        wave = Holder(gate, "wave")
        await _until(lambda: gate._exclusive_waiting == 1)
        clock.advance(12)
        reader = Holder(gate, "snapshot", shared=True)
        for _ in range(5):
            await asyncio.sleep(0)
        clock.advance(30)
        wave.task.cancel()
        await asyncio.gather(wave.task, return_exceptions=True)
        assert gate._exclusive_waiting == 0 and reader.hold is None and wave.hold is None
        clock.advance(8)
        await prefill.let_go(clock, then_us=4)
        got = await asyncio.wait_for(reader.acquired(), 5)
        clock.advance(6)
        await reader.let_go()
        async with gate.exclusive(holder="wave") as again:  # the gate still works
            clock.advance(2)
        return gate, got, again

    gate, got, again = asyncio.run(asyncio.wait_for(drive(), 10))
    assert gate.waits["wave"] == 1 and _row(gate, "wave") == {}  # only the last, granted at once
    assert _row(gate, "snapshot") == {"prefill": 38, GATE_FREE: 4} and got.waited_us == 42
    assert gate.held_us == {
        "wave": 2, "prefill": 50, "resume": 0, "install": 0, "snapshot": 6, "verify": 0,
    }
    assert again.held_us == 2
    _consistent(gate)


@pytest.mark.parametrize("ask", ["exclusive", "shared"])
@pytest.mark.parametrize("holder", ["decode", "", None, "free"])
def test_an_unknown_holder_raises(ask, holder):
    gate = DeviceGate()

    async def drive():
        async with getattr(gate, ask)(holder=holder):
            pass

    with pytest.raises(ValueError, match="unknown gate holder"):
        asyncio.run(drive())
    assert gate.idle and sum(gate.waits.values()) == 0


@pytest.mark.parametrize("ask", ["exclusive", "shared"])
def test_a_holder_is_required(ask):
    with pytest.raises(TypeError):
        getattr(DeviceGate(), ask)()


def test_the_ledger_costs_no_span_with_tracing_off(clock, monkeypatch):
    tracing.configure(enabled=False)

    class NoSpan:
        def __init__(self, *a, **kw):
            raise AssertionError("Span built with tracing off")

    monkeypatch.setattr(tracing, "Span", NoSpan)

    async def drive():
        gate = DeviceGate()
        p = Holder(gate, "prefill")
        await p.acquired()
        w = Holder(gate, "wave")
        await _until(lambda: gate._exclusive_waiting == 1)
        clock.advance(9)
        await p.let_go()
        await w.acquired()
        await w.let_go()
        return gate

    gate = asyncio.run(asyncio.wait_for(drive(), 10))
    assert _row(gate, "wave") == {"prefill": 9}
    _consistent(gate)


# ---------------------------------------------------------------------------
# Through the harness: the keys of metrics(), the spans of a served request.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _harness(conn, params, model_id):
    kvc = KVConnector(conn, CFG.kv_spec(NUM_BLOCKS), model_id, max_blocks=MAX_REQ_BLOCKS)
    return ContinuousBatchingHarness(
        EngineKVAdapter(kvc), params, CFG, NUM_BLOCKS, MAX_REQ_BLOCKS
    )


def _prompt(seed, blocks=3):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab, size=blocks * CFG.block_tokens).tolist()


def gate_keys():
    keys = set()
    for kind in GATE_HOLDERS:
        keys |= {f"gate_held_us_{kind}", f"gate_holds_{kind}", f"gate_waits_{kind}",
                 f"gate_wait_us_{kind}"}
        keys |= {f"gate_wait_us_{kind}_behind_{b}" for b in BEHIND}
    return keys


def test_metrics_carry_the_ledger_and_spans_agree(conn, params, traced):
    """A miss, a hit and a partial hit beside another miss: every key of the
    ledger is a number in ``metrics()``, a waiter's parts make its whole,
    the requests' own gate figures are the gate's, and each ``gate_wait``
    span's parts fit inside it."""
    h = _harness(conn, params, f"ledger-{conn.shm_active}")
    shared, other = _prompt(11, blocks=2), _prompt(12)
    longer = shared + _prompt(13, blocks=1)

    async def drive():
        miss = await h.run_request(shared, gen_tokens=GEN)
        hit = await h.run_request(shared, gen_tokens=GEN)
        both = await asyncio.gather(
            h.run_request(longer, gen_tokens=GEN), h.run_request(other, gen_tokens=GEN)
        )
        return [miss, hit, *both]

    miss, hit, resumed, miss2 = asyncio.run(asyncio.wait_for(drive(), 120))
    assert (miss.loaded_blocks, hit.loaded_blocks, resumed.loaded_blocks) == (0, 2, 2)
    assert resumed.computed_blocks == 1 and miss2.loaded_blocks == 0
    m = h.metrics()
    assert gate_keys() <= set(m)
    for key in gate_keys():
        assert isinstance(m[key], int) and not isinstance(m[key], bool) and m[key] >= 0, key
    for w in GATE_HOLDERS:
        assert m[f"gate_wait_us_{w}"] == sum(m[f"gate_wait_us_{w}_behind_{b}"] for b in BEHIND)
    assert m["gate_holds_prefill"] == 2 and m["gate_holds_resume"] == 1
    assert m["gate_holds_install"] == 2 and m["gate_holds_snapshot"] >= 3
    assert m["gate_holds_wave"] == m["gate_waits_wave"] == m["decode_waves"] > 0
    assert m["gate_holds_verify"] == 0 and m["gate_held_us_verify"] == 0
    assert m["gate_held_us_prefill"] > 0 and m["gate_held_us_wave"] > 0
    # One clock around one lock: what a request reports is what the gate measured.
    stats = [miss, hit, resumed, miss2]
    waits = [s for s in traced.snapshot() if s["name"] == "gate_wait"]
    request_waiters = ("prefill", "resume", "install")
    assert sum(s.gate_stall_us for s in stats) == sum(m[f"gate_wait_us_{w}"] for w in request_waiters)
    held_installs = sorted(
        s["attrs"]["held_us"] for s in waits if s["attrs"]["holder"] == "install"
    )
    assert sorted(s.gate_hold_us for s in (hit, resumed)) == held_installs
    assert sum(held_installs) == m["gate_held_us_install"]
    assert miss.gate_hold_us == miss2.gate_hold_us == 0
    # Every acquisition left a span that says who asked and what it stood behind.
    assert len(waits) == sum(m[f"gate_waits_{w}"] for w in GATE_HOLDERS)
    by_holder = dict.fromkeys(GATE_HOLDERS, 0)
    for s in waits:
        attrs = s["attrs"]
        assert attrs["holder"] in GATE_HOLDERS and set(attrs["behind_us"]) <= set(BEHIND)
        assert all(v > 0 for v in attrs["behind_us"].values())
        assert sum(attrs["behind_us"].values()) <= s["duration_us"]
        assert attrs["held_us"] >= 0
        by_holder[attrs["holder"]] += sum(attrs["behind_us"].values())
    assert by_holder == {w: m[f"gate_wait_us_{w}"] for w in GATE_HOLDERS}


def test_no_request_live_opens_and_closes_with_the_live_count(conn, params, traced):
    h = _harness(conn, params, f"nobody-{conn.shm_active}")
    seen = {}

    async def drive():
        assert h._nobody_live is None  # nothing before the first request has left
        first = await h.run_request(_prompt(21), gen_tokens=GEN)
        seen["after_first"] = h._nobody_live
        await asyncio.sleep(0.01)
        pair = await asyncio.gather(
            h.run_request(_prompt(22), gen_tokens=GEN), h.run_request(_prompt(23), gen_tokens=GEN)
        )
        seen["after_pair"] = h._nobody_live
        return [first, *pair]

    stats = asyncio.run(asyncio.wait_for(drive(), 120))
    assert h.live == 0 and seen["after_first"] is not seen["after_pair"]
    spans = traced.snapshot()
    # One stretch closed (first request out -> the pair in); the one after the
    # pair is still open and so not recorded. None while a request was live.
    (gap,) = [s for s in spans if s["name"] == "no_request_live"]
    assert gap["span_id"] == seen["after_first"].span_id and gap["status"] == "ok"
    assert gap["parent_id"] == 0
    assert gap["trace_id"] not in {s.trace_id for s in stats}
    assert not [s for s in spans if s["parent_id"] == gap["span_id"]]
    roots = sorted(
        (s for s in spans if s["name"] == "engine_request"), key=lambda s: s["start_us"]
    )
    assert len(roots) == 3
    assert roots[0]["end_us"] <= gap["start_us"] <= gap["end_us"] <= roots[1]["start_us"]
    assert seen["after_pair"].status == ""  # open: the harness holds it, the ring does not


def test_no_request_live_is_not_built_with_tracing_off(conn, params):
    tracing.configure(enabled=False)
    h = _harness(conn, params, f"nobody-off-{conn.shm_active}")
    asyncio.run(asyncio.wait_for(h.run_request(_prompt(31), gen_tokens=2), 60))
    assert h.live == 0 and h._nobody_live is None
