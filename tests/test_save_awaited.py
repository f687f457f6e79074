"""A save's class and its in-flight window follow whether its request is
blocked on it (docs/qos.md, "Producers"): the layerwise writer reads a class
cell per layer, a foreground write keeps as many layers in flight as fit in
``FG_WINDOW_BYTES`` (an answer's save: every deeper layer at once, then the
sentinel), a background one ``depth`` groups; the engine binds the cell
around its adapter's ``save_kv`` (``wire.SAVE_CLASS``), foreground for the
saves it awaits in line, background for the prompt write beside
``_generate`` and promoted at the join."""

import asyncio
import ctypes
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu import wire
from infinistore_tpu.cluster import ClusterKVConnector
from infinistore_tpu.connector import KVConnector
from infinistore_tpu.engine import ContinuousBatchingHarness, EngineKVAdapter
from infinistore_tpu.models import LlamaConfig, init_params
from infinistore_tpu.tpu import layerwise
from infinistore_tpu.tpu.layerwise import LayerwiseKVWriter
from infinistore_tpu.tpu.paged import PagedKVCacheSpec, gather_blocks
from infinistore_tpu.tpu.staging import RegisteredTransfer, StagedTransfer

BG = wire.PRIORITY_BACKGROUND
FG = wire.PRIORITY_FOREGROUND
TIMEOUT_S = 60

# ---------------------------------------------------------------------------
# The writer against a recording stand-in connection.
# ---------------------------------------------------------------------------

SPEC = PagedKVCacheSpec(16, 16, 4, 2, 8, jnp.float32)  # 16 layers, 256 B a block
N_BLOCKS = 4
LAYER_BYTES = 2 * N_BLOCKS * SPEC.block_nbytes
IDS = np.array([5, 2, 11, 7], dtype=np.int32)
SAVE_KEYS = ("save_puts", "save_fg_puts", "save_promotions", "save_fg_writes", "save_fg_rounds")


class Put:
    def __init__(self, blocks, block_size, ptr, kw, fut):
        layer, self.kind = blocks[0][0].split("/")[:2]
        self.layer = int(layer[1:])
        self.kw, self.fut, self.ptr = kw, fut, ptr
        self.nbytes = len(blocks) * block_size


class RecordingConn:
    """Holds every put until the test settles it; copies the bytes out at
    submission, and notes registrations."""

    def __init__(self, qos_aware=True):
        self.QOS_AWARE = qos_aware
        self.puts = []
        self.store = {}
        self.registered = {}
        self.unregistered = []

    def register_mr(self, ptr, nbytes):
        self.registered[ptr] = nbytes

    def unregister_mr(self, ptr):
        del self.registered[ptr]
        self.unregistered.append(ptr)

    async def write_cache_async(self, blocks, block_size, ptr, **kw):
        (base,) = [p for p, n in self.registered.items() if p <= ptr < p + n]
        assert ptr + len(blocks) * block_size <= base + self.registered[base]
        for key, off in blocks:
            self.store[key] = ctypes.string_at(ptr + off, block_size)
        put = Put(blocks, block_size, ptr, kw, asyncio.get_running_loop().create_future())
        self.puts.append(put)
        return await put.fut

    def pending(self):
        return [p for p in self.puts if not p.fut.done()]

    def layers_pending(self):
        return sorted({p.layer for p in self.pending()})


class StagePool:
    """What the writer needs of a staging pool: ``stage_out``."""

    def __init__(self, conn):
        self.conn = conn

    def stage_out(self, arrays):
        return RegisteredTransfer(StagedTransfer(arrays), self.conn)


def _caches(spec=SPEC):
    keys = jax.random.split(jax.random.PRNGKey(7), 2 * spec.num_layers)
    shape = (spec.num_blocks, *spec.block_shape)
    return [
        (jax.random.normal(keys[2 * l], shape, spec.dtype),
         jax.random.normal(keys[2 * l + 1], shape, spec.dtype))
        for l in range(spec.num_layers)
    ]


def _key_fn(layer, kind, i):
    return f"L{layer}/{kind}/{i}"


def _writer(conn, spec=SPEC):
    w = LayerwiseKVWriter(conn, StagePool(conn), spec, max_blocks=N_BLOCKS)
    w.counters = dict.fromkeys(SAVE_KEYS, 0) | {
        "save_d2h_bytes": 0, "save_d2h_wait_us": 0.0,
        "save_put_bytes": 0, "save_put_busy_us": 0.0,
        "save_puts_in_flight": 0, "save_put_busy_mark_s": 0.0,
    }
    return w


async def _settle(conn):
    """Let the writer run until it stands still: no put submitted or
    settled for 20 ms (a heavy layer's D2H wait hops through an executor)."""
    seen = None
    while not conn.puts or seen != (seen := (len(conn.puts), len(conn.pending()))):
        await asyncio.sleep(0.02)


def _run(coro):
    return asyncio.run(asyncio.wait_for(coro, TIMEOUT_S))


def test_awaited_save_submits_every_deeper_layer_at_once_then_the_sentinel():
    conn = RecordingConn()
    w = _writer(conn)
    caches = _caches()

    async def drive():
        task = asyncio.ensure_future(w.write(caches, IDS, _key_fn))  # the default: foreground
        await _settle(conn)
        # Layers 1..15, K and V, before any was acknowledged; no sentinel.
        assert len(conn.puts) == 30 and conn.layers_pending() == list(range(1, 16))
        assert all(p.kw == {} for p in conn.puts)
        for p in conn.puts[:29]:
            p.fut.set_result(0)
        await _settle(conn)
        assert len(conn.puts) == 30  # one deeper put still out: layer 0 waits
        conn.puts[29].fut.set_result(0)
        await _settle(conn)
        assert [(p.layer, p.kind) for p in conn.puts[30:]] == [(0, "k"), (0, "v")]
        assert not task.done()
        for p in conn.pending():
            p.fut.set_result(0)
        return await task

    assert _run(drive()) == 2 * 16 * N_BLOCKS
    c = w.counters
    assert (c["save_fg_writes"], c["save_fg_rounds"]) == (1, 2)
    assert c["save_puts"] == c["save_fg_puts"] == 32 and c["save_promotions"] == 0
    assert conn.registered == {} and len(conn.unregistered) == 16
    # What went to the store is what the cache held.
    for layer, (k, v) in enumerate(caches):
        for kind, cache in (("k", k), ("v", v)):
            want = np.asarray(gather_blocks(cache, jnp.asarray(IDS)))
            for i in range(N_BLOCKS):
                assert conn.store[_key_fn(layer, kind, i)] == want[i].tobytes()


@pytest.mark.parametrize("cls", ["foreground", "background"])
def test_window_is_the_byte_budget_foreground_and_two_groups_background(monkeypatch, cls):
    budget = 3 * LAYER_BYTES + LAYER_BYTES // 2  # three layers fit, not four
    monkeypatch.setattr(layerwise, "FG_WINDOW_BYTES", budget)
    conn = RecordingConn()
    w = _writer(conn)
    cell = {"value": FG if cls == "foreground" else BG}
    seen = []  # put groups (layers) in flight, whenever the writer stood still

    async def drive():
        task = asyncio.ensure_future(w.write(_caches(), IDS, _key_fn, priority_cell=cell))
        while not task.done():
            await _settle(conn)
            pending = conn.pending()
            if pending:
                seen.append(len({p.layer for p in pending}))
                assert sum(p.nbytes for p in pending) <= max(budget, 2 * LAYER_BYTES)
                for p in [p for p in pending if p.layer == pending[0].layer]:
                    p.fut.set_result(0)  # the oldest group, K and V
        return await task

    assert _run(drive()) == 2 * 16 * N_BLOCKS
    want_kw = {} if cls == "foreground" else {"priority": BG}
    assert all(p.kw == want_kw for p in conn.puts)
    # The sentinel rides alone; before it the window stays full.
    assert max(seen) == (3 if cls == "foreground" else 2)
    assert min(seen[:-2]) >= 2
    c = w.counters
    assert c["save_puts"] == 32 and c["save_fg_puts"] == (32 if cls == "foreground" else 0)
    if cls == "foreground":
        # Three at once, one more after each wait, the sentinel after all.
        assert (c["save_fg_writes"], c["save_fg_rounds"]) == (1, 1 + 12 + 1)
    else:
        assert (c["save_fg_writes"], c["save_fg_rounds"]) == (0, 0)
    assert conn.registered == {}


def test_promoted_write_sends_its_unsent_layers_untagged_in_the_foreground_window():
    conn = RecordingConn()
    w = _writer(conn)
    cell = {"value": BG}

    async def drive():
        task = asyncio.ensure_future(w.write(_caches(), IDS, _key_fn, priority_cell=cell))
        await _settle(conn)
        assert conn.layers_pending() == [1, 2]  # background: two groups
        cell["value"] = FG  # the caller starts waiting
        await _settle(conn)
        assert conn.layers_pending() == [1, 2]  # in flight: they finish at their class
        for p in conn.pending():
            p.fut.set_result(0)
        await _settle(conn)
        assert conn.layers_pending() == list(range(3, 16))  # the rest at once
        for p in conn.pending():
            p.fut.set_result(0)
        await _settle(conn)
        assert conn.layers_pending() == [0]
        for p in conn.pending():
            p.fut.set_result(0)
        return await task

    _run(drive())
    assert [p.kw for p in conn.puts] == [{"priority": BG}] * 4 + [{}] * 28
    c = w.counters
    assert c["save_promotions"] == 1 and c["save_fg_puts"] == 28
    assert (c["save_fg_writes"], c["save_fg_rounds"]) == (0, 0)  # it did not START foreground


@pytest.mark.parametrize("weight", ["light", "heavy"])
def test_light_layers_wait_for_their_d2h_in_line_and_heavy_ones_in_an_executor(monkeypatch, weight):
    """A layer of at most ``D2H_INLINE_BYTES`` lands sooner than a thread
    hop takes; a heavier one must not stop the event loop while it lands."""
    limit = LAYER_BYTES if weight == "light" else LAYER_BYTES - 1
    monkeypatch.setattr(layerwise, "D2H_INLINE_BYTES", limit)
    conn = RecordingConn()
    w = _writer(conn)
    waited_in = []
    real_wait = StagedTransfer.wait

    def wait(self):
        if self._hosts is None:
            waited_in.append(threading.current_thread())
        return real_wait(self)

    monkeypatch.setattr(StagedTransfer, "wait", wait)

    async def drive():
        task = asyncio.ensure_future(w.write(_caches(), IDS, _key_fn))
        while not task.done():
            await _settle(conn)
            for p in conn.pending():
                p.fut.set_result(0)
        return await task

    assert _run(drive()) == 2 * 16 * N_BLOCKS
    assert len(waited_in) == 16
    on_loop = [t is threading.main_thread() for t in waited_in]
    assert all(on_loop) if weight == "light" else not any(on_loop)
    assert w.counters["save_d2h_bytes"] == 16 * LAYER_BYTES and conn.registered == {}
    # The put ledger: every byte acknowledged, nothing left in flight, and
    # the union of the puts' time is some time.
    assert w.counters["save_put_bytes"] == 16 * LAYER_BYTES
    assert w.counters["save_puts_in_flight"] == 0 and w.counters["save_put_busy_us"] > 0


def test_connection_without_qos_gets_untagged_puts_in_both_classes():
    for cls in (FG, BG):
        conn = RecordingConn(qos_aware=False)
        w = _writer(conn)

        async def drive():
            task = asyncio.ensure_future(
                w.write(_caches(), IDS, _key_fn, priority_cell={"value": cls})
            )
            while not task.done():
                await _settle(conn)
                for p in conn.pending():
                    p.fut.set_result(0)
            return await task

        _run(drive())
        assert len(conn.puts) == 32 and all(p.kw == {} for p in conn.puts)


@pytest.mark.parametrize("cls", ["foreground", "background"])
def test_failed_deeper_put_ships_no_sentinel_and_releases_after_the_sibling(cls):
    conn = RecordingConn()
    w = _writer(conn)
    cell = {"value": FG if cls == "foreground" else BG}
    failed_layer = 2

    async def drive():
        task = asyncio.ensure_future(w.write(_caches(), IDS, _key_fn, priority_cell=cell))
        await _settle(conn)
        k_put, v_put = [p for p in conn.puts if p.layer == failed_layer]
        for p in conn.puts:
            if p.layer < failed_layer:
                p.fut.set_result(0)
        k_put.fut.set_exception(RuntimeError("put failed"))
        await _settle(conn)
        # The sibling V is still streaming from the host buffer: not released.
        assert v_put.ptr - N_BLOCKS * SPEC.block_nbytes in conn.registered
        assert not task.done()
        v_put.fut.set_result(0)
        while not task.done():
            await _settle(conn)
            for p in conn.pending():
                p.fut.set_result(0)
        with pytest.raises(RuntimeError, match="put failed"):
            await task

    _run(drive())
    assert all(p.layer != 0 for p in conn.puts)  # the sentinel never shipped
    assert conn.registered == {}  # every host buffer released
    # One buffer a layer that shipped: every deeper one of the foreground
    # write, the few the background one got to.
    shipped = {p.layer for p in conn.puts}
    assert len(conn.unregistered) == len(shipped)
    assert len(shipped) == 15 if cls == "foreground" else len(shipped) < 5


async def _acknowledge_all(conn, task):
    while not task.done():
        await _settle(conn)
        for p in conn.pending():
            p.fut.set_result(0)
    return await task


def test_event_loop_runs_while_a_heavy_layers_d2h_lands(monkeypatch):
    """What the hop is for: a wave's flush is a callback on this loop."""
    monkeypatch.setattr(layerwise, "D2H_INLINE_BYTES", LAYER_BYTES - 1)
    conn = RecordingConn()
    w = _writer(conn)
    landing, may_land = threading.Event(), threading.Event()
    real_wait = StagedTransfer.wait

    def slow_wait(self):
        if self._hosts is None and threading.current_thread() is not threading.main_thread():
            landing.set()
            assert may_land.wait(TIMEOUT_S)
        return real_wait(self)

    monkeypatch.setattr(StagedTransfer, "wait", slow_wait)

    async def drive():
        task = asyncio.ensure_future(w.write(_caches(), IDS, _key_fn))
        await asyncio.get_running_loop().run_in_executor(None, landing.wait, TIMEOUT_S)
        for _ in range(5):  # the loop turns while the first layer is still landing
            await asyncio.sleep(0)
        assert not conn.puts and not task.done()
        may_land.set()
        return await _acknowledge_all(conn, task)

    assert _run(drive()) == 2 * 16 * N_BLOCKS


def test_write_cancelled_while_a_heavy_layer_lands_leaves_nothing_registered(monkeypatch):
    """The hop is one more point at which the write's task can be cancelled:
    the puts in flight still settle before their buffers go, and the layer
    that was landing was never registered."""
    monkeypatch.setattr(layerwise, "D2H_INLINE_BYTES", LAYER_BYTES - 1)
    conn = RecordingConn()
    w = _writer(conn)
    may_land = threading.Event()
    real_wait = StagedTransfer.wait
    waits = []

    def wait(self):
        if self._hosts is None:
            waits.append(self)
            if len(waits) == 2:  # layer 1 shipped: hold layer 2
                assert may_land.wait(TIMEOUT_S)
        return real_wait(self)

    monkeypatch.setattr(StagedTransfer, "wait", wait)

    async def drive():
        task = asyncio.ensure_future(
            w.write(_caches(), IDS, _key_fn, priority=BG)
        )
        await _settle(conn)
        assert conn.layers_pending() == [1] and len(waits) == 2
        task.cancel()
        await asyncio.sleep(0.05)
        assert not task.done() and len(conn.registered) == 1  # waits for its puts in flight
        for p in conn.pending():
            p.fut.set_result(0)
        with pytest.raises(asyncio.CancelledError):
            await task
        may_land.set()

    _run(drive())
    assert conn.registered == {} and len(conn.unregistered) == 1
    assert {p.layer for p in conn.puts} == {1}


# ---------------------------------------------------------------------------
# The engine and the connector against the live store.
# ---------------------------------------------------------------------------

CFG = LlamaConfig(
    vocab=128, dim=64, n_layers=4, n_heads=4, n_kv_heads=2, ffn_dim=128,
    block_tokens=8, dtype=jnp.float32,
)
NUM_BLOCKS, MAX_REQ_BLOCKS, PROMPT_BLOCKS, GEN = 10, 5, 3, 9  # one whole answer block


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _prompt(seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab, size=PROMPT_BLOCKS * CFG.block_tokens).tolist()


class TappedConn:
    """The live connection, noting every put's class; puts tagged
    BACKGROUND wait for ``release`` where ``hold`` is set."""

    def __init__(self, conn, qos_aware=True):
        self._conn = conn
        self.QOS_AWARE = qos_aware
        self.kws = []
        self.hold = False
        self.release = asyncio.Event()

    def __getattr__(self, name):
        return getattr(self._conn, name)

    async def write_cache_async(self, blocks, block_size, ptr, **kw):
        self.kws.append(kw)
        if self.hold and kw.get("priority") == BG:
            await self.release.wait()
        return await self._conn.write_cache_async(blocks, block_size, ptr, **kw)


class PlainAdapter(EngineKVAdapter):
    """Shaped as the benchmark's ``CheckingAdapter``: ``save_kv`` with no
    keyword beyond ``first_block``, passing exactly those on."""

    async def save_kv(self, token_ids, caches, block_table, first_block=0):
        return await super().save_kv(token_ids, caches, block_table, first_block=first_block)


def _harness(conn, params, model_id, qos_aware=True):
    tapped = TappedConn(conn, qos_aware)
    kvc = KVConnector(tapped, CFG.kv_spec(NUM_BLOCKS), model_id, max_blocks=MAX_REQ_BLOCKS)
    h = ContinuousBatchingHarness(PlainAdapter(kvc), params, CFG, NUM_BLOCKS, MAX_REQ_BLOCKS)
    return h, kvc, tapped


async def _until(cond):
    t_end = time.perf_counter() + TIMEOUT_S
    while not cond():
        assert time.perf_counter() < t_end, "condition never held"
        await asyncio.sleep(0.005)


def test_prompt_write_is_background_until_the_join_and_the_answers_save_foreground(
    conn, params
):
    h, kvc, tapped = _harness(conn, params, f"awaited-join-{conn.shm_active}")
    tapped.hold = True
    prompt = _prompt(1)

    async def drive():
        task = asyncio.ensure_future(h.run_request(prompt, gen_tokens=GEN))
        await _until(lambda: h.wave.waves >= GEN)
        await asyncio.sleep(0.05)  # the request stands at its join
        assert not task.done()
        assert tapped.kws == [{"priority": BG}] * 4  # layers 1 and 2, held
        tapped.release.set()
        return await task

    stats = _run(drive())
    # The prompt write's layers 3 and 0 went out promoted, then the answer's
    # block: four layers, K and V, foreground from the first.
    assert tapped.kws == [{"priority": BG}] * 4 + [{}] * 4 + [{}] * 8
    s = kvc.get_stats()
    assert s["save_promotions"] == 1 and s["save_puts"] == 16 and s["save_fg_puts"] == 12
    assert (s["save_fg_writes"], s["save_fg_rounds"]) == (1, 2)
    assert stats.ack_tail_us >= stats.save_tail_us > 0.04e6
    assert kvc.lookup(prompt + stats.generated) == PROMPT_BLOCKS + 1


def test_prefill_only_save_and_three_argument_save_blocks_are_foreground(conn, params):
    h, kvc, tapped = _harness(conn, params, f"awaited-inline-{conn.shm_active}")
    prompt, chain = _prompt(2), _prompt(3)

    async def drive():
        stats = await h.run_request(prompt, gen_tokens=0)
        table = await h.pool.alloc(PROMPT_BLOCKS)
        await h._save_blocks(chain, table, 0)  # the benchmark's warm-up calls it so
        await h.pool.free(table)
        return stats

    stats = _run(drive())
    assert tapped.kws == [{}] * 16 and stats.ack_tail_us == 0.0
    s = kvc.get_stats()
    assert (s["save_fg_writes"], s["save_fg_rounds"], s["save_promotions"]) == (2, 4, 0)
    assert kvc.lookup(prompt) == kvc.lookup(chain) == PROMPT_BLOCKS


def test_connection_without_qos_is_untagged_through_a_whole_request(conn, params):
    h, kvc, tapped = _harness(conn, params, f"awaited-noqos-{conn.shm_active}", qos_aware=False)
    stats = _run(h.run_request(_prompt(4), gen_tokens=GEN))
    assert len(tapped.kws) == 16 and all(kw == {} for kw in tapped.kws)
    assert kvc.get_stats()["save_fg_puts"] == 16 and len(stats.generated) == GEN


def test_save_outside_the_engine_keeps_the_connectors_default(conn):
    """vllm_v1, disagg and the cluster's members call ``save`` so."""
    spec = CFG.kv_spec(NUM_BLOCKS)
    tapped = TappedConn(conn)
    kvc = KVConnector(tapped, spec, f"awaited-default-{conn.shm_active}", MAX_REQ_BLOCKS)
    _run(kvc.save(_prompt(5), _caches(spec), np.arange(PROMPT_BLOCKS)))
    assert tapped.kws == [{"priority": BG}] * 8
    s = kvc.get_stats()
    assert (s["save_puts"], s["save_fg_puts"], s["save_fg_writes"]) == (8, 0, 0)


@pytest.mark.parametrize("explicit", [BG, FG])
def test_explicit_priority_wins_over_a_bound_cell(conn, explicit):
    """``handoff`` and any caller that names a class get that class."""
    spec = CFG.kv_spec(NUM_BLOCKS)
    tapped = TappedConn(conn)
    kvc = KVConnector(tapped, spec, f"awaited-explicit-{explicit}-{conn.shm_active}", MAX_REQ_BLOCKS)

    async def drive():
        bound = wire.SAVE_CLASS.set({"value": FG if explicit == BG else BG})
        try:
            await kvc.save(_prompt(7), _caches(spec), np.arange(PROMPT_BLOCKS), priority=explicit)
        finally:
            wire.SAVE_CLASS.reset(bound)

    _run(drive())
    assert tapped.kws == [{"priority": BG} if explicit == BG else {}] * 8


@pytest.mark.parametrize("bound", ["awaited", "unbound"])
def test_cluster_mirror_stays_background_under_an_awaited_save(conn, bound):
    """The first copy goes out at the caller's class, the replication
    mirror at the members' default; ``health()["qos"]`` counts the op at
    the class it went out at."""
    spec = CFG.kv_spec(NUM_BLOCKS)
    taps = [TappedConn(conn), TappedConn(conn)]
    cluster = ClusterKVConnector(
        taps, spec, f"awaited-mirror-{bound}-{conn.shm_active}", MAX_REQ_BLOCKS,
        member_ids=["a", "b"], replicas=2,
    )
    prompt = _prompt(8)
    first, mirror = (taps[i] for i in cluster.write_indices(prompt))

    async def drive():
        token = wire.SAVE_CLASS.set({"value": FG}) if bound == "awaited" else None
        try:
            return await cluster.save(prompt, _caches(spec), np.arange(PROMPT_BLOCKS))
        finally:
            if token is not None:
                wire.SAVE_CLASS.reset(token)

    assert _run(drive()) == 2 * CFG.n_layers * PROMPT_BLOCKS
    assert first.kws == [{} if bound == "awaited" else {"priority": BG}] * 8
    assert mirror.kws == [{"priority": BG}] * 8
    qos = cluster.health()["qos"]
    assert (qos["fg_ops"], qos["bg_ops"]) == ((1, 0) if bound == "awaited" else (0, 1))
    assert qos["mirror_writes"] == 1
    cluster.close()


@pytest.mark.parametrize("cls", ["foreground", "background", "promoted"])
def test_read_back_is_byte_identical_in_both_classes(conn, cls):
    spec = CFG.kv_spec(NUM_BLOCKS)
    kvc = KVConnector(conn, spec, f"awaited-bytes-{cls}-{conn.shm_active}", MAX_REQ_BLOCKS)
    prompt, caches = _prompt(6), _caches(spec)
    ids = np.array([7, 1, 4], dtype=np.int32)
    cell = {"value": FG if cls == "foreground" else BG}

    async def drive():
        bound = wire.SAVE_CLASS.set(cell)
        try:
            write = asyncio.ensure_future(kvc.save(prompt, caches, ids))
            if cls == "promoted":
                await asyncio.sleep(0)
                cell["value"] = FG
            assert await write == 2 * CFG.n_layers * PROMPT_BLOCKS
        finally:
            wire.SAVE_CLASS.reset(bound)
        return await kvc.load(prompt, spec.make_caches(), np.arange(PROMPT_BLOCKS))

    loaded, n = _run(drive())
    assert n == PROMPT_BLOCKS
    for (k, v), (got_k, got_v) in zip(caches, loaded):
        for src, got in ((k, got_k), (v, got_v)):
            want = np.asarray(gather_blocks(src, jnp.asarray(ids)))
            assert np.asarray(gather_blocks(got, jnp.arange(PROMPT_BLOCKS))).tobytes() == want.tobytes()
