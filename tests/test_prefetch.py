"""The overlapped admission pipeline: gate-free fetch / short install.

Covers the two-phase split of a load (KVConnector.start_fetch ->
LayerwisePrefetch.install), the staging-pool reservation accounting it
leans on (cancellation must return every slot), fetch coalescing across a
wave of admissions, and the engine-level payoffs the split exists for:
store I/O never holds the device gate, and a prefix HIT is no slower
end-to-end than recomputing (the whole point of the store).
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import infinistore_tpu as its
from infinistore_tpu import tracing
from infinistore_tpu.connector import KVConnector
from infinistore_tpu.engine import ContinuousBatchingHarness, EngineKVAdapter
from infinistore_tpu.models import LlamaConfig, init_params
from infinistore_tpu.tpu.layerwise import (
    LayerwisePrefetch,
    PartialReadError,
    PrefetchDiscarded,
)
from infinistore_tpu.tpu.paged import PagedKVCacheSpec, gather_blocks
from infinistore_tpu.tpu.staging import HostStagingPool, StagingPoolExhausted

SPEC = PagedKVCacheSpec(
    num_layers=3, num_blocks=16, block_tokens=8, num_kv_heads=2, head_dim=32,
    dtype=jnp.float32,
)

CFG = LlamaConfig(
    vocab=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128,
    block_tokens=8, dtype=jnp.float32,
)
NUM_BLOCKS = 32
MAX_REQ_BLOCKS = 4


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture()
def server():
    srv = its.start_local_server(
        prealloc_bytes=64 << 20, block_bytes=64 << 10, enable_shm=True
    )
    yield srv
    srv.stop()


@pytest.fixture()
def conn(server):
    c = its.InfinityConnection(
        its.ClientConfig(
            host_addr="127.0.0.1", service_port=server.port, log_level="error"
        )
    )
    c.connect()
    yield c
    c.close()


def _rand_caches(seed):
    out = []
    for layer in range(SPEC.num_layers):
        k = jax.random.normal(
            jax.random.PRNGKey(seed * 100 + layer), SPEC.cache_shape, jnp.float32
        )
        v = jax.random.normal(
            jax.random.PRNGKey(seed * 100 + 50 + layer), SPEC.cache_shape, jnp.float32
        )
        out.append((k, v))
    return out


async def _drain_pool(pool, timeout_s=3.0):
    """Wait for async region releases (install marks regions consumed from
    an executor thread) to land back in the pool."""
    for _ in range(int(timeout_s / 0.02)):
        if pool.slots_in_use == 0:
            return
        await asyncio.sleep(0.02)
    raise AssertionError(f"staging slots leaked: {pool.slots_in_use} in use")


# -- staging-pool reservation accounting -------------------------------------


def test_staging_pool_reserve_release_accounting():
    pool = HostStagingPool(16 * 1024, 1024)
    assert pool.slots_in_use == 0
    a = pool.reserve(6)
    b = pool.reserve(10)
    assert pool.slots_in_use == 16
    with pytest.raises(StagingPoolExhausted):
        pool.reserve(1)
    a.release()
    assert pool.slots_in_use == 10
    a.release()  # idempotent
    assert pool.slots_in_use == 10
    # Freed run is reusable, and contiguity is honored: 6 free in one run.
    c = pool.reserve(6)
    assert pool.slots_in_use == 16
    b.release()
    c.release()
    assert pool.slots_in_use == 0
    with pytest.raises(ValueError):
        pool.reserve(0)


def test_staging_pool_reserve_needs_contiguity():
    pool = HostStagingPool(8 * 1024, 1024)
    holds = [pool.reserve(2) for _ in range(4)]
    holds[0].release()
    holds[2].release()
    # 4 slots free but split 2+2: a 3-slot run must NOT fit, 2 must.
    with pytest.raises(StagingPoolExhausted):
        pool.reserve(3)
    lease = pool.reserve(2)
    assert lease.num_slots == 2
    for h in holds[1::2] + [lease]:
        h.release()
    assert pool.slots_in_use == 0


# -- connector-level fetch/install -------------------------------------------


def test_start_fetch_install_roundtrips_bytes(conn):
    kvc = KVConnector(conn, SPEC, "pf-rt", max_blocks=8)
    caches = _rand_caches(1)
    toks = list(range(32))
    src = np.array([3, 7, 1, 9], np.int32)
    dst = np.array([8, 0, 14, 2], np.int32)

    async def drive():
        await kvc.save(toks, caches, src)
        h = kvc.start_fetch(toks)
        assert h.hit_blocks == 4 and h.n_blocks == 4
        await h.primed()  # gate-free wait: the store I/O happens here
        out, n = await h.install(SPEC.make_caches(), dst)
        assert n == 4
        for layer in range(SPEC.num_layers):
            for side in (0, 1):
                want = np.asarray(gather_blocks(caches[layer][side], jnp.asarray(src)))
                got = np.asarray(
                    gather_blocks(out[layer][side], jnp.asarray(dst, jnp.int32))
                )
                np.testing.assert_array_equal(want, got)
        await _drain_pool(kvc._prefetch_pool)

    asyncio.run(drive())


def test_prefetch_wraps_regions_when_pool_is_shallow(conn):
    """regions < num_layers: the pipeline double-buffers — a region refills
    only after install consumed its occupant — and the bytes still land
    exactly (install goes run by run: the layers staged when it looks)."""
    kvc = KVConnector(conn, SPEC, "pf-wrap", max_blocks=8)
    caches = _rand_caches(2)
    toks = list(range(32))
    src = np.array([2, 11, 5, 6], np.int32)
    dst = np.array([1, 4, 9, 13], np.int32)
    n = 4
    # Room for exactly 2 regions of 2*n blocks: forces the wrap with L=3.
    tiny = HostStagingPool(2 * 2 * n * SPEC.block_nbytes, SPEC.block_nbytes, conn=conn)

    async def drive():
        await kvc.save(toks, caches, src)
        h = kvc.start_fetch(toks, prefetch_pool=tiny)
        assert h.regions == 2 < SPEC.num_layers
        out, loaded = await h.install(SPEC.make_caches(), dst)
        assert loaded == 4
        for layer in range(SPEC.num_layers):
            want = np.asarray(gather_blocks(caches[layer][0], jnp.asarray(src)))
            got = np.asarray(gather_blocks(out[layer][0], jnp.asarray(dst, jnp.int32)))
            np.testing.assert_array_equal(want, got)
        await _drain_pool(tiny)

    asyncio.run(drive())


@pytest.mark.parametrize("regions, runs", [(None, [(0, 3)]), (2, [(0, 2), (2, 1)])])
def test_install_is_one_dispatch_a_run_of_staged_layers(conn, regions, runs):
    """The device is reached once a RUN of staged layers: every layer staged
    (a region a layer, the default) is one executor call for the whole hit;
    two regions of three layers are the two runs the wrap forces. The
    connector's ``install_layers`` / ``install_dispatches`` count what the
    ``install_upload`` spans show, and ``on_layer`` fires once a layer, in
    layer order, with the layer's updated tensors."""
    kvc = KVConnector(conn, SPEC, f"pf-runs-{regions}", max_blocks=8)
    caches = _rand_caches(7)
    toks = list(range(32))
    src = np.array([5, 0, 12, 3], np.int32)
    dst = np.array([7, 2, 15, 4], np.int32)
    n = 4
    pool = None
    if regions is not None:
        pool = HostStagingPool(
            regions * 2 * n * SPEC.block_nbytes, SPEC.block_nbytes, conn=conn
        )
    seen = []

    async def drive():
        await kvc.save(toks, caches, src)
        h = kvc.start_fetch(toks, prefetch_pool=pool)
        assert h.regions == (regions or SPEC.num_layers)
        await h.primed()  # a region a layer: waits for every layer's read
        assert all(h.layer_ready(l) for l in range(h.regions))
        out, loaded = await h.install(
            SPEC.make_caches(), dst, on_layer=lambda l, tensors: seen.append((l, tensors))
        )
        assert loaded == n and h.blocks_installed == 2 * n * SPEC.num_layers
        await _drain_pool(pool or kvc._prefetch_pool)
        return out

    rec = tracing.configure(enabled=True, capacity=4096, slow_op_us=0)
    rec.clear()
    try:
        out = asyncio.run(drive())
        uploads = sorted(
            (s for s in rec.snapshot() if s["name"] == "install_upload"),
            key=lambda s: s["start_us"],
        )
    finally:
        tracing.configure(enabled=False)
    assert [(u["attrs"]["layer"], u["attrs"]["layers"]) for u in uploads] == runs
    c = kvc.hit_counters
    assert c["install_dispatches"] == len(uploads) == len(runs)
    assert c["install_layers"] == sum(u["attrs"]["layers"] for u in uploads) == SPEC.num_layers
    assert c["install_upload_bytes"] == sum(u["attrs"]["bytes"] for u in uploads)
    assert [l for l, _ in seen] == list(range(SPEC.num_layers))
    for layer, tensors in seen:
        assert tensors is out[layer]
        for side in (0, 1):
            want = np.asarray(gather_blocks(caches[layer][side], jnp.asarray(src)))
            got = np.asarray(gather_blocks(tensors[side], jnp.asarray(dst, jnp.int32)))
            np.testing.assert_array_equal(want, got)


@pytest.mark.parametrize("missing", [False, True], ids=["store_error", "key_not_found"])
@pytest.mark.parametrize("k", [1, 2])
def test_store_failure_at_layer_k_keeps_the_layers_before_it(conn, k, missing):
    """Layer ``k``'s read fails once every other layer has landed: the run
    before it was scattered (its inputs DONATED), the layers from ``k`` on are
    the caller's own arrays, and that list is the one valid one: in
    ``PartialReadError.caches``, or returned with 0 blocks where the store
    said the keys are gone (a miss)."""
    kvc = KVConnector(conn, SPEC, f"pf-fail-{k}-{missing}", max_blocks=8)
    caches = _rand_caches(8)
    toks = list(range(32))
    ids = np.array([1, 6, 9, 13], np.int32)
    n = 4

    async def drive():
        await kvc.save(toks, caches, ids)
        pool = kvc._ensure_prefetch_pool()
        fail_now = asyncio.Event()

        async def submit(blocks, nbytes):
            if f"/L{k}/" in blocks[0][0]:
                await fail_now.wait()
                if missing:
                    raise its.InfiniStoreKeyNotFound("gone")
                raise its.InfiniStoreException("store down")
            await conn.read_cache_async(blocks, nbytes, pool.base_ptr)

        h = LayerwisePrefetch(
            conn, pool, SPEC, kvc._key_fn(kvc._chains(toks)[:n]), n, SPEC.num_layers,
            submit=submit, counters=kvc.hit_counters,
        )
        while not all(h.layer_ready(l) for l in range(SPEC.num_layers) if l != k):
            await asyncio.sleep(0.005)
        fail_now.set()
        fresh = SPEC.make_caches()
        if missing:
            out, loaded = await h.install(fresh, ids)
            assert loaded == 0
        else:
            with pytest.raises(PartialReadError) as err:
                await h.install(fresh, ids)
            out = err.value.caches
            assert isinstance(err.value.cause, its.InfiniStoreException)
        assert len(out) == SPEC.num_layers
        for layer in range(SPEC.num_layers):
            for side in (0, 1):
                got = np.asarray(gather_blocks(out[layer][side], jnp.asarray(ids)))
                if layer < k:  # the run before the failure: scattered
                    want = np.asarray(gather_blocks(caches[layer][side], jnp.asarray(ids)))
                    np.testing.assert_array_equal(want, got)
                else:  # untouched: still the caller's array
                    assert out[layer][side] is fresh[layer][side]
                    assert not got.any()
        assert h.blocks_installed == 2 * n * k
        assert kvc.hit_counters["install_layers"] == k
        assert kvc.hit_counters["install_dispatches"] == 1
        await _drain_pool(pool)

    asyncio.run(asyncio.wait_for(drive(), 30))


@pytest.mark.parametrize(
    "speculative,heavy", [(False, True), (True, True), (True, False)],
    ids=["foreground", "speculative", "speculative-light"],
)
def test_speculative_prefetch_sends_one_layer_at_a_time_until_promoted(
    conn, monkeypatch, speculative, heavy
):
    """A region a layer starts every layer's read at construction; a
    speculative prefetch (BACKGROUND class: its request is not admitted yet)
    keeps ``SPECULATIVE_READ_BYTES`` of reads in flight (of a heavy hit ONE
    layer's), so that ``promote()`` finds the rest unsent and sends it
    foreground: a read at the server keeps the class it went out with. A hit
    whose layers all fit goes out whole. Either way the same bytes reach the
    same blocks."""
    from infinistore_tpu import wire
    from infinistore_tpu.tpu import layerwise

    if heavy:  # a layer's hit (4 blocks of a K and a V) is the whole window
        monkeypatch.setattr(layerwise, "SPECULATIVE_READ_BYTES", SPEC.region_nbytes(4))
    kvc = KVConnector(conn, SPEC, f"pf-spec-{speculative}-{heavy}", max_blocks=8)
    caches = _rand_caches(9)
    toks = list(range(32))
    ids = np.array([2, 11, 4, 8], np.int32)
    n = 4
    bg, fg = wire.PRIORITY_BACKGROUND, wire.PRIORITY_FOREGROUND

    async def drive():
        await kvc.save(toks, caches, ids)
        pool = kvc._ensure_prefetch_pool()
        cell = {"value": bg if speculative else fg}
        sent, land = [], [asyncio.Event() for _ in range(SPEC.num_layers)]

        async def submit(blocks, nbytes):
            sent.append(cell["value"])
            await land[len(sent) - 1].wait()
            await conn.read_cache_async(blocks, nbytes, pool.base_ptr)

        async def settle():
            for _ in range(5):
                await asyncio.sleep(0)

        h = LayerwisePrefetch(
            conn, pool, SPEC, kvc._key_fn(kvc._chains(toks)[:n]), n, SPEC.num_layers,
            submit=submit, priority_cell=cell,
        )
        await settle()
        if speculative and not heavy:
            assert sent == [bg] * SPEC.num_layers  # light layers: all at once
            for ev in land:
                ev.set()
            await h.primed()
        elif speculative:
            assert sent == [bg]  # layer 0 alone
            land[0].set()
            while not h.layer_ready(0):
                await asyncio.sleep(0.005)
            await settle()
            assert sent == [bg, bg]  # layer 1 once layer 0 landed
            h.promote()
            await settle()
            assert sent == [bg, bg, fg]  # the rest at admission, foreground
            # The background read lands BEHIND the foreground rest: primed()
            # waits for it too (else the install would, under the gate).
            primed = asyncio.ensure_future(h.primed())
            land[2].set()
            while not h.layer_ready(2):
                await asyncio.sleep(0.005)
            await settle()
            assert not primed.done()
            land[1].set()
            await primed
        else:
            assert sent == [fg] * SPEC.num_layers  # all at construction
            for ev in land:
                ev.set()
            await h.primed()
        assert all(h.layer_ready(l) for l in range(SPEC.num_layers))
        out, loaded = await h.install(SPEC.make_caches(), ids)
        assert loaded == n
        for layer in range(SPEC.num_layers):
            for side in (0, 1):
                want = np.asarray(gather_blocks(caches[layer][side], jnp.asarray(ids)))
                got = np.asarray(gather_blocks(out[layer][side], jnp.asarray(ids)))
                np.testing.assert_array_equal(want, got)
        await _drain_pool(pool)

    asyncio.run(asyncio.wait_for(drive(), 30))


def test_speculative_prefetch_discarded_while_it_holds_reads_back(conn, monkeypatch):
    """Discard with layers still held back: none of them is sent, every
    task ends and the lease goes back."""
    from infinistore_tpu import wire

    from infinistore_tpu.tpu import layerwise

    monkeypatch.setattr(layerwise, "SPECULATIVE_READ_BYTES", SPEC.region_nbytes(4))
    kvc = KVConnector(conn, SPEC, "pf-spec-discard", max_blocks=8)
    toks = list(range(32))
    ids = np.array([2, 11, 4, 8], np.int32)

    async def drive():
        await kvc.save(toks, _rand_caches(10), ids)
        pool = kvc._ensure_prefetch_pool()
        sent, land = [], asyncio.Event()

        async def submit(blocks, nbytes):
            sent.append(blocks[0][0])
            await land.wait()
            await conn.read_cache_async(blocks, nbytes, pool.base_ptr)

        h = LayerwisePrefetch(
            conn, pool, SPEC, kvc._key_fn(kvc._chains(toks)[:4]), 4, SPEC.num_layers,
            submit=submit, priority=wire.PRIORITY_BACKGROUND,
        )
        await asyncio.sleep(0.01)
        assert len(sent) == 1
        discard = asyncio.ensure_future(h.discard())
        await asyncio.sleep(0.01)
        land.set()  # the read in flight drains into leased memory
        await asyncio.wait_for(discard, 10)
        assert len(sent) == 1
        with pytest.raises(PrefetchDiscarded):
            await h.install(SPEC.make_caches(), ids)
        await _drain_pool(pool)

    asyncio.run(asyncio.wait_for(drive(), 30))


def test_prefetch_pool_holds_four_full_depth_prefetches(conn):
    """The connector's arena is sized by the prefetch's own rule, a region a
    layer however many layers (ten here): four prefetches of the longest hit
    reserve ``num_layers`` regions each, none wraps, and the fifth is
    backpressure."""
    spec = PagedKVCacheSpec(
        num_layers=10, num_blocks=16, block_tokens=8, num_kv_heads=1, head_dim=16,
        dtype=jnp.float32,
    )
    kvc = KVConnector(conn, spec, "pf-four", max_blocks=8)
    caches = spec.make_caches()
    prompts = [list(range(1000 * i, 1000 * i + 64)) for i in range(5)]
    ids = np.arange(8, dtype=np.int32)

    async def drive():
        for toks in prompts:
            await kvc.save(toks, caches, ids)
        handles = [kvc.start_fetch(toks) for toks in prompts[:4]]
        assert [h.n_blocks for h in handles] == [8] * 4
        assert [h.regions for h in handles] == [spec.num_layers] * 4
        pool = kvc._prefetch_pool
        assert pool.slots_in_use == pool.num_slots
        with pytest.raises(StagingPoolExhausted):
            kvc.start_fetch(prompts[4])
        for h in handles:
            await h.primed()
            assert all(h.layer_ready(layer) for layer in range(spec.num_layers))
            out, loaded = await h.install(spec.make_caches(), ids)
            assert loaded == 8
        assert kvc.hit_counters["install_layers"] == 4 * spec.num_layers
        assert kvc.hit_counters["install_dispatches"] == 4
        await _drain_pool(pool)

    asyncio.run(asyncio.wait_for(drive(), 30))


def test_discard_returns_pool_to_baseline_and_counts_waste(conn):
    kvc = KVConnector(conn, SPEC, "pf-disc", max_blocks=8)
    caches = _rand_caches(3)
    toks = list(range(32))

    async def drive():
        await kvc.save(toks, caches, np.arange(4, dtype=np.int32))
        h = kvc.start_fetch(toks)
        await h.primed()  # let some layers actually stage (they become waste)
        await h.discard()
        assert kvc._prefetch_pool.slots_in_use == 0, "discard leaked staging slots"
        assert h.wasted_blocks == h.blocks_fetched > 0
        with pytest.raises(PrefetchDiscarded):
            await h.install(SPEC.make_caches(), np.arange(4, dtype=np.int32))
        # The pool is immediately reusable at full depth.
        h2 = kvc.start_fetch(toks)
        out, n = await h2.install(SPEC.make_caches(), np.arange(4, dtype=np.int32))
        assert n == 4
        await _drain_pool(kvc._prefetch_pool)

    asyncio.run(drive())


def test_raced_eviction_mid_fetch_reports_miss_and_releases(conn):
    kvc = KVConnector(conn, SPEC, "pf-race", max_blocks=8)
    caches = _rand_caches(4)
    toks = list(range(32))

    async def drive():
        await kvc.save(toks, caches, np.arange(4, dtype=np.int32))
        h = kvc.start_fetch(toks)  # lookup hits...
        kvc.drop(toks)  # ...but the blocks race away before the reads land
        out, n = await h.install(SPEC.make_caches(), np.arange(4, dtype=np.int32))
        assert n == 0, "raced-away blocks must read as a miss, never stale bytes"
        await _drain_pool(kvc._prefetch_pool)

    asyncio.run(drive())


def test_wave_of_fetches_coalesces_store_reads(conn):
    """Concurrent admissions' fetches merge into shared batched store calls
    (what a StripedConnection then splits across stripes) instead of one
    read per request per layer."""
    kvc = KVConnector(conn, SPEC, "pf-coal", max_blocks=8)
    caches = _rand_caches(5)
    toks_a = list(range(32))
    toks_b = list(range(500, 532))

    async def drive():
        await kvc.save(toks_a, caches, np.arange(4, dtype=np.int32))
        await kvc.save(toks_b, caches, np.arange(4, 8, dtype=np.int32))
        ha = kvc.start_fetch(toks_a)
        hb = kvc.start_fetch(toks_b)
        oa, na = await ha.install(SPEC.make_caches(), np.arange(4, dtype=np.int32))
        ob, nb = await hb.install(SPEC.make_caches(), np.arange(4, dtype=np.int32))
        assert na == 4 and nb == 4
        co = kvc._coalescer
        assert co.submissions == 2 * SPEC.num_layers
        assert co.calls < co.submissions, "wave reads never coalesced"
        assert co.max_batch >= 2
        await _drain_pool(kvc._prefetch_pool)

    asyncio.run(drive())


def test_exhausted_arena_raises_not_hangs(conn):
    kvc = KVConnector(conn, SPEC, "pf-full", max_blocks=8)
    caches = _rand_caches(6)
    toks = list(range(32))
    # An arena that cannot hold even one double-buffered pipeline.
    tiny = HostStagingPool(SPEC.block_nbytes, SPEC.block_nbytes, conn=conn)

    async def drive():
        await kvc.save(toks, caches, np.arange(4, dtype=np.int32))
        with pytest.raises(StagingPoolExhausted):
            kvc.start_fetch(toks, prefetch_pool=tiny)

    asyncio.run(drive())


# -- engine-level: the payoffs -----------------------------------------------


def _harness(conn, params, model_id, verify=True):
    kvc = KVConnector(conn, CFG.kv_spec(NUM_BLOCKS), model_id,
                      max_blocks=MAX_REQ_BLOCKS)
    return ContinuousBatchingHarness(
        EngineKVAdapter(kvc), params, CFG, NUM_BLOCKS, MAX_REQ_BLOCKS,
        verify=verify,
    )


def _prompt(seed, blocks=MAX_REQ_BLOCKS):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab, size=blocks * CFG.block_tokens).tolist()


def test_engine_prefetch_cancelled_by_alloc_wait_releases_staging(conn, params):
    """A request whose speculative fetch already ran but whose admission is
    cancelled while queued for device blocks must hand every staging slot
    back (accounting returns to baseline) and count the fetch as waste."""
    h = _harness(conn, params, "pf-eng-cancel", verify=False)
    p = _prompt(1)

    async def drive():
        await h.run_request(p)  # seed the store so the prefetch has a hit
        h.stats.clear()
        blockers = await h.pool.alloc(NUM_BLOCKS)  # exhaust the block pool
        task = asyncio.ensure_future(h.run_request(p))
        await asyncio.sleep(0.1)  # fetch staged; alloc still backpressured
        assert not task.done()
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        await h.pool.free(blockers)
        pool = h.adapter.connector._prefetch_pool
        assert pool is not None
        await _drain_pool(pool)
        m = h.metrics()
        assert m["prefetch_waste"] > 0, "cancelled prefetch not counted as waste"
        # The harness still serves the same prompt afterwards, correctly.
        s = await h.run_request(p)
        assert s.loaded_blocks == MAX_REQ_BLOCKS

    asyncio.run(asyncio.wait_for(drive(), 30))


def test_engine_raced_eviction_falls_back_to_recompute(conn, params):
    """Prefix evicted between the admission probe and the fetch: the
    request recomputes and its bytes still verify against the model's own
    prefill oracle — and the staging arena ends at baseline."""
    h = _harness(conn, params, "pf-eng-race", verify=True)
    p = _prompt(2)

    async def drive():
        await h.run_request(p)  # seed
        h.stats.clear()
        task = asyncio.ensure_future(h.run_request(p))
        await asyncio.sleep(0)  # lookup done, reads submitted, none landed
        h.adapter.evict_request(p)  # the race
        s = await task
        assert s.verified, "recompute after raced eviction delivered wrong bytes"
        assert s.computed_blocks == MAX_REQ_BLOCKS
        if s.raced_eviction:  # the drop won the race (timing-dependent)
            assert s.loaded_blocks == 0
        pool = h.adapter.connector._prefetch_pool
        await _drain_pool(pool)

    asyncio.run(asyncio.wait_for(drive(), 30))


def test_engine_hit_admission_not_slower_than_miss(conn):
    """THE regression the split exists for: a prefix hit must not lose to
    recomputing, and what keeps it from losing is structural: store I/O is
    off the gate and overlapped with the admission. Held here from the spans
    and stamps the engine records, pair by pair, with no clock compared
    against another: every layer's store read of a hit BEGINS before the
    request asks for the exclusive gate; none begins inside the install's
    hold; ``gate_hold_us`` covers the install and nothing else of the
    request; a miss never holds the gate for store work. The ratio itself
    (``ttft_hit_p50_ms`` against ``ttft_miss_p50_ms``) is the chip's to
    read, in the benchmark's reuse cells.

    Uses a model big enough that recompute has real cost (four layers: a
    hit is four store reads and four uploads)."""
    big = LlamaConfig(
        vocab=256, dim=256, n_layers=4, n_heads=4, n_kv_heads=2, ffn_dim=512,
        block_tokens=16, dtype=jnp.float32,
    )
    big_params = init_params(big, jax.random.PRNGKey(1))
    kvc = KVConnector(conn, big.kv_spec(NUM_BLOCKS), "pf-eng-hitmiss",
                      max_blocks=MAX_REQ_BLOCKS)
    h = ContinuousBatchingHarness(
        EngineKVAdapter(kvc), big_params, big, NUM_BLOCKS, MAX_REQ_BLOCKS,
        verify=False,
    )

    def prompt(seed):
        rng = np.random.default_rng(seed)
        return rng.integers(
            0, big.vocab, size=MAX_REQ_BLOCKS * big.block_tokens
        ).tolist()

    pairs = 12

    async def drive():
        seeds = [prompt(100 + i) for i in range(pairs)]
        for p in seeds:
            await h.run_request(p)  # seed the store
        h.stats.clear()
        rec.clear()
        for i, p in enumerate(seeds):
            await h.run_request(p)  # hit
            await h.run_request(prompt(200 + i))  # miss (cold prompt)
        return h.metrics()

    rec = tracing.configure(enabled=True, capacity=1 << 16, slow_op_us=0)
    try:
        m = asyncio.run(drive())
        recorded = rec.snapshot()
    finally:
        tracing.configure(enabled=False)
    assert m["hit_rate"] > 0
    hits, misses = h.stats[0::2], h.stats[1::2]
    assert len(hits) == len(misses) == pairs
    assert all(s.loaded_blocks == MAX_REQ_BLOCKS for s in hits)
    assert not any(s.loaded_blocks for s in misses)

    def trace_of(stats):
        spans = [s for s in recorded if s["trace_id"] == stats.trace_id]
        (root,) = [s for s in spans if s["name"] == "engine_request"]
        return spans, root, dict(root["stages"])

    for i, s in enumerate(hits):
        spans, root, stamps = trace_of(s)
        fetches = [x for x in spans if x["name"] == "fetch_layer"]
        (wait,) = [
            x for x in spans
            if x["name"] == "gate_wait" and x["attrs"]["mode"] == "expedite"
        ]
        (inst,) = [x for x in spans if x["name"] == "install"]
        assert sorted(x["attrs"]["layer"] for x in fetches) == list(range(big.n_layers))
        # Every layer's read was submitted (`region_free`) before the request
        # asked for the gate: the store's round trips ran beside the
        # admission, and the pipeline was full (`primed`) by then.
        begun = [dict(x["stages"])["region_free"] for x in fetches]
        assert max(begun) <= stamps["primed"] <= wait["start_us"], (i, begun, wait)
        # The exclusive hold runs from the gate's grant to the `install`
        # stamp: no store read begins inside it, ...
        hold = (wait["end_us"], stamps["install"])
        assert not [t for t in begun if hold[0] <= t <= hold[1]], (i, begun, hold)
        # ... it holds the install span and, of this request, nothing that is
        # not the install's own child: no compute, no save, no fetch ...
        assert hold[0] <= inst["start_us"] <= inst["end_us"] <= hold[1], (i, inst, hold)
        inside = [
            x for x in spans
            if hold[0] <= x["start_us"] <= hold[1] and x is not inst and x is not root
        ]
        assert {x["name"] for x in inside} <= {"install_staged_wait", "install_upload"}, (
            i, [x["name"] for x in inside],
        )
        assert all(x["parent_id"] == inst["span_id"] for x in inside)
        # ... and `gate_hold_us`, stamped around it, covers the install (the
        # span clock counts whole us).
        assert inst["end_us"] - inst["start_us"] <= s.gate_hold_us + 2, (i, s, inst)
        assert s.fetch_us > 0 and 0.0 < s.overlap_fraction <= 1.0
        assert not [x for x in spans if x["name"] == "compute"]
    for i, s in enumerate(misses):
        spans, root, stamps = trace_of(s)
        # A miss holds the gate for its compute alone: no store work under it.
        assert s.gate_hold_us == 0.0 and s.fetch_us == 0.0, (i, s)
        names = {x["name"] for x in spans}
        assert "compute" in names and not names & {"install", "fetch_layer"}, (i, names)
        assert "install" not in stamps


def test_engine_overlap_metrics_are_non_degenerate(conn, params):
    """The new bench metrics must be present and meaningful: installs hold
    the gate for a measurable, nonzero time; the fetch overlap fraction is
    a real fraction; waste is a ratio in [0, 1]."""
    h = _harness(conn, params, "pf-eng-metrics", verify=False)

    async def drive():
        fams = [_prompt(300 + i) for i in range(3)]
        for p in fams:
            await h.run_request(p)  # seed
        h.stats.clear()
        sched = []
        for i in range(6):
            sched.append(fams[i % 3])  # hits
            sched.append(_prompt(400 + i))  # misses
        return await h.run(sched, concurrency=4)

    m = asyncio.run(drive())
    for key in (
        "p50_gate_hold_us", "p99_gate_hold_us", "overlap_fraction",
        "prefetch_waste", "prefetch_fallbacks",
        "p50_prefix_ready_hit_us", "p50_prefix_ready_miss_us",
    ):
        assert key in m, f"metric {key} missing"
    assert m["p50_gate_hold_us"] > 0, "no install ever held the gate?"
    assert 0.0 < m["overlap_fraction"] <= 1.0, m["overlap_fraction"]
    assert 0.0 <= m["prefetch_waste"] <= 1.0
    # Store I/O no longer queues admissions at the gate: a MISS never
    # installs, so it holds the gate for store work exactly never (its
    # gate_stall still reports the COMPUTE phase's queue time).
    misses = [s for s in h.stats if not s.loaded_blocks]
    assert misses and all(s.gate_hold_us == 0.0 for s in misses)
    assert all(s.fetch_us == 0.0 for s in misses)
    # Every request's store fetch ran without holding the device gate:
    # overlap 1.0 means the fetch completed before the gate was even
    # acquired (the uncontended case); anything in (0, 1] is legal.
    per_req = [s.overlap_fraction for s in h.stats if s.overlap_fraction is not None]
    assert per_req and all(0.0 < f <= 1.0 for f in per_req)


def test_engine_fallback_when_arena_exhausted(conn, params):
    """StagingPoolExhausted at admission is backpressure: the request takes
    the one-phase gated load and still gets its blocks."""
    h = _harness(conn, params, "pf-eng-fallback", verify=True)
    p = _prompt(3)

    async def drive():
        await h.run_request(p)  # seed
        h.stats.clear()
        kvc = h.adapter.connector
        # Starve the arena: every slot reserved by someone else.
        arena = kvc._ensure_prefetch_pool()
        hog = arena.reserve(arena.num_slots)
        try:
            s = await h.run_request(p)
        finally:
            hog.release()
        assert h.prefetch_fallbacks == 1
        assert s.loaded_blocks == MAX_REQ_BLOCKS and s.verified
        m = h.metrics()
        assert m["prefetch_fallbacks"] == 1

    asyncio.run(asyncio.wait_for(drive(), 30))
