"""Self-healing cluster layer: per-member circuit breakers, R=2 rendezvous
replication with read failover, attributable per-member health, and the
stage-time degrade fix (docs/robustness.md is the contract narrative).
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import infinistore_tpu as its
from infinistore_tpu.cluster import (
    CircuitBreaker,
    ClusterKVConnector,
    rendezvous_owner,
    rendezvous_ranked,
)
from infinistore_tpu.tpu import PagedKVCacheSpec, gather_blocks

SPEC = PagedKVCacheSpec(
    num_layers=2, num_blocks=16, block_tokens=8, num_kv_heads=2, head_dim=32,
    dtype=jnp.bfloat16,
)


# ---------------------------------------------------------------------------
# CircuitBreaker state machine (fake clock: every transition is exact).
# ---------------------------------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _breaker(clock, **kw):
    kw.setdefault("fail_threshold", 3)
    kw.setdefault("probe_backoff_s", 1.0)
    kw.setdefault("max_backoff_s", 4.0)
    kw.setdefault("jitter_frac", 0.0)  # exact windows for the clock tests
    return CircuitBreaker(clock=clock, seed=0, **kw)


def test_breaker_opens_only_on_consecutive_failures():
    clk = _Clock()
    br = _breaker(clk)
    for _ in range(2):
        br.record_failure()
    br.record_success()  # streak broken
    assert br.state == CircuitBreaker.CLOSED
    for _ in range(2):
        br.record_failure()
    assert br.state == CircuitBreaker.CLOSED and br.allow()
    br.record_failure()  # third consecutive
    assert br.state == CircuitBreaker.OPEN
    assert not br.allow()


def test_breaker_half_open_probe_window_and_recovery():
    clk = _Clock()
    br = _breaker(clk)
    for _ in range(3):
        br.record_failure()
    assert not br.allow()  # window not elapsed
    clk.t = 1.0
    assert br.allow()  # THE probe
    assert br.state == CircuitBreaker.HALF_OPEN
    assert not br.allow()  # one probe in flight is enough
    assert br.record_success() is True  # recovery reported
    assert br.state == CircuitBreaker.CLOSED and br.allow()
    assert br.record_success() is False  # steady-state success is not recovery


def test_breaker_failed_probe_doubles_backoff_to_cap():
    clk = _Clock()
    br = _breaker(clk)
    for _ in range(3):
        br.record_failure()
    for expect in (1.0, 2.0, 4.0, 4.0):  # capped at max_backoff_s
        clk.t += expect - 0.01
        assert not br.allow(), expect
        clk.t += 0.01
        assert br.allow()
        br.record_failure()  # probe fails -> reopen, doubled
        assert br.state == CircuitBreaker.OPEN


def test_breaker_jitter_is_seeded_and_bounded():
    clk = _Clock()
    spreads = set()
    for seed in range(4):
        br = CircuitBreaker(
            fail_threshold=1, probe_backoff_s=1.0, max_backoff_s=8.0,
            jitter_frac=0.5, seed=seed, clock=clk,
        )
        br.record_failure()
        spreads.add(br.next_probe_at)
        assert 1.0 <= br.next_probe_at <= 1.5
        # Same seed replays the same schedule.
        br2 = CircuitBreaker(
            fail_threshold=1, probe_backoff_s=1.0, max_backoff_s=8.0,
            jitter_frac=0.5, seed=seed, clock=clk,
        )
        br2.record_failure()
        assert br2.next_probe_at == br.next_probe_at
    assert len(spreads) > 1  # members decorrelate


# ---------------------------------------------------------------------------
# Rendezvous ranking (replica placement).
# ---------------------------------------------------------------------------


def test_rendezvous_ranked_head_is_owner_and_drain_preserves_pairings():
    members = ["a:1", "b:2", "c:3", "d:4"]
    roots = [f"r{i}" for i in range(200)]
    for r in roots:
        ranked = rendezvous_ranked(members, r)
        assert sorted(ranked) == [0, 1, 2, 3]
        assert ranked[0] == rendezvous_owner(members, r)
    # Removing one member must not reshuffle pairs it did not appear in:
    # every (owner, successor) pair not involving the drained member stays.
    survivors = members[:3]  # drain d:4
    for r in roots:
        before = [members[i] for i in rendezvous_ranked(members, r)[:2]]
        after = [survivors[i] for i in rendezvous_ranked(survivors, r)[:2]]
        if "d:4" not in before:
            assert after == before


# ---------------------------------------------------------------------------
# Cluster failover / replication / attributable health over live servers.
# ---------------------------------------------------------------------------


@pytest.fixture()
def trio():
    """Three live loopback servers + reconnect-capable connections."""
    servers, conns = [], []
    try:
        for _ in range(3):
            srv = its.start_local_server(
                prealloc_bytes=64 << 20, block_bytes=16 << 10
            )
            conn = its.InfinityConnection(
                its.ClientConfig(
                    host_addr="127.0.0.1", service_port=srv.port,
                    log_level="error", auto_reconnect=True,
                    connect_timeout_ms=500, op_timeout_ms=2000,
                )
            )
            conn.connect()
            servers.append(srv)
            conns.append(conn)
        yield servers, conns
    finally:
        for c in conns:
            try:
                c.close()
            except Exception:
                pass
        for s in servers:
            s.stop()


def _fast_breakers(i, clock=None):
    kw = {} if clock is None else {"clock": clock}
    return CircuitBreaker(
        fail_threshold=2, probe_backoff_s=0.05, max_backoff_s=0.4, seed=i, **kw
    )


def _cluster(conns, **kw):
    kw.setdefault("breaker_factory", _fast_breakers)
    return ClusterKVConnector(conns, SPEC, "heal", max_blocks=8, **kw)


def _rand_caches(seed):
    out = []
    for layer in range(SPEC.num_layers):
        k = jax.random.normal(
            jax.random.PRNGKey(seed * 100 + layer), SPEC.cache_shape, jnp.float32
        ).astype(SPEC.dtype)
        v = jax.random.normal(
            jax.random.PRNGKey(seed * 100 + 50 + layer), SPEC.cache_shape,
            jnp.float32,
        ).astype(SPEC.dtype)
        out.append((k, v))
    return out


def _prompt_with_chain(cluster, want_chain, vocab=1000, tries=400):
    """A 2-block prompt whose (owner, successor) replica chain matches."""
    rng = np.random.default_rng(sum(want_chain))
    for _ in range(tries):
        p = rng.integers(0, vocab, size=2 * SPEC.block_tokens).tolist()
        if cluster.replica_indices(p) == list(want_chain):
            return p
    raise AssertionError(f"no prompt found with chain {want_chain}")


def _kvmap_lens(servers):
    from infinistore_tpu._native import lib as native

    return [int(native.its_server_kvmap_len(s.handle)) for s in servers]


def test_r2_save_mirrors_to_owner_and_successor_only(trio):
    servers, conns = trio
    cluster = _cluster(conns, replicas=2)
    tokens = _prompt_with_chain(cluster, (1, 0))
    caches = _rand_caches(1)
    src = np.array([3, 9], np.int32)
    written = asyncio.run(cluster.save(tokens, caches, src))
    assert written == 2 * 2 * SPEC.num_layers
    lens = _kvmap_lens(servers)
    assert lens[0] > 0 and lens[1] > 0 and lens[2] == 0
    assert lens[0] == lens[1]  # full mirror, not a partial copy
    # drop removes from BOTH replicas.
    assert cluster.drop(tokens) == 2 * 2 * SPEC.num_layers
    assert _kvmap_lens(servers) == [0, 0, 0]


def test_owner_death_degrades_to_replica_reads_byte_correct(trio):
    servers, conns = trio
    cluster = _cluster(conns, replicas=2, degrade=True)
    tokens = _prompt_with_chain(cluster, (2, 0))
    caches = _rand_caches(2)
    src = np.array([1, 5], np.int32)
    asyncio.run(cluster.save(tokens, caches, src))

    servers[2].stop()  # kill the OWNER; successor (member 0) holds the mirror

    assert cluster.lookup(tokens) == 2  # served by the replica, not a miss
    fresh = SPEC.make_caches()
    dst = np.array([6, 2], np.int32)
    loaded, n = asyncio.run(cluster.load(tokens, fresh, dst))
    assert n == 2
    for layer in range(SPEC.num_layers):
        for kind in (0, 1):
            got = np.asarray(
                gather_blocks(loaded[layer][kind], jnp.asarray(dst)), np.float32
            )
            want = np.asarray(
                gather_blocks(caches[layer][kind], jnp.asarray(src)), np.float32
            )
            np.testing.assert_array_equal(got, want)
    health = cluster.health()
    owner, replica = health["members"][2], health["members"][0]
    assert owner["errors"] >= 1 and owner["last_error"] is not None
    assert replica["replica_serves"] >= 2  # lookup + load
    # Replica reads are SERVED ops, not degraded ones.
    assert health["degraded_ops"] == 0


def test_breaker_fast_fails_then_probe_recovers_after_restart(trio):
    servers, conns = trio
    cluster = _cluster(conns, replicas=1, degrade=True)
    victim = 1
    tokens = _prompt_with_chain(cluster, (victim,))
    port = servers[victim].port
    servers[victim].stop()

    # fail_threshold=2 transport errors open the breaker...
    for _ in range(2):
        assert cluster.lookup(tokens) == 0
    h = cluster.health()["members"][victim]
    assert h["breaker_state"] == "open" and h["errors"] == 2
    # ...after which ops fast-fail locally without touching the member.
    before = h["errors"]
    for _ in range(3):
        assert cluster.lookup(tokens) == 0
    h = cluster.health()["members"][victim]
    assert h["errors"] == before  # no new transport attempts
    assert h["fast_fails"] >= 1
    assert cluster.degraded_ops == 5
    assert cluster.health()["members"][victim]["degraded_ops"] == 5
    # Healthy members carry no blame.
    for i in (0, 2):
        m = cluster.health()["members"][i]
        assert m["errors"] == 0 and m["degraded_ops"] == 0

    # Restart on the same port: the next due probe heals the connection and
    # closes the breaker within one probe window.
    import time

    for _ in range(50):
        try:
            servers[victim] = its.start_local_server(
                host="127.0.0.1", service_port=port,
                prealloc_bytes=64 << 20, block_bytes=16 << 10,
            )
            break
        except its.InfiniStoreException:
            time.sleep(0.1)
    else:
        pytest.skip("could not rebind the chaos port")
    deadline = time.time() + 5
    while time.time() < deadline:
        cluster.lookup(tokens)
        h = cluster.health()["members"][victim]
        if h["breaker_state"] == "closed":
            break
        time.sleep(0.02)
    h = cluster.health()["members"][victim]
    assert h["breaker_state"] == "closed"
    assert h["probes"] >= 1 and h["recoveries"] >= 1
    # Fully functional again: a save lands on the restarted member.
    asyncio.run(
        cluster.save(tokens, _rand_caches(3), np.array([4, 7], np.int32))
    )
    assert cluster.lookup(tokens) == 2


def test_strict_mode_raises_only_when_no_replica_serves(trio):
    servers, conns = trio
    cluster = _cluster(conns, replicas=2, degrade=False)
    tokens = _prompt_with_chain(cluster, (0, 1))
    asyncio.run(cluster.save(tokens, _rand_caches(4), np.array([1, 2], np.int32)))
    servers[0].stop()
    # Reads fail over: strict mode stays AVAILABLE while a replica serves.
    assert cluster.lookup(tokens) == 2
    # Writes must not silently under-replicate in strict mode.
    with pytest.raises(its.InfiniStoreException):
        asyncio.run(
            cluster.save(tokens, _rand_caches(4), np.array([1, 2], np.int32))
        )
    servers[1].stop()
    # Exhaust retries until the breaker opens, then the fast-fail path must
    # still raise a TYPED error in strict mode (never return a fake miss).
    for _ in range(4):
        with pytest.raises(its.InfiniStoreException):
            cluster.lookup(tokens)
    stats = cluster.stats()
    assert stats[0].get("unreachable") is True
    assert stats[0]["breaker_state"] in ("open", "half_open")


def test_stage_layer_save_stage_time_error_obeys_degrade():
    """The satellite fix: an InfiniStoreException raised AT STAGE TIME
    (before ship() exists) used to bypass the failure policy and crash the
    engine even with degrade=True."""

    class BoomMember:
        spec = SPEC

        def stage_layer_save(self, *a, **kw):
            raise its.InfiniStoreException("stage-time boom")

        def get_stats(self):
            return {}

    class FakeConn:
        class config:
            host_addr = "x"
            service_port = 1

    # Single member so the boom member is unavoidably the owner.
    soft = ClusterKVConnector(
        [FakeConn()], SPEC, "m", max_blocks=8, degrade=True,
        member_factory=lambda c: BoomMember(),
        breaker_factory=_fast_breakers,
    )
    tokens = list(range(2 * SPEC.block_tokens))
    kv = (jnp.zeros(SPEC.cache_shape, SPEC.dtype),
          jnp.zeros(SPEC.cache_shape, SPEC.dtype))
    ship = soft.stage_layer_save(tokens, 0, kv, np.array([0, 1], np.int32))
    assert asyncio.run(ship()) == 0  # noop ship, engine survives
    assert soft.degraded_ops == 1
    assert soft.health()["members"][0]["errors"] == 1

    strict = ClusterKVConnector(
        [FakeConn()], SPEC, "m", max_blocks=8, degrade=False,
        member_factory=lambda c: BoomMember(),
        breaker_factory=_fast_breakers,
    )
    with pytest.raises(its.InfiniStoreException, match="stage-time boom"):
        strict.stage_layer_save(tokens, 0, kv, np.array([0, 1], np.int32))


def test_partial_read_degrades_only_on_store_causes():
    """A mid-pipeline read failure degrades to "loaded 0, recompute" only
    when the STORE caused it. The reader wraps whatever broke its pipeline
    (so the live caches travel with the error); a device error inside an
    install must reach the engine, not read as a cache miss."""
    from infinistore_tpu.tpu.layerwise import PartialReadError

    class Member:
        spec = SPEC
        cause: Exception = None

        async def load(self, token_ids, caches, block_ids, **kw):
            raise PartialReadError(list(caches), self.cause)

    class FakeConn:
        class config:
            host_addr = "x"
            service_port = 1

    member = Member()
    soft = ClusterKVConnector(
        [FakeConn()], SPEC, "m", max_blocks=8, degrade=True,
        member_factory=lambda c: member,
        breaker_factory=_fast_breakers,
    )
    tokens = list(range(2 * SPEC.block_tokens))
    caches = SPEC.make_caches()
    ids = np.array([0, 1], np.int32)

    member.cause = its.InfiniStoreException("connection reset mid-read")
    out, n = asyncio.run(soft.load(tokens, caches, ids))
    assert n == 0 and soft.degraded_ops == 1
    assert soft.health()["members"][0]["errors"] == 1

    member.cause = RuntimeError("INVALID_ARGUMENT: donated buffer")
    with pytest.raises(PartialReadError) as err:
        asyncio.run(soft.load(tokens, caches, ids))
    assert isinstance(err.value.cause, RuntimeError)
    assert soft.degraded_ops == 1  # not counted as a degraded miss
    # The member answered: a device error must not trip its breaker.
    assert soft.health()["members"][0]["errors"] == 1


def test_per_member_stats_carry_health_and_aggregate_persists(trio):
    _, conns = trio
    cluster = _cluster(conns, replicas=1, degrade=True)
    stats = cluster.stats()
    assert len(stats) == 3
    for s in stats:
        assert s["breaker_state"] == "closed"
        assert s["degraded_ops"] == 0 and s["errors"] == 0
        assert "member_id" in s and s["last_error"] is None
    assert cluster.degraded_ops == 0  # aggregate keeps its name and meaning


def test_non_store_exception_never_wedges_a_half_open_probe():
    """StagingPoolExhausted (backpressure) or any non-store exception
    escaping THE half-open probe must propagate — but still resolve the
    probe, or the breaker would stay HALF_OPEN and fast-fail the member
    forever."""

    class FlakyMember:
        spec = SPEC
        boom: Exception = None

        def lookup(self, token_ids):
            if self.boom is not None:
                raise self.boom
            return 2

    class FakeConn:
        class config:
            host_addr = "x"
            service_port = 1

    clk = _Clock()
    member = FlakyMember()
    cluster = ClusterKVConnector(
        [FakeConn()], SPEC, "m", max_blocks=8, degrade=True,
        member_factory=lambda c: member,
        breaker_factory=lambda i: CircuitBreaker(
            fail_threshold=1, probe_backoff_s=1.0, max_backoff_s=4.0,
            jitter_frac=0.0, seed=i, clock=clk,
        ),
    )
    tokens = list(range(2 * SPEC.block_tokens))
    member.boom = its.InfiniStoreException("down")
    assert cluster.lookup(tokens) == 0  # opens the breaker (threshold 1)
    assert cluster.health()["members"][0]["breaker_state"] == "open"
    clk.t = 1.0  # probe window elapsed; the next op is THE probe...
    member.boom = RuntimeError("backpressure-ish, not a store failure")
    with pytest.raises(RuntimeError):
        cluster.lookup(tokens)
    # ...and despite escaping, the probe resolved: not wedged HALF_OPEN.
    assert cluster.health()["members"][0]["breaker_state"] == "closed"
    member.boom = None
    assert cluster.lookup(tokens) == 2  # member serves again


def test_striped_sweep_rejoin_restores_shm_segment_aliases():
    """An externally-reconnected stripe lost its alias registrations of
    stripe 0's shm segments; the op-entry sweep's rejoin must restore them
    (and never double-register ones still held), or the stripe would fail
    its first segment-based chunk and flap straight back into quarantine."""
    from infinistore_tpu.faults import kill_transport

    srv = its.start_local_server(prealloc_bytes=32 << 20, block_bytes=16 << 10)
    sc = its.StripedConnection(
        its.ClientConfig(
            host_addr="127.0.0.1", service_port=srv.port, log_level="error"
        ),
        streams=3,
    )
    sc.connect()
    seg = sc.alloc_shm_mr(64 << 10)
    assert seg is not None
    base = (seg.ctypes.data, seg.nbytes)
    assert base in sc.conns[1]._segment_aliases
    # External heal: transport dies, someone calls reconnect() directly —
    # the reconnect drops stripe 1's alias registrations.
    kill_transport(sc.conns[1])
    sc.conns[1].reconnect()
    assert base not in sc.conns[1]._segment_aliases
    sc._quarantined[1] = True  # as a failed batch would have left it
    sc._sweep_quarantine()
    assert not sc._quarantined[1]
    assert base in sc.conns[1]._segment_aliases  # re-aliased, not flapping
    # Stripe 2 never reconnected: its alias survived and was NOT duplicated.
    assert sc.conns[2]._segment_aliases.count(base) == 1
    sc.close()
    srv.stop()
