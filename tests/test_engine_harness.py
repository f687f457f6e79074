"""Engine-shaped connector proof: the continuous-batching harness drives the
KVConnector the way a vLLM-TPU-style engine does — N interleaved requests
with overlapping prefixes against the demo Llama, block tables owned by the
engine, evictions racing admissions — and every request's cache blocks are
verified against the model's own prefill oracle (BASELINE.md config 4 in
spirit; the reference's LMCache integration contract, reference README.md:22,
docs/source/design.rst:33-37)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import infinistore_tpu as its
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
    block_tokens=8, dtype=jnp.float32,  # float32: oracle comparisons
)
NUM_BLOCKS = 32  # engine-side physical blocks
MAX_REQ_BLOCKS = 4


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _assert_same_to_f32_rounding(got, want, err_msg):
    """One row computed in two launch shapes (inside a wave, and solo)
    agrees to float32 rounding, not bitwise: XLA picks a GEMM's
    accumulation order per shape, so the same sum of float32 products can
    land on a neighbouring float (jax 0.9.0 on the CPU shows 1 ulp). The
    bound is 32 ulps of the largest value — far above that reordering,
    and four orders of magnitude below what a drop to bfloat16 (2^-8)
    anywhere in the step would show. Cache bytes that MOVE (store round
    trips, install scatters) stay bitwise elsewhere in this file."""
    want = np.asarray(want)
    atol = 32 * np.finfo(np.float32).eps * float(np.max(np.abs(want)))
    np.testing.assert_allclose(
        np.asarray(got), want, rtol=0, atol=atol, err_msg=err_msg
    )


def _prompts(n, shared_blocks, total_blocks, seed=0):
    """n prompts sharing the first shared_blocks blocks, diverging after."""
    bt = CFG.block_tokens
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, CFG.vocab, size=shared_blocks * bt).tolist()
    out = []
    for i in range(n):
        tail = rng.integers(
            0, CFG.vocab, size=(total_blocks - shared_blocks) * bt
        ).tolist()
        out.append(shared + tail)
    return out


def _harness(conn, params, model_id, verify=True):
    spec = CFG.kv_spec(NUM_BLOCKS)
    kvc = KVConnector(conn, spec, model_id, max_blocks=MAX_REQ_BLOCKS)
    return ContinuousBatchingHarness(
        EngineKVAdapter(kvc), params, CFG, NUM_BLOCKS, MAX_REQ_BLOCKS,
        verify=verify,
    )


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


def test_concurrent_requests_share_prefix(conn, params):
    """8 requests, 4 in flight, sharing a 2-block prefix: the first to save
    seeds the store, later admissions hit. All verified vs the oracle."""
    h = _harness(conn, params, "engine-a")
    prompts = _prompts(8, shared_blocks=2, total_blocks=4)
    m = asyncio.run(h.run(prompts, concurrency=4))
    assert m["requests"] == 8
    assert m["max_live_requests"] >= 2, "harness never had 2 requests in flight"
    assert m["all_verified"], "a request's cache blocks diverged from the oracle"
    # The shared prefix must have produced real hits (the first request can't
    # hit; at least some of the other 7 must).
    assert m["loaded_blocks"] > 0
    assert m["hit_rate"] > 0
    # Store I/O overlapped: two saves were in flight at once at some point.
    assert m["max_concurrent_saves"] >= 2
    assert m["recompute_saved_s"] > 0


def test_repeat_prompt_full_hit(conn, params):
    """The same prompt twice: the second admission loads every block and
    computes none."""
    h = _harness(conn, params, "engine-b")
    p = _prompts(1, 1, 4)[0]
    s1 = asyncio.run(h.run_request(p))
    s2 = asyncio.run(h.run_request(p))
    assert s1.loaded_blocks == 0 and s1.computed_blocks == 4
    assert s2.loaded_blocks == 4 and s2.computed_blocks == 0
    assert s2.verified


def test_eviction_churn_correctness(params):
    """A store pool far smaller than the workload: evictions race admissions
    continuously. Every request must still verify — a raced load yields
    recompute, never stale bytes. (Cache semantics: the reference's design
    position, SURVEY.md §5.3.)"""
    spec = CFG.kv_spec(NUM_BLOCKS)
    # Each request saves 4 blocks x 2 layers x K+V = 16 store values of
    # block_nbytes; pool of 24 such blocks holds ~1.5 requests.
    srv = its.start_local_server(
        prealloc_bytes=24 * spec.block_nbytes,
        block_bytes=spec.block_nbytes,
        enable_shm=True,
        evict_min=0.5,
        evict_max=0.8,
    )
    c = its.InfinityConnection(
        its.ClientConfig(
            host_addr="127.0.0.1", service_port=srv.port, log_level="error"
        )
    )
    c.connect()
    try:
        h = _harness(c, params, "engine-churn")
        # 12 requests over 3 distinct prompt families -> repeats would hit if
        # not evicted; the small pool guarantees heavy eviction in between.
        fams = _prompts(3, 1, 4, seed=7)
        prompts = [fams[i % 3] for i in range(12)]
        m = asyncio.run(h.run(prompts, concurrency=3))
        assert m["requests"] == 12
        assert m["all_verified"], "eviction churn delivered wrong bytes"
        # The workload must actually have churned: the store saw far more
        # saves than it can hold, so SOME admissions missed or raced.
        assert m["computed_blocks"] > 0
    finally:
        c.close()
        srv.stop()


def test_resume_is_chunked_and_generation_waves_batch(conn, params):
    """Prefix-hit resumes compute their suffix as ONE chunked continuation
    (no per-token decode), while GENERATION rides the shared WaveDecoder:
    with several requests generating concurrently, at least one wave must
    carry >= 2 requests, lockstep must merge steps, and everything still
    verifies against the oracle."""

    async def drive():
        h = _harness(conn, params, "engine-waves")
        # Seed one 2-block family so later admissions hit 2 and resume.
        fams = _prompts(4, shared_blocks=2, total_blocks=3, seed=13)
        await h.run_request(fams[0])
        h.stats.clear()
        m = await h.run(fams[1:], concurrency=3, gen_tokens=8)
        return m

    m = asyncio.run(drive())
    assert m["all_verified"]
    assert m["loaded_blocks"] >= 3 * 2  # each resumed the seeded prefix
    assert m["generated_tokens"] == 3 * 8
    assert m["decode_waves"] > 0
    assert m["max_wave_size"] >= 2, (
        "concurrent generations never coalesced into one batched wave"
    )
    # Lockstep actually reduced step count: 3 requests x 8 tokens would be
    # 24 sequential steps; waves must have merged a chunk of them.
    assert m["decode_waves"] < 24


def test_generation_is_deterministic_under_wave_interleaving(conn, params):
    """Greedy generation depends only on a request's own cache blocks, so
    concurrent lockstep waves must produce token-for-token the same output
    as running each prompt alone."""

    async def concurrent():
        h = _harness(conn, params, "engine-det", verify=False)
        prompts = _prompts(3, shared_blocks=1, total_blocks=3, seed=17)
        sem = asyncio.Semaphore(3)

        async def one(p):
            async with sem:
                return await h.run_request(p, gen_tokens=8)

        # Keep the PROMPT -> OUTPUT pairing: set-compare would miss waves
        # handing one request another's continuation.
        stats = await asyncio.gather(*(one(p) for p in prompts))
        return prompts, [tuple(s.generated) for s in stats]

    prompts, together = asyncio.run(concurrent())

    async def solo():
        h = _harness(conn, params, "engine-det", verify=False)
        out = []
        for p in prompts:
            s = await h.run_request(p, gen_tokens=8)
            out.append(tuple(s.generated))
        return out

    alone = asyncio.run(solo())
    assert together == alone


def test_multi_turn_conversation_hits_generated_blocks(conn, params):
    """Turn 2's prompt = turn 1's prompt + its generated response: the
    response blocks were saved under the extended chain, so the follow-up
    admission is a FULL prefix hit — the conversation's KV never recomputes
    across turns."""

    async def drive():
        h = _harness(conn, params, "engine-turns")
        bt = CFG.block_tokens
        turn1 = _prompts(1, 1, 2, seed=23)[0]  # 2 complete blocks
        s1 = await h.run_request(turn1, gen_tokens=bt)  # fills 1 more block
        assert len(s1.generated) == bt
        turn2 = turn1 + s1.generated  # the conversation so far, 3 blocks
        s2 = await h.run_request(turn2)
        return s1, s2

    s1, s2 = asyncio.run(drive())
    assert s2.hit_blocks == 3, "generated block should extend the cached chain"
    assert s2.loaded_blocks == 3 and s2.computed_blocks == 0
    assert s2.verified


def test_wave_sizes_bucket_to_powers_of_two(conn, params, monkeypatch):
    """Varied wave shapes must reach the jitted ragged step only at
    power-of-two PADDED (B, T, P) buckets — table rows, flat token rows,
    flat attention pages (jit keys its cache on shape, so distinct shapes
    == compiles): a run whose natural wave sizes wander over 1..5 buckets
    to the power-of-two ladder, and the tail padding rows must not perturb
    any request's output (all verified)."""
    import infinistore_tpu.engine as engine_mod

    shapes_seen = set()
    real = engine_mod.verify_step_ragged

    def recording(params_, packed, prev_ids, caches, *, config, max_blocks, layout):
        assert packed.shape == (layout.size(max_blocks),)
        assert layout.window_pages is None  # no window in this spec
        shapes_seen.add((layout.tables, layout.rows, layout.pages))
        return real(
            params_, packed, prev_ids, caches, config=config, max_blocks=max_blocks,
            layout=layout,
        )

    # Every wave the decoder launches goes through the packed entry
    # (models/serving.py ``verify_step_ragged``), keyed by its layout.
    monkeypatch.setattr(engine_mod, "verify_step_ragged", recording)

    async def drive():
        h = _harness(conn, params, "engine-buckets")
        # 5 requests, staggered admission via concurrency 5 but different
        # prompt lengths -> wave sizes vary as requests finish prefill at
        # different times and drain at different steps.
        prompts = _prompts(5, shared_blocks=1, total_blocks=2, seed=29)
        return await h.run(prompts, concurrency=5, gen_tokens=6)

    m = asyncio.run(drive())
    assert m["all_verified"], "padding rows corrupted a request's blocks"
    assert m["generated_tokens"] == 5 * 6
    assert shapes_seen, "no waves decoded"
    for b, t, p in shapes_seen:
        assert b & (b - 1) == 0, f"non-power-of-two table-row bucket {b}"
        assert t & (t - 1) == 0, f"non-power-of-two flat-row bucket {t}"
        assert p & (p - 1) == 0, f"non-power-of-two page bucket {p}"
    # Compile count is bounded by the bucket ladder, not by how many
    # distinct natural sizes occurred. The (B, T, P) ladder is wider than
    # the old (B, K) one (P steps through pow2s as contexts lengthen), but
    # it must stay a LADDER — a change that buckets exactly instead of to
    # powers of two would proliferate shapes (= whole-model recompiles)
    # far past this cap.
    assert shapes_seen == set(m["wave_buckets"])
    assert len(shapes_seen) <= 8, sorted(shapes_seen)
    # Pure-decode waves: every chunk is one token, so ragged assembly pads
    # at most T_bucket - B rows per wave — strictly no more than the old
    # rectangle's (B_bucket - B) duplicated rows at K = 1.
    assert 0.0 <= m["wave_pad_fraction"] < 0.5, m["wave_pad_fraction"]
    # The page ledger beside it: padding is what a power of two adds to a
    # sum, so under half of what was launched.
    assert 0 <= m["wave_pad_pages"] < m["wave_pages"] / 2, m


def test_ngram_drafter_proposes_recurring_continuations():
    """Prompt-lookup drafting: the continuation after the most recent
    earlier occurrence of the suffix n-gram, longest n first; empty when
    nothing recurs."""
    from infinistore_tpu.engine import NGramDrafter

    d = NGramDrafter(max_draft=3, ngram=2)
    # suffix (7, 8) occurred earlier, followed by 9, 10, 11.
    assert d.draft([7, 8, 9, 10, 11, 5, 7, 8]) == [9, 10, 11]
    # Only a 1-gram recurs.
    assert d.draft([4, 9, 1, 2, 9]) == [1, 2, 9]
    # Nothing recurs.
    assert d.draft([1, 2, 3, 4]) == []
    # Most RECENT earlier occurrence wins (8 -> 6, not 8 -> 2).
    assert d.draft([8, 2, 5, 8, 6, 8]) == [6, 8]
    # max_draft caps the proposal.
    assert NGramDrafter(max_draft=1, ngram=2).draft([7, 8, 9, 7, 8]) == [9]


def test_speculative_generation_matches_greedy_exactly(conn, params):
    """Greedy acceptance makes speculative output token-for-token IDENTICAL
    to plain decode — on a repetitive prompt the drafter must also actually
    accept tokens (tokens/step > 1), or speculation is dead weight."""
    from infinistore_tpu.engine import NGramDrafter

    bt = CFG.block_tokens
    # Period-3 repetition: the 2-gram suffix always recurs and the model-
    # agnostic draft is often wrong (the model decides) — exercising both
    # accept and reject paths.
    prompts = [
        ([11, 12, 13] * (2 * bt))[: 2 * bt],
        ([3, 7] * bt)[: 2 * bt],
        ([9, 9, 4, 2] * bt)[: 2 * bt],
    ]

    async def run_with(drafter):
        h = _harness(conn, params, "engine-spec", verify=False)
        h.drafter = drafter
        stats = []
        for p in prompts:  # sequential: identical per-request wave makeup
            stats.append(await h.run_request(p, gen_tokens=2 * bt))
        return h, [tuple(s.generated) for s in stats]

    h_plain, plain = asyncio.run(run_with(None))
    h_spec, spec = asyncio.run(run_with(NGramDrafter(max_draft=4)))
    assert spec == plain, "speculation changed greedy output"
    m = h_spec.metrics()
    assert m["spec_drafted_tokens"] > 0, "drafter never proposed on a repetitive prompt"
    assert m["spec_tokens_per_step"] > 1.0, (
        f"speculation accepted nothing: {m['spec_tokens_per_step']}"
    )
    assert h_spec.spec_rounds < h_plain.spec_rounds, (
        "speculation did not reduce model rounds"
    )


def test_mixed_spec_and_decode_requests_share_waves(conn, params):
    """A drafting request and a plain-decode request coalesce into the SAME
    wave (chunks of different lengths CONCATENATE into one ragged launch —
    the decode rows no longer pad to the draft chunk's width) and both
    verify against the oracle."""
    from infinistore_tpu.engine import NGramDrafter

    bt = CFG.block_tokens

    async def drive():
        h = _harness(conn, params, "engine-mixed")
        h.drafter = NGramDrafter(max_draft=4)
        rng = np.random.default_rng(31)
        # One highly repetitive prompt (drafts fire) + ones with no
        # repetition (drafter proposes nothing -> 1-token chunks).
        p_rep = ([21, 22] * bt)[: 2 * bt]
        p_rand = [rng.integers(0, CFG.vocab, size=2 * bt).tolist() for _ in range(2)]
        return await h.run([p_rep] + p_rand, concurrency=3, gen_tokens=bt)

    m = asyncio.run(drive())
    assert m["all_verified"]
    assert m["generated_tokens"] == 3 * CFG.block_tokens
    assert m["max_wave_size"] >= 2, "requests never shared a wave"
    # At least one wave carried a chunk wider than 1 (the drafting row):
    # its flat-row bucket exceeds its table-row bucket.
    assert any(t > b for b, t, _ in m["wave_buckets"]), m["wave_buckets"]


def test_ragged_wave_byte_identical_to_sequential_decode(params):
    """THE ragged-assembly determinism pin: a MIXED wave (two 1-token
    decode rows beside a 3-token verification chunk, concatenated ragged —
    no row duplication) must produce the logits AND cache of advancing
    each request alone, one wave of one request at a time, to float32
    rounding (_assert_same_to_f32_rounding: XLA does not grant bitwise
    equality across launch shapes). This is the guarantee that lets the
    scheduler coalesce whatever happens to be ready without changing a
    request's output."""
    from infinistore_tpu.engine import ContinuousBatchingHarness, WaveDecoder
    from infinistore_tpu.models import prefill

    rng = np.random.default_rng(61)
    tables = np.array(
        [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11]], np.int32
    )
    prompts = [
        rng.integers(0, CFG.vocab, size=16).tolist() for _ in range(3)
    ]
    base = CFG.kv_spec(NUM_BLOCKS).make_caches()
    for p, tab in zip(prompts, tables):
        _, base = prefill(
            params, jnp.asarray(p, jnp.int32), base, jnp.asarray(tab[:2]), CFG
        )

    def mk():
        h = ContinuousBatchingHarness.__new__(ContinuousBatchingHarness)
        h.params = params
        h.config = CFG
        h.caches = jax.tree.map(jnp.copy, base)  # a wave donates its cache
        h.max_req_blocks = MAX_REQ_BLOCKS
        h.gate = DeviceGate()
        return h

    # Request 1 verifies a 3-token chunk; 0 and 2 decode one token each.
    chunks = [([5], [16]), ([9, 11, 12], [16, 17, 18]), ([13], [16])]

    async def wave_run():
        h = mk()
        wave = WaveDecoder(h)
        outs = await asyncio.gather(*(
            wave.step_chunk(toks, pos, jnp.asarray(tables[b]))
            for b, (toks, pos) in enumerate(chunks)
        ))
        return [np.asarray(o) for o in outs], h.caches, wave

    async def seq_run():
        h = mk()
        outs = []
        for b, (toks, pos) in enumerate(chunks):
            wave = WaveDecoder(h)  # fresh decoder: every wave is solo
            outs.append(
                np.asarray(
                    await wave.step_chunk(toks, pos, jnp.asarray(tables[b]))
                )
            )
        return outs, h.caches

    wave_outs, wave_caches, wave = asyncio.run(wave_run())
    seq_outs, seq_caches = asyncio.run(seq_run())
    assert wave.max_wave == 3, "requests did not coalesce into one wave"
    for b in range(3):
        _assert_same_to_f32_rounding(
            wave_outs[b], seq_outs[b],
            err_msg=f"request {b} logits diverged in the mixed wave",
        )
    for layer in range(CFG.n_layers):
        for kind in (0, 1):
            _assert_same_to_f32_rounding(
                wave_caches[layer][kind], seq_caches[layer][kind],
                err_msg=f"cache diverged (layer {layer})",
            )
    # Ragged pad accounting: 5 real flat rows bucket to 8 (3 pad rows) —
    # the rectangle would have launched 4 requests x 4-token chunks = 16.
    assert (wave.launched_rows, wave.pad_rows) == (8, 3)
    # Page accounting: every flat row (the three that repeat the last one
    # too) attends ceil((pos + 1) / 8) = 3 pages, 24 of a bucket of 32.
    assert (wave.wave_pages, wave.wave_pad_pages) == (32, 8)
    assert wave.bucket_sizes == {(4, 8, 32)}


def test_wave_decoder_failure_fails_all_waiters(params):
    """A flush that dies (model error) must fail every waiter — taken batch
    AND still-pending — and leave the decoder usable for the next wave, not
    wedge decode forever."""
    from infinistore_tpu.engine import ContinuousBatchingHarness, WaveDecoder

    class _Boom(Exception):
        pass

    h = ContinuousBatchingHarness.__new__(ContinuousBatchingHarness)
    h.params = params
    h.config = CFG
    h.caches = CFG.kv_spec(NUM_BLOCKS).make_caches()
    h.max_req_blocks = MAX_REQ_BLOCKS
    h.gate = DeviceGate()
    wave = WaveDecoder(h)

    async def run():
        bad = np.zeros(MAX_REQ_BLOCKS, np.int32)
        # Poison one step: a wrong-shaped table makes the wave step
        # raise for the whole wave.
        t1 = asyncio.ensure_future(wave.step(1, 8, jnp.asarray(bad)))
        t2 = asyncio.ensure_future(wave.step(2, 8, jnp.asarray(bad[:2])))
        r1, r2 = await asyncio.gather(t1, t2, return_exceptions=True)
        assert isinstance(r1, Exception) and isinstance(r2, Exception)
        # The decoder recovered: a good wave still decodes.
        good = np.arange(MAX_REQ_BLOCKS, dtype=np.int32)
        logits = await wave.step(3, 8, jnp.asarray(good))
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        assert wave.waves >= 1

    asyncio.run(run())


def test_block_pool_backpressure():
    """alloc() waits for free blocks instead of failing (scheduler-style
    admission deferral)."""

    async def run():
        pool = BlockPool(4)
        a = await pool.alloc(3)
        waiter = asyncio.ensure_future(pool.alloc(2))
        await asyncio.sleep(0.01)
        assert not waiter.done(), "alloc should have backpressured"
        await pool.free(a)
        got = await asyncio.wait_for(waiter, 1)
        assert len(got) == 2

    asyncio.run(run())


def test_device_gate_excludes_mutators():
    """Shared holders overlap; an exclusive phase waits for them and blocks
    new ones (the cache-consistency discipline the harness relies on)."""

    async def run():
        gate = DeviceGate()
        order = []

        async def reader(name, hold):
            async with gate.shared(holder="snapshot"):
                order.append(f"{name}+")
                await asyncio.sleep(hold)
                order.append(f"{name}-")

        async def writer():
            async with gate.exclusive(holder="prefill"):
                order.append("w+")
                order.append("w-")

        r1 = asyncio.ensure_future(reader("a", 0.02))
        r2 = asyncio.ensure_future(reader("b", 0.02))
        await asyncio.sleep(0.005)
        w = asyncio.ensure_future(writer())
        await asyncio.sleep(0.005)
        # Writer priority: a reader arriving while the writer WAITS must
        # queue behind it, or a steady reader stream starves every mutator.
        r3 = asyncio.ensure_future(reader("c", 0.0))
        await asyncio.gather(r1, r2, w, r3)
        # Both early readers overlapped (a+ b+ before a- b-), writer after
        # them, late reader after the writer.
        assert order.index("b+") < order.index("a-")
        assert order.index("w+") > order.index("a-")
        assert order.index("w+") > order.index("b-")
        assert order.index("c+") > order.index("w-")

    asyncio.run(run())


def test_device_gate_expedite_jumps_queued_writers():
    """An expedited exclusive (a prefix INSTALL — short, device-transfer
    bound) queued behind a normal exclusive (a prefill) must acquire first
    when the gate frees: installs arrive late by construction (their fetch
    runs gate-free first), so FIFO would park every cache hit behind a
    convoy of misses' prefills."""

    async def run():
        gate = DeviceGate()
        order = []

        async def holder():
            async with gate.exclusive(holder="prefill"):
                order.append("hold")
                await asyncio.sleep(0.03)

        async def normal():
            async with gate.exclusive(holder="prefill"):
                order.append("prefill")

        async def install():
            async with gate.exclusive(holder="install", expedite=True):
                order.append("install")

        h = asyncio.ensure_future(holder())
        await asyncio.sleep(0.005)
        n1 = asyncio.ensure_future(normal())
        n2 = asyncio.ensure_future(normal())
        await asyncio.sleep(0.005)
        i1 = asyncio.ensure_future(install())  # arrives LAST...
        await asyncio.gather(h, n1, n2, i1)
        assert order[0] == "hold"
        assert order[1] == "install", order  # ...acquires first
        assert sorted(order[2:]) == ["prefill", "prefill"]

    asyncio.run(asyncio.wait_for(run(), 10))


def test_device_gate_cancelled_writer_releases_queued_readers():
    """A reader queued behind a WAITING writer must wake when that writer's
    task is cancelled (e.g. a timed-out request) — not sleep forever on a
    free gate. Ordered by the gate's own state, not by sleeps: the first
    reader holds until released, so the writer cannot get in before it is
    cancelled however slow the box."""

    async def run():
        gate = DeviceGate()
        got = []
        release = asyncio.Event()

        async def until(cond):
            for _ in range(1000):
                if cond():
                    return
                await asyncio.sleep(0)
            raise AssertionError("the gate never reached the state waited for")

        async def hold_shared():
            async with gate.shared(holder="snapshot"):
                await release.wait()

        async def writer():
            async with gate.exclusive(holder="prefill"):
                got.append("w")

        async def late_reader():
            async with gate.shared(holder="snapshot"):
                got.append("r2")

        r1 = asyncio.ensure_future(hold_shared())
        await until(lambda: gate._shared == 1)
        w = asyncio.ensure_future(writer())
        await until(lambda: gate._exclusive_waiting == 1)
        r2 = asyncio.ensure_future(late_reader())
        for _ in range(5):  # the late reader parks behind the waiting writer
            await asyncio.sleep(0)
        assert got == [] and not r2.done()
        w.cancel()
        await asyncio.gather(w, return_exceptions=True)
        await asyncio.wait_for(r2, 5)  # woken though r1 still holds: readers overlap
        release.set()
        await r1
        assert got == ["r2"], got
        async with gate.exclusive(holder="prefill"):  # gate still fully functional
            got.append("w2")
        assert got == ["r2", "w2"], got

    asyncio.run(asyncio.wait_for(run(), 10))


# ---------------------------------------------------------------------------
# One flush path: whatever is ready rides the next wave
# ---------------------------------------------------------------------------

def _bare_wave_harness(params, caches=None):
    """A harness skeleton for driving a WaveDecoder directly (no store).
    It gets a COPY of ``caches``: every wave donates the harness's cache,
    and the tests hand one base cache to several harnesses."""
    from infinistore_tpu.engine import ContinuousBatchingHarness

    h = ContinuousBatchingHarness.__new__(ContinuousBatchingHarness)
    h.params = params
    h.config = CFG
    h.caches = (
        jax.tree.map(jnp.copy, caches) if caches is not None
        else CFG.kv_spec(NUM_BLOCKS).make_caches()
    )
    h.max_req_blocks = MAX_REQ_BLOCKS
    h.gate = DeviceGate()
    return h


def _skew_scenario(params):
    """Two 1-token decode rows + one 3-token chunk, whose admission bumps
    the T bucket 2 -> 8: a wave skewed in length."""
    from infinistore_tpu.models import prefill

    rng = np.random.default_rng(61)
    tables = np.array(
        [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11]], np.int32
    )
    prompts = [
        rng.integers(0, CFG.vocab, size=16).tolist() for _ in range(3)
    ]
    base = CFG.kv_spec(NUM_BLOCKS).make_caches()
    for p, tab in zip(prompts, tables):
        _, base = prefill(
            params, jnp.asarray(p, jnp.int32), base, jnp.asarray(tab[:2]), CFG
        )
    chunks = [([5], [16]), ([9, 11, 12], [16, 17, 18]), ([13], [16])]
    return tables, chunks, base


def test_a_harness_wave_donates_the_harness_cache(params):
    """A wave through the WaveDecoder updates the harness's cache in place:
    the arrays ``h.caches`` held at dispatch are deleted by the step, what
    the harness holds afterwards is live and in distinct buffers, and the
    base the test kept (the harness got a copy) is untouched."""
    from infinistore_tpu.engine import WaveDecoder

    tables, chunks, base = _skew_scenario(params)

    async def run():
        h = _bare_wave_harness(params, base)
        handed = [t for layer in h.caches for t in layer]
        wave = WaveDecoder(h)
        await asyncio.gather(*(
            wave.step_chunk(toks, pos, jnp.asarray(tables[b]))
            for b, (toks, pos) in enumerate(chunks)
        ))
        return handed, [t for layer in h.caches for t in layer], wave

    handed, held, wave = asyncio.run(run())
    assert wave.waves == 1
    assert all(t.is_deleted() for t in handed)
    assert not any(t.is_deleted() for t in held)
    assert len({t.unsafe_buffer_pointer() for t in held}) == len(held)
    assert not any(t.is_deleted() for layer in base for t in layer)


def test_a_length_skewed_wave_is_one_wave_and_matches_sequential_decode(params):
    """The flush takes whatever is ready: two one-token rows beside a
    three-token chunk ride ONE wave of 5 real flat rows bucketed to 8, and
    its logits and cache are those of advancing each request alone, to
    float32 rounding."""
    from infinistore_tpu.engine import WaveDecoder

    tables, chunks, base = _skew_scenario(params)

    async def wave_run():
        h = _bare_wave_harness(params, base)
        wave = WaveDecoder(h)
        outs = await asyncio.gather(*(
            wave.step_chunk(toks, pos, jnp.asarray(tables[b]))
            for b, (toks, pos) in enumerate(chunks)
        ))
        return [np.asarray(o) for o in outs], h.caches, wave

    async def seq_run():
        h = _bare_wave_harness(params, base)
        outs = []
        for b, (toks, pos) in enumerate(chunks):
            wave = WaveDecoder(h)
            outs.append(np.asarray(
                await wave.step_chunk(toks, pos, jnp.asarray(tables[b]))
            ))
        return outs, h.caches

    wave_outs, wave_caches, wave = asyncio.run(wave_run())
    seq_outs, seq_caches = asyncio.run(seq_run())
    assert (wave.waves, wave.max_wave) == (1, 3)
    assert (wave.launched_rows, wave.pad_rows) == (8, 3)
    assert wave.one_row_waves == 0 and not wave._pending
    for b in range(3):
        _assert_same_to_f32_rounding(
            wave_outs[b], seq_outs[b],
            err_msg=f"request {b} logits diverged in the skewed wave",
        )
    for layer in range(CFG.n_layers):
        for kind in (0, 1):
            _assert_same_to_f32_rounding(
                wave_caches[layer][kind], seq_caches[layer][kind],
                err_msg=f"cache diverged (layer {layer})",
            )


@pytest.mark.parametrize("rows", [1, 2, 4])
def test_concurrent_one_token_steps_launch_one_wave_on_their_bucket(params, rows):
    """What the benchmark's warm-up stands on (benchmarks/run.py): ``rows``
    concurrent one-token ``step_chunk`` calls on a fresh decoder launch ONE
    wave and mint the bucket ``(rows, rows, pages)``."""
    from infinistore_tpu.engine import WaveDecoder

    bt, pages = CFG.block_tokens, 2 * rows
    share = [pages // rows + (i < pages % rows) for i in range(rows)]
    table = np.zeros(MAX_REQ_BLOCKS, np.int32)

    async def run():
        wave = WaveDecoder(_bare_wave_harness(params))
        await asyncio.gather(
            *(wave.step_chunk([0], [n * bt - 1], table) for n in share)
        )
        return wave

    wave = asyncio.run(run())
    assert wave.waves == 1 and wave.max_wave == rows
    assert wave.bucket_sizes == {(rows, rows, pages)}
    assert (wave.launched_rows, wave.pad_rows) == (rows, 0)


def test_step_chunk_takes_a_priority_and_ignores_it(params):
    """The benchmark passes ``priority=`` on every step: a BACKGROUND and a
    FOREGROUND entry ride one wave, and each gets the rows it gets without
    the keyword."""
    from infinistore_tpu.engine import WaveDecoder
    from infinistore_tpu.wire import PRIORITY_BACKGROUND

    tables, chunks, base = _skew_scenario(params)

    async def run(classes):
        wave = WaveDecoder(_bare_wave_harness(params, base))
        outs = await asyncio.gather(*(
            wave.step_chunk(toks, pos, tables[b], **kw)
            for b, ((toks, pos), kw) in enumerate(zip(chunks, classes))
        ))
        return [np.asarray(o) for o in outs], wave

    tagged, wave = asyncio.run(run(
        [{"priority": PRIORITY_BACKGROUND}, {"priority": 0}, {"priority": PRIORITY_BACKGROUND}]
    ))
    plain, _ = asyncio.run(run([{}, {}, {}]))
    assert (wave.waves, wave.max_wave) == (1, 3)
    for a, b in zip(tagged, plain):
        np.testing.assert_array_equal(a, b)




@pytest.mark.parametrize("streams", [1, 2, 3])
def test_generation_cuts_no_logits_row_and_samples_what_the_logits_say(conn, params, streams):
    """One, two and three requests decode side by side through ``_generate``:
    the rows ``step_chunk`` hands a request are a handle it only asks for its
    ids (``WaveDecoder.token_ids``), so a run ends with no slice of a wave's
    logits dispatched, closing step included (an answer that ends on a block's
    edge). A second run, whose every ``step_chunk`` is tapped by a reader of
    the logits themselves, emits the same tokens, each the argmax of the rows
    read, and pays one slice a real rider."""
    bt = CFG.block_tokens
    prompts = _prompts(streams, shared_blocks=0, total_blocks=2, seed=53)
    gen = bt  # prompt and answer end on a block's edge: one closing step a request

    async def drive(model_id, tap):
        h = _harness(conn, params, model_id, verify=False)
        read = []
        if tap:
            step_chunk = h.wave.step_chunk

            async def tapped(tokens, positions, table, priority=0):
                rows = await step_chunk(tokens, positions, table, priority=priority)
                read.append((np.argmax(np.asarray(rows), axis=-1), h.wave.token_ids(rows)))
                return rows

            h.wave.step_chunk = tapped
        stats = await asyncio.gather(*(h.run_request(p, gen_tokens=gen) for p in prompts))
        return h, [s.generated for s in stats], read

    h, tokens, _ = asyncio.run(drive(f"rows-unread-{streams}", False))
    m = h.metrics()
    assert m["generated_tokens"] == streams * gen and m["decode_waves"] >= gen + 1
    assert (m["wave_row_slices"], h.wave.row_slices) == (0, 0)
    assert m["wave_host_transfers"] <= 2 * m["decode_waves"]
    tapped_h, tapped_tokens, read = asyncio.run(drive(f"rows-read-{streams}", True))
    assert tapped_tokens == tokens
    assert len(read) == streams * (gen + 1)
    for from_logits, ids in read:
        np.testing.assert_array_equal(from_logits, ids)
    # A wave of one entry in a one-row bucket hands its logits over whole;
    # every other rider's read is a slice.
    lone = tapped_h.wave.one_row_waves
    assert tapped_h.wave.row_slices == len(read) - lone
    assert (tapped_h.wave.row_slices > 0) == (streams > 1)
