"""A decode wave is one upload, one launch and one read-back.

The decoder hands the device ONE packed ``int32`` operand a wave
(models/serving.py ``pack_wave`` -> ``verify_step_ragged``) and reads ONE
array back, the sampled ids. These tests hold the packed entry to the model's
own wave body bit for bit, in both model files, on the waves the decoder
itself assembles (chunks of unlike length, padded rows, tables and pages, a
window where the spec names one), the ids to the argmax of the very logits
rows ``step_chunk`` hands back, and the count of transfers to 2 a wave.
"""

import asyncio
import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import infinistore_tpu as its
import infinistore_tpu.engine as engine_mod
from infinistore_tpu.connector import KVConnector
from infinistore_tpu.engine import (
    ContinuousBatchingHarness,
    DeviceGate,
    EngineKVAdapter,
    NGramDrafter,
    WaveDecoder,
    WaveRows,
)
from infinistore_tpu.models import (
    AfmoeConfig, LlamaConfig, afmoe, falcon_h1, llama, pangu_mtp, serving,
)
from infinistore_tpu.models.serving import WaveLayout, pack_wave, unpack_wave

NUM_BLOCKS, MAX_REQ_BLOCKS = 64, 16
MODELS = {
    "llama": (
        LlamaConfig(
            vocab=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128,
            block_tokens=8, dtype=jnp.float32,
        ),
        lambda cfg: llama.init_params(cfg, jax.random.PRNGKey(36)),
    ),
    # Sliding window 32 over 8-token blocks: a 10-block context leaves pages
    # behind the window, so the wave's second page list differs from its first.
    "afmoe": (
        AfmoeConfig(dtype=jnp.float32),
        lambda cfg: afmoe.init_params(cfg, jax.random.key(36)),
    ),
}


@pytest.fixture(scope="module", params=sorted(MODELS))
def model(request):
    cfg, init = MODELS[request.param]
    return cfg, init(cfg)


@pytest.fixture()
def conn():
    srv = its.start_local_server(prealloc_bytes=64 << 20, block_bytes=16 << 10, enable_shm=True)
    c = its.InfinityConnection(
        its.ClientConfig(host_addr="127.0.0.1", service_port=srv.port, log_level="error")
    )
    c.connect()
    yield c
    c.close()
    srv.stop()


def bare_harness(cfg, params, caches):
    """A harness skeleton for driving a WaveDecoder directly (no store), on a
    COPY of ``caches``: every wave donates the cache it is handed."""
    h = ContinuousBatchingHarness.__new__(ContinuousBatchingHarness)
    h.params, h.config = params, cfg
    h.caches = jax.tree.map(jnp.copy, caches)
    h.max_req_blocks = MAX_REQ_BLOCKS
    h.gate = DeviceGate()
    return h


def mixed_wave(cfg, params):
    """Three requests over prefilled contexts of 10, 3 and 6 blocks: two
    one-token rows around a drafter's chunk of three. 5 flat rows pad to 8
    (the tail repeats the last row), 3 tables to 4, and the rows' 11 + 3 * 4 +
    7 + 3 * 7 = 51 pages to 64."""
    bt = cfg.block_tokens
    rng = np.random.default_rng(361)
    blocks = (10, 3, 6)
    tables = np.zeros((3, MAX_REQ_BLOCKS), np.int32)
    caches = cfg.kv_spec(NUM_BLOCKS).make_caches()
    first = 1
    for r, n in enumerate(blocks):
        tables[r, : n + 1] = np.arange(first, first + n + 1)
        first += n + 1
        prompt = rng.integers(0, cfg.vocab, size=n * bt)
        _, caches = cfg.steps.prefill(
            params, jnp.asarray(prompt, jnp.int32), caches, jnp.asarray(tables[r, :n]), cfg
        )
    chunks = [
        ([5], [blocks[0] * bt]),
        ([9, 11, 12], [blocks[1] * bt + j for j in range(3)]),
        ([13], [blocks[2] * bt]),
    ]
    return tables, chunks, caches


MIXED_OFFSETS = (0, 1, 4)  # the flat row each of ``mixed_wave``'s chunks starts at


def same_bits(got, want, what):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want), what
    for i, (a, b) in enumerate(zip(got, want)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, (what, i)
        assert a.tobytes() == b.tobytes(), (what, i)


def test_the_packed_entry_is_the_body_bit_for_bit_on_the_decoders_own_wave(model, monkeypatch):
    """Every launch of the decoder is handed twice: to the packed entry as
    the decoder calls it, and, cut apart on the host, to the model's own wave
    body on a copy of the cache. Logits, every cache tensor and ``aux`` agree
    in every bit; the ids are the argmax of those logits."""
    cfg, params = model
    tables, chunks, caches = mixed_wave(cfg, params)
    real, seen = engine_mod.verify_step_ragged, []

    def twice(params_, packed, prev_ids, caches_, *, config, max_blocks, layout):
        f = {k: jnp.asarray(v) for k, v in unpack_wave(packed, layout, max_blocks).items()}
        assert min(f["tokens"]) >= 0, "a bare step_chunk's token comes from the host"
        kw = {}
        if layout.window_pages is not None:
            kw["window_pages"] = (
                f["window_pages"], f["window_page_rows"], f["window_page_starts"]
            )
        want_logits, want_caches, *want_aux = config.steps.wave(
            params_, f["tokens"], f["positions"], f["row_of"], f["pages"], f["page_rows"],
            f["page_starts"], jax.tree.map(jnp.copy, caches_), f["block_tables"], config,
            max_blocks, **kw,
        )
        logits, got_caches, ids, feed, aux = real(
            params_, packed, prev_ids, caches_, config=config, max_blocks=max_blocks,
            layout=layout,
        )
        same_bits(logits, want_logits, "logits")
        same_bits(got_caches, want_caches, "caches")
        same_bits(aux, want_aux[0] if want_aux else {}, "aux")
        assert ids.dtype == jnp.int32 and ids.shape == (layout.rows,)
        np.testing.assert_array_equal(np.asarray(ids), np.argmax(np.asarray(logits), axis=-1))
        # What the next wave's fed rows would read: the ids, padded to one shape.
        assert feed.dtype == jnp.int32 and feed.shape == (serving.FEED_ROWS,)
        np.testing.assert_array_equal(np.asarray(feed)[: layout.rows], np.asarray(ids))
        seen.append(layout)
        return logits, got_caches, ids, feed, aux

    monkeypatch.setattr(engine_mod, "verify_step_ragged", twice)

    async def run():
        wave = WaveDecoder(bare_harness(cfg, params, caches))
        await asyncio.gather(*(
            wave.step_chunk(toks, pos, tables[r]) for r, (toks, pos) in enumerate(chunks)
        ))
        return wave

    wave = asyncio.run(run())
    windowed = cfg.kv_spec(1).window is not None
    # Padded rows, padded tables, padded pages; with a window the second list
    # is shorter than the first (the 10-block row walks 5 of its 11 pages).
    assert seen == [WaveLayout(8, 4, 64, 8 * 5 if windowed else None)]
    assert wave.bucket_sizes == {(4, 8, 64)}
    assert (wave.pad_rows, wave.wave_pad_pages) == (3, 13)
    assert bool(wave.wave_window_pages_skipped) == windowed


def keeping_logits(wave):
    """``wave`` with every launched wave's whole ``logits`` noted, as
    ``(logits, its _WaveOut)``."""
    launch, launched = wave.launch, []

    def noted(*a, **kw):
        got = launch(*a, **kw)
        launched.append(got[:2])
        return got

    wave.launch = noted
    return launched


def test_the_ids_are_the_argmax_of_the_rows_handed_back_and_a_wave_reads_once(model):
    """One-token rows and a drafter's chunk: ``token_ids(rows)`` is the argmax
    of the logits rows ``step_chunk`` resolved to, and the three requests of
    the wave cost ONE blocking read between them. What ``step_chunk`` resolves
    to is a handle on those rows: asking for ids dispatches nothing, and read
    as an array, however and however often, it is bit for bit the slice of the
    wave's logits, cut once."""
    cfg, params = model
    tables, chunks, caches = mixed_wave(cfg, params)
    reads = []

    async def one(wave, r, toks, pos):
        rows = await wave.step_chunk(toks, pos, tables[r])
        assert isinstance(rows, WaveRows) and rows.shape == (len(toks), cfg.vocab)
        assert len(rows) == len(toks) and rows.dtype == cfg.dtype
        ids = wave.token_ids(rows)
        reads.append((wave.blocking_reads, wave.row_slices))
        assert isinstance(ids, np.ndarray) and ids.dtype == np.int32
        np.testing.assert_array_equal(ids, np.asarray(jnp.argmax(rows, axis=-1)))
        return rows

    async def run():
        wave = WaveDecoder(bare_harness(cfg, params, caches))
        launched = keeping_logits(wave)
        handed = await asyncio.gather(*(
            one(wave, r, toks, pos) for r, (toks, pos) in enumerate(chunks)
        ))
        return wave, handed, launched

    wave, handed, launched = asyncio.run(run())
    assert wave.waves == 1 and wave.max_wave == 3
    # The ids were asked for before anybody read a logit: no slice by then
    # for the first request, and one a request that went on to read its rows.
    assert reads == [(1, 0), (1, 1), (1, 2)], "a later request of the wave read the device again"
    assert (wave.waves, wave.blocking_reads, wave.row_slices) == (1, 1, 3)
    ((logits, out),) = launched
    for rows, off in zip(handed, MIXED_OFFSETS):
        want = logits[off : off + len(rows)]
        assert (rows.out, rows.off, rows.n) == (out, off, len(want))
        for got, cut in (
            (np.asarray(rows), want), (np.asarray(rows, np.float32), want.astype(jnp.float32)),
            (jnp.asarray(rows), want), (rows[:1], want[:1]), (rows[0], want[0]),
            (rows[0][0], want[0][0]), (jnp.argmax(rows, -1), jnp.argmax(want, -1)),
            (jnp.concatenate([rows[:1], rows[-1:]]), jnp.concatenate([want[:1], want[-1:]])),
        ):
            same_bits(got, cut, "a read of the handle")
        assert rows.rows() is rows.rows() and isinstance(rows.rows(), jax.Array)
        assert rows._logits is None, "a handle that was cut still holds the wave's whole logits"
    assert wave.row_slices == 3, "a handle read again was cut again"
    # Asking again costs nothing; rows of no wave are no key.
    wave.token_ids(handed[1])
    assert (wave.blocking_reads, wave.row_slices) == (1, 3)
    with pytest.raises(KeyError):
        wave.token_ids(jnp.zeros((1, cfg.vocab), jnp.float32))


def test_rows_that_are_the_whole_wave_cost_no_slice(model):
    """A wave of one entry and no padded row: its rows ARE the wave's logits,
    handed over as they are, read or not."""
    cfg, params = model
    tables, chunks, caches = mixed_wave(cfg, params)

    async def run():
        wave = WaveDecoder(bare_harness(cfg, params, caches))
        launched = keeping_logits(wave)
        rows = await wave.step_chunk(*chunks[0], tables[0])
        return wave, rows, launched

    wave, rows, ((logits, _),) = asyncio.run(run())
    assert logits.shape == rows.shape == (1, cfg.vocab)
    assert rows.rows() is logits and wave.row_slices == 0
    same_bits(np.asarray(rows), logits, "the one row")


def test_a_drafters_chunk_and_a_step_get_their_logits_and_count_a_slice_each(model):
    """Readers of logits are served as they were: ``step()`` returns its row,
    a chunk read as an array is its rows, one slice each; the entry beside
    them that asks for its ids alone causes none."""
    cfg, params = model
    tables, chunks, caches = mixed_wave(cfg, params)

    async def run():
        wave = WaveDecoder(bare_harness(cfg, params, caches))
        launched = keeping_logits(wave)

        async def ids_only():
            return wave.token_ids(await wave.step_chunk(*chunks[2], tables[2]))

        row, chunk, ids = await asyncio.gather(
            wave.step(chunks[0][0][0], chunks[0][1][0], tables[0]),
            wave.step_chunk(*chunks[1], tables[1]),
            ids_only(),
        )
        return wave, row, chunk, ids, launched

    wave, row, chunk, ids, ((logits, _),) = asyncio.run(run())
    assert wave.waves == 1 and wave.row_slices == 1, "step() reads its row; nobody else has yet"
    assert isinstance(row, jax.Array) and row.shape == (cfg.vocab,)
    same_bits(row, logits[0], "step()'s row")
    same_bits(np.asarray(chunk), logits[1:4], "the chunk's rows")
    assert wave.row_slices == 2
    np.testing.assert_array_equal(ids, np.argmax(np.asarray(logits[4:5]), axis=-1))
    assert wave.row_slices == 2


def test_the_benchmarks_four_uses_of_a_handle(model):
    """What ``benchmarks/run.py`` does with what ``step_chunk`` returns: the
    warm-up's ``jnp.argmax(h, -1)``, the check phase's ``h[0][0]`` and
    ``jnp.concatenate([h[:1] ...])`` and, for a routed model, ``choices(harness,
    h)``, which comes back through ``row_aux`` and reads no logits."""
    cfg, params = model
    tables, chunks, caches = mixed_wave(cfg, params)

    async def run():
        h = bare_harness(cfg, params, caches)
        h.wave = WaveDecoder(h)
        launched = keeping_logits(h.wave)
        handed = await asyncio.gather(*(
            h.wave.step_chunk(toks, pos, tables[r]) for r, (toks, pos) in enumerate(chunks)
        ))
        return h, handed, launched

    h, handed, ((logits, out),) = asyncio.run(run())
    if out.aux_rows is None:
        with pytest.raises(KeyError):
            h.wave.row_aux(handed[0])
    else:
        for rows, off in zip(handed, MIXED_OFFSETS):
            got = afmoe.choices(h, rows)
            assert isinstance(got, np.ndarray) and got.shape[0] == len(rows)
            same_bits(got, out.aux_rows[off : off + len(rows)], "choices")
    assert h.wave.row_slices == 0, "the choices are the wave's aux: no logits were read"
    for rows, off in zip(handed, MIXED_OFFSETS):
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(rows, axis=-1)), np.argmax(np.asarray(logits), -1)[off : off + len(rows)]
        )
        same_bits(np.asarray(rows[0][0], np.float32), np.asarray(logits[off][0], np.float32), "h[0][0]")
    got = jnp.concatenate([rows[:1] for rows in handed]).astype(jnp.float32)
    same_bits(got, logits[np.asarray(MIXED_OFFSETS)].astype(jnp.float32), "the rounds' first rows")
    assert h.wave.row_slices == 3


def test_only_this_decoders_handles_are_keys(model):
    """``token_ids`` and ``row_aux`` take what THIS decoder handed out: an
    array, a slice of a handle or another decoder's handle is a ``KeyError``."""
    cfg, params = model
    tables, chunks, caches = mixed_wave(cfg, params)

    async def run():
        mine = WaveDecoder(bare_harness(cfg, params, caches))
        other = WaveDecoder(bare_harness(cfg, params, caches))
        return mine, other, await mine.step_chunk(*chunks[1], tables[1]), \
            await other.step_chunk(*chunks[1], tables[1])

    mine, other, rows, foreign = asyncio.run(run())
    assert len(mine.token_ids(rows)) == len(other.token_ids(foreign)) == 3
    for ask in (mine.token_ids, mine.row_aux):
        for not_mine in (foreign, rows[:1], rows.rows(), np.asarray(rows), None):
            with pytest.raises(KeyError):
                ask(not_mine)


def test_a_handle_kept_past_many_later_waves_still_answers(model):
    """Nothing is evicted any more: a handle knows its wave for as long as it
    lives, ids, aux and logits, whatever was launched since."""
    cfg, params = model
    tables, chunks, caches = mixed_wave(cfg, params)

    async def run():
        wave = WaveDecoder(bare_harness(cfg, params, caches))
        launched = keeping_logits(wave)
        old = await asyncio.gather(*(
            wave.step_chunk(toks, pos, tables[r]) for r, (toks, pos) in enumerate(chunks)
        ))
        toks, pos = chunks[0]
        for k in range(1, 13):
            await wave.step_chunk([7 + k], [pos[0] + k], tables[0])
        return wave, old, launched

    wave, old, launched = asyncio.run(run())
    assert wave.waves == 13 and wave.blocking_reads == 0
    logits, out = launched[0]
    ids = np.argmax(np.asarray(logits), axis=-1)
    for rows, off in zip(old, MIXED_OFFSETS):
        np.testing.assert_array_equal(wave.token_ids(rows), ids[off : off + len(rows)])
        same_bits(np.asarray(rows), logits[off : off + len(rows)], "an old handle's rows")
        if out.aux_rows is not None:
            same_bits(wave.row_aux(rows), out.aux_rows[off : off + len(rows)], "an old handle's aux")
    assert wave.blocking_reads == 1


def harness(conn, cfg, params, name, **kw):
    kvc = KVConnector(conn, cfg.kv_spec(NUM_BLOCKS), name, max_blocks=MAX_REQ_BLOCKS)
    return ContinuousBatchingHarness(
        EngineKVAdapter(kvc), params, cfg, NUM_BLOCKS, MAX_REQ_BLOCKS, **kw
    )


def prompts(cfg, n, blocks, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, size=blocks * cfg.block_tokens).tolist() for _ in range(n)]


@pytest.mark.parametrize("drafted", [False, True], ids=["greedy", "drafter"])
def test_a_run_makes_two_host_transfers_a_wave(conn, model, drafted):
    """Several live requests, with and without a drafter's chunks:
    ``wave_host_transfers`` is 2 x ``decode_waves`` (no answer here ends on a
    block's edge, so no closing step rides a wave unread), the same tokens
    come out as from one request at a time, and ``_generate`` dispatches no
    argmax of its own (the decoder's one read a wave is all that is read)."""
    cfg, params = model
    ps = prompts(cfg, 4, 2, seed=362)
    # A repetitive prompt, so the drafter has something to propose.
    ps.append((ps[0][:4] * (2 * cfg.block_tokens))[: 2 * cfg.block_tokens])
    gen = 6

    async def drive(name, together, **kw):
        h = harness(conn, cfg, params, name, **kw)
        if together:
            stats = await asyncio.gather(*(h.run_request(p, gen_tokens=gen) for p in ps))
        else:
            stats = [await h.run_request(p, gen_tokens=gen) for p in ps]
        return h, [s.generated for s in stats]

    kw = {"drafter": NGramDrafter(max_draft=3)} if drafted else {}
    h, together = asyncio.run(drive(f"packed-{type(cfg).__name__}-{drafted}", True, **kw))
    m = h.metrics()
    assert m["max_wave_size"] >= 2 and m["generated_tokens"] == gen * len(ps)
    assert m["wave_host_transfers"] == 2 * m["decode_waves"]
    assert h.wave.blocking_reads == m["decode_waves"]
    if drafted:
        assert m["spec_drafted_tokens"] > 0, "no chunk of several rows rode a wave"
    _, alone = asyncio.run(drive(f"packed-solo-{type(cfg).__name__}-{drafted}", False))
    assert together == alone


def test_a_closing_step_rides_its_wave_unread(conn, model):
    """An answer that ends on a block's edge lands its last token's K/V with
    one more step, whose ids nobody asks for: an upload and no read."""
    cfg, params = model
    bt = cfg.block_tokens
    h = harness(conn, cfg, params, f"packed-closing-{type(cfg).__name__}")
    (p,) = prompts(cfg, 1, 2, seed=363)
    asyncio.run(h.run_request(p, gen_tokens=bt))
    m = h.metrics()
    assert m["decode_waves"] == bt + 1
    assert m["wave_host_transfers"] == 2 * bt + 1


def test_generate_holds_no_argmax():
    import inspect

    source = inspect.getsource(ContinuousBatchingHarness._generate)
    assert "jnp." not in source and "np.asarray" not in source
    assert "token_ids(rows)" in source


def test_the_layout_is_a_function_of_the_bucket_alone():
    """Field order, shapes and offsets follow from ``(T, B, P[, Pw])`` and
    ``max_blocks``; what is packed comes out again, piece by piece; a piece
    of another bucket's shape is refused."""
    rng = np.random.default_rng(364)
    for layout in (WaveLayout(8, 4, 32), WaveLayout(8, 4, 32, 40), WaveLayout(1, 1, 1, 1)):
        fields = layout.fields(MAX_REQ_BLOCKS)
        t, b, p, pw = layout
        want = 4 * t + 2 * p + 1 + b * MAX_REQ_BLOCKS
        if pw is not None:
            want += 2 * pw + 1 + t
        assert layout.size(MAX_REQ_BLOCKS) == want
        assert [n for n, _ in fields][:7] == [
            "tokens", "positions", "row_of", "pages", "page_rows", "page_starts", "block_tables",
        ]
        assert len(fields) == (7 if pw is None else 10)
        pieces = [rng.integers(0, 1 << 20, size=shape).astype(np.int32) for _, shape in fields]
        packed = pack_wave(layout, MAX_REQ_BLOCKS, pieces)
        assert packed.dtype == np.int32 and packed.shape == (want,)
        # One contiguous buffer, the pieces in order.
        np.testing.assert_array_equal(packed, np.concatenate([x.reshape(-1) for x in pieces]))
        out = unpack_wave(jnp.asarray(packed), layout, MAX_REQ_BLOCKS)
        assert list(out) == [n for n, _ in fields]
        for (name, shape), piece in zip(fields, pieces):
            assert out[name].shape == shape
            np.testing.assert_array_equal(np.asarray(out[name]), piece)
        with pytest.raises(ValueError):
            pack_wave(layout, MAX_REQ_BLOCKS, pieces[:-1])
        with pytest.raises(ValueError):
            pack_wave(layout, MAX_REQ_BLOCKS, [np.zeros(t + 1, np.int32)] + pieces[1:])
        with pytest.raises(ValueError):
            unpack_wave(jnp.zeros(want + 1, jnp.int32), layout, MAX_REQ_BLOCKS)
    # Lists go in as the flush builds them.
    packed = pack_wave(WaveLayout(2, 1, 1), 2, [[3, 4], [5, 6], [0, 0], [7], [0, 1], [0, 0], [[8, 9]]])
    np.testing.assert_array_equal(packed, [3, 4, 5, 6, 0, 0, 7, 0, 1, 0, 0, 8, 9])


def test_one_wave_program_a_bucket_as_before(conn):
    """The buckets a run lands on are the (B, T, P) triples they were, and
    the packed entry holds exactly one compiled program for each: the layout
    adds nothing to the jit key that the bucket does not say. (A vocabulary
    of its own, so nothing here was traced before.)"""
    cfg = LlamaConfig(
        vocab=127, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128,
        block_tokens=8, dtype=jnp.float32,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(365))
    before = serving.verify_step_ragged._cache_size()
    h = harness(conn, cfg, params, "packed-buckets")
    ps = [p[: (2 + i % 2) * cfg.block_tokens] for i, p in enumerate(prompts(cfg, 5, 3, seed=365))]
    m = asyncio.run(h.run(ps, concurrency=5, gen_tokens=6))
    buckets = m["wave_buckets"]
    assert buckets and all(
        x & (x - 1) == 0 for bucket in buckets for x in bucket
    ), buckets
    assert serving.verify_step_ragged._cache_size() - before == len(buckets)


# ---------------------------------------------------------------------------
# One wave ahead: a declared stream's next row is launched with its token read
# on the device from the wave before it.
# ---------------------------------------------------------------------------

AHEAD_MODELS = {
    "llama": MODELS["llama"],
    # A state and a conv tail beside K/V pages in every layer: a row launched
    # twice, or once too often, would show in the state.
    "falcon_h1": (
        falcon_h1.FalconH1Config(dtype=jnp.float32, rope_theta=1e4),
        lambda cfg: falcon_h1.init_params(cfg, jax.random.key(49)),
    ),
}


@pytest.fixture(scope="module", params=sorted(AHEAD_MODELS))
def ahead_model(request):
    cfg, init = AHEAD_MODELS[request.param]
    return cfg, init(cfg)


def landed_prompts(cfg, params, lengths, seed):
    """Requests whose prompts are on the device as ``run_request`` leaves them
    before the first wave: all but the last token under a recurrent state, cut
    at block edges through the model's resume step; otherwise the prompt's
    whole blocks through its prefill. Returns ``(tables, first round's (token, position) a
    request, caches)``."""
    bt = cfg.block_tokens
    rng = np.random.default_rng(seed)
    has_state = cfg.kv_spec(1).has_state
    tables = np.zeros((len(lengths), MAX_REQ_BLOCKS), np.int32)
    caches = cfg.kv_spec(NUM_BLOCKS).make_caches()
    starts, first = [], 1
    for r, n in enumerate(lengths):
        tables[r] = np.arange(first, first + MAX_REQ_BLOCKS)
        first += MAX_REQ_BLOCKS
        if not has_state:
            n = n // bt * bt
        prompt = rng.integers(0, cfg.vocab, size=n)
        if has_state:
            for at in range(0, n - 1, bt):
                _, caches = cfg.steps.resume(
                    params, jnp.asarray(prompt[at : min(at + bt, n - 1)], jnp.int32),
                    jnp.int32(at), caches, tables[r], cfg, MAX_REQ_BLOCKS,
                )
        else:
            _, caches = cfg.steps.prefill(
                params, jnp.asarray(prompt, jnp.int32), caches,
                jnp.asarray(tables[r, : n // bt]), cfg,
            )
        starts.append((int(prompt[-1]), n - 1))
    return tables, starts, caches


async def request_loop(wave, table, tok, pos, rounds, declare=True, seen=None):
    """``_generate``'s loop without a drafter: a round's token is on the host
    before the next round is asked for."""
    out = []
    declared = wave.stream(table, pos + rounds - 1) if declare else contextlib.nullcontext()
    with declared:
        for _ in range(rounds):
            rows = await asyncio.wait_for(wave.step_chunk([tok], [pos], table), 60)
            ids = wave.token_ids(rows)
            if seen is not None:
                seen.append((wave.waves, wave.waves_ahead))
            out.append((np.asarray(rows), int(ids[0])))
            tok, pos = int(ids[0]), pos + 1
    return out


def streaming_decoder(cfg, params, caches):
    h = bare_harness(cfg, params, caches)
    h.arriving = 0
    return WaveDecoder(h)


def test_a_wave_launched_ahead_is_the_host_fed_wave_bit_for_bit(ahead_model):
    """Three requests decode two blocks' worth of rounds in lockstep, once as
    declared streams (every wave but the first launched while its requests
    still read the wave before it, its tokens read on the device) and once as
    bare ``step_chunk`` calls that carry the tokens up from the host: the same
    waves, every logits row and id equal in every bit, and every cache tensor
    (K/V pages, and the state and tail where the model keeps them) after."""
    cfg, params = ahead_model
    bt = cfg.block_tokens
    rounds = 2 * bt
    # Part-full last blocks, so the rounds cross block edges at different waves.
    tables, starts, caches = landed_prompts(cfg, params, (2 * bt, bt + 3, 3 * bt - 2), seed=491)

    async def run(declare):
        wave = streaming_decoder(cfg, params, caches)
        outs = await asyncio.gather(*(
            request_loop(wave, tables[r], tok, pos, rounds, declare)
            for r, (tok, pos) in enumerate(starts)
        ))
        return wave, outs

    ahead, got = asyncio.run(run(True))
    plain, want = asyncio.run(run(False))
    assert (ahead.waves, ahead.launched_rows, ahead.pad_rows) == (rounds, 4 * rounds, rounds)
    assert (plain.waves, plain.launched_rows, plain.bucket_sizes) == (
        rounds, 4 * rounds, ahead.bucket_sizes
    )
    assert (ahead.waves_ahead, plain.waves_ahead) == (rounds - 1, 0)
    assert ahead.blocking_reads == plain.blocking_reads == rounds
    for r in range(3):
        assert [tok for _, tok in got[r]] == [tok for _, tok in want[r]]
        for k in range(rounds):
            assert got[r][k][0].tobytes() == want[r][k][0].tobytes(), (r, k)
    same_bits(ahead.h.caches, plain.h.caches, "caches")


def test_the_fed_operand_reads_the_named_row_and_nothing_else(model):
    """The program itself: a slot ``fed_token(src)`` is read as
    ``prev_ids[src]``, a token slot is the token, and the wave is in every bit
    the wave whose tokens the host wrote out."""
    cfg, params = model
    tables, chunks, caches = mixed_wave(cfg, params)
    wave = WaveDecoder(bare_harness(cfg, params, caches))
    batch = [(toks, pos, tables[r], None) for r, (toks, pos) in enumerate(chunks)]
    w = wave._assemble(batch)
    prev = np.zeros(serving.FEED_ROWS, np.int32)
    prev[[0, 5, serving.FEED_ROWS - 1]] = w.tokens[0], w.tokens[4], w.tokens[2]
    slots = list(w.tokens)
    slots[0], slots[2] = serving.fed_token(0), serving.fed_token(serving.FEED_ROWS - 1)
    slots[4:] = [serving.fed_token(5)] * (len(slots) - 4)  # the last real row and its repeats
    want = wave.launch(w.tokens, w.positions, w.row_of, w.meta, w.tables, w.wmeta)
    want_caches = wave.h.caches
    wave.h.caches = jax.tree.map(jnp.copy, caches)
    got = wave.launch(slots, w.positions, w.row_of, w.meta, w.tables, w.wmeta, jnp.asarray(prev))
    same_bits(got[0], want[0], "logits")
    same_bits((got[1].ids, got[1].feed), (want[1].ids, want[1].feed), "ids")
    same_bits(wave.h.caches, want_caches, "caches")
    with pytest.raises(ValueError):
        serving.fed_token(serving.FEED_ROWS)


def test_a_last_round_launches_no_row_and_a_drafter_launches_none_ahead(conn, model):
    """Through the harness: a lone request's every round but the first rides a
    wave launched ahead, no row is launched that no round takes (a closing
    step is a round), and under a drafter nothing is launched ahead at all."""
    cfg, params = model
    bt = cfg.block_tokens
    (p,) = prompts(cfg, 1, 2, seed=492)
    for gen, rows in ((5, 5), (bt, bt + 1)):
        h = harness(conn, cfg, params, f"ahead-last-{type(cfg).__name__}-{gen}")
        asyncio.run(h.run_request(p, gen_tokens=gen))
        m = h.metrics()
        assert h.wave.launched_rows - h.wave.pad_rows == rows == m["decode_waves"]
        assert m["wave_ahead_waves"] == rows - 1
        assert h.arriving == 0 and not h.wave._streams
    h = harness(
        conn, cfg, params, f"ahead-drafter-{type(cfg).__name__}", drafter=NGramDrafter(max_draft=3)
    )
    repetitive = (p[:4] * (2 * bt))[: 2 * bt]
    asyncio.run(h.run_request(repetitive, gen_tokens=6))
    m = h.metrics()
    assert "wave_ahead_waves" in m and m["wave_ahead_waves"] == 0
    assert m["spec_drafted_tokens"] > 0


def test_an_arriving_request_holds_the_launch_and_its_first_wave_carries_everyone():
    """While the harness counts a request between its admission and its first
    wave, a stream's waves are launched as they always were (its request back
    with the token first). The newcomer's first wave carries the stream's row
    too, and from the next wave on both are launched ahead, in ONE wave."""
    cfg, init = MODELS["llama"]
    params = init(cfg)
    bt = cfg.block_tokens
    tables, starts, caches = landed_prompts(cfg, params, (2 * bt, 2 * bt), seed=493)

    async def run():
        wave = streaming_decoder(cfg, params, caches)
        h, seen, late = wave.h, [], []

        async def newcomer():
            h.arriving += 1  # admitted: it will want the device for its prefill
            while wave.waves < 3:
                await asyncio.sleep(0)
            held = (wave.waves, wave.waves_ahead)
            h.arriving -= 1  # its first round is next
            await request_loop(wave, tables[1], *starts[1], 6, seen=late)
            return held

        first = asyncio.ensure_future(request_loop(wave, tables[0], *starts[0], 12, seen=seen))
        held = await newcomer()
        await first
        return wave, held, seen, late

    wave, held, seen, late = asyncio.run(run())
    assert held[0] >= 3 and held[1] == 0, "a wave was launched ahead in front of an arrival"
    # The newcomer's first wave is the next one, both rows the host's; when its
    # first token is read the wave after that is on the device, both rows fed.
    assert late[0] == (held[0] + 2, 1)
    # 12 + 6 rounds in 12 waves: no stream rode a wave of its own.
    assert (wave.waves, wave.max_wave, wave.launched_rows - wave.pad_rows) == (12, 2, 18)
    assert wave.waves_ahead == 12 - (held[0] + 1)


def test_a_bare_step_chunk_is_launched_as_before_and_rides_beside_a_stream():
    """Bare calls (the benchmark's warm-up, a test, a drafter's chunk) resolve
    as they always did, one wave a call; beside a stream they ride its waves,
    their tokens the host's while the stream's come from the device."""
    cfg, init = MODELS["llama"]
    params = init(cfg)
    bt = cfg.block_tokens
    tables, starts, caches = landed_prompts(cfg, params, (2 * bt, 2 * bt), seed=494)

    async def run():
        wave = streaming_decoder(cfg, params, caches)
        for k in range(3):
            await wave.step_chunk([7 + k], [2 * bt + k], tables[1])
        alone = (wave.waves, wave.waves_ahead, wave.max_wave)
        stream = asyncio.ensure_future(request_loop(wave, tables[0], *starts[0], 8))
        for k in range(3, 6):
            await wave.step_chunk([7 + k, 8 + k], [2 * bt + k, 2 * bt + k + 1], tables[1])
        await stream
        return wave, alone

    wave, alone = asyncio.run(run())
    assert alone == (3, 0, 1)
    assert wave.max_wave == 2, "the bare chunks rode waves of their own"
    assert wave.waves_ahead >= 6


def test_a_stream_that_never_comes_back_strands_nobody():
    """One of two streams stops after its second round (its request was
    cancelled): the row launched ahead for it is never taken, the other stream
    runs to its end, and nothing more is launched for the one that left."""
    cfg, init = MODELS["llama"]
    params = init(cfg)
    bt = cfg.block_tokens
    tables, starts, caches = landed_prompts(cfg, params, (2 * bt, 2 * bt), seed=495)

    async def run():
        wave = streaming_decoder(cfg, params, caches)

        async def leaves():
            (tok, pos), table = starts[1], tables[1]
            with pytest.raises(asyncio.CancelledError):
                with wave.stream(table, pos + 9):
                    for _ in range(2):
                        rows = await wave.step_chunk([tok], [pos], table)
                        tok, pos = int(wave.token_ids(rows)[0]), pos + 1
                    raise asyncio.CancelledError

        stays, _ = await asyncio.gather(request_loop(wave, tables[0], *starts[0], 10), leaves())
        return wave, stays

    wave, stays = asyncio.run(run())
    assert len(stays) == 10 and not wave._streams and not wave._pending
    # Ten rows of the one that stayed; two taken and one launched ahead of the other.
    assert wave.launched_rows - wave.pad_rows == 13


def test_a_dead_flush_fails_every_waiter_ahead_or_not(monkeypatch):
    """The program raises at its fourth launch: the stream whose row that wave
    would have carried and the bare call beside it both get the error, no
    future is left pending, and the decoder launches again afterwards."""
    cfg, init = MODELS["llama"]
    params = init(cfg)
    bt = cfg.block_tokens
    tables, starts, caches = landed_prompts(cfg, params, (2 * bt, 2 * bt), seed=496)
    real, calls = engine_mod.verify_step_ragged, []

    def dies_once(*args, **kw):
        calls.append(1)
        if len(calls) == 4:
            raise RuntimeError("the device said no")
        return real(*args, **kw)

    monkeypatch.setattr(engine_mod, "verify_step_ragged", dies_once)

    async def run():
        wave = streaming_decoder(cfg, params, caches)

        async def bare():
            while len(calls) < 3:
                await asyncio.sleep(0)
            return await wave.step_chunk([9], [2 * bt], tables[1])

        got = await asyncio.gather(
            request_loop(wave, tables[0], *starts[0], 10), bare(), return_exceptions=True
        )
        assert not wave._pending and not wave._streams and not wave._flush_scheduled
        again = await request_loop(wave, tables[0], *starts[0], 3)
        return got, again

    got, again = asyncio.run(run())
    assert all(isinstance(e, RuntimeError) and "said no" in str(e) for e in got), got
    assert len(again) == 3


def test_a_stream_that_comes_back_elsewhere_is_an_error():
    """A declared stream's call that does not fit the row launched for it (a
    position that is not the next one) is refused, not served from the host."""
    cfg, init = MODELS["llama"]
    params = init(cfg)
    bt = cfg.block_tokens
    tables, starts, caches = landed_prompts(cfg, params, (2 * bt,), seed=497)

    async def run():
        wave = streaming_decoder(cfg, params, caches)
        (tok, pos), table = starts[0], tables[0]  # the stream is known by the table OBJECT
        with wave.stream(table, pos + 4):
            rows = await wave.step_chunk([tok], [pos], table)
            tok = int(wave.token_ids(rows)[0])
            with pytest.raises(RuntimeError, match="launched for 1 at"):
                await wave.step_chunk([tok], [pos + 2], table)

    asyncio.run(run())


def test_the_counter_is_in_the_harness_metrics(conn):
    cfg, init = MODELS["llama"]
    h = harness(conn, cfg, init(cfg), "ahead-metric")
    assert h.metrics()["wave_ahead_waves"] == 0
    ps = prompts(cfg, 3, 2, seed=498)
    m = asyncio.run(h.run(ps, concurrency=3, gen_tokens=6))
    assert 0 < m["wave_ahead_waves"] < m["decode_waves"]
    assert m["wave_host_transfers"] == 2 * m["decode_waves"]


# ---------------------------------------------------------------------------
# A model that drafts: its stream's next SLOT ``[token, draft]`` is launched
# ahead under a guessed verdict, one position on (the draft rejected) or two.
# ---------------------------------------------------------------------------

# The multi-token-prediction model at its small size (tests/test_pangu_mtp.py).
# With these seeded weights no draft lands at a vocabulary of 512, every draft
# at 4, and about half of them at 2.
DRAFTING = pangu_mtp.PanguMtpConfig(dtype=jnp.float32)
LANDS = {"rejected": 512, "accepted": 4}


def drafting_run(conn, name, vocab, together, *, ahead=True, guess=None, gen=25):
    """Three requests through a harness of the drafting model, served one at a
    time or together. ``ahead`` false: the host-fed decoder (no request declares
    its stream, as before drafting streams were). ``guess``: the verdict every
    slot is launched under (the harness's record says so from the start; None:
    the record as the run itself writes it). Returns ``(harness, generated a
    request, (prompt length, table) a request, the fed entries that broke a
    stream's promise)``."""
    cfg = dataclasses.replace(DRAFTING, vocab=vocab)
    params = pangu_mtp.init_params(cfg, jax.random.key(622))
    rng = np.random.default_rng(623)
    bt = cfg.block_tokens
    # 29 + 25, 18 + 25, 15 + 25: the third answer closes a block.
    ps = [rng.integers(0, vocab, size=n).tolist() for n in (3 * bt + 5, 2 * bt + 2, bt + 7)]
    h = harness(conn, cfg, params, name)
    if guess is not None:
        h.spec_drafted, h.spec_accepted = 10_000, 10_000 * guess
    wave, live, broken, served = h.wave, {}, [], []
    real_stream, real_assemble, real_generate = wave.stream, wave._assemble, h._generate

    @contextlib.contextmanager
    def stream(table, last):
        live[id(table)] = last
        try:
            with real_stream(table, last) if ahead else contextlib.nullcontext():
                yield
        finally:
            del live[id(table)]

    def assemble(batch):
        # A fed entry has no future: its stream is live and the slot ends by ``last``.
        broken.extend(
            (pos, live.get(id(table))) for _, pos, table, fut in batch
            if fut is None and not pos[-1] <= live.get(id(table), -1)
        )
        return real_assemble(batch)

    async def generate(token_ids, table, gen_tokens):
        served.append((len(token_ids), np.array(table)))
        return await real_generate(token_ids, table, gen_tokens)

    wave.stream, wave._assemble, h._generate = stream, assemble, generate

    async def drive():
        if together:
            return await asyncio.gather(*(h.run_request(p, gen_tokens=gen) for p in ps))
        return [await h.run_request(p, gen_tokens=gen) for p in ps]

    stats = asyncio.run(drive())
    return h, [s.generated for s in stats], served, broken


def committed(h, served, gen):
    """What a request's committed positions hold, layer by layer: every latent
    slot up to the last position its rounds wrote, and the boundary rows of its
    complete blocks."""
    bt = h.config.block_tokens
    out = []
    for n, table in served:
        end = n + gen - 1 + ((n + gen) % bt == 0)  # a closing step lands the last token too
        ids = jnp.asarray(table[: -(-end // bt)])
        for layer in h.caches:
            latent = np.asarray(layer[0][ids])  # [blocks, width, bt]
            out.append(np.moveaxis(latent, 2, 1).reshape(-1, latent.shape[1])[:end])
            out.extend(np.asarray(t[ids[: end // bt]]) for t in layer[1:])
    return out


@pytest.fixture()
def guess_by_the_harness(monkeypatch):
    """The guess follows the harness's record alone, which ``drafting_run`` fixes."""
    monkeypatch.setattr(WaveDecoder, "OWN_RECORD_ROUNDS", 1 << 30)


@pytest.mark.parametrize("together", [False, True], ids=["one-at-a-time", "three-live"])
@pytest.mark.parametrize("fact", sorted(LANDS))
@pytest.mark.parametrize("guess", sorted(LANDS))
def test_a_drafting_streams_slot_launched_ahead_is_the_host_fed_slot(
    conn, guess_by_the_harness, guess, fact, together
):
    """Every slot launched under ``guess`` while every draft is, in ``fact``,
    rejected or accepted: the tokens are the host-fed decoder's and so is every
    committed position of every layer's cache, the drafting layer's slots and
    boundary rows among them; a wave is read at most once; no slot is launched
    for a stream that ended or past its last position. A right guess drops
    nothing and costs no wave; a wrong one raises nothing, is counted, and costs
    its request one more wave, the round through the host."""
    gen, name = 25, f"slot-{guess}-{fact}-{together}"
    h, got, served, broken = drafting_run(
        conn, name, LANDS[fact], together, guess=guess == "accepted", gen=gen
    )
    p, want, p_served, _ = drafting_run(conn, name + "-plain", LANDS[fact], together, ahead=False, gen=gen)
    assert got == want
    assert not broken, broken
    mine, theirs = (sorted(s, key=lambda r: r[0]) for s in (served, p_served))  # by prompt
    for i, (a, b) in enumerate(zip(committed(h, mine, gen), committed(p, theirs, gen))):
        np.testing.assert_array_equal(a, b, err_msg=f"cache tensor {i}")
    m, base = h.metrics(), p.metrics()
    assert base["wave_ahead_waves"] == base["wave_ahead_dropped"] == 0
    assert m["spec_rounds"] == base["spec_rounds"]
    assert m["spec_accepted_tokens"] - 10_000 * (guess == "accepted") == base["spec_accepted_tokens"]
    assert h.wave.blocking_reads <= h.wave.waves and not h.wave._streams
    assert m["wave_ahead_waves"] > 10 - 5 * together
    if guess == fact:
        # (At 512 one draft of these seeds may land: one slot dropped.)
        assert m["wave_ahead_dropped"] <= (fact == "rejected")
    else:
        assert m["wave_ahead_dropped"] > 10
    if not together:
        assert m["wave_ahead_dropped"] <= m["wave_ahead_waves"]
        assert m["decode_waves"] == base["decode_waves"] + m["wave_ahead_dropped"]
        assert h.wave.blocking_reads == p.wave.blocking_reads  # a dropped slot's wave is never read


@pytest.mark.parametrize("vocab", [2, 4], ids=["half-land", "all-land"])
def test_the_guess_follows_what_the_drafts_did(conn, vocab):
    """Nothing fixed: the first slots go out as rejected (nothing is known), and
    once the record says that drafts land the slots go out as accepted. Where
    every draft lands the wrong guesses are the first few; where half do, about
    half the slots are dropped, and the answer is the host-fed decoder's still."""
    h, got, _, broken = drafting_run(conn, f"guess-{vocab}", vocab, together=False)
    _, want, _, _ = drafting_run(conn, f"guess-{vocab}-plain", vocab, together=False, ahead=False)
    m = h.metrics()
    assert got == want and not broken
    assert m["spec_accepted_tokens"] >= 20
    if vocab == 4:
        assert 1 <= m["wave_ahead_dropped"] <= 3
    else:
        assert 3 < m["wave_ahead_dropped"] < m["wave_ahead_waves"]


def test_a_streams_own_record_outvotes_the_harness_after_a_few_rounds():
    cfg, init = MODELS["llama"]
    h = bare_harness(cfg, init(cfg), cfg.kv_spec(NUM_BLOCKS).make_caches())
    h.spec_drafted = h.spec_accepted = 0
    wave = WaveDecoder(h)
    stream = engine_mod._Stream(np.zeros(MAX_REQ_BLOCKS, np.int32), 99)
    assert wave._guess(stream) is False  # nothing known
    h.spec_drafted, h.spec_accepted = 10, 6
    assert wave._guess(stream) is True
    stream.rounds, stream.accepted = wave.OWN_RECORD_ROUNDS - 1, 0
    assert wave._guess(stream) is True  # too few rounds of its own
    stream.rounds += 1
    assert wave._guess(stream) is False
    stream.accepted = stream.rounds // 2 + 1
    assert wave._guess(stream) is True


def drafting_decoder():
    """A bare decoder of the drafting model over one landed prompt: ``(decoder,
    the request's table, the position after its prompt)``."""
    cfg = dataclasses.replace(DRAFTING, vocab=64)
    params = pangu_mtp.init_params(cfg, jax.random.key(633))
    prompt = np.random.default_rng(634).integers(0, cfg.vocab, size=2 * cfg.block_tokens + 3)
    table = np.arange(1, 1 + MAX_REQ_BLOCKS, dtype=np.int32)
    _, caches = cfg.steps.prefill(
        params, jnp.asarray(prompt, jnp.int32), cfg.kv_spec(NUM_BLOCKS).make_caches(),
        jnp.asarray(table[:3]), cfg,
    )
    h = bare_harness(cfg, params, caches)
    h.arriving = h.spec_drafted = h.spec_accepted = 0
    return WaveDecoder(h), table, len(prompt)


def test_a_drafting_waves_feed_carries_its_drafts_and_a_slot_reads_either():
    """The program of a model that drafts: its ``feed`` is the ids of its first
    ``FEED_ROWS`` rows and then their drafts, a slot ``fed_token(src)`` reads
    the one and ``fed_token(src, draft=True)`` the other, and the fed wave is in
    every bit the wave whose tokens the host wrote out."""
    rows = serving.FEED_ROWS
    assert (serving.feed_rows(), serving.feed_rows(True)) == (rows, 2 * rows)
    assert serving.no_feed(True).shape == (2 * rows,)
    wave, table, at = drafting_decoder()
    w = wave._assemble([([5, 9], [at, at + 1], table, None), ([7], [at], table + 20, None)])
    _, first, _ = wave.launch(w.tokens, w.positions, w.row_of, w.meta, w.tables)
    ids = np.asarray(first.ids)
    assert ids.shape == (2, 4)  # two slots of two; sampled over drafted
    feed = np.zeros(2 * rows, np.int32)
    feed[:4], feed[rows : rows + 4] = ids
    np.testing.assert_array_equal(np.asarray(first.feed), feed)
    # The next wave: the first entry's next slot under "rejected", from row 0.
    nxt = wave._assemble([([int(ids[0, 0]), int(ids[1, 0])], [at + 1, at + 2], table, None)])
    kept = jax.tree.map(jnp.copy, wave.h.caches)
    want = wave.launch(nxt.tokens, nxt.positions, nxt.row_of, nxt.meta, nxt.tables)
    want_caches, wave.h.caches = wave.h.caches, kept
    slots = [serving.fed_token(0), serving.fed_token(0, draft=True)]
    got = wave.launch(slots, nxt.positions, nxt.row_of, nxt.meta, nxt.tables, None, first.feed)
    same_bits(got[0], want[0], "logits")
    same_bits((got[1].ids, got[1].feed), (want[1].ids, want[1].feed), "ids")
    same_bits(wave.h.caches, want_caches, "caches")
    with pytest.raises(ValueError):
        wave.launch(slots, nxt.positions, nxt.row_of, nxt.meta, nxt.tables, None, serving.no_feed())


def test_a_drafting_stream_fits_one_of_two_verdicts_or_is_an_error():
    """A drafting stream's call may come back one position on or two (the slot
    launched for the other verdict is dropped and nothing is raised); anywhere
    else, or past the table it declared, is refused."""
    wave, table, at = drafting_decoder()

    async def run():
        with pytest.raises(ValueError, match="past a table"):
            with wave.stream(table, MAX_REQ_BLOCKS * wave.h.config.block_tokens):
                pass
        with wave.stream(table, at + 20):
            rows = await wave.step_chunk([5], [at - 1], table)  # a first round: one token
            tok, draft = int(wave.token_ids(rows)[0]), int(wave.draft_ids(rows)[0])
            rows = await wave.step_chunk([tok, draft], [at, at + 1], table)  # launched ahead
            # Handed only once the flush that took it had launched the slot after it.
            assert (wave.waves, wave.waves_ahead, wave.ahead_dropped) == (3, 2, 0)
            # As if the draft had been accepted: two on, where nothing was launched.
            ids, drafts = wave.token_ids(rows), wave.draft_ids(rows)
            await wave.step_chunk([int(ids[1]), int(drafts[1])], [at + 2, at + 3], table)
            assert wave.ahead_dropped == 1
            with pytest.raises(RuntimeError, match="its slot was launched for 2 at"):
                await wave.step_chunk([1, 2], [at + 9, at + 10], table)

    asyncio.run(run())
