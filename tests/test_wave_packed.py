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
)
from infinistore_tpu.models import AfmoeConfig, LlamaConfig, afmoe, llama, serving
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

    def twice(params_, packed, caches_, *, config, max_blocks, layout):
        f = {k: jnp.asarray(v) for k, v in unpack_wave(packed, layout, max_blocks).items()}
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
        logits, got_caches, ids, aux = real(
            params_, packed, caches_, config=config, max_blocks=max_blocks, layout=layout
        )
        same_bits(logits, want_logits, "logits")
        same_bits(got_caches, want_caches, "caches")
        same_bits(aux, want_aux[0] if want_aux else {}, "aux")
        assert ids.dtype == jnp.int32 and ids.shape == (layout.rows,)
        np.testing.assert_array_equal(np.asarray(ids), np.argmax(np.asarray(logits), axis=-1))
        seen.append(layout)
        return logits, got_caches, ids, aux

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


def test_the_ids_are_the_argmax_of_the_rows_handed_back_and_a_wave_reads_once(model):
    """One-token rows and a drafter's chunk: ``token_ids(rows)`` is the argmax
    of the logits rows ``step_chunk`` resolved to, and the three requests of
    the wave cost ONE blocking read between them."""
    cfg, params = model
    tables, chunks, caches = mixed_wave(cfg, params)
    reads = []

    async def one(wave, r, toks, pos):
        rows = await wave.step_chunk(toks, pos, tables[r])
        assert isinstance(rows, jax.Array) and rows.shape == (len(toks), cfg.vocab)
        ids = wave.token_ids(rows)
        reads.append(wave.blocking_reads)
        assert isinstance(ids, np.ndarray) and ids.dtype == np.int32
        np.testing.assert_array_equal(ids, np.asarray(jnp.argmax(rows, axis=-1)))
        return rows

    async def run():
        wave = WaveDecoder(bare_harness(cfg, params, caches))
        handed = await asyncio.gather(*(
            one(wave, r, toks, pos) for r, (toks, pos) in enumerate(chunks)
        ))
        return wave, handed

    wave, handed = asyncio.run(run())
    assert wave.waves == 1 and wave.max_wave == 3
    assert reads == [1, 1, 1], "a later request of the wave read the device again"
    assert (wave.waves, wave.blocking_reads) == (1, 1)
    # Asking again costs nothing; rows of no wave are no key.
    wave.token_ids(handed[1])
    assert wave.blocking_reads == 1
    with pytest.raises(KeyError):
        wave.token_ids(jnp.zeros((1, cfg.vocab), jnp.float32))


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
