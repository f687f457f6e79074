"""Fused paged decode attention: the ragged Pallas kernel (interpret mode on
CPU) against the XLA fallback and a from-scratch numpy oracle, across GQA
shapes, partial blocks, wave layouts and padded tables. The reference has no
engine-side compute at all (SURVEY.md §2.9) — this kernel is the TPU build's
consumer-side hot op: every decode row of models/llama.py attends through
it (verify_step_ragged, decode_step as its one-row view, the disagg
decode_wave_layer)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest


def _numpy_oracle(q, k_cache, v_cache, table, seq_len):
    """Dense decode attention in float64 numpy: gather, mask, softmax."""
    q = np.asarray(q, np.float64)
    h, d = q.shape
    kvh = k_cache.shape[2]
    groups = h // kvh
    k = np.asarray(k_cache, np.float64)[np.asarray(table)].reshape(-1, kvh, d)
    v = np.asarray(v_cache, np.float64)[np.asarray(table)].reshape(-1, kvh, d)
    k = np.repeat(k, groups, axis=1)
    v = np.repeat(v, groups, axis=1)
    logits = np.einsum("hd,thd->ht", q, k) / np.sqrt(d)
    logits[:, seq_len:] = -np.inf
    p = np.exp(logits - logits.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    return np.einsum("ht,thd->hd", p, v)


CASES = [
    # (num_blocks, block_tokens, kv_heads, head_dim, q_heads, table_len)
    (16, 8, 4, 16, 8, 8),  # GQA x2
    (32, 16, 2, 32, 8, 16),  # GQA x4
    (8, 8, 8, 16, 8, 4),  # MHA (no GQA)
    (16, 8, 1, 64, 4, 16),  # MQA (one kv head)
]


def test_padded_table_entries_are_ignored():
    """Entries past seq_len may alias ANY valid block (engines pad with 0);
    their contents must not leak into the output — the contract of the XLA
    body that ``_ragged_row_tables`` leans on, and of the kernel under a
    full-width rectangle."""
    from infinistore_tpu.tpu.paged_attention import (
        _paged_decode_attention_pallas_ragged,
        paged_decode_attention_xla_batched,
        rectangle_as_ragged,
    )

    n, bt, kvh, d, h = 8, 8, 2, 16, 4
    rng = np.random.default_rng(7)
    k_cache = jnp.asarray(rng.standard_normal((n, bt, kvh, d)), jnp.float32)
    v_cache = jnp.asarray(rng.standard_normal((n, bt, kvh, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((1, h, d)), jnp.float32)
    sl = jnp.asarray([bt + 3], jnp.int32)  # two blocks in play, second partial
    base = jnp.asarray([[2, 5, 0, 0]], jnp.int32)
    alias = jnp.asarray([[2, 5, 7, 1]], jnp.int32)  # different garbage tail
    np.testing.assert_array_equal(
        np.asarray(paged_decode_attention_xla_batched(q, k_cache, v_cache, base, sl)),
        np.asarray(paged_decode_attention_xla_batched(q, k_cache, v_cache, alias, sl)),
    )
    kernel = lambda tables: _paged_decode_attention_pallas_ragged(
        q, k_cache, v_cache, *rectangle_as_ragged(tables), sl, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(kernel(base)), np.asarray(kernel(alias)))


def _tiny_config(n_heads=4, n_kv_heads=2, vocab=64):
    from infinistore_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab=vocab, dim=32, n_layers=2, n_heads=n_heads, n_kv_heads=n_kv_heads,
        ffn_dim=64, block_tokens=8, dtype=jnp.float32,
    )


def _prefilled_wave(cfg, rng, prompt_lens=(16, 16, 16), num_blocks=16, max_blocks=3):
    """Caches holding one prefilled prompt a request, disjoint tables."""
    from infinistore_tpu.models import init_params, prefill

    params = init_params(cfg, jax.random.PRNGKey(0))
    bsz = len(prompt_lens)
    tables = np.arange(bsz * max_blocks, dtype=np.int32).reshape(bsz, max_blocks)
    caches = cfg.kv_spec(num_blocks).make_caches()
    for n, tab in zip(prompt_lens, tables):
        prompt = rng.integers(0, cfg.vocab, size=n)
        _, caches = prefill(
            params, jnp.asarray(prompt, jnp.int32), caches,
            jnp.asarray(tab[: n // cfg.block_tokens]), cfg,
        )
    return params, caches, tables


def _assert_caches_close(got, want, tol=2e-5):
    for layer, (g, w) in enumerate(zip(got, want)):
        for kind in (0, 1):
            np.testing.assert_allclose(
                np.asarray(g[kind]), np.asarray(w[kind]), rtol=tol, atol=tol,
                err_msg=f"layer {layer} {'kv'[kind]}",
            )


def test_ragged_wave_matches_sequential_decode_step():
    """A wave of requests through verify_step_ragged must produce the same
    logits and cache contents as advancing each request alone with
    decode_step (disjoint block tables, shared cache), to float32
    rounding."""
    from infinistore_tpu.models import decode_step, verify_step_ragged
    from infinistore_tpu.tpu.paged_attention import build_ragged_wave

    cfg = _tiny_config()
    max_blocks = 3
    # Three requests at different positions, disjoint block tables.
    params, caches, tables = _prefilled_wave(
        cfg, np.random.default_rng(4), prompt_lens=(16, 8, 16)
    )
    next_toks = jnp.asarray([5, 9, 13], jnp.int32)
    positions = jnp.asarray([16, 8, 16], jnp.int32)

    # The steps donate their cache: the sequential run gets a copy of its own.
    seq_caches = jax.tree.map(jnp.copy, caches)
    seq_logits = []
    for b in range(3):
        lg, seq_caches = decode_step(
            params, next_toks[b], positions[b], seq_caches,
            jnp.asarray(tables[b]), cfg, max_blocks,
        )
        seq_logits.append(lg)

    meta = build_ragged_wave(
        list(tables), np.asarray(positions) + 1, cfg.block_tokens
    )
    wave_logits, wave_caches = verify_step_ragged(
        params, next_toks, positions, jnp.arange(3, dtype=jnp.int32),
        jnp.asarray(meta.pages), jnp.asarray(meta.page_rows),
        jnp.asarray(meta.page_starts), caches, jnp.asarray(tables), cfg,
        max_blocks,
    )
    np.testing.assert_allclose(
        np.asarray(wave_logits), np.asarray(jnp.stack(seq_logits)),
        rtol=2e-5, atol=2e-5,
    )
    _assert_caches_close(wave_caches, seq_caches)


@pytest.mark.parametrize(
    "entry", ["prefill", "decode_step", "verify_step_ragged", "resume_chunk"]
)
def test_serving_entries_donate_their_cache(entry):
    """Every jitted entry that rewrites the paged cache donates it (jax
    honours donation on the CPU too): the arrays handed in are deleted by
    the call, the returned ones are live and distinct buffers, the blocks
    the call wrote differ from the input's and every other block holds the
    input's bytes."""
    from infinistore_tpu.models import (
        decode_step, prefill, resume_chunk, verify_step_ragged,
    )
    from infinistore_tpu.tpu.paged_attention import build_ragged_wave

    cfg = _tiny_config()
    max_blocks = 3
    params, caches, tables = _prefilled_wave(
        cfg, np.random.default_rng(11), prompt_lens=(16, 8, 16)
    )
    # A copy made on the device: on the CPU ``np.asarray`` of the input
    # itself is a view that shares its buffer, and a shared buffer is not
    # donated (the step then copies it, silently).
    before = [
        (np.asarray(k), np.asarray(v)) for k, v in jax.tree.map(jnp.copy, caches)
    ]
    handed = [t for layer in caches for t in layer]
    tok, pos = jnp.asarray([5, 9, 13], jnp.int32), jnp.asarray([16, 8, 16], jnp.int32)
    if entry == "prefill":
        _, out = prefill(
            params, jnp.arange(8, dtype=jnp.int32), caches, jnp.asarray([9]), cfg
        )
        touched = [9]
    elif entry == "decode_step":
        _, out = decode_step(
            params, tok[0], pos[0], caches, jnp.asarray(tables[0]), cfg, max_blocks
        )
        touched = [tables[0][2]]
    elif entry == "verify_step_ragged":
        meta = build_ragged_wave(list(tables), np.asarray(pos) + 1, cfg.block_tokens)
        _, out = verify_step_ragged(
            params, tok, pos, jnp.arange(3, dtype=jnp.int32),
            jnp.asarray(meta.pages), jnp.asarray(meta.page_rows),
            jnp.asarray(meta.page_starts), caches, jnp.asarray(tables), cfg,
            max_blocks,
        )
        touched = [tables[0][2], tables[1][1], tables[2][2]]
    else:
        _, out = resume_chunk(
            params, jnp.arange(4, dtype=jnp.int32), jnp.int32(16), caches,
            jnp.asarray(tables[0]), cfg,
        )
        touched = [tables[0][2]]
    assert all(t.is_deleted() for t in handed)
    returned = [t for layer in out for t in layer]
    assert not any(t.is_deleted() for t in returned)
    assert len({t.unsafe_buffer_pointer() for t in returned}) == len(returned)
    untouched = np.setdiff1d(np.arange(16), touched)
    for (k0, v0), (k1, v1) in zip(before, out):
        np.testing.assert_array_equal(np.asarray(k1)[untouched], k0[untouched])
        np.testing.assert_array_equal(np.asarray(v1)[untouched], v0[untouched])
        assert not np.array_equal(np.asarray(k1)[touched], k0[touched])


GEOMETRIES = [(4, 2), (4, 4), (4, 1)]  # (q heads, kv heads): GQA, MHA, MQA


@pytest.mark.parametrize("geom", GEOMETRIES, ids=["gqa", "mha", "mqa"])
def test_decode_wave_layer_chain_equals_ragged_wave_body(geom, monkeypatch):
    """The disagg decode layer chained over all layers (embed_wave ..
    lm_logits) equals verify_step_ragged on the same [B, K] wave: logits
    and caches to float32 rounding. And both, with decode_step, attend
    through ONE dispatcher: the test fails if either is pointed at another
    attention."""
    from infinistore_tpu.models import (
        decode_step, decode_wave_layer, embed_wave, llama, lm_logits,
        verify_step_ragged,
    )
    from infinistore_tpu.tpu.paged_attention import rectangle_as_ragged

    calls = []
    real = llama.paged_decode_attention_rows

    def spy(*args):
        calls.append(1)
        return real(*args)

    monkeypatch.setattr(llama, "paged_decode_attention_rows", spy)
    # A vocabulary no other test uses: the jitted steps trace here, under
    # the spy, and not from another test's cache entry.
    cfg = _tiny_config(*geom, vocab=61)
    max_blocks, bsz, kk = 4, 3, 2
    params, caches, tables = _prefilled_wave(
        cfg, np.random.default_rng(8), prompt_lens=(16, 8, 24), num_blocks=16,
        max_blocks=max_blocks,
    )
    rng = np.random.default_rng(9)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, size=(bsz, kk)), jnp.int32)
    positions = jnp.asarray([[16, 17], [8, 9], [24, 25]], jnp.int32)
    block_tables = jnp.asarray(tables)

    x = embed_wave(params, tokens)
    layer_caches = []
    for layer, (k_cache, v_cache) in enumerate(caches):
        x, k_cache, v_cache = decode_wave_layer(
            params, x, positions, k_cache, v_cache, block_tables, cfg, layer,
            max_blocks,
        )
        layer_caches.append((k_cache, v_cache))
    layer_logits = lm_logits(params, x)
    assert len(calls) == cfg.n_layers, "decode_wave_layer left the dispatcher"

    row_of = jnp.repeat(jnp.arange(bsz, dtype=jnp.int32), kk)
    row_tables = jnp.take(block_tables, row_of, axis=0)
    wave_logits, wave_caches = verify_step_ragged(
        params, tokens.reshape(-1), positions.reshape(-1), row_of,
        *rectangle_as_ragged(row_tables), jax.tree.map(jnp.copy, caches),
        block_tables, cfg, max_blocks,
    )
    # The wave body traces ONE layer for all of them (models/llama.py
    # _wave_layer), so it reaches the dispatcher once.
    assert len(calls) == cfg.n_layers + 1, "verify_step_ragged left the dispatcher"
    np.testing.assert_allclose(
        np.asarray(layer_logits).reshape(bsz * kk, -1), np.asarray(wave_logits),
        rtol=2e-5, atol=2e-5,
    )
    _assert_caches_close(layer_caches, wave_caches)

    one_logits, _ = decode_step(
        params, tokens[0, 0], positions[0, 0], caches, block_tables[0], cfg,
        max_blocks,
    )
    assert len(calls) == cfg.n_layers + 2, "decode_step left the dispatcher"
    np.testing.assert_allclose(
        np.asarray(one_logits), np.asarray(wave_logits[0]), rtol=2e-5, atol=2e-5
    )


def _ragged_meta(tables, seq_lens, bt, pad_to=0):
    from infinistore_tpu.tpu.paged_attention import build_ragged_wave

    m = build_ragged_wave(tables, seq_lens, bt, pad_to=pad_to)
    return (
        jnp.asarray(m.pages), jnp.asarray(m.page_rows),
        jnp.asarray(m.page_starts), jnp.asarray(m.seq_lens),
    )


# The two serving geometries' head counts (Mistral's GQA 32/8, DeepSeek's
# MHA 32/32) at a small head_dim, 16-token blocks (8 pages a grid step).
SERVING_CASES = [
    (48, 16, 8, 16, 32, 20),
    (48, 16, 32, 16, 32, 20),
]
LAYOUT_WAVES = ["one_row", "rectangle", "mixed"]
# What the walk by grid steps has to get right: rows whose page count is not
# a multiple of the pages a step; a bucket whose padding is longer than its
# real pages; an empty row first, in the middle, last.
STEP_WAVES = [
    "step_edges", "long_padding", "zero_first", "zero_middle", "zero_last",
]
RAGGED_PARAMS = [
    pytest.param(case, dtype, wave, id=f"{wave}-{name}-{dtype.__name__}")
    for cases, waves in (
        (list(zip(CASES, ["gqa2", "gqa4", "mha", "mqa"])), LAYOUT_WAVES),
        (
            list(zip(SERVING_CASES + CASES[3:], ["gqa32_8", "mha32_32", "mqa"])),
            STEP_WAVES,
        ),
    )
    for wave in waves
    for case, name in cases
    for dtype in (jnp.float32, jnp.bfloat16)
]


@pytest.mark.parametrize("case, dtype, wave", RAGGED_PARAMS)
def test_ragged_kernel_matches_oracle(case, dtype, wave):
    """The ragged kernel (flat page list, interpret mode) against the numpy
    oracle across GQA shapes, dtypes and wave layouts. ``one_row``: what
    decode_step rides, one request's full-width table over every context
    length that matters (one token, a partial block, a block boundary,
    mid-table, full). ``rectangle``: what the disagg decode layer rides,
    full-width tables with uneven lengths and one row empty. Both through
    the in-jit metadata (rectangle_as_ragged). ``mixed``: host-built
    metadata (build_ragged_wave) for waves of 3 and 8 with skewed seq_lens,
    a seq_len=1 row next to a near-max one. ``STEP_WAVES`` (host-built, at
    the serving head counts and MQA): the walk by grid steps of several
    pages. float32 agrees to float32 rounding — the proof that the walk
    leaves nothing out of the mathematics — and bf16 to bf16's."""
    from infinistore_tpu.tpu.paged_attention import (
        _STEP_TOKENS,
        _paged_decode_attention_pallas_ragged,
        paged_decode_attention_ragged,
        paged_decode_attention_xla_batched,
        rectangle_as_ragged,
    )

    n, bt, kvh, d, h, ntbl = case
    rng = np.random.default_rng(hash(("ragged", case)) % 2**32)
    k_cache = jnp.asarray(rng.standard_normal((n, bt, kvh, d)), dtype)
    v_cache = jnp.asarray(rng.standard_normal((n, bt, kvh, d)), dtype)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    full = ntbl * bt
    step = max(1, _STEP_TOKENS // bt)  # pages a grid step
    # Rows of 1, step - 1, step, step + 1 and 2 x step + 1 pages (as far as
    # the table goes), the last page partial in some.
    edges = [
        min(pages, ntbl) * bt - cut
        for pages, cut in (
            (1, 3), (step - 1, 0), (step, 1), (step + 1, bt - 1), (2 * step + 1, 2),
        )
    ]
    waves = {
        "one_row": [[1], [bt - 1], [bt], [full // 2 + 3], [full]],
        "rectangle": [[1, full, 0, full // 2 + 1, bt]],
        "mixed": [
            [1, full, full // 2 + 1],  # seq_len=1 beside a near-max row
            [1, full, 3, full - 1, bt, bt - 1, full // 2, 2],
        ],
        "step_edges": [edges, edges[::-1]],
        "long_padding": [[bt + 1, 3]],  # 3 real pages in a bucket of 64
        "zero_first": [[0, full - 1, bt]],
        "zero_middle": [[full // 2, 0, 0, bt + 1]],
        "zero_last": [[bt, full, 0]],
    }[wave]
    for lens in waves:
        bsz = len(lens)
        q = jnp.asarray(rng.standard_normal((bsz, h, d)), dtype)
        tables = [rng.permutation(n)[:ntbl] for _ in range(bsz)]
        rect = jnp.asarray(np.stack(tables), jnp.int32)
        if wave in ("one_row", "rectangle"):
            meta = (*jax.jit(rectangle_as_ragged)(rect), jnp.asarray(lens, jnp.int32))
        else:
            meta = _ragged_meta(
                tables, lens, bt, pad_to=64 if wave == "long_padding" else 0
            )
        got = _paged_decode_attention_pallas_ragged(
            q, k_cache, v_cache, *meta, interpret=True
        )
        for b in range(bsz):
            if lens[b] == 0:  # the empty row reads as zeros, not NaN
                assert not np.asarray(got[b], np.float64).any()
                continue
            want = _numpy_oracle(q[b], k_cache, v_cache, tables[b], lens[b])
            np.testing.assert_allclose(
                np.asarray(got[b], np.float64), want, rtol=tol, atol=tol,
                err_msg=f"wave={wave} row={b} len={lens[b]}",
            )
        # The public dispatcher (XLA fallback on this backend) and the XLA
        # body over the rectangular tables agree.
        for got_xla in (
            paged_decode_attention_ragged(
                q, k_cache, v_cache, *meta, table_width=ntbl
            ),
            paged_decode_attention_xla_batched(q, k_cache, v_cache, rect, meta[3]),
        ):
            np.testing.assert_allclose(
                np.asarray(got_xla, np.float64), np.asarray(got, np.float64),
                rtol=tol, atol=tol,
            )


def test_ragged_padding_pages_are_bitwise_noops():
    """Bucket-padding the flat page list (what the engine does to bound jit
    compiles) must not change one output bit: the padding is past the
    wave's real steps, which the kernel neither fetches nor computes (and a
    step that did run fully masked would still be a bitwise no-op: alpha =
    1, p = 0, see _attn_fold)."""
    from infinistore_tpu.tpu.paged_attention import (
        _paged_decode_attention_pallas_ragged,
    )

    n, bt, kvh, d, h = 16, 8, 2, 16, 4
    rng = np.random.default_rng(43)
    k_cache = jnp.asarray(rng.standard_normal((n, bt, kvh, d)), jnp.float32)
    v_cache = jnp.asarray(rng.standard_normal((n, bt, kvh, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((3, h, d)), jnp.float32)
    tables = [rng.permutation(n)[:4] for _ in range(3)]
    lens = [9, 30, 17]
    exact = _paged_decode_attention_pallas_ragged(
        q, k_cache, v_cache, *_ragged_meta(tables, lens, bt), interpret=True
    )
    padded = _paged_decode_attention_pallas_ragged(
        q, k_cache, v_cache, *_ragged_meta(tables, lens, bt, pad_to=16),
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(exact), np.asarray(padded))


def test_ragged_zero_length_row_returns_zeros():
    """A just-admitted request with no cached tokens (seq_len 0) carries one
    fully-masked page and must read as zeros on both backends — not 0/0 NaN
    (kernel) or a uniform garbage average (naive softmax fallback)."""
    from infinistore_tpu.tpu.paged_attention import (
        _paged_decode_attention_pallas_ragged,
        paged_decode_attention_ragged,
    )

    n, bt, kvh, d, h = 8, 8, 2, 16, 4
    rng = np.random.default_rng(47)
    k_cache = jnp.asarray(rng.standard_normal((n, bt, kvh, d)), jnp.float32)
    v_cache = jnp.asarray(rng.standard_normal((n, bt, kvh, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((2, h, d)), jnp.float32)
    meta = _ragged_meta([[0, 1], [2, 3]], [0, 5], bt)
    for out in (
        _paged_decode_attention_pallas_ragged(
            q, k_cache, v_cache, *meta, interpret=True
        ),
        paged_decode_attention_ragged(
            q, k_cache, v_cache, *meta, table_width=2
        ),
    ):
        row0 = np.asarray(out[0], np.float64)
        assert np.array_equal(row0, np.zeros_like(row0))
        assert np.isfinite(np.asarray(out, np.float64)).all()
        assert np.abs(np.asarray(out[1], np.float64)).max() > 0


def test_ragged_stats_kernel_matches_xla_stats():
    """The ragged stats kernel (interpret mode) and the reconstructed-table
    XLA stats normalize identically — the combinability contract ragged
    sharded decode rides."""
    from infinistore_tpu.tpu.paged_attention import (
        _decode_attention_stats_xla,
        _paged_decode_attention_pallas_ragged_stats,
        _ragged_row_tables,
    )

    n, bt, kvh, d, h = 16, 8, 2, 16, 4
    rng = np.random.default_rng(53)
    k_cache = jnp.asarray(rng.standard_normal((n, bt, kvh, d)), jnp.float32)
    v_cache = jnp.asarray(rng.standard_normal((n, bt, kvh, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((3, h, d)), jnp.float32)
    tables = [rng.permutation(n)[:4] for _ in range(3)]
    lens = [1, 4 * bt, 0]
    pages, rows, starts, sls = _ragged_meta(tables, lens, bt)
    a1, m1, l1 = _paged_decode_attention_pallas_ragged_stats(
        q, k_cache, v_cache, pages, rows, starts, sls, interpret=True
    )
    rect = _ragged_row_tables(pages, starts, 4)
    a2, m2, l2 = _decode_attention_stats_xla(q, k_cache, v_cache, rect, sls)
    for b in range(3):
        if float(l2[b].max()) == 0.0:
            assert float(l1[b].max()) == 0.0
            assert float(jnp.abs(a1[b]).max()) == 0.0
        else:
            np.testing.assert_allclose(
                np.asarray(a1[b] / l1[b]), np.asarray(a2[b] / l2[b]),
                rtol=1e-5, atol=1e-5,
            )


def test_ragged_sharded_wave_matches_dense_oracle():
    """A ragged WAVE with contexts sharded over the 8-way 'sp' mesh: per-
    shard ragged stats combined with pmax/psum must equal dense attention
    over each row's concatenated context — including rows absent from some
    shards entirely (local_len 0)."""
    from jax.sharding import Mesh

    from infinistore_tpu.tpu.paged_attention import (
        build_ragged_wave_sharded,
        paged_decode_attention_ragged_sharded,
    )

    P_, nb_local, bt, kvh, d, h, R = 8, 4, 4, 2, 16, 4, 3
    rng = np.random.default_rng(59)
    k_cache = jnp.asarray(
        rng.standard_normal((P_ * nb_local, bt, kvh, d)), jnp.float32
    )
    v_cache = jnp.asarray(
        rng.standard_normal((P_ * nb_local, bt, kvh, d)), jnp.float32
    )
    q = jnp.asarray(rng.standard_normal((R, h, d)), jnp.float32)
    local_tables = [
        [rng.permutation(nb_local)[:3] for _ in range(R)] for _ in range(P_)
    ]
    local_lens = rng.integers(0, 3 * bt + 1, size=(P_, R)).astype(np.int32)
    local_lens[0, 0] = max(local_lens[0, 0], 1)
    local_lens[:, 2] = 0
    local_lens[4, 2] = 7  # row 2 lives on exactly one shard

    pages, rows, starts, lens, width = build_ragged_wave_sharded(
        local_tables, local_lens, bt
    )
    devices = jax.devices()
    assert len(devices) == 8
    mesh = Mesh(np.array(devices), ("sp",))
    got = paged_decode_attention_ragged_sharded(
        q, k_cache, v_cache, pages, rows, starts, lens,
        mesh=mesh, table_width=width,
    )
    groups = h // kvh
    for r in range(R):
        ks, vs = [], []
        for p in range(P_):
            rowsg = p * nb_local + np.asarray(local_tables[p][r])
            ks.append(
                np.asarray(k_cache)[rowsg].reshape(-1, kvh, d)[: local_lens[p][r]]
            )
            vs.append(
                np.asarray(v_cache)[rowsg].reshape(-1, kvh, d)[: local_lens[p][r]]
            )
        k_all = np.concatenate(ks)
        v_all = np.concatenate(vs)
        k_rep = np.repeat(k_all, groups, axis=1).astype(np.float64)
        v_rep = np.repeat(v_all, groups, axis=1).astype(np.float64)
        logits = np.einsum(
            "hd,thd->ht", np.asarray(q[r], np.float64), k_rep
        ) / np.sqrt(d)
        p_ = np.exp(logits - logits.max(axis=1, keepdims=True))
        p_ /= p_.sum(axis=1, keepdims=True)
        want = np.einsum("ht,thd->hd", p_, v_rep)
        np.testing.assert_allclose(
            np.asarray(got[r], np.float64), want, rtol=1e-5, atol=1e-5,
            err_msg=f"row {r}",
        )


def test_build_ragged_wave_validates():
    """The metadata builder rejects short tables, undersized pad_to, and
    empty waves; pads belong to the last row with the sentinel terminating
    the map."""
    from infinistore_tpu.tpu.paged_attention import build_ragged_wave

    with pytest.raises(ValueError):
        build_ragged_wave([], [], 8)
    with pytest.raises(ValueError):
        build_ragged_wave([[0]], [9], 8)  # needs 2 pages for len 9
    with pytest.raises(ValueError):
        build_ragged_wave([[0, 1], [2]], [16, 3], 8, pad_to=2)
    m = build_ragged_wave([[0, 1], [2]], [16, 3], 8, pad_to=8)
    assert m.num_pages == 8 and m.pad_pages == 5
    assert list(m.page_rows[:3]) == [0, 0, 1]
    assert all(r == 1 for r in m.page_rows[3:8])  # padding rides row 1
    assert m.page_rows[8] == 2  # sentinel
    assert list(m.page_starts) == [0, 2]


def test_decode_step_uses_contract_matching_prefill():
    """decode_step routes attention through the dispatcher; on CPU that is
    the XLA fallback, and the f32-softmax contract keeps incremental decode
    equal to full prefill (the tight-tolerance invariant the model tests
    pin). This guards the dispatcher wiring specifically."""
    from infinistore_tpu.models import decode_step, init_params, prefill

    cfg = _tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    full = jax.random.randint(jax.random.PRNGKey(1), (24,), 0, cfg.vocab)
    table = jnp.asarray([3, 1, 6, 2], jnp.int32)
    caches = cfg.kv_spec(8).make_caches()
    ref_logits, _ = prefill(
        params, full, cfg.kv_spec(8).make_caches(), table[:3], cfg
    )
    logits, caches = prefill(params, full[:16], caches, table[:2], cfg)
    for pos in range(16, 24):
        logits, caches = decode_step(
            params, full[pos], jnp.int32(pos), caches, table, cfg, 4
        )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )
