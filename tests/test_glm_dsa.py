"""The ``glm_moe_dsa`` model on the serving path, at a small size on the CPU:
hidden 64, 4 heads (query latent 32, latent 32, nope 16, rope 8, v 16), an
indexer of 4 heads x 16 that keeps 16 positions, blocks of 8, 8 experts top-2,
one dense layer and two expert layers, seeded float32 weights.

- the selection (the sort, and the Pallas search interpreted) against
  ``lax.top_k`` as SETS, ties, short rows and empty rows included; the scoring
  kernels, the masked latent decode and the chunk's attention under a bias
  (the page loop and its Pallas kernel, interpreted) against plain mathematics;
- the interleaved rotation, the program's and the reference's, against a
  product of complex numbers;
- the program through the harness, the connector and a store (a miss and its
  decode through the cache across a block boundary, a full hit, a partial hit)
  against ``benchmarks/reference_glm_dsa.py``, with a context LONGER than
  ``index_topk``, so that the selection drops keys, and one shorter, so that
  it drops none; the sets the program selected against the reference's own;
- the shares of the expert layer add up to the uncut layer;
- both cache tensors through save, fetch and install, the ledger's index
  bytes, the wave's counters, the file's arithmetic.
"""

import asyncio
import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import infinistore_tpu as its
from infinistore_tpu.connector import KVConnector
from infinistore_tpu.engine import ContinuousBatchingHarness, EngineKVAdapter
from infinistore_tpu.models import layers
from infinistore_tpu.models import glm_dsa as gd
from infinistore_tpu.models.glm_dsa import GlmDsaConfig
from infinistore_tpu.tpu import dsa, mla, moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
import reference_glm_dsa  # noqa: E402 - the benchmark's plain reference
import reference_kimi_linear  # noqa: E402 - whose expert half the reference runs

CFG = GlmDsaConfig(dtype=jnp.float32)  # the defaults are the small size above
ALL = dataclasses.replace(CFG, index_topk=4096)  # a selection that drops nothing
BT = CFG.block_tokens
NUM_BLOCKS, MAX_REQ_BLOCKS = 64, 8
GEN = 7


def file_of(cfg: GlmDsaConfig) -> dict:
    """``cfg`` as the configuration file's keys."""
    first, count = cfg.held
    return {
        "hidden_size": cfg.dim, "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.n_dense_layers, "num_attention_heads": cfg.n_heads,
        "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "index_topk": cfg.index_topk,
        "n_routed_experts": count, "router_experts": cfg.n_experts, "experts_held": [first, count],
        "num_experts_per_tok": cfg.experts_per_token, "n_shared_experts": cfg.n_shared_experts,
        "norm_topk_prob": True, "routed_scaling_factor": cfg.route_scale, "scoring_func": "sigmoid",
        "n_group": 1, "topk_group": 1, "rms_norm_eps": cfg.rms_eps, "rope_interleave": True,
        "indexer_rope_interleave": True,
        "rope_parameters": {"rope_theta": cfg.rope_theta, "rope_type": "default"},
    }


@pytest.fixture(scope="module")
def params():
    return gd.init_params(CFG, jax.random.key(56))


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


# ---------------------------------------------------------------------------
# The kernels' mathematics.
# ---------------------------------------------------------------------------


def _sets(bias) -> list:
    """[P, R, bt] bias -> per row the set of selected positions."""
    flat = np.asarray(bias).transpose(1, 0, 2).reshape(bias.shape[1], -1)
    return [set(np.nonzero(row == 0)[0].tolist()) for row in flat]


def _selection_case(kind: str, p=5, r=16, bt=128, k=100):
    """Scores [P, R, bt] and lens [R] of one row tile, by what the k-th-value
    search meets in them."""
    rng = np.random.default_rng(561)
    scores = rng.standard_normal((p, r, bt)).astype(np.float32)
    lens = rng.integers(1, p * bt + 1, size=r).astype(np.int32)
    lens[:4] = [0, p * bt, k, k - 1]  # nothing to choose, everything, exactly k, under k
    if kind == "tied":
        scores = np.round(scores * 4) / 4  # a few dozen values: ties at every threshold
    elif kind == "mixed":
        # Rows that end exact after a few bits, one whose ties need the search
        # by position, and (the first four) rows that choose all they have.
        scores[:, 5] = np.round(scores[:, 5] * 4) / 4
        lens[4:] = p * bt - np.arange(r - 4)
    elif kind == "equal":
        scores[:] = 0.5  # no threshold ever counts exactly k
    elif kind == "duplicate":
        # ONE value twice, at the k-th and the (k + 1)-th place of every row.
        lens[4:] = rng.integers(k + 2, p * bt + 1, size=r - 4)
        flat = scores.transpose(1, 0, 2).reshape(r, -1)
        for row in range(4, r):
            order = np.argsort(-flat[row, : lens[row]], kind="stable")
            flat[row, order[k]] = flat[row, order[k - 1]]
        scores = flat.reshape(r, p, bt).transpose(1, 0, 2).copy()
    elif kind == "short":
        lens = rng.integers(0, k + 1, size=r).astype(np.int32)  # every row chooses all it has
    else:
        assert kind == "distinct", kind
    return scores, lens, k


SELECTION_CASES = ["distinct", "tied", "mixed", "equal", "duplicate", "short"]


@pytest.mark.parametrize("scores_kind", SELECTION_CASES)
@pytest.mark.parametrize("form", ["sort", "search"])
def test_the_selection_is_top_k_as_a_set(form, scores_kind):
    scores, lens, k = _selection_case(scores_kind)
    r = scores.shape[1]
    if form == "sort":
        bias = dsa.select_xla(jnp.asarray(scores), jnp.asarray(lens), k=k)
    else:
        bias, _ = dsa.dsa_select_pallas(jnp.asarray(scores), jnp.asarray(lens), k=k, interpret=True)
    assert bias.shape == scores.shape and bias.dtype == jnp.float32
    assert set(np.unique(np.asarray(bias)).tolist()) <= {0.0, float(np.float32(-1e30))}
    flat = scores.transpose(1, 0, 2).reshape(r, -1)
    for row, got in enumerate(_sets(bias)):
        n = int(lens[row])
        want = set(np.asarray(jax.lax.top_k(jnp.asarray(flat[row, :n]), min(n, k))[1]).tolist())
        assert got == want, (row, n, sorted(got ^ want)[:8])


@pytest.mark.parametrize("rows", [8, 16, 64])
@pytest.mark.parametrize("scores_kind", ["short", "distinct", "mixed", "equal"])
def test_the_search_counts_the_passes_it_made(scores_kind, rows):
    """The kernel's second result, a row tile: no pass where every row
    chooses all it has, the value bits at most where some threshold counts
    exactly k for every row, every value bit, the count above and every
    position bit where a tie stands across the k-th place. A tile is 32
    rows, or all of fewer."""
    scores, lens, k = _selection_case(scores_kind, r=rows)
    p, _, bt = scores.shape
    bias, passes = dsa.dsa_select_pallas(jnp.asarray(scores), jnp.asarray(lens), k=k, interpret=True)
    assert passes.shape == (-(-rows // 32), 1) and passes.dtype == jnp.int32
    assert _sets(bias) == _sets(dsa.select_xla(jnp.asarray(scores), jnp.asarray(lens), k=k))
    every = 32 + 1 + (p * bt).bit_length()
    made = np.asarray(passes)[:, 0].tolist()
    if scores_kind == "short":
        assert made == [0] * len(made)
    elif scores_kind == "distinct":
        assert all(0 < n <= 32 for n in made), made
    else:
        assert made[0] == every and all(n == every or n <= 32 for n in made), made


def _index_case(rows, seed, hi=2, di=16, bt=8, blocks=12, table=4):
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], (rows, hi, di), jnp.float32)
    w = jax.random.normal(keys[1], (rows, hi), jnp.float32)
    index = jax.random.normal(keys[2], (blocks, di, bt), jnp.float32)
    tables = jax.random.permutation(keys[3], blocks)[: rows * table].reshape(rows, table)
    return q, w, index, tables.astype(jnp.int32)


def _plain_scores(q, w, index, table):
    """I[t, s] over the table's positions, written out."""
    ctx = np.concatenate([np.asarray(index[b]).T for b in np.asarray(table)])  # [C, Di]
    per_head = np.maximum(np.einsum("thd,cd->thc", np.asarray(q), ctx), 0.0)
    return np.einsum("thc,th->tc", per_head, np.asarray(w))


@pytest.mark.parametrize("shape", ["wave", "chunk"])
def test_the_scoring_kernels_are_the_indexers_sum(shape):
    if shape == "wave":
        q, w, index, tables = _index_case(3, 562)
        lens = jnp.asarray([32, 9, 17], jnp.int32)
        got = dsa.dsa_index_decode_pallas(q, w, index, tables, lens, interpret=True)
        plain = dsa.index_scores_xla(q, w, index, tables)
        for t in range(3):
            want = _plain_scores(q[t : t + 1], w[t : t + 1], index, tables[t])[0]
            n = int(lens[t])
            for scores in (got, plain):
                flat = np.asarray(scores)[:, t].reshape(-1)
                np.testing.assert_allclose(flat[:n], want[:n], rtol=1e-5, atol=1e-5)
    else:
        rows = dsa.CHUNK_ROW_TILE
        q, w, index, _ = _index_case(rows, 563, bt=128, blocks=6, table=0)
        table = jnp.asarray([4, 1, 5, 0], jnp.int32)
        got = dsa.dsa_index_chunk_pallas(
            jnp.swapaxes(q, 0, 1), w, index, table, jnp.asarray([3], jnp.int32), interpret=True
        )
        plain = dsa.index_scores_xla(q, w, index, table)
        want = _plain_scores(q, w, index, table)
        for scores in (got, plain):
            flat = np.asarray(scores).transpose(1, 0, 2).reshape(rows, -1)
            np.testing.assert_allclose(flat[:, : 3 * 128], want[:, : 3 * 128], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_the_masked_latent_decode_attends_the_selected_positions_alone(form):
    rows, h, rank, rope, bt, blocks, table = 3, 4, 32, 8, 8, 12, 4
    keys = jax.random.split(jax.random.key(564), 4)
    q = jax.random.normal(keys[0], (rows, h, rank + rope), jnp.float32)
    latent = jax.random.normal(keys[1], (blocks, rank + rope, bt), jnp.float32)
    tables = jax.random.permutation(keys[2], blocks).reshape(rows, table).astype(jnp.int32)
    lens = jnp.asarray([32, 9, 17], jnp.int32)
    scores = jax.random.normal(keys[3], (table, rows, bt), jnp.float32)
    bias = dsa.select_xla(scores, lens, k=6)
    # The first row's first page holds no selected position.
    bias = bias.at[0, 0].set(-1e30)
    fn = dsa.mla_sparse_decode_xla if form == "xla" else functools.partial(
        dsa.mla_sparse_decode_pallas, interpret=True
    )
    got = np.asarray(fn(q, latent, bias, tables, lens, rank=rank, scale=0.25))
    for t, chosen in enumerate(_sets(bias)):
        ctx = np.concatenate([np.asarray(latent[b]).T for b in np.asarray(tables[t])])
        ids = sorted(chosen)
        s = np.einsum("hw,cw->hc", np.asarray(q[t]), ctx[ids]) * 0.25
        p = np.exp(s - s.max(axis=1, keepdims=True))
        want = (p / p.sum(axis=1, keepdims=True)) @ ctx[ids][:, :rank]
        np.testing.assert_allclose(got[t], want, rtol=1e-4, atol=1e-4)


def _chunk_attention(form, *args, **kwargs):
    """``mla.latent_chunk_attention``'s two forms: the page loop in plain XLA
    and the Pallas kernel, interpreted."""
    if form == "xla":
        return mla.latent_chunk_attention_xla(*args, **kwargs)
    return mla.mla_chunk_attention_pallas(*args, **kwargs, interpret=True)


def _chunk_case(start, s, k, pages=17, h=4, rank=32, rope=8, nope=16, vdim=16, bt=8, blocks=24):
    keys = jax.random.split(jax.random.key(565), 5)
    q = jax.random.normal(keys[0], (s, h, nope + rope), jnp.float32)
    latent = jax.random.normal(keys[1], (blocks, rank + rope, bt), jnp.float32)
    w_kvb = jax.random.normal(keys[2], (rank, h, nope + vdim), jnp.float32) / np.sqrt(rank)
    table = jax.random.permutation(keys[4], blocks)[:pages].astype(jnp.int32)
    lens = start + jnp.arange(s, dtype=jnp.int32) + 1
    bias = dsa.select_xla(jax.random.normal(keys[3], (pages, s, bt), jnp.float32), lens, k=k)
    return q, latent, table, w_kvb, bias, (rank, nope)


# (first position, rows, positions a row keeps): the table's third block; a
# chunk that starts mid-block; a hit's 127-row question (sixteen pages of 8);
# a selection that leaves every row ONE position.
BIASED_CHUNKS = {
    "third-block": (16, 8, 5), "mid-block": (19, 5, 5), "question-127": (1, 127, 5),
    "one-position": (16, 8, 1),
}


@pytest.mark.parametrize("case", BIASED_CHUNKS)
@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_the_chunk_attention_under_a_bias_attends_the_selected_positions_alone(form, case):
    start, s, k = BIASED_CHUNKS[case]
    q, latent, table, w_kvb, bias, (rank, nope) = _chunk_case(start, s, k)
    got = np.asarray(_chunk_attention(
        form, q, latent, table, jnp.int32(start), w_kvb, rank=rank, nope=nope, scale=0.2, bias=bias
    ))
    ctx = np.concatenate([np.asarray(latent[b]).T for b in np.asarray(table)])
    kv = np.einsum("cr,rhd->chd", ctx[:, :rank], np.asarray(w_kvb))
    for i, chosen in enumerate(_sets(bias)):
        ids = sorted(chosen)
        assert len(ids) == min(k, start + i + 1) and max(ids) <= start + i
        sc = np.einsum("hd,chd->hc", np.asarray(q[i, :, :nope]), kv[ids][..., :nope])
        sc = (sc + np.asarray(q[i, :, nope:]) @ ctx[ids][:, rank:].T) * 0.2
        p = np.exp(sc - sc.max(axis=1, keepdims=True))
        want = np.einsum("hc,chd->hd", p / p.sum(axis=1, keepdims=True), kv[ids][..., nope:])
        np.testing.assert_allclose(got[i], want, rtol=1e-4, atol=1e-4)
        if k == 1:  # one position: the row IS that position's values
            np.testing.assert_allclose(got[i], kv[ids[0]][..., nope:], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_the_chunk_attention_under_a_bias_reads_no_page_past_its_context(form):
    """A table of 17 entries, a context of three pages: the fourteen pages
    behind it hold NaN and neither form reads one."""
    start, s, k = 16, 8, 5
    q, latent, table, w_kvb, bias, (rank, nope) = _chunk_case(start, s, k)
    kw = dict(rank=rank, nope=nope, scale=0.2, bias=bias)
    want = _chunk_attention(form, q, latent, table, jnp.int32(start), w_kvb, **kw)
    poisoned = latent.at[table[3:]].set(jnp.nan)
    got = _chunk_attention(form, q, poisoned, table, jnp.int32(start), w_kvb, **kw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("case", BIASED_CHUNKS)
def test_the_chunk_kernel_is_the_page_loop_it_replaced(case):
    """The kernel against its twin directly: one arithmetic (float32 here), the
    sums in another order."""
    start, s, k = BIASED_CHUNKS[case]
    q, latent, table, w_kvb, bias, (rank, nope) = _chunk_case(start, s, k)
    args = (q, latent, table, jnp.int32(start), w_kvb)
    kw = dict(rank=rank, nope=nope, scale=0.2, bias=bias)
    np.testing.assert_allclose(
        _chunk_attention("pallas", *args, **kw), _chunk_attention("xla", *args, **kw),
        rtol=2e-5, atol=2e-5,
    )


@pytest.mark.parametrize("first", [0, 16])
@pytest.mark.parametrize("whose", ["program", "reference"])
def test_the_interleaved_rotation_is_a_complex_product(whose, first):
    """Pair (2i, 2i + 1) as the complex number a + bi, times e^(i pos f_i)."""
    rope, width = CFG.qk_rope_head_dim, 16 + CFG.qk_rope_head_dim
    x = jax.random.normal(jax.random.key(566), (5, 3, width), jnp.float32)
    positions = jnp.asarray([0, 1, 7, 300, 32767], jnp.int32)
    if whose == "program":
        got = gd.rotate_pairs(x, positions, first, CFG)
    else:
        got = reference_glm_dsa._rotate(x, positions, CFG.rope_theta, first, rope)
    xs = np.asarray(x, np.float64)
    part = xs[..., first : first + rope]
    z = part[..., 0::2] + 1j * part[..., 1::2]
    freq = CFG.rope_theta ** (-np.arange(0, rope, 2) / rope)
    angles = np.asarray(positions, np.float64)[:, None, None] * freq.astype(np.float32)
    turned = z * np.exp(1j * angles.astype(np.float32))
    want = xs.copy()
    want[..., first : first + rope : 2] = turned.real
    want[..., first + 1 : first + rope : 2] = turned.imag
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
    untouched = [i for i in range(width) if not first <= i < first + rope]
    np.testing.assert_array_equal(np.asarray(got)[..., untouched], np.asarray(x)[..., untouched])


# ---------------------------------------------------------------------------
# Through the harness, the connector and the store.
# ---------------------------------------------------------------------------


class Tapped:
    """A harness whose ``step_chunk`` keeps, per call, the logits rows and the
    choices the program reports for them (as the benchmark's taps do), and
    whose installs poison the prefix's blocks with NaN first: what a hit does
    not install must never be read."""

    def __init__(self, conn, params, name, cfg=CFG):
        self.kvc = KVConnector(conn, cfg.kv_spec(NUM_BLOCKS), name, max_blocks=MAX_REQ_BLOCKS)
        self.h = ContinuousBatchingHarness(
            EngineKVAdapter(self.kvc), params, cfg, NUM_BLOCKS, MAX_REQ_BLOCKS
        )
        self.calls = []
        step_chunk, install = self.h.wave.step_chunk, self.h.adapter.install_kv

        async def tapped(tokens, positions, table, priority=0):
            rows = await step_chunk(tokens, positions, table, priority=priority)
            self.calls.append((np.asarray(rows, np.float32), gd.choices(self.h, rows)))
            return rows

        async def poisoned(prefetch, caches, block_table):
            ids = jnp.asarray(np.asarray(block_table), jnp.int32)
            caches = [tuple(t.at[ids].set(jnp.nan) for t in layer) for layer in caches]
            return await install(prefetch, caches, block_table)

        self.h.wave.step_chunk = tapped
        self.h.adapter.install_kv = poisoned

    async def ask(self, tokens, gen=GEN):
        self.calls.clear()
        stats = await self.h.run_request(tokens, gen_tokens=gen)
        return stats, list(self.calls)


def against_reference(params, cfg, tokens, stats, calls, rounds=GEN):
    """Round j decodes position len - 1 + j, teacher-forced on the tokens it
    chose; the reference follows row 0's sets of each round, the expert
    layers' and the indexer's."""
    got = np.concatenate([rows[:1] for rows, _ in calls[:rounds]])
    chosen = np.stack([c[0] for _, c in calls[:rounds]])
    words = -(-MAX_REQ_BLOCKS * BT // (32 * cfg.experts_per_token))  # sites a layer's bits take
    assert chosen.shape == (rounds, cfg.sites + cfg.n_layers * words, cfg.experts_per_token)
    ref, gaps = reference_glm_dsa.logits_following(
        params, file_of(cfg), list(tokens) + stats.generated[: rounds - 1], rounds, chosen
    )
    ref = np.asarray(ref)
    scale = np.sqrt(np.mean(ref * ref))
    assert np.all(np.isfinite(got))
    assert np.max(np.abs(got - ref)) / scale < 2e-4, np.max(np.abs(got - ref)) / scale
    assert gaps.shape == (rounds, cfg.sites)
    assert float(np.max(np.asarray(gaps))) < 1e-3, np.asarray(gaps)


@pytest.mark.parametrize("fault", ["random", "short", "ahead"])
def test_the_reference_refuses_a_selection_that_is_none(params, fault):
    """What the program reports as a row's selection must be ``index_topk``
    positions at or before the row that mostly are the reference's own."""
    rng = np.random.default_rng(573)
    tokens = rng.integers(0, CFG.vocab, size=40).tolist()
    own = reference_glm_dsa.selected(params, file_of(CFG), tokens, 1)
    sets = [set(ids[0].tolist()) for ids, _ in own]
    if fault == "random":  # the 16 lowest-scored positions
        sets[1] = set(np.argsort(own[1][1][0, :40])[:16].tolist())
    elif fault == "short":
        sets[1] = set(sorted(sets[1])[:-1])
    else:  # a position behind the row
        sets[1] = set(sorted(sets[1])[:-1]) | {41}
    words = np.zeros((CFG.n_layers, 2), np.int64)
    for layer, chosen in enumerate(sets):
        for s in chosen:
            words[layer, s // 32] |= 1 << (s % 32)
    bits = np.where(words >= 2**31, words - 2**32, words).astype(np.int32)
    experts = np.tile(np.arange(2, dtype=np.int32), (1, CFG.sites, 1))
    choices = np.concatenate([experts, bits.reshape(1, -1, 2)], axis=1)
    with pytest.raises(ValueError, match="shares under|not 16 of the 40"):
        reference_glm_dsa.logits_following(params, file_of(CFG), tokens, 1, choices)


# A document of three blocks and a question that completes none: the prompt's
# last block is part full, as at 1,024-token blocks under a 128-token question.
# 29 tokens and seven more: every compared row reads 16 of its 29 to 36.
DOC, QUESTION = 3 * BT, 5


@pytest.mark.parametrize("selection", ["drops-keys", "drops-none"])
@pytest.mark.parametrize("path", ["miss", "full-hit", "partial-hit"])
def test_the_program_through_the_harness_against_the_reference(conn, params, path, selection):
    cfg = CFG if selection == "drops-keys" else ALL
    rng = np.random.default_rng(567)
    doc = rng.integers(0, cfg.vocab, size=DOC).tolist()
    first = doc + rng.integers(0, cfg.vocab, size=QUESTION).tolist()
    other = doc + rng.integers(0, cfg.vocab, size=QUESTION).tolist()

    async def drive():
        t = Tapped(conn, params, f"glm-{path}-{selection}", cfg)
        miss, miss_calls = await t.ask(first)
        assert (miss.loaded_blocks, miss.computed_blocks) == (0, 3)
        if path == "miss":
            return first, miss, miss_calls, t.h.metrics()
        tokens = first if path == "full-hit" else other
        hit, calls = await t.ask(tokens)
        assert (hit.hit_blocks, hit.loaded_blocks, hit.computed_blocks) == (3, 3, 0)
        assert hit.prefetched_blocks == 3 * 2 * cfg.n_layers  # both tensors, every block
        if path == "full-hit":
            # The resume runs the programs the miss ran, on the bytes the miss
            # saved: equal to the bit.
            np.testing.assert_array_equal(calls[0][0], miss_calls[0][0])
            assert hit.generated == miss.generated
        return tokens, hit, calls, t.h.metrics()

    tokens, stats, calls, metrics = asyncio.run(drive())
    against_reference(params, cfg, tokens, stats, calls)
    # The wave's own count of what its selections kept.
    kept, could = metrics["dsa_keys_selected"], metrics["dsa_keys_in_context"]
    assert 0 < kept <= could
    assert (kept < could) == (selection == "drops-keys"), (kept, could)


@pytest.mark.parametrize("form", ["sort", "search"])
def test_the_waves_counters_are_the_selections_own(conn, params, monkeypatch, form):
    """One request, GEN + 1 waves of one row at positions 28 .. 35: each keeps
    16 of its position + 1 keys at each of the three layers, in one search a
    layer (the row's tile). The sort makes no counting pass; the search
    (interpreted here) at least one a search, its context being longer than
    ``index_topk``, and never more than it has bits to try."""
    if form == "search":
        def searched(scores, lens, k):
            pad = -scores.shape[1] % 8
            bias, passes = dsa.dsa_select_pallas(
                jnp.pad(scores, ((0, 0), (0, pad), (0, 0))), jnp.pad(lens, (0, pad)), k=k, interpret=True
            )
            return bias[:, : scores.shape[1]], passes[:, 0]

        monkeypatch.setattr(dsa, "select", searched)
        jax.clear_caches()
    rng = np.random.default_rng(568)
    tokens = rng.integers(0, CFG.vocab, size=DOC + QUESTION).tolist()

    async def drive():
        t = Tapped(conn, params, f"glm-counters-{form}")
        stats, calls = await t.ask(tokens)
        return t.h.metrics(), len(calls)

    try:
        metrics, waves = asyncio.run(drive())
    finally:
        if form == "search":
            jax.clear_caches()  # no later test finds a program traced around the stand-in
    positions = range(len(tokens) - 1, len(tokens) - 1 + waves)
    assert metrics["dsa_keys_selected"] == CFG.n_layers * CFG.index_topk * waves
    assert metrics["dsa_keys_in_context"] == CFG.n_layers * sum(p + 1 for p in positions)
    assert metrics["moe_pairs"] == waves * CFG.sites * CFG.experts_per_token
    searches = CFG.n_layers * 1 * waves  # layers x row tiles x waves
    assert metrics["dsa_select_searches"] == searches
    every = 32 + 1 + (MAX_REQ_BLOCKS * BT).bit_length()
    if form == "sort":
        assert metrics["dsa_select_passes"] == 0
    else:
        assert searches <= metrics["dsa_select_passes"] <= every * searches


@pytest.mark.parametrize("phase", ["chunk", "wave"])
def test_the_program_selects_the_references_sets_where_no_near_tie_stands(params, monkeypatch, phase):
    """The sets ``tpu/dsa.py`` handed the attention, layer by layer, against
    the reference's own top-k for the same rows: equal wherever the
    reference's k-th and (k + 1)-th scores lie apart."""
    rng = np.random.default_rng(569)
    tokens = rng.integers(0, CFG.vocab, size=4 * BT + 5).tolist()
    seen = []
    real_select = dsa.select

    def recording(scores, lens, k):
        bias, passes = real_select(scores, lens, k)
        seen.append(_sets(bias))
        return bias, passes

    monkeypatch.setattr(dsa, "select", recording)
    caches = CFG.kv_spec(NUM_BLOCKS).make_caches()
    table = jnp.arange(1, 1 + MAX_REQ_BLOCKS, dtype=jnp.int32)
    rows = 4  # the last rows of the prompt, or one decoded row
    with jax.disable_jit():
        if phase == "chunk":
            _, caches = gd.prefill(params, tokens, caches, table, CFG)
            got = [layer[-rows:] for layer in seen[-CFG.n_layers :]]
            context = tokens
        else:
            _, caches = gd.prefill(params, tokens[:-1], caches, table, CFG)
            seen.clear()
            pos = jnp.asarray([len(tokens) - 1], jnp.int32)
            zero = jnp.zeros((1,), jnp.int32)
            gd.verify_step_ragged(
                params, jnp.asarray(tokens[-1:], jnp.int32), pos, zero, zero, jnp.zeros((2,), jnp.int32),
                zero, caches, table[None], config=CFG, max_blocks=MAX_REQ_BLOCKS,
            )
            got, rows, context = seen, 1, tokens
    assert len(got) == CFG.n_layers
    want = reference_glm_dsa.selected(params, file_of(CFG), context, rows)
    compared = 0
    for layer, (ids, scores) in enumerate(want):
        for row in range(rows):
            position = len(context) - rows + row
            ranked = np.sort(scores[row, : position + 1])[::-1]
            if ranked[CFG.index_topk - 1] - ranked[CFG.index_topk] < 1e-4 * np.std(ranked):
                continue  # a near-tie at the last place: either side is right
            assert got[layer][row] == set(ids[row].tolist()), (layer, row)
            compared += 1
    assert compared >= rows * CFG.n_layers - 2, compared


@pytest.mark.parametrize("shares", [2, 4])
def test_the_expert_layers_shares_add_up_to_the_uncut_layer(shares):
    """A router of 8 cut into ``shares`` spans: the parts the shares give, the
    shared expert counted once (by the share that holds expert 0), add up to
    what the REFERENCE gives for the whole layer held by one."""
    whole = dataclasses.replace(CFG, experts_held=None)
    w = layers.layer_weights(gd.init_params(whole, jax.random.key(570)), 1)
    h = 3.0 * jax.random.normal(jax.random.key(571), (24, CFG.dim), jnp.float32)
    m = layers.rms(h, w["pre_mlp_norm"], CFG.rms_eps)
    span = CFG.n_experts // shares
    total = jnp.zeros_like(h)
    for first in range(0, CFG.n_experts, span):
        part = dataclasses.replace(CFG, experts_held=(first, span))
        held = dict(w, **{
            name: w[name][first : first + span] for name in ("w_gate", "w_up", "w_down_moe")
        })
        total = total + moe.expert_layer(held, m, part)[0]
    with jax.default_matmul_precision("highest"):
        ref, _ = reference_kimi_linear._expert_half(
            {k: w[k] for k in reference_kimi_linear.EXPERT}, h, jnp.zeros((0, 2), jnp.int32),
            CFG.rms_eps, CFG.experts_per_token, True, CFG.route_scale, 0, True,
        )
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref - h), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n", [1, 2, 5])
def test_a_hit_fetches_both_tensors_of_every_block_and_the_ledger_counts_the_index_keys(conn, params, n):
    rng = np.random.default_rng(572 + n)
    doc = rng.integers(0, CFG.vocab, size=n * BT).tolist()
    ask = lambda: doc + rng.integers(0, CFG.vocab, size=3).tolist()
    latent, index = BT * CFG.latent_width * 4, BT * CFG.index_head_dim * 4  # float32 here

    async def drive():
        t = Tapped(conn, params, f"glm-policy-{n}")
        await t.ask(ask(), gen=2)
        saved = t.kvc.get_stats()
        assert conn.get_stats()["kvmap_len"] == n * 2 * CFG.n_layers
        assert saved["save_latent_bytes"] == n * CFG.n_layers * latent
        assert saved["save_index_bytes"] == n * CFG.n_layers * index
        assert saved["save_bytes"] == n * CFG.n_layers * (latent + index)
        hit, _ = await t.ask(ask(), gen=2)
        stats = t.kvc.get_stats()
        assert hit.loaded_blocks == n and hit.prefetched_blocks == n * 2 * CFG.n_layers
        assert stats["hit_values_fetched"] == stats["hit_values_whole_prefix"] == n * 2 * CFG.n_layers
        assert stats["hit_index_bytes_fetched"] == n * CFG.n_layers * index
        assert stats["hit_bytes_fetched"] == n * CFG.n_layers * (latent + index)
        assert stats["hit_state_bytes_fetched"] == 0

    asyncio.run(drive())


def test_the_cache_is_two_tensors_a_layer_and_the_engine_serves_it_by_blocks():
    spec = CFG.kv_spec(4)
    assert not spec.uniform and not spec.has_state and CFG.steps.resume_in_block
    for layer in range(CFG.n_layers):
        latent, index = spec.layer_tensors(layer)
        assert (latent.name, latent.kind, latent.block_shape) == ("latent", "latent", (40, BT))
        assert (index.name, index.kind, index.block_shape) == ("index", "index", (16, BT))
        assert latent.last_blocks is None and index.last_blocks is None
    assert [tuple(t.shape) for t in spec.make_caches()[0]] == [(4, 40, BT), (4, 16, BT)]


def test_a_latent_cache_served_by_blocks_takes_a_drafter(conn, params):
    """Nothing here is a recurrent state: a slot a rejected draft wrote is
    overwritten by the next round's first row, so the engine's refusal (a
    state absorbs every row it is handed) does not reach this model since PR
    62, though it is served a block at a time."""
    kvc = KVConnector(conn, CFG.kv_spec(NUM_BLOCKS), "glm-drafter", max_blocks=MAX_REQ_BLOCKS)
    drafter = object()
    h = ContinuousBatchingHarness(
        EngineKVAdapter(kvc), params, CFG, NUM_BLOCKS, MAX_REQ_BLOCKS, drafter=drafter
    )
    assert h.by_blocks and not h.spec.has_state and h.drafter is drafter and not h.drafts


def test_the_real_file_states_what_the_program_builds():
    with open(os.path.join(REPO, "benchmarks", "configs", "glm-5.json")) as f:
        real = json.load(f)
    fields = {k: real[v] for k, v in real["program"]["fields"].items()}
    cfg = GlmDsaConfig(block_tokens=real["serving"]["block_tokens"], **fields)
    assert (cfg.dim, cfg.n_heads, cfg.q_lora_rank, cfg.latent_width) == (6144, 64, 2048, 576)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (32, 128, 2048)
    assert (cfg.held, cfg.n_experts, cfg.rope_theta, cfg.sites) == ((0, 16), 256, 1e6, 4)
    shapes = jax.eval_shape(lambda k: gd.init_params(cfg, k), jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in shapes.values()) == 3_909_632_768
    spec = cfg.kv_spec(real["serving"]["cache_blocks"])
    per_block = sum(t.nbytes for layer in range(cfg.n_layers) for t in spec.layer_tensors(layer))
    assert per_block == real["serving"]["kv_bytes_per_token"] * cfg.block_tokens == 7040 << 10
    assert [t.nbytes >> 10 for t in spec.layer_tensors(0)] == [1152, 256]
