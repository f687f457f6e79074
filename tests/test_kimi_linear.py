"""The ``kimi_linear`` model on the serving path, at a small size on the CPU:
hidden 64, KDA 4 heads x 16 with a 4-tap convolution, MLA 4 heads (rank 32,
nope 16, rope 8, v 16), blocks of 8, 8 experts top-2, one dense layer and four
expert layers (KDA, KDA, KDA, MLA, KDA), seeded float32 weights.

- the chunked KDA program against the token-by-token recurrence for any cut
  into chunks, with an initial state, and the state at every boundary;
- the absorbed latent decode and the chunk's unabsorbed attention (each in
  plain XLA and as its Pallas kernel, interpreted) against unabsorbed MLA: a
  chunk from mid-block, across a boundary, of a question's 127 rows, over a
  table whose later pages are NaN, with no bias and under one of zeros;
- the program through the harness, the connector and a store (a miss and its
  decode through the cache across a block boundary, a full hit, a partial
  hit) against ``benchmarks/reference_kimi_linear.py`` following the choices
  the timed waves reported; a full hit's first-token logits equal the miss's
  exactly; what a hit does not install is poisoned with NaN and never read;
- a hit of n blocks fetches n latent values and one state and one tail a KDA
  layer, every block saves every tensor, and values of the published sizes
  (2,048, 1,152 and 72 KiB) pass staging, upload and D2H;
- the three accepted files' specs, keys and hit policies read as before;
- the serving entries at the published widths compile for a v5e with no chip,
  every cache tensor aliased and no state-, tail- or latent-shaped copy.
"""

import asyncio
import functools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import infinistore_tpu as its
from infinistore_tpu.connector import KVConnector
from infinistore_tpu.engine import ContinuousBatchingHarness, EngineKVAdapter
from infinistore_tpu.models import kimi_linear as kl
from infinistore_tpu.models.kimi_linear import KimiLinearConfig
from infinistore_tpu.tpu import kda, mla
from infinistore_tpu.tpu.paged import CacheTensor, PagedKVCacheSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
import reference_kimi_linear  # noqa: E402 - the benchmark's plain reference

CFG = KimiLinearConfig(dtype=jnp.float32)  # the defaults are the small size above
FILE = {  # the same size as the configuration file's keys
    "hidden_size": 64, "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "linear_attn_config": {
        "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4], "num_heads": 4, "head_dim": 16,
        "short_conv_kernel_size": 4,
    },
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_experts": 8, "num_experts_per_token": 2, "num_shared_experts": 1,
    "moe_renormalize": True, "routed_scaling_factor": 2.446,
    "moe_router_activation_func": "sigmoid", "num_expert_group": 1, "topk_group": 1,
    "mla_use_nope": True, "rms_norm_eps": 1e-5,
}
BT = CFG.block_tokens
NUM_BLOCKS, MAX_REQ_BLOCKS = 64, 8
GEN = 7
KDA_LAYERS, VALUES_A_BLOCK = 4, 9  # a state and a tail a KDA layer, one latent


@pytest.fixture(scope="module")
def params():
    return kl.init_params(CFG, jax.random.key(41))


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


def _kda_inputs(s, h=4, d=16, seed=0):
    keys = jax.random.split(jax.random.key(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (s, h, d))) / np.sqrt(d)
    k = unit(jax.random.normal(keys[1], (s, h, d)))
    v = jax.random.normal(keys[2], (s, h, d))
    # Decays from barely any to e^-4 a token: the strong ones overflow any
    # form that takes exp(-G) alone.
    g = -jnp.exp(jax.random.uniform(keys[3], (s, h, d), minval=-6.0, maxval=1.4))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (s, h)))
    state = jax.random.normal(keys[5], (h, d, d))
    return q, k, v, g, beta, state


def _token_by_token(q, k, v, g, beta, state):
    """The recurrence as published, a token at a time; the state after each."""
    outs, states = [], []
    for t in range(q.shape[0]):
        o, s = kda.kda_step(q[t : t + 1], k[t : t + 1], v[t : t + 1], g[t : t + 1],
                            beta[t : t + 1], state[None])
        state = s[0]
        outs.append(o[0])
        states.append(state)
    return jnp.stack(outs), states


@pytest.mark.parametrize("cuts", [(75,), (32, 43), (1, 7, 64, 3), (5,) * 15], ids=str)
def test_the_chunked_kda_program_is_the_recurrence_for_any_cut(cuts):
    q, k, v, g, beta, state = _kda_inputs(sum(cuts))
    want, states = _token_by_token(q, k, v, g, beta, state)
    at = 0
    for n in cuts:
        piece = [a[at : at + n] for a in (q, k, v, g, beta)]
        o, state = kda.kda_chunk(*piece, state)
        at += n
        np.testing.assert_allclose(o, want[at - n : at], atol=2e-5, rtol=0)
        # The state the recurrence holds at this boundary.
        np.testing.assert_allclose(state, states[at - 1], atol=2e-5, rtol=0)


def _mla_case(rows=3, h=4, rank=32, rope=8, nope=16, vdim=16, bt=8, blocks=12, table=4):
    keys = jax.random.split(jax.random.key(7), 4)
    latent = jax.random.normal(keys[0], (blocks, rank + rope, bt))  # tokens minor
    q = jax.random.normal(keys[1], (rows, h, nope + rope))
    w_kvb = jax.random.normal(keys[2], (rank, h, nope + vdim)) / np.sqrt(rank)
    tables = jax.random.permutation(keys[3], blocks)[: rows * table].reshape(rows, table)
    lens = jnp.asarray([5, 17, 32][:rows], jnp.int32)
    return q, latent, w_kvb, tables.astype(jnp.int32), lens, (rank, nope, vdim)


def _unabsorbed(q, latent, w_kvb, tables, lens, sizes):
    rank, nope, vdim = sizes
    out = []
    for r in range(q.shape[0]):
        ctx = jnp.swapaxes(latent[tables[r]], 1, 2).reshape(-1, latent.shape[1])[: int(lens[r])]
        kv = jnp.einsum("cr,rhd->chd", ctx[:, :rank], w_kvb)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(ctx[:, None, rank:], (*kv.shape[:2], ctx.shape[1] - rank))], -1
        )
        p = jax.nn.softmax(jnp.einsum("hd,chd->hc", q[r], k) / np.sqrt(q.shape[-1]), axis=-1)
        out.append(jnp.einsum("hc,chd->hd", p, kv[..., nope:]))
    return jnp.stack(out)


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_the_absorbed_latent_decode_is_unabsorbed_mla(form):
    q, latent, w_kvb, tables, lens, (rank, nope, vdim) = _mla_case()
    want = _unabsorbed(q, latent, w_kvb, tables, lens, (rank, nope, vdim))
    q_abs = jnp.einsum("thd,rhd->thr", q[..., :nope], w_kvb[..., :nope])
    q_lat = jnp.concatenate([q_abs, q[..., nope:]], axis=-1)
    scale = float(q.shape[-1] ** -0.5)
    if form == "xla":
        mix = mla.mla_decode_xla(q_lat, latent, tables, lens, rank=rank, scale=scale)
    else:
        mix = mla.mla_decode_pallas(q_lat, latent, tables, lens, rank=rank, scale=scale, interpret=True)
    got = jnp.einsum("thr,rhd->thd", mix, w_kvb[..., nope:])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def _chunk_attention(form, *args, **kwargs):
    """The chunk's latent attention in one of its two forms (``tpu/mla.py``:
    the page loop in plain XLA, the Pallas kernel interpreted)."""
    if form == "xla":
        return mla.latent_chunk_attention_xla(*args, **kwargs)
    return mla.mla_chunk_attention_pallas(*args, **kwargs, interpret=True)


# (first position, rows): inside block 1 of the row's table; from a block's
# first token; from mid-block across the boundary into the next; the 127 rows
# of a hit's question (blocks of 8: it spans sixteen pages).
CHUNKS = [(11, 6), (8, 8), (5, 9), (1, 127)]


@pytest.mark.parametrize("chunk", CHUNKS, ids=lambda c: f"{c[1]}-rows-from-{c[0]}")
@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_the_chunk_against_the_paged_prefix_is_unabsorbed_mla(form, chunk):
    start, s = chunk
    q1, latent, w_kvb, tables, _, (rank, nope, vdim) = _mla_case(rows=1, blocks=20, table=16)
    q = jax.random.normal(jax.random.key(9), (s, 4, q1.shape[-1]))
    got = _chunk_attention(
        form, q, latent, tables[0], jnp.int32(start), w_kvb, rank=rank, nope=nope,
        scale=float(q.shape[-1] ** -0.5),
    )
    # ``_unabsorbed`` a row, every row at once: the whole table's context,
    # a row's later positions masked.
    ctx = np.concatenate([np.asarray(latent[b], np.float64).T for b in np.asarray(tables[0])])
    kv = np.einsum("cr,rhd->chd", ctx[:, :rank], np.asarray(w_kvb, np.float64))
    qs = np.asarray(q, np.float64)
    sc = np.einsum("shd,chd->shc", qs[..., :nope], kv[..., :nope])
    sc = (sc + np.einsum("shd,cd->shc", qs[..., nope:], ctx[:, rank:])) / np.sqrt(q.shape[-1])
    later = np.arange(ctx.shape[0])[None, :] > start + np.arange(s)[:, None]
    sc = np.where(later[:, None, :], -np.inf, sc)
    p = np.exp(sc - sc.max(axis=-1, keepdims=True))
    want = np.einsum("shc,chd->shd", p / p.sum(axis=-1, keepdims=True), kv[..., nope:])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    first = _unabsorbed(q[:1], latent, w_kvb, tables[:1], jnp.asarray([start + 1]), (rank, nope, vdim))
    np.testing.assert_allclose(got[0], first[0], atol=2e-5, rtol=0)


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_the_chunk_reads_no_page_past_its_context(form):
    """The table's entries past the chunk's last position name pages of NaN:
    neither form reads one (the loop stops at the context's pages; the kernel's
    grid clamps a later step to the last real page and computes nothing)."""
    q1, latent, w_kvb, tables, _, (rank, nope, vdim) = _mla_case(rows=1)
    start, s = 11, 4  # the context ends in block 1 of 4
    q = jax.random.normal(jax.random.key(10), (s, 4, q1.shape[-1]))
    kw = dict(rank=rank, nope=nope, scale=float(q.shape[-1] ** -0.5))
    want = _chunk_attention(form, q, latent, tables[0], jnp.int32(start), w_kvb, **kw)
    poisoned = latent.at[tables[0, 2:]].set(jnp.nan)
    got = _chunk_attention(form, q, poisoned, tables[0], jnp.int32(start), w_kvb, **kw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_the_chunk_without_a_bias_is_the_chunk_under_a_bias_of_zeros(form):
    """``bias=None`` is a branch of the program, not a tensor of zeros: the
    two give the same result to the bit."""
    q1, latent, w_kvb, tables, _, (rank, nope, vdim) = _mla_case(rows=1)
    start, s = 9, 7
    q = jax.random.normal(jax.random.key(11), (s, 4, q1.shape[-1]))
    kw = dict(rank=rank, nope=nope, scale=float(q.shape[-1] ** -0.5))
    plain = _chunk_attention(form, q, latent, tables[0], jnp.int32(start), w_kvb, **kw)
    zeros = jnp.zeros((tables.shape[1], s, latent.shape[2]), jnp.float32)
    under = _chunk_attention(form, q, latent, tables[0], jnp.int32(start), w_kvb, bias=zeros, **kw)
    np.testing.assert_array_equal(plain, under)


def test_the_chunk_dispatcher_takes_the_kernel_on_the_chip_alone(monkeypatch):
    """``latent_chunk_attention`` asks ``paged._use_pallas`` and nothing else:
    off the chip it IS the page loop."""
    from infinistore_tpu.tpu import paged

    q1, latent, w_kvb, tables, _, (rank, nope, vdim) = _mla_case(rows=1)
    q = jax.random.normal(jax.random.key(12), (5, 4, q1.shape[-1]))
    args = (q, latent, tables[0], jnp.int32(3), w_kvb)
    kw = dict(rank=rank, nope=nope, scale=0.2)
    assert not paged._use_pallas()
    np.testing.assert_array_equal(
        mla.latent_chunk_attention(*args, **kw), mla.latent_chunk_attention_xla(*args, **kw)
    )
    called = []
    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    monkeypatch.setattr(mla, "mla_chunk_attention_pallas", lambda *a, **k: called.append(k) or "kernel")
    assert mla.latent_chunk_attention(*args, **kw) == "kernel"
    assert called == [dict(kw, bias=None)]


# ---------------------------------------------------------------------------
# Through the harness, the connector and the store.
# ---------------------------------------------------------------------------


def fetched_values(n: int) -> int:
    """What the per-tensor policy names for a hit of n blocks."""
    return n + 2 * KDA_LAYERS


class Tapped:
    """A harness whose ``step_chunk`` keeps, per call, the logits rows and the
    choices the program reports for them (as the benchmark's taps do), and
    whose installs poison the prefix's blocks with NaN first: what a hit does
    not install must never be read."""

    def __init__(self, conn, params, name):
        self.kvc = KVConnector(conn, CFG.kv_spec(NUM_BLOCKS), name, max_blocks=MAX_REQ_BLOCKS)
        self.h = ContinuousBatchingHarness(
            EngineKVAdapter(self.kvc), params, CFG, NUM_BLOCKS, MAX_REQ_BLOCKS
        )
        self.calls = []
        step_chunk, install = self.h.wave.step_chunk, self.h.adapter.install_kv

        async def tapped(tokens, positions, table, priority=0):
            rows = await step_chunk(tokens, positions, table, priority=priority)
            self.calls.append((np.asarray(rows, np.float32), kl.choices(self.h, rows)))
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


def against_reference(params, tokens, stats, calls, rounds=GEN):
    """Round j decodes position len - 1 + j, teacher-forced on the tokens it
    chose; the reference follows row 0's choices of each round."""
    got = np.concatenate([rows[:1] for rows, _ in calls[:rounds]])
    chosen = np.stack([c[0] for _, c in calls[:rounds]])
    assert chosen.shape == (rounds, 4, 2)
    ref, gaps = reference_kimi_linear.logits_following(
        params, FILE, list(tokens) + stats.generated[: rounds - 1], rounds, chosen
    )
    ref = np.asarray(ref)
    scale = np.sqrt(np.mean(ref * ref))
    assert np.all(np.isfinite(got))
    assert np.max(np.abs(got - ref)) / scale < 2e-4, np.max(np.abs(got - ref)) / scale
    assert float(np.max(np.asarray(gaps))) < 1e-3, np.asarray(gaps)


# A document of three blocks and a question that completes none: the prompt's
# last block is part full, as at 1,024-token blocks under a 128-token question.
DOC, QUESTION = 3 * BT, 5


@pytest.mark.parametrize("path", ["miss", "full-hit", "partial-hit"])
def test_the_program_through_the_harness_against_the_reference(conn, params, path):
    rng = np.random.default_rng(411)
    doc = rng.integers(0, CFG.vocab, size=DOC).tolist()
    first = doc + rng.integers(0, CFG.vocab, size=QUESTION).tolist()
    other = doc + rng.integers(0, CFG.vocab, size=QUESTION).tolist()

    async def drive():
        t = Tapped(conn, params, f"kimi-{path}")
        miss, miss_calls = await t.ask(first)
        assert (miss.loaded_blocks, miss.computed_blocks) == (0, 3)
        if path == "miss":
            # 5 + 7 tokens after the document: the decode crosses into block 4.
            assert t.h.metrics()["state_carries"] == 1
            return first, miss, miss_calls
        tokens = first if path == "full-hit" else other
        hit, calls = await t.ask(tokens)
        assert (hit.hit_blocks, hit.loaded_blocks, hit.computed_blocks) == (3, 3, 0)
        assert hit.prefetched_blocks == fetched_values(3)
        if path == "full-hit":
            # The resume from the installed snapshot runs the programs the
            # miss ran, on the bytes the miss saved: equal to the bit.
            np.testing.assert_array_equal(calls[0][0], miss_calls[0][0])
            assert hit.generated == miss.generated
        return tokens, hit, calls

    tokens, stats, calls = asyncio.run(drive())
    against_reference(params, tokens, stats, calls)


def test_a_whole_block_prompt_lands_its_last_token_once(conn, params):
    """A prompt of whole blocks: the compute phase lands all but its last
    token, so its last block is saved with the answer's, and a second ask
    installs one block fewer and computes the rest again."""
    rng = np.random.default_rng(412)
    tokens = rng.integers(0, CFG.vocab, size=3 * BT).tolist()

    async def drive():
        t = Tapped(conn, params, "kimi-whole")
        miss, calls = await t.ask(tokens, gen=BT + 2)
        assert (miss.loaded_blocks, miss.computed_blocks) == (0, 2)
        against_reference(params, tokens, miss, calls)
        hit, hit_calls = await t.ask(tokens, gen=BT + 2)
        assert (hit.loaded_blocks, hit.computed_blocks) == (2, 0)
        return miss, calls, hit, hit_calls

    miss, calls, hit, hit_calls = asyncio.run(drive())
    against_reference(params, tokens, hit, hit_calls)
    assert hit.generated == miss.generated


@pytest.mark.parametrize("n", [1, 2, 5])
def test_a_hit_fetches_n_latents_and_one_state_a_layer_and_every_block_saves_all(conn, params, n):
    rng = np.random.default_rng(413 + n)
    doc = rng.integers(0, CFG.vocab, size=n * BT).tolist()
    ask = lambda: doc + rng.integers(0, CFG.vocab, size=3).tolist()

    async def drive():
        t = Tapped(conn, params, f"kimi-policy-{n}")
        await t.ask(ask(), gen=2)
        saved = t.kvc.get_stats()
        assert conn.get_stats()["kvmap_len"] == n * VALUES_A_BLOCK  # every tensor of every block
        state = KDA_LAYERS * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)  # float32 here, tail included
        latent = BT * 40 * 4
        assert saved["save_state_bytes"] == n * state
        assert saved["save_latent_bytes"] == n * latent
        hit, _ = await t.ask(ask(), gen=2)
        stats = t.kvc.get_stats()
        assert hit.loaded_blocks == n and hit.prefetched_blocks == fetched_values(n)
        assert stats["hit_values_fetched"] == n + 2 * KDA_LAYERS
        assert stats["hit_values_whole_prefix"] == n * VALUES_A_BLOCK
        assert stats["hit_state_bytes_fetched"] == state
        assert stats["hit_bytes_fetched"] == state + n * latent
        assert stats["hit_bytes_whole_prefix"] == n * (state + latent)
        assert stats["save_state_bytes"] == n * state  # a hit's short answer writes nothing

    asyncio.run(drive())


def test_values_of_the_published_sizes_pass_staging_upload_and_d2h(conn):
    """A state of 2,048 KiB in float32, a latent block of 1,152 KiB and a
    tail of 72 KiB, saved and read back byte for byte through all three
    reads: the prefetch's install, its layer-by-layer ``install_layer`` and
    the one-phase load (one upload helper: host views, host-cut ids)."""
    layers = [
        (CacheTensor("state", (32, 128, 128), jnp.float32, 1, "state"),
         CacheTensor("tail", (288, 128), jnp.bfloat16, 1, "state")),
        (CacheTensor("latent", (576, 1024), jnp.bfloat16, None, "latent"),),
    ]
    spec = PagedKVCacheSpec.of_layers(4, 1024, layers)
    assert [t.nbytes >> 10 for tensors in layers for t in tensors] == [2048, 72, 1152]
    assert spec.has_state and not spec.uniform and spec.slot_nbytes == 72 << 10
    kvc = KVConnector(conn, spec, "sizes", max_blocks=2)
    keys = iter(jax.random.split(jax.random.key(5), 3))
    filled = [
        tuple(jax.random.normal(next(keys), (4, *t.block_shape), jnp.float32).astype(t.dtype)
              for t in tensors)
        for tensors in layers
    ]
    want = [[np.asarray(t) for t in layer] for layer in filled]
    tokens = list(range(2048))

    async def drive():
        assert await kvc.save(tokens, filled, np.array([1, 3], np.int32)) == 2 * 3
        assert kvc.lookup(tokens) == 2
        prefetch = await kvc.start_fetch_async(tokens)
        assert prefetch.n_blocks == 2
        await prefetch.primed()
        out, loaded = await prefetch.install(spec.make_caches(), np.array([0, 2], np.int32))
        assert loaded == 2 and prefetch.blocks_fetched == 2 + 2  # two latents, one state, one tail
        again, n = await kvc.load(tokens, spec.make_caches(), np.array([2, 0], np.int32))
        assert n == 2
        layered, by_layer = await kvc.start_fetch_async(tokens), spec.make_caches()
        for layer in range(spec.num_layers):
            by_layer, ok = await layered.install_layer(by_layer, np.array([2, 0], np.int32), layer)
            assert ok
        return out, again, by_layer

    out, again, by_layer = asyncio.run(drive())
    for got, (last, first) in ((out, (2, 0)), (again, (0, 2)), (by_layer, (0, 2))):
        # The LAST block's state and tail alone; both blocks' latents.
        np.testing.assert_array_equal(np.asarray(got[0][0])[last], want[0][0][3])
        np.testing.assert_array_equal(np.asarray(got[0][1])[last], want[0][1][3])
        assert not np.asarray(got[0][0])[first].any()
        np.testing.assert_array_equal(np.asarray(got[1][0])[first], want[1][0][1])
        np.testing.assert_array_equal(np.asarray(got[1][0])[last], want[1][0][3])


@pytest.mark.parametrize("name", ["mistral-7b-v0.3", "deepseek-llm-7b", "trinity-mini"])
def test_the_accepted_files_specs_keys_and_policies_read_as_before(name):
    import importlib

    with open(os.path.join(REPO, "benchmarks", "configs", f"{name}.json")) as f:
        file = json.load(f)
    prog = file["program"]
    module, _, attr = prog["config_class"].partition(":")
    cfg = getattr(importlib.import_module(module), attr)(
        block_tokens=file["serving"]["block_tokens"], dtype=jnp.bfloat16,
        **{k: file[v] for k, v in prog["fields"].items()},
    )
    spec = cfg.kv_spec(8)
    assert spec.uniform and not spec.has_state and spec.layers is None
    kvh, hd = file["num_key_value_heads"], file.get("head_dim") or file["hidden_size"] // file["num_attention_heads"]
    assert spec.block_shape == (16, kvh, hd) and spec.block_nbytes == 16 * kvh * hd * 2
    assert spec.slot_nbytes == spec.block_nbytes
    # Made once a spec: the data plane asks per layer and per BLOCK (a 32k
    # request names 20,000 values; a tensor reckoned anew each time cost the
    # event loop 70 ms of it and the reuse cells 3-5%: PERF.md, PR 41).
    assert spec.layer_tensors(0) is spec.layer_tensors(0)
    assert spec.layer_tensors(0)[0].nbytes and "nbytes" in vars(spec.layer_tensors(0)[0])
    assert spec.region_nbytes(5) == 2 * 5 * spec.block_nbytes
    for layer in range(spec.num_layers):
        k, v = spec.layer_tensors(layer)
        assert (k.name, v.name) == ("k", "v") and k.kind == v.kind == "kv"
        assert k.block_shape == v.block_shape == spec.block_shape
    kvc = KVConnector(None, spec, name, max_blocks=4)
    assert kvc.block_key(1, "k", "abc") == f"{name}/L1/k/abc"
    if name == "trinity-mini":
        # Three sliding layers and one of the dense kind before the full one:
        # a window of 2,048 tokens is 128 blocks of 16.
        assert [spec.hit_first_block(l, 2056) for l in range(5)] == [1928] * 4 + [0]
        assert spec.hit_values(2056) == (2 * 4 * 128, 2 * 2056)
        assert spec.hit_values(100) == (2 * 4 * 100, 2 * 100)
        assert spec.layer_tensors(0)[0].last_blocks == 128
    else:
        assert spec.windows is None and spec.window is None
        assert spec.hit_first_block(0, 512) == 0
        assert spec.hit_values(512) == (0, 2 * spec.num_layers * 512)
        assert all(t.last_blocks is None for t in spec.layer_tensors(0))


def test_a_block_keeps_the_sets_its_last_tokens_chose_and_a_wave_hands_them_back(conn, params):
    """``route_tail``: beside each row's own sets the wave reports the sets
    the tokens before it chose, the nearest first, as the cache kept them:
    through the chunks of a miss, across a block boundary, and after a hit
    that installed them with the last block's state."""
    tail = 6
    cfg = KimiLinearConfig(dtype=jnp.float32, route_tail=tail)
    spec = cfg.kv_spec(NUM_BLOCKS)
    assert [t.name for t in spec.layer_tensors(4)] == ["state", "tail", "routes"]
    assert spec.layer_tensors(4)[2].block_shape == (1, tail * 4 * 2)
    assert len(spec.layer_tensors(3)) == 1 and len(spec.layer_tensors(0)) == 2
    rng = np.random.default_rng(415)
    doc = rng.integers(0, CFG.vocab, size=2 * BT).tolist()
    tokens = doc + rng.integers(0, CFG.vocab, size=3).tolist()

    async def drive(name):
        kvc = KVConnector(conn, spec, name, max_blocks=MAX_REQ_BLOCKS)
        h = ContinuousBatchingHarness(EngineKVAdapter(kvc), params, cfg, NUM_BLOCKS, MAX_REQ_BLOCKS)
        seen = []
        step_chunk = h.wave.step_chunk

        async def tapped(toks, positions, table, priority=0):
            rows = await step_chunk(toks, positions, table, priority=priority)
            seen.append(np.asarray(kl.choices(h, rows))[0])
            return rows

        h.wave.step_chunk = tapped
        miss = await h.run_request(tokens, gen_tokens=8)
        first = list(seen)
        seen.clear()
        hit = await h.run_request(tokens, gen_tokens=8)
        assert (miss.loaded_blocks, hit.loaded_blocks) == (0, 2)
        assert hit.prefetched_blocks == 2 + 2 * KDA_LAYERS + 1  # ... and the routes
        return first, list(seen)

    first, again = asyncio.run(drive("kimi-routes"))
    for got in (first, again):
        assert got[0].shape == (4 * (1 + tail), 2)
        own = [g[:4] for g in got]
        for step in range(1, len(got)):
            context = got[step][4:].reshape(tail, 4, 2)  # the nearest first
            for back in range(1, min(step, tail) + 1):
                np.testing.assert_array_equal(context[back - 1], own[step - back])
        # The prompt's tokens' sets stand behind the first row's: none unset.
        assert (got[0][4:] >= 0).all()
    # A hit resumes from the miss's saved tail: the same sets, to the id.
    np.testing.assert_array_equal(np.stack(first), np.stack(again))


# ---------------------------------------------------------------------------
# Compiled for the chip, without one (tests/test_tpu_aot_compile.py's way; here
# so that the file's one long compile runs beside that file, not at its end).
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def v5e():
    pytest.importorskip("libtpu")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


# The third model file's serving entries (models/kimi_linear.py): a cache of
# per-layer kinds at the published widths (a float32 state of 32 x 128 x 128
# and a folded tail a KDA layer, one latent tensor of 576 x 1,024 the MLA
# layer, 1,024-token blocks), 8 experts top-2 of a small width and a small
# vocabulary; one layer of each kind (KDA with the dense MLP, MLA with the
# expert layer), which is every program a deeper stack repeats.
AOT_ENTRIES = ["packed_wave", "resume_chunk_block", "resume_chunk_question"]


@pytest.mark.parametrize("entry", AOT_ENTRIES)
def test_kimi_linear_entries_compile_and_update_every_cache_tensor_in_place(v5e, monkeypatch, entry):
    """Each entry compiles for the v5e with its Mosaic kernels (the wave: the
    latent paged decode and the wave's expert kernel; a chunk: the grouped
    matmul) and holds an ``input_output_alias`` for EVERY cache tensor (two a
    KDA layer, one the MLA layer's), the aliased bytes the whole cache's; and
    no ``copy``, ``copy-start`` or ``slice-start`` in the program has the
    shape of a layer's state, tail or latent array: the state is updated in
    place, and the latent cache is never laid out again (kept token-major it
    was copied whole twice a wave)."""
    from infinistore_tpu.models import serving
    from infinistore_tpu.tpu import paged

    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    cfg = kl.KimiLinearConfig(
        vocab=1031 if entry == "packed_wave" else 1033, dim=2304, n_layers=2, kda_layers=(1,),
        full_attn_layers=(2,), kda_heads=32, kda_head_dim=128,
        gate_rank=128, n_heads=32, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, ffn_dim=512, moe_ffn_dim=256, n_experts=8, experts_per_token=2,
        block_tokens=1024, dtype=jnp.bfloat16,
    )
    blocks, table = 40, 33
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    i32 = lambda *shape: s(shape, jnp.int32)
    shapes = jax.eval_shape(lambda k: kl.init_params(cfg, k), jax.random.key(0))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype), shapes)
    spec = cfg.kv_spec(blocks)
    caches = [
        tuple(s((blocks, *t.block_shape), t.dtype) for t in spec.layer_tensors(layer))
        for layer in range(cfg.n_layers)
    ]
    if entry == "packed_wave":  # the bucket three clients' waves land on
        layout = serving.WaveLayout(rows=4, tables=4, pages=128)
        jitted, args = serving.verify_step_ragged, (
            params, i32(layout.size(table)), i32(serving.FEED_ROWS), caches,
        )
        static = {"config": cfg, "max_blocks": table, "layout": layout}
    else:
        tokens = 1024 if entry == "resume_chunk_block" else 127
        jitted, args = kl.resume_chunk, (params, i32(tokens), i32(), caches, i32(table))
        static = {"config": cfg}
    lowered = jitted.trace(*args, **static).lower(lowering_platforms=("tpu",))
    kernels = set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
    exe = lowered.compile()
    text = exe.as_text()
    tensors = [t for layer in caches for t in layer]
    assert len(tensors) == 2 + 1
    header = text.split("\n", 1)[0]
    assert len(re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)", header)) == len(tensors), header
    assert exe.memory_analysis().alias_size_in_bytes == sum(
        int(np.prod(t.shape)) * jnp.dtype(t.dtype).itemsize for t in tensors
    )
    if entry == "packed_wave":
        assert {"_decode_kernel", "_moe_wave_kernel"} <= kernels, kernels
    else:
        assert kernels, kernels  # the grouped matmul's
    shaped = "|".join(
        rf"{'f32' if t.dtype == jnp.float32 else 'bf16'}\[{','.join(map(str, t.shape))}\]"
        for t in caches[0] + caches[1]
    )
    moved = re.findall(
        rf"^.* = [^=]*(?:{shaped})[^=]* (?:copy|copy-start|slice-start)\(.*$", text, flags=re.M
    )
    assert not moved, moved[:3]
