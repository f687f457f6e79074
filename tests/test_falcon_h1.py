"""The ``falcon_h1`` model on the serving path, at a small size on the CPU:
hidden 64, 10 query heads on 2 KV heads of 16 (5 a KV head, as published), a
Mamba-2 mixer of 4 heads x 16 with a state of 32 in 2 groups and a 4-tap
convolution with bias, chunks of 16 in blocks of 32, an MLP of 160, two
layers, seeded float32 weights and EVERY multiplier at a value other than 1,
so that a dropped one fails.

- the chunked SSD program against the token-by-token recurrence for any cut
  into pieces (a state in, the state at every boundary out);
- the program through the harness, the connector and a store (a miss by blocks
  and its decode through the cache across a block boundary, a full hit, a
  partial hit) against ``benchmarks/reference_falcon_h1.py``; a full hit's
  first-token logits equal the miss's exactly; what a hit does not install is
  poisoned with NaN and never read;
- a hit of n blocks fetches n K, n V, one state and one tail a layer and the
  counters say so; every block saves all four; a saved state is read back
  byte-identical in float32; values of the published sizes (4 MiB, 1 MiB, 30
  KiB) pass staging, upload and D2H through both reads;
- a layer's hit policy is its tensors' (``tpu/paged.py``): the mixed layer;
- the decode and the chunk kernel at 1,024-token pages (interpreted) against
  the XLA path, a page a grid step, and at 16-token pages eight pages a step;
- the configuration's file builds the cache its ``serving`` states, and its
  cost module counts useful work only;
- the serving entries at the published widths compile for a v5e with no chip,
  every cache tensor aliased and no K-, V-, state- or tail-shaped copy.
"""

import asyncio
import functools
import importlib
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
from infinistore_tpu.models import falcon_h1 as fh
from infinistore_tpu.tpu import chunk_attention, paged_attention, ssd
from infinistore_tpu.tpu.paged import CacheTensor, PagedKVCacheSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
import cache_geometry  # noqa: E402
import costs  # noqa: E402
import costs_falcon_h1  # noqa: E402
import reference_falcon_h1  # noqa: E402 - the benchmark's plain reference

MULTIPLIERS = {
    "embedding_multiplier": 1.7, "lm_head_multiplier": 0.6, "attention_in_multiplier": 0.9,
    "attention_out_multiplier": 0.7, "key_multiplier": 0.8, "ssm_in_multiplier": 0.75,
    "ssm_out_multiplier": 1.3, "ssm_multipliers": [0.6, 0.8, 0.7, 1.2, 0.9],
    "mlp_multipliers": [0.65, 1.4],
}
CFG = fh.FalconH1Config(dtype=jnp.float32, rope_theta=1e4, **MULTIPLIERS)
FILE = {  # the same size as the configuration file's keys
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 10, "num_key_value_heads": 2,
    "head_dim": 16, "mamba_d_ssm": 64, "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 32,
    "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 16, "intermediate_size": 160,
    "rope_theta": 1e4, "rope_scaling": None, "rms_norm_eps": 1e-5, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_conv_bias": True, **MULTIPLIERS,
}
BT = CFG.block_tokens
NUM_BLOCKS, MAX_REQ_BLOCKS = 48, 6
GEN = 7
LAYERS, VALUES_A_BLOCK = CFG.n_layers, 4 * CFG.n_layers


@pytest.fixture(scope="module")
def params():
    p = fh.init_params(CFG, jax.random.key(43))
    # A bias that is there: the seeded one is zero, as published.
    for layer in range(LAYERS):
        name = f"l{layer}.conv_b"
        p[name] = 0.1 * jax.random.normal(jax.random.key(layer), p[name].shape, p[name].dtype)
    return p


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
# The state-space walk.
# ---------------------------------------------------------------------------


def _ssd_inputs(s, h=4, p=16, n=32, g=2, seed=0):
    keys = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(keys[0], (s, h, p))
    # Steps from barely any to several: the strong decays overflow any form
    # that takes exp(-L) alone.
    dt = jnp.exp(jax.random.uniform(keys[1], (s, h), minval=-7.0, maxval=1.5))
    a_log = jnp.log(jax.random.uniform(keys[2], (h,), minval=1.0, maxval=16.0))
    b = jax.random.normal(keys[3], (s, g, n))
    c = jax.random.normal(keys[4], (s, g, n))
    d = jax.random.normal(keys[5], (h,))
    state = jax.random.normal(keys[6], (h, p, n))
    return x, dt, a_log, b, c, d, state


def _token_by_token(x, dt, a_log, b, c, d, state):
    """The recurrence as published, a token at a time; the state after each."""
    outs, states = [], []
    for t in range(x.shape[0]):
        o, s = ssd.ssd_step(x[t : t + 1], dt[t : t + 1], a_log, b[t : t + 1], c[t : t + 1], d, state[None])
        state = s[0]
        outs.append(o[0])
        states.append(state)
    return jnp.stack(outs), states


@pytest.mark.parametrize("cuts", [(75,), (32, 43), (1, 7, 64, 3), (5,) * 15], ids=str)
def test_the_chunked_ssd_program_is_the_recurrence_for_any_cut(cuts):
    x, dt, a_log, b, c, d, state = _ssd_inputs(sum(cuts))
    want, states = _token_by_token(x, dt, a_log, b, c, d, state)
    at = 0
    for n in cuts:
        piece = [a[at : at + n] for a in (x, dt)] + [a_log] + [a[at : at + n] for a in (b, c)]
        o, state = ssd.ssd_chunk(*piece, d, state, chunk=16)
        at += n
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(o, want[at - n : at], atol=3e-5 * scale, rtol=0)
        # The state the recurrence holds at this boundary: what a block saves.
        np.testing.assert_allclose(
            state, states[at - 1], atol=3e-5 * float(jnp.max(jnp.abs(states[at - 1]))), rtol=0
        )


def test_one_token_reads_its_groups_b_and_c():
    """Head h reads group h // (heads / groups): with group 1's C zeroed, the
    heads of group 0 alone still read their state."""
    x, dt, a_log, b, c, d, state = _ssd_inputs(1)
    o, _ = ssd.ssd_step(x, dt, a_log, b, c.at[:, 1].set(0.0), jnp.zeros_like(d), state[None])
    assert np.abs(np.asarray(o[0, :2])).min() > 0 and not np.asarray(o[0, 2:]).any()


# ---------------------------------------------------------------------------
# Through the harness, the connector and the store.
# ---------------------------------------------------------------------------


def fetched_values(n: int) -> int:
    """What the per-tensor policy names for a hit of n blocks: n K and n V, a
    state and a tail, a layer."""
    return LAYERS * (2 * n + 2)


class Tapped:
    """A harness whose ``step_chunk`` keeps, per call, the logits rows (as the
    benchmark's taps do), and whose installs poison the prefix's blocks with
    NaN first: what a hit does not install must never be read."""

    def __init__(self, conn, params, name):
        self.kvc = KVConnector(conn, CFG.kv_spec(NUM_BLOCKS), name, max_blocks=MAX_REQ_BLOCKS)
        self.h = ContinuousBatchingHarness(
            EngineKVAdapter(self.kvc), params, CFG, NUM_BLOCKS, MAX_REQ_BLOCKS
        )
        self.calls = []
        step_chunk, install = self.h.wave.step_chunk, self.h.adapter.install_kv

        async def tapped(tokens, positions, table, priority=0):
            rows = await step_chunk(tokens, positions, table, priority=priority)
            self.calls.append(np.asarray(rows, np.float32))
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
    """Round j decodes position len - 1 + j, teacher-forced on the tokens it chose."""
    got = np.concatenate([rows[:1] for rows in calls[:rounds]])
    ref = np.asarray(reference_falcon_h1.logits(
        params, FILE, list(tokens) + stats.generated[: rounds - 1], rounds
    ))
    scale = np.sqrt(np.mean(ref * ref))
    assert np.all(np.isfinite(got))
    assert np.max(np.abs(got - ref)) / scale < 2e-4, np.max(np.abs(got - ref)) / scale


# A document of three blocks and a question that completes none: the prompt's
# last block is part full, as at 1,024-token blocks under a 128-token question.
DOC, QUESTION = 3 * BT, 27


@pytest.mark.parametrize("path", ["miss", "full-hit", "partial-hit"])
def test_the_program_through_the_harness_against_the_reference(conn, params, path):
    rng = np.random.default_rng(431)
    doc = rng.integers(0, CFG.vocab, size=DOC).tolist()
    first = doc + rng.integers(0, CFG.vocab, size=QUESTION).tolist()
    other = doc + rng.integers(0, CFG.vocab, size=QUESTION).tolist()

    async def drive():
        t = Tapped(conn, params, f"falcon-{path}")
        miss, miss_calls = await t.ask(first)
        assert (miss.loaded_blocks, miss.computed_blocks) == (0, 3)
        if path == "miss":
            # 27 + 7 tokens after the document: the decode crosses into block 5,
            # and the row carries its running state into the new block's slot.
            assert t.h.metrics()["state_carries"] == 1
            return first, miss, miss_calls
        tokens = first if path == "full-hit" else other
        hit, calls = await t.ask(tokens)
        assert (hit.hit_blocks, hit.loaded_blocks, hit.computed_blocks) == (3, 3, 0)
        assert hit.prefetched_blocks == fetched_values(3)
        if path == "full-hit":
            # The resume from the installed pages and snapshot runs the programs
            # the miss ran, on the bytes the miss saved: equal to the bit.
            np.testing.assert_array_equal(calls[0], miss_calls[0])
            assert hit.generated == miss.generated
        return tokens, hit, calls

    tokens, stats, calls = asyncio.run(drive())
    against_reference(params, tokens, stats, calls)


def test_a_whole_block_prompt_lands_its_last_token_once(conn, params):
    """A prompt of whole blocks: the compute phase lands all but its last
    token, so its last block is saved with the answer's, and a second ask
    installs one block fewer and computes the rest again."""
    rng = np.random.default_rng(432)
    tokens = rng.integers(0, CFG.vocab, size=3 * BT).tolist()

    async def drive():
        t = Tapped(conn, params, "falcon-whole")
        miss, calls = await t.ask(tokens, gen=BT + 2)
        assert (miss.loaded_blocks, miss.computed_blocks) == (0, 2)
        against_reference(params, tokens, miss, calls)
        hit, hit_calls = await t.ask(tokens, gen=BT + 2)
        assert (hit.loaded_blocks, hit.computed_blocks) == (2, 0)
        return miss, hit, hit_calls

    miss, hit, hit_calls = asyncio.run(drive())
    against_reference(params, tokens, hit, hit_calls)
    assert hit.generated == miss.generated


@pytest.mark.parametrize("n", [1, 2, 5])
def test_a_hit_fetches_n_k_n_v_one_state_and_one_tail_a_layer_and_every_block_saves_all(conn, params, n):
    rng = np.random.default_rng(433 + n)
    doc = rng.integers(0, CFG.vocab, size=n * BT).tolist()
    ask = lambda: doc + rng.integers(0, CFG.vocab, size=3).tolist()
    page = BT * 2 * 16 * 4  # float32 here
    state = 4 * 16 * 32 * 4 + 3 * 192 * 4  # ... the tail (3 rows of x, B and C) included

    async def drive():
        t = Tapped(conn, params, f"falcon-policy-{n}")
        await t.ask(ask(), gen=2)
        saved = t.kvc.get_stats()
        assert conn.get_stats()["kvmap_len"] == n * VALUES_A_BLOCK  # every tensor of every block
        assert saved["save_state_bytes"] == n * LAYERS * state
        assert saved["save_kv_bytes"] == n * LAYERS * 2 * page
        assert saved["save_bytes"] == saved["save_d2h_bytes"] == n * LAYERS * (2 * page + state)
        hit, _ = await t.ask(ask(), gen=2)
        stats = t.kvc.get_stats()
        assert hit.loaded_blocks == n and hit.prefetched_blocks == fetched_values(n)
        assert stats["hit_values_fetched"] == fetched_values(n)
        assert stats["hit_values_whole_prefix"] == n * VALUES_A_BLOCK
        assert stats["hit_state_bytes_fetched"] == LAYERS * state
        assert stats["hit_bytes_fetched"] == LAYERS * (state + n * 2 * page)
        assert stats["hit_bytes_whole_prefix"] == n * LAYERS * (state + 2 * page)
        assert stats["save_bytes"] == saved["save_bytes"]  # a hit's short answer writes nothing

    asyncio.run(drive())


def test_values_of_the_published_sizes_pass_staging_upload_and_d2h(conn):
    """One layer of the published cache: K and V pages of 1,024 KiB, a state
    of 4,096 KiB in float32 (the largest value any configuration stores: a
    call's METADATA body is what ``wire.MAX_BODY_SIZE`` caps, not its payload)
    and a tail of 30 KiB, saved and read back byte for byte through both
    reads, the prefetch's install and the one-phase load."""
    cfg = fh.FalconH1Config(
        n_heads=20, n_kv_heads=4, head_dim=128, ssm_width=4096, ssm_heads=32, ssm_head_dim=128,
        ssm_state=256, ssm_chunk=128, block_tokens=1024, n_layers=1,
    )
    spec = cfg.kv_spec(4)
    (layer,) = [spec.layer_tensors(0)]
    assert [(t.name, t.nbytes >> 10, t.last_blocks, t.kind) for t in layer] == [
        ("k", 1024, None, "kv"), ("v", 1024, None, "kv"), ("state", 4096, 1, "state"),
        ("tail", 30, 1, "state"),
    ]
    assert layer[3].block_shape == (120, 128) and layer[2].dtype == jnp.float32
    assert spec.has_state and not spec.uniform and spec.slot_nbytes == 30 << 10
    # A staging region holds the heaviest layer's hit: 32 x 2 MiB + 4 MiB + 30 KiB.
    assert spec.region_nbytes(32) == (32 * 2048 + 4096 + 30) << 10
    kvc = KVConnector(conn, spec, "sizes", max_blocks=2)
    keys = iter(jax.random.split(jax.random.key(5), 4))
    filled = [tuple(
        jax.random.normal(next(keys), (4, *t.block_shape), jnp.float32).astype(t.dtype) for t in layer
    )]
    want = [np.asarray(t) for t in filled[0]]
    tokens = list(range(2048))

    async def drive():
        assert await kvc.save(tokens, filled, np.array([1, 3], np.int32)) == 2 * 4
        assert kvc.lookup(tokens) == 2
        prefetch = await kvc.start_fetch_async(tokens)
        await prefetch.primed()
        out, loaded = await prefetch.install(spec.make_caches(), np.array([0, 2], np.int32))
        assert loaded == 2 and prefetch.blocks_fetched == 2 + 2 + 1 + 1
        again, n = await kvc.load(tokens, spec.make_caches(), np.array([2, 0], np.int32))
        assert n == 2
        return out, again

    out, again = asyncio.run(drive())
    for got, (last, first) in ((out, (2, 0)), (again, (0, 2))):
        for tensor in (0, 1):  # both blocks' K and V
            np.testing.assert_array_equal(np.asarray(got[0][tensor])[first], want[tensor][1])
            np.testing.assert_array_equal(np.asarray(got[0][tensor])[last], want[tensor][3])
        for tensor in (2, 3):  # the LAST block's state (float32, to the byte) and tail alone
            assert np.asarray(got[0][tensor])[last].tobytes() == want[tensor][3].tobytes()
            assert not np.asarray(got[0][tensor])[first].any()


def test_a_layers_hit_policy_is_its_tensors():
    """The mixed layer: two tensors fetched in every block of a hit and two in
    its last block only. ``hit_first_block`` answers for a layer whose tensors
    agree and refuses one whose tensors do not."""
    spec = CFG.kv_spec(8)
    assert [t.hit_first(5) for t in spec.layer_tensors(0)] == [0, 0, 4, 4]
    assert spec.hit_values(5) == (LAYERS * 2, LAYERS * 2 * 5)
    page, state, tail = (spec.layer_tensors(0)[i].nbytes for i in (0, 2, 3))
    assert spec.hit_nbytes(1, 5) == 2 * 5 * page + state + tail
    with pytest.raises(ValueError, match="differ"):
        spec.hit_first_block(0, 5)
    uniform = PagedKVCacheSpec(2, 8, 16, 2, 16, windows=(32, None))
    assert [uniform.hit_first_block(layer, 5) for layer in (0, 1)] == [3, 0]
    states = PagedKVCacheSpec.of_layers(
        8, 16, [(CacheTensor("state", (4,), jnp.float32, 1, "state"), CacheTensor("tail", (4,), jnp.float32, 1, "state"))]
    )
    assert states.hit_first_block(0, 5) == 4


# ---------------------------------------------------------------------------
# The two K/V kernels at a page longer than a step.
# ---------------------------------------------------------------------------


def _paged_case(bt, blocks=6, kvh=2, h=10, d=128, seed=3):
    keys = jax.random.split(jax.random.key(seed), 3)
    k = jax.random.normal(keys[0], (blocks, bt, kvh, d), jnp.float32)
    v = jax.random.normal(keys[1], (blocks, bt, kvh, d), jnp.float32)
    return k, v, keys[2], h, d


def _grid(jitted, *args):
    """The grid of the one ``pallas_call`` in ``jitted``'s program."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jitted.trace(*args, interpret=True).jaxpr.jaxpr)
    (grid,) = found
    return grid


def test_the_decode_kernel_takes_a_1024_token_page_as_one_step():
    k, v, key, h, d = _paged_case(1024)
    tables = np.array([[4, 1, 3], [0, 5, 2], [2, 2, 2]], np.int32)
    lens = np.array([2049, 1500, 7], np.int32)  # a row a token into its third page
    q = jax.random.normal(key, (3, h, d), jnp.float32)
    meta = paged_attention.build_ragged_wave(list(tables), lens, 1024, pad_to_pow2=True)
    args = (
        q, k, v, jnp.asarray(meta.pages), jnp.asarray(meta.page_rows),
        jnp.asarray(meta.page_starts), jnp.asarray(lens),
    )
    got = paged_attention._paged_decode_attention_pallas_ragged(*args, interpret=True)
    want = paged_attention.paged_decode_attention_xla_batched(
        q, k, v, jnp.asarray(tables), jnp.asarray(lens)
    )
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # 6 real pages in a bucket of 8: a step a page, at most 8 steps for 3 rows.
    assert _grid(paged_attention._paged_decode_attention_pallas_ragged, *args) == (8,)


@pytest.mark.parametrize("rows,start", [(1024, 1024), (127, 2048), (5, 1019)])
def test_the_chunk_kernel_takes_a_1024_token_page_as_one_step(rows, start):
    k, v, key, h, d = _paged_case(1024)
    table = jnp.asarray([4, 1, 3, 0], jnp.int32)
    q = jax.random.normal(key, (rows, h, d), jnp.float32)
    args = (q, k, v, table, jnp.int32(start))
    got = chunk_attention._chunk_prefix_attention_pallas(*args, interpret=True)
    want = chunk_attention.chunk_prefix_attention_xla(*args)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # (row tiles of 128, a step a page of the table).
    assert _grid(chunk_attention._chunk_prefix_attention_pallas, *args) == (-(-rows // 128), 4)


@pytest.mark.parametrize("kernel", ["decode", "chunk"])
def test_at_16_token_pages_a_step_is_eight_pages_as_it_was(kernel):
    """The accepted K/V configurations' geometry: 128 keys a step, eight
    16-token pages; the long page changed no line of either kernel."""
    k, v, key, h, d = _paged_case(16, blocks=40)
    if kernel == "decode":
        q = jax.random.normal(key, (2, h, d), jnp.float32)
        i32 = lambda *a: jnp.asarray(a, jnp.int32)
        args = (q, k, v, jnp.arange(16, dtype=jnp.int32), jnp.zeros(17, jnp.int32), i32(0, 8), i32(100, 128))
        # R + (P - R) // 8 steps bound any two rows over 16 pages.
        assert _grid(paged_attention._paged_decode_attention_pallas_ragged, *args) == (2 + 14 // 8,)
    else:
        q = jax.random.normal(key, (24, h, d), jnp.float32)
        args = (q, k, v, jnp.arange(16, dtype=jnp.int32), jnp.int32(130))
        assert _grid(chunk_attention._chunk_prefix_attention_pallas, *args) == (1, 2)


# ---------------------------------------------------------------------------
# The configuration's file and its cost module.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def file():
    with open(os.path.join(REPO, "benchmarks", "configs", "falcon-h1-34b.json")) as f:
        return json.load(f)


def test_the_file_builds_the_cache_its_serving_states(file):
    prog, serving = file["program"], file["serving"]
    module, _, attr = prog["config_class"].partition(":")
    cfg = getattr(importlib.import_module(module), attr)(
        block_tokens=serving["block_tokens"], dtype=jnp.bfloat16,
        **{k: file[v] for k, v in prog["fields"].items()},
    )
    assert cfg.in_width == 9248 and cfg.conv_width == 5120 and cfg.tail_shape == (120, 128)
    assert isinstance(file["rope_theta"], int) and cfg.rope_theta == 1e11  # past 32 bits: a float here
    spec = cfg.kv_spec(2)
    shapes = [
        [jax.ShapeDtypeStruct((2, *t.block_shape), t.dtype) for t in spec.layer_tensors(layer)]
        for layer in range(spec.num_layers)
    ]

    class Tensor:  # what ``CacheGeometry.of`` asks of a tensor
        def __init__(self, s):
            self.shape, self.nbytes = s.shape, int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize

    geometry = cache_geometry.CacheGeometry.of(
        [[Tensor(s) for s in layer] for layer in shapes], serving["hit_installs"]
    )
    geometry.check(serving)
    assert geometry.block_nbytes == 24696 << 10 and geometry.values_per_block == 16
    # n K, n V, one state and one tail a layer: the file's hit arithmetic.
    for n, mib in ((8, 80.1), (16, 144.1), (32, 272.1)):
        assert geometry.fetched_values(n) == 4 * (2 * n + 2)
        assert abs(geometry.installed_nbytes(n) / 2**20 - mib) < 0.05
    assert spec.hit_values(32) == (8, 4 * 2 * 32)
    layout = cache_geometry.store_layout(serving)
    assert (layout.unit_kib, layout.block_kib, layout.pool_units_per_block) == (16, 4096, 1544)
    params = jax.eval_shape(lambda k: fh.init_params(cfg, k), jax.random.key(0))
    count = sum(int(np.prod(p.shape)) for p in params.values())
    layer = sum(int(np.prod(p.shape)) for name, p in params.items() if name.startswith("l0."))
    assert round(layer / 1e6, 1) == 430.1 and abs(count * 2 / 1e9 - 6.115) < 0.001
    assert serving["cache_blocks"] >= 3 * 33 + 33


def test_the_file_holds_the_published_config_but_for_what_it_lists(file):
    assert file["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert file["published"] == {"num_hidden_layers": 72, "vocab_size": 261120}
    assert (file["num_hidden_layers"], file["vocab_size"]) == (4, 130560)
    assert (file["hidden_size"], file["intermediate_size"], file["mamba_d_ssm"]) == (5120, 21504, 4096)
    assert (file["num_attention_heads"], file["num_key_value_heads"], file["head_dim"]) == (20, 4, 128)
    assert "choices" not in file["program"] and not hasattr(reference_falcon_h1, "logits_following")


def test_the_cost_module_counts_useful_work_only(file):
    bt = file["serving"]["block_tokens"]
    # A miss of 8,192 + 127 tokens is nine pieces; its attention's pairs are
    # those of one causal pass over all of it.
    miss = costs_falcon_h1.prefill_work(file, 8 * bt + 127)
    assert list(costs_falcon_h1.pieces(file, 8 * bt + 127, 8 * bt + 127))[-1] == (8 * bt + 127, 127)
    whole = 4 * costs.flash_prefill_flops(8 * bt + 127, 20, 128)
    assert miss["chunk_attn_flops"] == whole
    # A hit's resume: what the harness calls 9 pages and 127 rows is a context
    # of 8,192 + 127 tokens, never 9 x 1,024.
    hit = costs_falcon_h1.resume_work(file, 9, 127)
    assert hit["chunk_attn_flops"] == 4 * costs.chunk_attn_flops(8 * bt + 127, 127, 20, 128)
    assert hit["chunk_attn_bytes"] == 4 * costs.chunk_attn_bytes(8 * bt + 127, 127, 20, 4, 128, 2)
    # A partial hit that resumes over two blocks and a part.
    part = costs_falcon_h1.resume_work(file, 11, 2 * bt + 127)
    assert list(costs_falcon_h1.pieces(file, 10 * bt + 127, 2 * bt + 127)) == [
        (9 * bt, bt), (10 * bt, bt), (10 * bt + 127, 127),
    ]
    assert part["ssd_chunk_flops"] == 4 * costs_falcon_h1.ssd_chunk_flops(file, 2 * bt + 127)
    # A wave row over 9 pages must read 8 whole pages and one key of the ninth.
    wave = costs_falcon_h1.wave_work(file, 9, 1)
    keys = 8 * bt + 1
    assert wave["ragged_decode_bytes"] == 4 * (2 * keys * 4 * 128 * 2 + 2 * 20 * 128 * 2)
    assert wave["ragged_decode_bytes"] < 4 * costs.ragged_decode_bytes(9, 1, bt, 20, 4, 128, 2)
    assert wave["ssd_step_bytes"] == 4 * 2 * ((4096 << 10) + (30 << 10))
    assert set(miss) | set(hit) | set(wave) == set(costs_falcon_h1.WORK_KEYS)
    assert costs_falcon_h1.ssd_chunk_flops(file, 1) == 5373952  # 5.4 MFLOP a token and layer


# ---------------------------------------------------------------------------
# Compiled for the chip, without one (tests/test_tpu_aot_compile.py's way; here
# so that the file's long compiles run beside that file, not at its end).
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def v5e():
    pytest.importorskip("libtpu")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


# The fourth model file's serving entries (models/falcon_h1.py) at the published
# widths of one layer (every layer is the same program) and a small vocabulary:
# the wave bucket three clients' waves land on, a miss's piece, a hit's question.
AOT_ENTRIES = ["packed_wave", "resume_chunk_block", "resume_chunk_question"]


@pytest.mark.parametrize("entry", AOT_ENTRIES)
def test_falcon_h1_entries_compile_and_update_every_cache_tensor_in_place(v5e, monkeypatch, entry):
    """Each entry compiles for the v5e with its Mosaic kernel (the wave: the
    ragged paged decode; a chunk: the chunk-against-paged-prefix kernel), both
    at 1,024-token pages, and holds an ``input_output_alias`` for EVERY cache
    tensor (four a layer), the aliased bytes the whole cache's; and no
    ``copy``, ``copy-start``, ``slice-start``, ``slice`` or ``gather`` in the
    program yields an array as long as the cache has blocks."""
    from infinistore_tpu.models import serving
    from infinistore_tpu.tpu import paged

    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    cfg = fh.FalconH1Config(
        vocab=1031 if entry == "packed_wave" else 1033, dim=5120, n_layers=2, n_heads=20,
        n_kv_heads=4, head_dim=128, ssm_width=4096, ssm_heads=32, ssm_head_dim=128, ssm_state=256,
        ssm_groups=2, ssm_chunk=128, ffn_dim=1024, block_tokens=1024, dtype=jnp.bfloat16,
        key_multiplier=0.011, ssm_multipliers=(0.35, 0.25, 0.18, 0.5, 0.35), mlp_multipliers=(0.18, 0.011),
    )
    blocks, table = 40, 33
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    i32 = lambda *shape: s(shape, jnp.int32)
    shapes = jax.eval_shape(lambda k: fh.init_params(cfg, k), jax.random.key(0))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype), shapes)
    spec = cfg.kv_spec(blocks)
    caches = [
        tuple(s((blocks, *t.block_shape), t.dtype) for t in spec.layer_tensors(layer))
        for layer in range(cfg.n_layers)
    ]
    if entry == "packed_wave":
        layout = serving.WaveLayout(rows=4, tables=4, pages=128)
        jitted, args = serving.verify_step_ragged, (
            params, i32(layout.size(table)), i32(serving.FEED_ROWS), caches,
        )
        static = {"config": cfg, "max_blocks": table, "layout": layout}
    else:
        tokens = 1024 if entry == "resume_chunk_block" else 127
        jitted, args = fh.resume_chunk, (params, i32(tokens), i32(), caches, i32(table))
        static = {"config": cfg}
    lowered = jitted.trace(*args, **static).lower(lowering_platforms=("tpu",))
    kernels = set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
    exe = lowered.compile()
    text = exe.as_text()
    tensors = [t for layer in caches for t in layer]
    assert len(tensors) == 2 * 4
    header = text.split("\n", 1)[0]
    assert len(re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)", header)) == len(tensors), header
    assert exe.memory_analysis().alias_size_in_bytes == sum(
        int(np.prod(t.shape)) * jnp.dtype(t.dtype).itemsize for t in tensors
    )
    want = "_ragged_attn_kernel" if entry == "packed_wave" else "_chunk_attn_kernel"
    assert want in kernels, kernels
    # ... nor any array that leads with the block axis: a gather by row made
    # XLA:TPU slice the whole state array in two along its 256-wide minor axis
    # first (``mini-gather-slice``, f32[blocks, 32, 128, 128] twice a layer:
    # 2 ms a layer and wave on the chip, PERF.md, PR 43).
    moved = re.findall(
        rf"^.* = [^=]*(?:f32|bf16)\[{blocks},[\d,]+\][^=]* (?:copy|copy-start|slice-start|slice|gather)\(.*$",
        text, flags=re.M,
    )
    assert not moved, moved[:3]
