"""The ``granitemoehybrid`` model on the serving path, at a small size on the
CPU: hidden 64, five layers ``m m a m m`` (Mamba-2 mixers of 8 heads x 16 with
a state of 32 in ONE group and a 4-tap convolution with bias, chunks of 16 in
blocks of 32; one attention layer of 4 query heads on 2 KV heads of 16, no
rotation), an expert layer behind every mixer (top-3 of 8 by the logits,
softmax over the chosen three, width 32, one shared expert of 64), seeded
float32 weights and all four multipliers at values other than 1.

- ``ssd_chunk`` / ``ssd_step`` at one group, 128 x 64 heads and chunk 256
  against the token-by-token walk;
- the new router against a direct top-k-then-softmax, the accepted router's
  program the parent's, and the two shares of a layer (the shared expert
  counted once) adding up to the uncut reference's layer;
- the program through the harness, the connector and a store (a miss by blocks
  and its decode through the cache across a block boundary, a full hit, a
  partial hit) against ``benchmarks/reference_granite_hybrid.py`` following
  the choices the timed waves reported; a full hit's first-token logits equal
  the miss's exactly; what a hit does not install is poisoned with NaN and
  never read; a state kept in bf16 or a router in bf16 fails the comparison;
- a hit of n blocks fetches n K and n V of the ONE attention layer and a state
  and a tail of every Mamba layer (and the followed ids); every block saves
  all; values of the published sizes pass staging, upload and D2H, a host
  array that is not contiguous included;
- the configuration's file builds the cache its ``serving`` states, holds the
  published config but for what it lists, and its cost module counts useful
  work only;
- the two K/V kernels at 2,048-token pages (interpreted) against the XLA path,
  and their programs at 16- and 1,024-token pages what they were; the wave's
  expert kernel at a width of 768;
- the serving entries at the published widths compile for a v5e with no chip,
  every cache tensor aliased and no state-, tail- or page-shaped copy.
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
from infinistore_tpu.models import afmoe, layers
from infinistore_tpu.models import granite_hybrid as gh
from infinistore_tpu.models.kimi_linear import KimiLinearConfig
from infinistore_tpu.tpu import chunk_attention, layerwise, moe, paged_attention, ssd
from infinistore_tpu.tpu.staging import StagedTransfer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
import cache_geometry  # noqa: E402
import costs  # noqa: E402
import costs_granite_hybrid  # noqa: E402
import reference_granite_hybrid  # noqa: E402 - the benchmark's plain reference

MULTIPLIERS = {
    "embedding_multiplier": 3.0, "attention_multiplier": 0.2, "residual_multiplier": 0.6,
    "logits_scaling": 2.5,
}
KINDS = ("mamba", "mamba", "attention", "mamba", "mamba")
TAIL = 6  # tokens whose sets a block keeps
CFG = gh.GraniteHybridConfig(dtype=jnp.float32, layer_types=KINDS, route_tail=TAIL, **MULTIPLIERS)
FILE = {  # the same size as the configuration file's keys
    "hidden_size": 64, "num_hidden_layers": 5, "layer_types": list(KINDS), "num_attention_heads": 4,
    "num_key_value_heads": 2, "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 32,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2, "intermediate_size": 32,
    "shared_intermediate_size": 64, "num_local_experts": 8, "num_experts_per_tok": 3,
    "rms_norm_eps": 1e-5, "position_embedding_type": "nope", "attention_bias": False,
    "mamba_proj_bias": False, "mamba_conv_bias": True, "tie_word_embeddings": True, **MULTIPLIERS,
}
BT = CFG.block_tokens
SITES, K = CFG.n_layers, CFG.experts_per_token
MAMBA_LAYERS = [l for l, kind in enumerate(KINDS) if kind == "mamba"]
NUM_BLOCKS, MAX_REQ_BLOCKS = 48, 6
GEN = 7
# A block of every layer: a state and a tail of four, a K and a V of one, the ids.
VALUES_A_BLOCK = 2 * len(MAMBA_LAYERS) + 2 + 1


@pytest.fixture(scope="module")
def params():
    p = gh.init_params(CFG, jax.random.key(47))
    # A bias that is there: the seeded one is zero, as published.
    for layer in MAMBA_LAYERS:
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
# The state-space walk at the published heads: 128 x 64, ONE group, state 128.
# ---------------------------------------------------------------------------


def _ssd_inputs(s, h=128, p=64, n=128, g=1, seed=0):
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


@pytest.mark.parametrize("cuts", [(300,), (256, 44), (1, 255, 44)], ids=str)
def test_the_ssd_walk_at_one_group_and_chunk_256_is_the_recurrence(cuts):
    x, dt, a_log, b, c, d, state = _ssd_inputs(sum(cuts))

    def token(state, at):
        o, s = ssd.ssd_step(*(v[None] for v in at[:2]), a_log, *(v[None] for v in at[2:]), d, state[None])
        return s[0], (o[0], s[0])

    _, (want, states) = jax.lax.scan(token, state, (x, dt, b, c))
    at = 0
    for n in cuts:
        piece = [a[at : at + n] for a in (x, dt)] + [a_log] + [a[at : at + n] for a in (b, c)]
        o, state = ssd.ssd_chunk(*piece, d, state, chunk=256)
        at += n
        # Float32 sums over a chunk of 256 and a state of 128 in another order
        # than the walk's: 6e-5 of the largest output was read (falcon's test
        # holds chunks of 16 to 3e-5); a bf16 product is a hundred times that.
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(o, want[at - n : at], atol=1e-4 * scale, rtol=0)
        # The state the recurrence holds at this boundary: what a block saves.
        np.testing.assert_allclose(
            state, states[at - 1], atol=1e-4 * float(jnp.max(jnp.abs(states[at - 1]))), rtol=0
        )


# ---------------------------------------------------------------------------
# The router, and the share of a layer.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows", [1, 5, 40])
def test_the_router_is_a_top_k_over_the_logits_and_a_softmax_over_the_chosen(rows, params):
    m = jax.random.normal(jax.random.key(rows), (rows, CFG.dim), jnp.float32)
    router = params["l0.router"]
    ids, weights = moe.route(m, router, None, CFG)
    logits = np.asarray(m, np.float64) @ np.asarray(router, np.float64)
    order = np.argsort(-logits, axis=1)[:, :K]
    np.testing.assert_array_equal(np.sort(np.asarray(ids), axis=1), np.sort(order, axis=1))
    top = np.take_along_axis(logits, np.asarray(ids), axis=1)
    want = np.exp(top - top.max(axis=1, keepdims=True))
    want /= want.sum(axis=1, keepdims=True)
    np.testing.assert_allclose(weights, want, atol=2e-6, rtol=0)
    # No sigmoid, no bias, no scale: the chosen ten's weights add up to one.
    np.testing.assert_allclose(np.asarray(weights).sum(axis=1), 1.0, atol=1e-6)


@pytest.mark.parametrize("config", [afmoe.AfmoeConfig(), KimiLinearConfig()], ids=["afmoe", "kimi"])
def test_the_accepted_routers_program_is_the_parents(config):
    """The sigmoid router as the parent commit wrote it, traced beside
    ``route``: the two programs are one, to the character."""

    def parents(m, router, bias):
        with jax.named_scope("afmoe_router"):
            logits = jnp.dot(
                m.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            scores = jax.nn.sigmoid(logits)
            _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), config.experts_per_token)
            chosen = jnp.take_along_axis(scores, ids, axis=1)
            if config.route_norm:
                chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
            return ids.astype(jnp.int32), chosen * config.route_scale

    args = (
        jnp.zeros((5, config.dim), jnp.bfloat16), jnp.zeros((config.dim, config.n_experts), jnp.bfloat16),
        jnp.zeros((config.n_experts,), jnp.float32),
    )
    now = jax.make_jaxpr(lambda m, r, b: moe.route(m, r, b, config))(*args)
    assert config.router == "sigmoid" and str(now) == str(jax.make_jaxpr(parents)(*args))


@pytest.mark.parametrize("rows", [3, 40], ids=["a-wave", "a-chunk"])
def test_the_two_shares_add_up_to_the_uncut_references_layer(rows, params):
    """Experts 0-3 and 4-7 of 8 (36 and 36 of 72 as published), each share
    routing over all eight and computing its own experts' part, the shared
    expert riding with the share that holds expert 0: their sum is the uncut
    layer, as the reference writes it out."""
    h = jax.random.normal(jax.random.key(10 + rows), (rows, CFG.dim), jnp.float32)
    w = layers.layer_weights(params, 1)
    m = layers.rms(h, w["pre_mlp_norm"], CFG.rms_eps)
    total = jnp.zeros_like(h)
    for first in (0, 4):
        share = gh.GraniteHybridConfig(
            dtype=jnp.float32, layer_types=KINDS, experts_held=(first, 4), **MULTIPLIERS
        )
        held = dict(w, **{name: w[name][first : first + 4] for name in ("w_gate", "w_up", "w_down_moe")})
        out, ids, _ = moe.expert_layer(held, m, share)
        total = total + out
    none = jnp.full((rows, K), -1, jnp.int32)
    with jax.default_matmul_precision("highest"):
        rm, _, rids, weights = reference_granite_hybrid._route(w, h, none, CFG.rms_eps, K)
        routed = reference_granite_hybrid._experts(
            {name: w[name] for name in reference_granite_hybrid.HELD}, rm, rids, weights, 0
        )
        want = reference_granite_hybrid._expert_close(w, jnp.zeros_like(h), rm, routed, True, 1.0)
    np.testing.assert_array_equal(np.sort(ids, axis=1), np.sort(rids, axis=1))
    np.testing.assert_allclose(total, want, atol=2e-5 * float(jnp.max(jnp.abs(want))), rtol=0)


@pytest.mark.parametrize("f", [768, 1024], ids=["768-whole", "two-tiles"])
@pytest.mark.parametrize("n", [4, 0], ids=["padded", "no-slot"])
def test_the_waves_expert_kernel_takes_a_width_of_768_whole(f, n):
    """768 = 6 x 128 is no whole number of 512-wide tiles: the kernel takes the
    width as one tile (the accepted widths keep theirs), and its result is the
    XLA twin's; so is it at two tiles, with ``n`` of its six slots real (the
    held experts the rows chose: four of them, and none)."""
    assert [moe._wave_f_tile(f) for f in (32, 512, 768, 1024)] == [32, 512, 768, 512]
    keys = jax.random.split(jax.random.key(7), 5)
    e, d, tp = 6, 128, 16
    x = jax.random.normal(keys[0], (tp, d), jnp.float32)
    wg, wu = (jax.random.normal(k, (e, d, f), jnp.float32) / np.sqrt(d) for k in keys[1:3])
    wd = jax.random.normal(keys[3], (e, f, d), jnp.float32) / np.sqrt(f)
    slots = jnp.asarray([0, 2, 3, 5, 5, 5], jnp.int32)
    combine = jax.random.uniform(keys[4], (6, tp), jnp.float32).at[n:].set(0.0)
    got = moe._moe_wave_pallas(
        x, slots, jnp.asarray([n], jnp.int32), jnp.broadcast_to(combine[:, :, None], (6, tp, 128)),
        wg, wu, wd, interpret=True,
    )
    want = moe.moe_wave_xla(x, slots, combine, wg, wu, wd)
    np.testing.assert_allclose(got, want, atol=1e-4 * max(float(jnp.max(jnp.abs(want))), 1.0), rtol=0)
    assert n or not np.asarray(got).any()


# ---------------------------------------------------------------------------
# Through the harness, the connector and the store.
# ---------------------------------------------------------------------------


def fetched_values(n: int) -> int:
    """What the per-tensor policy names for a hit of n blocks: n K and n V of
    the attention layer, a state and a tail of each Mamba layer, the ids."""
    return 2 * n + 2 * len(MAMBA_LAYERS) + 1


class Tapped:
    """A harness whose ``step_chunk`` keeps, per call, the logits rows and the
    choices the wave reported (as the benchmark's taps do), and whose installs
    poison the prefix's blocks first (NaN, and -7 for the ids): what a hit
    does not install must never be read."""

    def __init__(self, conn, params, name, cfg=CFG):
        self.kvc = KVConnector(conn, cfg.kv_spec(NUM_BLOCKS), name, max_blocks=MAX_REQ_BLOCKS)
        self.h = ContinuousBatchingHarness(
            EngineKVAdapter(self.kvc), params, cfg, NUM_BLOCKS, MAX_REQ_BLOCKS
        )
        self.calls = []
        step_chunk, install = self.h.wave.step_chunk, self.h.adapter.install_kv

        async def tapped(tokens, positions, table, priority=0):
            rows = await step_chunk(tokens, positions, table, priority=priority)
            self.calls.append((np.asarray(rows, np.float32), gh.choices(self.h, rows)))
            return rows

        async def poisoned(prefetch, caches, block_table):
            ids = jnp.asarray(np.asarray(block_table), jnp.int32)
            bad = lambda t: jnp.nan if jnp.issubdtype(t.dtype, jnp.floating) else -7
            caches = [tuple(t.at[ids].set(bad(t)) for t in layer) for layer in caches]
            return await install(prefetch, caches, block_table)

        self.h.wave.step_chunk = tapped
        self.h.adapter.install_kv = poisoned

    async def ask(self, tokens, gen=GEN):
        self.calls.clear()
        stats = await self.h.run_request(tokens, gen_tokens=gen)
        return stats, list(self.calls)


# Float32 on both sides, so what is left is the order of the sums: the chunked
# walk against the token-by-token one, the kernel's online softmax against the
# dense one, 5e-6 of the logits' rms here. The limit is forty times that and a
# hundredth of what the benchmark allows bf16 (2.5%): a state kept in bf16 (a
# relative 4e-3 a stored element) or a router in bf16 (another expert at a
# near-tie) is far outside, as the two tests after this one show.
LOGITS_LIMIT, GAP_LIMIT = 2e-4, 1e-3


def compare(params, tokens, stats, calls, rounds=GEN):
    """Round j decodes position len - 1 + j, teacher-forced on the tokens it
    chose; the reference follows row 0's choices of each round and the sets of
    the tokens before the first. Returns (worst logit error over the logits'
    rms, widest choice gap)."""
    got = np.concatenate([rows[:1] for rows, _ in calls[:rounds]])
    chosen = np.stack([c[0] for _, c in calls[:rounds]])
    assert chosen.shape == (rounds, SITES * (1 + TAIL), K)
    ref, gaps = reference_granite_hybrid.logits_following(
        params, FILE, list(tokens) + stats.generated[: rounds - 1], rounds, chosen
    )
    ref = np.asarray(ref)
    assert np.all(np.isfinite(got))
    return float(np.max(np.abs(got - ref)) / np.sqrt(np.mean(ref * ref))), float(np.max(np.asarray(gaps)))


def against_reference(params, tokens, stats, calls, rounds=GEN):
    worst, gap = compare(params, tokens, stats, calls, rounds)
    assert worst < LOGITS_LIMIT and gap < GAP_LIMIT, (worst, gap)


# A document of three blocks and a question that completes none: the prompt's
# last block is part full, as at 2,048-token blocks under a 128-token question.
DOC, QUESTION = 3 * BT, 27


def _prompts(seed=471):
    rng = np.random.default_rng(seed)
    doc = rng.integers(0, CFG.vocab, size=DOC).tolist()
    return (
        doc + rng.integers(0, CFG.vocab, size=QUESTION).tolist(),
        doc + rng.integers(0, CFG.vocab, size=QUESTION).tolist(),
    )


@pytest.mark.parametrize("path", ["miss", "full-hit", "partial-hit"])
def test_the_program_through_the_harness_against_the_reference(conn, params, path):
    first, other = _prompts()

    async def drive():
        t = Tapped(conn, params, f"granite-{path}")
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
            # The resume from the installed pages and snapshots runs the programs
            # the miss ran, on the bytes the miss saved: equal to the bit.
            np.testing.assert_array_equal(calls[0][0], miss_calls[0][0])
            np.testing.assert_array_equal(calls[0][1], miss_calls[0][1])
            assert hit.generated == miss.generated
        return tokens, hit, calls

    tokens, stats, calls = asyncio.run(drive())
    against_reference(params, tokens, stats, calls)


@pytest.mark.parametrize("lowered", ["state", "router"])
def test_a_bf16_state_or_a_bf16_router_fails_the_comparison(conn, params, monkeypatch, lowered):
    """What the limits are for: the same miss with the cache's state kept in
    bf16, or with the router's logits from bf16 operands."""
    if lowered == "state":
        real = gh.GraniteHybridConfig.layer_cache

        def in_bf16(self, layer):
            return tuple(
                t if t.name != "state" else type(t)(t.name, t.block_shape, jnp.bfloat16, 1, "state")
                for t in real(self, layer)
            )

        monkeypatch.setattr(gh.GraniteHybridConfig, "layer_cache", in_bf16)
    else:
        monkeypatch.setattr(
            moe, "_router_logits",
            lambda m, router: jnp.dot(m.astype(jnp.bfloat16), router.astype(jnp.bfloat16)).astype(jnp.float32),
        )
        jax.clear_caches()
    tokens, _ = _prompts()

    async def drive():
        t = Tapped(conn, params, f"granite-lowered-{lowered}")
        return await t.ask(tokens)

    try:
        stats, calls = asyncio.run(drive())
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    worst, gap = compare(params, tokens, stats, calls)
    assert worst > LOGITS_LIMIT or gap > GAP_LIMIT, (worst, gap)


def test_a_whole_block_prompt_lands_its_last_token_once(conn, params):
    """A prompt of whole blocks: the compute phase lands all but its last
    token, so its last block is saved with the answer's, and a second ask
    installs one block fewer and computes the rest again."""
    rng = np.random.default_rng(472)
    tokens = rng.integers(0, CFG.vocab, size=3 * BT).tolist()

    async def drive():
        t = Tapped(conn, params, "granite-whole")
        miss, calls = await t.ask(tokens, gen=BT + 2)
        assert (miss.loaded_blocks, miss.computed_blocks) == (0, 2)
        against_reference(params, tokens, miss, calls)
        hit, hit_calls = await t.ask(tokens, gen=BT + 2)
        assert (hit.loaded_blocks, hit.computed_blocks) == (2, 0)
        return miss, hit, hit_calls

    miss, hit, hit_calls = asyncio.run(drive())
    against_reference(params, tokens, hit, hit_calls)
    assert hit.generated == miss.generated


def test_the_wave_reports_the_sets_behind_each_row_and_counts_the_pairs_held_here(conn, params):
    """Beside each row's own sets the wave reports the sets the tokens before
    it chose at all five layers, the nearest first, as the cache kept them
    through the chunks of a miss; ``moe_held_pairs`` is the rows' pairs on
    the experts held here (all of them where the instance holds all, about half
    where it holds experts 0-3 of 8)."""
    rng = np.random.default_rng(473)
    tokens = rng.integers(0, CFG.vocab, size=2 * BT + 3).tolist()
    share = gh.GraniteHybridConfig(
        dtype=jnp.float32, layer_types=KINDS, route_tail=TAIL, experts_held=(0, 4), **MULTIPLIERS
    )
    held = dict(params)
    for name in list(held):
        if name.endswith((".w_gate", ".w_up", ".w_down_moe")):
            held[name] = held[name][:4]

    async def drive(cfg, weights, name):
        t = Tapped(conn, weights, name, cfg)
        _, calls = await t.ask(tokens, gen=8)
        return [c[0] for _, c in calls], t.h.metrics()

    got, metrics = asyncio.run(drive(CFG, params, "granite-routes"))
    assert got[0].shape == (SITES * (1 + TAIL), K)
    own = [g[:SITES] for g in got]
    for step in range(1, len(got)):
        context = got[step][SITES:].reshape(TAIL, SITES, K)  # the nearest first
        for back in range(1, min(step, TAIL) + 1):
            np.testing.assert_array_equal(context[back - 1], own[step - back])
    assert (got[0][SITES:] >= 0).all()  # the prompt's tokens' sets stand behind the first row's
    assert metrics["moe_pairs"] == metrics["moe_held_pairs"] == 8 * SITES * K
    got, metrics = asyncio.run(drive(share, held, "granite-routes-share"))
    mine = sum(int(np.sum(g[:SITES] < 4)) for g in got)
    assert metrics["moe_held_pairs"] == mine and 0 < mine < metrics["moe_pairs"] == 8 * SITES * K


@pytest.mark.parametrize("n", [1, 2, 5])
def test_a_hit_fetches_one_layers_k_and_v_and_the_others_last_state_and_every_block_saves_all(conn, params, n):
    rng = np.random.default_rng(473 + n)
    doc = rng.integers(0, CFG.vocab, size=n * BT).tolist()
    ask = lambda: doc + rng.integers(0, CFG.vocab, size=3).tolist()
    page = BT * 2 * 16 * 4  # float32 here
    # ... the tail (3 rows of x, B and C) and, once a block, the ids included.
    state = len(MAMBA_LAYERS) * (8 * 16 * 32 * 4 + 3 * 192 * 4) + TAIL * SITES * K * 4

    async def drive():
        t = Tapped(conn, params, f"granite-policy-{n}")
        await t.ask(ask(), gen=2)
        saved = t.kvc.get_stats()
        assert conn.get_stats()["kvmap_len"] == n * VALUES_A_BLOCK  # every tensor of every block
        assert saved["save_state_bytes"] == n * state
        assert saved["save_kv_bytes"] == n * 2 * page
        assert saved["save_bytes"] == saved["save_d2h_bytes"] == n * (2 * page + state)
        hit, _ = await t.ask(ask(), gen=2)
        stats = t.kvc.get_stats()
        assert hit.loaded_blocks == n and hit.prefetched_blocks == fetched_values(n)
        assert stats["hit_values_fetched"] == fetched_values(n)
        assert stats["hit_values_whole_prefix"] == n * VALUES_A_BLOCK
        assert stats["hit_state_bytes_fetched"] == state
        assert stats["hit_bytes_fetched"] == state + n * 2 * page
        assert stats["hit_bytes_whole_prefix"] == n * (state + 2 * page)
        assert stats["save_bytes"] == saved["save_bytes"]  # a hit's short answer writes nothing

    asyncio.run(drive())


class _OtherOrder:
    """What the TPU runtime handed back for an int32 ``[4, 100, 128]``: the
    values right, the host array in another dimension order."""

    def __init__(self, values):
        self.values = values

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        return np.ascontiguousarray(np.swapaxes(self.values, 0, 1)).swapaxes(0, 1)


def test_a_staged_host_array_is_contiguous_whatever_the_runtime_hands_back():
    """A save addresses block i of a staged tensor at ``base + i * nbytes``:
    ``StagedTransfer.wait`` hands out C-contiguous arrays only (on the chip the
    last block's ids were saved from another block's bytes: PERF.md, PR 47)."""
    values = np.arange(4 * 100 * 128, dtype=np.int32).reshape(4, 100, 128)
    assert not np.asarray(_OtherOrder(values)).flags["C_CONTIGUOUS"]
    (host,) = StagedTransfer([_OtherOrder(values)]).wait()
    assert host.flags["C_CONTIGUOUS"] and host.strides == (51200, 512, 4)
    np.testing.assert_array_equal(host, values)
    plain = jnp.asarray(values)
    (host,) = StagedTransfer([plain]).wait()
    assert host.flags["C_CONTIGUOUS"] and host.tobytes() == values.tobytes()


def test_values_of_the_published_sizes_pass_staging_upload_and_d2h(conn):
    """The published cache's two kinds of layer at ONE block each way: a state
    of 4,096 KiB in float32, a tail of 50 KiB, the ids of 50 KiB (the same size
    as the tail, another type) and a K and a V page of 4,096 KiB, saved and
    read back byte for byte through the prefetch's install and the one-phase
    load."""
    cfg = gh.GraniteHybridConfig(
        dim=4096, layer_types=("attention", "mamba"), n_heads=32, n_kv_heads=8, ssm_heads=128,
        ssm_head_dim=64, ssm_state=128, ssm_chunk=256, n_experts=72, experts_per_token=10,
        route_tail=640, block_tokens=2048,  # 640 x 2 layers: the ids of 128 x 10
    )
    spec = cfg.kv_spec(4)
    assert [(t.name, t.nbytes >> 10, t.last_blocks, t.kind) for t in spec.layer_tensors(0)] == [
        ("k", 4096, None, "kv"), ("v", 4096, None, "kv"),
    ]
    assert [(t.name, t.nbytes >> 10, t.last_blocks, t.kind) for t in spec.layer_tensors(1)] == [
        ("state", 4096, 1, "state"), ("tail", 50, 1, "state"), ("routes", 50, 1, "state"),
    ]
    assert spec.layer_tensors(1)[1].block_shape == (200, 128) and spec.layer_tensors(1)[2].dtype == jnp.int32
    assert spec.has_state and not spec.uniform and spec.slot_nbytes == 50 << 10
    # A staging region holds the heaviest layer's hit: the K/V layer's 2 x 8 MiB.
    assert spec.region_nbytes(2) == 2 * 8192 << 10
    kvc = KVConnector(conn, spec, "sizes", max_blocks=2)
    keys = iter(jax.random.split(jax.random.key(5), 5))
    filled = [
        tuple(
            (50 * jax.random.normal(next(keys), (4, *t.block_shape), jnp.float32)).astype(t.dtype)
            for t in spec.layer_tensors(layer)
        )
        for layer in range(2)
    ]
    want = [[np.asarray(t) for t in layer] for layer in filled]
    tokens = list(range(4096))

    async def drive():
        assert await kvc.save(tokens, filled, np.array([1, 3], np.int32)) == 2 * 5
        assert kvc.lookup(tokens) == 2
        prefetch = await kvc.start_fetch_async(tokens)
        # A region a layer, each as large as ITS layer's hit in whole slots of
        # 50 KiB (328 for the K/V layer's 16 MiB, 84 for the state layer's
        # 4,196 KiB), not two of the heaviest; the arena holds four such hits.
        assert prefetch.regions == 2 and prefetch._lease.num_slots == 328 + 84
        assert kvc._prefetch_pool.num_slots == 4 * (328 + 84)
        await prefetch.primed()
        out, loaded = await prefetch.install(spec.make_caches(), np.array([0, 2], np.int32))
        assert loaded == 2 and prefetch.blocks_fetched == 2 + 2 + 3
        assert kvc.hit_counters["install_layers"] == 2
        again, n = await kvc.load(tokens, spec.make_caches(), np.array([2, 0], np.int32))
        assert n == 2
        return out, again

    out, again = asyncio.run(drive())
    for got, (last, first) in ((out, (2, 0)), (again, (0, 2))):
        for tensor in (0, 1):  # both blocks' K and V
            np.testing.assert_array_equal(np.asarray(got[0][tensor])[first], want[0][tensor][1])
            np.testing.assert_array_equal(np.asarray(got[0][tensor])[last], want[0][tensor][3])
        for tensor in (0, 1, 2):  # the LAST block's state (float32, to the byte), tail and ids alone
            assert np.asarray(got[1][tensor])[last].tobytes() == want[1][tensor][3].tobytes()
            assert not np.asarray(got[1][tensor])[first].any()


# ---------------------------------------------------------------------------
# The configuration's file, the cache it states, and its cost module.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def file():
    with open(os.path.join(REPO, "benchmarks", "configs", "granite-4.0-h-small.json")) as f:
        return json.load(f)


def _config_of(file):
    prog, serving = file["program"], file["serving"]
    module, _, attr = prog["config_class"].partition(":")
    cfg = getattr(importlib.import_module(module), attr)(
        block_tokens=serving["block_tokens"], dtype=jnp.bfloat16,
        **{k: file[v] for k, v in prog["fields"].items()},
    )
    for attr, key in prog["equals"].items():
        assert getattr(cfg, attr) == file[key], (attr, key)
    return cfg


def test_the_file_builds_the_cache_its_serving_states(file):
    cfg, serving = _config_of(file), file["serving"]
    assert (cfg.in_width, cfg.conv_width, cfg.tail_shape, cfg.routes_shape) == (
        16768, 8448, (200, 128), (100, 128)
    )
    assert (cfg.head_dim, cfg.ssm_width, cfg.held, cfg.sites) == (128, 8192, (0, 36), 10)
    spec = cfg.kv_spec(2)
    kinds = [[t.kind for t in spec.layer_tensors(layer)] for layer in range(10)]
    assert kinds == [["state"] * 2] * 5 + [["kv"] * 2] + [["state"] * 2] * 3 + [["state"] * 3]

    class Tensor:  # what ``CacheGeometry.of`` asks of a tensor
        def __init__(self, t):
            self.shape, self.nbytes = (2, *t.block_shape), 2 * t.nbytes

    geometry = cache_geometry.CacheGeometry.of(
        [[Tensor(t) for t in spec.layer_tensors(layer)] for layer in range(10)], serving["hit_installs"]
    )
    geometry.check(serving)
    assert geometry.block_nbytes == 45556 << 10 and geometry.values_per_block == 21
    layout = cache_geometry.store_layout(serving)
    assert (layout.unit_kib, layout.block_kib, layout.pool_units_per_block) == (16, 4096, 2856)
    params = jax.eval_shape(lambda k: gh.init_params(cfg, k), jax.random.key(0))
    count = sum(int(np.prod(p.shape)) for p in params.values())
    layer = lambda l: sum(int(np.prod(p.shape)) for name, p in params.items() if name.startswith(f"l{l}."))
    assert count == 4_757_211_776 and "lm_head" not in params  # the head is the embedding
    assert (round(layer(0) / 1e6, 1), round(layer(5) / 1e6, 1)) == (461.2, 400.9)
    assert params["l0.w_gate"].shape == (36, 4096, 768) and params["l0.router"].shape == (4096, 72)
    assert serving["cache_blocks"] >= 3 * 17 + 17


@pytest.mark.parametrize("n,mib,share,state_share", [(4, 68.5, 38.5, 53.3), (8, 100.5, 28.2, 36.3), (16, 164.5, 23.1, 22.2)])
def test_a_hit_of_the_published_cache_is_the_files_arithmetic(file, n, mib, share, state_share):
    """``hit_values``, ``hit_nbytes`` and the data plane's ``_layer_plan``
    against ``serving.hit_arithmetic``: n K and n V blocks of ONE layer, nine
    states and tails and the ids."""
    spec = _config_of(file).kv_spec(2)
    assert spec.hit_values(n) == (19, 2 * n)
    total = sum(spec.hit_nbytes(layer, n) for layer in range(10))
    assert abs(total / 2**20 - mib) < 0.05
    block = sum(t.nbytes for layer in range(10) for t in spec.layer_tensors(layer))
    assert abs(100 * total / (n * block) - share) < 0.05
    state = sum(t.nbytes for layer in range(10) for t in spec.layer_tensors(layer) if t.kind == "state")
    assert abs(100 * state / total - state_share) < 0.05 and abs(100 * state / block - 82.0) < 0.05
    for layer in range(10):
        plan = layerwise._layer_plan(spec, layer, n, hit=True)
        moved = [(t.name, first, m) for t, first, m, _ in plan]
        if layer == 5:
            assert moved == [("k", 0, n), ("v", 0, n)]
        else:
            assert moved[:2] == [("state", n - 1, 1), ("tail", n - 1, 1)]
            assert (moved[2:] == [("routes", n - 1, 1)]) == (layer == 9)
        assert layerwise._plan_nbytes(plan) == spec.hit_nbytes(layer, n)
        # A save moves every block of every tensor.
        assert all(first == 0 and m == n for _, first, m, _ in layerwise._layer_plan(spec, layer, n, hit=False))
    assert spec.region_nbytes(17) == 17 * 8192 << 10


def test_the_file_holds_the_published_config_but_for_what_it_lists(file):
    assert file["reduced"] == ["num_hidden_layers", "layer_types", "num_local_experts", "vocab_size"]
    assert (file["num_hidden_layers"], file["num_local_experts"], file["vocab_size"]) == (10, 36, 50176)
    assert file["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    published = file["published"]
    assert (published["num_hidden_layers"], published["num_local_experts"], published["vocab_size"]) == (40, 72, 100352)
    assert (file["hidden_size"], file["intermediate_size"], file["shared_intermediate_size"]) == (4096, 768, 1536)
    assert (file["num_attention_heads"], file["num_key_value_heads"], file["num_experts_per_tok"]) == (32, 8, 10)
    assert (file["mamba_n_heads"], file["mamba_d_head"], file["mamba_d_state"], file["mamba_n_groups"]) == (128, 64, 128, 1)
    assert (file["mamba_d_conv"], file["mamba_chunk_size"], file["router_experts"]) == (4, 256, 72)
    assert (
        file["embedding_multiplier"], file["attention_multiplier"], file["residual_multiplier"],
        file["logits_scaling"], file["rms_norm_eps"],
    ) == (12, 0.0078125, 0.22, 16, 1e-5)
    # At least 8 experts held and an eighth of the vocabulary; a whole period.
    assert file["experts_held"] == [0, 36] and file["vocab_size"] * 8 >= published["vocab_size"]
    assert file["program"]["choices"].endswith(":choices")
    assert hasattr(reference_granite_hybrid, "logits_following")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            (row,) = [r for r in map(json.loads, f) if r["name"] == file["name"]]
        assert file["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if file.get(k) != v}
        assert differs == set(file["reduced"])


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmarks", "reference_granite_hybrid.py")) as f:
        source = f.read()
    assert not re.search(r"^\s*(from|import)\s+infinistore_tpu", source, flags=re.M)
    assert 'default_matmul_precision("highest")' in source


def test_the_cost_module_counts_useful_work_only(file):
    bt = file["serving"]["block_tokens"]
    # A miss of 8,192 + 127 tokens is five pieces; its attention's pairs are
    # those of one causal pass over all of it, in the ONE attention layer.
    miss = costs_granite_hybrid.prefill_work(file, 4 * bt + 127)
    assert list(costs_granite_hybrid.pieces(file, 4 * bt + 127, 4 * bt + 127))[-1] == (4 * bt + 127, 127)
    assert miss["chunk_attn_flops"] == costs.flash_prefill_flops(4 * bt + 127, 32, 128)
    # A hit's resume: what the harness calls 5 pages and 127 rows is a context
    # of 8,192 + 127 tokens, never 5 x 2,048.
    hit = costs_granite_hybrid.resume_work(file, 5, 127)
    assert hit["chunk_attn_flops"] == costs.chunk_attn_flops(4 * bt + 127, 127, 32, 128)
    assert hit["chunk_attn_bytes"] == costs.chunk_attn_bytes(4 * bt + 127, 127, 32, 8, 128, 2)
    assert hit["ssd_chunk_flops"] == 9 * costs_granite_hybrid.ssd_chunk_flops(file, 127)
    # Five of a token's ten choices fall on the 36 experts held here, ten layers.
    assert costs_granite_hybrid.held_choices(file) == 5.0
    assert hit["moe_prefill_flops"] == 127 * 5 * 6 * 4096 * 768 * 10
    # A wave row over 5 pages must read 4 whole pages and one key of the fifth.
    wave = costs_granite_hybrid.wave_work(file, 5, 1)
    keys = 4 * bt + 1
    assert wave["ragged_decode_bytes"] == 2 * keys * 8 * 128 * 2 + 2 * 32 * 128 * 2
    assert wave["ragged_decode_bytes"] < costs.ragged_decode_bytes(5, 1, bt, 32, 8, 128, 2)
    assert wave["ssd_step_bytes"] == 9 * 2 * ((4096 << 10) + 50688)
    # A 4-row wave's 40 pairs over 72 experts: 81% of them distinct, half held.
    assert abs(costs_granite_hybrid.wave_distinct_share(file) - 0.8103) < 1e-4
    assert abs(wave["moe_wave_bytes"] - 5 * 3 * 4096 * 768 * 2 * 10 * 0.8103) < 1e6
    assert set(miss) | set(hit) | set(wave) == set(costs_granite_hybrid.WORK_KEYS)


# ---------------------------------------------------------------------------
# The two K/V kernels at a page of 2,048 tokens.
# ---------------------------------------------------------------------------


def _paged_case(bt, blocks=5, kvh=2, h=8, d=128, seed=3):
    keys = jax.random.split(jax.random.key(seed), 3)
    k = jax.random.normal(keys[0], (blocks, bt, kvh, d), jnp.float32)
    v = jax.random.normal(keys[1], (blocks, bt, kvh, d), jnp.float32)
    return k, v, keys[2], h, d


def _pallas_calls(jitted, *args):
    """(grid, the operands' block shapes) of the one ``pallas_call`` in
    ``jitted``'s program, and the program's text."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                mapping = eqn.params["grid_mapping"]
                sizes = lambda b: tuple(getattr(n, "block_size", n) for n in b.block_shape)
                found.append((tuple(mapping.grid), [sizes(b) for b in mapping.block_mappings]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    traced = jitted.trace(*args, interpret=True)
    walk(traced.jaxpr.jaxpr)
    (call,) = found
    return call, str(traced.jaxpr)


def test_the_decode_kernel_takes_a_2048_token_page_as_one_step():
    k, v, key, h, d = _paged_case(2048)
    tables = np.array([[4, 1, 3], [0, 2, 2], [2, 2, 2]], np.int32)
    lens = np.array([4097, 3000, 7], np.int32)  # a row a token into its third page
    q = jax.random.normal(key, (3, h, d), jnp.float32)
    meta = paged_attention.build_ragged_wave(list(tables), lens, 2048, pad_to_pow2=True)
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
    (grid, _), _ = _pallas_calls(paged_attention._paged_decode_attention_pallas_ragged, *args)
    assert grid == (8,)


@pytest.mark.parametrize("rows,start", [(200, 2048), (127, 4096), (5, 3000)])
def test_the_chunk_kernel_walks_a_2048_token_page_in_two_slices(rows, start):
    k, v, key, h, d = _paged_case(2048)
    table = jnp.asarray([4, 1, 3], jnp.int32)
    q = jax.random.normal(key, (rows, h, d), jnp.float32)
    args = (q, k, v, table, jnp.int32(start))
    got = chunk_attention._chunk_prefix_attention_pallas(*args, interpret=True)
    want = chunk_attention.chunk_prefix_attention_xla(*args)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # (row tiles of 128, a step a slice: two a page of the table), a K and a V
    # operand of 1,024 tokens each.
    (grid, blocks), _ = _pallas_calls(chunk_attention._chunk_prefix_attention_pallas, *args)
    assert grid == (-(-rows // 128), 6) and blocks.count((1, 1024, 2, 128)) == 2


@pytest.mark.parametrize("bt", [16, 1024])
def test_at_the_accepted_page_lengths_the_chunk_kernels_program_is_what_it_was(bt, monkeypatch):
    """16-token pages (eight a step) and 1,024-token pages (one a step): the
    program traced with the slicing out of reach is the program traced with
    it, to the character."""
    k, v, key, h, d = _paged_case(bt, blocks=40 if bt == 16 else 5)
    q = jax.random.normal(key, (24, h, d), jnp.float32)
    table = jnp.arange(16 if bt == 16 else 4, dtype=jnp.int32)
    args = (q, k, v, table, jnp.int32(130 if bt == 16 else 2048))
    (grid, blocks), now = _pallas_calls(chunk_attention._chunk_prefix_attention_pallas, *args)
    assert grid == ((1, 2) if bt == 16 else (1, 4)) and (1, bt, 2, 128) in blocks
    monkeypatch.setattr(chunk_attention, "_PAGE_SLICE_TOKENS", 1 << 30)
    fresh = jax.jit(
        chunk_attention._chunk_prefix_attention_pallas.__wrapped__, static_argnames=("interpret", "window")
    )
    _, was = _pallas_calls(fresh, *args)
    assert now == was


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


# The fifth model file's serving entries (models/granite_hybrid.py) at the
# published widths of three layers (a Mamba layer, the attention layer, the last
# layer with the followed ids) and a small vocabulary: the wave bucket three
# clients' waves land on, a miss's piece, a hit's question.
AOT_ENTRIES = ["packed_wave", "resume_chunk_block", "resume_chunk_question"]


@pytest.mark.parametrize("entry", AOT_ENTRIES)
def test_granite_hybrid_entries_compile_and_update_every_cache_tensor_in_place(v5e, monkeypatch, entry):
    """Each entry compiles for the v5e with its Mosaic kernels (the wave: the
    ragged paged decode and the wave's expert kernel at a width of 768; a
    chunk: the chunk-against-paged-prefix kernel, a 2,048-token page in two
    slices under the 64 MiB its accepted pages compile under), and holds an
    ``input_output_alias`` for EVERY cache tensor, the aliased bytes the whole
    cache's; and no ``copy``, ``copy-start``, ``slice-start``, ``slice`` or
    ``gather`` in the program yields an array as long as the cache has blocks."""
    from infinistore_tpu.models import serving
    from infinistore_tpu.tpu import paged

    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    cfg = gh.GraniteHybridConfig(
        vocab=1031 if entry == "packed_wave" else 1033, dim=4096,
        layer_types=("mamba", "attention", "mamba"), n_heads=32, n_kv_heads=8, ssm_heads=128,
        ssm_head_dim=64, ssm_state=128, ssm_chunk=256, moe_ffn_dim=768, shared_ffn_dim=1536,
        n_experts=72, experts_per_token=10, experts_held=(0, 36), route_tail=128, block_tokens=2048,
        dtype=jnp.bfloat16, embedding_multiplier=12.0, attention_multiplier=0.0078125,
        residual_multiplier=0.22, logits_scaling=16.0,
    )
    blocks, table = 41, 17
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    i32 = lambda *shape: s(shape, jnp.int32)
    shapes = jax.eval_shape(lambda k: gh.init_params(cfg, k), jax.random.key(0))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype), shapes)
    spec = cfg.kv_spec(blocks)
    caches = [
        tuple(s((blocks, *t.block_shape), t.dtype) for t in spec.layer_tensors(layer))
        for layer in range(cfg.n_layers)
    ]
    if entry == "packed_wave":
        layout = serving.WaveLayout(rows=4, tables=4, pages=64)
        jitted, args = serving.verify_step_ragged, (
            params, i32(layout.size(table)), i32(serving.FEED_ROWS), caches,
        )
        static = {"config": cfg, "max_blocks": table, "layout": layout}
    else:
        tokens = 2048 if entry == "resume_chunk_block" else 127
        jitted, args = gh.resume_chunk, (params, i32(tokens), i32(), caches, i32(table))
        static = {"config": cfg}
    lowered = jitted.trace(*args, **static).lower(lowering_platforms=("tpu",))
    kernels = set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
    exe = lowered.compile()
    text = exe.as_text()
    tensors = [t for layer in caches for t in layer]
    assert len(tensors) == 2 + 2 + 3
    header = text.split("\n", 1)[0]
    assert len(re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)", header)) == len(tensors), header
    # ... the whole cache's, and the rows the device pads a 200-row tail and a
    # 100-row ids tensor to its tiles with (a KiB a block).
    held = sum(int(np.prod(t.shape)) * jnp.dtype(t.dtype).itemsize for t in tensors)
    assert 0 <= exe.memory_analysis().alias_size_in_bytes - held <= blocks * 2048
    want = {"_ragged_attn_kernel", "_moe_wave_kernel"} if entry == "packed_wave" else {"_chunk_attn_kernel"}
    assert want <= kernels, kernels
    moved = re.findall(
        rf"^.* = [^=]*(?:f32|bf16|s32)\[{blocks},[\d,]+\][^=]* (?:copy|copy-start|slice-start|slice|gather)\(.*$",
        text, flags=re.M,
    )
    assert not moved, moved[:3]
