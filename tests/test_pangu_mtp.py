"""The ``pangu_ultra_moe`` model on the serving path, at a small size on the
CPU: hidden 64, 4 heads (query latent 32, latent 32, nope 16, rope 8, v 16),
sandwich norms, blocks of 8, 8 experts top-2, one dense layer and two expert
layers, ONE multi-token-prediction (MTP) layer behind them, seeded float32
weights.

- the program through the harness, the connector and a store (a miss and its
  decode through the cache across a block boundary, a full hit, a partial hit
  with a NEW question) against ``benchmarks/reference_pangu_mtp.py``: the main
  logits, the expert sites' gaps and the DRAFT site's (the program's drafted
  id over the reference's own draft logits); the tolerances with their
  reasons, and a bfloat16-everywhere control that fails one;
- a request's output with its drafter is its output without; at a vocabulary
  small enough that drafts land, rounds emit two tokens and the output is
  still the same;
- after a partial hit with a NEW question the drafts are a miss's, and the
  one slot of the MTP layer the hit could not know is a miss's to the byte;
  without the boundary row the hit fetched it is not;
- the shares of the expert layer add up to the uncut layer;
- the cache (six latent layers and a boundary row a block) through save,
  fetch and install; the decoder's slots and the buckets a run of bare
  one-token calls pins; who takes a drafter and who is refused one; the
  file's arithmetic.
"""

import asyncio
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import infinistore_tpu as its
from infinistore_tpu import tracing
from infinistore_tpu.connector import KVConnector
from infinistore_tpu.engine import ContinuousBatchingHarness, EngineKVAdapter, NGramDrafter
from infinistore_tpu.models import glm_dsa, kimi_linear, layers
from infinistore_tpu.models import pangu_mtp as pm
from infinistore_tpu.models.pangu_mtp import PanguMtpConfig
from infinistore_tpu.models.serving import ServingSteps
from infinistore_tpu.tpu import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
import reference_pangu_mtp  # noqa: E402 - the benchmark's plain reference

CFG = PanguMtpConfig(dtype=jnp.float32)  # the defaults are the small size above
BT = CFG.block_tokens
NUM_BLOCKS, MAX_REQ_BLOCKS = 64, 8
GEN = 7
# float32 program against the float32 reference at ``highest``: the two differ
# in the order of their sums alone (absorbed against unabsorbed attention, a
# page at a time against whole rows). Over three layers that is a few 1e-6 of
# the logits' rms; 2e-4 is the siblings' bound and leaves two orders of room,
# while bfloat16 anywhere (8 bits: 4e-3 a rounding, ten roundings a layer)
# reads 1e-2 or more. A gap is a difference of two scores over their rms: the
# same order, so 1e-3 holds a set that IS the reference's own top-k (negative)
# or a tie, and fails a drafted id the reference does not rank first.
LOGITS_TOL, GAP_TOL = 2e-4, 1e-3


def file_of(cfg: PanguMtpConfig) -> dict:
    """``cfg`` as the configuration file's keys."""
    first, count = cfg.held
    return {
        "hidden_size": cfg.dim, "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.n_dense_layers, "num_attention_heads": cfg.n_heads,
        "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "n_routed_experts": count,
        "router_experts": cfg.n_experts, "experts_held": [first, count],
        "num_experts_per_tok": cfg.experts_per_token, "n_shared_experts": cfg.n_shared_experts,
        "norm_topk_prob": True, "routed_scaling_factor": cfg.route_scale,
        "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta, "sandwich_norm": True,
        "num_nextn_predict_layers": 1,
    }


@pytest.fixture(scope="module")
def params():
    return pm.init_params(CFG, jax.random.key(62))


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


class Drafterless(PanguMtpConfig):
    """The same model served WITHOUT its drafter: the role is not declared,
    so the engine sends one token a round and reads no draft (the wave body's
    ``aux["drafts"]`` is nobody's), the prompt pieces' ``next_token`` a nought
    (the MTP layer's slots are written and read by nothing that is emitted)."""

    @property
    def steps(self) -> ServingSteps:
        def resume(*args, **kw):
            return pm.prefill_continue(*args, next_token=jnp.int32(0), **kw)

        return ServingSteps(pm.prefill, resume, pm.verify_step_ragged, resume_in_block=True)


class Tapped:
    """A harness whose ``step_chunk`` keeps, per call, the logits rows, the
    choices the program reports for them (as the benchmark's taps do) and the
    chunk it was handed, and whose installs poison the prefix's blocks with
    NaN first: what a hit does not install must never be read."""

    def __init__(self, conn, params, name, cfg=CFG, poison=True):
        self.kvc = KVConnector(conn, cfg.kv_spec(NUM_BLOCKS), name, max_blocks=MAX_REQ_BLOCKS)
        self.h = ContinuousBatchingHarness(
            EngineKVAdapter(self.kvc), params, cfg, NUM_BLOCKS, MAX_REQ_BLOCKS
        )
        self.calls, self.tables = [], []
        step_chunk, install = self.h.wave.step_chunk, self.h.adapter.install_kv

        async def tapped(tokens, positions, table, priority=0):
            rows = await step_chunk(tokens, positions, table, priority=priority)
            chosen = pm.choices(self.h, rows) if self.h.drafts else None
            self.calls.append((np.asarray(rows, np.float32), chosen, list(tokens)))
            self.tables.append(np.array(table))
            return rows

        async def poisoned(prefetch, caches, block_table):
            ids = jnp.asarray(np.asarray(block_table), jnp.int32)
            caches = [tuple(t.at[ids].set(jnp.nan) for t in layer) for layer in caches]
            return await install(prefetch, caches, block_table)

        self.h.wave.step_chunk = tapped
        if poison:
            self.h.adapter.install_kv = poisoned

    async def ask(self, tokens, gen=GEN):
        self.calls.clear()
        self.tables.clear()
        stats = await self.h.run_request(tokens, gen_tokens=gen)
        return stats, list(self.calls)


def against_reference(params, cfg, tokens, stats, calls, rounds=GEN):
    """Round j decodes position len - 1 + j, teacher-forced on the tokens it
    chose (no draft lands at a vocabulary of 512 in these few rounds: asserted);
    the reference follows row 0's sets of each round and holds the drafted id
    to its own draft logits. Returns (worst logit error, widest gap)."""
    assert all(len(chunk) == 1 or chunk[1] != int(np.argmax(rows[0])) for rows, _, chunk in calls)
    got = np.concatenate([rows[:1] for rows, _, _ in calls[:rounds]])
    chosen = np.stack([c[0] for _, c, _ in calls[:rounds]])
    assert chosen.shape == (rounds, cfg.sites, cfg.experts_per_token)
    # The last site is the (committed, drafted) pair: the committed id is the
    # row's own argmax, which the request then emitted.
    np.testing.assert_array_equal(chosen[:, -1, 0], np.argmax(got, axis=-1))
    np.testing.assert_array_equal(chosen[:, -1, 0], stats.generated[:rounds])
    ref, gaps = reference_pangu_mtp.logits_following(
        params, file_of(cfg), list(tokens) + stats.generated[: rounds - 1], rounds, chosen
    )
    ref = np.asarray(ref)
    scale = np.sqrt(np.mean(ref * ref))
    assert np.all(np.isfinite(got))
    assert gaps.shape == (rounds, cfg.sites)
    return float(np.max(np.abs(got - ref)) / scale), float(np.max(np.asarray(gaps)))


# A document of three blocks and a question that completes none: the prompt's
# last block is part full, as at 1,024-token blocks under a 128-token question.
DOC, QUESTION = 3 * BT, 5


@pytest.mark.parametrize("question", [QUESTION, BT + 3], ids=["in-block", "over-a-block"])
@pytest.mark.parametrize("path", ["miss", "full-hit", "partial-hit"])
def test_the_program_through_the_harness_against_the_reference(conn, params, path, question):
    rng = np.random.default_rng(640 + question)
    doc = rng.integers(0, CFG.vocab, size=DOC).tolist()
    first = doc + rng.integers(0, CFG.vocab, size=question).tolist()
    other = doc + rng.integers(0, CFG.vocab, size=question).tolist()
    prompt_blocks = (DOC + question - 1) // BT

    async def drive():
        t = Tapped(conn, params, f"pangu-{path}-{question}")
        miss, miss_calls = await t.ask(first)
        assert (miss.loaded_blocks, miss.computed_blocks) == (0, prompt_blocks)
        # The rounds between the first and the last rode slots launched ahead
        # of their request (``WaveDecoder.stream``), none under a wrong verdict.
        ahead = t.h.metrics()
        assert (ahead["wave_ahead_waves"], ahead["wave_ahead_dropped"]) == (GEN - 2, 0), ahead
        if path == "miss":
            return first, miss, miss_calls
        tokens = first if path == "full-hit" else other
        hit, calls = await t.ask(tokens)
        loaded = prompt_blocks if path == "full-hit" else 3
        assert (hit.hit_blocks, hit.loaded_blocks) == (loaded, loaded)
        # Six latent layers of every block, and the LAST block's boundary row.
        assert hit.prefetched_blocks == loaded * (CFG.n_layers + 1) + 1
        if path == "full-hit":
            # The resume runs the programs the miss ran, on the bytes the miss
            # saved: equal to the bit, drafts included.
            np.testing.assert_array_equal(calls[0][0], miss_calls[0][0])
            assert hit.generated == miss.generated
            assert [c[2] for c in calls] == [c[2] for c in miss_calls]
        assert t.h.metrics()["wave_ahead_waves"] == 2 * (GEN - 2)
        return tokens, hit, calls

    tokens, stats, calls = asyncio.run(drive())
    # Every round but the first (no draft yet) and the last (one token is
    # wanted: none is drafted past the answer) is a chunk [token, draft].
    assert [len(chunk) for _, _, chunk in calls[:GEN]] == [1] + [2] * (GEN - 2) + [1]
    worst, gap = against_reference(params, CFG, tokens, stats, calls)
    assert worst < LOGITS_TOL, worst
    assert gap < GAP_TOL, gap


def test_bfloat16_everywhere_fails_the_tolerance_float32_keeps(conn):
    """The control: the same program in bfloat16 (weights, cache, products)
    against the float32 reference of the same weights is two orders outside
    the float32 bound, and still inside the benchmark's (run.py: 0.025 rms,
    0.15 worst), whose room is for exactly that."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = pm.init_params(cfg, jax.random.key(62))
    rng = np.random.default_rng(621)
    tokens = rng.integers(0, cfg.vocab, size=DOC + QUESTION).tolist()

    async def drive():
        t = Tapped(conn, params, "pangu-bf16", cfg)
        return await t.ask(tokens)

    stats, calls = asyncio.run(drive())
    worst, gap = against_reference(params, cfg, tokens, stats, calls)
    assert 20 * LOGITS_TOL < worst < 0.15, worst
    assert gap < 0.10, gap  # the benchmark's CHOICE_SLACK


def _serve(conn, params, cfg, name, prompts, gen, together):
    """The generated tokens of ``prompts``, served one at a time or together."""

    async def drive():
        t = Tapped(conn, params, name, cfg, poison=False)
        if together:
            stats = await asyncio.gather(*(t.h.run_request(p, gen_tokens=gen) for p in prompts))
        else:
            stats = [await t.h.run_request(p, gen_tokens=gen) for p in prompts]
        return [s.generated for s in stats], t.h.metrics(), list(t.calls)

    return asyncio.run(drive())


@pytest.mark.parametrize("together", [False, True], ids=["one-at-a-time", "three-live"])
@pytest.mark.parametrize("vocab", [512, 2, 4], ids=["drafts-miss", "half-land", "all-land"])
def test_the_output_with_the_drafter_is_the_output_without(conn, vocab, together):
    """Greedy verification: a draft is emitted only where the main model's own
    argmax confirms it. At a vocabulary of 2 about half the drafts land and at
    4 (these seeded weights: both models settle on one token) all of them:
    those rounds emit two tokens, the rounds are fewer than the tokens, and
    the output is still the drafterless model's, token for token, an answer
    that closes a block (one more wave lands its last token) too."""
    cfg = dataclasses.replace(CFG, vocab=vocab)
    plain = Drafterless(**dataclasses.asdict(cfg))
    params = pm.init_params(cfg, jax.random.key(622))
    rng = np.random.default_rng(623)
    prompts = [rng.integers(0, vocab, size=n).tolist() for n in (DOC + 5, 2 * BT + 2, BT + 7)]
    gen = 25  # 29 + 25, 18 + 25, 15 + 25: the second answer ends ... the third closes a block
    want, base, _ = _serve(conn, params, plain, f"plain-{vocab}-{together}", prompts, gen, together)
    got, m, calls = _serve(conn, params, cfg, f"mtp-{vocab}-{together}", prompts, gen, together)
    assert got == want
    # Both were launched ahead, a row of one and a slot of two; a slot dropped
    # (a verdict guessed wrong) where drafts land, and only there.
    assert base["wave_ahead_waves"] > 10 and m["wave_ahead_waves"] > 10, (base, m)
    assert base["wave_ahead_dropped"] == 0 and (m["wave_ahead_dropped"] > 0) == (vocab < 512), m
    assert base["spec_drafted_tokens"] == 0 and base["spec_emitted_tokens"] == base["spec_rounds"] == 3 * gen
    assert m["spec_emitted_tokens"] == 3 * gen == m["spec_rounds"] + m["spec_accepted_tokens"]
    assert m["spec_drafted_tokens"] >= m["spec_rounds"] - 3 - m["spec_accepted_tokens"] - 3
    if vocab < 512:
        assert m["spec_accepted_tokens"] >= 20 and m["spec_rounds"] < 3 * gen, m
        assert m["spec_tokens_per_step"] > 1.2
        if vocab == 4:
            assert m["spec_accepted_tokens"] == m["spec_drafted_tokens"], m
    else:
        assert m["spec_accepted_tokens"] <= 1, m
    assert all(len(chunk) <= 2 for _, _, chunk in calls)


def _mtp_slots(h, table, n_blocks):
    """The MTP layer's latents and boundary rows of a request's first blocks."""
    ids = jnp.asarray(np.asarray(table[:n_blocks]), jnp.int32)
    latent, boundary = h.caches[CFG.n_layers]
    return np.asarray(latent[ids]), np.asarray(boundary[ids])


@pytest.mark.parametrize("rewrite", ["from-the-fetched-row", "from-a-wrong-row"])
def test_a_partial_hit_with_a_new_question_drafts_what_a_miss_drafts(conn, params, rewrite):
    """The MTP layer's slot at a block's last position is a function of the
    token AFTER the block. A miss of ``doc + q2`` writes it from q2's first
    token; a hit of ``doc`` saved under ``doc + q1`` installs the slot q1 left,
    and its resume rewrites it from the boundary row it fetched: the slot, every
    later slot and every draft are then the miss's, to the byte. With a wrong
    boundary row (what the hit would rewrite from had it not fetched the real
    one) the slot is not."""
    rng = np.random.default_rng(624)
    doc = rng.integers(0, CFG.vocab, size=DOC).tolist()
    q1, q2 = (rng.integers(0, CFG.vocab, size=QUESTION).tolist() for _ in range(2))
    assert q1[0] != q2[0]

    async def drive():
        miss = Tapped(conn, params, f"pangu-rewrite-miss-{rewrite}", poison=False)
        m_stats, m_calls = await miss.ask(doc + q2)
        want = _mtp_slots(miss.h, miss.tables[0], 3)
        hit = Tapped(conn, params, f"pangu-rewrite-hit-{rewrite}")
        await hit.ask(doc + q1)
        stale = _mtp_slots(hit.h, hit.tables[0], 3)
        if rewrite == "from-a-wrong-row":
            install = hit.h.adapter.install_kv

            async def wrong_row(prefetch, caches, block_table):
                caches, loaded = await install(prefetch, caches, block_table)
                latent, boundary = caches[CFG.n_layers]
                last = int(np.asarray(block_table)[loaded // BT - 1])
                caches[CFG.n_layers] = (latent, boundary.at[last].multiply(-1.0))
                return caches, loaded

            hit.h.adapter.install_kv = wrong_row
        h_stats, h_calls = await hit.ask(doc + q2)
        assert (h_stats.loaded_blocks, h_stats.computed_blocks) == (3, 0)
        return want, stale, _mtp_slots(hit.h, hit.tables[0], 3), m_stats, m_calls, h_stats, h_calls

    want, stale, got, m_stats, m_calls, h_stats, h_calls = asyncio.run(drive())
    # What the first ask left: every slot but the block's last is the document's own.
    np.testing.assert_array_equal(stale[0][:, :, : BT - 1], want[0][:, :, : BT - 1])
    np.testing.assert_array_equal(stale[0][:2, :, BT - 1], want[0][:2, :, BT - 1])
    assert not np.array_equal(stale[0][2, :, BT - 1], want[0][2, :, BT - 1])
    assert h_stats.generated == m_stats.generated  # the output never depends on a draft
    if rewrite == "from-the-fetched-row":
        np.testing.assert_array_equal(got[1][2], want[1][2])  # the last block's boundary row, as saved
        np.testing.assert_array_equal(got[0], want[0])
        assert [c[2] for c in h_calls] == [c[2] for c in m_calls]  # every chunk: token and draft
        for (a, ca, _), (b, cb, _) in zip(h_calls, m_calls):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(ca, cb)
    else:
        assert not np.array_equal(got[0][2, :, BT - 1], want[0][2, :, BT - 1])
        np.testing.assert_array_equal(got[0][:, :, : BT - 1], want[0][:, :, : BT - 1])


def test_a_draft_that_is_not_the_references_own_fails_the_draft_site(conn, params):
    """What the benchmark's check holds the draft site to: the gap of the
    program's drafted id over the reference's own draft logits is negative
    where the id is the reference's best (the best OTHER logit lies below
    it), and for another id the distance to the best in the logits' own
    spread: over the benchmark's slack for a row whose slot, next token or
    hidden row were another's."""
    rng = np.random.default_rng(625)
    tokens = rng.integers(0, CFG.vocab, size=DOC + QUESTION).tolist()

    async def drive():
        return await Tapped(conn, params, "pangu-draft-site").ask(tokens)

    stats, calls = asyncio.run(drive())
    chosen = np.stack([c[0] for _, c, _ in calls[:GEN]])
    context = tokens + stats.generated[: GEN - 1]
    _, gaps = reference_pangu_mtp.logits_following(params, file_of(CFG), context, GEN, chosen)
    assert np.all(np.asarray(gaps) < GAP_TOL)
    _, drafts = reference_pangu_mtp.draft_logits(params, file_of(CFG), context, GEN)
    # The program's drafts ARE the reference's (its last row's next token is
    # its own argmax, which the program committed too).
    np.testing.assert_array_equal(chosen[:, -1, 1], np.argmax(np.asarray(drafts), -1))
    wrong = chosen.copy()
    wrong[:, -1, 1] = (wrong[:, -1, 1] + 1) % CFG.vocab
    _, gaps = reference_pangu_mtp.logits_following(params, file_of(CFG), context, GEN, wrong)
    gaps = np.asarray(gaps)
    assert np.all(gaps[:, :-1] < GAP_TOL) and np.all(gaps[:, -1] > 0) and gaps[:, -1].max() > 0.10


@pytest.mark.parametrize("fault", ["shape", "vocabulary", "expert"])
def test_the_reference_refuses_choices_that_are_none(params, fault):
    rng = np.random.default_rng(626)
    tokens = rng.integers(0, CFG.vocab, size=12).tolist()
    choices = np.tile(np.arange(2, dtype=np.int32), (1, CFG.sites, 1))
    if fault == "shape":
        choices = choices[:, :-1]
    elif fault == "vocabulary":
        choices[0, -1, 1] = CFG.vocab
    else:
        choices[0, 0] = [CFG.n_experts, 0]
    with pytest.raises(ValueError, match="of shape|outside the vocabulary|distinct ids"):
        reference_pangu_mtp.logits_following(params, file_of(CFG), tokens, 1, choices)


@pytest.mark.parametrize("shares", [2, 4])
def test_the_expert_layers_shares_add_up_to_the_uncut_layer(shares):
    """A router of 8 cut into ``shares`` spans: the parts the shares give, the
    shared expert counted once (by the share that holds expert 0), add up to
    what the REFERENCE gives for the whole layer's branch held by one (the
    branch before its post-norm: a norm of a sum is no sum of norms, so the
    exchange the absent chips would make comes before it)."""
    whole = dataclasses.replace(CFG, experts_held=None)
    w = layers.layer_weights(pm.init_params(whole, jax.random.key(627)), 1)
    h = 3.0 * jax.random.normal(jax.random.key(628), (24, CFG.dim), jnp.float32)
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
        ref, _ = reference_pangu_mtp._expert_branch(
            {k: w[k] for k in reference_pangu_mtp.EXPERT}, h, jnp.zeros((0, 2), jnp.int32),
            CFG.rms_eps, CFG.experts_per_token, True, CFG.route_scale, 0, True,
        )
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n", [1, 2, 5])
def test_a_hit_fetches_six_latents_a_block_and_one_boundary_row(conn, params, n):
    rng = np.random.default_rng(629 + n)
    doc = rng.integers(0, CFG.vocab, size=n * BT).tolist()
    ask = lambda: doc + rng.integers(0, CFG.vocab, size=3).tolist()
    latent, row = BT * CFG.latent_width * 4, CFG.dim * 4  # float32 here
    layers_ = CFG.n_layers + 1

    async def drive():
        t = Tapped(conn, params, f"pangu-policy-{n}")
        await t.ask(ask(), gen=2)
        saved = t.kvc.get_stats()
        assert conn.get_stats()["kvmap_len"] == n * (layers_ + 1)
        assert saved["save_latent_bytes"] == n * layers_ * latent
        assert saved["save_state_bytes"] == n * row
        hit, _ = await t.ask(ask(), gen=2)
        stats = t.kvc.get_stats()
        assert hit.loaded_blocks == n and hit.prefetched_blocks == n * layers_ + 1
        assert stats["hit_values_fetched"] == n * layers_ + 1
        assert stats["hit_values_whole_prefix"] == n * (layers_ + 1)
        assert stats["hit_state_bytes_fetched"] == row
        assert stats["hit_bytes_fetched"] == n * layers_ * latent + row

    asyncio.run(drive())


def test_a_hit_never_ends_where_the_landed_prompt_ends(conn, params):
    """The last landed slot of the MTP layer is a function of the prompt's
    LAST token, which no block's chain covers: a prompt of k blocks and one
    token is a hit of k - 1 blocks, and its last block is computed."""
    rng = np.random.default_rng(633)
    doc = rng.integers(0, CFG.vocab, size=3 * BT).tolist()

    async def drive():
        t = Tapped(conn, params, "pangu-cap")
        first, _ = await t.ask(doc + [5], gen=3)
        again, _ = await t.ask(doc + [9], gen=3)
        return first, again

    first, again = asyncio.run(drive())
    assert (first.loaded_blocks, first.computed_blocks) == (0, 3)
    assert (again.loaded_blocks, again.computed_blocks) == (2, 1)


def test_the_cache_is_six_latent_layers_and_a_boundary_row_that_is_no_recurrence():
    spec = CFG.kv_spec(4)
    assert not spec.uniform and not spec.has_state and spec.num_layers == CFG.n_layers + 1
    steps = CFG.steps
    assert steps.resume_in_block and steps.drafts
    for layer in range(CFG.n_layers):
        (latent,) = spec.layer_tensors(layer)
        assert (latent.name, latent.kind, latent.block_shape, latent.last_blocks) == (
            "latent", "latent", (40, BT), None,
        )
    latent, boundary = spec.layer_tensors(CFG.n_layers)
    assert (latent.kind, latent.last_blocks) == ("latent", None)
    assert (boundary.name, boundary.kind, boundary.block_shape) == ("boundary", "state", (1, CFG.dim))
    assert boundary.last_blocks == 1 and not boundary.recurrent
    assert spec.hit_values(5) == (1, 5 * (CFG.n_layers + 1))


@pytest.mark.parametrize("entries", [1, 2, 3])
def test_bare_one_token_calls_pin_the_buckets_the_chunks_land_on(conn, params, entries):
    """The benchmark's warm-up launches ``rows`` concurrent one-token calls and
    expects the bucket ``(rows, rows, pages)``: a drafting model's decoder lays
    every entry out as a slot of two rows, so that call and a chunk ``[token,
    draft]`` at the same positions are ONE program, and its ``bucket_sizes``
    count slots."""
    kvc = KVConnector(conn, CFG.kv_spec(NUM_BLOCKS), f"pangu-slots-{entries}", max_blocks=MAX_REQ_BLOCKS)
    h = ContinuousBatchingHarness(EngineKVAdapter(kvc), params, CFG, NUM_BLOCKS, MAX_REQ_BLOCKS)
    assert h.wave.width == 2 and h.drafts
    table = np.zeros(MAX_REQ_BLOCKS, np.int32)

    async def drive():
        bare = await asyncio.gather(*(h.wave.step_chunk([3], [2 * BT - 1], table) for _ in range(entries)))
        pinned = set(h.wave.bucket_sizes)
        chunks = await asyncio.gather(
            *(h.wave.step_chunk([3, 4], [2 * BT - 2, 2 * BT - 1], table) for _ in range(entries))
        )
        return bare, pinned, chunks

    bare, pinned, chunks = asyncio.run(drive())
    slots = 1 << (entries - 1).bit_length()
    assert pinned == {(slots, slots, 1 << (entries * 2 - 1).bit_length())}
    assert set(h.wave.bucket_sizes) == pinned and h.wave.waves == 2
    assert all(len(rows) == 1 for rows in bare) and all(len(rows) == 2 for rows in chunks)
    # A slot's spare row is padding, like the tail's.
    assert h.wave.launched_rows == 2 * 2 * slots
    assert h.wave.launched_rows - h.wave.pad_rows == entries + 2 * entries
    assert h.wave.draft_ids(chunks[0]).shape == (2,) and h.wave.token_ids(bare[0]).shape == (1,)
    with pytest.raises(ValueError, match="at most 2 rows"):
        asyncio.run(h.wave.step_chunk([1, 2, 3], [0, 1, 2], table))


def test_who_takes_a_drafter(conn, params):
    """A recurrent state refuses one (a rejected row would stay absorbed); a
    latent cache served by blocks takes a host drafter; a model that drafts
    itself takes no second one, and needs no argument to draft."""
    kimi = kimi_linear.KimiLinearConfig(dtype=jnp.float32)
    assert kimi.kv_spec(4).has_state
    kvc = KVConnector(conn, kimi.kv_spec(NUM_BLOCKS), "kimi-drafter", max_blocks=MAX_REQ_BLOCKS)
    with pytest.raises(ValueError, match="recurrent state absorbs every row.*no drafter"):
        ContinuousBatchingHarness(
            EngineKVAdapter(kvc), kimi_linear.init_params(kimi, jax.random.key(0)), kimi,
            NUM_BLOCKS, MAX_REQ_BLOCKS, drafter=NGramDrafter(),
        )
    glm = glm_dsa.GlmDsaConfig(dtype=jnp.float32)
    kvc = KVConnector(conn, glm.kv_spec(NUM_BLOCKS), "glm-drafter", max_blocks=MAX_REQ_BLOCKS)
    h = ContinuousBatchingHarness(
        EngineKVAdapter(kvc), glm_dsa.init_params(glm, jax.random.key(0)), glm,
        NUM_BLOCKS, MAX_REQ_BLOCKS, drafter=NGramDrafter(),
    )
    assert h.by_blocks and not h.drafts and h.wave.width == 1
    kvc = KVConnector(conn, CFG.kv_spec(NUM_BLOCKS), "pangu-drafter", max_blocks=MAX_REQ_BLOCKS)
    with pytest.raises(ValueError, match="drafts itself.*no second drafter"):
        ContinuousBatchingHarness(
            EngineKVAdapter(kvc), params, CFG, NUM_BLOCKS, MAX_REQ_BLOCKS, drafter=NGramDrafter()
        )
    assert ContinuousBatchingHarness(
        EngineKVAdapter(kvc), params, CFG, NUM_BLOCKS, MAX_REQ_BLOCKS
    ).drafts


def test_a_host_drafter_on_a_latent_cache_served_by_blocks_changes_no_token(conn):
    """What the narrowed refusal admits: GLM-5's model under the n-gram
    drafter, on a prompt that repeats itself so that drafts land."""
    glm = glm_dsa.GlmDsaConfig(dtype=jnp.float32, vocab=16)
    weights = glm_dsa.init_params(glm, jax.random.key(3))
    prompt = ([1, 2, 3, 4, 5] * 6)[: 3 * glm.block_tokens + 3]

    def serve(name, **kw):
        kvc = KVConnector(conn, glm.kv_spec(NUM_BLOCKS), name, max_blocks=MAX_REQ_BLOCKS)
        h = ContinuousBatchingHarness(
            EngineKVAdapter(kvc), weights, glm, NUM_BLOCKS, MAX_REQ_BLOCKS, **kw
        )
        return asyncio.run(h.run_request(prompt, gen_tokens=20)).generated, h.metrics()

    want, _ = serve("glm-plain")
    got, m = serve("glm-ngram", drafter=NGramDrafter(max_draft=3))
    assert got == want and m["spec_drafted_tokens"] > 0


@pytest.mark.parametrize("key, value", [("mtp_layers", 2), ("sandwich_norm", False), ("experts_per_token", 1)])
def test_the_config_refuses_what_is_not_written(key, value):
    with pytest.raises(ValueError, match="one MTP layer|two ids"):
        PanguMtpConfig(**{key: value})


def test_the_spans_say_what_a_round_accepted_and_which_slot_a_hit_rewrote(conn, params):
    rng = np.random.default_rng(634)
    doc = rng.integers(0, CFG.vocab, size=DOC).tolist()
    tracing.configure(enabled=True, capacity=4096)
    try:
        async def drive():
            t = Tapped(conn, params, "pangu-spans", poison=False)
            await t.ask(doc + [1, 2, 3])
            await t.ask(doc + [4, 5, 6])

        asyncio.run(drive())
        spans = tracing.recorder().snapshot()
    finally:
        tracing.configure(enabled=False)
    generate = [s for s in spans if s["name"] == "generate"]
    assert len(generate) == 2 and all(len(s["attrs"]["accepted"]) == GEN for s in generate)
    (rewrite,) = [s for s in spans if s["name"] == "boundary_rewrite"]
    assert rewrite["attrs"] == {"block": 2, "slot": DOC - 1}


def test_the_real_file_states_what_the_program_builds():
    with open(os.path.join(REPO, "benchmarks", "configs", "openpangu-ultra-moe-718b.json")) as f:
        real = json.load(f)
    fields = {k: real[v] for k, v in real["program"]["fields"].items()}
    cfg = PanguMtpConfig(block_tokens=real["serving"]["block_tokens"], **fields)
    assert (cfg.dim, cfg.n_heads, cfg.q_lora_rank, cfg.latent_width) == (7680, 128, 1536, 576)
    assert (cfg.held, cfg.n_experts, cfg.rope_theta, cfg.sites) == ((0, 8), 256, 25_600_000, 6)
    shapes = jax.eval_shape(lambda k: pm.init_params(cfg, k), jax.random.key(0))
    count = sum(int(np.prod(a.shape)) for a in shapes.values())
    # 621.2 + 4 x 623.2 + 294.9 + 741.2 M and the norms: 8.30 GB at 2 B.
    assert 4_149_000_000 < count < 4_152_000_000, count
    mtp = sum(
        int(np.prod(a.shape)) for k, a in shapes.items() if k.startswith(("mtp.", f"l{cfg.n_layers}."))
    )
    assert 741_000_000 < mtp < 741_500_000, mtp
    spec = cfg.kv_spec(real["serving"]["cache_blocks"])
    per_block = sum(t.nbytes for layer in range(spec.num_layers) for t in spec.layer_tensors(layer))
    assert per_block == real["serving"]["kv_bytes_per_token"] * cfg.block_tokens == 6927 << 10
    assert [t.nbytes for t in spec.layer_tensors(cfg.n_layers)] == [1152 << 10, 15 << 10]
    assert real["serving"]["hit_installs"] == [{"layers": [cfg.n_layers], "tensor": 1, "last_blocks": 1}]
