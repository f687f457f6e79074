"""A partial prefix hit through the engine: the cached document is installed,
the question is computed by ONE chunked resume (models/llama.py resume_chunk:
the chunk attends the request's pages once), and what the request then says
is what a miss of the same prompt says.

Written tolerance for the first-token logits: float32 on the CPU, where the
resume's attention (gather + dense, HIGHEST) and the miss's prefill attention
(dense causal, HIGHEST) sum the same products in different orders: 2e-4
absolute and relative, the bound the harness's own ``verify`` uses
(observed under 1e-5). The tokens must be equal. The same bound holds
where the resume runs the Pallas kernel in interpret mode (float32, HIGHEST,
an online softmax over 128-key steps)."""

import asyncio
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu import tracing
from infinistore_tpu.connector import KVConnector
from infinistore_tpu.engine import ContinuousBatchingHarness, EngineKVAdapter
from infinistore_tpu.models import LlamaConfig, init_params, llama
from infinistore_tpu.tpu import chunk_attention as ca

CFG = LlamaConfig(
    vocab=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128,
    block_tokens=8, dtype=jnp.float32,
)
NUM_BLOCKS, MAX_REQ_BLOCKS, GEN = 48, 8, 5
DOC_BLOCKS, QUESTION_BLOCKS = 3, 2


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture()
def traced():
    rec = tracing.configure(enabled=True, capacity=4096, slow_op_us=0)
    rec.clear()
    yield rec
    tracing.configure(enabled=False)


def _harness(conn, params, model_id, cfg=CFG, num_blocks=NUM_BLOCKS,
             max_req_blocks=MAX_REQ_BLOCKS):
    kvc = KVConnector(conn, cfg.kv_spec(num_blocks), model_id, max_blocks=max_req_blocks)
    h = ContinuousBatchingHarness(
        EngineKVAdapter(kvc), params, cfg, num_blocks, max_req_blocks
    )
    # The first wave's logits rows of every request, in request order.
    first_rows = []
    step_chunk = h.wave.step_chunk

    async def keep_first(tokens, positions, padded_table, priority=0):
        rows = await step_chunk(tokens, positions, padded_table, priority=priority)
        if positions[0] == len(h._prompt_now) - 1:
            first_rows.append(np.asarray(rows[0]))
        return rows

    h.wave.step_chunk = keep_first
    return h, first_rows


def _tokens(seed, blocks, vocab=CFG.vocab):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, size=blocks * CFG.block_tokens).tolist()


async def _drive(h, prompts):
    out = []
    for p in prompts:
        h._prompt_now = p
        out.append(await h.run_request(p, gen_tokens=GEN))
    return out


def test_partial_hit_resumes_once_and_says_what_a_miss_says(conn, params, traced):
    bt = CFG.block_tokens
    doc, question = _tokens(1, DOC_BLOCKS), _tokens(2, QUESTION_BLOCKS)
    other_doc = _tokens(3, DOC_BLOCKS + 1)

    # The engine under test: the document is saved by a first request, then
    # asked about (a resume of the question), then a second document the
    # same way (so that the counters add up over requests).
    h, rows = _harness(conn, params, f"resume-{conn.shm_active}")
    saved, hit, saved2, hit2 = asyncio.run(asyncio.wait_for(_drive(
        h, [doc, doc + question, other_doc, other_doc + question[:bt]]
    ), 120))
    assert saved.loaded_blocks == 0 and saved2.loaded_blocks == 0
    assert (hit.loaded_blocks, hit.computed_blocks) == (DOC_BLOCKS, QUESTION_BLOCKS)
    assert (hit2.loaded_blocks, hit2.computed_blocks) == (DOC_BLOCKS + 1, 1)

    # A miss of the same prompts on an engine whose store has none of it.
    ref, ref_rows = _harness(conn, params, f"resume-ref-{conn.shm_active}")
    miss, miss2 = asyncio.run(asyncio.wait_for(_drive(
        ref, [doc + question, other_doc + question[:bt]]
    ), 120))
    assert miss.loaded_blocks == 0 and miss2.loaded_blocks == 0
    assert hit.generated == miss.generated and hit2.generated == miss2.generated
    np.testing.assert_allclose(rows[1], ref_rows[0], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(rows[3], ref_rows[1], rtol=2e-4, atol=2e-4)

    # The mechanism's counters: what the requests sent imply.
    m = h.metrics()
    assert m["resumes"] == 2
    assert m["resume_tokens"] == (QUESTION_BLOCKS + 1) * bt
    assert m["resume_pages"] == (DOC_BLOCKS + QUESTION_BLOCKS) + (DOC_BLOCKS + 1 + 1)
    r = ref.metrics()
    assert (r["resumes"], r["resume_tokens"], r["resume_pages"]) == (0, 0, 0)

    # The compute span of a resume says how many pages its chunk attended.
    def compute_span(stats):
        (span,) = [
            s for s in traced.snapshot()
            if s["trace_id"] == stats.trace_id and s["name"] == "compute"
        ]
        return span["attrs"]

    attrs = compute_span(hit)
    assert attrs["kind"] == "chunked_resume" and not attrs["waits_for_device"]
    assert attrs["tokens"] == QUESTION_BLOCKS * bt
    assert attrs["pages"] == DOC_BLOCKS + QUESTION_BLOCKS
    assert compute_span(hit2)["pages"] == DOC_BLOCKS + 2
    full = compute_span(saved)
    assert full["kind"] == "prefill_full" and "pages" not in full


@pytest.mark.parametrize("form", ["xla", "pallas_interpret"])
def test_suffix_longer_than_a_row_tile_resumes_in_one_call(conn, monkeypatch, form):
    """A short shared prefix and a long fresh remainder: ``_chunked_resume``
    hands the whole suffix (here 1.3 row tiles of the kernel) to ONE
    ``prefill_continue`` call, and the request says what a miss says. Once
    with the form this backend dispatches to, once with the Pallas kernel
    (interpret mode) inside the engine's own program, where the suffix is
    cut into row tiles. Every case gets a config of its own (the
    vocabulary), so that none finds another's program in ``resume_chunk``'s
    cache."""
    bt = CFG.block_tokens
    suffix_blocks = (ca._TILE_ROWS + ca._TILE_ROWS // 3) // bt
    assert suffix_blocks * bt > ca._TILE_ROWS
    vocab = {"xla": 120, "pallas_interpret": 112}[form] - 4 * conn.shm_active
    cfg = dataclasses.replace(CFG, vocab=vocab)
    traced_layers = []
    if form == "pallas_interpret":
        kernel = functools.partial(ca._chunk_prefix_attention_pallas, interpret=True)

        def through_the_kernel(q, *rest):
            traced_layers.append(q.shape[0])
            return kernel(q, *rest)

        monkeypatch.setattr(llama, "chunk_prefix_attention", through_the_kernel)
    params = init_params(cfg, jax.random.PRNGKey(1))
    sizes = dict(cfg=cfg, num_blocks=96, max_req_blocks=DOC_BLOCKS + suffix_blocks + 1)
    doc = _tokens(4, DOC_BLOCKS, cfg.vocab)
    rest = _tokens(5, suffix_blocks, cfg.vocab)

    h, rows = _harness(conn, params, f"long-{form}-{conn.shm_active}", **sizes)
    saved, hit = asyncio.run(asyncio.wait_for(_drive(h, [doc, doc + rest]), 120))
    assert (hit.loaded_blocks, hit.computed_blocks) == (DOC_BLOCKS, suffix_blocks)
    m = h.metrics()
    assert (m["resumes"], m["resume_tokens"]) == (1, suffix_blocks * bt)
    assert m["resume_pages"] == DOC_BLOCKS + suffix_blocks
    if form == "pallas_interpret":  # traced once, a kernel call a layer
        assert traced_layers == [suffix_blocks * bt] * cfg.n_layers

    ref, ref_rows = _harness(conn, params, f"long-ref-{form}-{conn.shm_active}", **sizes)
    (miss,) = asyncio.run(asyncio.wait_for(_drive(ref, [doc + rest]), 120))
    assert miss.loaded_blocks == 0 and hit.generated == miss.generated
    np.testing.assert_allclose(rows[1], ref_rows[0], rtol=2e-4, atol=2e-4)
