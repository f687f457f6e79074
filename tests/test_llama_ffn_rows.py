"""A one-row wave's dense FFN rides the matrix unit's path.

``models/llama.py`` ``_ffn`` pads the activations of a ONE-row call with zero
rows up to ``ONE_ROW_FFN_ROWS`` and keeps row 0 (on the chip XLA lowers a
one-row product to a vector-unit multiply-and-reduce that reads ``w_gate_up
[dim, 2, ffn]`` at a third to two thirds of the rate the matrix-unit fusion of
a two-row wave reads it at: ``tools/ffn_rows_probe.py``, PERF.md section 6, PR
45). Nothing a clock says is tested here. What is: the padded row gives what
the row gave (the plain formula, and the same row inside a wave of several), a
lone request's wave equals its row in a fuller wave on logits and cache, the
traced one-row layer HOLDS the pad (a refactor cannot drop it in silence), two
rows and more trace what they traced, and the decoder counts the waves the
path engages on (``wave_one_row_waves``).
"""

import asyncio
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import infinistore_tpu as its
from infinistore_tpu.connector import KVConnector
from infinistore_tpu.engine import (
    ContinuousBatchingHarness,
    DeviceGate,
    EngineKVAdapter,
    WaveDecoder,
)
from infinistore_tpu.hostmesh import cpu_child_env
from infinistore_tpu.models import LlamaConfig, llama
from infinistore_tpu.tpu.paged_attention import build_ragged_wave

# Dense llama configurations whose FFN width is and is not a multiple of 512
# lanes' worth (DeepSeek's 11008 = 86 lane tiles is not, Mistral's 14336 is).
FFN_WIDTHS = [512, 344]
NUM_BLOCKS, MAX_REQ_BLOCKS = 32, 4


def config(ffn_dim, dtype=jnp.float32):
    return LlamaConfig(
        vocab=64, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=ffn_dim,
        block_tokens=8, dtype=dtype,
    )


def plain_ffn(params, x):
    """The dense FFN's formula on the rows as they come: no pad, no slice."""
    h = llama._rms_norm(x, params["l0.ffn_norm"])
    gate_up = jnp.einsum("bsd,dcf->bscf", h, params["l0.w_gate_up"])
    act = jax.nn.silu(gate_up[:, :, 0]) * gate_up[:, :, 1]
    return x + jnp.einsum("bsf,fd->bsd", act, params["l0.w_down"])


def assert_same(got, want, dtype, what):
    """float32: one row in two launch shapes agrees to float32 rounding, 32
    ulps of the largest value (tests/test_engine_harness.py). bfloat16: the
    2e-2 the kernel tests write down for it (tests/test_paged_attention.py)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if dtype == jnp.float32:
        rtol, atol = 0, 32 * np.finfo(np.float32).eps * float(np.max(np.abs(want)))
    else:
        rtol = atol = 2e-2
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


@pytest.mark.parametrize("ffn_dim", FFN_WIDTHS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_one_row_gives_what_the_row_gives_unpadded_and_inside_a_wave(dtype, ffn_dim):
    """``_ffn`` of one row (padded inside) against the plain formula on that
    row, and against row 0 of ``_ffn`` of the row stacked with a second (the
    path two rows and more take, which pads nothing)."""
    cfg = config(ffn_dim, dtype)
    params = llama.init_params(cfg, jax.random.PRNGKey(45))
    rows = jax.random.normal(jax.random.PRNGKey(ffn_dim), (1, 2, cfg.dim), jnp.float32)
    rows = rows.astype(dtype)
    one = llama._ffn(params, 0, rows[:, :1], cfg)
    assert one.shape == (1, 1, cfg.dim) and one.dtype == dtype
    assert_same(one, plain_ffn(params, rows[:, :1]), dtype, "against the plain formula")
    two = llama._ffn(params, 0, rows, cfg)
    assert two.shape == (1, 2, cfg.dim)
    assert_same(one[0, 0], two[0, 0], dtype, "against the row inside a wave of two")
    # Two rows are the formula as it stands, bit for bit.
    np.testing.assert_array_equal(
        np.asarray(two, np.float32), np.asarray(plain_ffn(params, rows), np.float32)
    )


def prefilled(cfg, prompt_lens, seed):
    """Caches holding one prefilled prompt a request, disjoint tables."""
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    tables = np.arange(len(prompt_lens) * MAX_REQ_BLOCKS, dtype=np.int32).reshape(
        len(prompt_lens), MAX_REQ_BLOCKS
    )
    caches = cfg.kv_spec(NUM_BLOCKS).make_caches()
    for n, tab in zip(prompt_lens, tables):
        prompt = rng.integers(0, cfg.vocab, size=n)
        _, caches = llama.prefill(
            params, jnp.asarray(prompt, jnp.int32), caches,
            jnp.asarray(tab[: n // cfg.block_tokens]), cfg,
        )
    return params, caches, tables


def wave(params, cfg, caches, tables, toks, positions):
    """``verify_step_ragged`` on one-token rows, one a request of ``tables``."""
    meta = build_ragged_wave(list(tables), np.asarray(positions) + 1, cfg.block_tokens)
    return llama.verify_step_ragged(
        params, jnp.asarray(toks, jnp.int32), jnp.asarray(positions, jnp.int32),
        jnp.arange(len(toks), dtype=jnp.int32), jnp.asarray(meta.pages),
        jnp.asarray(meta.page_rows), jnp.asarray(meta.page_starts), caches,
        jnp.asarray(tables), cfg, MAX_REQ_BLOCKS,
    )


@pytest.mark.parametrize("ffn_dim", FFN_WIDTHS)
def test_a_one_row_wave_equals_sequential_decode_and_its_row_in_a_fuller_wave(ffn_dim):
    """Three requests at unlike positions: each advanced ALONE by a one-row
    ``verify_step_ragged`` (the padded FFN) gives the logits and the cache of
    ``decode_step`` (its one-row view) and of the three-row wave (no pad),
    to float32 rounding."""
    cfg = config(ffn_dim)
    params, caches, tables = prefilled(cfg, (16, 8, 24), seed=45)
    toks, positions = [5, 9, 13], [16, 8, 24]
    together_logits, together = wave(
        params, cfg, jax.tree.map(jnp.copy, caches), tables, toks, positions
    )
    alone, stepped = jax.tree.map(jnp.copy, caches), caches
    for r in range(3):
        one_logits, alone = wave(
            params, cfg, alone, tables[r : r + 1], toks[r : r + 1], positions[r : r + 1]
        )
        step_logits, stepped = llama.decode_step(
            params, jnp.int32(toks[r]), jnp.int32(positions[r]), stepped,
            jnp.asarray(tables[r]), cfg, MAX_REQ_BLOCKS,
        )
        assert one_logits.shape == (1, cfg.vocab)
        np.testing.assert_allclose(
            np.asarray(one_logits[0]), np.asarray(step_logits), rtol=2e-5, atol=2e-5
        )
        np.testing.assert_allclose(
            np.asarray(one_logits[0]), np.asarray(together_logits[r]), rtol=2e-5, atol=2e-5
        )
    for layer, (a, s, t) in enumerate(zip(alone, stepped, together)):
        for kind in (0, 1):
            for other in (s, t):
                np.testing.assert_allclose(
                    np.asarray(a[kind]), np.asarray(other[kind]), rtol=2e-5, atol=2e-5,
                    err_msg=f"layer {layer} {'kv'[kind]}",
                )


def primitives(jaxpr, name):
    """Every equation named ``name`` in a jaxpr, nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(primitives(sub, name))
    return found


@pytest.mark.parametrize("rows", [1, 2, 4])
def test_the_traced_wave_layer_holds_the_pad_for_one_row_and_for_one_row_only(rows):
    """``_wave_layer`` traced at T rows: at T = 1 the gate/up ``dot_general``
    runs on ``ONE_ROW_FFN_ROWS`` rows, fed by a ``pad`` with a scalar, and
    so does ``w_down``'s; at 2 and 4 rows both run on T and nothing pads."""
    cfg = config(344)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    cache = jnp.zeros(cfg.kv_spec(NUM_BLOCKS).cache_shape, cfg.dtype)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    jaxpr = jax.make_jaxpr(functools.partial(llama._wave_layer, config=cfg))(
        llama._layer_weights(params, 0), jnp.zeros((1, rows, cfg.dim), cfg.dtype),
        i32(1, rows), cache, cache, i32(rows), i32(rows), i32(rows, MAX_REQ_BLOCKS),
        jnp.ones((rows,), jnp.int32), i32(rows), i32(rows + 1), i32(rows),
    ).jaxpr
    dots = [eqn.outvars[0].aval.shape for eqn in primitives(jaxpr, "dot_general")]
    ffn_rows = llama.ONE_ROW_FFN_ROWS if rows == 1 else rows
    assert llama.ONE_ROW_FFN_ROWS >= 2
    assert (1, ffn_rows, 2, cfg.ffn_dim) in dots, dots  # gate/up
    assert (1, ffn_rows, cfg.dim) in dots, dots  # w_down (and wo at T > 1)
    assert not any(shape[:2] == (1, 1) and shape[2:] == (2, cfg.ffn_dim) for shape in dots)
    pads = primitives(jaxpr, "pad")
    if rows == 1:
        (pad,) = pads
        assert pad.outvars[0].aval.shape == (1, ffn_rows, cfg.dim)
        # The row first, a constant behind it: no copy of the row rides along.
        assert pad.params["padding_config"] == ((0, 0, 0), (0, ffn_rows - 1, 0), (0, 0, 0))
        assert pad.invars[1].aval.shape == ()
    else:
        assert not pads


def test_the_probe_prints_a_line_a_form_of_the_documented_shape():
    """``tools/ffn_rows_probe.py`` at a toy width on this backend: one line a
    form, the host's time and the compiled text's facts in each (no device
    plane here, so no device time: a number of the chip is not made up)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    try:
        import ffn_rows_probe
    finally:
        sys.path.pop(0)
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "ffn_rows_probe.py"), "--dim", "64",
         "--ffn", "88", "--calls", "1", "--layers", "2"],
        capture_output=True, text=True, timeout=300, check=True,
        env=cpu_child_env(),
    )
    lines = [json.loads(ln) for ln in out.stdout.splitlines() if ln.startswith("{")]
    forms = [v[0] for v in ffn_rows_probe.VARIANTS] + ["llama_ffn_rows1", "llama_ffn_rows2"]
    assert [ln["variant"] for ln in lines] == forms
    for ln in lines:
        assert (ln["dim"], ln["ffn"], ln["layers"]) == (64, 88, 2)
        assert ln["host_ms_a_layer"] > 0 and ln["device"] == "cpu"
        assert set(ln["hlo"]) == {"gate_up", "convolution", "weight_copies"}
        assert "device_ms_a_layer" not in ln and "weights_gb_s" not in ln


# ---------------------------------------------------------------------------
# The counter: how often the path engages.
# ---------------------------------------------------------------------------


def bare_decoder(cfg, params, caches):
    """A WaveDecoder over a harness skeleton (no store), on a copy of
    ``caches``: every wave donates the cache it is handed."""
    h = ContinuousBatchingHarness.__new__(ContinuousBatchingHarness)
    h.params, h.config = params, cfg
    h.caches = jax.tree.map(jnp.copy, caches)
    h.max_req_blocks = MAX_REQ_BLOCKS
    h.gate = DeviceGate()
    return WaveDecoder(h)


def test_the_decoder_counts_the_waves_of_one_real_row_and_only_those():
    """A lone one-token chunk is a one-row wave; three requests' tokens in
    one wave are not, and neither is a lone request's chunk of three tokens
    (a drafter's): its wave has three flat rows."""
    cfg = config(344)
    params, caches, tables = prefilled(cfg, (16, 8, 16), seed=46)
    bt = cfg.block_tokens

    async def run():
        dec = bare_decoder(cfg, params, caches)
        seen = []
        await dec.step_chunk([5], [16], tables[0])
        seen.append((dec.waves, dec.one_row_waves))
        await asyncio.gather(
            dec.step_chunk([6], [17], tables[0]),
            dec.step_chunk([9], [8], tables[1]),
            dec.step_chunk([13], [16], tables[2]),
        )
        seen.append((dec.waves, dec.one_row_waves))
        await dec.step_chunk([1, 2, 3], [bt + 1 + j for j in range(3)], tables[1])
        seen.append((dec.waves, dec.one_row_waves))
        await dec.step_chunk([7], [18], tables[0])
        seen.append((dec.waves, dec.one_row_waves))
        return dec, seen

    dec, seen = asyncio.run(run())
    assert seen == [(1, 1), (2, 1), (3, 1), (4, 2)]
    # (table rows, flat rows, pages): the one-row bucket is the counted one.
    assert {(b, t) for b, t, _p in dec.bucket_sizes} == {(1, 1), (4, 4), (1, 4)}


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


def test_metrics_carry_the_one_row_waves_of_a_lone_request_and_of_a_crowd(conn):
    """``harness.metrics()["wave_one_row_waves"]``: every decode wave of a
    request served alone, fewer than every wave once requests decode
    together (what ``wave_one_row_share`` divides by ``waves``)."""
    cfg = config(344)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(47)
    prompts = [rng.integers(0, cfg.vocab, size=2 * cfg.block_tokens).tolist() for _ in range(3)]

    def harness(name):
        kvc = KVConnector(conn, cfg.kv_spec(NUM_BLOCKS), name, max_blocks=MAX_REQ_BLOCKS)
        return ContinuousBatchingHarness(
            EngineKVAdapter(kvc), params, cfg, NUM_BLOCKS, MAX_REQ_BLOCKS
        )

    lone = harness("ffn-rows-lone")
    assert lone.metrics()["wave_one_row_waves"] == 0
    asyncio.run(lone.run_request(prompts[0], gen_tokens=5))
    m = lone.metrics()
    assert m["decode_waves"] >= 4
    assert m["wave_one_row_waves"] == m["decode_waves"] == lone.wave.one_row_waves

    crowd = harness("ffn-rows-crowd")

    async def together():
        await asyncio.gather(*(crowd.run_request(p, gen_tokens=5) for p in prompts))

    asyncio.run(together())
    m = crowd.metrics()
    assert m["max_wave_size"] >= 2
    assert m["wave_one_row_waves"] < m["decode_waves"]
