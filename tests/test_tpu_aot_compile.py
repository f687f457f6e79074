"""Every Pallas kernel must COMPILE for a TPU v5e — checked with no chip.

The rest of the suite runs the kernels in Pallas interpret mode on the CPU,
which accepts block shapes Mosaic rejects (flash prefill's 132-row block for
a 264-token prompt went unnoticed that way). ``libtpu`` can compile for a
device it does not have: ``get_topology_desc("v5e:2x2")`` describes the
chips, and ``jit(...).trace(...).lower(lowering_platforms=("tpu",))
.compile()`` runs the real Mosaic / XLA:TPU compiler against them. So an
interpret-only kernel fails here, on the CPU box, before any chip time is
spent. Numerical agreement and donation stay questions for the chip
(``chip_smoke.py``).

Geometries: the smoke's (Llama-3-8B attention: 32 q / 8 kv heads, head_dim
128, 16-token blocks, bf16) and the two demo geometries ``bench.py`` and the
examples run (engine demo: 4 q / 2 kv heads, head_dim 32, 16-token blocks,
f32; disagg demo: head_dim 16, 8-token blocks, f32); for the resume of a
prefix hit also the benchmark's two reuse cells (``RESUMES``).
"""

import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("libtpu")

from jax.experimental import topologies  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from infinistore_tpu.tpu import chunk_attention as ca  # noqa: E402
from infinistore_tpu.tpu import flash_prefill as fp  # noqa: E402
from infinistore_tpu.tpu import kv_quant as kq  # noqa: E402
from infinistore_tpu.tpu import paged  # noqa: E402
from infinistore_tpu.tpu import paged_attention as pa  # noqa: E402

# (name, q heads, kv heads, head_dim, block_tokens, dtype)
GEOMETRIES = [
    ("smoke", 32, 8, 128, 16, jnp.bfloat16),
    ("engine_demo", 4, 2, 32, 16, jnp.float32),
    ("disagg_demo", 4, 2, 16, 8, jnp.float32),
]
NUM_BLOCKS = 128  # cache blocks
N_IDS = 16  # blocks per gather/scatter
ROWS = 8  # decode rows (a wave of 8)
TABLE = 16  # table entries per row
PAGES = 64  # flat ragged page list


@pytest.fixture(scope="module")
def v5e():
    topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


def _compile(jitted, *args, **static):
    """Compile for TPU; return the executable after checking the Mosaic
    kernel is really in it (not an XLA fallback that happens to compile)."""
    exe = (
        jitted.trace(*args, **static)
        .lower(lowering_platforms=("tpu",))
        .compile()
    )
    assert "tpu_custom_call" in exe.as_text(), "no Mosaic kernel in the program"
    return exe


def _param_shapes(s, cfg):
    """The model's parameters as shapes placed by ``s`` (nothing is made)."""
    from infinistore_tpu.models import llama

    return jax.tree.map(
        lambda x: s(x.shape, x.dtype),
        jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0))),
    )


def _shapes(v5e, geom):
    _, h, kvh, d, bt, dtype = geom
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    return {
        "cache": s((NUM_BLOCKS, bt, kvh, d), dtype),
        "ids": s((N_IDS,), jnp.int32),
        "blocks": s((N_IDS, bt, kvh, d), dtype),
        "q": s((ROWS, h, d), dtype),
        "tables": s((ROWS, TABLE), jnp.int32),
        "lens": s((ROWS,), jnp.int32),
        "pages": s((PAGES,), jnp.int32),
        "page_rows": s((PAGES + 1,), jnp.int32),
        "i8": s((NUM_BLOCKS, bt, kvh, d), jnp.int8),
        "scales": s((NUM_BLOCKS, bt, kvh), jnp.float32),
    }


@pytest.mark.parametrize("geom", GEOMETRIES, ids=[g[0] for g in GEOMETRIES])
def test_paged_kernels_compile(v5e, geom):
    a = _shapes(v5e, geom)
    _compile(paged._gather_blocks_pallas, a["cache"], a["ids"], interpret=False)
    exe = _compile(
        paged._scatter_blocks_pallas, a["cache"], a["ids"], a["blocks"],
        interpret=False,
    )
    # The scatter's whole point: the cache is updated in place.
    assert exe.memory_analysis().alias_size_in_bytes > 0
    ragged = (
        a["q"], a["cache"], a["cache"], a["pages"], a["page_rows"], a["lens"],
        a["lens"],
    )
    _compile(pa._paged_decode_attention_pallas_ragged, *ragged, interpret=False)
    _compile(
        pa._paged_decode_attention_pallas_ragged_stats, *ragged, interpret=False
    )
    # A rectangle rides the same kernel: full-width tables through the
    # in-jit metadata, as decode_step and the disagg decode layer pass them.
    _compile(
        jax.jit(
            lambda q, k, v, tables, lens: pa._paged_decode_attention_pallas_ragged(
                q, k, v, *pa.rectangle_as_ragged(tables), lens, interpret=False
            )
        ),
        a["q"], a["cache"], a["cache"], a["tables"], a["lens"],
    )
    _compile(
        kq._quant_decode_pallas, a["q"], a["i8"], a["scales"], a["i8"],
        a["scales"], a["tables"], a["lens"], interpret=False,
    )


@pytest.mark.parametrize("geom", GEOMETRIES, ids=[g[0] for g in GEOMETRIES])
@pytest.mark.parametrize("seq", [48, 264, 272, 1024])
def test_flash_prefill_compiles_at_any_prompt_length(v5e, geom, seq):
    """48: one short block; 264: the length the old divisor rule broke
    (bq=132); 272: the old rule's 136-row block, 8-aligned but not a bf16
    tile; 1024: the smoke's prompt."""
    _, h, kvh, d, _, dtype = geom
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    q = s((1, seq, h, d), dtype)
    kv = s((1, seq, kvh, d), dtype)
    _compile(
        fp._flash_prefill_pallas, q, kv, kv,
        causal=True, block_q=256, block_k=256, interpret=False,
    )


# The resume of a prefix hit (tpu/chunk_attention.py, models/llama.py
# resume_chunk) at the benchmark's two reuse cells (bf16, 16-token blocks,
# head_dim 128, a 128-token question: Mistral's 32/8 heads over a 524-entry
# table and a 1,024-block cache, DeepSeek's 32/32 over 140 and 320) and at
# the two demo geometries with a one-block chunk. ``LONG_RESUMES``: a short
# shared prefix and a long fresh remainder, as far as each cell's table
# allows (the kernel cuts the chunk into row tiles, so its VMEM is one
# tile's whatever the length: 2,048 rows in one block did not fit).
# (name, q heads, kv heads, head_dim, block_tokens, dtype, chunk, table, blocks)
RESUMES = [
    ("mistral_reuse", 32, 8, 128, 16, jnp.bfloat16, 128, 524, 1024),
    ("deepseek_reuse", 32, 32, 128, 16, jnp.bfloat16, 128, 140, 320),
    ("engine_demo", 4, 2, 32, 16, jnp.float32, 16, 16, NUM_BLOCKS),
    ("disagg_demo", 4, 2, 16, 8, jnp.float32, 8, 16, NUM_BLOCKS),
]
LONG_RESUMES = [
    ("mistral_reuse_2048", 32, 8, 128, 16, jnp.bfloat16, 2048, 524, 1024),
    ("mistral_reuse_8064", 32, 8, 128, 16, jnp.bfloat16, 8192 - 128, 524, 1024),
    ("deepseek_reuse_2048", 32, 32, 128, 16, jnp.bfloat16, 2048, 140, 320),
    ("deepseek_reuse_2100", 32, 32, 128, 16, jnp.bfloat16, 2100, 140, 320),
]


@pytest.mark.parametrize(
    "case", RESUMES + LONG_RESUMES, ids=[c[0] for c in RESUMES + LONG_RESUMES]
)
def test_chunk_prefix_attention_compiles(v5e, case):
    _, h, kvh, d, bt, dtype, chunk, table, blocks = case
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    cache = s((blocks, bt, kvh, d), dtype)
    _compile(
        ca._chunk_prefix_attention_pallas, s((chunk, h, d), dtype), cache, cache,
        s((table,), jnp.int32), s((), jnp.int32), interpret=False,
    )


def test_chunk_prefix_attention_compiles_for_a_draft_of_five(v5e):
    """``speculative_verify`` hands ``prefill_continue`` a draft of any
    length: the rows are padded to the dtype's sublane tile."""
    a = _shapes(v5e, GEOMETRIES[0])
    _, h, _, d, _, dtype = GEOMETRIES[0]
    q = jax.ShapeDtypeStruct((5, h, d), dtype, sharding=v5e)
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e)
    _compile(
        ca._chunk_prefix_attention_pallas, q, a["cache"], a["cache"],
        a["pages"], start, interpret=False,
    )


@pytest.mark.parametrize(
    "case", RESUMES + LONG_RESUMES[:1], ids=[c[0] for c in RESUMES + LONG_RESUMES[:1]]
)
def test_resume_program_compiles_with_its_kernel(v5e, monkeypatch, case):
    """``resume_chunk`` through the model's own dispatcher: one Mosaic call
    a layer in the program (one layer at the cell's attention widths: the
    layers are identical, 16 of them only compile for longer, and the FFN
    and the vocabulary, which the kernel never sees, are kept small)."""
    from infinistore_tpu.models import llama

    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    _, h, kvh, d, bt, dtype, chunk, table, blocks = case
    cfg = llama.LlamaConfig(
        vocab=1024, dim=h * d, n_layers=1, n_heads=h, n_kv_heads=kvh,
        ffn_dim=1024, block_tokens=bt, dtype=dtype,
    )
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    params = _param_shapes(s, cfg)
    cache = s(cfg.kv_spec(blocks).cache_shape, cfg.dtype)
    exe = _compile(
        jax.jit(llama.resume_chunk.__wrapped__, static_argnames=("config",)),
        params, s((chunk,), jnp.int32), s((), jnp.int32),
        [(cache, cache)] * cfg.n_layers, s((table,), jnp.int32),
        config=cfg,
    )
    assert exe.as_text().count("tpu_custom_call") >= cfg.n_layers


@pytest.mark.parametrize("view", ["decode_step", "decode_wave_layer"])
def test_rectangle_views_compile_with_the_ragged_kernel(v5e, monkeypatch, view):
    """``decode_step`` and the disagg ``decode_wave_layer`` through the
    model's own dispatcher at the smoke's attention widths (one layer, small
    FFN and vocabulary): one Mosaic call a layer, and it is the ragged
    decode kernel, fed the in-jit rectangle metadata."""
    from infinistore_tpu.models import llama

    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    _, h, kvh, d, bt, dtype = GEOMETRIES[0]
    cfg = llama.LlamaConfig(
        vocab=1024, dim=h * d, n_layers=1, n_heads=h, n_kv_heads=kvh,
        ffn_dim=1024, block_tokens=bt, dtype=dtype,
    )
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    params = _param_shapes(s, cfg)
    cache = s(cfg.kv_spec(NUM_BLOCKS).cache_shape, cfg.dtype)
    # Fresh jit wrappers, the wave body under decode_step included: a trace
    # taken under the CPU dispatch must not be reused, and this one must
    # not be left in the module's caches for a later test.
    fresh = lambda fn, *static: jax.jit(fn.__wrapped__, static_argnames=static)
    monkeypatch.setattr(
        llama, "verify_step_ragged",
        fresh(llama.verify_step_ragged, "config", "max_blocks"),
    )
    i32 = lambda *shape: s(shape, jnp.int32)
    if view == "decode_step":
        traced = fresh(llama.decode_step, "config", "max_blocks").trace(
            params, i32(), i32(), [(cache, cache)], i32(TABLE),
            config=cfg, max_blocks=TABLE,
        )
    else:
        traced = fresh(llama.decode_wave_layer, "config", "layer", "max_blocks").trace(
            params, s((ROWS, 2, cfg.dim), dtype), i32(ROWS, 2), cache, cache,
            i32(ROWS, TABLE), config=cfg, layer=0, max_blocks=TABLE,
        )
    lowered = traced.lower(lowering_platforms=("tpu",))
    assert 'kernel_name = "_ragged_attn_kernel"' in lowered.as_text()
    assert "tpu_custom_call" in lowered.compile().as_text()


# The benchmark's two configurations at the decode kernel (bf16, 16-token
# blocks, head_dim 128): Mistral's GQA 32/8, DeepSeek's MHA 32/32.
# (name, q heads, kv heads)
SERVING_HEADS = [("mistral", 32, 8), ("deepseek", 32, 32)]


def _count_primitive(jaxpr, name=None):
    """Equations named ``name`` (all of them if None) in ``jaxpr`` and every
    jaxpr under it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += name in (None, eqn.primitive.name)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_primitive(sub, name)
    return n


@pytest.mark.parametrize("heads", SERVING_HEADS, ids=[c[0] for c in SERVING_HEADS])
@pytest.mark.parametrize("twin", ["attend", "stats"])
def test_ragged_kernel_body_stays_small(heads, twin):
    """A guard on the program's size, with no clock in it. Every wave
    bucket a run warms (24 in the chat cell) traces, lowers and loads the
    decode kernel's body again, so what the body holds is paid a bucket in
    set-up: a kernel that unrolled its heads and pages cost one cell 6 s of
    ``setup_s`` and the PR its acceptance (PERF.md, PR 30). The body holds
    TWO ``dot_general`` whatever the heads and the pages a step — never
    more than the two a KV head of the kernel before — and its size does
    not follow the head count."""
    _, h, kvh = heads
    fn = (
        pa._paged_decode_attention_pallas_ragged if twin == "attend"
        else pa._paged_decode_attention_pallas_ragged_stats
    )
    s = jax.ShapeDtypeStruct
    cache = s((NUM_BLOCKS, 16, kvh, 128), jnp.bfloat16)
    i32 = lambda n: s((n,), jnp.int32)
    traced = fn.trace(
        s((ROWS, h, 128), jnp.bfloat16), cache, cache, i32(PAGES),
        i32(PAGES + 1), i32(ROWS), i32(ROWS), interpret=False,
    )
    (call,) = [e for e in traced.jaxpr.eqns if e.primitive.name == "pallas_call"]
    body = call.params["jaxpr"]
    assert _count_primitive(body, "dot_general") == 2 <= 2 * kvh
    # About a hundred equations (PR 31), the same for 8 KV heads and 32;
    # the per-head body it replaced held 138 and 420.
    assert _count_primitive(body) < 130, _count_primitive(body)
    # The step table is built by the program around the kernel: a few dozen
    # primitive equations, no nested function to lower.
    around = [e.primitive.name for e in traced.jaxpr.eqns]
    assert len(around) < 80 and "jit" not in around and "pjit" not in around, around


def _dots(jaxpr):
    """Every ``dot_general`` equation in ``jaxpr`` and the jaxprs under it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _dots(sub)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_ragged_kernel_precision_follows_the_operands_dtype(dtype):
    """No switch chooses the MXU passes: a bf16 cache under bf16 queries
    sends both dots native operands with float32 accumulation (default
    precision: one pass a product that is exact in float32), a float32
    cache asks ``Precision.HIGHEST`` of float32 operands, as before PR 31."""
    s = jax.ShapeDtypeStruct
    cache = s((NUM_BLOCKS, 16, 8, 128), dtype)
    i32 = lambda n: s((n,), jnp.int32)
    traced = pa._paged_decode_attention_pallas_ragged.trace(
        s((ROWS, 32, 128), dtype), cache, cache, i32(PAGES), i32(PAGES + 1),
        i32(ROWS), i32(ROWS), interpret=False,
    )
    (call,) = [e for e in traced.jaxpr.eqns if e.primitive.name == "pallas_call"]
    dots = list(_dots(call.params["jaxpr"]))
    assert len(dots) == 2
    highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    for dot in dots:
        assert dot.params["preferred_element_type"] == jnp.float32
        assert {v.aval.dtype for v in dot.invars} == {jnp.dtype(dtype)}
        if dtype == jnp.float32:
            assert dot.params["precision"] == highest
        else:
            assert dot.params["precision"] in (
                None, jax.lax.Precision.DEFAULT,
                (jax.lax.Precision.DEFAULT, jax.lax.Precision.DEFAULT),
            )


@pytest.mark.parametrize("entry", ["body", "packed"])
def test_wave_program_shares_one_layer_and_one_kernel_function(v5e, monkeypatch, entry):
    """One chat bucket of ``verify_step_ragged``, the model's wave body and
    the packed entry the decoder launches it through (8 rows, 1,024 flat pages,
    Mistral's attention widths, 16 layers; the FFN and the vocabulary, which
    the kernel never sees, kept small) lowered for a v5e: the 16 layers call
    ONE lowered layer function, which holds ONE function around the Mosaic
    kernel — the jits are what makes them share — under the name the
    benchmark's ``decode_attn_roofline`` finds the device op by. What the
    program holds once, a wave bucket's set-up pays once."""
    from infinistore_tpu.models import llama, serving

    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    # The packed entry is a module-level jit: a vocabulary of its own, so its
    # trace is taken here, under the TPU dispatch, and serves no other test.
    cfg = llama.LlamaConfig(
        vocab=1024 if entry == "body" else 1023, dim=4096, n_layers=16, n_heads=32,
        n_kv_heads=8, ffn_dim=1024, block_tokens=16, dtype=jnp.bfloat16,
    )
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    params = _param_shapes(s, cfg)
    cache = s(cfg.kv_spec(640).cache_shape, cfg.dtype)
    caches = [(cache, cache)] * cfg.n_layers
    i32 = lambda *shape: s(shape, jnp.int32)
    rows, pages, table = 8, 1024, 80
    if entry == "body":
        traced = jax.jit(
            llama.verify_step_ragged.__wrapped__,
            static_argnames=("config", "max_blocks"),
        ).trace(
            params, i32(rows), i32(rows), i32(rows), i32(pages), i32(pages + 1),
            i32(rows), caches, i32(rows, table), config=cfg, max_blocks=table,
        )
    else:
        layout = serving.WaveLayout(rows, rows, pages)
        traced = serving.verify_step_ragged.trace(
            params, i32(layout.size(table)), i32(serving.FEED_ROWS), caches, config=cfg,
            max_blocks=table, layout=layout,
        )
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    layers = re.findall(r"call @(_wave_layer\w*)\(", text)
    assert len(layers) == cfg.n_layers and len(set(layers)) == 1, layers
    calls = re.findall(r"call @(\w*paged_decode_attention_pallas_ragged\w*)\(", text)
    assert len(calls) == 1, calls
    assert text.count("tpu_custom_call") == 1
    assert text.count('kernel_name = "_ragged_attn_kernel"') == 1


# The benchmark's two dense FFN widths (hidden 4096): DeepSeek's 11008 is 86
# lane tiles, no multiple of 512; Mistral's 14336 is.
@pytest.mark.parametrize("ffn_dim", [11008, 14336])
def test_one_row_wave_reads_gate_up_on_the_matrix_unit(v5e, monkeypatch, ffn_dim):
    """A ONE-row bucket of the wave body at a cell's real FFN width (one
    layer, small vocabulary), compiled for a v5e: the gate/up product is a
    convolution on ``ONE_ROW_FFN_ROWS`` rows inside an output fusion (the
    matrix unit, as a two-row wave's is), not the loop fusion of the vector
    unit's multiply-and-reduce over ``w_gate_up [dim, 2, ffn]``, which read
    the weights at a third of HBM's peak at 11008 (PERF.md section 6, PR
    45); nor is ``w_gate_up`` copied whole."""
    from infinistore_tpu.models import llama

    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    cfg = llama.LlamaConfig(
        vocab=1024, dim=4096, n_layers=1, n_heads=32, n_kv_heads=8, ffn_dim=ffn_dim,
        block_tokens=16, dtype=jnp.bfloat16,
    )
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    cache = s(cfg.kv_spec(NUM_BLOCKS).cache_shape, cfg.dtype)
    i32 = lambda *shape: s(shape, jnp.int32)
    exe = _compile(
        jax.jit(
            llama.verify_step_ragged.__wrapped__, static_argnames=("config", "max_blocks")
        ),
        _param_shapes(s, cfg), i32(1), i32(1), i32(1), i32(PAGES), i32(PAGES + 1), i32(1),
        [(cache, cache)], i32(1, TABLE), config=cfg, max_blocks=TABLE,
    )
    text = exe.as_text()
    rows = llama.ONE_ROW_FFN_ROWS
    product = [
        line for line in text.splitlines()
        if "dcf->bscf/dot_general" in line and re.search(r" (fusion|convolution|reduce)\(", line)
    ]
    assert any(
        re.search(rf"bf16\[{rows},2,{ffn_dim}\]\S* convolution\(", line) for line in product
    ), product
    # The multiply-and-reduce was a kLoop fusion under the same op_name.
    assert all(" convolution(" in line or "kind=kOutput" in line for line in product), product
    entry = text[text.index("ENTRY"):]
    assert not re.search(rf"= bf16\[[\d,]*2,{ffn_dim}\]\S* copy\(", entry)


# The three serving entries as the module declares them (their donation is
# what is under test, so no fresh wrapper), each at a cell's attention widths
# and cache (bf16, 16-token blocks, head_dim 128), 4 layers, FFN and vocabulary
# kept small: one chat bucket of the wave body, the resume of a Mistral reuse
# hit, a 1,024-token miss in DeepSeek's cell.
# (entry, kv heads, cache blocks)
DONATING = [
    ("verify_step_ragged", 8, 640),
    ("packed_wave", 8, 640),  # serving.verify_step_ragged: what the decoder launches
    ("resume_chunk", 8, 1024),
    ("prefill", 32, 320),
]


@pytest.mark.parametrize("case", DONATING, ids=[c[0] for c in DONATING])
def test_serving_entries_update_the_cache_in_place(v5e, monkeypatch, case):
    """No clock: each entry compiled for the v5e holds an
    ``input_output_alias`` for EVERY cache tensor, the aliased bytes are the
    whole cache's, and no ``copy``, ``copy-start`` or ``slice-start`` in the
    program has the shape of one layer's K or V array or of a quarter of it.
    Undonated, this wave program holds 4 ``copy`` + 4 ``copy-start`` of
    ``bf16[640,16,8,128]`` and 28 ``slice-start`` of its quarters, the cache
    read and written whole every step (PERF.md, PR 34). (At Mistral's
    widths and these 4 layers XLA still stages 3 of a ``prefill``'s 8 donated
    tensors through VMEM, asynchronously and in quarters; at 16 layers it
    stages none. Hence DeepSeek's cell for that entry.)"""
    from infinistore_tpu.models import llama, serving

    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    entry, kvh, blocks = case
    # A vocabulary no other test uses: the module's own jitted entries trace
    # here, under the TPU dispatch, and never from or for another test.
    cfg = llama.LlamaConfig(
        vocab=1021 if entry != "packed_wave" else 1013, dim=4096, n_layers=4,
        n_heads=32, n_kv_heads=kvh, ffn_dim=1024, block_tokens=16, dtype=jnp.bfloat16,
    )
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    i32 = lambda *shape: s(shape, jnp.int32)
    params = _param_shapes(s, cfg)
    cache = s(cfg.kv_spec(blocks).cache_shape, cfg.dtype)
    caches = [(cache, cache)] * cfg.n_layers
    if entry == "verify_step_ragged":
        rows, pages, table = 8, 1024, 80
        args = (
            params, i32(rows), i32(rows), i32(rows), i32(pages), i32(pages + 1),
            i32(rows), caches, i32(rows, table),
        )
        static = {"config": cfg, "max_blocks": table}
    elif entry == "packed_wave":
        layout = serving.WaveLayout(rows=8, tables=8, pages=1024)
        args = (params, i32(layout.size(80)), i32(serving.FEED_ROWS), caches)
        static = {"config": cfg, "max_blocks": 80, "layout": layout}
    elif entry == "resume_chunk":
        args, static = (params, i32(128), i32(), caches, i32(524)), {"config": cfg}
    else:
        args, static = (params, i32(1024), caches, i32(64)), {"config": cfg}
    jitted = serving.verify_step_ragged if entry == "packed_wave" else getattr(llama, entry)
    exe = _compile(jitted, *args, **static)
    text = exe.as_text()
    header = text.split("\n", 1)[0]
    tensors = 2 * cfg.n_layers
    assert len(re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)", header)) == tensors, header
    cache_bytes = tensors * int(np.prod(cache.shape)) * 2
    assert exe.memory_analysis().alias_size_in_bytes == cache_bytes
    whole = ",".join(map(str, cache.shape))
    quarter = ",".join(map(str, (blocks // 4, *cache.shape[1:])))
    moved = re.findall(
        rf"^.* = [^=]*bf16\[(?:{whole}|{quarter})\][^=]* (?:copy|copy-start|slice-start)\(.*$",
        text, flags=re.M,
    )
    assert not moved, moved[:3]


def test_sharded_decode_compiles_for_four_chips(monkeypatch):
    """The sharded decode entry runs the stats kernel INSIDE shard_map; on
    the CPU it always took the XLA fallback, so nothing had ever asked
    jax's varying-axes typing about a pallas_call there (it refused: the
    kernel's out_shape must say which mesh axes it varies over). A wave of
    four rows, and the one-row wave a single long request is."""
    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    mesh = Mesh(np.array(topo.devices), ("sp",))
    _, h, kvh, d, bt, dtype = GEOMETRIES[0]
    per, n_local, rows, max_p = 128, 32, 4, 64

    def s(shape, dt, *spec):
        return jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, P(*spec)))

    cache = s((4 * per, bt, kvh, d), dtype, "sp", None, None, None)
    fn, _ = pa._sharded_ragged_decode_fn(mesh, "sp", n_local)
    meta = lambda n: s((4, n), jnp.int32, "sp", None)
    for r, pages in ((rows, max_p), (1, n_local)):
        _compile(
            fn, s((r, h, d), dtype, None, None, None), cache, cache,
            meta(pages), meta(pages + 1), meta(r), meta(r),
        )


@pytest.mark.slow
def test_full_width_steps_compile_and_fit_one_v5e(v5e, monkeypatch):
    """The smoke's two big programs — ``prefill`` (S=1024) and
    ``verify_step_ragged`` (a wave of 8 rows over 64-block requests) at
    Llama-3-8B widths, 8 layers, 2 GiB paged cache — compile for one v5e
    through the models' own dispatchers and fit its 16 GiB."""
    from infinistore_tpu.models import llama

    # The dispatchers look at the process's default backend (cpu here);
    # this test asks what they emit when that backend is the chip.
    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    cfg = llama.LlamaConfig(
        vocab=128256, dim=4096, n_layers=8, n_heads=32, n_kv_heads=8,
        ffn_dim=14336, rope_theta=500000.0, block_tokens=16,
        dtype=jnp.bfloat16,
    )
    num_blocks, req_blocks, rows, seq = 4096, 64, 8, 1024
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    params = _param_shapes(s, cfg)
    cache = s(cfg.kv_spec(num_blocks).cache_shape, cfg.dtype)
    caches = [(cache, cache)] * cfg.n_layers
    i32 = lambda *shape: s(shape, jnp.int32)

    def fits_one_chip(exe):
        m = exe.memory_analysis()
        live = (
            m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes
        )
        return live < 16 << 30

    # Fresh jit wrappers: the module-level ones cache traces by argument
    # shapes, and a trace taken under the CPU dispatch must not be reused.
    exe = _compile(
        jax.jit(llama.prefill.__wrapped__, static_argnames=("config",)),
        params, i32(seq), caches, i32(seq // cfg.block_tokens), config=cfg,
    )
    assert exe.as_text().count("tpu_custom_call") >= 3 * cfg.n_layers
    assert fits_one_chip(exe)

    pages = rows * req_blocks
    exe = _compile(
        jax.jit(
            llama.verify_step_ragged.__wrapped__,
            static_argnames=("config", "max_blocks"),
        ),
        params, i32(rows), i32(rows), i32(rows), i32(pages), i32(pages + 1),
        i32(rows), caches, i32(rows, req_blocks),
        config=cfg, max_blocks=req_blocks,
    )
    assert exe.as_text().count("tpu_custom_call") >= cfg.n_layers
    assert fits_one_chip(exe)


# The second model file's serving entries (models/afmoe.py): sliding and full
# layers in one stack, an expert layer in both its shapes. Attention at the
# configuration's widths (32 q / 4 kv heads x 128, window 2,048, 16-token
# blocks, bf16), 8 experts top-2 of a small width and a small vocabulary.
AFMOE_ENTRIES = ["verify_step_ragged", "packed_wave", "resume_chunk", "prefill"]


@pytest.mark.parametrize("entry", AFMOE_ENTRIES)
def test_afmoe_entries_compile_and_update_the_cache_in_place(v5e, monkeypatch, entry):
    """Each entry compiles for the v5e with its Mosaic kernels (the windowed
    attention kernels, the wave's expert kernel, the grouped matmul) and
    holds an ``input_output_alias`` for EVERY cache tensor, the aliased bytes
    the whole cache's: the donation ``llama.py``'s entries carry."""
    from infinistore_tpu.models import afmoe, serving

    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    cfg = afmoe.AfmoeConfig(
        vocab=1019 if entry != "packed_wave" else 1009, dim=2048, n_heads=32, n_kv_heads=4, head_dim=128, ffn_dim=512,
        moe_ffn_dim=256, n_experts=8, experts_per_token=2, sliding_window=2048,
        block_tokens=16, dtype=jnp.bfloat16,
    )
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    i32 = lambda *shape: s(shape, jnp.int32)
    shapes = jax.eval_shape(lambda k: afmoe.init_params(cfg, k), jax.random.key(0))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype), shapes)
    cache = s(cfg.kv_spec(640).cache_shape, cfg.dtype)
    caches = [(cache, cache)] * cfg.n_layers
    if entry == "verify_step_ragged":
        rows, pages, table = 4, 1024, 320
        args = (
            params, i32(rows), i32(rows), i32(rows), i32(pages), i32(pages + 1), i32(rows),
            caches, i32(rows, table),
        )
        windowed = (i32(rows * 129), i32(rows * 129 + 1), i32(rows))
        static = {"config": cfg, "max_blocks": table, "window_pages": windowed}
    elif entry == "packed_wave":  # the same bucket as the decoder launches it
        layout = serving.WaveLayout(rows=4, tables=4, pages=1024, window_pages=4 * 129)
        args = (params, i32(layout.size(320)), i32(serving.FEED_ROWS), caches)
        static = {"config": cfg, "max_blocks": 320, "layout": layout}
    elif entry == "resume_chunk":
        args, static = (params, i32(128), i32(), caches, i32(320)), {"config": cfg}
    else:
        args, static = (params, i32(4224), caches, i32(264)), {"config": cfg}
    jitted = serving.verify_step_ragged if entry == "packed_wave" else getattr(afmoe, entry)
    lowered = jitted.trace(*args, **static).lower(lowering_platforms=("tpu",))
    kernels = set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
    exe = lowered.compile()
    text = exe.as_text()
    assert "tpu_custom_call" in text
    tensors = 2 * cfg.n_layers
    header = text.split("\n", 1)[0]
    assert len(re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)", header)) == tensors, header
    assert exe.memory_analysis().alias_size_in_bytes == tensors * int(np.prod(cache.shape)) * 2
    want = {
        "verify_step_ragged": {"_ragged_attn_kernel", "_moe_wave_kernel"},
        "packed_wave": {"_ragged_attn_kernel", "_moe_wave_kernel"},
        "resume_chunk": {"_chunk_attn_kernel"},
        "prefill": {"_flash_kernel"},
    }[entry]
    assert want <= kernels, kernels
    if entry not in ("verify_step_ragged", "packed_wave"):
        assert kernels - want, kernels  # the grouped matmul's


# The sixth model file's serving entries (models/mellum.py) at the published
# widths of its configuration's file: hidden 2,304, 32 q / 4 kv heads x 128,
# 64 experts of width 896 = 7 x 128 (the wave kernel takes it whole, the
# grouped matmul tiles it), window 1,024, 16-token blocks, both rotations with
# every published constant; one period of the layer pattern and a small
# vocabulary, so that the four compile in a minute.
MELLUM_ENTRIES = ["verify_step_ragged", "packed_wave", "resume_chunk", "prefill"]


@pytest.mark.parametrize("entry", MELLUM_ENTRIES)
def test_mellum_entries_compile_at_published_widths_and_update_the_cache_in_place(v5e, monkeypatch, entry):
    """Each entry compiles for the v5e with its Mosaic kernels, holds an
    ``input_output_alias`` for EVERY cache tensor (the aliased bytes the whole
    cache's), and moves no array of a layer's K or V shape, or of a quarter
    of it, through a ``copy``, ``copy-start`` or ``slice-start``."""
    from infinistore_tpu.models import mellum, serving

    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmarks", "configs", "mellum2-12b-a2.5b.json")) as f:
        real = json.load(f)
    fields = {k: real[v] for k, v in real["program"]["fields"].items()}
    fields.update(
        vocab=1031 if entry != "packed_wave" else 1033, layer_types=real["layer_types"][:4]
    )
    cfg = mellum.MellumConfig(block_tokens=16, dtype=jnp.bfloat16, **fields)
    assert (cfg.dim, cfg.moe_ffn_dim, cfg.n_experts, cfg.sliding_window) == (2304, 896, 64, 1024)
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    i32 = lambda *shape: s(shape, jnp.int32)
    shapes = jax.eval_shape(lambda k: mellum.init_params(cfg, k), jax.random.key(0))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype), shapes)
    blocks = real["serving"]["cache_blocks"]  # 6,656: a small cache XLA stages through VMEM in quarters
    cache = s(cfg.kv_spec(blocks).cache_shape, cfg.dtype)
    caches = [(cache, cache)] * cfg.n_layers
    if entry == "verify_step_ragged":
        rows, pages, table = 4, 2048, 528
        args = (
            params, i32(rows), i32(rows), i32(rows), i32(pages), i32(pages + 1), i32(rows),
            caches, i32(rows, table),
        )
        windowed = (i32(rows * 65), i32(rows * 65 + 1), i32(rows))
        static = {"config": cfg, "max_blocks": table, "window_pages": windowed}
    elif entry == "packed_wave":  # the same bucket as the decoder launches it
        layout = serving.WaveLayout(rows=4, tables=4, pages=2048, window_pages=4 * 65)
        args = (params, i32(layout.size(528)), i32(serving.FEED_ROWS), caches)
        static = {"config": cfg, "max_blocks": 528, "layout": layout}
    elif entry == "resume_chunk":
        args, static = (params, i32(128), i32(), caches, i32(528)), {"config": cfg}
    else:
        args, static = (params, i32(8320), caches, i32(520)), {"config": cfg}
    jitted = serving.verify_step_ragged if entry == "packed_wave" else getattr(mellum, entry)
    lowered = jitted.trace(*args, **static).lower(lowering_platforms=("tpu",))
    kernels = set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
    exe = lowered.compile()
    text = exe.as_text()
    tensors = 2 * cfg.n_layers
    header = text.split("\n", 1)[0]
    assert len(re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)", header)) == tensors, header
    assert exe.memory_analysis().alias_size_in_bytes == tensors * int(np.prod(cache.shape)) * 2
    whole = ",".join(map(str, cache.shape))
    quarter = ",".join(map(str, (blocks // 4, *cache.shape[1:])))
    moved = re.findall(
        rf"^.* = [^=]*bf16\[(?:{whole}|{quarter})\][^=]* (?:copy|copy-start|slice-start)\(.*$",
        text, flags=re.M,
    )
    assert not moved, moved[:3]
    want = {
        "verify_step_ragged": {"_ragged_attn_kernel", "_moe_wave_kernel"},
        "packed_wave": {"_ragged_attn_kernel", "_moe_wave_kernel"},
        "resume_chunk": {"_chunk_attn_kernel"},
        "prefill": {"_flash_kernel"},
    }[entry]
    assert want <= kernels, kernels
    if entry not in ("verify_step_ragged", "packed_wave"):
        assert kernels - want, kernels  # the grouped matmul's


def _page_loops(text, heads, rows, vdim):
    """The ``while`` instructions of a compiled program whose carry holds the
    XLA page loop's running max / sum / accumulator (``mla.latent_chunk
    _attention_xla``: ``[H, S, 1]``, ``[H, S, 1]``, ``[H, S, v]`` float32)."""
    stat, acc = rf"f32\[{heads},{rows},1\]", rf"f32\[{heads},{rows},{vdim}\]"
    return re.findall(rf"^\s*%?[\w.\-]+ = \(.*{stat}.*{stat}.*{acc}.*\) while\(.*$", text, flags=re.M)


def _chunk_program(jitted, args, static):
    """(kernel names, compiled text, bytes of temporaries) of a chunk program."""
    lowered = jitted.trace(*args, **static).lower(lowering_platforms=("tpu",))
    kernels = set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
    exe = lowered.compile()
    return kernels, exe.as_text(), exe.memory_analysis().temp_size_in_bytes


def _with_the_page_loop(monkeypatch, jitted, args, static):
    """The same chunk program as the parent of PR 57 compiled it: the latent
    attention a loop over pages in plain XLA."""
    from infinistore_tpu.tpu import mla

    with monkeypatch.context() as m:
        m.setattr(mla, "latent_chunk_attention", mla.latent_chunk_attention_xla)
        jax.clear_caches()
        try:
            return _chunk_program(jitted, args, static)
        finally:
            jax.clear_caches()


# The seventh model file's serving entries (models/glm_dsa.py) at the published
# widths of its configuration's file: hidden 6,144, 64 heads over a query
# latent of 2,048 and a latent cache of 512 + 64, an indexer of 32 x 128 that
# keeps 2,048 positions, 16 of 256 experts of width 2,048, pages of 1,024
# tokens, a table of 33 blocks; one dense and one expert layer and a small
# vocabulary, so that the four compile in a minute.
GLM_ENTRIES = ["verify_step_ragged", "packed_wave", "miss-piece", "hit-question"]


@pytest.mark.parametrize("entry", GLM_ENTRIES)
def test_glm_dsa_entries_compile_at_published_widths_and_update_both_cache_tensors_in_place(v5e, monkeypatch, entry):
    """Each entry compiles for the v5e with its Mosaic kernels (the scoring
    pass, the selection, the masked latent decode; a piece's latent attention
    and grouped matmul, and no loop over pages),
    holds an ``input_output_alias`` for BOTH tensors of every layer (the
    aliased bytes the whole cache's), and moves no array of a latent or an
    index cache's shape through a ``copy``, ``copy-start`` or ``slice-start``."""
    from infinistore_tpu.models import glm_dsa, serving

    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmarks", "configs", "glm-5.json")) as f:
        real = json.load(f)
    fields = {k: real[v] for k, v in real["program"]["fields"].items()}
    fields.update(vocab=1031 if entry != "packed_wave" else 1033, n_layers=2)
    cfg = glm_dsa.GlmDsaConfig(block_tokens=real["serving"]["block_tokens"], dtype=jnp.bfloat16, **fields)
    assert (cfg.dim, cfg.n_heads, cfg.latent_width, cfg.index_topk, cfg.held) == (6144, 64, 576, 2048, (0, 16))
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    i32 = lambda *shape: s(shape, jnp.int32)
    shapes = jax.eval_shape(lambda k: glm_dsa.init_params(cfg, k), jax.random.key(0))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype), shapes)
    blocks, table = real["serving"]["cache_blocks"], 33
    spec = cfg.kv_spec(blocks)
    caches = [
        tuple(s((blocks, *t.block_shape), t.dtype) for t in spec.layer_tensors(layer))
        for layer in range(cfg.n_layers)
    ]
    if entry == "verify_step_ragged":
        rows = 4
        args = (
            params, i32(rows), i32(rows), i32(rows), i32(128), i32(129), i32(rows), caches,
            i32(rows, table),
        )
        jitted, static = glm_dsa.verify_step_ragged, {"config": cfg, "max_blocks": table}
    elif entry == "packed_wave":  # the same bucket as the decoder launches it
        layout = serving.WaveLayout(rows=4, tables=4, pages=128)
        args = (params, i32(layout.size(table)), i32(serving.FEED_ROWS), caches)
        jitted = serving.verify_step_ragged
        static = {"config": cfg, "max_blocks": table, "layout": layout}
    else:
        rows = 1024 if entry == "miss-piece" else 127
        args, static = (params, i32(rows), i32(), caches, i32(table)), {"config": cfg}
        jitted = glm_dsa.resume_chunk
    lowered = jitted.trace(*args, **static).lower(lowering_platforms=("tpu",))
    kernels = set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
    exe = lowered.compile()
    text = exe.as_text()
    header = text.split("\n", 1)[0]
    assert len(re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)", header)) == 2 * cfg.n_layers, header
    assert exe.memory_analysis().alias_size_in_bytes == cfg.n_layers * blocks * (1152 + 256) * 1024
    if entry in ("miss-piece", "hit-question"):
        # The chunk's latent attention is the kernel: no loop over pages is
        # left in the program (no clock: the carry's shapes name it), and the
        # page loop's score tensors are not among the temporaries.
        assert "_chunk_kernel" in kernels, kernels
        assert text.count("mla_chunk_attention_pallas") >= cfg.n_layers
        assert not _page_loops(text, cfg.n_heads, rows, cfg.v_head_dim)
        if entry == "miss-piece":
            was, loop_text, loop_temp = _with_the_page_loop(monkeypatch, jitted, args, static)
            assert "_chunk_kernel" not in was
            assert _page_loops(loop_text, cfg.n_heads, rows, cfg.v_head_dim)
            # 64 x 1,024 x 1,024 float32 is 268 MB a score tensor, but XLA had
            # laid the loop's over the buffers of the selection (its scores and
            # bias, 276 MB, are the program's peak with or without an
            # attention): the loop stood 88.6 MB above that floor, the kernel
            # stands on it.
            assert loop_temp - exe.memory_analysis().temp_size_in_bytes >= 64 << 20
    moved = re.findall(
        rf"^.* = [^=]*bf16\[{blocks},(?:576|128),1024\][^=]* (?:copy|copy-start|slice-start)\(.*$",
        text, flags=re.M,
    )
    # Since PR 57 no loop stands between a piece's layers, and XLA's memory
    # space assignment PREFETCHES index pages into its fast memory (a result
    # in ``S(1)``) under the attention kernel: a read the scoring pass would
    # have made, not a cache laid out again.
    moved = [m for m in moved if "S(1)}" not in re.split(r" (?:copy|copy-start|slice-start)\(", m)[0]]
    assert not moved, moved[:3]
    if entry in ("verify_step_ragged", "packed_wave"):
        want = {"_index_decode_kernel", "_select_kernel", "_sparse_decode_kernel", "_moe_wave_kernel"}
    else:
        want = {"_index_chunk_kernel", "_select_kernel", "_chunk_kernel"}
        assert kernels - want, kernels  # the grouped matmul's
    assert want <= kernels, kernels


# The ninth model file's serving entries (models/pangu_mtp.py) at the published
# widths of its configuration's file: hidden 7,680, 128 heads over a query
# latent of 1,536 and a latent cache of 512 + 64, 8 of 256 experts of width
# 2,048, pages of 1,024 tokens, a table of 33 blocks; one dense and one expert
# layer, the MTP layer and a small vocabulary. The wave is ONE program, the
# main stack and the drafter behind it, in the buckets the decoder launches
# for one request's chunk of two and for three's (8 rows, 4 tables).
PANGU_ENTRIES = ["packed_wave", "packed_wave_one_entry", "miss-piece", "hit-question"]


def test_the_boundary_row_passes_the_block_copy_kernels(v5e):
    """A save's gather and an install's scatter take the MTP layer's boundary
    rows as the cache keeps them, folded to 128 lanes (``[blocks, 60, 128]``
    at a hidden size of 7,680): a block whose last two axes are whole. The
    row unfolded, ``[blocks, 7680]``, is refused by Mosaic (a block of ONE
    row of 160), which the cell's first chip run found (PERF.md, PR 62)."""
    from infinistore_tpu.models import pangu_mtp

    cfg = pangu_mtp.PanguMtpConfig(dim=7680, dtype=jnp.bfloat16)
    assert cfg.boundary_shape == (60, 128)
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    cache, ids = s((160, *cfg.boundary_shape), jnp.bfloat16), s((8,), jnp.int32)
    _compile(paged._gather_blocks_pallas, cache, ids, interpret=False)
    exe = _compile(
        paged._scatter_blocks_pallas, cache, ids, s((8, *cfg.boundary_shape), jnp.bfloat16),
        interpret=False,
    )
    assert exe.memory_analysis().alias_size_in_bytes == 160 * 7680 * 2
    with pytest.raises(Exception, match="divisible by 8 and 128"):
        _compile(paged._gather_blocks_pallas, s((160, 7680), jnp.bfloat16), ids, interpret=False)


@pytest.mark.parametrize("entry", PANGU_ENTRIES)
def test_pangu_mtp_entries_compile_at_published_widths_and_update_every_cache_tensor_in_place(v5e, monkeypatch, entry):
    """Each entry compiles for the v5e with its Mosaic kernels (the latent
    decode and the wave's expert product; a piece's latent attention and
    grouped matmul), holds an ``input_output_alias`` for every cache tensor
    (the main layers' latents, the MTP layer's and its boundary rows: the
    aliased bytes the whole cache's), and moves no array of a latent cache's
    shape through a ``copy``, ``copy-start`` or ``slice-start``. The wave runs
    one latent decode a main layer and ONE more for the drafter, and hands
    back the ids over the drafts as one array; a prompt piece runs no kernel
    for the MTP layer at all (it writes slots)."""
    from infinistore_tpu.models import pangu_mtp, serving

    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmarks", "configs", "openpangu-ultra-moe-718b.json")) as f:
        real = json.load(f)
    fields = {k: real[v] for k, v in real["program"]["fields"].items()}
    fields.update(vocab=1031, n_layers=2)
    cfg = pangu_mtp.PanguMtpConfig(
        block_tokens=real["serving"]["block_tokens"], dtype=jnp.bfloat16, **fields
    )
    assert (cfg.dim, cfg.n_heads, cfg.q_lora_rank, cfg.latent_width, cfg.held) == (7680, 128, 1536, 576, (0, 8))
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    i32 = lambda *shape: s(shape, jnp.int32)
    shapes = jax.eval_shape(lambda k: pangu_mtp.init_params(cfg, k), jax.random.key(0))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype), shapes)
    blocks, table = real["serving"]["cache_blocks"], 33
    spec = cfg.kv_spec(blocks)
    caches = [
        tuple(s((blocks, *t.block_shape), t.dtype) for t in spec.layer_tensors(layer))
        for layer in range(spec.num_layers)
    ]
    tensors = cfg.n_layers + 2
    wave = entry.startswith("packed_wave")
    if wave:
        layout = (
            serving.WaveLayout(rows=8, tables=4, pages=256) if entry == "packed_wave"
            else serving.WaveLayout(rows=2, tables=1, pages=64)
        )
        args = (params, i32(layout.size(table)), i32(serving.feed_rows(drafts=True)), caches)
        jitted = serving.verify_step_ragged
        static = {"config": cfg, "max_blocks": table, "layout": layout}
    else:
        rows = 1024 if entry == "miss-piece" else 127
        args = (params, i32(rows), i32(), caches, i32(table))
        jitted, static = pangu_mtp.resume_chunk, {"config": cfg, "next_token": i32()}
    lowered = jitted.trace(*args, **static).lower(lowering_platforms=("tpu",))
    kernels = set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
    exe = lowered.compile()
    text = exe.as_text()
    header = text.split("\n", 1)[0]
    assert len(re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)", header)) == tensors, header
    whole = blocks * ((cfg.n_layers + 1) * 1152 * 1024 + cfg.dim * 2)
    assert exe.memory_analysis().alias_size_in_bytes == whole
    moved = re.findall(
        rf"^.* = [^=]*bf16\[{blocks},576,1024\][^=]* (?:copy|copy-start|slice-start)\(.*$",
        text, flags=re.M,
    )
    moved = [m for m in moved if "S(1)}" not in re.split(r" (?:copy|copy-start|slice-start)\(", m)[0]]
    assert not moved, moved[:3]
    if wave:
        assert kernels == {"_decode_kernel", "_moe_wave_kernel"}, kernels
        assert len(re.findall(r"^\s*%?[\w.\-]*mla_decode_pallas[\w.\-]* = .*custom-call\(", text, flags=re.M)) == cfg.n_layers + 1
        ids = jax.eval_shape(functools.partial(jitted, **static), *args)[2]
        assert (ids.shape, ids.dtype) == ((2, layout.rows), jnp.int32)
    else:
        assert "_chunk_kernel" in kernels and kernels - {"_chunk_kernel"}, kernels  # + the grouped matmul's
        assert text.count("mla_chunk_attention_pallas") >= cfg.n_layers
        assert not _page_loops(text, cfg.n_heads, rows, cfg.v_head_dim)


# The third model file's chunk program (models/kimi_linear.py) at the published
# widths of its configuration's file, one KDA and one latent layer and a small
# vocabulary: the same kernel at 32 heads of 128 / 64 / 128 and NO bias.
@pytest.mark.parametrize("rows", [1024, 127], ids=["miss-piece", "hit-question"])
def test_kimi_linear_chunk_program_attends_through_the_kernel_and_no_page_loop(v5e, monkeypatch, rows):
    """``kimi_linear.resume_chunk`` compiles for the v5e with the chunk's
    latent attention as ``_chunk_kernel`` (the one ``glm_dsa`` runs under a
    bias), no ``while`` whose carry is the page loop's, and for a whole block
    temporaries smaller than the page loop's program's by a score tensor's
    part that XLA had not overlaid (32 x 1,024 x 1,024 float32 is 134 MB)."""
    from infinistore_tpu.models import kimi_linear as kl

    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmarks", "configs", "kimi-linear-48b-a3b.json")) as f:
        real = json.load(f)
    fields = {k: real[v] for k, v in real["program"]["fields"].items()}
    group = dict(fields.pop("linear_attn"), kda_layers=[1], full_attn_layers=[2])
    fields.update(vocab=1033, n_layers=2, linear_attn=group, route_tail=0)
    cfg = kl.KimiLinearConfig(block_tokens=real["serving"]["block_tokens"], dtype=jnp.bfloat16, **fields)
    assert (cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        32, 512, 128, 64, 128
    )
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    i32 = lambda *shape: s(shape, jnp.int32)
    shapes = jax.eval_shape(lambda k: kl.init_params(cfg, k), jax.random.key(0))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype), shapes)
    blocks, table = 40, 33
    spec = cfg.kv_spec(blocks)
    caches = [
        tuple(s((blocks, *t.block_shape), t.dtype) for t in spec.layer_tensors(layer))
        for layer in range(cfg.n_layers)
    ]
    args, static = (params, i32(rows), i32(), caches, i32(table)), {"config": cfg}
    kernels, text, temp = _chunk_program(kl.resume_chunk, args, static)
    assert "_chunk_kernel" in kernels, kernels
    assert "mla_chunk_attention_pallas" in text
    assert not _page_loops(text, cfg.n_heads, rows, cfg.v_head_dim)
    if rows == 1024:
        was, loop_text, loop_temp = _with_the_page_loop(monkeypatch, kl.resume_chunk, args, static)
        assert "_chunk_kernel" not in was
        assert _page_loops(loop_text, cfg.n_heads, rows, cfg.v_head_dim)
        assert loop_temp > temp, (loop_temp, temp)


# The grouped expert product under the tiles its rule hands it (models/afmoe.py
# ``_gmm_tiling``), at the published widths of the routed configurations'
# files and the token counts the benchmark's traffic gives them: one expert
# layer's three products alone, so that each compiles in seconds.
GROUPED_FFNS = [
    ("kimi-linear-48b-a3b", "miss-piece", 1024),
    ("kimi-linear-48b-a3b", "hit-question", 128),
    ("mellum2-12b-a2.5b", "hit-question", 128),
    ("mellum2-12b-a2.5b", "miss-chunk", 4224),
    ("granite-4.0-h-small", "miss-piece", 2048),
    ("trinity-mini", "miss-chunk", 5504),
]


@pytest.mark.parametrize("case", GROUPED_FFNS, ids=[f"{c[0]}-{c[1]}" for c in GROUPED_FFNS])
def test_grouped_ffn_compiles_under_its_tile_rule(v5e, monkeypatch, case):
    """``_grouped_ffn`` compiles for the v5e with three grouped matmuls whose
    tiles are whole divisors of the padded pair count and of both widths, K
    whole (VMEM holds them: the compiler refuses a triple it cannot place)."""
    import importlib

    from jax.experimental.pallas.ops.tpu import megablox

    from infinistore_tpu.tpu import moe

    name, _, tokens = case
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmarks", "configs", f"{name}.json")) as f:
        real = json.load(f)
    module, _, cls = real["program"]["config_class"].partition(":")
    fields = {k: real[v] for k, v in real["program"]["fields"].items()}
    cfg = getattr(importlib.import_module(module), cls)(
        block_tokens=real["serving"]["block_tokens"], dtype=jnp.bfloat16, **fields
    )
    seen, gmm = [], megablox.gmm

    def recorded(lhs, rhs, group_sizes, **kw):
        seen.append((lhs.shape, rhs.shape, kw["tiling"]))
        return gmm(lhs, rhs, group_sizes, **kw)

    monkeypatch.setattr(megablox, "gmm", recorded)
    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    k, held = cfg.experts_per_token, cfg.held[1]
    w = {
        "w_gate": s((held, cfg.dim, cfg.moe_ffn_dim), jnp.bfloat16),
        "w_up": s((held, cfg.dim, cfg.moe_ffn_dim), jnp.bfloat16),
        "w_down_moe": s((held, cfg.moe_ffn_dim, cfg.dim), jnp.bfloat16),
    }
    exe = _compile(
        jax.jit(moe._grouped_ffn, static_argnames=("config",)),
        s((tokens, cfg.dim), jnp.bfloat16), s((tokens, k), jnp.int32),
        s((tokens, k), jnp.float32), w, config=cfg,
    )
    assert exe.as_text().count("tpu_custom_call") >= 3
    assert len(seen) == 3
    for (m, kk), (_, _, n), (tm, tk, tn) in seen:
        assert tm == 128 and m % tm == 0 and kk == tk and n % tn == 0, seen


# The eighth model file's serving entries (models/sambay.py) at the published
# widths of its configuration's file: hidden 2,560, 40 q / 20 kv heads of 64
# (20 query pairs over 10 K/V pairs of 128), MLP 10,240, Mamba-1 mixers of
# 5,120 channels x 16 states, window 512, the file's blocks; TWO periods of the
# layer pattern (m s m s m F g c) and a small vocabulary, so that each
# compiles in seconds.
SAMBAY_ENTRIES = ["verify_step_ragged", "packed_wave", "packed_wave_one_row", "miss-piece", "hit-question"]


def _sambay_config(entry):
    from infinistore_tpu.models import sambay

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmarks", "configs", "phi-4-mini-flash-reasoning.json")) as f:
        real = json.load(f)
    fields = {k: real[v] for k, v in real["program"]["fields"].items()}
    fields.update(vocab=1031 if not entry.startswith("packed") else 1033, n_layers=8)
    cfg = sambay.SambaYConfig(block_tokens=real["serving"]["block_tokens"], dtype=jnp.bfloat16, **fields)
    assert (cfg.dim, cfg.pair_dim, cfg.kv_pairs, cfg.ssm_width, cfg.sliding_window) == (2560, 128, 10, 5120, 512)
    return real, cfg


@pytest.mark.parametrize("entry", SAMBAY_ENTRIES)
def test_sambay_entries_compile_at_published_widths_and_update_every_cache_tensor_in_place(v5e, monkeypatch, entry):
    """Each entry compiles for the v5e with its Mosaic kernels (a piece: the
    selective scan and the flash kernel's band, no page walk; a wave: the
    ragged decode kernel over 2,048-token pages of ten 128-wide heads), holds
    an ``input_output_alias`` for EVERY cache tensor (the aliased bytes the
    whole cache's), and moves no array of a cache tensor's shape through a
    ``copy``, ``copy-start`` or ``transpose``."""
    from infinistore_tpu.models import sambay, serving

    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    real, cfg = _sambay_config(entry)
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    i32 = lambda *shape: s(shape, jnp.int32)
    shapes = jax.eval_shape(lambda k: sambay.init_params(cfg, k), jax.random.key(0))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype), shapes)
    blocks = real["serving"]["cache_blocks"]
    spec = cfg.kv_spec(blocks)
    caches = [
        tuple(s((blocks, *t.block_shape), t.dtype) for t in spec.layer_tensors(layer))
        for layer in range(spec.num_layers)
    ]
    table = -(-(32768 + 128 + 64) // cfg.block_tokens)
    if entry == "verify_step_ragged":
        rows, pages = 4, 64
        jitted = sambay.verify_step_ragged
        args = (
            params, i32(rows), i32(rows), i32(rows), i32(pages), i32(pages + 1), i32(rows),
            caches, i32(rows, table),
        )
        static = {"config": cfg, "max_blocks": table}
    elif entry.startswith("packed_wave"):  # the buckets as the decoder launches them
        rows = 1 if entry.endswith("one_row") else 4
        layout = serving.WaveLayout(rows=rows, tables=rows, pages=16 * rows)
        jitted = serving.verify_step_ragged
        args = (params, i32(layout.size(table)), i32(serving.FEED_ROWS), caches)
        static = {"config": cfg, "max_blocks": table, "layout": layout}
    else:
        rows = cfg.block_tokens if entry == "miss-piece" else 127
        jitted, args, static = sambay.resume_chunk, (params, i32(rows), i32(), caches, i32(table)), {"config": cfg}
    lowered = jitted.trace(*args, **static).lower(lowering_platforms=("tpu",))
    kernels = set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
    exe = lowered.compile()
    text = exe.as_text()
    tensors = [t for layer in caches for t in layer]
    header = text.split("\n", 1)[0]
    assert len(re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)", header)) == len(tensors), header
    nbytes = sum(int(np.prod(t.shape)) * jnp.dtype(t.dtype).itemsize for t in tensors)
    assert exe.memory_analysis().alias_size_in_bytes == nbytes
    whole = "|".join(sorted({",".join(map(str, t.shape)) for t in tensors}))
    moved = re.findall(
        rf"^.* = [^=]*(?:bf16|f32)\[(?:{whole})\][^=]* (?:copy|copy-start|transpose)\(.*$", text, flags=re.M,
    )
    assert not moved, moved[:3]
    want = {"_ragged_attn_kernel"} if "piece" not in entry and "question" not in entry else {"_scan_kernel", "_flash_kernel"}
    assert kernels == want, kernels


@pytest.mark.parametrize("rows", [2048, 127], ids=["miss-piece", "hit-question"])
def test_the_selective_scan_kernel_compiles_at_the_published_widths(v5e, rows):
    from infinistore_tpu.tpu import selective_scan

    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    c, n, f32 = 5120, 16, jnp.float32
    _compile(
        selective_scan.selective_scan_pallas,
        s((rows, c), jnp.bfloat16), s((rows, c), f32), s((c, n), f32), s((rows, n), f32), s((rows, n), f32),
        s((c,), f32), s((n, c), f32),
    )


def test_a_five_mib_page_passes_the_block_copy_kernels(v5e):
    """A 2,048-token page of ten 128-wide heads is 5 MiB: a grid step's four
    blocks pass Mosaic's 16 MiB of scoped VMEM, so the copy kernels ask for
    their own limit (``paged._copy_params``); a 4 MiB block asks for none."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    assert paged._copy_params(s((80, 16384, 128), jnp.bfloat16)) == {}
    cache, ids = s((80, 20480, 128), jnp.bfloat16), s((17,), jnp.int32)
    assert paged._copy_params(cache)["compiler_params"].vmem_limit_bytes == 24 << 20
    _compile(paged._gather_blocks_pallas, cache, ids, interpret=False)
    exe = _compile(paged._scatter_blocks_pallas, cache, ids, s((17, 20480, 128), jnp.bfloat16), interpret=False)
    assert exe.memory_analysis().alias_size_in_bytes > 0
