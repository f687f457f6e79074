"""On the chip: each Pallas kernel of ``infinistore_tpu/tpu/dsa.py``, and the
chunk's latent attention of ``tpu/mla.py``, against its XLA twin at the shapes
``benchmarks/configs/glm-5.json`` gives them, then the model's chunk and wave
programs with the kernels on against the same programs on the XLA paths, over
the same weights and cache.

Tier-1 holds the kernels in interpret mode at toy shapes
(``tests/test_glm_dsa.py``) and compiles them for a v5e
(``tests/test_tpu_aot_compile.py``); what only the chip shows is whether the
COMPILED kernels compute what their twins do at 33 pages of 1,024 tokens: the
scoring pass of a wave and of a piece (relative rms error over the valid
positions), the k-th-value search against the sort as SETS (``lax.top_k``'s,
ties by position: equal or not, nothing between; then the selection ALONE at
the three shapes the cell's trace names it by, a piece's 1,024 rows, a
question's 128 and a wave's 8 at the end of 8 / 16 / 33 pages of scores of
the configuration's form: device milliseconds a call, the counting passes a
row tile made and the sets' equality, the probe ISSUE 61 asks for before any
cell is run; in a ``git archive`` of a tree before PR 61 the same rows
without the passes), the latent decode under a
selection's bias, the chunk's latent attention (``mla_chunk_attention_pallas``
against the page loop it replaced, a block's rows over 1 / 8 / 32 pages with a
selection's bias and without, a 127-row question, and the same kernel at
``kimi-linear-48b-a3b``'s head sizes; both sides TIMED, the milliseconds a
page-step printed: the probe ISSUE 57 asks for before any cell is run), and a
model of three published-width layers through three whole pieces, a part piece
and one wave.

    chiprun --chips 1 -- python3 tools/dsa_kernel_check.py
    chiprun --chips 1 -- python3 tools/dsa_kernel_check.py --sections select   # ~1 min

One line a check, then ``{"ok": ...}``; exit code 1 unless the kernels' sets
are equal and their errors under ``--tol`` (default 0.02: bf16 products summed
in another order; the chunk's attention under ``--chunk-tol``, 0.0015: what the
masked decode reads). The model's lines are reported and not held: two scoring
passes that sum in another order part a row's near-ties, and the places in
which the wave's sets then differ are printed beside the logits. Needs a TPU:
the kernels have no compiled form elsewhere.
"""

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "benchmarks")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import trace_reduce  # noqa: E402
from infinistore_tpu.models import glm_dsa  # noqa: E402
from infinistore_tpu.tpu import dsa, mla, paged  # noqa: E402

BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2)) / (np.sqrt(np.mean(b**2)) + 1e-30))


def kernels(real: dict, key) -> dict:
    """The four kernels alone, a wave of four rows over 33 pages and a piece
    of a block's rows over four."""
    bt, blocks = real["serving"]["block_tokens"], real["serving"]["cache_blocks"]
    hi, di, k = real["index_n_heads"], real["index_head_dim"], real["index_topk"]
    rank, width = real["kv_lora_rank"], real["kv_lora_rank"] + real["qk_rope_head_dim"]
    heads, p, t = real["num_attention_heads"], 33, 4
    ks = jax.random.split(key, 8)
    index = jax.random.normal(ks[0], (blocks, di, bt), F32).astype(BF)
    latent = jax.random.normal(ks[1], (blocks, width, bt), F32).astype(BF)
    tables = jax.random.permutation(ks[2], blocks)[: t * p].reshape(t, p).astype(I32)
    lens = jnp.asarray([33000, 8200, 16500, 1500], I32)
    q = jax.random.normal(ks[3], (t, hi, di), F32).astype(BF)
    w = jax.random.normal(ks[4], (t, hi), F32) * 0.05
    out = {}

    got = dsa.dsa_index_decode_pallas(q, w, index, tables, lens)
    want = dsa.index_scores_xla(q, w, index, tables)
    valid = np.arange(p * bt)[None] < np.asarray(lens)[:, None]
    flat = lambda s: np.asarray(s).transpose(1, 0, 2).reshape(t, -1)[valid]
    out["index_decode_rel"] = rel(flat(got), flat(want))

    pad = -t % 8
    got = _bias(dsa.dsa_select_pallas(jnp.pad(want, ((0, 0), (0, pad), (0, 0))), jnp.pad(lens, (0, pad)), k=k))[:, :t]
    bias = dsa.select_xla(want, lens, k=k)
    out["select_wave_sets_equal"] = bool(jnp.array_equal(got, bias))

    ql = jax.random.normal(ks[5], (t, heads, width), F32).astype(BF)
    scale = (real["qk_nope_head_dim"] + real["qk_rope_head_dim"]) ** -0.5
    got = dsa.mla_sparse_decode_pallas(ql, latent, bias, tables, lens, rank=rank, scale=scale)
    want = dsa.mla_sparse_decode_xla(ql, latent, bias, tables, lens, rank=rank, scale=scale)
    out["sparse_decode_rel"] = rel(got, want)

    table = tables[0, :4]
    qc = jax.random.normal(ks[6], (bt, hi, di), F32).astype(BF)
    wc = jax.random.normal(ks[7], (bt, hi), F32) * 0.05
    got = dsa.dsa_index_chunk_pallas(jnp.swapaxes(qc, 0, 1), wc, index, table, jnp.asarray([4], I32))
    want = dsa.index_scores_xla(qc, wc, index, table)
    out["index_chunk_rel"] = rel(got, want)
    lens_c = 3 * bt + jnp.arange(bt, dtype=I32) + 1
    out["select_piece_sets_equal"] = bool(
        jnp.array_equal(_bias(dsa.dsa_select_pallas(want, lens_c, k=k)), dsa.select_xla(want, lens_c, k=k))
    )
    return out


def _bias(result):
    """``dsa_select_pallas``'s bias: its first result since PR 61, its only
    one before (the tool also runs inside a ``git archive`` of an older tree)."""
    return result if isinstance(result, jax.Array) else result[0]


def selection(real: dict, key, calls: int = 8) -> dict:
    """The selection alone at the shapes the cell's trace names it by, ``[33,
    1024 | 128 | 8, 1024]`` (a piece, a question, a wave of three rows and a
    padded one), its rows at the END of a context of 8 / 16 / 33 pages, over
    scores of the configuration's own form (the chunk's scoring kernel over
    seeded index keys): device milliseconds a call from a trace, the counting
    passes a row tile made, and the sets against the sort's."""
    bt, blocks = real["serving"]["block_tokens"], real["serving"]["cache_blocks"]
    hi, di, k, p = real["index_n_heads"], real["index_head_dim"], real["index_topk"], 33
    ks = jax.random.split(key, 4)
    index = jax.random.normal(ks[0], (blocks, di, bt), F32).astype(BF)
    table = jax.random.permutation(ks[1], blocks)[:p].astype(I32)
    q = jax.random.normal(ks[2], (hi, bt, di), F32).astype(BF)
    w = jax.random.normal(ks[3], (bt, hi), F32) * 0.05
    scores = dsa.dsa_index_chunk_pallas(q, w, index, table, jnp.asarray([p], I32))
    out = {}
    for pages in (8, 16, 33):
        last = min(pages * bt, p * bt - 1)
        cases = {
            "piece": (scores, last - bt + 1 + jnp.arange(bt, dtype=I32)),
            "question": (scores[:, :128], last - 127 + jnp.arange(128, dtype=I32)),
            "wave": (scores[:, :8], jnp.asarray([last, last - 1500, last - 3000, 1, 0, 0, 0, 0], I32)),
        }
        results = {name: dsa.dsa_select_pallas(s, lens, k=k) for name, (s, lens) in cases.items()}
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for s, lens in cases.values():
                    for _ in range(calls):
                        timed = dsa.dsa_select_pallas(s, lens, k=k)
                    jax.block_until_ready(timed)
            ops = trace_reduce.reduce(trace_reduce.load(trace_reduce.find_xplane(tmp)))["ops"]
        for name, (s, lens) in cases.items():
            op = ops.get(f"dsa_select_pallas_f32_{p}_{s.shape[1]}_{bt}")
            row = {"ms": op and round(op[0] / op[1] * 1e3, 4)}
            if not isinstance(results[name], jax.Array):
                row["passes_a_tile"] = round(float(jnp.mean(results[name][1])), 2)
            row["sets_equal"] = bool(jnp.array_equal(_bias(results[name]), dsa.select_xla(s, lens, k=k)))
            out[f"select_{name}_{pages}_pages"] = row
    return out


def _ms(fn, *args, reps: int = 3) -> float:
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / reps * 1e3


def chunk(real: dict, kimi: dict, key) -> dict:
    """The chunk's latent attention, kernel against page loop: the error over
    the whole result and the milliseconds a page-step of both."""
    bt, blocks, k = real["serving"]["block_tokens"], real["serving"]["cache_blocks"], real["index_topk"]
    p, out = 33, {}
    ks = jax.random.split(key, 6)
    table = jax.random.permutation(ks[0], blocks)[:p].astype(I32)
    cases = [("glm5", real, True, bt, n) for n in (1, 8, 32)]
    cases += [("glm5", real, False, bt, n) for n in (1, 8, 32)]
    cases += [("glm5", real, True, 127, 8), ("kimi", kimi, False, bt, 8)]
    for name, cfg, biased, s, n in cases:
        heads, rank, rope = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
        nope, vdim = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
        latent = jax.random.normal(ks[1], (blocks, rank + rope, bt), F32).astype(BF)
        q = jax.random.normal(ks[2], (s, heads, nope + rope), F32).astype(BF)
        w_kvb = (jax.random.normal(ks[3], (rank, heads, nope + vdim), F32) * rank**-0.5).astype(BF)
        start = jnp.asarray(n * bt - s, I32)
        bias = None
        if biased:
            lens = start + jnp.arange(s, dtype=I32) + 1
            bias = _bias(dsa.select(jax.random.normal(ks[4], (p, s, bt), F32), lens, k))
        kw = dict(rank=rank, nope=nope, scale=float((nope + rope) ** -0.5))
        twin = jax.jit(lambda q, l, t, at, w, b: mla.latent_chunk_attention_xla(q, l, t, at, w, bias=b, **kw))
        kern = lambda q, l, t, at, w, b: mla.mla_chunk_attention_pallas(q, l, t, at, w, bias=b, **kw)
        args = (q, latent, table, start, w_kvb, bias)
        tag = f"chunk_{name}_{'bias' if biased else 'nobias'}_{s}x{n}"
        out[f"{tag}_rel"] = rel(kern(*args), twin(*args))
        out[f"{tag}_ms_a_page_step"] = [round(_ms(kern, *args) / n, 4), round(_ms(twin, *args) / n, 4)]
    return out


def model(real: dict, key) -> dict:
    """Three layers at the published widths (a vocabulary of 2,048): three
    whole pieces, a piece of 1,000 rows and one wave, kernels on and off."""
    fields = {k: real[v] for k, v in real["program"]["fields"].items()}
    fields.update(vocab=2048, n_layers=3)
    bt = real["serving"]["block_tokens"]
    cfg = glm_dsa.GlmDsaConfig(block_tokens=bt, dtype=BF, **fields)
    params = jax.jit(lambda k: glm_dsa.init_params(cfg, k))(jax.random.key(7, impl="rbg"))
    toks = jax.random.randint(key, (4 * bt,), 0, cfg.vocab, I32)
    table = jnp.asarray([3, 1, 4, 2, 0, 0], I32)
    last = 3 * bt + 1000
    use_pallas, got = paged._use_pallas, {}
    try:
        for on in (True, False):
            paged._use_pallas = lambda on=on: on
            jax.clear_caches()
            caches = cfg.kv_spec(6).make_caches()
            for start in range(0, last, bt):
                piece = toks[start : min(start + bt, last)]
                logits, caches = glm_dsa.resume_chunk(params, piece, jnp.int32(start), caches, table, cfg)
            z = jnp.zeros((1,), I32)
            wave, caches, aux = glm_dsa.verify_step_ragged(
                params, toks[last : last + 1], jnp.asarray([last], I32), z, z, jnp.zeros((2,), I32), z,
                caches, table[None], config=cfg, max_blocks=6,
            )
            got[on] = (np.asarray(logits, np.float32), np.asarray(wave, np.float32), np.asarray(aux["rows"]))
    finally:
        paged._use_pallas = use_pallas
    bits = lambda x: ((np.asarray(x, np.int64).reshape(-1)[:, None] & 0xFFFFFFFF) >> np.arange(32)) & 1
    sites = cfg.sites
    return {
        "model_chunk_logits_rel": rel(got[True][0], got[False][0]),
        "model_wave_logits_rel": rel(got[True][1], got[False][1]),
        "model_wave_set_places_differ": int(np.sum(bits(got[True][2][:, sites:]) != bits(got[False][2][:, sites:]))),
        "model_wave_experts_equal": bool(np.array_equal(got[True][2][:, :sites], got[False][2][:, :sites])),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--tol", type=float, default=0.02)
    ap.add_argument("--chunk-tol", type=float, default=0.0015)
    ap.add_argument("--sections", default="kernels,select,chunk,model", help="which of them to run")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("dsa_kernel_check: needs a TPU (the kernels have no compiled form elsewhere)", file=sys.stderr)
        return 2
    with open(os.path.join(REPO, "benchmarks", "configs", "glm-5.json")) as f:
        real = json.load(f)
    with open(os.path.join(REPO, "benchmarks", "configs", "kimi-linear-48b-a3b.json")) as f:
        kimi = json.load(f)
    key, sections = jax.random.key(args.seed), set(args.sections.split(","))
    held = kernels(real, key) if "kernels" in sections else {}
    timed = selection(real, jax.random.fold_in(key, 3)) if "select" in sections else {}
    held.update({f"{name}_sets_equal": row["sets_equal"] for name, row in timed.items()})
    if "chunk" in sections:
        timed.update(chunk(real, kimi, jax.random.fold_in(key, 2)))
        held.update({name: value for name, value in timed.items() if name.endswith("_rel")})
    reported = model(real, jax.random.fold_in(key, 1)) if "model" in sections else {}
    for name, value in {**timed, **held, **reported}.items():
        print(json.dumps({name: value}), flush=True)
    ok = all(
        value is True if name.endswith("_equal")
        else value < (args.chunk_tol if name.startswith("chunk_") else args.tol)
        for name, value in held.items()
    )
    print(json.dumps({"ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
