#!/usr/bin/env python3
"""The quickest proof that the store -> engine path still starts on the chip.

One process (the only one that touches JAX) drives the system's main path
once, through the entry points a user would call, at the published widths of
Llama-3-8B (the model BASELINE.json names) with depth cut from 32 layers to
8 and seeded random weights:

  store server (its own OS process, ``python -m infinistore_tpu.server``)
    <-> InfinityConnection <-> KVConnector <-> EngineKVAdapter
    <-> ContinuousBatchingHarness <-> the jitted model steps + Pallas kernels

and checks what comes out by the repo's own means. It exits non-zero — and
prints no result line — unless ``jax.devices()[0].platform == "tpu"``, when
any phase raises, and anywhere the rest of the repo is missing. The last
line of stdout on success is one JSON object:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

This is a smoke, not a benchmark: the times it prints are observations for
CHANGES.md, never claims. Sizing: no width is cut; 8 identical layers are
5.2 GiB of bf16 weights beside a 2 GiB paged cache on a 16 GB chip.
"""

import asyncio
import dataclasses
import functools
import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# ---------------------------------------------------------------------------
# Tolerances, each with its reason. bfloat16 keeps 8 significant bits: unit
# roundoff u = 2^-9, and one ulp of a value x is at most 2^-7 * |x|.
# ---------------------------------------------------------------------------

# Kernel-against-reference bounds are per ROW (one query), because rows
# differ a hundredfold in size: a one-token context returns a value row
# (|x| up to ~4), a thousand-token context their average (|x| ~ 0.05).
#
# Decode-family kernels (ragged, its stats twin, int8) and their XLA
# references both do float32 math at HIGHEST precision and round ONCE to the
# query dtype. They may land on neighbouring bfloat16 values where the
# float32 sums (accumulated in different orders) straddle a rounding
# boundary: 2 ulps of the row's largest output. (An empty row is zeros on
# both sides: bound 0.)
DECODE_ULPS = 2
# Flash prefill runs native bf16 MXU dots with float32 accumulation and
# rounds the probabilities to bf16 for the PV pass: error <= u * sum(p|v|)
# <= u * max|v|. Then it and the float32 reference each round the output
# once (half an ulp each): + 1 ulp of the row's largest output.
FLASH_OUT_ULPS = 1
# Engine logits (bf16 activations, 8 layers) against the float32 dense
# reference: each layer rounds its activations about ten times, so ~80
# roundings of relative size u walk to ~sqrt(80) * u ~ 1.7% of the residual
# stream; the final norm and the lm_head carry that to ~1.7% of a logit's
# standard deviation, and the worst of ~4M logits (5.2 sigma) to ~9%. The
# v5e shows less than that estimate, 0.68% rms and 3.7% worst (my chip run,
# PR 21); the bounds are about 3.5x what it shows. A drop to 8-bit values
# anywhere (u 16x larger), a wrong mask or a wrong page would be far outside.
LOGITS_RMS_TOL = 0.025  # x rms(reference logits)
LOGITS_MAX_TOL = 0.15  # x rms(reference logits)
# The harness's own verify_tol default (2e-4) is a CPU/float32 number. On
# the MXU the harness cache and its one-shot prefill oracle can differ
# wherever the two took different paths: a suffix computed by
# prefill_continue (the chunk kernel over the cached prefix's pages, folded
# 128 keys a step) against the oracle's flash prefill (256-key blocks over
# the whole prompt); both round probabilities to bf16. K/V entries are
# unit-variance projections of a stream carrying the ~1.7% estimate above,
# so an entry may be off by ~6 sigma * 1.7% ~ 0.1 whatever its own size.
# Stale or misplaced bytes differ by O(1) in most entries and still fail.
VERIFY_TOL = 0.1


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The traffic's shape. Few distinct lengths on purpose: every new one
    compiles an 8-layer program."""

    prompt_tokens: int = 1024
    suffix_tokens: int = 192  # 1.5 row tiles of the resume's chunk kernel
    gen_tokens: int = 32
    num_blocks: int = 4096  # x 16 tokens x 8 kv heads x 128 x bf16 x K,V x 8 layers = 2 GiB
    max_req_blocks: int = 80  # (1024 + 192 + 32) / 16 = 78, rounded up


def smoke_config():
    from infinistore_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab=128256, dim=4096, n_layers=8, n_heads=32, n_kv_heads=8,
        ffn_dim=14336, rope_theta=500000.0, block_tokens=16,
        dtype=jnp.bfloat16,
    )


# ---------------------------------------------------------------------------
# Bookkeeping: phases, compilations.
# ---------------------------------------------------------------------------


class Compiles:
    """Counts backend compilations and persistent-cache hits as JAX reports
    them (jax.monitoring), so set-up can be told from steady state."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Phases:
    def __init__(self, compiles: Compiles):
        self.compiles = compiles
        self.log = []

    def run(self, name, fn, *args):
        """Run one phase; any exception ends the smoke (no phase is ever
        turned into a warning)."""
        t0, c0, s0 = time.perf_counter(), self.compiles.count, self.compiles.seconds
        out = fn(*args)
        entry = {
            "phase": name,
            "wall_s": round(time.perf_counter() - t0, 2),
            "compiles": self.compiles.count - c0,
            "compile_s": round(self.compiles.seconds - s0, 2),
        }
        self.log.append(entry)
        print(f"phase {name}: ok {json.dumps(entry)}", flush=True)
        return out


def _bytes_equal(a, b) -> bool:
    return np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _check_rows(name, got, ref, row_axis, ulps, floor=0.0) -> dict:
    """Every row of ``got`` within ``floor + ulps`` bf16 ulps of that row's
    largest reference value. Returns what was seen."""
    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    rest = tuple(a for a in range(ref.ndim) if a != row_axis)
    err = jnp.max(jnp.abs(got - ref), axis=rest)
    tol = floor + ulps * 2.0**-7 * jnp.max(jnp.abs(ref), axis=rest)
    worst = int(jnp.argmax(err - tol))
    rec = {
        "max_abs_err": float(jnp.max(err)),
        "tightest_row": {"row": worst, "err": float(err[worst]), "tol": float(tol[worst])},
    }
    assert bool(jnp.all(err <= tol)), f"{name}: |pallas - reference| {rec}"
    return rec


def _mosaic_kernels(jitted, *args, **static) -> set:
    """Names of the Mosaic kernels in the lowered program — the proof a
    dispatcher took its Pallas branch rather than an XLA path that happens
    to give the same numbers. (A kernel called once a layer is lowered
    once and called many times, so this is a set, not a count.)"""
    text = jitted.lower(*args, **static).as_text()
    return set(re.findall(r'kernel_name = "(\w+)"', text))


# ---------------------------------------------------------------------------
# Phase: device, native build, server.
# ---------------------------------------------------------------------------


def report_device() -> dict:
    import importlib.metadata as md

    dev = jax.devices()[0]
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = "not installed"
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    print(f"device: {json.dumps(device)} versions: {json.dumps(versions)}", flush=True)
    return device


def build_native():
    """Build the native core from the committed sources, in this run: the
    tree may carry objects and a .so from another machine, and the loader's
    mtime rule cannot tell after a copy."""
    native = os.path.join(REPO, "native")
    subprocess.run(["make", "-s", "-C", native, "clean"], check=True)
    subprocess.run(
        ["make", "-s", "-C", native, "-j", str(os.cpu_count() or 2)], check=True
    )
    so = os.path.join(REPO, "infinistore_tpu", "_native", "libinfinistore_tpu.so")
    return {"so_bytes": os.path.getsize(so)}


def start_server():
    """The store through its normal entry point, as its own OS process
    (tools/fleet.py: ``python -m infinistore_tpu.server --service-port N
    --manage-port M --prealloc-size 1 --no-pin-memory ...``, waits until the
    service socket accepts and /health answers)."""
    from tools import fleet

    (member,) = fleet.spawn_fleet_servers(1, timeout_s=60.0)
    # One process for each chip: the server must never load the runtime.
    with open(f"/proc/{member['proc'].pid}/maps") as f:
        maps = f.read()
    for lib in ("libtpu", "jaxlib", "xla_extension"):
        assert lib not in maps, f"the store server process mapped {lib}"
    return member


# ---------------------------------------------------------------------------
# Phase: every dispatcher takes its Pallas branch; each kernel agrees with
# its XLA reference at this geometry.
# ---------------------------------------------------------------------------


def check_kernels(cfg) -> dict:
    from infinistore_tpu.tpu import chunk_attention as ca
    from infinistore_tpu.tpu import flash_prefill as fp
    from infinistore_tpu.tpu import kv_quant as kq
    from infinistore_tpu.tpu import paged
    from infinistore_tpu.tpu import paged_attention as pa

    assert paged._use_pallas(), "the dispatchers would take their XLA branch here"

    out = {}
    rng = np.random.default_rng(3)
    h, kvh, d, bt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.block_tokens
    nblk, dt = 256, cfg.dtype
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape, np.float32), dt)
    k_cache, v_cache = normal(nblk, bt, kvh, d), normal(nblk, bt, kvh, d)

    # Gather / scatter are copies: byte equality IS the expectation, so the
    # "not compared with itself" evidence is the Mosaic call in the lowering.
    ids = jnp.asarray(rng.permutation(nblk)[:64], jnp.int32)
    assert _mosaic_kernels(
        paged._gather_blocks_pallas, k_cache, ids, interpret=False
    ) == {"_copy_kernel"}
    got = paged._gather_blocks_pallas(k_cache, ids, interpret=False)
    assert _bytes_equal(got, paged.gather_blocks_xla(k_cache, ids)), "gather"
    blocks = normal(64, bt, kvh, d)
    want = paged.scatter_blocks_xla(jnp.copy(k_cache), ids, blocks)
    target = jnp.copy(k_cache)
    assert _mosaic_kernels(
        paged._scatter_blocks_pallas, target, ids, blocks, interpret=False
    ) == {"_scatter_kernel"}
    got = paged._scatter_blocks_pallas(target, ids, blocks, interpret=False)
    assert _bytes_equal(got, want), "scatter"
    # Donation is real: the input is gone, and fresh caches are distinct
    # buffers that survive donating scatters across K, V and layers.
    assert target.is_deleted(), "scatter did not donate its cache"
    fresh = cfg.kv_spec(nblk).make_caches()[:2]
    jax.block_until_ready([
        (paged.scatter_blocks(k, ids, blocks), paged.scatter_blocks(v, ids, blocks))
        for k, v in fresh
    ])
    out["gather_scatter"] = "byte-identical, donated"

    # Decode family: a wave of 8 rows over uneven contexts, one of them empty.
    rows, width = 8, 64
    q = normal(rows, h, d)
    tables = np.stack([rng.permutation(nblk)[:width] for _ in range(rows)]).astype(np.int32)
    lens = np.asarray([1, 17, 0, 1024, 333, 16, 1000, 512], np.int32)
    dense = (q, k_cache, v_cache, jnp.asarray(tables), jnp.asarray(lens))
    dense_ref = pa.paged_decode_attention_xla_batched(*dense)

    def decode_case(name, jitted, args, ref, normalize=None):
        assert len(_mosaic_kernels(jitted, *args, interpret=False)) == 1, name
        got = jitted(*args, interpret=False)
        if normalize is not None:
            got = normalize(got)
        out[name] = _check_rows(name, got, ref, 0, DECODE_ULPS)

    # Raw (acc, m, l) statistics normalize to the same output.
    unstat = lambda s: (s[0] / jnp.maximum(s[2], 1e-30)).astype(dt)
    meta = pa.build_ragged_wave(list(tables), lens, bt, pad_to_pow2=True)
    ragged = (
        q, k_cache, v_cache, jnp.asarray(meta.pages), jnp.asarray(meta.page_rows),
        jnp.asarray(meta.page_starts), jnp.asarray(meta.seq_lens),
    )
    decode_case(
        "decode_ragged", pa._paged_decode_attention_pallas_ragged, ragged, dense_ref
    )
    decode_case(
        "decode_ragged_stats", pa._paged_decode_attention_pallas_ragged_stats,
        ragged, dense_ref, unstat,
    )
    # The same wave as a rectangle, full-width tables through the in-jit
    # metadata: what decode_step and the disagg decode layer ride.
    rect = (
        q, k_cache, v_cache, *pa.rectangle_as_ragged(jnp.asarray(tables)),
        jnp.asarray(lens),
    )
    decode_case(
        "decode_rectangle", pa._paged_decode_attention_pallas_ragged, rect, dense_ref
    )

    # int8: both sides dequantize the same int8 cache, so the scheme's own
    # error cancels and the decode bound applies.
    (kq8, ks), (vq8, vs) = kq.quantize_kv(k_cache), kq.quantize_kv(v_cache)
    quant = (q, kq8, ks, vq8, vs, jnp.asarray(tables), jnp.asarray(lens))
    decode_case(
        "decode_int8", kq._quant_decode_pallas, quant, kq._quant_decode_xla(*quant)
    )

    # Flash prefill: the smoke's prompt, and the two lengths whose blocks the
    # old divisor rule got wrong (264 -> 132 rows, rejected by Mosaic; 272 ->
    # 136 rows, 8-aligned but not a bf16 tile). Both now run as 144-row
    # blocks over 288 padded rows; their numbers are checked here.
    for s in (1024, 264, 272):
        qs, ks_, vs_ = normal(1, s, h, d), normal(1, s, kvh, d), normal(1, s, kvh, d)
        kw = dict(causal=True, block_q=256, block_k=256, interpret=False)
        assert _mosaic_kernels(fp._flash_prefill_pallas, qs, ks_, vs_, **kw) == {
            "_flash_kernel"
        }
        got = fp._flash_prefill_pallas(qs, ks_, vs_, **kw)
        p_rounding = 2.0**-9 * float(jnp.max(jnp.abs(vs_.astype(jnp.float32))))
        out[f"flash_prefill_{s}"] = _check_rows(
            f"flash S={s}", got, fp.flash_prefill_xla(qs, ks_, vs_, causal=True),
            1, FLASH_OUT_ULPS, floor=p_rounding,
        )
    # The resume's chunk kernel: a 64-token chunk over prefixes that end on
    # a page-group boundary and inside a page, and a 300-token remainder
    # after a short prefix (two of the kernel's row tiles and a part), the
    # table padded with block 0. Like flash prefill it rounds probabilities
    # to bf16.
    for start, rows in ((1024, 64), (1000, 64), (100, 300)):
        qc = normal(rows, h, d)
        table = np.zeros(72, np.int32)
        n_pages = -(-(start + rows) // bt)
        table[:n_pages] = 1 + rng.permutation(nblk - 1)[:n_pages]
        args = (qc, k_cache, v_cache, jnp.asarray(table), jnp.int32(start))
        assert _mosaic_kernels(
            ca._chunk_prefix_attention_pallas, *args, interpret=False
        ) == {"_chunk_attn_kernel"}
        got = ca._chunk_prefix_attention_pallas(*args, interpret=False)
        p_rounding = 2.0**-9 * float(jnp.max(jnp.abs(v_cache.astype(jnp.float32))))
        out[f"chunk_resume_{start}"] = _check_rows(
            f"chunk start={start}", got, ca.chunk_prefix_attention_xla(*args),
            0, FLASH_OUT_ULPS, floor=p_rounding,
        )
    for name, rec in out.items():
        print(f"  kernel {name}: {rec}", flush=True)
    return out


# ---------------------------------------------------------------------------
# Phase: traffic through the harness.
# ---------------------------------------------------------------------------


def make_adapter(connector):
    from infinistore_tpu.connector import token_chain_hashes
    from infinistore_tpu.engine import EngineKVAdapter
    from infinistore_tpu.tpu.paged import gather_blocks

    class ByteCheckingAdapter(EngineKVAdapter):
        """The engine adapter, plus the store's contract checked on every
        hit: a host copy is kept of each block the harness hands to a save
        (what was gathered), and after every install the blocks are read
        back out of the paged cache and must be the same bytes."""

        def __init__(self, conn):
            super().__init__(conn)
            self.saved = {}  # chain hash -> per-layer (K bytes, V bytes)
            self.checked_blocks = 0

        async def save_kv(self, token_ids, caches, block_table, first_block=0):
            chains = token_chain_hashes(token_ids, self.block_tokens)[first_block:]
            host = [(np.asarray(k), np.asarray(v)) for k, v in caches]
            for i, blk in enumerate(np.asarray(block_table)):
                self.saved[chains[i]] = [(k[blk], v[blk]) for k, v in host]
            return await super().save_kv(
                token_ids, caches, block_table, first_block=first_block
            )

        async def start_fetch_async(self, token_ids, limit_blocks=None, priority=0):
            handle = await super().start_fetch_async(
                token_ids, limit_blocks=limit_blocks, priority=priority
            )
            handle.smoke_tokens = list(token_ids)
            return handle

        async def install_kv(self, prefetch, caches, block_table):
            out, loaded = await super().install_kv(prefetch, caches, block_table)
            n = loaded // self.block_tokens
            chains = token_chain_hashes(prefetch.smoke_tokens, self.block_tokens)[:n]
            ids = jnp.asarray(np.asarray(block_table[:n]), jnp.int32)
            for layer, pair in enumerate(out if n else ()):
                for kind, cache in enumerate(pair):
                    want = np.stack([self.saved[c][layer][kind] for c in chains])
                    assert _bytes_equal(gather_blocks(cache, ids), want), (
                        f"layer {layer} {'KV'[kind]}: installed blocks are "
                        "not the bytes that were saved"
                    )
            self.checked_blocks += n
            return out, loaded

    return ByteCheckingAdapter(connector)


class WaveTap:
    """Keeps every logits chunk the wave decoder hands back to a request —
    the harness itself keeps only the argmax."""

    def __init__(self, wave):
        self.phase = ""
        self.chunks = []  # (phase, first physical block, first position, logits)
        self._inner = wave.step_chunk
        wave.step_chunk = self

    async def __call__(self, tokens, positions, padded_table, priority=0):
        rows = await self._inner(tokens, positions, padded_table, priority=priority)
        self.chunks.append((self.phase, int(padded_table[0]), positions[0], rows))
        return rows

    def request_logits(self, phase, prompt_len, rounds):
        """[rounds, vocab]: the logits that chose each generated token of
        the request whose prompt is ``prompt_len`` long (round j decodes
        position prompt_len - 1 + j, one token a round without a drafter)."""
        requests = {}  # live requests of one phase own distinct blocks
        for ph, block, pos, rows in self.chunks:
            if ph == phase:
                requests.setdefault(block, {})[pos] = rows
        (by_pos,) = [r for r in requests.values() if min(r) == prompt_len - 1]
        return jnp.concatenate(
            [by_pos[prompt_len - 1 + j][:1] for j in range(rounds)]
        )


def dense_f32_logits(params, cfg, tokens, last_n: int):
    """The model's dense masked-attention path (llama._block with an
    explicit mask) in float32 at the highest matmul precision, one layer
    resident in float32 at a time; logits of the last ``last_n`` positions."""
    from infinistore_tpu.models import llama

    @functools.partial(jax.jit, static_argnames=("config",))
    def layer_fn(lp, x, positions, config):
        lp = {name: w.astype(jnp.float32) for name, w in lp.items()}
        mask = positions[:, :, None] >= positions[:, None, :]
        k, v = llama._kv_proj(lp, 0, x, positions, config)
        return llama._block(lp, 0, x, k, v, positions, mask, config)

    @jax.jit
    def head_fn(norm_w, head_w, x):
        x = llama._rms_norm(x, norm_w.astype(jnp.float32))
        return jnp.einsum("bsd,dv->bsv", x, head_w.astype(jnp.float32))

    names = [k.split(".", 1)[1] for k in params if k.startswith("l0.")]
    toks = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(len(tokens), dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], toks, axis=0).astype(jnp.float32)[None]
        for layer in range(cfg.n_layers):
            lp = {f"l0.{n}": params[f"l{layer}.{n}"] for n in names}
            x = layer_fn(lp, x, positions, cfg)
        return head_fn(params["final_norm"], params["lm_head"], x[:, -last_n:])[0]


def check_logits(name, got, ref) -> dict:
    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    scale = float(jnp.sqrt(jnp.mean(ref * ref)))
    rms = float(jnp.sqrt(jnp.mean((got - ref) ** 2))) / scale
    worst = float(jnp.max(jnp.abs(got - ref))) / scale
    rec = {"rms_err": rms, "max_err": worst, "ref_rms": scale}
    print(f"  logits {name}: {rec} (x ref rms; tol {LOGITS_RMS_TOL} / {LOGITS_MAX_TOL})", flush=True)
    assert bool(jnp.all(jnp.isfinite(got))), f"{name}: non-finite logits"
    assert rms <= LOGITS_RMS_TOL and worst <= LOGITS_MAX_TOL, f"{name}: {rec}"
    return rec


async def run_traffic(conn, cfg, params, sizes: Sizes, compiles: Compiles) -> dict:
    """A handful of requests through ContinuousBatchingHarness.run, enough
    to take every branch once. Returns the report; raises on any check."""
    from infinistore_tpu.connector import KVConnector
    from infinistore_tpu.engine import ContinuousBatchingHarness

    bt = cfg.block_tokens
    kvc = KVConnector(
        conn, cfg.kv_spec(sizes.num_blocks), "chip-smoke",
        max_blocks=sizes.max_req_blocks,
    )
    adapter = make_adapter(kvc)
    h = ContinuousBatchingHarness(
        adapter, params, cfg, sizes.num_blocks, sizes.max_req_blocks,
        verify=True, verify_tol=VERIFY_TOL,  # see VERIFY_TOL: not loosened silently
    )
    tap = WaveTap(h.wave)
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab, size=sizes.prompt_tokens).tolist()
    longer = prompt + rng.integers(0, cfg.vocab, size=sizes.suffix_tokens).tolist()
    p_blocks, l_blocks = len(prompt) // bt, len(longer) // bt
    gen = sizes.gen_tokens
    report = {"shm_active": bool(conn.shm_active)}

    async def phase(name, prompts, concurrency=1):
        tap.phase = name
        n0, t0, c0 = len(h.stats), time.perf_counter(), compiles.count
        await h.run(prompts, concurrency=concurrency, gen_tokens=gen)
        # Completion order is the scheduler's business; report by length.
        stats = sorted(h.stats[n0:], key=lambda st: st.tokens)
        assert all(s.verified for s in stats), f"{name}: cache != prefill oracle"
        assert not any(s.raced_eviction for s in stats), name
        report[name] = {
            "wall_s": round(time.perf_counter() - t0, 3),
            "compiles": compiles.count - c0,
            "loaded_blocks": [s.loaded_blocks for s in stats],
            "computed_blocks": [s.computed_blocks for s in stats],
        }
        print(f"  traffic {name}: {report[name]}", flush=True)
        return stats

    # 1. Cold: flash prefill -> gather -> D2H -> store put.
    (cold,) = await phase("cold", [prompt])
    assert (cold.hit_blocks, cold.loaded_blocks, cold.computed_blocks) == (0, 0, p_blocks)
    # 2. The same prompt: lookup -> fetch -> H2D -> donated scatter install;
    # nothing recomputed under the hit.
    (hit,) = await phase("full_hit", [prompt])
    assert (hit.hit_blocks, hit.loaded_blocks, hit.computed_blocks) == (p_blocks, p_blocks, 0)
    assert hit.prefetched_blocks == 2 * cfg.n_layers * p_blocks and hit.wasted_blocks == 0
    # The hit path's first-token logits against the miss path's: the same
    # program over the same bytes, so 0.0 is the honest expectation here.
    report["hit_vs_miss"] = check_logits(
        "first token, hit vs miss",
        tap.request_logits("full_hit", len(prompt), 1),
        tap.request_logits("cold", len(prompt), 1),
    )
    assert hit.generated == cold.generated
    # 3. That prompt plus a suffix: partial hit -> prefill_continue.
    (part,) = await phase("partial_hit", [longer])
    assert (part.hit_blocks, part.loaded_blocks) == (p_blocks, p_blocks)
    assert part.computed_blocks == l_blocks - p_blocks
    # 4. Four concurrent requests of unequal length (prefixes of stored
    # prompts: chain keys commit to the whole prefix, so each is a full
    # hit): verify_step_ragged launches waves with several rows and the
    # ragged kernel walks an uneven page list.
    mixed = [prompt[: len(prompt) // 4], prompt[: len(prompt) // 2], prompt, longer]
    waves0 = h.wave.waves
    stats = await phase("ragged_waves", mixed, concurrency=len(mixed))
    assert [s.loaded_blocks for s in stats] == [len(p) // bt for p in mixed]
    assert sum(s.computed_blocks for s in stats) == 0
    assert all(len(s.generated) == gen for s in stats)
    assert h.wave.max_wave >= 2, "no wave carried more than one request"
    assert h.max_live == len(mixed)
    report["ragged_waves"].update(
        waves=h.wave.waves - waves0, max_wave=h.wave.max_wave,
        buckets=sorted(h.wave.bucket_sizes),
    )
    # Every token the longest of the four generated, against the float32
    # dense reference teacher-forced on the same tokens.
    longest = stats[-1]
    report["vs_dense_f32"] = check_logits(
        f"{gen} wave rounds vs dense f32",
        tap.request_logits("ragged_waves", len(longer), gen),
        dense_f32_logits(params, cfg, longer + longest.generated[:-1], gen),
    )
    # 5. Steady: the full hit again. Every shape it needs exists by now.
    await phase("steady_full_hit", [prompt])
    assert report["steady_full_hit"]["compiles"] == 0, "a steady request compiled"

    assert adapter.checked_blocks == p_blocks * 4 + l_blocks + p_blocks * 3 // 4
    report["byte_checked_blocks"] = adapter.checked_blocks
    report["engine"] = {
        k: v for k, v in h.metrics().items()
        if k in ("requests", "hit_rate", "loaded_blocks", "computed_blocks",
                 "decode_waves", "max_wave_size", "generated_tokens",
                 "max_concurrent_saves", "prefetch_fallbacks")
    }

    await check_staging_reuse(kvc, cfg, h.caches, p_blocks, rng, report)
    return report


def check_steps_hold_kernels(cfg, params, sizes: Sizes) -> dict:
    """The three jitted steps the harness runs hold the Mosaic kernels at
    the traffic's shapes: flash prefill and the block scatter in
    ``prefill``, the ragged decode kernel in the wave step, the chunk
    kernel in the resume of a partial hit. So does the disagg decode
    layer, which rides the wave step's kernel as a rectangle."""
    from infinistore_tpu.models import llama, serving

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    spec = cfg.kv_spec(sizes.num_blocks)
    cache = jax.ShapeDtypeStruct(spec.cache_shape, spec.dtype)
    caches = [(cache, cache)] * cfg.n_layers
    s, bt, mrb = sizes.prompt_tokens, cfg.block_tokens, sizes.max_req_blocks
    in_prefill = _mosaic_kernels(
        llama.prefill, params, i32(s), caches, i32(s // bt), config=cfg
    )
    assert in_prefill == {"_flash_kernel", "_scatter_kernel"}, in_prefill
    # The wave as the harness launches it: one row over one page, packed.
    layout = serving.WaveLayout(rows=1, tables=1, pages=1)
    in_wave = _mosaic_kernels(
        serving.verify_step_ragged, params, i32(layout.size(mrb)), i32(serving.FEED_ROWS), caches,
        config=cfg, max_blocks=mrb, layout=layout,
    )
    assert in_wave == {"_ragged_attn_kernel"}, in_wave
    in_resume = _mosaic_kernels(
        llama.resume_chunk, params, i32(sizes.suffix_tokens), i32(), caches,
        i32(mrb), config=cfg,
    )
    assert in_resume == {"_chunk_attn_kernel"}, in_resume
    x = jax.ShapeDtypeStruct((2, 1, cfg.dim), cfg.dtype)
    in_layer = _mosaic_kernels(
        llama.decode_wave_layer, params, x, i32(2, 1), cache, cache, i32(2, mrb),
        config=cfg, layer=0, max_blocks=mrb,
    )
    assert in_layer == {"_ragged_attn_kernel"}, in_layer
    return {
        "prefill": sorted(in_prefill), "verify_step_ragged": sorted(in_wave),
        "resume_chunk": sorted(in_resume), "decode_wave_layer": sorted(in_layer),
    }


def check_steps_donate(cfg, params, sizes: Sizes) -> dict:
    """``prefill`` and ``decode_step`` update the cache they are handed in
    place (as the scatter above does): the input arrays are gone after the
    call, and the result is byte-right: the same prompt and the same token
    written through another table leave the same bytes in its blocks, and a
    block in neither table is still zero. At the traffic's own shapes, so
    ``prefill`` compiles once for both phases."""
    from infinistore_tpu.models import decode_step, prefill
    from infinistore_tpu.tpu.paged import gather_blocks

    bt, mrb = cfg.block_tokens, sizes.max_req_blocks
    n = sizes.prompt_tokens // bt
    rng = np.random.default_rng(5)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, size=sizes.prompt_tokens), jnp.int32)

    def run(first_block):
        table = np.arange(first_block, first_block + mrb, dtype=np.int32)
        handed = cfg.kv_spec(sizes.num_blocks).make_caches()
        _, caches = prefill(params, prompt, handed, jnp.asarray(table[:n]), cfg)
        assert all(t.is_deleted() for pair in handed for t in pair), "prefill did not donate"
        handed = caches
        logits, caches = decode_step(
            params, jnp.int32(7), jnp.int32(sizes.prompt_tokens), handed,
            jnp.asarray(table), cfg, mrb,
        )
        assert all(t.is_deleted() for pair in handed for t in pair), "decode_step did not donate"
        ids = jnp.asarray(np.append(table[: n + 1], first_block + mrb), jnp.int32)
        return logits, [
            (gather_blocks(k, ids), gather_blocks(v, ids)) for k, v in caches
        ]

    (logits_a, a), (logits_b, b) = run(3), run(sizes.num_blocks - 2 * mrb)
    assert _bytes_equal(logits_a, logits_b), "logits differ with the table"
    for layer, (pair_a, pair_b) in enumerate(zip(a, b)):
        for got, want in zip(pair_a, pair_b):
            assert _bytes_equal(got, want), f"layer {layer}: bytes differ with the table"
            assert np.asarray(got[:n], np.float32).any(), f"layer {layer}: nothing written"
            assert not np.asarray(got[n + 1], np.float32).any(), (
                f"layer {layer}: a block outside the table was written"
            )
    return {"prefill": "donated, byte-right", "decode_step": "donated, byte-right"}


async def check_staging_reuse(kvc, cfg, caches, written_blocks, rng, report):
    """More layers in flight than staging regions, byte-compared: the
    region-reuse rule differs by backend (tpu/layerwise.py
    _device_put_copies) and its non-CPU side had never run. The one-phase
    reader has 6 regions for 8 layers; a prefetch over a pool of 3 regions
    wraps the two-phase path too."""
    from infinistore_tpu.tpu.paged import gather_blocks
    from infinistore_tpu.tpu.staging import HostStagingPool

    # The pool hands out low block ids first, so the first request's
    # blocks hold real K/V, not zeros.
    n = min(32, written_blocks)
    tokens = rng.integers(0, cfg.vocab, size=n * cfg.block_tokens).tolist()
    src = rng.permutation(written_blocks)[:n].astype(np.int32)
    dst = np.arange(n, dtype=np.int32)[::-1].copy()
    want = [
        tuple(np.asarray(gather_blocks(c, jnp.asarray(src))) for c in pair)
        for pair in caches
    ]
    assert all(np.any(a != 0) for pair in want for a in pair)
    await kvc.save(tokens, caches, src)
    assert kvc._reader.regions.count < cfg.n_layers
    spec = cfg.kv_spec(n)

    def same(loaded):
        return all(
            _bytes_equal(gather_blocks(c, jnp.asarray(dst)), want[layer][kind])
            for layer, pair in enumerate(loaded)
            for kind, c in enumerate(pair)
        )

    loaded, got = await kvc.load(tokens, spec.make_caches(), dst)
    assert got == n and same(loaded), "one-phase load: bytes differ"
    pool = HostStagingPool(
        3 * 2 * n * spec.block_nbytes, spec.block_nbytes, conn=kvc.conn
    )
    handle = await kvc.start_fetch_async(tokens, prefetch_pool=pool)
    assert handle.regions == 3 < cfg.n_layers
    loaded, got = await handle.install(spec.make_caches(), dst)
    assert got == n and same(loaded), "two-phase install: bytes differ"
    report["staging_reuse"] = {
        "reader_regions": kvc._reader.regions.count,
        "prefetch_regions": handle.regions, "layers": cfg.n_layers,
        "blocks": n, "result": "byte-identical",
    }
    print(f"  staging reuse: {report['staging_reuse']}", flush=True)


# ---------------------------------------------------------------------------
# Phase: four chips — ICI handoff and sharded decode over a real mesh.
# ---------------------------------------------------------------------------


def _own_slices(arr, rows: int):
    """Every chip holds its own slice of ``arr``'s leading axis — not four
    slices on chip 0."""
    shards = arr.addressable_shards
    assert len({s.device for s in shards}) == len(shards) == 4, arr.sharding
    assert sorted(s.index[0].start or 0 for s in shards) == [i * rows for i in range(4)]
    assert all(s.data.shape[0] == rows for s in shards)


def check_four_chips(cfg) -> dict:
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from infinistore_tpu.connector import KVConnector
    from infinistore_tpu.tpu import paged_attention as pa
    from infinistore_tpu.tpu.ici import IciBlockTransfer

    devs = jax.devices()[:4]
    rng = np.random.default_rng(7)
    bt, nblk, n = cfg.block_tokens, 256, 16
    spec = cfg.kv_spec(nblk)
    np_dt = np.dtype(cfg.dtype)
    out = {}

    # -- KVConnector.handoff(src=0, dst=k): one launch, all layers ----------
    mesh = Mesh(np.array(devs), ("store",))
    ici = IciBlockTransfer(mesh, "store", perm=[(0, 1)])
    kvc = KVConnector(None, spec, "chip-smoke-ici", max_blocks=n, ici=ici)
    host = [
        [rng.standard_normal((4, *spec.cache_shape), np.float32).astype(np_dt)
         for _ in "kv"]
        for _ in range(cfg.n_layers)
    ]
    sharding = NamedSharding(mesh, P("store"))
    caches = [tuple(jax.device_put(a, sharding) for a in pair) for pair in host]
    tokens = rng.integers(0, cfg.vocab, size=n * bt).tolist()
    for dst in (1, 2, 3):
        src_ids = rng.permutation(nblk)[:n].astype(np.int32)
        dst_ids = rng.permutation(nblk)[:n].astype(np.int32)
        before, donated = ici.launches, caches
        caches, moved = asyncio.run(
            kvc.handoff(tokens, caches, src_ids, dst_ids, src=0, dst=dst)
        )
        jax.block_until_ready(caches)
        assert moved == n and ici.launches == before + 1
        assert all(c.is_deleted() for pair in donated for c in pair), "not donated"
        for layer, pair in enumerate(caches):
            for kind, c in enumerate(pair):
                host[layer][kind][dst, dst_ids] = host[layer][kind][0, src_ids]
                assert _bytes_equal(c, host[layer][kind]), (
                    f"handoff 0->{dst} layer {layer}: bytes differ"
                )
                _own_slices(c, 1)
    out["ici_handoff"] = {"dst": [1, 2, 3], "blocks": n, "launches_each": 1,
                          "result": "byte-identical, own slices, donated"}

    # -- paged_decode_attention_ragged_sharded against the single-chip kernel
    mesh = Mesh(np.array(devs), ("sp",))
    per, n_local, rows = 128, 32, 3
    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k_host, v_host = (
        rng.standard_normal((4 * per, bt, kvh, d), np.float32).astype(np_dt)
        for _ in "kv"
    )
    q = jnp.asarray(rng.standard_normal((rows, h, d), np.float32), cfg.dtype)
    block_sharded = NamedSharding(mesh, P("sp", None, None, None))
    k_sh, v_sh = (jax.device_put(a, block_sharded) for a in (k_host, v_host))
    _own_slices(k_sh, per)
    _own_slices(v_sh, per)
    local_tables = np.stack([
        [rng.permutation(per)[:n_local] for _ in range(rows)] for _ in range(4)
    ]).astype(np.int32)  # [shard, row, n_local]
    # Per (shard, row): a long request over uneven shards, one of them empty
    # (the only partial block is the last shard's last); a request that
    # lives on one shard; a short one over two.
    local_lens = np.asarray([
        [n_local * bt, 0, 3 * bt],
        [20 * bt, 0, 7],
        [0, 0, 0],
        [n_local * bt - 5, 17 * bt + 3, 0],
    ], np.int32)
    pages, page_rows, page_starts, lens, width = pa.build_ragged_wave_sharded(
        local_tables, local_lens, bt
    )
    got = pa.paged_decode_attention_ragged_sharded(
        q, k_sh, v_sh, pages, page_rows, page_starts, lens,
        mesh=mesh, table_width=width,
    )
    assert got.sharding.is_fully_replicated
    assert len({s.device for s in got.addressable_shards}) == 4
    # One chip, the whole cache: each row's global table is its shards'
    # valid pages in shard order (every shard but a row's last is whole
    # blocks, so the concatenation is the row's context).
    tables = [
        np.concatenate([
            local_tables[p, r, : -(-int(local_lens[p, r]) // bt)] + p * per
            for p in range(4)
        ])
        for r in range(rows)
    ]
    meta = pa.build_ragged_wave(tables, local_lens.sum(axis=0), bt)
    one = lambda a: jax.device_put(a, devs[0])
    ref = pa._paged_decode_attention_pallas_ragged(
        one(q), one(k_host), one(v_host), one(meta.pages), one(meta.page_rows),
        one(meta.page_starts), one(meta.seq_lens), interpret=False,
    )
    out["sharded_decode"] = _check_rows(
        "ragged sharded decode, 4 chips vs 1", one(got), ref, 0, DECODE_ULPS
    )
    for name, rec in out.items():
        print(f"  four chips {name}: {rec}", flush=True)
    return out


# ---------------------------------------------------------------------------


def main() -> int:
    t_start = time.perf_counter()
    device = report_device()
    if device["platform"] != "tpu":
        print(
            f"chip_smoke: jax found platform {device['platform']!r}, not a tpu; "
            "nothing was checked",
            file=sys.stderr,
        )
        return 1
    compiles = Compiles()
    phases = Phases(compiles)
    summary = {"device": device, "phases": phases.log}

    summary["native"] = phases.run("native_build", build_native)
    sys.path.insert(0, REPO)
    import infinistore_tpu as its
    from infinistore_tpu import compile_cache
    from infinistore_tpu.models import init_params

    summary["compile_cache"] = compile_cache.enable()
    cfg, sizes = smoke_config(), Sizes()
    from tools import fleet

    server = phases.run("server_start", start_server)
    try:
        conn = its.InfinityConnection(its.ClientConfig(
            host_addr="127.0.0.1", service_port=server["service_port"],
            log_level="error",
        ))
        conn.connect()
        summary["kernels"] = phases.run("kernels", check_kernels, cfg)
        # Seeded random weights. The rbg generator, because XLA:TPU takes
        # about a minute to compile threefry at these shapes.
        params = phases.run(
            "weights", lambda: jax.block_until_ready(
                init_params(cfg, jax.random.key(0, impl="rbg"))
            ),
        )
        summary["mosaic_calls"] = phases.run(
            "steps_hold_kernels", check_steps_hold_kernels, cfg, params, sizes
        )
        summary["steps_donate"] = phases.run(
            "steps_donate", check_steps_donate, cfg, params, sizes
        )
        setup_s = time.perf_counter() - t_start
        summary["traffic"] = phases.run(
            "traffic",
            lambda: asyncio.run(run_traffic(conn, cfg, params, sizes, compiles)),
        )
        conn.close()
        del params
        if device["count"] >= 4:
            summary["four_chips"] = phases.run("four_chips", check_four_chips, cfg)
        else:
            summary["four_chips"] = f"skipped ({device['count']} device)"
            print(f"phase four_chips: skipped ({device['count']} device, needs 4)", flush=True)
    finally:
        fleet.stop_members([server])

    traffic = summary["traffic"]
    summary["timing"] = {
        # Set-up: everything before the first request (native build, server,
        # kernel checks, weights). compile_s: every compilation of the run,
        # those inside the traffic included.
        "setup_s": round(setup_s, 1),
        "compile_s": round(compiles.seconds, 1),
        "compilations": compiles.count,
        "persistent_cache_hits": compiles.cache_hits,
        "steady_full_hit_s": traffic["steady_full_hit"]["wall_s"],
        "total_s": round(time.perf_counter() - t_start, 1),
    }
    stats = jax.devices()[0].memory_stats()
    summary["peak_bytes_in_use"] = stats["peak_bytes_in_use"]
    print(f"timing: {json.dumps(summary['timing'])}", flush=True)
    print(f"memory: peak_bytes_in_use {stats['peak_bytes_in_use']} "
          f"({stats['peak_bytes_in_use'] / 2**30:.2f} GiB)", flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "chip_smoke.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
