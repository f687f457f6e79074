"""Benchmark CLI (reference infinistore/benchmark.py surface: N blocks x
block-size KB simulating --steps layers, RDMA-style async batched or TCP
single-key transfers, write/read MB/s report + data verification,
benchmark.py:53-271). numpy staging buffers replace torch CUDA tensors — on
TPU the client side stages in host DRAM (see infinistore_tpu.tpu for the
HBM<->host path).
"""

import argparse
import asyncio
import json
import time
import uuid

import numpy as np

from . import compile_cache
from .config import TYPE_RDMA, TYPE_TCP, ClientConfig
from .lib import InfinityConnection, StripedConnection


def parse_args(argv=None):
    p = argparse.ArgumentParser(prog="infinistore-tpu-benchmark")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--service-port", type=int, default=22345)
    p.add_argument("--size", type=int, default=128, help="total MB to transfer")
    p.add_argument("--block-size", type=int, default=32, help="block size in KB")
    p.add_argument(
        "--steps", type=int, default=32,
        help="simulate N layers: the batch is split into N sequential batched ops",
    )
    p.add_argument("--type", choices=["rdma", "tcp"], default="rdma",
                   help="rdma = batched zero-copy data plane; tcp = single-key ops")
    p.add_argument("--iteration", type=int, default=1)
    p.add_argument("--verify", action=argparse.BooleanOptionalAction, default=True)
    p.add_argument("--json", action="store_true", help="emit one JSON line")
    p.add_argument(
        "--latency", action="store_true",
        help="also measure single-block fetch latency p50/p99 at 4KB and 64KB "
             "(the BASELINE.md 'p50 block-fetch latency' configs)",
    )
    p.add_argument(
        "--streams", type=int, default=1,
        help="connection stripes for batched ops (cross-host DCN scaling; "
             "see docs/multistream.md)",
    )
    p.add_argument(
        "--adaptive", action=argparse.BooleanOptionalAction, default=True,
        help="striped fan-out mode: adaptive work-stealing chunk scheduler "
             "(default) vs the legacy static 1/N split (--no-adaptive, for "
             "A/B comparison of the two data planes)",
    )
    p.add_argument(
        "--wave", type=int, default=0,
        help="also measure admission-wave read coalescing (N concurrent "
             "requests' reads issued as N separate calls vs merged into one "
             "— the FetchCoalescer mechanism; connector.py)",
    )
    p.add_argument(
        "--trace", default=None, metavar="FILE|PRESET",
        help="replay a loadgen trace (JSON file or preset name: skewed, "
             "uniform, outlier_flood) through the continuous-batching "
             "engine harness against the server (docs/serving_load.md)",
    )
    p.add_argument(
        "--trace-seed", type=int, default=0,
        help="generator seed when --trace names a preset",
    )
    p.add_argument(
        "--trace-duration", type=float, default=0.4,
        help="trace duration in seconds when --trace names a preset",
    )
    p.add_argument(
        "--pacing-mbps", type=int, default=0,
        help="cap each connection's egress in MB/s (SO_MAX_PACING_RATE); "
             "implies the socket path (shm off — a same-host memcpy would "
             "bypass the cap). Emulates a bandwidth-limited cross-host "
             "stream; see tools/striping_emulation.py",
    )
    return p.parse_args(argv)


def _measure_latency(conn, samples: int = 200) -> dict:
    """p50/p99 single-block fetch latency at 4KB and 64KB.

    Sync (read_cache, the low-latency API: the calling thread blocks on the
    native completion) and async samples are taken in short INTERLEAVED
    chunks — hosts swing between seconds, and the async-minus-sync delta
    (``async_overhead_us``) only means 'bridge cost' when both paths saw
    the same weather (same discipline as bench.py's _fetch_latency_us)."""
    out = {}
    chunk = 50
    for size in (4 << 10, 64 << 10):
        buf = np.random.randint(0, 256, size=size, dtype=np.uint8)
        dst = np.zeros_like(buf)
        conn.register_mr(buf)
        conn.register_mr(dst)
        key = f"lat-{uuid.uuid4().hex[:8]}"

        async def async_chunk(k):
            lats = []
            for _ in range(k):
                t0 = time.perf_counter()
                await conn.read_cache_async([(key, 0)], size, dst.ctypes.data)
                lats.append((time.perf_counter() - t0) * 1e6)
            return lats

        async def seed():
            await conn.write_cache_async([(key, 0)], size, buf.ctypes.data)
            await conn.read_cache_async([(key, 0)], size, dst.ctypes.data)

        asyncio.run(seed())  # write + warm the async path
        conn.read_cache([(key, 0)], size, dst.ctypes.data)  # warm sync
        lats = []
        sync_lats = []
        for _ in range(max(1, samples // chunk)):
            for _ in range(chunk):
                t0 = time.perf_counter()
                conn.read_cache([(key, 0)], size, dst.ctypes.data)
                sync_lats.append((time.perf_counter() - t0) * 1e6)
            lats += asyncio.run(async_chunk(chunk))
        lats.sort()
        sync_lats.sort()
        p50 = lats[len(lats) // 2]
        sync_p50 = sync_lats[len(sync_lats) // 2]
        out[f"fetch_{size >> 10}kb"] = {
            "p50_us": round(p50, 1),
            "p99_us": round(lats[int(len(lats) * 0.99)], 1),
            "sync_p50_us": round(sync_p50, 1),
            "sync_p99_us": round(sync_lats[int(len(sync_lats) * 0.99)], 1),
            # The asyncio bridge's whole per-op tax in one number; its floor
            # is the eventfd loop wake (bench.py asyncio_efd_floor_us).
            "async_overhead_us": round(p50 - sync_p50, 1),
        }
        conn.delete_keys([key])
    return out


def _measure_wave_coalescing(conn, keys, offsets, block_size, dst, wave: int) -> dict:
    """N concurrent 'admissions' reading disjoint spans: N separate
    read_cache_async calls racing on the connection vs the SAME blocks
    merged into one call (what connector.FetchCoalescer does for a wave of
    engine admissions). The gain is per-call overhead amortization — the
    number striped deployments multiply, since one merged call splits
    across all stripes."""
    n = len(keys)
    # Exactly `wave` near-equal spans (never more, never fewer — except
    # when there are fewer keys than requests), so the reported
    # wave_requests is the concurrency actually raced.
    wave = min(wave, n)
    bounds = [round(j * n / wave) for j in range(wave + 1)]
    spans = [
        list(zip(keys[a:b], offsets[a:b]))
        for a, b in zip(bounds, bounds[1:])
        if b > a
    ]

    async def split():
        await asyncio.gather(*(
            conn.read_cache_async(span, block_size, dst.ctypes.data)
            for span in spans
        ))

    async def merged():
        await conn.read_cache_async(
            [b for span in spans for b in span], block_size, dst.ctypes.data
        )

    asyncio.run(split())  # warm
    best_split = best_merged = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        asyncio.run(split())
        best_split = min(best_split, time.perf_counter() - t0)
        t0 = time.perf_counter()
        asyncio.run(merged())
        best_merged = min(best_merged, time.perf_counter() - t0)
    moved_mb = n * block_size / (1 << 20)
    return {
        "wave_requests": len(spans),
        "wave_split_mb_s": round(moved_mb / best_split, 2),
        "wave_merged_mb_s": round(moved_mb / best_merged, 2),
        "wave_coalescing_gain": round(best_split / best_merged, 3),
    }


def _run_trace(args) -> dict:
    """``--trace`` mode: replay a loadgen trace (file or preset) through
    the continuous-batching engine harness against the server
    (docs/serving_load.md). Reports the harness's serving metrics (TTFT
    percentiles, wave pad fraction, waves) plus the trace's own shape."""
    import os

    try:
        import jax
        import jax.numpy as jnp
    except ImportError as e:
        raise SystemExit(f"--trace needs jax for the engine harness: {e}")
    compile_cache.enable()

    from . import loadgen
    from .connector import KVConnector
    from .engine import (
        ContinuousBatchingHarness,
        EngineKVAdapter,
        NGramDrafter,
    )
    from .models import LlamaConfig, init_params

    if os.path.exists(args.trace):
        trace = loadgen.Trace.load(args.trace)
    else:
        trace = loadgen.preset(
            args.trace, seed=args.trace_seed,
            duration_s=args.trace_duration,
        )

    cfg = LlamaConfig(
        vocab=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=128, block_tokens=8, dtype=jnp.float32,
    )
    num_blocks, max_blocks = 96, 8
    conn = InfinityConnection(ClientConfig(
        host_addr=args.host, service_port=args.service_port,
        log_level="warning",
    ))
    conn.connect()
    try:
        kvc = KVConnector(
            conn, cfg.kv_spec(num_blocks),
            f"trace-{uuid.uuid4().hex[:8]}", max_blocks=max_blocks,
        )
        h = ContinuousBatchingHarness(
            EngineKVAdapter(kvc),
            init_params(cfg, jax.random.PRNGKey(0)),
            cfg, num_blocks, max_blocks, verify=args.verify,
        )
        h.drafter = NGramDrafter(max_draft=4)
        t0 = time.perf_counter()
        stats = asyncio.run(loadgen.replay(trace, h, concurrency=8))
        wall = time.perf_counter() - t0
        errs = [s for s in stats if isinstance(s, Exception)]
        if errs:
            raise SystemExit(f"trace replay failed: {errs[:3]}")
        m = h.metrics()
        return {
            "trace": args.trace,
            "trace_seed": trace.seed,
            "trace_requests": len(trace.requests),
            "trace_prefill_only": sum(
                1 for r in trace.requests if r.gen_tokens == 0
            ),
            "trace_background": sum(
                1 for r in trace.requests if r.priority != 0
            ),
            "replay_wall_s": round(wall, 3),
            "requests_per_s": round(len(trace.requests) / wall, 1),
            "verified": bool(m["all_verified"]) if args.verify else None,
            "hit_rate": round(m["hit_rate"], 3),
            "p50_ttft_us": m["p50_ttft_us"],
            "p99_ttft_us": m["p99_ttft_us"],
            "p99_ttft_fg_us": m["p99_ttft_fg_us"],
            "wave_pad_fraction": round(m["wave_pad_fraction"], 4),
            "decode_waves": m["decode_waves"],
        }
    finally:
        conn.close()


async def _run_batched(conn, keys, offsets, block_size, src, dst, steps):
    """Layer-wise streaming shape (reference benchmark.py:188-256): the block
    list is split into `steps` chunks issued as pipelined batched ops."""
    n = len(keys)
    per = max(1, n // steps)
    t0 = time.perf_counter()
    writes = []
    for s in range(0, n, per):
        blocks = list(zip(keys[s : s + per], offsets[s : s + per]))
        writes.append(conn.write_cache_async(blocks, block_size, src.ctypes.data))
    await asyncio.gather(*writes)
    t1 = time.perf_counter()
    reads = []
    for s in range(0, n, per):
        blocks = list(zip(keys[s : s + per], offsets[s : s + per]))
        reads.append(conn.read_cache_async(blocks, block_size, dst.ctypes.data))
    await asyncio.gather(*reads)
    t2 = time.perf_counter()
    return t1 - t0, t2 - t1


def run(args) -> dict:
    cfg = ClientConfig(
        host_addr=args.host,
        service_port=args.service_port,
        connection_type=TYPE_RDMA if args.type == "rdma" else TYPE_TCP,
        log_level="warning",
        pacing_rate_mbps=args.pacing_mbps,
        # Pacing shapes SOCKET egress; the same-host shm fast path moves
        # payloads by memcpy and would silently bypass the cap.
        enable_shm=args.pacing_mbps == 0,
    )
    if args.streams > 1:
        conn = StripedConnection(cfg, streams=args.streams, adaptive=args.adaptive)
    else:
        conn = InfinityConnection(cfg)
    conn.connect()

    total_bytes = args.size << 20
    block_size = args.block_size << 10
    nblocks = max(1, total_bytes // block_size)
    total_bytes = nblocks * block_size

    src = np.random.randint(0, 256, size=total_bytes, dtype=np.uint8)
    dst = np.zeros_like(src)
    run_id = uuid.uuid4().hex[:8]
    keys = [f"bench-{run_id}-{i}" for i in range(nblocks)]
    offsets = [i * block_size for i in range(nblocks)]

    write_s = read_s = 0.0
    try:
        if args.type == "rdma":
            conn.register_mr(src)
            conn.register_mr(dst)
            for _ in range(args.iteration):
                w, r = asyncio.run(
                    _run_batched(conn, keys, offsets, block_size, src, dst, args.steps)
                )
                write_s += w
                read_s += r
        else:
            for _ in range(args.iteration):
                t0 = time.perf_counter()
                for i, key in enumerate(keys):
                    conn.tcp_write_cache(
                        key, src.ctypes.data + offsets[i], block_size
                    )
                t1 = time.perf_counter()
                for i, key in enumerate(keys):
                    out = conn.tcp_read_cache(key)
                    dst[offsets[i] : offsets[i] + block_size] = out
                t2 = time.perf_counter()
                write_s += t1 - t0
                read_s += t2 - t1

        ok = bool(np.array_equal(src, dst)) if args.verify else None
        moved = total_bytes * args.iteration
        result = {
            "type": args.type,
            "blocks": nblocks,
            "block_size_kb": args.block_size,
            "total_mb": moved >> 20,
            "write_mb_s": round(moved / write_s / (1 << 20), 2),
            "read_mb_s": round(moved / read_s / (1 << 20), 2),
            "verified": ok,
        }
        if args.latency and args.type == "rdma":
            result["latency"] = _measure_latency(conn)
        if args.wave > 1 and args.type == "rdma":
            result["coalescing"] = _measure_wave_coalescing(
                conn, keys, offsets, block_size, dst, args.wave
            )
        if args.type == "rdma":
            # Wakeup coalescing over the whole run (native ring pushes vs
            # eventfd signals; >1 means pipelined ops shared loop wakes).
            result["completion_batch_size"] = round(
                conn.completion_stats()["completion_batch_size"], 2
            )
        if args.streams > 1:
            # Adaptive scheduler receipt: per-stripe chunk/block counts,
            # steals, EWMA rates, and same-host collapse count.
            result["striping"] = conn.data_plane_stats()
        conn.delete_keys(keys)
        return result
    finally:
        conn.close()


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.trace:
        result = _run_trace(args)
        if args.json:
            print(json.dumps(result))
        else:
            print(
                f"replayed {result['trace_requests']} requests "
                f"({result['trace']}) in {result['replay_wall_s']}s"
            )
            print(
                f"p99 TTFT: {result['p99_ttft_us']}us (fg "
                f"{result['p99_ttft_fg_us']}us), pad fraction "
                f"{result['wave_pad_fraction']}, waves "
                f"{result['decode_waves']}"
            )
            if result["verified"] is not None:
                print(f"data verified: {result['verified']}")
        return 0 if result.get("verified") in (True, None) else 1
    result = run(args)
    if args.json:
        print(json.dumps(result))
    else:
        print(f"write throughput: {result['write_mb_s']} MB/s")
        print(f"read throughput: {result['read_mb_s']} MB/s")
        if result["verified"] is not None:
            print(f"data verified: {result['verified']}")
    return 0 if result.get("verified") in (True, None) else 1


if __name__ == "__main__":
    raise SystemExit(main())
