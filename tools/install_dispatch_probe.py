"""What a hit's install costs by the granularity at which it reaches the device.

``LayerwisePrefetch.install`` hands a hit's staged layers to the device from
an executor thread: one ``jax.device_put`` and two ``scatter_blocks`` a K/V
layer. This probe times that, with no model and no store, for ``--layers``
layers of a K and a V in a host buffer as ``HostStagingPool`` gives one
(``plain``: its page-aligned numpy buffer; ``shm``: a shared mapping, as the
pool a connection backs), at Mistral's block shape with 128 blocks (8 MiB a
layer) and DeepSeek's with 64 and 128 (16 / 32 MiB a layer). The forms:

- ``hop_per_layer``: what the tree did until PR 48: for EACH layer one
  ``run_in_executor`` (a packed upload, two device slices, two scatters) and
  one more executor call that waits for the upload;
- ``hop_per_layer_views``: the same hops with each layer's K and V uploaded
  as two host views, so nothing is cut on the device: what the hops alone
  cost, and what ``install_layer`` and ``LayerwiseKVReader`` do since PR 48;
- ``one_hop``: one ``run_in_executor`` for all layers: one ``device_put`` of
  every tensor's host view, then the scatters layer by layer, one executor
  call that waits;
- ``one_hop_packed``: the same with a layer's K and V uploaded as one array
  and cut on the device;
- ``inline``: ``one_hop``'s body on the event loop's own thread: the
  dispatches with no hop in them;
- ``one_program``: ``one_hop`` with every layer's scatters in ONE jitted
  program (the caches donated), a compiled program a layer count and hit
  size: what the scatters' own dispatch costs.

For each it prints one JSON line with the medians over ``--reps`` of: the time
to the call's return (``return_ms``: how long the exclusive gate would be
held), the time until ``jax.block_until_ready`` of every scattered cache
(``landed_ms``: a decode wave launched after the install queues behind these
on the device), and inside the executor function ``put_ms`` (``device_put``'s
return) and ``scatter_ms`` (the scatters' dispatch). ``--contend`` runs a
second pass with one Python thread spinning beside the loop, as an engine's
other threads hold the interpreter lock.

    python3 tools/install_dispatch_probe.py              # on the chip: ~1 min
    python3 tools/install_dispatch_probe.py --layers 3 --blocks-scale 16 --reps 2   # a smoke, anywhere
"""

import argparse
import asyncio
import functools
import json
import mmap
import os
import statistics
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# (label, block shape [tokens, KV heads, head dim], cache blocks, hit blocks)
CASES = (
    ("mistral_128", (16, 8, 128), 1024, 128),
    ("deepseek_64", (16, 32, 128), 320, 64),
    ("deepseek_128", (16, 32, 128), 320, 128),
)
FORMS = ("hop_per_layer", "hop_per_layer_views", "one_hop", "one_hop_packed", "inline", "one_program")


@functools.cache
def scatter_all_program():
    """Every layer's K and V scattered in one jitted program, caches donated."""
    import jax

    from infinistore_tpu.tpu.paged import scatter_blocks

    def body(caches, ids, parts):
        return [
            (scatter_blocks(k, ids, parts[2 * i]), scatter_blocks(v, ids, parts[2 * i + 1]))
            for i, (k, v) in enumerate(caches)
        ]

    return jax.jit(body, donate_argnums=(0,))


def host_buffer(kind: str, nbytes: int):
    import numpy as np

    from infinistore_tpu.tpu.staging import HostStagingPool

    if kind == "plain":
        pool = HostStagingPool(nbytes, 4096)
        buf = pool.buf
    else:
        buf = np.frombuffer(mmap.mmap(-1, nbytes), dtype=np.uint8)
    # Random finite bfloat16 (a NaN's payload need not survive a copy), written
    # once: every page is touched before a clock starts.
    buf.view(np.uint16)[:] = np.random.default_rng(0).integers(0, 0x3F80, nbytes // 2, dtype=np.uint16)
    return buf


def views(buf, layer: int, layer_bytes: int, n: int, shape, dtype, packed: bool):
    span = buf[layer * layer_bytes : (layer + 1) * layer_bytes].view(dtype)
    if packed:
        return [span.reshape((2 * n, *shape))]
    half = span.reshape((2, n, *shape))
    return [half[0], half[1]]


async def run_form(form: str, buf, caches, ids_dev, n: int, shape, dtype):
    """One install of every layer; returns (caches, stamps in ms)."""
    import jax

    from infinistore_tpu.tpu.paged import scatter_blocks

    loop = asyncio.get_running_loop()
    layers = len(caches)
    layer_bytes = buf.nbytes // layers
    packed = form in ("hop_per_layer", "one_hop_packed")
    stamps = {"put_ms": 0.0, "scatter_ms": 0.0}

    def dev(first: int, last: int, tensors):
        t0 = time.perf_counter()
        host = [v for l in range(first, last) for v in views(buf, l, layer_bytes, n, shape, dtype, packed)]
        up = jax.device_put(host)
        t1 = time.perf_counter()
        out = []
        if form == "one_program":
            out = scatter_all_program()(tensors, ids_dev, up)
            tensors = ()
        for i, (k, v) in enumerate(tensors):
            if packed:
                parts = (up[i][:n], up[i][n:])
            else:
                parts = (up[2 * i], up[2 * i + 1])
            out.append((scatter_blocks(k, ids_dev, parts[0]), scatter_blocks(v, ids_dev, parts[1])))
        t2 = time.perf_counter()
        stamps["put_ms"] += (t1 - t0) * 1e3
        stamps["scatter_ms"] += (t2 - t1) * 1e3
        return up, out

    waits = []
    out = list(caches)
    t0 = time.perf_counter()
    if form.startswith("hop_per_layer"):
        for l in range(layers):
            up, done = await loop.run_in_executor(None, dev, l, l + 1, [out[l]])
            out[l] = done[0]
            waits.append(loop.run_in_executor(None, jax.block_until_ready, up))
    elif form == "inline":
        up, out = dev(0, layers, out)
        waits.append(loop.run_in_executor(None, jax.block_until_ready, up))
    else:
        up, out = await loop.run_in_executor(None, dev, 0, layers, out)
        waits.append(loop.run_in_executor(None, jax.block_until_ready, up))
    stamps["return_ms"] = (time.perf_counter() - t0) * 1e3
    jax.block_until_ready(out)
    stamps["landed_ms"] = (time.perf_counter() - t0) * 1e3
    await asyncio.gather(*waits)
    return out, stamps


async def probe(args, contended: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    dtype = np.dtype(jnp.bfloat16)
    device = jax.devices()[0]
    for label, shape, cache_blocks, n in CASES:
        n = max(2, n // args.blocks_scale)
        cache_blocks = max(2 * n, cache_blocks // args.blocks_scale)
        layer_bytes = 2 * n * int(np.prod(shape)) * dtype.itemsize
        ids = np.random.default_rng(1).permutation(cache_blocks)[:n].astype(np.int32)
        ids_dev = jnp.asarray(ids)
        for kind in args.buffers:
            buf = host_buffer(kind, args.layers * layer_bytes)
            caches = [
                (jnp.zeros((cache_blocks, *shape), jnp.bfloat16), jnp.zeros((cache_blocks, *shape), jnp.bfloat16))
                for _ in range(args.layers)
            ]
            for form in FORMS:
                runs = []
                for rep in range(args.reps + 1):  # the first compiles and is dropped
                    caches, stamps = await run_form(form, buf, caches, ids_dev, n, shape, dtype)
                    if rep:
                        runs.append(stamps)
                    await asyncio.sleep(0.02)
                if form == FORMS[0]:
                    # Layer 0's K landed where the ids say, byte for byte.
                    want = views(buf, 0, layer_bytes, n, shape, dtype, False)[0]
                    got = np.asarray(caches[0][0][ids_dev]).view(np.uint16)
                    if not np.array_equal(got, want.view(np.uint16)):
                        raise SystemExit("scatter landed wrong bytes")
                line = {
                    "case": label, "buffer": kind, "form": form, "contended": contended,
                    "layers": args.layers, "mib_a_layer": round(layer_bytes / 2**20, 2),
                    "device": f"{device.platform}:{device.device_kind}",
                }
                for key in ("return_ms", "landed_ms", "put_ms", "scatter_ms"):
                    line[key] = round(statistics.median(r[key] for r in runs), 3)
                line["return_ms_all"] = [round(r["return_ms"], 2) for r in runs]
                print(json.dumps(line), flush=True)
            del caches, buf


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=16)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--blocks-scale", type=int, default=1, help="divide every block count (a smoke)")
    ap.add_argument("--buffers", nargs="+", default=["plain", "shm"], choices=("plain", "shm"))
    ap.add_argument("--contend", action="store_true")
    args = ap.parse_args()
    asyncio.run(probe(args, False))
    if args.contend:
        stop = threading.Event()

        def spin():
            x = 0
            while not stop.is_set():
                x += 1

        t = threading.Thread(target=spin, daemon=True)
        t.start()
        try:
            asyncio.run(probe(args, True))
        finally:
            stop.set()
            t.join(timeout=5)
    return 0


if __name__ == "__main__":
    sys.exit(main())
