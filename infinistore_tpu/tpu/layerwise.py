"""Layer-wise streaming of paged KV blocks between TPU HBM and the store.

This is the TPU realization of the reference's core latency trick: stream the
KV cache layer by layer so network transfer overlaps per-layer compute, which
is how it keeps prefill network overhead "no more than 1%"
(reference docs/source/design.rst:54-63; the benchmark models it as
--steps "layers", benchmark.py:188-193). Here the overlap is two-level:
device->host copies (async, overlap with TPU compute) and network puts
(async, a window of layers in flight) are pipelined, and the writer ships
directly from jax's D2H buffers — zero staging copies (see staging.py).

Key naming follows the reference's convention of hash-chain keys per block
(design.rst:50): one key per (request-chain hash, layer, k/v, block index), so
`get_match_last_index` gives longest-prefix reuse across requests.
"""

import asyncio
import functools
import time
from collections import deque
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from .. import tracing, wire
from ..lib import (
    InfiniStoreException,
    InfiniStoreKeyNotFound,
    InfiniStoreResourcePressure,
)
from .paged import PagedKVCacheSpec, gather_blocks, scatter_blocks
from .staging import HostStagingPool

KeyFn = Callable[[int, str, int], str]  # (layer, tensor name, block_index) -> key


def _layer_plan(spec: PagedKVCacheSpec, layer: int, n: int, hit: bool):
    """``[(tensor, first, m, offset)]`` for ``layer``'s tensors over an
    ``n``-block span: tensor ``t`` moves its blocks ``first .. n - 1`` (``m``
    of them: all ``n`` for a save, the hit policy's for a hit), packed one
    tensor after the other from byte ``offset`` of the layer's region. A K/V
    layer's is [K blocks | V blocks], as it always was."""
    plan, off = [], 0
    for t in spec.layer_tensors(layer):
        first = t.hit_first(n) if hit else 0
        plan.append((t, first, n - first, off))
        off += (n - first) * t.nbytes
    return plan


def _plan_reads(plan, key_fn: KeyFn, layer: int, base: int):
    """The store reads of one layer's hit: ``[(value bytes, [(key, offset),
    ...])]``, one entry a value size (a store call moves values of one size):
    ONE for a K/V layer, its K keys then its V keys."""
    by_size = {}
    for t, first, m, off in plan:
        name, nbytes, at = t.name, t.nbytes, base + off
        # A list, not a generator: a frame a value is the event loop's time.
        by_size.setdefault(nbytes, []).extend(
            [(key_fn(layer, name, first + i), at + i * nbytes) for i in range(m)]
        )
    return list(by_size.items())


def _plan_views(plan, buf, base: int):
    """Per tensor, the staged blocks as a host array ``[m, *block_shape]``:
    zero-copy views of ``buf`` from byte ``base`` on."""
    return [
        buf[base + off : base + off + m * t.nbytes]
        .view(np.dtype(jax.numpy.dtype(t.dtype)))
        .reshape((m, *t.block_shape))
        for t, _, m, off in plan
    ]


def _packs(plan) -> bool:
    """Whether a save's gathers of the layer's tensors go down as ONE packed
    array (a K and a V: one shape, one type, as many blocks each)."""
    return len({(t.block_shape, jax.numpy.dtype(t.dtype), m) for t, _, m, _ in plan}) == 1


def _plan_nbytes(plan) -> int:
    return sum(m * t.nbytes for t, _, m, _ in plan)


def _upload_and_scatter(plans, buf, bases, tensors, ids, uploaded=None):
    """A run of layers' staged blocks (layer ``i`` of the run: ``plans[i]``,
    in ``buf`` from byte ``bases[i]``) -> device in ONE ``jax.device_put``,
    scattered into ``tensors[i]`` (donated) at the plan's blocks of ``ids``
    (host int32). What goes up is every tensor's host view, unlike shapes and
    types side by side, and behind them ``ids`` from each distinct first
    block the run's tensors start at: nothing is packed and nothing is cut on
    the device, where a slice costs a millisecond whatever its size (PERF.md,
    PR 48). ``uploaded()`` is called once ``device_put`` has returned, before
    the scatters. Returns (what was uploaded, the updated tensors a layer)."""
    views = [v for plan, base in zip(plans, bases) for v in _plan_views(plan, buf, base)]
    firsts = sorted({first for plan in plans for _, first, _, _ in plan})
    up = jax.device_put(views + [ids[first:] for first in firsts])
    if uploaded is not None:
        uploaded()
    parts, ids_from = iter(up), dict(zip(firsts, up[len(views) :]))
    return up, [
        tuple(
            scatter_blocks(cache, ids_from[first], next(parts))
            for cache, (_, first, _, _) in zip(layer_tensors, plan)
        )
        for plan, layer_tensors in zip(plans, tensors)
    ]


# What a write keeps in flight. BACKGROUND (nobody waits for it):
# BG_PUT_GROUPS layers' puts, with BG_D2H_AHEAD layers gathered and their D2H
# started ahead of them (device memory: 2 x n x block_nbytes a layer): the
# bound on background bytes in flight of docs/qos.md. FOREGROUND (its caller
# awaits the acknowledgement): as many layers' gathers, D2H and puts as fit
# in FG_WINDOW_BYTES, never fewer than BG_PUT_GROUPS. An answer's save
# (0.6-15 MiB over all its layers) fits whole; a promoted prompt write's
# remainder (layers of 5-34 MiB) gets two to six layers.
BG_PUT_GROUPS = 2
BG_D2H_AHEAD = 4
FG_WINDOW_BYTES = 32 << 20
# What a speculative prefetch (BACKGROUND class, its request not admitted
# yet) keeps on the wire: as many layers' reads as fit, never fewer than one.
# A hit of light layers goes out whole, as it always did; of a heavy one
# (Mistral's longest: about 32 MiB a layer, 16 layers) one layer at a time, so
# that promote() finds the rest unsent and sends it foreground.
SPECULATIVE_READ_BYTES = 32 << 20
# A layer's D2H wait stands on the event loop up to this weight and moves to
# an executor thread above it. An answer's or a question's layer (0.1-1 MiB)
# lands in 0.2-0.4 ms, less than the thread hop costs (about 3 ms a hop on
# the chip's host, PERF.md PR 40); a miss's layer (5-67 MiB) takes its D2H
# and the host-side layout conversion, 2-30 ms in which no wave could flush.
D2H_INLINE_BYTES = 2 << 20


class PartialReadError(InfiniStoreException):
    """A layerwise read failed mid-pipeline.

    ``caches`` is the ONLY valid cache list after this error: layers
    scattered before the failure are new arrays whose inputs were DONATED
    (in-place update on TPU — the caller's originals are deleted buffers
    there); layers at/after the failure are the caller's untouched arrays.
    ``cause`` is the underlying store error (e.g. InfiniStoreKeyNotFound
    when blocks raced away between lookup and read). Callers that swallow
    the failure as a cache miss must hand ``caches`` — never their original
    list — back to the engine."""

    def __init__(self, caches, cause: BaseException):
        super().__init__(f"layerwise read failed mid-pipeline: {cause!r}")
        self.caches = caches
        self.cause = cause

# On TPU, device_put always copies host bytes into HBM, so "upload ready"
# means the staging region is free. On CPU (the test backend), device_put of
# an aligned numpy view is ZERO-COPY — the device array aliases the staging
# memory, and scatters read through the alias until they execute — so region
# reuse must additionally wait for the occupant's scatters.
def _device_put_copies() -> bool:
    return jax.default_backend() != "cpu"


def kv_block_key(model: str, chain_hash: str, layer: int, kind: str, block: int) -> str:
    """Default key scheme: model/chain-hash/layer/k|v/block."""
    return f"{model}/{chain_hash}/L{layer}/{kind}{block}"


class _LayerRegions:
    """Read-staging layout: region r holds one layer's K blocks immediately
    followed by its V blocks — a single contiguous span, so the whole layer
    uploads to the device as ONE transfer. The region count adapts to the
    pool size (>= 2 — double buffering — up to 8), deepening the fetch/H2D
    pipeline when the pool affords it."""

    def __init__(self, pool: HostStagingPool, spec: PagedKVCacheSpec, max_blocks: int):
        if spec.uniform and spec.block_nbytes > pool.block_size:
            raise ValueError(
                f"staging pool block_size {pool.block_size} < KV block "
                f"{spec.block_nbytes}"
            )
        self.pool = pool
        self.spec = spec
        self.max_blocks = max_blocks
        # A region: (K + V) x max_blocks slots, or as many as hold the
        # heaviest layer's hit where the layers' tensors differ.
        self.slots = (
            2 * max_blocks if spec.uniform
            else -(-spec.region_nbytes(max_blocks) // pool.block_size)
        )
        self.count = min(8, pool.num_slots // self.slots)
        if self.count < 2:
            raise ValueError(
                f"staging pool too small: need {2 * self.slots} slots of "
                f"{pool.block_size}B, have {pool.num_slots}"
            )

    def base_offset(self, region: int) -> int:
        """Byte offset of a region's contiguous span."""
        return self.pool.slot_offset(region * self.slots)


class LayerwiseKVWriter:
    """Stream a request's KV blocks to the store, one layer at a time.

    Pipeline per layer: Pallas-gather blocks from the paged cache (device),
    pack K and V into one array, start ONE async D2H (the readers upload a
    layer's tensors as host views, unpacked: `_upload_and_scatter`), and ship previous layers' host
    buffers on the network concurrently — a window of layer-groups of puts
    in flight (below). Puts go straight from jax's D2H buffer (registered for
    the op's lifetime), so the only host copy is the one into the server's
    pool.

    Tracing (docs/observability.md): under the caller's span (the engine's
    ``save_io``) one ``save_layer`` a layer, from its gather's dispatch to
    its two puts' acknowledgement, and under it ``save_d2h_wait``: the
    wait for the layer's D2H (also the ``its.save_d2h`` device-call
    region): on the caller's EVENT LOOP for a layer of at most
    ``D2H_INLINE_BYTES``, in an executor thread above it, so that a miss's
    layers do not stop the loop. Always on:
    ``counters`` (the connector's ledger, when it set one) gains the bytes
    and the microseconds of those waits, and the puts, rounds and
    promotions of :meth:`write`.

    Class and window follow whether the caller is blocked on the write
    (docs/qos.md, "Producers"). BACKGROUND, nobody waits: ``BG_PUT_GROUPS``
    groups of puts and ``BG_D2H_AHEAD`` staged layers, the bound on
    background bytes in flight. FOREGROUND, the caller awaits the
    acknowledgement: as many layers in flight as fit in ``FG_WINDOW_BYTES``
    (never fewer than the background's), so a save lighter than the budget
    submits every deeper layer at once and the sentinel after them: two
    rounds of put latency, whatever the layer count."""

    def __init__(self, conn, pool: HostStagingPool, spec: PagedKVCacheSpec,
                 max_blocks: int):
        self.conn = conn
        self.spec = spec
        # The writer ships straight from jax D2H buffers — the pool provides
        # only the connection to register them with; no slots are consumed.
        self.pool = pool
        self.max_blocks = max_blocks
        # The save keys of KVConnector.hit_counters, which the connector
        # shares here; a writer on its own counts nothing.
        self.counters: Optional[dict] = None

    async def write(
        self,
        caches: Sequence[Tuple[jax.Array, jax.Array]],
        block_ids: np.ndarray,
        key_fn: KeyFn,
        priority: int = wire.PRIORITY_FOREGROUND,
        priority_cell: Optional[dict] = None,
    ) -> int:
        """Returns total blocks written (K+V across layers). ``priority``:
        QoS class for the network puts — connectors tag whole-request saves
        BACKGROUND (prefill saves must not delay decode-blocking reads;
        docs/qos.md) while the default stays untagged.

        ``priority_cell``: a mutable ``{"value": PRIORITY_*}`` in place of
        ``priority``, read per LAYER (not captured once): the caller flips
        it to FOREGROUND the moment it starts waiting for this write, and
        the layers not yet submitted go out untagged and in the foreground
        window; submissions in flight finish at their class
        (``LayerwisePrefetch.promote``'s contract)."""
        n = len(block_ids)
        if n == 0:
            return 0
        if n > self.max_blocks:
            raise ValueError(f"{n} blocks > writer capacity {self.max_blocks}")
        ids_dev = jax.numpy.asarray(block_ids, dtype=jax.numpy.int32)
        pool = self.pool
        spec = self.spec
        loop = asyncio.get_running_loop()
        pri_cell = priority_cell if priority_cell is not None else {"value": priority}
        counters = self.counters
        promoted = False
        # Every block saves every tensor of its layer, whatever a hit reads.
        plans = [_layer_plan(spec, layer, n, hit=False) for layer in range(len(caches))]
        # Layers a foreground write keeps in flight (puts, and staged ahead),
        # at the heaviest layer's weight.
        fg_layers = max(BG_PUT_GROUPS, FG_WINDOW_BYTES // max(_plan_nbytes(p) for p in plans))
        # (futures, registered transfer, blocks count, `save_layer` span)
        # groups in flight.
        inflight: deque = deque()
        total = 0
        # A drain that had to wait since the last submission: the next
        # submission starts a new ROUND of put latency (`save_fg_rounds`).
        waited = False

        def foreground() -> bool:
            return pri_cell["value"] == wire.PRIORITY_FOREGROUND

        started_fg = foreground()

        async def drain_one() -> int:
            nonlocal waited
            futs, tr, count, lspan = inflight.popleft()
            if not all(f.done() for f in futs):
                waited = True
            # Let BOTH puts settle before releasing the host buffers — a
            # failed K-batch must not free memory the V-batch's writev is
            # still streaming from — then surface the first failure.
            results = await asyncio.gather(*futs, return_exceptions=True)
            tr.release()
            for r in results:
                if isinstance(r, BaseException):
                    if lspan is not None:
                        lspan.finish(status=f"error:{type(r).__name__}")
                    raise r
            if lspan is not None:
                lspan.finish()
            return count

        # Layer 0 is written LAST: connectors use a block's layer-0 K key as
        # the presence sentinel for the whole block (one prefix-match probe
        # instead of layers x 2), so it must commit only after every deeper
        # layer did — a half-saved block then reads as absent, never as a
        # false hit.
        order = list(range(1, len(caches))) + [0] if len(caches) > 1 else [0]
        # Stage ahead: gather + start async D2H for up to BG_D2H_AHEAD
        # layers (foreground: as many as the byte budget leaves beside the
        # puts in flight, if that is more) before consuming the oldest —
        # device->host transfers pipeline.
        staged: deque = deque()
        todo = iter(enumerate(order))

        def top_up():
            while len(staged) < BG_D2H_AHEAD or (
                foreground() and len(staged) + len(inflight) < fg_layers
            ):
                nxt = next(todo, None)
                if nxt is None:
                    return
                pos, layer = nxt
                # `save_layer`: this gather's dispatch to its puts' ack; a
                # child of the caller's span (the engine's `save_io`, which
                # the store's write ops keep stamping).
                lspan = tracing.start_span("save_layer")
                if lspan is not None:
                    lspan.annotate(layer=layer, bytes=_plan_nbytes(plans[layer]))
                gathered = [gather_blocks(t, ids_dev) for t in caches[layer]]
                if _packs(plans[layer]) and len(gathered) > 1:
                    # K blocks then V blocks packed into ONE device array ->
                    # one D2H transfer per layer (the device-side concat is an
                    # HBM copy, trivial next to the host transfer it halves).
                    # Tensors of unlike shapes go down side by side.
                    gathered = [jax.numpy.concatenate(gathered)]
                staged.append((pos, layer, pool.stage_out(gathered), lspan))

        try:
            top_up()
            while staged:
                pos, layer, tr, lspan = staged[0]
                # Keep one put group fewer than the window in flight while
                # this D2H lands.
                while len(inflight) >= (fg_layers if foreground() else BG_PUT_GROUPS):
                    total += await drain_one()
                if pos == len(order) - 1:
                    # Layer-0-last barrier: every deeper layer's put must have
                    # completed (= committed) before the sentinel ships.
                    while inflight:
                        total += await drain_one()
                # `save_d2h_wait`: until the gather and its D2H have landed.
                # A heavy layer's wait (with the host-side layout conversion
                # under it, 2-4 GB/s: tens of ms for a miss's layer) goes to
                # an executor thread, so that it does not pin the event
                # loop, through which every wave flushes; the span is handed
                # in, as to an install's upload. A light layer's is shorter
                # than the hop and stays in line. wait() then only registers
                # the packed buffer.
                dspan = tracing.start_span("save_d2h_wait", parent=lspan)
                t_wait = time.perf_counter()

                def landed(tr=tr, dspan=dspan):
                    with tracing.device_call("its.save_d2h", dspan):
                        tr.transfer.wait()

                plan = plans[layer]
                try:
                    if _plan_nbytes(plan) > D2H_INLINE_BYTES:
                        await loop.run_in_executor(None, landed)
                    else:
                        landed()
                finally:
                    if dspan is not None:
                        dspan.finish()
                hosts = tr.wait()
                if counters is not None:
                    counters["save_d2h_bytes"] += sum(h.nbytes for h in hosts)
                    counters["save_d2h_wait_us"] += (
                        time.perf_counter() - t_wait
                    ) * 1e6
                    for t, _, m, _ in plan:
                        # By kind, and their total (a caller's own dict may
                        # name neither: the keys are made here).
                        for key in ("save_bytes", f"save_{t.kind}_bytes"):
                            counters[key] = counters.get(key, 0) + m * t.nbytes
                # Where each tensor's blocks lie on the host: in the one
                # packed array at its offset, or in an array of its own.
                bases = (
                    [hosts[0].ctypes.data + off for _, _, _, off in plan]
                    if len(hosts) == 1 else [h.ctypes.data for h in hosts]
                )
                # The class as it stands NOW: the caller may have started
                # waiting (promoted the cell) since the last layer went out.
                pri_kw = wire.qos_kwargs(self.conn, pri_cell["value"])
                if counters is not None:
                    counters["save_puts"] += len(plan)
                    if not pri_kw:
                        counters["save_fg_puts"] += len(plan)
                    if started_fg:
                        if pos == 0:
                            counters["save_fg_writes"] += 1
                        if pos == 0 or waited:
                            counters["save_fg_rounds"] += 1
                    elif foreground() and not promoted:
                        promoted = True
                        counters["save_promotions"] += 1
                waited = False
                futs = tuple(
                    asyncio.ensure_future(self.conn.write_cache_async(
                        [(key_fn(layer, t.name, i), i * t.nbytes) for i in range(n)],
                        t.nbytes, base, **pri_kw))
                    for (t, _, _, _), base in zip(plan, bases)
                )
                if counters is not None:
                    # `save_put_bytes` over `save_put_busy_us`: what the
                    # store took over the time a save's put was in flight.
                    for fut, (t, _, _, _) in zip(futs, plan):
                        _busy_step(counters, "save_put", +1)
                        fut.add_done_callback(functools.partial(
                            _save_put_done, counters, n * t.nbytes))
                staged.popleft()
                inflight.append((futs, tr, len(plan) * n, lspan))
                top_up()  # refill the D2H pipeline before blocking again
            while inflight:
                total += await drain_one()
        finally:
            # On error, still wait for anything in flight before dropping the
            # host buffers — the native reactor may be mid-writev on them
            # (a dead connection fails these futures promptly via fail_all).
            while inflight:
                futs, tr, _, lspan = inflight.popleft()
                try:
                    await asyncio.gather(*futs, return_exceptions=True)
                finally:
                    tr.release()
                    if lspan is not None:
                        lspan.finish(status="error:aborted")
            for _, _, _, lspan in staged:  # gathered, never shipped
                if lspan is not None:
                    lspan.finish(status="error:aborted")
        return total


class LayerwiseKVReader:
    """Fetch a request's KV blocks from the store layer by layer, scattering
    into the paged cache; network get of layer l+1 overlaps the device upload
    + scatter of layer l. Reads land in the pool — same-host that is the
    server-mapped segment (one-RTT GetInto) — and jax uploads straight from
    it."""

    def __init__(self, conn, pool: HostStagingPool, spec: PagedKVCacheSpec,
                 max_blocks: int):
        self.conn = conn
        self.spec = spec
        self.regions = _LayerRegions(pool, spec, max_blocks)

    async def read(
        self,
        caches: Sequence[Tuple[jax.Array, jax.Array]],
        block_ids: np.ndarray,
        key_fn: KeyFn,
        on_layer=None,
        priority: int = wire.PRIORITY_FOREGROUND,
    ) -> List[Tuple[jax.Array, jax.Array]]:
        """Returns the updated per-layer (K, V) cache list.

        ``priority``: QoS class of the per-layer store reads
        (wire.PRIORITY_*). The one-phase load is decode-blocking, so
        FOREGROUND is the default; a speculative caller may tag
        BACKGROUND so the fetches yield to live decode traffic
        (docs/qos.md). The tag is dropped on QoS-unaware connections
        (wire.qos_kwargs).

        ``on_layer(layer, (k, v))``: optional hook invoked as each layer's
        scatter is ISSUED (layers complete in order 0..L-1) with that
        layer's updated cache arrays — the seam a layer-by-layer engine
        contract (vllm_v1.wait_for_layer_load) gates on. The arrays are
        dispatched, not necessarily materialized; callers that hand them to
        compute get correct results via jax's program order."""
        n = len(block_ids)
        num_layers = len(caches)
        if n == 0:
            return list(caches)
        if n > self.regions.max_blocks:
            raise ValueError(f"{n} blocks > reader capacity {self.regions.max_blocks}")
        ids = np.asarray(block_ids, np.int32)
        pool = self.regions.pool

        # What a hit fetches of each layer's tensors: a sliding layer's last
        # window / block_tokens blocks, a state's last block, else every block
        # (CacheTensor.last_blocks).
        plans = [_layer_plan(self.spec, layer, n, hit=True) for layer in range(num_layers)]

        def fetch(layer: int):
            # The layer's tensors one after the other in one region span (K
            # blocks then V blocks); one store read a value size.
            base = self.regions.base_offset(layer % self.regions.count)
            pri_kw = wire.qos_kwargs(self.conn, priority)
            return asyncio.gather(*(
                self.conn.read_cache_async(blocks, nbytes, pool.base_ptr, **pri_kw)
                for nbytes, blocks in _plan_reads(plans[layer], key_fn, layer, base)
            ))

        # Pipeline: with R regions, keep W = R-2 network fetches in flight
        # ahead of device consumption. A region is reused only once its
        # previous occupant's UPLOAD (the layer's one device_put) has landed —
        # never its scatters, which queue on the device and must not gate the
        # host loop. The barrier targets a transfer dispatched W layers ago,
        # so several H2D uploads stay in flight instead of serializing.
        R = self.regions.count
        W = max(1, R - 2)
        out: List[Tuple[jax.Array, jax.Array]] = list(caches)
        fetches = {}
        uploads = {}

        copies = _device_put_copies()

        def start(f: int):
            if f < num_layers and f not in fetches:
                occupant = f - R
                if occupant >= 0:
                    # Region free once the device consumed its bytes.
                    jax.block_until_ready(uploads.pop(occupant))
                    if not copies:
                        # Zero-copy backend: the upload aliases the region;
                        # only the scatters' completion frees it.
                        jax.block_until_ready(out[occupant])
                fetches[f] = fetch(f)

        try:
            for f in range(min(W, num_layers)):
                start(f)
            for layer in range(num_layers):
                await fetches.pop(layer)
                # ONE device_put a layer: its tensors' host views side by side.
                uploads[layer], (out[layer],) = _upload_and_scatter(
                    [plans[layer]], pool.buf, [self.regions.base_offset(layer % R)],
                    [out[layer]], ids,
                )
                if on_layer is not None:
                    on_layer(layer, out[layer])
                start(layer + W)
        except Exception as exc:
            # Already-scattered layers donated their input buffers; the
            # caller's original list is unusable on TPU. Ship the partial
            # result with the error so recovery paths return live arrays.
            raise PartialReadError(out, exc) from exc
        finally:
            # Failure drain: pending fetches would otherwise keep writing
            # into regions a subsequent read() on this pool is using. The
            # pool may also be reused (or freed) by the caller as soon as we
            # return, so every staged byte must be consumed by the device.
            if fetches:
                await asyncio.gather(*fetches.values(), return_exceptions=True)
            jax.block_until_ready(list(uploads.values()))
            jax.block_until_ready(out)
        return out


def _save_put_done(counters: dict, nbytes: int, fut) -> None:
    _busy_step(counters, "save_put", -1)
    if not fut.cancelled() and fut.exception() is None:
        counters["save_put_bytes"] += nbytes


def _busy_step(counters: dict, op: str, step: int) -> None:
    """One ``op`` (``hit_read``: a hit's layer read; ``save_put``: a put of
    a save) goes in flight (``step`` +1) or comes back (-1): the time since
    the last change counts into ``<op>_busy_us`` where at least one was in
    flight through it. The UNION of their time, so that ``<op>_bytes`` over
    it is the rate the store delivered (took) while anyone was asking,
    however many asked at once."""
    now = time.perf_counter()
    if counters[f"{op}s_in_flight"] > 0:
        counters[f"{op}_busy_us"] += (now - counters[f"{op}_busy_mark_s"]) * 1e6
    counters[f"{op}_busy_mark_s"] = now
    counters[f"{op}s_in_flight"] += step


class PrefetchDiscarded(RuntimeError):
    """install() was called on a prefetch that was discarded (or the
    prefetch was discarded out from under a waiter)."""


class LayerwisePrefetch:
    """The two-phase split of :class:`LayerwiseKVReader`: a gate-free FETCH
    (store -> reserved host staging regions, running the moment the object
    is constructed) and a short device INSTALL (host -> HBM upload +
    scatter) the engine runs under its exclusive cache discipline.

    The reader's monolithic ``read`` forces the caller to hold its
    cache-mutation lock across the whole network fetch; splitting lets the
    fetch overlap other requests' compute and start speculatively at
    admission, before the engine has even allocated device blocks — the
    block table is only needed at :meth:`install`.

    Layout: ``regions`` staging regions, each one contiguous span of a
    layer's tensors one after the other ([K blocks | V blocks]), reserved
    from the pool as ONE lease. A region a layer by default, each as large
    as its own layer's hit: every layer's read starts at construction and
    none waits for the install, so the whole hit is fetched before the gate
    and :meth:`install` hands every staged layer to the device in one
    executor call. (A speculative prefetch, BACKGROUND class, keeps
    ``SPECULATIVE_READ_BYTES`` of reads in flight, of a heavy hit one
    layer's, until :meth:`promote`, which sends the rest foreground.) Only
    where the pool cannot hold that (or ``regions=``
    asks for fewer) does the pipeline wrap: regions of the heaviest layer's
    size, layer L fetches into region ``L % regions`` and a region is
    refilled only after :meth:`install` consumed its occupant (double
    buffering); install then goes run by run, each run the layers that sit
    staged when it looks, and waits under the gate for the rest.

    Cancellation (:meth:`discard`) is safe at ANY point before install:
    in-flight store reads are drained (they write into leased memory),
    then the lease is released — pool accounting returns to baseline and
    the staged bytes are counted as waste (``wasted_blocks``).

    Single event loop: construct, install, and discard from the same
    running loop (the fetch tasks and consumed-events bind to it)."""

    def __init__(
        self,
        conn,
        pool: HostStagingPool,
        spec: PagedKVCacheSpec,
        key_fn: KeyFn,
        n_blocks: int,
        num_layers: int,
        regions: Optional[int] = None,
        submit=None,
        priority: int = wire.PRIORITY_FOREGROUND,
        priority_cell: Optional[dict] = None,
        retry_missing_s: float = 0.0,
        retry_interval_s: float = 0.002,
        fetch_gate=None,
        counters: Optional[dict] = None,
    ):
        """``submit(blocks)``: optional override for the store read (the
        connector's fetch coalescer batches concurrent admissions' reads
        into shared calls); default is a direct ``read_cache_async``.
        ``priority``: QoS class for the default submit's store reads —
        admission-blocking fetches stay FOREGROUND (untagged); a
        speculative prefetch beyond the next wave may be tagged
        BACKGROUND (docs/qos.md). Ignored when ``submit`` is given (the
        coalescer owns tagging there).
        ``retry_missing_s`` > 0 switches a layer's KeyNotFound from "dooms
        the prefix" to a bounded re-probe loop (every
        ``retry_interval_s``): the handoff mode, where the decode side's
        fetch legitimately RACES the prefill side's layer ships
        (docs/disaggregation.md) and a missing key usually means "not
        shipped yet", not "evicted". Each re-probe counts into
        :attr:`retry_stalls`; past the deadline the error keeps its normal
        miss semantics (the watermark path falls back to recompute).
        ``fetch_gate``: optional ``async fetch_gate(layer)`` awaited before
        layer ``layer``'s store read issues — the ANNOUNCE-DRIVEN handoff
        mode: when the producer can signal per-layer publication (same
        process, or a control channel), gating on the announcement replaces
        blind re-probing, so the reader never burns store round trips on
        keys that cannot exist yet. Composable with ``retry_missing_s``
        (the gate bounds when to START, the retry rides any residual race).
        ``counters``: the connector's own ledger (``KVConnector.hit_counters``,
        every key of it); every layer that lands adds the store values it
        fetched to ``hit_values_fetched`` (those of tensors a hit installs in
        its trailing blocks only, a sliding layer's K and V or a state, to
        ``hit_window_values_fetched`` too), what every block of that layer
        would have been to ``hit_values_whole_prefix`` and its bytes to
        ``hit_read_bytes``; ``hit_read_busy_us`` is the time in which at
        least one layer read was in flight (``_busy_step``), and an
        install adds ``install_upload_bytes`` / ``install_upload_us`` and,
        a run of staged layers, their count to ``install_layers`` and one
        to ``install_dispatches``.
        Raises :class:`~..tpu.staging.StagingPoolExhausted` when the pool
        cannot hold even a double-buffered pipeline."""
        self.conn = conn
        self.pool = pool
        self.spec = spec
        self.n_blocks = n_blocks
        self.num_layers = num_layers
        self.hit_blocks = n_blocks  # overridden by the connector's lookup
        # Per layer, what this hit fetches and installs of each tensor, and
        # where it lies in the layer's region: every block, a sliding layer's
        # last window / block_tokens blocks, a state's last block
        # (CacheTensor.last_blocks).
        self._plans = [_layer_plan(spec, l, n_blocks, hit=True) for l in range(num_layers)]
        self._counters = counters
        # QoS class cell read per submission (not captured once): promote()
        # flips it when the request is ADMITTED — a speculative background
        # prefetch whose request made it into the engine is decode-blocking
        # from that moment, and leaving it background would serve the
        # install at the aged background trickle. A caller whose ``submit``
        # override tags its own store calls shares ITS cell via
        # ``priority_cell`` so promote() flips that closure too (the
        # connector's coalescer path).
        self._pri_cell = (
            priority_cell if priority_cell is not None else {"value": priority}
        )
        # Set once the reads go out foreground (from the start, or by
        # promote()): until then the prefetch is speculative and keeps
        # SPECULATIVE_READ_BYTES of layer reads in flight (`_fetch_layer`), so
        # that what its request's admission finds unsent is most of a heavy
        # hit, not none of it.
        self._admitted: Optional[asyncio.Future] = None  # no reads, no hold
        self.blocks_fetched = 0  # K+V blocks landed in staging
        self.blocks_installed = 0  # K+V blocks scattered to the device
        self.retry_missing_s = retry_missing_s
        self.retry_interval_s = retry_interval_s
        self._fetch_gate = fetch_gate
        self.retry_stalls = 0  # KeyNotFound re-probes (handoff read-racing-write)
        self.wait_stalls = 0  # install_layer() calls that blocked on staging
        self.fetch_started_s = time.perf_counter()
        self.fetch_finished_s: Optional[float] = None
        self._cancelled = False
        self._discarded = False
        self._error: Optional[BaseException] = None  # first store failure
        self._lease = None
        if n_blocks == 0:
            self.regions = 0
            self._staged: List[asyncio.Future] = []
            self._consumed: List[asyncio.Event] = []
            self._drained = asyncio.Event()
            self._drained.set()
            self.fetch_finished_s = self.fetch_started_s
            return
        # Regions in whole pool slots. A region a layer is as large as ITS
        # layer's hit (a state-only layer's few MiB beside a K/V layer's
        # hundreds: the host pays for what the hit weighs, not for layers x
        # the heaviest); fewer regions wrap, so each must hold any layer:
        # the heaviest's hit (a K/V cache: 2*n_blocks blocks, either way).
        layer_slots = spec.hit_slots(n_blocks, pool.block_size)[:num_layers]
        slots_per_region = max(layer_slots)
        want = num_layers if regions is None else regions
        want = max(2, min(want, num_layers)) if num_layers > 1 else 1
        # Degrade to a shallower pipeline before giving up: fewer regions
        # only means more install/fetch handoffs, not less data. (Of unlike
        # layers the first wrap, N-1 regions of the heaviest's size, is as a
        # rule LARGER than a region a layer was: those attempts fail at once
        # and the loop settles where r x the heaviest fits, often at 2.)
        lease = None
        for r in range(want, (1 if num_layers == 1 else 2) - 1, -1):
            try:
                lease = pool.reserve(
                    sum(layer_slots) if r == num_layers else r * slots_per_region
                )
                self.regions = r
                break
            except Exception:
                if r <= (1 if num_layers == 1 else 2):
                    raise
        self._lease = lease
        if self.regions == num_layers:
            firsts = [sum(layer_slots[:layer]) for layer in range(num_layers)]
        else:
            firsts = [(layer % self.regions) * slots_per_region for layer in range(num_layers)]
        # Per layer, the byte offset of its region in the lease.
        self._region_at = [first * pool.block_size for first in firsts]
        self._speculative_ahead = max(
            1, SPECULATIVE_READ_BYTES // max(_plan_nbytes(plan) for plan in self._plans)
        )
        pri_cell = self._pri_cell  # closure reads the LIVE class (promote())
        self._submit = submit or (
            lambda blocks, nbytes: conn.read_cache_async(
                blocks, nbytes, pool.base_ptr,
                **wire.qos_kwargs(conn, pri_cell["value"]),
            )
        )
        loop = asyncio.get_running_loop()
        self._admitted = loop.create_future()
        if self._pri_cell["value"] == wire.PRIORITY_FOREGROUND:
            self._admitted.set_result(None)
        self._staged = [loop.create_future() for _ in range(num_layers)]
        for fut in self._staged:
            # Defensively retrieve exceptions: a prefetch discarded before
            # install must not spew "exception was never retrieved".
            fut.add_done_callback(
                lambda f: f.exception() if not f.cancelled() else None
            )
        self._consumed = [asyncio.Event() for _ in range(num_layers)]
        self._installing: set = set()  # layers whose bytes the device reads
        self._drained = asyncio.Event()
        self._key_fn = key_fn
        self._tasks = [
            asyncio.ensure_future(self._fetch_layer(layer))
            for layer in range(num_layers)
        ]
        self._live = len(self._tasks)
        for t in self._tasks:
            t.add_done_callback(self._on_task_done)

    # -- fetch phase (gate-free) --------------------------------------------

    def _region_offset(self, layer: int) -> int:
        return self._lease.offset + self._region_at[layer]

    async def _fetch_layer(self, layer: int):
        plan = self._plans[layer]
        values, nbytes = sum(m for _, _, m, _ in plan), _plan_nbytes(plan)
        counters = self._counters
        # `fetch_layer`: one span a layer of a hit, a child of whatever span
        # started the prefetch (the engine's `engine_request`) and the
        # ACTIVE span of this task, so the store read below stamps it
        # (`coalesce`, `submit`, `completion_ring`) and puts its id on the
        # wire: the server's ticks hang under the layer they served.
        # `queued` -> `region_free` is the wait for install to hand the
        # staging region on (and an announce-driven handoff's gate),
        # `region_free` -> `landed` the read.
        with tracing.trace_op("fetch_layer", stage="queued") as span:
            if span is not None:
                span.annotate(
                    layer=layer, region=layer % self.regions,
                    values=values, bytes=nbytes, kind=plan[0][0].kind,
                )
            if self._fetch_gate is not None:
                # Announce-driven handoff: wait for the producer's per-layer
                # publication signal before spending a store round trip.
                await self._fetch_gate(layer)
            if layer >= self.regions:
                # Double buffering: refill a region only once install
                # consumed (or discard wrote off) its previous occupant.
                await self._consumed[layer - self.regions].wait()
            if span is not None:
                span.stage("region_free")
            ahead = self._speculative_ahead
            if layer >= ahead and not self._admitted.done():
                # Speculative (BACKGROUND class: the request is not admitted
                # yet): this layer's read goes out when the layer `ahead`
                # before it has landed, or at promote(). A read already at
                # the server keeps its class, and with a region a layer every
                # read would be there: a heavy hit whole at the aged
                # background trickle, with nothing left for promote() to send
                # foreground.
                await asyncio.wait(
                    [self._staged[layer - ahead], self._admitted],
                    return_when=asyncio.FIRST_COMPLETED,
                )
            if self._cancelled:
                if span is not None:
                    span.finish(status="cancelled")
                return
            reads = _plan_reads(plan, self._key_fn, layer, self._region_offset(layer))
            if counters is not None:
                _busy_step(counters, "hit_read", +1)
            try:
                # One store read a value size: ONE for a K/V layer.
                if len(reads) == 1:
                    await self._submit_with_retry(reads[0][1], reads[0][0])
                else:
                    await asyncio.gather(*(
                        self._submit_with_retry(blocks, size) for size, blocks in reads
                    ))
            except asyncio.CancelledError:
                self._cancel_rest()
                raise
            except BaseException as e:
                if span is not None:
                    span.finish(status=f"error:{type(e).__name__}")
                if self._error is None:
                    self._error = e
                if not self._staged[layer].done():
                    self._staged[layer].set_exception(e)
                # One failing layer dooms the whole prefix (a partial prefix
                # has no value) — stop refilling regions.
                self._cancel_rest()
                return
            finally:
                if counters is not None:
                    _busy_step(counters, "hit_read", -1)
            if span is not None:
                span.stage("landed")
            self.blocks_fetched += values
            if counters is not None:
                whole = self.n_blocks * sum(t.nbytes for t, _, _, _ in plan)
                counters["hit_values_fetched"] += values
                counters["hit_values_whole_prefix"] += self.n_blocks * len(plan)
                counters["hit_window_values_fetched"] += sum(
                    m for t, _, m, _ in plan if t.last_blocks is not None
                )
                counters["hit_read_bytes"] += nbytes
                counters["hit_bytes_fetched"] += nbytes
                counters["hit_bytes_whole_prefix"] += whole
                counters["hit_state_bytes_fetched"] += sum(
                    m * t.nbytes for t, _, m, _ in plan if t.kind == "state"
                )
                counters["hit_index_bytes_fetched"] += sum(
                    m * t.nbytes for t, _, m, _ in plan if t.kind == "index"
                )
            if not self._staged[layer].done():
                self._staged[layer].set_result(layer % self.regions)
            if layer == self.num_layers - 1:
                self.fetch_finished_s = time.perf_counter()

    async def _submit_with_retry(self, blocks, nbytes: int):
        """The store read, with the handoff mode's bounded KeyNotFound
        re-probe loop (``retry_missing_s``; docs/disaggregation.md): a key
        the prefill side has not shipped YET is a stall, not a miss —
        until the deadline, after which the error keeps its normal
        semantics and the caller's fallback machinery takes over."""
        if self.retry_missing_s <= 0:
            await self._submit(blocks, nbytes)
            return
        deadline = time.perf_counter() + self.retry_missing_s
        while True:
            try:
                await self._submit(blocks, nbytes)
                return
            except InfiniStoreKeyNotFound:
                if self._cancelled or time.perf_counter() >= deadline:
                    raise
                self.retry_stalls += 1
                await asyncio.sleep(self.retry_interval_s)

    def _on_task_done(self, task):
        if not task.cancelled() and task.exception() is not None:
            # _fetch_layer catches store errors itself; anything here is a
            # bug or a cancellation-at-teardown — don't lose it silently.
            self._cancel_rest()
        self._live -= 1
        if self._live == 0:
            self._drained.set()
            self._maybe_release()

    # -- lifecycle -----------------------------------------------------------

    def _cancel_rest(self):
        """Stop refilling regions and write off layers that never staged.
        Layers that DID stage successfully are NOT written off here: a
        later install() may still legally read them from the lease, and
        marking them consumed would release the lease under its feet (a
        concurrent prefetch could re-reserve and overwrite the slots).
        They are written off by install()'s own abort paths or discard()
        — the two places that guarantee no further reads."""
        if self._cancelled:
            return
        self._cancelled = True
        for fut in self._staged:
            if not fut.done():
                fut.cancel()
        for layer, ev in enumerate(self._consumed):
            fut = self._staged[layer]
            staged_ok = fut.done() and not fut.cancelled() and fut.exception() is None
            if layer not in self._installing and not staged_ok:
                ev.set()

    def _write_off_uninstalled(self):
        """Mark every layer the device will never read as consumed (call
        only when no further install reads can happen: install() aborting,
        or discard())."""
        for layer, ev in enumerate(self._consumed):
            if layer not in self._installing:
                ev.set()
        self._maybe_release()

    def _maybe_release(self):
        if (
            self._lease is not None
            and self._drained.is_set()
            and all(ev.is_set() for ev in self._consumed)
        ):
            self._lease.release()

    @property
    def wasted_blocks(self) -> int:
        """Blocks fetched into staging that never reached the device —
        meaningful once the prefetch settled (installed or discarded)."""
        return max(0, self.blocks_fetched - self.blocks_installed)

    def promote(self) -> None:
        """Upgrade the remaining fetch to FOREGROUND class. Engines call
        this the moment the request is ADMITTED (block pool allocated): a
        speculative BACKGROUND prefetch is opportunistic only while its
        request waits beyond the next wave — once admitted, its remaining
        layer fetches are decode-blocking and must not drain at the aged
        background trickle. Submissions already in flight finish at their
        original class (bounded by the aging escapes); later ones go out
        untagged: of a heavy hit every layer but the one in flight, since a
        speculative prefetch holds back what ``SPECULATIVE_READ_BYTES`` does
        not cover. No-op on an
        already-foreground prefetch. Idempotent."""
        self._pri_cell["value"] = wire.PRIORITY_FOREGROUND
        if self._admitted is not None and not self._admitted.done():
            self._admitted.set_result(None)

    async def primed(self) -> None:
        """Wait (gate-free) until the fetch pipeline is full: every staging
        region holds a layer — or every layer is staged, whichever is less.
        Entering the exclusive install phase before this point would hold
        the engine's gate across raw network time; after it, install
        consumes at device speed while any remaining layers fetch into the
        regions it frees. Store errors do NOT raise here — they surface
        with proper miss/partial semantics from :meth:`install`."""
        if self.n_blocks == 0:
            return
        # Every one of them, not the last alone: reads need not land in layer
        # order (a speculative prefetch's one background read lands behind
        # the foreground rest), and a layer still on the wire at the install
        # is waited for under the gate.
        await asyncio.wait(self._staged[: min(self.num_layers, self.regions)])

    async def discard(self) -> None:
        """Cancel the prefetch and return every staging slot to the pool.
        Safe at any point except concurrently with install(); counts the
        staged-but-never-installed bytes as waste. Idempotent."""
        self._discarded = True
        self._cancel_rest()
        # install() is forbidden from here on, so staged-but-uninstalled
        # layers can be written off wholesale.
        self._write_off_uninstalled()
        await self._drained.wait()
        for ev in self._consumed:
            await ev.wait()
        if self._lease is not None:
            self._lease.release()

    # -- install phase (device; caller holds its cache-mutation discipline) --

    def _release_region_async(self, layers, uploads, outs, loop):
        """Mark regions consumed once the device actually copied (or, on
        the zero-copy CPU backend, finished computing through) their bytes
        — off-thread, so the caller's gate-held install stays short."""
        copies = _device_put_copies()

        def wait_and_mark():
            jax.block_until_ready(uploads)
            if not copies:
                jax.block_until_ready(outs)

            def mark():
                for layer in layers:
                    self._consumed[layer].set()
                self._maybe_release()

            try:
                loop.call_soon_threadsafe(mark)
            except RuntimeError:
                # Loop closed at teardown: nothing will reuse the regions;
                # release the lease directly so the pool is never leaked.
                for layer in layers:
                    self._consumed[layer].set()
                self._maybe_release()

        loop.run_in_executor(None, wait_and_mark)

    async def install(
        self,
        caches: Sequence[Tuple[jax.Array, jax.Array]],
        block_ids: np.ndarray,
        on_layer=None,
    ):
        """Scatter the staged prefix into the engine's paged cache; returns
        ``(updated caches, blocks_loaded)`` with :meth:`KVConnector.load`'s
        exact semantics (DONATION of inputs; raced-away blocks -> partial
        caches and 0 loaded; ``on_layer`` fires per layer in order).

        This is the only phase that needs the engine's exclusive cache
        gate; the host bytes usually sit staged already, so the hold is
        device-transfer time, not store time. The device is reached once a
        RUN of staged layers, not once a layer: at layer L every layer from
        L on that has landed and is healthy goes up in ONE executor call (one
        ``jax.device_put`` of the run's host views, then its scatters), so a
        fully staged hit is one thread hop whatever its layer count or cache
        kind, and a layer still on the network is waited for and starts the
        next run."""
        if self._discarded:
            raise PrefetchDiscarded("install() after discard()")
        out = list(caches)
        if self.n_blocks == 0:
            return out, 0
        n = self.n_blocks
        if len(block_ids) != n:
            raise ValueError(
                f"install needs exactly the {n} fetched blocks' placement, "
                f"got {len(block_ids)} block ids"
            )
        if len(caches) != self.num_layers:
            raise ValueError(
                f"cache list has {len(caches)} layers, prefetch fetched "
                f"{self.num_layers}"
            )
        ids = np.asarray(block_ids, np.int32)
        loop = asyncio.get_running_loop()
        # Children of the caller's span (the engine's `install`, whose
        # duration is the exclusive gate's hold): one `install_upload` a run,
        # just before `run_in_executor` to its return (`started`: the
        # executor thread entered, `h2d`: `device_put` returned; the thread
        # does not inherit the span, so it is handed in, and the
        # `its.install` device call is on it), and `install_staged_wait` for
        # each layer that had NOT landed when the install reached it: the
        # exclusive gate waiting for the network.
        counters = self._counters

        def dev_run(first, tensors, uspan):
            t_up = time.perf_counter()
            if uspan is not None:
                uspan.stage("started")
            layers = range(first, first + len(tensors))
            with tracing.device_call("its.install", uspan):
                uploads, scattered = _upload_and_scatter(
                    self._plans[first : layers.stop], self.pool.buf,
                    [self._region_offset(layer) for layer in layers], tensors, ids,
                    uploaded=None if uspan is None else lambda: uspan.stage("h2d"),
                )
            return uploads, scattered, (time.perf_counter() - t_up) * 1e6

        layer = 0
        while layer < self.num_layers:
            fut = self._staged[layer]
            try:
                if fut.done():
                    await asyncio.shield(fut)
                else:
                    with tracing.trace_op("install_staged_wait") as wspan:
                        if wspan is not None:
                            wspan.annotate(layer=layer)
                        await asyncio.shield(fut)
            except asyncio.CancelledError:
                if not fut.cancelled():
                    raise  # the INSTALLING task was cancelled, not the fetch
                # A DEEPER layer's store failure cancels shallower pending
                # futures (completion order is not layer order) — surface
                # that first error's semantics, not a bogus "discarded".
                self._write_off_uninstalled()  # no further reads from here
                err = self._error
                if err is None:
                    raise PrefetchDiscarded(
                        f"prefetch discarded before layer {layer}"
                    )
                if isinstance(
                    err, (InfiniStoreKeyNotFound, InfiniStoreResourcePressure)
                ):
                    return out, 0
                raise PartialReadError(out, err) from err
            except (InfiniStoreKeyNotFound, InfiniStoreResourcePressure):
                # Blocks raced away (eviction between lookup and read) or
                # the store shed load: cache semantics — report a miss, the
                # engine recomputes. Layers already scattered donated their
                # inputs, so the partial list is the only valid one.
                self._cancel_rest()
                self._write_off_uninstalled()
                return out, 0
            except Exception as e:
                self._cancel_rest()
                self._write_off_uninstalled()
                raise PartialReadError(out, e) from e
            if self._lease is None or self._lease._released:
                # Belt and braces: never read staging memory after the
                # lease went back to the pool (another prefetch may own the
                # slots now) — treat as the miss it semantically is.
                return out, 0
            # The run: this layer and those behind it that sit staged now. A
            # layer that shares a region with one of them cannot be among
            # them: its read starts only once that one is consumed.
            end = layer + 1
            while end < self.num_layers and self.layer_ready(end):
                end += 1
            nbytes = sum(_plan_nbytes(plan) for plan in self._plans[layer:end])
            # Off-loop: upload + scatter must not freeze other requests'
            # fetch completions; the caller's gate still serializes the
            # cache mutation across the await.
            with tracing.trace_op("install_upload") as uspan:
                if uspan is not None:
                    uspan.annotate(layer=layer, layers=end - layer, bytes=nbytes)
                uploads, scattered, upload_us = await loop.run_in_executor(
                    None, dev_run, layer, out[layer:end], uspan
                )
            if counters is not None:
                counters["install_upload_bytes"] += nbytes
                counters["install_upload_us"] += upload_us
                counters["install_layers"] += end - layer
                counters["install_dispatches"] += 1
            out[layer:end] = scattered
            for done in range(layer, end):
                self._installing.add(done)
                self.blocks_installed += sum(m for _, _, m, _ in self._plans[done])
                if on_layer is not None:
                    on_layer(done, out[done])
            self._release_region_async(
                list(range(layer, end)), uploads, scattered, loop
            )
            layer = end
        return out, n

    # -- per-layer handles (watermark-gated decode admission) ----------------

    def layer_ready(self, layer: int) -> bool:
        """True once ``layer``'s bytes sit staged and healthy — the
        watermark plane's non-blocking probe (how many layers are still in
        flight at first-token time is counted off this)."""
        if self.n_blocks == 0:
            return True
        fut = self._staged[layer]
        return fut.done() and not fut.cancelled() and fut.exception() is None

    async def install_layer(
        self,
        caches: Sequence[Tuple[jax.Array, jax.Array]],
        block_ids: np.ndarray,
        layer: int,
        on_layer=None,
    ):
        """Install ONE layer's staged prefix — the watermark rule's unit of
        admission (docs/disaggregation.md): layer l's attention launches
        after ``install_layer(..., l)`` returns True, while layers > l are
        still on the network. Returns ``(updated caches, ok)``; only
        ``caches[layer]`` changes (donated like :meth:`install`).

        Call with strictly increasing ``layer`` — staging regions wrap, and
        region ``l % regions`` is refilled only after layer ``l`` is
        consumed, so out-of-order installs deadlock the fetch pipeline.
        ``ok`` False means the layer is unavailable (missing past the retry
        deadline, store failure, or discarded): the prefetch is written off
        and the caller must fall back to recompute — never read the
        partial prefix as if it were complete."""
        if self._discarded:
            raise PrefetchDiscarded("install_layer() after discard()")
        out = list(caches)
        if self.n_blocks == 0:
            return out, True
        n = self.n_blocks
        if len(block_ids) != n:
            raise ValueError(
                f"install_layer needs exactly the {n} fetched blocks' "
                f"placement, got {len(block_ids)} block ids"
            )
        if len(caches) != self.num_layers:
            raise ValueError(
                f"cache list has {len(caches)} layers, prefetch fetched "
                f"{self.num_layers}"
            )
        if layer in self._installing:
            raise ValueError(f"layer {layer} already installed")
        fut = self._staged[layer]
        if not fut.done():
            # The compute side outran the transfer: a genuine watermark
            # stall (the overlap's residual wait, counted for /metrics).
            self.wait_stalls += 1
        try:
            await asyncio.shield(fut)
        except asyncio.CancelledError:
            if not fut.cancelled():
                raise  # the INSTALLING task was cancelled, not the fetch
            self._write_off_uninstalled()
            return out, False
        except Exception:
            # Missing past the retry deadline, shed load, or transport
            # failure: one verdict for the watermark path — this layer is
            # unavailable, fall back (the error already routed through the
            # connector's degrade machinery on the fetch side).
            self._cancel_rest()
            self._write_off_uninstalled()
            return out, False
        if self._lease is None or self._lease._released:
            return out, False
        loop = asyncio.get_running_loop()
        plan = self._plans[layer]

        def dev_one(tensors):
            return _upload_and_scatter(
                [plan], self.pool.buf, [self._region_offset(layer)], [tensors],
                np.asarray(block_ids, np.int32),
            )

        kv_dev, (out[layer],) = await loop.run_in_executor(
            None, dev_one, out[layer]
        )
        self._installing.add(layer)
        self.blocks_installed += sum(m for _, _, m, _ in plan)
        if on_layer is not None:
            on_layer(layer, out[layer])
        self._release_region_async([layer], kv_dev, out[layer], loop)
        return out, True
