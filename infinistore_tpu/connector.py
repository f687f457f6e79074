"""Engine-facing KV-cache connector: the LMCache-style glue layer.

The reference integrates with vLLM "through LMCache" (reference README.md:22):
the engine never speaks the store protocol directly — a connector hashes token
prefixes into chain keys, asks the store how much of a prompt is already
cached (`get_match_last_index`, reference src/infinistore.cpp:786-798), and
streams paged-KV blocks layer by layer. This module is that connector for
JAX/TPU engines: it binds a paged cache spec + host staging pool + store
connection to a model id and exposes lookup / save / load in engine terms
(token ids and block ids), with the chain-hash key scheme that makes
cross-request prefix reuse work (reference docs/source/design.rst:50).

Key scheme: ``{model}/L{layer}/{k|v}/{chain_hash_i}`` where ``chain_hash_i``
is a rolling SHA-256 over token blocks [0..i]. A block's key therefore commits
to the *entire prefix*, so two prompts share keys exactly for their common
block-aligned prefix — and the store's binary-search prefix match applies.
"""

import asyncio
import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import tracing, wire
from .lib import (
    InfiniStoreColdTier,
    InfiniStoreKeyNotFound,
    InfiniStoreNoMatch,
    InfiniStoreResourcePressure,
)
from .tiering import note_demotion_hit as tiering_note_demotion_hit
from .tpu.layerwise import (
    LayerwiseKVReader,
    LayerwiseKVWriter,
    LayerwisePrefetch,
    PartialReadError,
)
from .tpu.paged import PagedKVCacheSpec
from .tpu.staging import HostStagingPool, StagingPoolExhausted  # noqa: F401 - re-export


def token_chain_hashes(token_ids: Sequence[int], block_tokens: int) -> List[str]:
    """Rolling prefix hash per *complete* token block.

    hash_i covers tokens [0, (i+1) * block_tokens); an incomplete tail block
    is excluded (it cannot be reused — its key would never match another
    request's complete block).
    """
    n_full = len(token_ids) // block_tokens
    hashes = []
    h = hashlib.sha256()
    for i in range(n_full):
        chunk = np.asarray(
            token_ids[i * block_tokens : (i + 1) * block_tokens], dtype=np.int64
        )
        h.update(chunk.tobytes())
        hashes.append(h.copy().hexdigest()[:32])
    return hashes


class _ChainHashCache:
    """Incremental chain-hash cache for repeated/extended token prefixes.

    Chain hashes commit to the whole prefix, so an unchanged prefix yields
    byte-identical hashes call after call — yet every connector entry point
    (lookup, load, save, start_fetch, per-layer saves) re-ran one sha256
    update PER BLOCK per call. This caches the last prompt's full-block
    tokens, its chain list, and the live sha256 state after the final full
    block:

    - same prompt again        -> one array compare, zero hashing
    - the cached prompt's own  -> a slice of the cached chains (hash_i only
      prefix (fewer blocks)       depends on tokens [0, (i+1)*block); the
                                  cache keeps the LONGER chain)
    - extended prompt          -> hash only the new tail blocks (decode
                                  steps growing a prompt block by block pay
                                  O(new), not O(total))
    - anything else            -> full recompute, cache replaced

    One entry only, held as ONE tuple read once and swapped atomically
    (the GIL makes the swap safe; sync lookups may run from concurrent
    threads — same discipline as InfinityConnection's match-blob cache):
    admission churn alternating between two prompt families costs a
    recompute, never a wrong hash."""

    __slots__ = ("_state",)

    def __init__(self):
        # (block_tokens, full-block tokens ndarray, chain hashes, sha256
        # state after the last cached full block) — or None before first use.
        self._state: Optional[tuple] = None

    def hashes(self, token_ids: Sequence[int], block_tokens: int) -> List[str]:
        n_full = len(token_ids) // block_tokens
        if n_full == 0:
            return []
        # copy=True matters: for ndarray inputs asarray would keep a VIEW of
        # the caller's buffer, and an engine reusing that buffer for the next
        # prompt would mutate our cached tokens into falsely matching it —
        # returning the OLD prompt's hashes (another request's KV keys).
        toks = np.array(token_ids[: n_full * block_tokens], dtype=np.int64, copy=True)
        state = self._state  # one read: threads race the swap, never a tear
        if state is not None and state[0] == block_tokens:
            _, c_toks, c_hashes, c_h = state
            if toks.size <= c_toks.size and np.array_equal(
                toks, c_toks[: toks.size]
            ):
                # Repeat or prefix of the cached prompt: pure cache read
                # (keep the longer entry — serving its prefixes is free).
                return c_hashes[:n_full]
            if toks.size > c_toks.size and np.array_equal(
                toks[: c_toks.size], c_toks
            ):
                # Extension: hash only the new tail blocks.
                h = c_h.copy()
                hashes = list(c_hashes)
                for i in range(len(hashes), n_full):
                    h.update(toks[i * block_tokens : (i + 1) * block_tokens].tobytes())
                    hashes.append(h.copy().hexdigest()[:32])
                self._state = (block_tokens, toks, hashes, h)  # atomic swap
                return list(hashes)
        h = hashlib.sha256()
        hashes = []
        for i in range(n_full):
            h.update(toks[i * block_tokens : (i + 1) * block_tokens].tobytes())
            hashes.append(h.copy().hexdigest()[:32])
        self._state = (block_tokens, toks, hashes, h)  # atomic swap
        return list(hashes)


class FetchCoalescer:
    """Merge store reads issued in the same event-loop tick into ONE
    batched ``read_cache_async`` call.

    A wave of concurrent admissions starts one prefetch each; without
    coalescing, every layer of every request is its own store round trip.
    Batched, the wave's reads ride a single call — which a
    ``StripedConnection`` then splits across its connection stripes, so a
    burst of admissions shares the stripes instead of queueing serially.

    All submitters must target the same base pointer (one staging pool)
    and block size; the coalescer only merges, it never copies.

    Merges are SIZED to the connection's fan-out: a striped connection
    reports ``preferred_fanout_blocks()`` (every stripe's maximum per-trip
    pull — more blocks in one call adds no parallelism), and a tick's
    submissions are packed into merged calls of at most that many blocks,
    issued concurrently. This keeps a mega-wave's failure isolation at
    group granularity (one evicted key re-splits its group, not the whole
    wave) without giving up the per-call amortization merging exists for.
    Unstriped connections report no hint and keep the single-merge
    behavior."""

    def __init__(self, conn, block_size: int, base_ptr: int,
                 max_merge_blocks: Optional[int] = None):
        self.conn = conn
        self.block_size = block_size
        self.base_ptr = base_ptr
        if max_merge_blocks is None:
            hint = getattr(conn, "preferred_fanout_blocks", None)
            max_merge_blocks = hint() if callable(hint) else 0
        self.max_merge_blocks = max_merge_blocks or 0  # 0 = unbounded
        self._pending: list = []
        self._flush_scheduled = False
        # Strong refs: the loop holds only weak refs to tasks (same
        # discipline as engine.WaveDecoder).
        self._flush_tasks: set = set()
        self.calls = 0  # batched store calls issued
        self.submissions = 0  # logical submits merged into them
        self.max_batch = 0

    def submit(self, blocks, priority: int = 0) -> "asyncio.Future":
        """Queue one logical read (list of (key, offset-from-base) pairs);
        returns a future resolving when those bytes are staged.
        ``priority``: QoS class (wire.PRIORITY_*) — submissions merge only
        with same-class peers, so a BACKGROUND speculative prefetch never
        drags a FOREGROUND admission fetch into its service class.

        Tracing: the submitter's active span is captured HERE — the flush
        task inherits the contextvars of whichever submitter SCHEDULED it,
        not of each merged peer — and stamped ``coalesce`` when its merged
        batched call issues (docs/observability.md)."""
        fut = asyncio.get_running_loop().create_future()
        self._pending.append((blocks, fut, priority, tracing.active_span()))
        self.submissions += 1
        if not self._flush_scheduled:
            self._flush_scheduled = True
            task = asyncio.ensure_future(self._flush())
            self._flush_tasks.add(task)
            task.add_done_callback(self._flush_tasks.discard)
        return fut

    def _group(self, batch):
        """Pack this tick's submissions into merged-call groups of at most
        ``max_merge_blocks`` blocks (a single oversized submission still
        rides alone — the data plane chunks it internally), partitioned by
        QoS class first so each merged call carries one honest tag."""
        by_class: dict = {}
        for blocks, fut, priority, span in batch:
            by_class.setdefault(priority, []).append((blocks, fut, span))
        groups = []
        for priority, items in by_class.items():
            if not self.max_merge_blocks:
                groups.append((priority, items))
                continue
            cur, cur_blocks = [], 0
            for blocks, fut, span in items:
                if cur and cur_blocks + len(blocks) > self.max_merge_blocks:
                    groups.append((priority, cur))
                    cur, cur_blocks = [], 0
                cur.append((blocks, fut, span))
                cur_blocks += len(blocks)
            if cur:
                groups.append((priority, cur))
        return groups

    async def _flush(self):
        # One yield: everything enqueued this tick joins the batch.
        await asyncio.sleep(0)
        batch, self._pending = self._pending, []
        self._flush_scheduled = False
        if not batch:
            return
        # Eagerly open this tick's ring batch window (no-op off-ring or on
        # a pre-ring connection stand-in): the gathered merged calls — and
        # any per-stripe grandchild tasks a StripedConnection spawns before
        # the window's call_soon flush runs — then publish their ring posts
        # as ONE multi-op batch slot instead of one slot + doorbell each
        # (docs/descriptor_ring.md, batch-slot section).
        window = getattr(self.conn, "ring_batch_window", None)
        if callable(window):
            window()
        await asyncio.gather(*(self._issue(g, p) for p, g in self._group(batch)))

    async def _issue(self, batch, priority: int = 0):
        self.calls += 1
        self.max_batch = max(self.max_batch, len(batch))
        merged = [b for blocks, _, _ in batch for b in blocks]
        pri_kw = wire.qos_kwargs(self.conn, priority)
        # Tracing: every merged submission stamps `coalesce` now; the
        # merged wire op rides the FIRST traced submitter's context (one
        # batched call carries one trace id — siblings still see their
        # merge moment and group size). override_span, not use_span: this
        # flush task INHERITS the scheduling submitter's contextvars, so a
        # fully-untraced group must clear that inherited span or its wire
        # op (and stamps) would be misattributed to an unrelated request.
        lead_span = None
        for _, _, span in batch:
            if span is not None:
                span.stage("coalesce")
                span.annotate(coalesced_group=len(batch))
                if lead_span is None:
                    lead_span = span
        try:
            with tracing.override_span(lead_span):
                await self.conn.read_cache_async(
                    merged, self.block_size, self.base_ptr, **pri_kw
                )
        except Exception as e:
            # Per-submission retry exists to isolate ONE evicted/pressured
            # key from its group-mates. A transport error is different: the
            # whole connection is sick, and re-driving N submissions into it
            # would burn N more timeouts against a dead store — fail the
            # group fast instead (the store's own failover/breaker layers
            # decide what happens next).
            retryable = isinstance(
                e, (InfiniStoreKeyNotFound, InfiniStoreResourcePressure)
            )
            if len(batch) == 1 or not retryable:
                for blocks, fut, _ in batch:
                    if not fut.done():
                        fut.set_exception(e)
                return
            for blocks, fut, span in batch:
                if fut.done():
                    continue
                self.calls += 1
                try:
                    with tracing.override_span(span):
                        await self.conn.read_cache_async(
                            blocks, self.block_size, self.base_ptr, **pri_kw
                        )
                except Exception as e2:
                    fut.set_exception(e2)
                else:
                    fut.set_result(None)
            return
        for _, fut, _ in batch:
            if not fut.done():
                fut.set_result(None)


class KVConnector:
    """Bind one model's paged KV cache to a store connection.

    ``QOS_AWARE``: this connector accepts the two-class priority kwarg on
    ``start_fetch`` (adapters gate forwarding on the attribute so pre-QoS
    connector stand-ins keep working — see docs/qos.md).

    The engine calls, per request:
      - ``lookup(tokens)`` -> how many leading blocks are already cached
      - ``load(tokens, caches, block_ids)`` -> scatter those blocks into the
        engine's paged cache (skipping recompute of the shared prefix)
      - ``save(tokens, caches, block_ids)`` -> stream the request's blocks
        out, layer by layer, overlapping D2H with the network
    """

    QOS_AWARE = True

    def __init__(
        self,
        conn,
        spec: PagedKVCacheSpec,
        model_id: str,
        max_blocks: int,
        pool: Optional[HostStagingPool] = None,
        ici=None,
    ):
        """``ici``: an optional ``IciBlockTransfer`` bound to the SPMD mesh
        this engine runs in. When set, ``handoff`` moves blocks HBM->HBM over
        the interconnect; without it (or across meshes) the same call
        degrades to the DCN store path (SURVEY §7 hard part 4). ``conn`` may
        be None for a pure-ICI connector (no store in the loop)."""
        self.conn = conn
        self.spec = spec
        self.model_id = model_id
        self.max_blocks = max_blocks
        self.ici = ici
        # The hop's ledger (get_stats), always on. Of the hits' prefetches:
        # store values (one block of one tensor of one layer) fetched, and
        # what every block of every layer of the same hits would have been
        # (they differ where a tensor's policy is the hit's trailing blocks,
        # CacheTensor.last_blocks; the values fetched under that policy are
        # counted apart: the part of a hit that does not grow with the
        # prefix), the same two in bytes and, of the bytes
        # fetched, those of a recurrent state (kind "state": what does not
        # grow with the prefix) and those of a latent layer's index keys
        # (kind "index"); of the saves, the bytes written and those
        # of them by the tensor's kind (kv, state, latent, index); the bytes the
        # hits' layer reads landed and the microseconds in which at least one such read was in
        # flight (with the gauge and the perf_counter mark that union is
        # kept by). Of the installs: bytes handed to the device and the
        # summed time of the executor calls that handed them (host time, not
        # the DMA's end), those calls and the layers they carried (one call
        # a run of staged layers). Of the saves: bytes whose D2H the writer waited
        # for, and those waits; put calls submitted and those of them
        # untagged (foreground); writes whose class was flipped to foreground
        # with layers still unsent; writes that STARTED foreground, and over
        # those the rounds of put latency they took (submissions that
        # followed a wait for an earlier group, plus one a write); bytes the
        # puts delivered and the union of the time in which one was in
        # flight (``save_puts_in_flight`` and ``save_put_busy_mark_s`` keep it).
        self.hit_counters = {
            "hit_values_fetched": 0, "hit_values_whole_prefix": 0,
            "hit_window_values_fetched": 0,
            "hit_bytes_fetched": 0, "hit_bytes_whole_prefix": 0,
            "hit_state_bytes_fetched": 0, "hit_index_bytes_fetched": 0,
            "save_bytes": 0, "save_kv_bytes": 0,
            "save_state_bytes": 0, "save_latent_bytes": 0, "save_index_bytes": 0,
            "hit_read_bytes": 0, "hit_read_busy_us": 0.0,
            "hit_reads_in_flight": 0, "hit_read_busy_mark_s": 0.0,
            "install_upload_bytes": 0, "install_upload_us": 0.0,
            "install_layers": 0, "install_dispatches": 0,
            "save_d2h_bytes": 0, "save_d2h_wait_us": 0.0,
            "save_puts": 0, "save_fg_puts": 0, "save_promotions": 0,
            "save_fg_writes": 0, "save_fg_rounds": 0,
            "save_put_bytes": 0, "save_put_busy_us": 0.0,
            "save_puts_in_flight": 0, "save_put_busy_mark_s": 0.0,
        }
        if conn is None:
            # Pure-ICI connector: no store data plane, so don't allocate the
            # (potentially tens of MB) host staging pool it would need.
            self.pool = pool
            self._writer = self._reader = None
        else:
            if pool is None:
                # 6 read-staging regions (K+V each): deep enough that network
                # fetches and H2D uploads overlap several layers (layerwise.py
                # _LayerRegions adapts the pipeline depth to this size).
                pool = HostStagingPool(
                    6 * spec.region_nbytes(max_blocks), spec.slot_nbytes, conn=conn
                )
            self.pool = pool
            self._writer = LayerwiseKVWriter(conn, pool, spec, max_blocks)
            self._writer.counters = self.hit_counters
            self._reader = LayerwiseKVReader(conn, pool, spec, max_blocks)
        # Two-phase admission path (start_fetch): its own staging pool —
        # the reader's ``_LayerRegions`` owns ``pool``'s layout outright, so
        # speculative prefetches reserve from a separate arena. Lazy: only
        # engines on the pipelined path pay for it.
        self._prefetch_pool: Optional[HostStagingPool] = None
        self._coalescers: Dict[int, FetchCoalescer] = {}  # by value size
        # Chain-hash + sentinel-key caches: admission re-derives the same
        # prefix's keys on every lookup/load/save (satellite of the adaptive
        # data-plane PR; BENCH_r05 put the 256-chain lookup at 26.1us with
        # the hashing/keying on top of it).
        self._chain_cache = _ChainHashCache()
        self._keys0_cache: Optional[Tuple[List[str], List[str]]] = None

    def _require_store(self, what: str):
        if self.conn is None:
            raise ValueError(
                f"{what} needs a store connection; this connector was built "
                "conn=None (pure-ICI)"
            )

    # -- key scheme ----------------------------------------------------------

    def block_key(self, layer: int, kind: str, chain_hash: str) -> str:
        """Store key for one block of one tensor: ``{model}/L{layer}/{k|v}/
        {chain_hash}``, ``kind`` the tensor's name (``CacheTensor.name``)."""
        return f"{self.model_id}/L{layer}/{kind}/{chain_hash}"

    def _tensors(self):
        """(layer, tensor) over the whole cache, the sentinel's first."""
        return [
            (layer, t) for layer in range(self.spec.num_layers)
            for t in self.spec.layer_tensors(layer)
        ]

    def _key_fn(self, chains: List[str]):
        def key_fn(layer: int, kind: str, block: int) -> str:
            return self.block_key(layer, kind, chains[block])

        return key_fn

    def _chains(self, token_ids: Sequence[int]) -> List[str]:
        """Chain hashes for this prompt's complete blocks, served from the
        incremental cache (repeat prefixes are an array compare; extensions
        hash only their tail)."""
        return self._chain_cache.hashes(token_ids, self.spec.block_tokens)

    def _sentinel_keys(self, chains: List[str]) -> List[str]:
        """Layer-0 K keys for a chain (the whole-block presence sentinels
        lookups send). Cached: because chain hash i commits to the entire
        prefix, a match on length + final hash proves the whole key list is
        the cached one — repeated admissions of a hot prefix skip N string
        formats per call, and a shorter chain is served as a slice of a
        cached longer one."""
        cached = self._keys0_cache
        n = len(chains)
        if cached is not None:
            c_chains, c_keys = cached
            if len(c_chains) >= n and c_chains[n - 1] == chains[-1]:
                return c_keys[:n]
        sentinel = self.spec.layer_tensors(0)[0].name  # "k" of a K/V cache
        keys = [self.block_key(0, sentinel, c) for c in chains]
        self._keys0_cache = (list(chains), keys)
        return keys

    def manifest(self, token_ids, n_blocks: Optional[int] = None):
        """Every store key this connector would hold for the prompt's first
        ``n_blocks`` complete blocks (default: all), as size-grouped
        ``[(block_nbytes, [key, ...])]`` — the raw-byte inventory the
        membership resharder migrates between members without knowing the
        key scheme (docs/membership.md). Sentinel ordering: the layer-0 K
        key of each block (what ``lookup`` probes) is LAST in its group, so
        a batched copy that dies mid-stream never publishes a sentinel for
        an incompletely copied block."""
        chains = self._chains(token_ids)
        if n_blocks is not None:
            chains = chains[:n_blocks]
        (_, sentinel), *rest = self._tensors()
        by_size: Dict[int, List[str]] = {}
        for layer, t in rest:
            by_size.setdefault(t.nbytes, []).extend(
                self.block_key(layer, t.name, c) for c in chains
            )
        # The sentinel's size group goes last, the sentinels last in it.
        last = by_size.pop(sentinel.nbytes, [])
        by_size[sentinel.nbytes] = last + [self.block_key(0, sentinel.name, c) for c in chains]
        return [(size, keys) for size, keys in by_size.items()] if chains else []

    # -- engine surface ------------------------------------------------------

    def lookup(self, token_ids: Sequence[int]) -> int:
        """Number of leading blocks of this prompt already in the store.

        One control round-trip: the layer-0 K keys stand in for the whole
        block (the writer commits layer 0 last, so a present sentinel means
        every layer is present), and the store's binary-search longest-prefix
        match does the rest.

        Only a semantic no-match maps to 0. A dead store, a timeout, or a
        protocol error raises — the engine must see the difference between
        "not cached" and "store unreachable", or it silently recomputes
        forever (the reference likewise surfaces transport errors as their
        own exceptions, reference lib.py:575-577).
        """
        self._require_store("lookup")
        return self._lookup_chains(self._chains(token_ids))

    def _lookup_chains(self, chains: List[str]) -> int:
        if not chains:
            return 0
        keys = self._sentinel_keys(chains)
        try:
            # Audited: the blocking probe RTT. Every async caller hops it
            # through an executor (load()'s to_thread, start_fetch_async's
            # known_hit handoff); the remaining inline path is sync
            # lookup()/start_fetch(), whose docstrings own the cost.
            return self.conn.get_match_last_index(keys) + 1  # its: allow[ITS-L001]
        except InfiniStoreNoMatch:
            return 0

    async def save(
        self, token_ids, caches, block_ids: np.ndarray, first_block: int = 0,
        priority: Optional[int] = None,
    ) -> int:
        """Stream the request's KV blocks to the store. ``block_ids[i]`` is
        the engine's physical block holding logical block ``first_block + i``
        of this prompt. Returns blocks written (K+V across layers).

        Saves are BACKGROUND class by default (docs/qos.md): a prefill save
        is never decode-blocking, so its store puts yield to concurrent
        foreground reads in every queue they cross (true of the engine's
        own request too since PR 25: ``run_request`` runs this write
        beside the request's generation, not ahead of its first token). Pass
        ``priority=wire.PRIORITY_FOREGROUND`` to opt a save out (e.g. a
        handoff the consumer is already waiting on): an explicit
        ``priority`` is the whole write's class. Left out, a caller that
        knows whether anyone is BLOCKED on this save, and may come to be
        while it runs, can have bound a class cell in ``wire.SAVE_CLASS``
        (the engine's ``run_request``: the answer's save foreground, the
        prompt's write promoted at its join), which the writer reads per
        layer; with neither, BACKGROUND.

        ``first_block`` serves sharded producers: under sequence-parallel
        prefill (models/long_context.py) each host holds only its chunk's
        blocks — it passes the FULL token list (chain hashes commit to the
        whole prefix) but saves just its logical span. The spans compose:
        once every shard saved, a consumer's lookup sees the whole prefix."""
        self._require_store("save")
        chains = self._chains(token_ids)
        if first_block < 0 or first_block > len(chains):
            raise ValueError(
                f"first_block={first_block} outside the prompt's "
                f"{len(chains)} complete blocks"
            )
        chains = chains[first_block:]
        n = min(len(chains), len(block_ids))
        if n == 0:
            return 0
        cell = None
        if priority is None:
            cell = wire.SAVE_CLASS.get()
            priority = wire.PRIORITY_BACKGROUND
        return await self._writer.write(
            caches, np.asarray(block_ids[:n]), self._key_fn(chains),
            priority=priority, priority_cell=cell,
        )

    async def load(
        self, token_ids, caches, block_ids: np.ndarray, first_block: int = 0,
        on_layer=None,
    ):
        """Fetch this prompt's cached prefix into the engine's paged cache.

        Fetches up to ``lookup(tokens) - first_block`` blocks (capped by
        len(block_ids)) and scatters them; returns (updated caches,
        blocks_loaded). ``first_block`` skips a prefix the engine already
        holds (its own prefix cache / computed tokens): ``block_ids[i]``
        then receives logical block ``first_block + i`` — symmetric with
        ``save``'s ``first_block``.

        DONATION: the input ``caches`` are consumed (scatter_blocks donates
        the cache buffer on TPU so the update is in-place in HBM). Use the
        returned caches; do not touch the inputs again — on a real chip they
        are deleted buffers after this call.

        ``on_layer(layer, (k, v))``: optional per-layer progress hook
        (layers complete in order — see LayerwiseKVReader.read), the seam
        the vLLM-v1 worker's ``wait_for_layer_load`` gates on.
        """
        self._require_store("load")
        chains = self._chains(token_ids)
        if first_block < 0 or first_block > len(chains):
            raise ValueError(
                f"first_block={first_block} outside the prompt's "
                f"{len(chains)} complete blocks"
            )
        # The prefix lookup is a blocking store round trip (native
        # get_match_last_index): on a remote store that is a full RTT, which
        # must not stall the event loop mid-wave (ITS-L001) — hop it through
        # the default executor; the sync ``lookup()`` path stays direct.
        hit = await asyncio.to_thread(self._lookup_chains, chains)
        n = min(hit - first_block, len(block_ids))
        if n <= 0:
            return list(caches), 0
        # Trace: the cached prefix's store streaming begins here (the probe
        # above is control-plane; fetch_start marks the first data-plane leg).
        tspan = tracing.active_span()
        if tspan is not None:
            tspan.stage("fetch_start")
            tspan.annotate(hit_blocks=hit, fetch_blocks=n)
        span = chains[first_block : first_block + n]
        try:
            out = await self._reader.read(
                caches, np.asarray(block_ids[:n]), self._key_fn(span),
                on_layer=on_layer,
            )
        except PartialReadError as e:
            # e.caches, not the original list: layers scattered before the
            # failure donated their input buffers (deleted on TPU).
            if isinstance(
                e.cause, (InfiniStoreKeyNotFound, InfiniStoreResourcePressure)
            ):
                # KeyNotFound: blocks raced away (eviction/delete between
                # lookup and read). ResourcePressure: store RAM too pressured
                # to promote/serve right now (507; the spilled data
                # survives). Cache semantics either way — the engine just
                # recomputes; transport errors still propagate (lookup()'s
                # contract), carrying the partial caches.
                if isinstance(e.cause, InfiniStoreColdTier):
                    # The typed 512: cold BUT ALIVE — a tier demotion hit,
                    # not a miss (the data is one tier down, and the tier
                    # stats must be able to tell the two apart;
                    # docs/tiering.md).
                    tiering_note_demotion_hit()
                return e.caches, 0
            raise
        return out, n

    def start_fetch(
        self,
        token_ids,
        first_block: int = 0,
        limit_blocks: Optional[int] = None,
        prefetch_pool: Optional[HostStagingPool] = None,
        priority: int = wire.PRIORITY_FOREGROUND,
        known_hit: Optional[int] = None,
        retry_missing_s: float = 0.0,
        retry_interval_s: float = 0.002,
        fetch_gate=None,
    ) -> LayerwisePrefetch:
        """Begin the GATE-FREE half of a load: probe the store (one control
        round trip) and immediately start streaming the hit prefix's layers
        into reserved host staging regions — no device work, no engine
        lock, callable before the engine has even allocated blocks. The
        returned :class:`~.tpu.layerwise.LayerwisePrefetch` carries
        ``hit_blocks`` (the lookup answer) and ``n_blocks`` (what is being
        fetched); ``install(caches, block_ids)`` is the short exclusive
        phase with ``load``'s exact semantics, and ``discard()`` cancels
        cleanly (staging accounting returns to baseline).

        Concurrent admissions' fetches coalesce into shared batched store
        reads (:class:`FetchCoalescer`), so a wave of requests splits
        striped connections instead of queueing serially.

        ``priority``: QoS class of the fetch's store reads. Admission-
        blocking fetches stay FOREGROUND (the default, untagged);
        engines tag a speculative prefetch for a request beyond the next
        wave ``wire.PRIORITY_BACKGROUND`` so it never delays
        decode-blocking reads (docs/qos.md). Same-class submissions still
        coalesce; classes never merge.

        ``retry_missing_s``: handoff read-racing-write mode (disagg.py).
        A decode engine fetching a prefix the prefill engine is STILL
        SHIPPING sees KeyNotFound for layers not yet published; with a
        nonzero deadline the prefetch re-probes missing keys instead of
        failing, so per-layer installs (``install_layer``) ride out the
        race. Zero (the default) keeps strict cache semantics: absent
        means miss. Retry mode bypasses the coalescer (each layer's reads
        go direct) so one stalled layer never wedges merged group-mates.
        ``retry_interval_s`` is the re-probe cadence — it bounds the
        quantization latency a just-published layer waits before its
        re-probe lands, so TTFT-critical handoffs pass a sub-millisecond
        interval. ``fetch_gate`` (``async fetch_gate(layer)``) is the
        announce-driven variant: when the producer signals per-layer
        publication, layer ``l``'s read waits for the announcement instead
        of blind-probing keys that cannot exist yet (a probe storm that
        contends with the very ships it is waiting on). Gated fetches also
        bypass the coalescer.

        Raises :class:`~.tpu.staging.StagingPoolExhausted` when the
        prefetch arena cannot hold another pipeline — callers treat that
        as backpressure and fall back to the one-phase ``load``. Must be
        called from a running event loop (the loop the install/discard
        will run on) — which also means the inline probe BLOCKS that loop
        for one store RTT; async callers should prefer
        :meth:`start_fetch_async`, which hops the probe through an
        executor (``known_hit`` is how it hands the answer back in)."""
        self._require_store("start_fetch")
        chains = self._chains(token_ids)
        if first_block < 0 or first_block > len(chains):
            raise ValueError(
                f"first_block={first_block} outside the prompt's "
                f"{len(chains)} complete blocks"
            )
        hit = self._lookup_chains(chains) if known_hit is None else known_hit
        n = max(0, hit - first_block)
        n = min(n, self.max_blocks)
        if limit_blocks is not None:
            n = min(n, limit_blocks)
        pool = prefetch_pool or self._ensure_prefetch_pool()
        # Trace: the gate-free layer streaming starts with the handle below.
        tspan = tracing.active_span()
        if tspan is not None and n > 0:
            tspan.stage("fetch_start")
            sliding, full = self.spec.hit_values(n)
            tspan.annotate(
                hit_blocks=hit, fetch_blocks=n, values_window=sliding, values_full=full,
            )
            if self.spec.uniform:
                tspan.annotate(
                    bytes_window=sliding * self.spec.block_nbytes,
                    bytes_full=full * self.spec.block_nbytes,
                )
            else:
                tspan.annotate(bytes=sum(
                    self.spec.hit_nbytes(l, n) for l in range(self.spec.num_layers)
                ))
        span = chains[first_block : first_block + n]
        # Mutable class cell so promote() upgrades LATER submissions even
        # on the coalescer path (the closure reads it per call).
        pri_cell = {"value": priority}
        if prefetch_pool is None and retry_missing_s <= 0 and fetch_gate is None:
            submit = lambda blocks, nbytes: self._ensure_coalescer(pool, nbytes).submit(
                blocks, priority=pri_cell["value"]
            )
        else:
            # Retry/gated modes go direct: a KeyNotFound re-probe loop (or
            # an announcement wait) inside a merged batch would re-drive —
            # or stall — its group-mates' reads too.
            submit = None
        try:
            handle = LayerwisePrefetch(
                self.conn,
                pool,
                self.spec,
                self._key_fn(span),
                n,
                self.spec.num_layers,
                submit=submit,
                priority=priority,
                # One shared cell: promote() on the handle flips the class
                # the coalescer closure reads too.
                priority_cell=pri_cell,
                retry_missing_s=retry_missing_s,
                retry_interval_s=retry_interval_s,
                fetch_gate=fetch_gate,
                counters=self.hit_counters,
            )
        except StagingPoolExhausted as e:
            # The probe already ran — hand its answer to the fallback so a
            # backpressured admission (the most loaded moment) does not pay
            # the control round trip twice.
            e.hit_blocks = hit
            raise
        handle.hit_blocks = hit
        return handle

    async def start_fetch_async(
        self,
        token_ids,
        first_block: int = 0,
        limit_blocks: Optional[int] = None,
        prefetch_pool: Optional[HostStagingPool] = None,
        priority: int = wire.PRIORITY_FOREGROUND,
        known_hit: Optional[int] = None,
        retry_missing_s: float = 0.0,
        retry_interval_s: float = 0.002,
        fetch_gate=None,
    ) -> LayerwisePrefetch:
        """:meth:`start_fetch` for event-loop callers: the probe (a full
        store round trip) runs in the default executor, then the handle is
        built inline on the loop via ``known_hit`` — the fetch futures it
        starts need the running loop, so ONLY the probe may leave it.
        Mid-wave admission (vllm_v1 phase 1, the engine's install path)
        calls this so one request's lookup RTT never stalls the wave's
        other reads (ITS-L001, docs/static_analysis.md).

        ``known_hit`` skips the probe entirely — the overlapped handoff
        path (disagg.py) passes the block count the prefill side announced,
        because a store probe during an in-flight handoff would see only
        the layers published so far (layer 0 ships FIRST there, and it IS
        the sentinel, so the probe is also racy-optimistic)."""
        self._require_store("start_fetch")
        if known_hit is None:
            known_hit = await asyncio.to_thread(
                self._lookup_chains, self._chains(token_ids)
            )
        return self.start_fetch(
            token_ids, first_block=first_block, limit_blocks=limit_blocks,
            prefetch_pool=prefetch_pool, priority=priority,
            known_hit=known_hit, retry_missing_s=retry_missing_s,
            retry_interval_s=retry_interval_s, fetch_gate=fetch_gate,
        )

    def _ensure_prefetch_pool(self) -> HostStagingPool:
        if self._prefetch_pool is None:
            # ~4 full-depth prefetches of the longest hit (a region a
            # layer, each its own layer's size in whole slots, matching
            # LayerwisePrefetch's default; of a K/V cache 4 x layers x
            # region_nbytes): enough for a concurrent admission wave; an
            # over-wave falls back to the gated load.
            slot = self.spec.slot_nbytes
            nbytes = 4 * slot * sum(self.spec.hit_slots(self.max_blocks, slot))
            self._prefetch_pool = HostStagingPool(
                nbytes, self.spec.slot_nbytes, conn=self.conn
            )
        return self._prefetch_pool

    def _ensure_coalescer(
        self, pool: HostStagingPool, nbytes: Optional[int] = None
    ) -> FetchCoalescer:
        """The coalescer of store reads of ``nbytes``-byte values (a merged
        call moves values of one size): of a K/V cache, the one."""
        nbytes = self.spec.block_nbytes if nbytes is None else nbytes
        held = self._coalescers.get(nbytes)
        if held is None or held.base_ptr != pool.base_ptr:
            held = self._coalescers[nbytes] = FetchCoalescer(self.conn, nbytes, pool.base_ptr)
        return held

    @property
    def _coalescer(self) -> Optional[FetchCoalescer]:
        """A K/V cache's one coalescer (None before the first prefetch)."""
        return self._coalescers.get(self.spec.block_nbytes)

    def stage_layer_save(
        self, token_ids, layer: int, kv_pair, block_ids: np.ndarray,
        first_block: int = 0, priority: int = wire.PRIORITY_BACKGROUND,
    ):
        """Stage ONE layer's computed blocks for saving; returns ``ship``,
        an async callable performing the network puts (2*n blocks written).

        The gather + async D2H start NOW, on the caller's thread — the
        bytes are snapshotted before later compute (or the next step) can
        perturb the cache — while ``ship()`` does only awaits (the D2H
        wait runs in an executor so it never stalls the caller's event
        loop). This is the layer-granular half of ``save()`` for engines
        that stream saves as each layer's forward completes (the vLLM v1
        worker, vllm_v1.py): such callers MUST ship layer 0 last — its
        keys are the whole-block presence sentinel (``lookup``), so
        shipping it before deeper layers commit would publish a half-saved
        block. Whole-request saves should use ``save()``, whose writer
        enforces that ordering internally.

        ``priority``: QoS class of the puts (docs/qos.md). Layer-streamed
        saves default BACKGROUND — they run behind the engine's forward
        pass and must never delay a decode-blocking fetch. A prefill→decode
        HANDOFF ship passes ``wire.PRIORITY_FOREGROUND``: its consumer is
        actively waiting on these exact bytes (disagg.py), so background
        class would delay the reader it feeds. Disagg producers must name
        the class explicitly at the call site (ITS-P004,
        docs/static_analysis.md).

        Tracing: the CALLER's active span (captured now, not at ship time)
        rides the ship — one trace id covers prefill compute → store puts →
        decode install. The ship stamps ``submit`` when its puts issue."""
        self._require_store("stage_layer_save")
        import jax.numpy as jnp

        from .tpu.paged import gather_blocks

        chains = self._chains(token_ids)
        if first_block < 0 or first_block > len(chains):
            # Same bounds contract as save()/load(): an out-of-range
            # first_block would silently slice to an empty chain list and
            # return a no-op ship, hiding the caller's bug.
            raise ValueError(
                f"first_block={first_block} outside the prompt's "
                f"{len(chains)} complete blocks"
            )
        chains = chains[first_block:]
        n = min(len(chains), len(block_ids))
        if n == 0:
            async def noop() -> int:
                return 0

            return noop
        k_cache, v_cache = kv_pair
        bn = self.spec.block_nbytes
        ids_dev = jnp.asarray(np.asarray(block_ids[:n]), dtype=jnp.int32)
        # One packed [K blocks | V blocks] span -> one D2H transfer (the
        # writer's shape, tpu/layerwise.py).
        tr = self.pool.stage_out([
            jnp.concatenate([
                gather_blocks(k_cache, ids_dev),
                gather_blocks(v_cache, ids_dev),
            ])
        ])
        keys_k = [(self.block_key(layer, "k", chains[i]), i * bn) for i in range(n)]
        keys_v = [(self.block_key(layer, "v", chains[i]), (n + i) * bn) for i in range(n)]
        pri_kw = wire.qos_kwargs(self.conn, priority)
        # Capture the request's trace context HERE: ship() typically runs as
        # a free-floating task whose contextvars are whatever scheduled it,
        # not the request that staged this layer.
        span = tracing.active_span()

        async def ship() -> int:
            loop = asyncio.get_running_loop()
            (kv_host,) = await loop.run_in_executor(None, tr.wait)
            base = kv_host.ctypes.data
            if span is not None:
                span.stage("submit")
                span.annotate(handoff_layer=layer, handoff_blocks=2 * n)
            try:
                with tracing.override_span(span):
                    await asyncio.gather(
                        self.conn.write_cache_async(keys_k, bn, base, **pri_kw),
                        self.conn.write_cache_async(keys_v, bn, base, **pri_kw),
                    )
            finally:
                tr.release()
            return 2 * n

        return ship

    async def handoff(
        self,
        token_ids,
        caches,
        src_block_ids: np.ndarray,
        dst_block_ids: np.ndarray,
        src: Optional[int] = None,
        dst: Optional[int] = None,
    ):
        """Move a request's KV blocks from a producer to a consumer — one
        API, two transports (reference has only its NIC transport; on TPU
        pods the interconnect is the fast path).

        Same-mesh (``ici`` bound and ``src``/``dst`` shard indices given):
        gather + ppermute + scatter for ALL layers fused into ONE jitted
        SPMD program with a single collective (IciBlockTransfer.
        handoff_layers) — HBM->HBM over ICI, no host, no store, one launch.
        ``caches`` must be per-layer (K, V) arrays of shape
        [axis_size, num_blocks, *block] sharded over the transfer axis, with
        a uniform shape/dtype across layers (ragged layers raise ValueError);
        inputs are donated (use the returned caches).

        Otherwise: degrades to the DCN store — save the blocks under the
        request's chain keys, then load them into ``dst_block_ids`` (the
        cross-process flow runs save on the producer and load on the
        consumer; calling handoff on one process does both for tests and
        single-engine reuse). ``caches`` are plain [num_blocks, *block]
        arrays here.

        Returns (updated caches, blocks moved).
        """
        # Both transports move the same amount: the request's COMPLETE token
        # blocks (an incomplete tail block has no chain key, so the DCN path
        # could never carry it — the ICI path must agree or a cross-mesh
        # fallback would silently serve different data).
        chains = self._chains(token_ids)
        n = min(len(src_block_ids), len(dst_block_ids), len(chains))
        if n == 0:
            return list(caches), 0
        if self.ici is not None and src is not None and dst is not None:
            flat = [c for kv in caches for c in kv]
            uniform = all(
                c.shape == flat[0].shape and c.dtype == flat[0].dtype for c in flat
            )
            if uniform:
                # All layers in ONE SPMD launch (single collective over the
                # stacked blocks) — a per-layer loop here would pay L
                # sequential dispatch round-trips on the latency-critical path.
                out = self.ici.handoff_layers(
                    list(caches), src_block_ids[:n], dst_block_ids[:n], src, dst
                )
            else:
                # Ragged layers (hybrid architectures: sliding-window layers
                # with fewer blocks, mixed precision) cannot stack into one
                # collective — fall back to one fused K+V launch per layer.
                out = [
                    self.ici.handoff_kv(
                        k, v, src_block_ids[:n], dst_block_ids[:n], src, dst
                    )
                    for k, v in caches
                ]
            return out, n
        if self.ici is not None and self.conn is None:
            raise ValueError(
                "pure-ICI connector: handoff needs src and dst shard indices "
                "(no store connection to fall back to)"
            )
        self._require_store("handoff (DCN fallback)")
        # The DCN path gathers along axis 0 = blocks, so an ICI-layout cache
        # ([axis_size, num_blocks, *block] — one extra leading dim) would be
        # gathered along the DEVICE axis and ship wrong bytes under valid
        # keys. Reject it loudly instead of corrupting silently.
        want = 1 + len(self.spec.block_shape)  # [num_blocks, *block]
        for k_cache, v_cache in caches:
            for c in (k_cache, v_cache):
                if c.ndim != want or tuple(c.shape[1:]) != tuple(self.spec.block_shape):
                    raise ValueError(
                        "handoff DCN fallback needs per-layer caches of shape "
                        f"[num_blocks, {', '.join(map(str, self.spec.block_shape))}]; "
                        f"got {tuple(c.shape)}. ICI-layout caches "
                        "([axis_size, num_blocks, *block]) require src and dst "
                        "shard indices so the transfer rides the interconnect."
                    )
        # FOREGROUND: a handoff's consumer is actively waiting on this save
        # (it loads the same blocks next) — background class would delay
        # exactly the reader it feeds.
        await self.save(
            token_ids, caches, np.asarray(src_block_ids)[:n],
            priority=wire.PRIORITY_FOREGROUND,
        )
        return await self.load(token_ids, caches, np.asarray(dst_block_ids)[:n])

    def get_stats(self) -> dict:
        """The store connection's per-op stats snapshot (observability
        surface composed members re-expose — cluster.py stats()), with this
        connector's ledger of the hop beside it (``hit_counters``):
        ``hit_values_fetched`` and ``hit_values_whole_prefix``, and of the
        first ``hit_window_values_fetched`` (a sliding layer's K and V, a
        state: tensors a hit installs in its trailing blocks only);
        ``hit_read_bytes`` over ``hit_read_busy_us`` (the union of the time
        in which a hit's layer read was in flight; ``hit_reads_in_flight``
        and ``hit_read_busy_mark_s`` keep it), the store's delivered rate;
        ``install_upload_bytes`` over ``install_upload_us``, the host's rate
        of handing a hit's bytes to the device, in ``install_dispatches``
        executor calls that carried ``install_layers`` layers (one call a
        run of staged layers); ``save_d2h_bytes`` over
        ``save_d2h_wait_us``, what a save's D2H waits delivered;
        ``save_fg_puts`` of ``save_puts`` put calls went out untagged,
        ``save_promotions`` writes were promoted with layers still unsent,
        and ``save_fg_rounds`` over ``save_fg_writes`` is the rounds of put
        latency a write that started foreground took; ``save_put_bytes``
        over ``save_put_busy_us`` (the union of the time in which a save's
        put was in flight), the rate the store took the saves at. Beside
        them the connection's own put copy ledger (``touch_stats``):
        ``put_file_bytes`` of ``put_copy_bytes``, ``put_file_calls``,
        ``put_copy_us``, and ``put_touched_bytes``, ``pretouch_bytes``."""
        self._require_store("get_stats")
        touch = getattr(self.conn, "touch_stats", dict)
        return {**self.conn.get_stats(), **touch(), **self.hit_counters}

    def drop(self, token_ids) -> int:
        """Remove this prompt's blocks from the store (all layers). Returns
        the number of store keys deleted."""
        self._require_store("drop")
        chains = self._chains(token_ids)
        keys = [self.block_key(layer, t.name, c) for layer, t in self._tensors() for c in chains]
        return self.conn.delete_keys(keys) if keys else 0
