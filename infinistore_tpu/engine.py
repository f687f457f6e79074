"""Continuous-batching engine harness: the connector under engine fire.

The reference exists to serve a production inference engine through LMCache
(reference README.md:22, docs/source/design.rst:33-37): many interleaved
requests with overlapping prefixes, admission-time prefix probes, loads racing
evictions, block tables owned by the engine. This module provides both halves
of that story for JAX/TPU engines:

- ``EngineKVAdapter`` — the vLLM-TPU-style connector surface: token-granular
  prefix probe at admission (``get_num_matched_tokens``), load/save keyed by
  the ENGINE'S physical block table, request drop. It is a thin veneer over
  ``KVConnector`` — the seam where a real engine integration bolts on.
- ``ContinuousBatchingHarness`` — a scheduler-shaped driver: N requests in
  flight against ONE shared paged cache (``BlockPool`` hands out physical
  blocks, exactly an engine's block-table manager), prefix-hit loads skipping
  recompute, suffix decode coalesced across live requests into lockstep
  batched RAGGED waves (``WaveDecoder`` -> one ``verify_step_ragged`` call
  per wave, chunks concatenated, no padding to the wave's widest chunk),
  byte-verified against the model's prefill oracle, and
  store writes of every computed prefix. Device-cache discipline mirrors a
  real engine scheduler: mutating phases (install scatters donate cache
  buffers; compute rewrites blocks) are exclusive; saves snapshot their
  blocks with cheap device-side gathers and then stream to the store with
  no lock held — so multiple requests keep store I/O in flight concurrently
  while the device cache stays consistent.

Admission is TWO-PHASE and store I/O never holds the device gate: a
speculative, gate-free FETCH (``KVConnector.start_fetch``) starts streaming
the hit prefix into host staging at enqueue — before blocks are even
allocated — with concurrent admissions' reads coalesced into shared store
calls; only the short INSTALL (host->device scatter) takes the exclusive
gate, in an expedited lane so late-arriving-but-cheap installs are not
parked behind a convoy of prefills. Gate-held compute runs in executor
threads so the event loop keeps draining fetch completions — that, plus
the fetch/install split, is what turns the old serialized
probe->load->prefill admission into a pipeline where a cache hit is
cheaper end-to-end than recomputing (``p50_prefix_ready_hit_us`` vs
``_miss_`` in the metrics). Prefetches cancel cleanly: a raced eviction
or an abandoned admission discards the handle, staging accounting returns
to baseline, and the waste is reported (``prefetch_waste``).

Metrics reported (the engine-side figures of merit the reference never
measured): prefix hit rate, admission latency percentiles, recompute seconds
saved (hit blocks x measured per-block prefill cost), and lookup->load races
lost to eviction (the cache-semantics path: the engine just recomputes).
"""

import asyncio
import contextlib
import time
from contextlib import asynccontextmanager
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import tracing
from .models.serving import (
    FEED_ROWS,
    WaveLayout,
    fed_token,
    no_feed,
    pack_wave,
    verify_step_ragged,
)
from .tpu.paged import gather_blocks
from .tpu.paged_attention import RaggedWaveMeta, build_ragged_wave
from .tpu.staging import StagingPoolExhausted
from .wire import PRIORITY_BACKGROUND, PRIORITY_FOREGROUND, SAVE_CLASS


class BlockPool:
    """Engine-owned physical block allocator (the block-table manager).

    ``alloc`` backpressures when the pool is exhausted — a request waits for
    blocks exactly as an engine scheduler defers admission, instead of
    failing."""

    def __init__(self, num_blocks: int):
        self._free = list(range(num_blocks - 1, -1, -1))
        self._cond = asyncio.Condition()

    @property
    def available(self) -> int:
        return len(self._free)

    async def alloc(self, n: int) -> np.ndarray:
        # `pool_alloc`: entry to blocks in hand (the wait for another
        # request to free its table is the whole of it when there is one).
        span = tracing.start_span("pool_alloc")
        if span is not None:
            span.annotate(blocks=n, free_at_entry=len(self._free))
        async with self._cond:
            await self._cond.wait_for(lambda: len(self._free) >= n)
            ids = [self._free.pop() for _ in range(n)]
        if span is not None:
            span.finish()
        return np.asarray(ids, dtype=np.int32)

    async def free(self, ids: np.ndarray):
        async with self._cond:
            self._free.extend(int(i) for i in ids)
            self._cond.notify_all()


# Who may hold the device gate, and so who a wait stood behind: the one
# vocabulary of ``DeviceGate``'s ledger, its ``gate_*`` keys in
# ``ContinuousBatchingHarness.metrics()`` and the ``gate_wait`` span's
# ``holder`` / ``behind_us`` (docs/observability.md, "Inside the engine").
GATE_HOLDERS = ("wave", "prefill", "resume", "install", "snapshot", "verify")
# What is left of a wait once every holder's part is taken out: the gate was
# nobody's and the waiter had not yet been woken.
GATE_FREE = "free"


def _gate_now_us() -> int:
    """The gate's one clock: ``perf_counter`` in whole microseconds, so that
    holds and waits add up exactly."""
    return time.perf_counter_ns() // 1000


class GateHold:
    """What one acquisition of the gate measured, handed to the ``async
    with`` body: ``asked_us`` (the gate's clock at entry; ``asked_s`` the
    same as a ``perf_counter`` reading) and ``waited_us`` (entry to
    acquired) from the start, ``held_us`` (acquired to released) once the
    body is left."""

    __slots__ = ("asked_us", "waited_us", "held_us")

    def __init__(self, asked_us: int, waited_us: int):
        self.asked_us = asked_us
        self.waited_us = waited_us
        self.held_us = 0

    @property
    def asked_s(self) -> float:
        return self.asked_us / 1e6


class DeviceGate:
    """Reader-writer discipline over the shared paged cache.

    Exclusive: phases that MUTATE the cache arrays (load's scatters and the
    model's steps — prefill, resume, decode waves — all DONATE the cache
    buffers and hand back the ones to use) — two such phases interleaving at
    await points would fork the functional cache state and one side's blocks
    would be lost (or a donated buffer would be read).
    Shared: gather-only phases (save snapshots, verification reads) — they
    overlap each other freely and are over in microseconds, after which the
    actual store I/O runs with no gate held at all.

    The gate keeps a ledger of who held it, always on. Every acquisition
    names its ``holder`` (one of ``GATE_HOLDERS``). ``held_us[kind]`` /
    ``holds[kind]``: how long that kind has held the gate, and how many holds
    it has ended. A stretch of shared holds, from the first one in to the last
    one out, is ONE hold's time however many overlap, under the kind that
    opened it. A waiter reads the ledger when it starts to wait and when it
    acquires: the difference, kind by kind, is how long it stood behind each,
    and what is left of its wait is ``GATE_FREE``. ``wait_us[waiter][behind]``
    adds those up and ``waits[waiter]`` counts them, so a waiter's parts and
    ``free`` make its whole wait to the microsecond. A cancelled wait adds
    nothing. With the recorder on, the ``gate_wait`` span carries the same
    for its one wait: ``holder``, ``behind_us`` (the parts that are not 0)
    and, written at release, ``held_us``."""

    def __init__(self):
        self._cond = asyncio.Condition()
        self._shared = 0
        self._exclusive = False
        # Writer priority: a waiting mutator blocks NEW shared holders, or a
        # steady stream of snapshot/verify phases could starve loads and
        # computes indefinitely. (Phases are never nested per request, so
        # priority cannot deadlock.)
        self._exclusive_waiting = 0
        # Expedite lane: short mutators (prefix INSTALLS — a device
        # transfer, not a model forward) go ahead of queued long ones
        # (prefills). Installs arrive LATE by construction (their gate-free
        # fetch runs first), so FIFO would park every cache hit behind a
        # convoy of misses' prefills — shortest-job-first keeps the hit
        # path's latency at install cost. No starvation in practice: each
        # admission expedites at most once, so the lane drains.
        self._expedite_waiting = 0
        # The ledger (class docstring). ``_holding``: the kind the time since
        # ``_since_us`` goes to, None while the gate is nobody's.
        self.held_us: Dict[str, int] = dict.fromkeys(GATE_HOLDERS, 0)
        self.holds: Dict[str, int] = dict.fromkeys(GATE_HOLDERS, 0)
        self.waits: Dict[str, int] = dict.fromkeys(GATE_HOLDERS, 0)
        self.wait_us: Dict[str, Dict[str, int]] = {
            waiter: dict.fromkeys(GATE_HOLDERS + (GATE_FREE,), 0) for waiter in GATE_HOLDERS
        }
        self._holding: Optional[str] = None
        self._since_us = 0

    @property
    def idle(self) -> bool:
        """Nobody holds the gate or waits for it: ``exclusive()`` would be
        granted without a suspension, and a wave launched now stands in no
        other phase's way."""
        return not self._exclusive and self._exclusive_waiting == 0 and self._shared == 0

    def counters(self) -> Dict[str, int]:
        """The ledger under flat keys, plain monotone counters (microseconds
        and counts): ``gate_held_us_<kind>``, ``gate_holds_<kind>``,
        ``gate_waits_<waiter>``, ``gate_wait_us_<waiter>`` (the whole) and
        ``gate_wait_us_<waiter>_behind_<kind>``, ``<kind>`` over
        ``GATE_HOLDERS`` and ``free``: the parts of a waiter add up to its
        whole exactly."""
        out: Dict[str, int] = {}
        for kind in GATE_HOLDERS:
            out[f"gate_held_us_{kind}"] = self.held_us[kind]
            out[f"gate_holds_{kind}"] = self.holds[kind]
            out[f"gate_waits_{kind}"] = self.waits[kind]
            out[f"gate_wait_us_{kind}"] = sum(self.wait_us[kind].values())
            for behind, us in self.wait_us[kind].items():
                out[f"gate_wait_us_{kind}_behind_{behind}"] = us
        return out

    def _held_by_now(self, now_us: int) -> Dict[str, int]:
        """``held_us`` with the running hold's part up to ``now_us``: what a
        waiter reads when it starts to wait and when it acquires."""
        held = dict(self.held_us)
        if self._holding is not None:
            held[self._holding] += now_us - self._since_us
        return held

    def _begin_wait(self, holder: str, mode: str, ready):
        """Entry: the ``gate_wait`` span (whoever asked is its parent), the
        clock, and the ledger as it stands where the gate is not the
        caller's for the asking. An unknown ``holder`` raises."""
        if holder not in self.held_us:
            raise ValueError(f"unknown gate holder {holder!r}: one of {GATE_HOLDERS}")
        span = tracing.start_span("gate_wait")
        if span is not None:
            span.annotate(mode=mode, holder=holder)
        asked_us = _gate_now_us()
        return span, asked_us, None if ready() else self._held_by_now(asked_us)

    def _acquired(self, holder: str, span, asked_us: int, before) -> GateHold:
        """The wait is over (under ``_cond``): cut it by who held the gate
        meanwhile, add it to the ledger, close the span."""
        now_us = _gate_now_us()
        free = waited = now_us - asked_us
        row = self.wait_us[holder]
        behind = None if span is None else {}
        if before is not None:
            for kind, us in self._held_by_now(now_us).items():
                part = us - before[kind]
                if part:
                    row[kind] += part
                    free -= part
                    if behind is not None:
                        behind[kind] = part
        row[GATE_FREE] += free
        self.waits[holder] += 1
        if self._holding is None:  # an exclusive hold, or the first shared one in
            self._holding, self._since_us = holder, now_us
        if span is not None:
            if free:
                behind[GATE_FREE] = free
            span.annotate(behind_us=behind)
            span.finish()
        return GateHold(asked_us, waited)

    def _released(self, holder: str, span, hold: GateHold):
        """A hold ends (under ``_cond``): its time to the ledger where the
        gate is nobody's now, and to its own span (an attr written after
        ``finish``: the recorder's ring reads it, the slow-op copy was taken
        before)."""
        now_us = _gate_now_us()
        hold.held_us = now_us - hold.asked_us - hold.waited_us
        self.holds[holder] += 1
        if not self._exclusive and self._shared == 0:
            self.held_us[self._holding] += now_us - self._since_us
            self._holding = None
        if span is not None:
            span.annotate(held_us=hold.held_us)

    @asynccontextmanager
    async def exclusive(self, holder: str, expedite: bool = False):
        """Hold the gate alone as ``holder``; yields its ``GateHold``."""

        def ready():
            return (
                not self._exclusive
                and self._shared == 0
                and (expedite or self._expedite_waiting == 0)
            )

        span, asked_us, before = self._begin_wait(
            holder, "expedite" if expedite else "exclusive", ready
        )
        async with self._cond:
            self._exclusive_waiting += 1
            if expedite:
                self._expedite_waiting += 1
            try:
                await self._cond.wait_for(ready)
            finally:
                self._exclusive_waiting -= 1
                if expedite:
                    self._expedite_waiting -= 1
                # A cancelled wait (e.g. a timed-out request) may be the
                # writer that shared() waiters queued behind; without this
                # notify they would sleep forever on a free gate.
                self._cond.notify_all()
            hold = self._acquired(holder, span, asked_us, before)
            self._exclusive = True
        try:
            yield hold
        finally:
            async with self._cond:
                self._exclusive = False
                self._released(holder, span, hold)
                self._cond.notify_all()

    @asynccontextmanager
    async def shared(self, holder: str):
        """Hold the gate beside other shared holders as ``holder``; yields
        its ``GateHold``."""

        def ready():
            return not self._exclusive and self._exclusive_waiting == 0

        span, asked_us, before = self._begin_wait(holder, "shared", ready)
        async with self._cond:
            await self._cond.wait_for(ready)
            hold = self._acquired(holder, span, asked_us, before)
            self._shared += 1
        try:
            yield hold
        finally:
            async with self._cond:
                self._shared -= 1
                self._released(holder, span, hold)
                if self._shared == 0:
                    self._cond.notify_all()


class _WaveOut:
    """What one launched wave returned beside its logits, alive while a
    ``WaveRows`` of it is (``WaveDecoder.token_ids``, ``row_aux``)."""

    __slots__ = ("ids", "feed", "aux_rows", "host_ids")

    def __init__(self, ids, feed, aux_rows):
        # [T] int32 on the device, its host copy under way; of a model that
        # drafts [2, T]: the ids over the drafts, one array for one read.
        self.ids = ids
        self.feed = feed  # [FEED_ROWS]: what the next wave's fed rows read, on the device
        self.aux_rows = aux_rows  # the model's per-row aux [T, ...], or None
        self.host_ids = None  # np, ``ids`` on the host once the first request has asked


class WaveRows:
    """A request's rows of a launched wave's logits, as ``step_chunk``
    resolves to them: ``[n, vocab]``, on the device, and not cut out of the
    wave's ``[T, vocab]`` array until somebody reads them. A request that
    asks its decoder for the sampled ids (``WaveDecoder.token_ids``) or the
    rows' ``aux`` (``row_aux``) hands this back and dispatches nothing; one
    that reads the logits (an index, ``np.asarray``, any ``jnp`` function)
    gets ``logits[off : off + n]``, cut on the first read, kept, and counted
    in the decoder's ``row_slices``. Once cut the handle holds the slice
    alone and lets the wave's whole array go; rows that ARE the whole array
    (a wave of one entry with no padded row) cost no dispatch and no count."""

    __slots__ = ("decoder", "out", "off", "n", "shape", "dtype", "_logits", "_rows")

    def __init__(self, decoder: "WaveDecoder", logits: jax.Array, out: _WaveOut, off: int, n: int):
        self.decoder = decoder  # the one that handed these out, and counts their slice
        self.out = out  # the wave they rode
        self.off, self.n = off, n  # their flat rows there
        self.shape, self.dtype = (n, *logits.shape[1:]), logits.dtype
        self._logits, self._rows = logits, None

    def rows(self) -> jax.Array:
        """The logits rows themselves, cut out of the wave's on the first call."""
        if self._rows is None:
            if self.n == self._logits.shape[0]:
                self._rows = self._logits
            else:
                self._rows = self._logits[self.off : self.off + self.n]
                self.decoder.row_slices += 1
            self._logits = None
        return self._rows

    def __len__(self) -> int:
        return self.n

    def __getitem__(self, index):
        return self.rows()[index]

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.rows(), dtype=dtype)

    def __jax_array__(self) -> jax.Array:
        return self.rows()


class _Ahead(NamedTuple):
    """A stream's slot in a launched wave that its request has not been handed."""

    position: int  # of the slot's first row
    # Its logits rows, as ``step_chunk`` will resolve to them; they know the
    # wave it rode (``out``) and its first flat row there (``off``: what
    # ``fed_token`` names).
    rows: WaveRows
    # Of a model that drafts, the verdict on the round before that this slot was
    # launched under (its draft accepted: the slot starts two positions on, not
    # one); None where the slot is the call's own, its tokens the host's.
    accepted: Optional[bool] = None


class _Stream:
    """A request's declared run of rounds (``WaveDecoder.stream``)."""

    __slots__ = ("table", "last", "ahead", "guess", "rounds", "accepted")

    def __init__(self, table, last: int):
        self.table = table  # the padded table its request hands in every round
        self.last = last  # no slot launched ahead writes a position past it
        self.ahead: Optional[_Ahead] = None
        # The verdict its slot in the wave being launched goes out under, as
        # ``_Ahead.accepted`` will keep it.
        self.guess: Optional[bool] = None
        # Of a model that drafts: the rounds whose verdict the decoder has seen
        # (a call that came back where a slot was launched for it, or where the
        # other verdict puts it), and how many of them accepted their draft.
        self.rounds = self.accepted = 0


class _Wave(NamedTuple):
    """One assembled wave (``WaveDecoder._assemble``): ``launch``'s operands
    and the rows of them that are real."""

    tokens: List[int]  # [T], the tail the last real row repeated
    positions: List[int]  # [T]
    row_of: List[int]  # [T], the table row each flat token reads
    meta: RaggedWaveMeta  # every layer's pages
    tables: List[np.ndarray]  # [B], the tail the last real table repeated
    wmeta: Optional[RaggedWaveMeta]  # the sliding layers' pages
    real_rows: int


class WaveDecoder:
    """Coalesce decode AND verify steps from concurrent requests into
    lockstep waves.

    A real continuous-batching engine advances EVERY live request one step
    per wave with one batched model call; per-request sequential decode
    forfeits that. Each request awaits ``step(token, position, table)``
    (one decode token) or ``step_chunk(tokens, positions, table)`` (a
    speculative-verification chunk — the committed token plus drafted
    continuations); the first arrival schedules a flush, the flush yields
    to the event loop so every ready request joins, then ONE
    ``verify_step_ragged`` launch (under the device gate's exclusive phase —
    it mutates the shared cache) advances the whole MIXED wave: decoding
    requests ride as 1-token chunks beside verifying requests' K-token
    chunks, so speculation never leaves the lockstep batch.

    Wave assembly is RAGGED (models/llama.py ``verify_step_ragged``): the
    wave's chunks are CONCATENATED into one flat token list — a mixed wave
    costs sum(len_i) rows, not the old rectangle's B x max(len_i) with
    every shorter chunk padded by duplicated rows (a length-skewed wave
    used to pay the widest chunk B times over). The flat list pads only at
    the TAIL to a power-of-two row bucket by repeating the last
    (token, position) row — a repeated row scatters the SAME K/V bytes to
    the same (block, slot), so the byte-determinism guarantee is
    unchanged, and a padded row that used to be a duplicated rectangle
    column is now simply absent. Request tables pad to a power-of-two B
    whose padded rows no flat token references (they neither scatter nor
    attend). Attention page metadata (tpu/paged_attention.py
    ``build_ragged_wave``) pads to a power-of-two page bucket the same
    way; the ragged kernel neither fetches nor computes the padded pages.

    **One upload, one launch, one read-back a wave.** The flush writes the
    wave's integer metadata — flat tokens, positions, owning rows, the page
    triple, the stacked tables and, where the spec names a window, the
    second page triple — into ONE ``int32`` host buffer whose layout is a
    function of the bucket ``(T, B, P[, Pw])`` alone (models/serving.py
    ``pack_wave``) and hands it to one jitted call (``launch`` ->
    ``serving.verify_step_ragged``, which slices it at static offsets and
    runs the model's wave body). The program returns the greedy ids
    ``argmax(logits, -1)`` beside the logits; their copy to the host starts
    with the launch and stays with the wave. ``step_chunk`` resolves to a
    ``WaveRows``: the request's rows of the wave's logits, which stay on the
    device and are cut out of the wave's array only when, and only if,
    somebody reads them (a slice is a device call of its own, about as dear
    to the host as the launch's share a row, and a flush would pay one an
    entry on the thread that has the next wave to launch). The request asks
    ``token_ids(rows)``, which dispatches nothing: a wave's first asker
    blocks once for the whole wave's ids, every other request of that wave
    reads the host copy. ``waves + blocking_reads`` is
    ``wave_host_transfers`` in ``metrics()``: 2 a wave, whatever its rows;
    ``row_slices`` (``wave_row_slices``) counts the slices readers of logits
    caused (the benchmark's check phase, a test): none in a serving loop.

    **And one wave ahead.** The token a round samples is four bytes that are
    on the device when its wave ends, and all that the next wave wants of
    them is to read them as its ``tokens``. A request that will come back
    says so (``stream``: round after round with this table, up to a last
    position; ``_generate`` does, unless a host drafter writes its chunks), and the decoder
    then launches the row of round k + 1 while the request still reads round
    k's token: the row's token slot names its row of the wave before it
    (``serving.fed_token``) and the program reads the id out of that wave's
    ``feed``, one ``[FEED_ROWS]`` array whatever either bucket is, so one
    program a bucket as before, with fed rows and rows whose token the host
    sends side by side. The request's ``step_chunk(token, position + 1,
    table)`` is then matched to the row already launched (position and table;
    anything else is an error) and resolves to that wave's logits rows, with
    no device time to wait for, but not before the flush that took it has
    launched the stream's NEXT row: the request goes on to block the event
    loop in ``token_ids``, and by then the wave after the one it reads is
    behind it on the device. Depth is one: a stream's row n + 2 is launched
    only when its request has been handed row n + 1. A stream's first call has
    no row yet: it rides the flush's wave with its token from the host, and
    is taken again by the next flush (started at once) as if it had been
    launched ahead. Rows fed in one wave all read the wave launched LAST; a
    stream whose row rode an older one comes back through the host once.
    Nothing is launched for a call that will not come (a row past the stream's
    declared last position, a stream that ended), so no row is wasted and no
    recurrent state advances
    unasked. **When it does not engage**, by what the flush finds and by no
    option: a bare ``step`` / ``step_chunk`` (no stream: a test, a warm-up, a
    host drafter's chunk); a row past ``FEED_ROWS``; ``harness.arriving`` non-zero
    (a request between its admission and its first wave will ask for the
    device, and a wave queued ahead of its need would stand in front of its
    install, prefill or snapshot by up to a step); the device gate not idle
    (a mutator holds or wants it, or a save's snapshot reads under it: the
    launch would have to wait, and nothing between a flush's sort and its
    launch may suspend). Then every call resolves as it always did and comes
    back with its token. ``waves_ahead`` counts the launched waves with a fed
    row (``wave_ahead_waves`` in ``metrics()``).

    **A model that drafts** (``config.steps.drafts``, models/serving.py) has a
    row of two: every entry is laid out as a SLOT of ``width`` = 2 flat rows,
    ``[token, draft]`` at ``[p, p + 1]``, and an entry that brings one token
    (a request's first round, its last, a bare call) has its row repeated
    within its slot (the same bytes to the same place, as the tail's
    padding). So a wave of n entries is one program whatever its entries
    drafted, and the buckets a run of bare one-token calls pins are the
    buckets the drafting requests land on. The wave's program hands back the
    ids and the drafts as ONE array: ``token_ids`` and ``draft_ids`` read its
    two rows, one blocking read a wave.

    Its requests declare their streams too, and what is launched ahead is the
    stream's next SLOT. Round k is ``[token, draft]`` at ``[p, p + 1]``, flat
    rows ``r`` and ``r + 1`` of its wave, whose program returns ``ids`` and
    ``drafts`` a row; the next slot has two forms, both at positions the host
    knows before the wave has run: the draft REJECTED, ``[ids[r], drafts[r]]``
    at ``[p + 1, p + 2]``; the draft ACCEPTED, ``[ids[r + 1], drafts[r + 1]]``
    at ``[p + 2, p + 3]``. Which one holds the device knows when the wave
    ends and the host only after its read, so the wave's ``feed`` carries the
    drafts behind the ids (``serving.feed_rows``; ``fed_token(src, True)`` is
    row ``src``'s draft) and the flush launches ONE of the two, by the
    likelier verdict (``_guess``: drafts accepted over drafts made, the
    stream's own count once it has ``OWN_RECORD_ROUNDS`` rounds, before that
    the harness's ``spec_accepted`` over ``spec_drafted``; rejected while
    nothing is known; a slot of one token has no draft and one form). A request
    that comes back where the slot was launched is handed its rows as a
    width-1 stream is. One that comes back where the OTHER verdict puts it has
    its slot dropped (``ahead_dropped``, ``wave_ahead_dropped``) and rides this
    flush's wave with its tokens from the host, as a stream that fell out of the
    wave train does: one round through the host and nothing else. What the
    dropped slot wrote is overwritten by the real slot's rows before anything
    reads it, or lies past the request's committed end under the position
    mask, as a rejected draft's row always did: guessed rejected, accepted in
    fact, ``p + 1`` holds the token that was there (the accepted draft IS
    ``ids[r]``) and ``p + 2`` is the real slot's first row; guessed accepted,
    rejected in fact, the real slot rewrites ``p + 1`` and ``p + 2`` and
    ``p + 3`` waits for a real row, which comes, since no slot is launched past
    the stream's ``last``. That holds for the drafting layer's own slots and
    boundary rows (each a function of its own row) and is why a cache with a
    recurrent state takes no drafter. Its one-token calls (a first round, a
    last one, the closing step) lie outside ``last`` or before any slot: the
    host launches them, as it launched every call before.

    ``bucket_sizes`` records the distinct (B, T, P) buckets — table rows,
    flat token rows, flat attention pages, the last two in SLOTS (of one row,
    but for a model that drafts: of ``width``) — which ARE the jit cache
    entries; the harness test pins the count. ``pad_rows``/
    ``launched_rows`` feed the ``engine_wave_pad_fraction`` metric: the
    share of launched wave rows that were padding (the rectangle's was
    1 - sum(len_i) / (B_bucket * K_bucket); the ragged tail's is
    1 - sum(len_i) / T_bucket). ``wave_pages``/``wave_pad_pages`` count the
    flat attention pages launched and how many were the page bucket's
    padding (``RaggedWaveMeta.pad_pages``): the share the kernel skips.
    """

    def __init__(self, harness: "ContinuousBatchingHarness"):
        self.h = harness
        # Of the cache's shape the decoder needs the layers' windows alone:
        # a sliding layer walks the wave's second page list.
        spec = harness.config.kv_spec(1)
        self._window = spec.window
        self._layers = spec.num_layers
        self._sliding = sum(w is not None for w in spec.windows or ())
        # Flat rows an entry's slot takes (class docstring, "a model that drafts").
        self.width = 2 if harness.config.steps.drafts else 1
        self._pending: List[tuple] = []
        self._flush_scheduled = False
        # Wave-row padding ledger (engine_wave_pad_fraction).
        self.pad_rows = 0
        self.launched_rows = 0
        # Launched waves that carried ONE real flat row: the waves whose
        # dense FFN pads its row to reach the matrix unit (models/llama.py
        # ``_ffn``).
        self.one_row_waves = 0
        # Flat attention pages launched (bucket padding included) and how
        # many of them were padding: what the ragged kernel skips.
        self.wave_pages = 0
        self.wave_pad_pages = 0
        # (layer, page) pairs the launched waves' real rows attended, and how
        # many more a stack of full layers would have: what the sliding
        # layers' second page list spared (0 where no layer has a window).
        self.wave_layer_pages = 0
        self.wave_window_pages_skipped = 0
        # What the wave program hands back beside its logits
        # (models/serving.py): the sampled ids and the model's per-row ``aux``
        # arrays stay with the wave (``_WaveOut``), which the rows handed out
        # know (``WaveRows``: ``token_ids``, ``row_aux``); the model's named
        # counters are added up on the device (a wave's are results like its
        # logits: nothing here waits for them, and this class reads neither).
        # Slices of a wave's logits dispatched for a reader of them
        # (``WaveRows.rows``): none for a request that asks for ids alone.
        self.row_slices = 0
        # Blocking device-to-host reads made for the waves' tokens: one a
        # wave whose tokens anyone asked for. With the one host array a
        # launched wave uploads (``waves``) they are ``wave_host_transfers``.
        self.blocking_reads = 0
        # name -> device scalars not yet folded; the names a model's steps
        # count by (``config.step_counters``) read 0 before the first wave.
        self._step_counters = {
            name: [] for name in getattr(harness.config, "step_counters", ())
        }
        # Strong references: the event loop holds only weak refs to tasks,
        # so a fire-and-forget flush could be GC'd mid-flight and strand
        # every waiter with _flush_scheduled stuck True. A SET, not a slot:
        # _flush clears _flush_scheduled before awaiting the gate, so a new
        # step() can legally start a second flush while the first is still
        # in flight — a single slot would drop the older task's reference.
        self._flush_tasks = set()
        self.waves = 0
        self.max_wave = 0
        self.bucket_sizes = set()  # distinct PADDED (B, T, P) buckets (= compiles)
        # One wave ahead (class docstring): the declared streams by the id of
        # the table each holds, the wave launched last (whose ``feed`` the
        # next one's fed rows read), what a wave with no fed row is handed in
        # its place, and the launched waves that had a fed row.
        self._streams: Dict[int, _Stream] = {}
        self._last: Optional[_WaveOut] = None
        self._no_feed = no_feed(harness.config.steps.drafts)
        self.waves_ahead = 0
        # Slots launched ahead under a verdict that did not hold: their calls
        # came back elsewhere and went through the host once.
        self.ahead_dropped = 0

    async def step(self, token: int, position: int, padded_table) -> jax.Array:
        """Advance this request by one token; returns its logits row (read,
        so cut: a caller that wants the id alone takes ``step_chunk``)."""
        rows = await self.step_chunk([token], [position], padded_table)
        return rows[0]

    async def step_chunk(
        self,
        tokens: Sequence[int],
        positions: Sequence[int],
        padded_table,
        priority: int = 0,
    ) -> WaveRows:
        """Advance this request by a token chunk (tokens[0] committed,
        tokens[1:] speculative); returns its [len(tokens), vocab] logits
        rows — row j follows tokens[:j+1] — as a ``WaveRows``: hand it to
        ``token_ids`` / ``row_aux``, or read it as the array it stands for.
        ``priority`` is taken and ignored: every entry rides the next wave,
        whatever its class."""
        if not tokens or len(tokens) != len(positions):
            raise ValueError("need non-empty tokens with matching positions")
        if self.width > 1 and len(tokens) > self.width:
            raise ValueError(
                f"a model that drafts takes a chunk of at most {self.width} rows (the token and "
                f"its draft), got {len(tokens)}"
            )
        fut = asyncio.get_running_loop().create_future()
        # A queue entry: (tokens, positions, table, future).
        self._enqueue([(list(tokens), list(positions), padded_table, fut)])
        return await fut

    def _enqueue(self, entries: List[tuple]):
        self._pending.extend(entries)
        if not self._flush_scheduled:
            self._flush_scheduled = True
            task = asyncio.ensure_future(self._flush())
            self._flush_tasks.add(task)
            task.add_done_callback(self._flush_tasks.discard)

    @contextlib.contextmanager
    def stream(self, padded_table, last: int):
        """A request's way of saying that it will come back: inside the block
        it calls ``step_chunk`` round after round, every time with
        ``padded_table`` (the object: the decoder knows the stream by it), each
        call a slot that follows from the rows of the call before. A slot is
        one token at the next position, the token that call's row sampled; of
        a model that drafts it is ``[token, draft]`` as one of that call's rows
        sampled and drafted them, one position on (its draft rejected) or two
        (accepted). ``last`` is the last position such a slot will write: what
        a drafting request brings as one token (its first round, its last, a
        closing step) is the host's to launch. The decoder may then launch the
        slot of call k + 1 while the request still reads call k's rows (class
        docstring, "one wave ahead"). A call that fits neither the slot launched
        for it nor, under a drafting model, the slot the other verdict gives is
        an error. Leaving the block ends the stream: nothing of it is launched
        afterwards."""
        if last >= len(padded_table) * self.h.config.block_tokens:
            raise ValueError(f"position {last} lies past a table of {len(padded_table)} blocks")
        stream = _Stream(padded_table, last)
        self._streams[id(padded_table)] = stream
        try:
            yield
        finally:
            stream.last, stream.ahead = -1, None
            del self._streams[id(padded_table)]

    # -- what the wave program returned beside its logits ---------------------

    # Waves whose counters are held apart before one small sum folds them.
    COUNTERS_FOLDED_EVERY = 64

    def _keep(self, aux: dict):
        for name, value in aux.get("counters", {}).items():
            held = self._step_counters.setdefault(name, [])
            held.append(value)
            if len(held) >= self.COUNTERS_FOLDED_EVERY:
                held[:] = [jnp.sum(jnp.stack(held))]

    def _mine(self, rows) -> WaveRows:
        if not isinstance(rows, WaveRows) or rows.decoder is not self:
            raise KeyError("these are not rows this decoder handed out")
        return rows

    def token_ids(self, rows: WaveRows) -> np.ndarray:
        """The greedy ids ``[len(rows)] int32`` of the logits ``rows`` that
        ``step_chunk`` handed a request, on the host: the wave program's own
        ``argmax(logits, -1)``, copied to the host since the launch. The
        first request of a wave to ask blocks once, for the whole wave's ids;
        every other reads that copy. Nothing is dispatched: the rows know
        their wave. ``KeyError`` for anything this decoder did not hand out."""
        return self._host_ids(rows, 0)

    def _host_ids(self, rows: WaveRows, which: int) -> np.ndarray:
        """Row ``which`` (0: the sampled ids, 1: the drafts) of the wave's one
        host copy, cut to ``rows``."""
        rows = self._mine(rows)
        out = rows.out
        if which and out.ids.ndim == 1:
            raise KeyError("this model's wave drafts nothing")
        if out.host_ids is None:
            out.host_ids = np.asarray(out.ids)
            self.blocking_reads += 1
        host = out.host_ids if out.host_ids.ndim == 1 else out.host_ids[which]
        return host[rows.off : rows.off + rows.n]

    def row_aux(self, rows: WaveRows):
        """The per-row ``aux`` slice the wave returned with the logits
        ``rows`` that ``step_chunk`` handed a request; ``KeyError`` for
        anything this decoder did not hand out, or of a model whose wave
        returns no per-row ``aux``."""
        rows = self._mine(rows)
        if rows.out.aux_rows is None:
            raise KeyError("this model's wave returns no per-row aux")
        return rows.out.aux_rows[rows.off : rows.off + rows.n]

    def draft_ids(self, rows: WaveRows) -> np.ndarray:
        """The draft ids ``[len(rows)] int32`` of a model that drafts, for the
        logits ``rows`` that ``step_chunk`` handed a request, on the host:
        row j's is the token the model's drafting layer proposes AFTER the one
        row j sampled. The same host copy ``token_ids`` reads: the wave's
        program hands back the ids and the drafts as one array, so a wave is
        ONE blocking read with or without a drafter. ``KeyError`` for anything
        this decoder did not hand out, or of a model that drafts nothing."""
        return self._host_ids(rows, 1)

    def step_counters(self) -> dict:
        """The model step's named counters, summed over every wave so far
        (a read waits for the last wave launched)."""
        return {
            name: int(sum(int(v) for v in held))
            for name, held in self._step_counters.items()
        }

    def _assemble(self, batch: List[tuple]) -> "_Wave":
        """The taken ``batch`` as one wave's host-side operands, and the
        decoder's pad and page ledgers moved by it: no ``await``, no device.

        Ragged assembly (class docstring): the chunks concatenated into one
        flat token list, padded only at the tail to the power-of-two row
        bucket by repeating the last flat row (same-bytes scatter,
        cache-safe). Table rows pad to a power-of-two B: no flat token
        references a padded row, so it neither scatters nor attends. Tables
        arrive host-resident (``_padded_table``): converting a DEVICE array
        here would pay a blocking sync per request per wave."""
        tokens: List[int] = []
        positions: List[int] = []
        row_of: List[int] = []
        t_real = 0
        for r, (toks, pos, _tbl, _fut) in enumerate(batch):
            # A slot's spare rows repeat the entry's last (width 1: none).
            slot = self._slot(toks)
            spare = slot - len(toks)
            tokens.extend(toks + toks[-1:] * spare)
            positions.extend(pos + pos[-1:] * spare)
            row_of.extend([r] * slot)
            t_real += len(toks)
        t_bucket = 1 << (len(tokens) - 1).bit_length()
        tail = t_bucket - len(tokens)
        tokens.extend([tokens[-1]] * tail)
        positions.extend([positions[-1]] * tail)
        row_of.extend([row_of[-1]] * tail)
        b_bucket = 1 << (len(batch) - 1).bit_length()
        tables = [np.asarray(b[2], dtype=np.int32) for b in batch]
        tables.extend([tables[-1]] * (b_bucket - len(batch)))
        # The builder picks the page bucket (pad_to_pow2): the per-row
        # page-count rule lives in build_ragged_wave alone.
        row_tables = [tables[r] for r in row_of]
        row_lens = [p + 1 for p in positions]
        bt = self.h.config.block_tokens
        meta = build_ragged_wave(row_tables, row_lens, bt, pad_to_pow2=True)
        # The wave's SECOND page list, which its sliding layers walk: per
        # row only the pages from its window's first on, padded to what the
        # bucket's rows can hold at most, so the bucket stays one program.
        # None where the cache's spec names no window.
        wmeta = None
        if self._window is not None:
            wmeta = build_ragged_wave(
                row_tables, row_lens, bt, window=self._window,
                pad_to=min(meta.num_pages, t_bucket * (self._window // bt + 1)),
            )
        self.bucket_sizes.add((b_bucket, t_bucket // self.width, meta.num_pages // self.width))
        self.pad_rows += t_bucket - t_real
        self.launched_rows += t_bucket
        self.wave_pages += meta.num_pages
        self.wave_pad_pages += meta.pad_pages
        real_pages = meta.num_pages - meta.pad_pages
        if wmeta is not None:
            self.wave_window_pages_skipped += self._sliding * (
                real_pages - (wmeta.num_pages - wmeta.pad_pages)
            )
        self.wave_layer_pages += self._layers * real_pages
        return _Wave(tokens, positions, row_of, meta, tables, wmeta, t_real)

    def _slot(self, toks: List[int]) -> int:
        """Flat rows the entry ``toks`` takes in a wave: its own, or the slot
        of a model that drafts."""
        return len(toks) if self.width == 1 else self.width

    def launch(self, tokens, positions, row_of, meta, tables, wmeta=None, prev_ids=None):
        """ONE wave on the device (cache-mutating: caller holds the exclusive
        gate): the wave's integer metadata goes up as one packed ``int32``
        operand (models/serving.py ``pack_wave``: ``tokens``, ``positions``,
        ``row_of`` ``[T]``, ``meta``'s page triple, the ``[B, max_blocks]``
        ``tables`` and, where the spec names a window, ``wmeta``'s triple),
        one jitted call runs the model's wave body on it, and the sampled
        ids start their way back to the host at once. ``prev_ids``: the
        ``feed`` of the wave whose rows the ``fed_token`` slots of ``tokens``
        name, already on the device; None where every token is the host's.
        Returns ``(logits [T, vocab] on the device, the wave's _WaveOut,
        aux)``."""
        pieces = [
            tokens, positions, row_of, meta.pages, meta.page_rows,
            meta.page_starts, np.stack(tables),
        ]
        if wmeta is not None:
            pieces += [wmeta.pages, wmeta.page_rows, wmeta.page_starts]
        layout = WaveLayout(
            len(tokens), len(tables), meta.num_pages,
            None if wmeta is None else wmeta.num_pages,
        )
        mrb = self.h.max_req_blocks
        logits, self.h.caches, ids, feed, aux = verify_step_ragged(
            self.h.params, pack_wave(layout, mrb, pieces),
            self._no_feed if prev_ids is None else prev_ids, self.h.caches,
            config=self.h.config, max_blocks=mrb, layout=layout,
        )
        ids.copy_to_host_async()
        return logits, _WaveOut(ids, feed, aux.get("rows")), aux

    # Rounds of its own a drafting stream's record needs before the guess of
    # its next verdict follows it and not the harness's.
    OWN_RECORD_ROUNDS = 8

    def _guess(self, stream: _Stream) -> bool:
        """Whether the draft a stream's next call brings will be accepted, by
        the likelier verdict so far: the stream's own once it has a few rounds,
        before that every request's (the harness's count of drafts accepted
        over drafts made); rejected while nothing is known."""
        if stream.rounds >= self.OWN_RECORD_ROUNDS:
            return 2 * stream.accepted > stream.rounds
        return 2 * self.h.spec_accepted > self.h.spec_drafted

    def _sort(self, batch: List[tuple]):
        """The taken ``batch`` by what each entry wants of this flush.

        ``taken``: ``(future, rows)`` of the calls whose slot was launched
        ahead of them; they resolve to those rows once this flush's wave is
        on the device. ``launched``: the entries that ride this flush's wave:
        the calls that have no slot yet (a drafting stream's call whose slot
        was launched under the other verdict among them: that slot is dropped),
        as they came, then, where ``ahead_ok``, one ``fed`` entry (no future,
        its tokens ``fed_token``s) for each taken call whose stream goes on and
        whose slot rode the wave launched last; the stream keeps the verdict it
        goes out under (``guess``: None where a slot is one row and there is
        nothing to guess). ``ahead_ok``: the engage rule (class docstring),
        asked once a flush, and only where a stream is in it."""
        ahead_ok = (
            any(id(table) in self._streams for _, _, table, _ in batch)
            and self.h.arriving == 0
            and self.h.gate.idle
        )
        width = self.width
        taken, launched, fed = [], [], []
        for entry in batch:
            toks, pos, table, fut = entry
            if fut.done():  # its request was cancelled while it queued
                continue
            stream = self._streams.get(id(table))
            if stream is None or stream.ahead is None:
                launched.append(entry)
                continue
            ahead, stream.ahead = stream.ahead, None
            fits, guessed = len(toks) == ahead.rows.n and pos[0] == ahead.position, ahead.accepted
            if not fits and (
                guessed is None or len(toks) > width
                or pos[0] != ahead.position + (-1 if guessed else 1)
            ):
                fut.set_exception(RuntimeError(
                    f"a declared stream came back with {len(toks)} token(s) at position "
                    f"{pos[0]}: its slot was launched for {ahead.rows.n} at {ahead.position}"
                ))
                continue
            if guessed is not None:
                # The call says which verdict held: the one guessed, or the other.
                stream.rounds += 1
                stream.accepted += guessed == fits
            if not fits:
                # The other verdict: through the host once, over what that slot wrote.
                self.ahead_dropped += 1
                launched.append(entry)
                continue
            taken.append((fut, ahead.rows))
            if not ahead_ok or ahead.rows.out is not self._last:
                continue
            # The stream's next slot, read from this one's rows on the device:
            # ``width`` tokens from the position after this slot's first. Under
            # a drafting model from its first row (what it sampled and drafted,
            # one position on) or, this slot's draft accepted, from its second
            # (two on): one of the two, by the likelier verdict; a slot of one
            # token had no draft to accept.
            src, at = ahead.rows.off, ahead.position + 1
            stream.guess = None
            if width > 1:
                stream.guess = ahead.rows.n == width and self._guess(stream)
                src, at = src + stream.guess, at + stream.guess
            if at + width - 1 <= stream.last and src < FEED_ROWS:
                slot = [fed_token(src, draft=bool(k)) for k in range(width)]  # the token, its draft
                fed.append((slot, list(range(at, at + width)), table, None))
        return taken, launched + fed, len(fed), ahead_ok

    def _resolve(self, launched: List[tuple], fed: int, ahead_ok: bool,
                 wave: "_Wave", logits, out, aux) -> List[tuple]:
        """A launched wave's rows to their entries, each a ``WaveRows`` on the
        wave's ``logits`` (no device call: nothing is cut until a reader asks),
        and the wave's counters kept. A call whose stream ends here, or has none,
        resolves now (only real rows' futures resolve). A call whose stream
        goes on keeps its rows as the stream's ``ahead`` and is returned, to be
        enqueued again: the flush that takes it launches the stream's next slot
        and only then resolves it, so the request blocks in its read-back
        with that slot already behind its wave on the device. Each of the last
        ``fed`` entries, which no call waits for yet, becomes its stream's
        ``ahead``, under the verdict ``_sort`` launched it under."""
        self.waves += 1
        self.waves_ahead += fed > 0
        self.one_row_waves += wave.real_rows == 1
        self.max_wave = max(self.max_wave, len(launched))
        self._last = out
        width = self.width
        off, again = 0, []
        for entry in launched:
            toks, pos, table, fut = entry
            if fut is None or not fut.done():
                rows = WaveRows(self, logits, out, off, len(toks))  # its own rows of its slot
                stream = self._streams.get(id(table))
                if fut is None:
                    if stream is not None:
                        stream.ahead = _Ahead(pos[0], rows, stream.guess)
                elif (
                    stream is not None and ahead_ok and pos[0] + width <= stream.last
                    and len(toks) <= width and off + width <= FEED_ROWS
                ):
                    stream.ahead = _Ahead(pos[0], rows)
                    again.append(entry)
                else:
                    fut.set_result(rows)
            off += self._slot(toks)
        self._keep(aux)
        return again

    @staticmethod
    def _hand(taken: List[tuple]):
        for fut, rows in taken:
            fut.set_result(rows)

    async def _flush(self):
        batch: List[tuple] = []
        wspan = None
        try:
            # Yield twice: once so sibling coroutines already unblocked this
            # tick can enqueue, once more for requests their completions wake.
            await asyncio.sleep(0)
            await asyncio.sleep(0)
            batch, self._pending = self._pending, []
            # New arrivals after this point start the next wave.
            self._flush_scheduled = False
            if not batch:
                return
            taken, launched, fed, ahead_ok = self._sort(batch)
            again: List[tuple] = []
            if not fed:
                # No next row of theirs rides this flush's wave: their rows
                # are theirs now, whatever the gate makes the wave wait for.
                self._hand(taken)
            if launched:
                # `wave`: a trace of its own (it serves many requests), one
                # span per launched flush.
                if tracing.enabled():
                    wspan = tracing.Span("wave")
                    wspan.stage("taken")
                wave = self._assemble(launched)
                if wspan is not None:
                    wspan.stage("assembled")
                    wspan.annotate(
                        entries=len(launched), real_rows=wave.real_rows,
                        rows=len(wave.tokens), pages=wave.meta.num_pages,
                        pad_pages=wave.meta.pad_pages, fed_rows=fed * self.width,
                    )

                # The flush task inherited the context of the request that
                # scheduled it: bind the wave's own span, so the gate wait
                # below is the wave's child and not that request's.
                with tracing.override_span(wspan):
                    async with self.h.gate.exclusive(holder="wave"):
                        if wspan is not None:
                            wspan.stage("gate")
                        with tracing.device_call("its.wave_dispatch", wspan):
                            logits, out, aux = self.launch(
                                wave.tokens, wave.positions, wave.row_of,
                                wave.meta, wave.tables, wave.wmeta,
                                self._last.feed if fed else None,
                            )
                        if wspan is not None:
                            wspan.stage("dispatched")
                again = self._resolve(launched, fed, ahead_ok, wave, logits, out, aux)
            if fed:
                # After the launch: a request handed its rows blocks the loop
                # in its read-back, and its stream's next row is then behind it.
                self._hand(taken)
            if again:
                self._enqueue(again)
            if wspan is not None:
                wspan.stage("resolved")
                wspan.finish()
        except BaseException as e:  # noqa: BLE001 - must fail the waiters
            if wspan is not None:
                wspan.finish(status=f"error:{type(e).__name__}")
            # A dead flush (model error, or cancellation/GC at shutdown)
            # must strand NO waiter: fail the taken batch and anything still
            # pending (a call whose row was launched ahead is in one of the
            # two like any other), and clear the flag so a later step()
            # starts fresh.
            self._flush_scheduled = False
            stranded, self._pending = batch + self._pending, []
            exc = e if isinstance(e, Exception) else RuntimeError(
                f"decode wave aborted: {e!r}"
            )
            for _, _, _, fut in stranded:
                if not fut.done():
                    fut.set_exception(exc)
            if not isinstance(e, Exception):
                raise


class NGramDrafter:
    """Prompt-lookup drafting: propose the tokens that FOLLOWED the most
    recent earlier occurrence of the request's current suffix n-gram in its
    own history (prompt + generated so far). Free speculation — no draft
    model, no device work — that wins exactly where serving workloads
    repeat themselves (quoting the prompt, code identifiers, templated
    text), and greedy verification makes output token-for-token identical
    to plain decode regardless of draft quality (tested). The same
    self-drafting idea as published prompt-lookup / LLMA decoding.
    """

    def __init__(self, max_draft: int = 7, ngram: int = 2):
        if max_draft < 1 or ngram < 1:
            raise ValueError("max_draft and ngram must be >= 1")
        self.max_draft = max_draft
        self.ngram = ngram

    def draft(self, history: Sequence[int]) -> List[int]:
        """Up to ``max_draft`` proposed continuations of ``history`` (empty
        when no suffix n-gram recurs — the caller then runs a plain decode
        step). Longest n first: a longer matched context drafts better."""
        h = list(history)
        for n in range(min(self.ngram, len(h) - 1), 0, -1):
            pattern = h[-n:]
            # Most recent earlier occurrence: scan right to left, excluding
            # the suffix occurrence itself (i + n <= len(h) - 1, so the
            # continuation is never empty).
            for i in range(len(h) - n - 1, -1, -1):
                if h[i : i + n] == pattern:
                    return h[i + n : i + n + self.max_draft]
        return []


class EngineKVAdapter:
    """vLLM-TPU-style connector surface over ``KVConnector`` (engine terms:
    token counts in, engine-owned physical block tables in, caches out)."""

    # This adapter can forward the two-class QoS tag (wire.PRIORITY_*) on
    # start_fetch; the harness gates tagging on the attribute so duck-typed
    # adapter stand-ins without the kwarg keep working.
    QOS_AWARE = True

    def __init__(self, connector):
        self.connector = connector
        self.block_tokens = connector.spec.block_tokens

    def get_num_matched_tokens(self, token_ids: Sequence[int]) -> int:
        """Admission-time probe: how many leading TOKENS of this prompt the
        store already holds (block-aligned; one control round trip)."""
        return self.connector.lookup(token_ids) * self.block_tokens

    def tier_location(self, token_ids) -> Optional[str]:
        """Which tier would serve this prompt right now — ``"hot"`` /
        ``"cold"`` / ``None`` — from the connector's catalog knowledge
        (``ClusterKVConnector.tier_location``, docs/tiering.md); ``None``
        for connectors without a tiered pool. Network-free: the harness
        consults this at admission to pick the staged two-phase path vs
        the direct one-phase load for a cold-only root."""
        fn = getattr(self.connector, "tier_location", None)
        return fn(token_ids) if fn is not None else None

    def note_tier_direct(self):
        """The harness skipped the staged prefetch for a cold-only root:
        count it in the connector's tier ledger too, so /metrics
        ``infinistore_tier_direct_reads`` reflects engine flows (the
        harness-local ``tier_direct_loads`` metric counts the same
        events engine-side)."""
        tiering = getattr(self.connector, "tiering", None)
        if tiering is not None:
            tiering.note_direct_read()

    def start_fetch(
        self, token_ids, limit_blocks: Optional[int] = None, priority: int = 0
    ):
        """Speculative, gate-free half of a load: probe + start streaming
        the hit prefix into host staging NOW (before the engine has even
        allocated blocks). Returns a prefetch handle (``hit_blocks``,
        ``install``, ``discard`` — KVConnector.start_fetch), or None when
        the underlying connector has no two-phase path (the caller then
        uses the one-phase ``load_kv``). StagingPoolExhausted propagates —
        it is admission backpressure, not failure.

        ``priority``: QoS class for the fetch's store reads
        (wire.PRIORITY_*) — the harness tags a prefetch BACKGROUND when
        the request cannot make the next wave anyway (docs/qos.md). The
        kwarg is forwarded only when nonzero and the connector advertises
        ``QOS_AWARE`` — a pre-QoS duck-typed connector keeps its old
        signature and the tag is dropped, never TypeError'd (the
        wire.qos_kwargs convention)."""
        if not hasattr(self.connector, "start_fetch"):
            return None
        kw = (
            {"priority": priority}
            if priority and getattr(self.connector, "QOS_AWARE", False)
            else {}
        )
        # Audited: sync entry point — the probe RTT is the caller's
        # documented cost; loop callers use start_fetch_async.
        return self.connector.start_fetch(  # its: allow[ITS-L001]
            token_ids, limit_blocks=limit_blocks, **kw
        )

    async def start_fetch_async(
        self, token_ids, limit_blocks: Optional[int] = None, priority: int = 0
    ):
        """``start_fetch`` for event-loop callers: routes to the
        connector's :meth:`~.connector.KVConnector.start_fetch_async`
        (probe RTT in an executor) when it has one; a sync-only
        duck-typed connector falls back to the inline probe, same as
        before this method existed."""
        sf_async = getattr(self.connector, "start_fetch_async", None)
        if sf_async is None:
            return self.start_fetch(
                token_ids, limit_blocks=limit_blocks, priority=priority
            )
        kw = (
            {"priority": priority}
            if priority and getattr(self.connector, "QOS_AWARE", False)
            else {}
        )
        return await sf_async(token_ids, limit_blocks=limit_blocks, **kw)

    async def install_kv(self, prefetch, caches, block_table: np.ndarray):
        """The short exclusive half: scatter a prefetch's staged layers
        into the engine's cache blocks. Same contract as ``load_kv``
        (donation; returns (caches, tokens_loaded))."""
        out, blocks = await prefetch.install(caches, block_table)
        return out, blocks * self.block_tokens

    async def load_kv(self, token_ids, caches, block_table: np.ndarray):
        """Fetch the cached prefix into the engine's cache blocks. Returns
        (updated caches, tokens_loaded). Input caches are consumed
        (donation) — use the returned ones."""
        out, blocks = await self.connector.load(token_ids, caches, block_table)
        return out, blocks * self.block_tokens

    async def save_kv(
        self, token_ids, caches, block_table: np.ndarray, first_block: int = 0
    ) -> int:
        """Stream this request's computed KV blocks to the store (layer by
        layer, D2H overlapping the network). ``first_block``: logical index
        of block_table[0] within the prompt — pass the prefix-hit count to
        save only the computed suffix (the loaded prefix is already stored)."""
        return await self.connector.save(
            token_ids, caches, block_table, first_block=first_block
        )

    def evict_request(self, token_ids) -> int:
        """Drop a request's blocks from the store (engine-initiated)."""
        return self.connector.drop(token_ids)


@dataclass
class RequestStats:
    """Per-request outcome, engine-side."""

    tokens: int
    hit_blocks: int  # lookup()'s admission answer
    loaded_blocks: int  # what load actually delivered (== hit unless raced)
    computed_blocks: int
    admission_us: float  # t0 -> prefix load settled (the scheduler stall)
    raced_eviction: bool  # lookup hit but blocks evicted before the read
    verified: Optional[bool]  # None when verification is off
    generated: Optional[List[int]] = None  # wave-decoded tokens (greedy)
    # Decomposition of admission_us: what the STORE cost (admission lookup
    # + the load pipeline: fetch/H2D/scatter) vs what was spent WAITING for
    # the exclusive device gate behind other requests' loads and computes.
    # The two do not sum to admission_us (event-loop scheduling and future
    # plumbing fill the gap) but each is individually honest — a fat
    # gate_stall with a thin store_io means the engine is compute-bound,
    # not store-bound. gate_stall_us totals EVERY exclusive-gate wait the
    # request paid (install at admission, then the compute phase), so
    # misses — which no longer touch the gate at admission — still report
    # their queue time. Both gate figures are the gate's own readings
    # (``GateHold.waited_us`` / ``.held_us``: ``DeviceGate`` is the one place
    # that times the gate).
    store_io_us: float = 0.0
    gate_stall_us: float = 0.0
    # Two-phase admission (prefetch path): how long the exclusive gate was
    # actually HELD for the install (host->device scatter — the only part
    # of a load that still needs exclusivity), the gate-free store fetch's
    # duration, and what fraction of that fetch ran while this request
    # held NO gate (1.0 = store I/O fully hidden behind other work).
    # What the two really span: gate_hold_us is the whole `install_kv`
    # await, which on the per-layer path also awaits every layer that had
    # not landed yet (a store read under the exclusive gate: the
    # `install_staged_wait` span). fetch_us runs from the prefetch's
    # construction to the moment its LAST layer landed; with fewer staging
    # regions than layers (at most 8), layer L >= regions starts its read
    # only once install consumed region L - regions, so fetch_us holds the
    # request's own alloc, primed(), gate wait and first uploads: bytes
    # over it is not the store's rate (that is `hit_read_bytes` over
    # `hit_read_busy_us`, the connector's get_stats(); per layer, the
    # `fetch_layer` span's `region_free` -> `landed`).
    gate_hold_us: float = 0.0
    fetch_us: float = 0.0
    overlap_fraction: Optional[float] = None
    # Prefetch accounting: K+V blocks staged for this request, and how
    # many of those never reached the device (discarded on raced
    # eviction / cancellation — the waste the speculation paid).
    prefetched_blocks: int = 0
    wasted_blocks: int = 0
    # t0 -> the request's ENTIRE prefix resident on device (loaded and/or
    # computed): the end-to-end figure that decides whether a cache hit
    # actually beats recomputing.
    prefix_ready_us: float = 0.0
    # t0 -> the first wave handed this request its logits rows (0.0 when
    # gen_tokens == 0): the serving-side latency figure. It PRECEDES the
    # read-back that puts the sampled token on the host; the emit time
    # itself is token_emit_s[0]. And the request's QoS class
    # (wire.PRIORITY_*), so TTFT percentiles split by class.
    ttft_us: float = 0.0
    priority: int = 0
    # time.perf_counter() of every generated token, taken once the token
    # is on the host (after the round's read-back); always filled, tracing
    # on or off. A speculative round that emits several tokens stamps them
    # all with the one reading.
    token_emit_s: List[float] = field(default_factory=list)
    # The request's trace (tracing.py): every span it recorded carries
    # this id. 0 with tracing off.
    trace_id: int = 0
    # The prompt save's store write runs beside the request's generation
    # (run_request). save_overlap_us: how long it ran while the request
    # generated. save_tail_us: how long the request, its last token out,
    # still waited for the write's acknowledgement (0.0 when it had
    # arrived). Both 0.0 where nothing was computed or nothing generated.
    # save_tail_us is the PROMPT write's tail alone; ack_tail_us is the
    # whole: `_generate`'s return to the last acknowledgement (the
    # `generated` and `acknowledged` stamps of `engine_request`), so the
    # prompt write's tail and the answer's snapshot and write (0.0 when
    # gen_tokens == 0).
    save_overlap_us: float = 0.0
    save_tail_us: float = 0.0
    ack_tail_us: float = 0.0


class ContinuousBatchingHarness:
    """Drive N concurrent requests through the adapter against one shared
    paged cache — the BASELINE config-4 workload shape (vLLM paged-KV via an
    LMCache-style connector), minus the real engine.

    Drive one harness instance from ONE event loop: its asyncio primitives
    (pool/gate conditions, wave futures) bind to the loop that first awaits
    them, so spreading requests across several ``asyncio.run`` calls raises
    "bound to a different event loop" once anything actually blocks.

    ``verify=True`` recomputes every request with a fresh one-shot prefill
    (the model's own oracle) and compares the harness cache's blocks —
    catching any stale/corrupt bytes a load under eviction churn could have
    delivered. Decode-computed suffixes match the prefill oracle to float
    tolerance (same bound the model tests use); store-loaded prefixes are
    byte-identical by the data plane's contract.
    """

    def __init__(
        self,
        adapter: EngineKVAdapter,
        params,
        config,
        num_blocks: int,
        max_req_blocks: int,
        verify: bool = False,
        verify_tol: float = 2e-4,
        drafter: Optional[NGramDrafter] = None,
    ):
        """``drafter``: enables speculative decoding in the serving loop —
        each generation round verifies the drafted chunk in one wave row
        (verify_step_ragged), emitting every greedy-accepted token plus
        the model's continuation, so tokens/round can exceed 1 with output
        identical to plain greedy decode. A model that drafts ITSELF brings
        its drafter with its configuration, as it brings its steps
        (``config.steps.drafts``, models/serving.py): nothing is handed in
        here, and a host drafter beside it is refused."""
        self.adapter = adapter
        self.params = params
        self.config = config
        self.drafter = drafter
        self.spec_rounds = 0  # generation waves a request participated in
        self.spec_drafted = 0  # draft tokens proposed
        self.spec_accepted = 0  # draft tokens accepted
        self.spec_emitted = 0  # tokens those rounds emitted
        # The model's three steps (``config.steps``, models/serving.py) and
        # its cache's shape come with the configuration: no model file is
        # named here.
        self.spec = config.kv_spec(num_blocks)
        # A prompt is computed a block at a time (``_compute_by_blocks``)
        # where a recurrent state absorbs a token once, and where the model's
        # resume step takes no more than a block's part (``ServingSteps``).
        self.by_blocks = self.spec.has_state or config.steps.resume_in_block
        # Whether the model drafts itself: each round's chunk is then
        # ``[token, draft]``, the draft the wave before's (``_generate``).
        self.drafts = config.steps.drafts
        if self.spec.has_state and (drafter is not None or self.drafts):
            # A rejected row's slot in a latent or K/V cache is overwritten by
            # the next round's first row, whether or not the cache is served
            # by blocks; a state would keep the rejected row absorbed.
            raise ValueError(
                "a cache that holds a recurrent state absorbs every row it is handed, a "
                "rejected draft's too: no drafter (a latent or K/V cache takes one, served "
                "by blocks or not)"
            )
        if self.drafts and (drafter is not None or not config.steps.resume_in_block):
            raise ValueError(
                "a model that drafts itself (config.steps.drafts) takes no second drafter, and "
                "its resume step lies inside one block (the piece's next token is its operand)"
            )
        self.caches = self.spec.make_caches()
        self.pool = BlockPool(num_blocks)
        self.gate = DeviceGate()
        self.wave = WaveDecoder(self)
        self.max_req_blocks = max_req_blocks
        self.verify = verify
        # float-exact stores hold 2e-4; a quantizing adapter (int8 blocks,
        # tpu/kv_quant.py QuantizingKVAdapter) needs the scheme's tolerance.
        self.verify_tol = verify_tol
        # Instrumentation the test pins: request-level concurrency and
        # overlapping store writes.
        self.live = 0
        self.max_live = 0
        # With the recorder on: the open `no_request_live` span while `live`
        # is 0 after a request has left (run_request).
        self._nobody_live: Optional[tracing.Span] = None
        # Requests admitted and not yet in a wave: each will ask for the
        # device (an install, a prefill or a resume, the prompt snapshot's
        # wait) before its first round, and a wave launched ahead of its need
        # would stand in front of it. While it is non-zero the decoder
        # launches none (``WaveDecoder``, "one wave ahead"). Not ``live``,
        # which also holds the requests in their save's tail.
        self.arriving = 0
        self._saving = 0
        self.max_concurrent_saves = 0
        # Prompt saves whose store write ran as a task beside the request's
        # own `_generate` (run_request): every request that computed
        # blocks and generates.
        self.saves_overlapped = 0
        # Prefix hits resumed as one chunk (_chunked_resume): how many, the
        # suffix tokens they computed and the context pages they attended
        # (ceil((prefix + suffix) / block_tokens) each: what the chunk
        # kernel walks once, whatever the table's padding).
        self.resumes = 0
        self.resume_tokens = 0
        self.resume_pages = 0
        # Rows the prompt pieces of a model served a block at a time computed
        # (``_compute_by_blocks``): ``metrics()`` adds them to the step counter
        # the model names for them (``config.prompt_rows_counter``: a model
        # whose prompt steps run a part of its stack counts the rows EVERY
        # program computed beside those its wave counts under that name).
        self.prompt_rows = 0
        # Admissions that wanted a prefetch but found the staging arena
        # full and fell back to the one-phase gated load (backpressure).
        self.prefetch_fallbacks = 0
        # Admissions whose root was COLD-ONLY (tiered capacity plane,
        # docs/tiering.md): the staged prefetch was skipped on purpose and
        # the one-phase load read the root directly from the cold pool.
        self.tier_direct_loads = 0
        # Prefetch bytes from requests that DIED before install (cancelled
        # mid-admission): they never reach self.stats, but their waste is
        # real and must show in prefetch_waste.
        self._prefetch_extra_fetched = 0
        self._prefetch_extra_wasted = 0
        self.stats: List[RequestStats] = []
        self._prefill_per_block_s: Optional[float] = None

    # -- model compute -------------------------------------------------------

    def _step_counters(self) -> dict:
        """The wave's named counters, the prompt pieces' rows added to the one
        the model names for them (``prompt_rows``)."""
        counters = self.wave.step_counters()
        name = getattr(self.config, "prompt_rows_counter", None)
        if name is not None:
            counters[name] = counters.get(name, 0) + self.prompt_rows
        return counters

    def _padded_table(self, table: np.ndarray) -> np.ndarray:
        """Host-resident padded table. Numpy ON PURPOSE: the WaveDecoder
        re-reads it every flush to assemble ragged metadata, and a device
        array there would cost a blocking device->host sync per request
        per wave (jitted callees convert the small [max_blocks] int32 at
        trace time either way)."""
        pad = np.zeros(self.max_req_blocks, dtype=np.int32)
        pad[: len(table)] = table
        return pad

    def _compute_by_blocks(self, token_ids, table: np.ndarray, start_block: int):
        """A model served a block at a time (``self.by_blocks``): ``token_ids``
        from block ``start_block`` on, cut at block boundaries through the
        model's resume step, one program a piece, so that a miss runs the very
        programs a hit's resume runs and, where the cache has a recurrent
        state, every block's slot holds the state at its end (what its save
        writes and a later hit installs). Each piece that completes a block
        is a ``state_snapshot`` span (``block``), a child of the caller's
        ``compute``. Returns at DISPATCH. Cache-mutating: caller holds the
        exclusive gate."""
        bt = self.config.block_tokens
        padded = self._padded_table(table)
        first = start_block * bt
        if self.drafts:
            # Handed the WHOLE prompt (``run_request``): its last token is
            # landed by the first wave, and is here the last piece's operand.
            token_ids, next_token = token_ids[:-1], token_ids[-1]
        for start in range(first, len(token_ids), bt):
            piece = jnp.asarray(token_ids[start : start + bt], jnp.int32)
            # A piece that completes its block leaves a snapshot a save can write.
            whole = piece.shape[0] == bt
            snapshot = tracing.trace_op("state_snapshot") if whole else contextlib.nullcontext()
            kw = {}
            if self.drafts:
                # The drafting layer's slot at the piece's last position is a
                # function of the token AFTER it: the next piece's first, or
                # ``next_token`` (the prompt's last, which the first wave lands).
                after = token_ids[start + bt] if start + bt < len(token_ids) else next_token
                kw["next_token"] = jnp.int32(after)
            # A hit's first piece carries the rewrite of the one slot of the
            # drafting layer that the installed prefix could not know.
            rewrite = (
                tracing.trace_op("boundary_rewrite")
                if self.drafts and start == first and first > 0 else contextlib.nullcontext()
            )
            with snapshot as span, rewrite as rspan:
                if span is not None:
                    span.annotate(block=start // bt)
                if rspan is not None:
                    rspan.annotate(block=start_block - 1, slot=first - 1)
                _, self.caches = self.config.steps.resume(
                    self.params, piece, jnp.int32(start), self.caches, padded,
                    self.config, self.max_req_blocks, **kw,
                )
            self.prompt_rows += piece.shape[0]

    def _prefill_full(self, token_ids, table: np.ndarray):
        """Whole-prompt prefill into this request's blocks (cache-mutating:
        caller holds the exclusive gate)."""
        t0 = time.perf_counter()
        if self.by_blocks:
            self._compute_by_blocks(token_ids, table, 0)
        else:
            _, self.caches = self.config.steps.prefill(
                self.params,
                jnp.asarray(token_ids, dtype=jnp.int32),
                self.caches,
                jnp.asarray(table),
                self.config,
            )
        jax.block_until_ready(self.caches[-1][0])
        # Calibrates recompute_saved_s: what one block of prefill costs
        # on this device. Min across calls — the first includes the jit
        # compile, which a steady-state engine never pays per request.
        per_block = (time.perf_counter() - t0) / len(table)
        if self._prefill_per_block_s is None or per_block < self._prefill_per_block_s:
            self._prefill_per_block_s = per_block

    def _chunked_resume(self, token_ids, table: np.ndarray, start_block: int):
        """Compute the suffix after a prefix hit as ONE chunked continuation
        (models/llama.py prefill_continue -> resume_chunk, the engine's
        chunked-prefill resume path): a program of its own whose attention
        reads each of the request's context pages once for the whole chunk
        (tpu/chunk_attention.py), with chunk-wide GEMMs, instead of S_c
        decode rows that each walk the padded table. Returns at DISPATCH;
        the device time is first waited for by whoever reads the cache next
        (the save's snapshot). Cache-mutating: caller holds the exclusive
        gate. A model served a block at a time takes the suffix so too
        (``_compute_by_blocks``)."""
        bt = self.config.block_tokens
        self.resumes += 1
        landed = len(token_ids) - self.drafts  # a drafting model is handed the whole prompt
        self.resume_tokens += landed - start_block * bt
        self.resume_pages += -(-landed // bt)
        if self.by_blocks:
            return self._compute_by_blocks(token_ids, table, start_block)
        suffix = jnp.asarray(token_ids[start_block * bt :], jnp.int32)
        _, self.caches = self.config.steps.resume(
            self.params,
            suffix,
            jnp.int32(start_block * bt),
            self.caches,
            self._padded_table(table),
            self.config,
            self.max_req_blocks,
        )

    async def _snapshot_blocks(self, phys_blocks, before_first_token: bool = False):
        """A save's first phase: gather the given physical blocks into
        PRIVATE arrays under the shared gate (device-side gathers) and wait
        until they are ready. What comes back no later wave, install or
        prefill can change, so the write may run beside any of them.

        One ``save_snapshot`` span with ``blocks`` and
        ``before_first_token`` (true for the prompt's snapshot, which the
        first token waits for): the shared gate's wait (its ``gate_wait``
        child), the executor hop, the gathers and their readiness wait."""
        dev = jnp.asarray(np.asarray(phys_blocks))
        with tracing.trace_op("save_snapshot") as sspan:
            if sspan is not None:
                sspan.annotate(
                    blocks=len(phys_blocks), before_first_token=before_first_token
                )
            async with self.gate.shared(holder="snapshot"):
                caches = self.caches  # stable under the shared gate

                def snap():
                    with tracing.device_call("its.save_snapshot", sspan):
                        s = [
                            tuple(gather_blocks(t, dev) for t in layer)
                            for layer in caches
                        ]
                        jax.block_until_ready(s)
                    return s

                # Executor: the gathers + readiness wait must not pin the
                # event loop (it is the artery every gate-free fetch
                # completion and wave flush flows through).
                return await asyncio.get_running_loop().run_in_executor(None, snap)

    async def _write_snapshot(
        self, chain_ids, snapshot, first_block: int,
        save_class: Optional[dict] = None,
    ):
        """A save's second phase: stream a snapshot's blocks to the store
        with NO gate held, keyed by ``chain_ids`` from logical block
        ``first_block`` on. Returns the perf_counter readings of its start
        and its end (the store's acknowledgement).

        ``save_class``: the write's QoS class cell (``wire.SAVE_CLASS``,
        bound here around the adapter call, whose signature carries no
        class). None: the caller awaits this write in line, so it is
        FOREGROUND from its first put. ``run_request`` hands the prompt
        write it runs beside ``_generate`` a BACKGROUND cell and flips it at
        the join.

        One ``save_io`` span, the whole ``adapter.save_kv`` await, with
        ``blocks``, ``before_first_token`` (false since PR 25: no first
        token waits for a store write) and ``overlaps_generate`` (true
        where ``run_request`` ran it as a task beside ``_generate``)."""
        n = len(snapshot[0][0])
        overlaps_generate = save_class is not None
        if save_class is None:
            save_class = {"value": PRIORITY_FOREGROUND}
        t_start = time.perf_counter()
        self._saving += 1
        self.max_concurrent_saves = max(self.max_concurrent_saves, self._saving)
        bound = SAVE_CLASS.set(save_class)
        try:
            with tracing.trace_op("save_io") as iospan:
                await self.adapter.save_kv(
                    chain_ids,
                    snapshot,
                    np.arange(n, dtype=np.int32),
                    first_block=first_block,
                )
                # Last: the store's write ops annotate the span they run
                # under too (`op`, and one op's `blocks`).
                if iospan is not None:
                    iospan.annotate(
                        blocks=n,
                        before_first_token=False,
                        overlaps_generate=overlaps_generate,
                    )
        finally:
            SAVE_CLASS.reset(bound)
            self._saving -= 1
        return t_start, time.perf_counter()

    async def _save_blocks(
        self, chain_ids, phys_blocks, first_block: int,
        before_first_token: bool = False,
    ):
        """Snapshot the given physical blocks (``_snapshot_blocks``: under
        the shared gate), then stream them to the store with NO gate held
        (``_write_snapshot``): the save — the long store-I/O phase —
        overlaps other requests' loads, computes, and saves. Holding the
        gate across the save would serialize the whole pipeline (the next
        request's exclusive load waits on it). ``chain_ids`` key the blocks
        (the prompt, or prompt + generated for response blocks). Both
        phases in turn, awaited: the caller has its acknowledgement when
        this returns. ``run_request`` runs the two apart for a prompt that
        generates (its docstring)."""
        snapshot = await self._snapshot_blocks(phys_blocks, before_first_token)
        await self._write_snapshot(chain_ids, snapshot, first_block)

    async def _generate(self, token_ids, table: np.ndarray, gen_tokens: int):
        """Greedy generation through the shared WaveDecoder: every live
        request advances one round per lockstep wave (the continuous-
        batching inner loop). The first round re-decodes the last prompt
        token — its K/V insert rewrites identical bytes (the decode ==
        prefill invariant) and yields the logits that choose token one.

        With a ``drafter``, each round's wave row is a CHUNK: the committed
        token plus drafted continuations, verified in one pass (row j's
        argmax follows chunk[:j+1], so chunk[j+1] is accepted iff it equals
        that argmax — the speculative_verify recurrence, models/llama.py).
        Every accepted token plus the model's own continuation is emitted:
        tokens/round > 1 whenever drafts land, and rejected rows cost
        nothing (their K/V is masked by position until real tokens
        overwrite it). The chunk is capped to the tokens still wanted, so
        a round never overshoots ``gen_tokens``.

        Returns ``(tokens, first_token_t, emit_s)``: the perf_counter stamp
        of the first wave's result feeds ``RequestStats.ttft_us``, and
        ``emit_s`` (one perf_counter reading per token, taken after the
        round's read-back) is ``RequestStats.token_emit_s``.

        One ``generate`` span per request, with three stage stamps a round
        (a span per token would be thousands a minute): ``wave_enqueue``
        as the chunk goes to the decoder, ``wave_result`` when the wave's
        future hands the rows back, ``token`` once the sampled token is on
        the host."""
        padded = self._padded_table(table)
        pos = len(token_ids) - 1
        tok = int(token_ids[-1])
        history = list(token_ids)
        out: List[int] = []
        emit_s: List[float] = []
        first_token_t: Optional[float] = None
        closing = (len(token_ids) + gen_tokens) % self.config.block_tokens == 0
        # What this loop can promise of its rounds, it declares: the decoder may
        # then have round k + 1's slot on the device while round k's rows are
        # read here (``WaveDecoder.stream``). Without a drafter every round is
        # one token at the next position, the closing step too. A model that
        # drafts brings ``[token, draft]`` while two tokens are still wanted,
        # one or two positions on by a verdict the device knows first; its
        # one-token rounds (the first, the last, the closing step) are
        # launched from here. A host drafter's next chunk is the host's to write.
        if self.drafter is not None:
            declared = contextlib.nullcontext()
        else:
            declared = self.wave.stream(
                padded, pos + gen_tokens - 1 + (closing and not self.drafts)
            )
        drafting = self.drafter is not None or self.drafts
        draft: Optional[int] = None  # the model's own, from the round before
        accepted: List[int] = []  # drafts accepted, a round
        with tracing.trace_op("generate") as gspan, declared:
            while len(out) < gen_tokens:
                chunk = [tok]
                remaining = gen_tokens - len(out)
                if self.drafter is not None:
                    chunk += self.drafter.draft(history)[: remaining - 1]
                elif draft is not None and remaining > 1:
                    chunk.append(draft)
                if gspan is not None:
                    gspan.stage("wave_enqueue")
                rows = await self.wave.step_chunk(
                    chunk, list(range(pos, pos + len(chunk))), padded
                )
                if gspan is not None:
                    gspan.stage("wave_result")
                if first_token_t is None:
                    first_token_t = time.perf_counter()
                # The wave's own argmaxes: its first request to ask blocks
                # for the wave's ONE device->host read, the others read the
                # host copy (``WaveDecoder.token_ids``).
                with tracing.device_call("its.readback", gspan):
                    preds = self.wave.token_ids(rows)
                    if self.drafts:
                        drafted = self.wave.draft_ids(rows)
                now = time.perf_counter()
                if gspan is not None:
                    gspan.stage("token")
                n_acc = 1
                while n_acc < len(chunk) and chunk[n_acc] == int(preds[n_acc - 1]):
                    n_acc += 1
                emitted = chunk[1:n_acc] + [int(preds[n_acc - 1])]
                out.extend(emitted)
                emit_s.extend([now] * len(emitted))
                history.extend(emitted)
                self.spec_rounds += 1
                self.spec_drafted += len(chunk) - 1
                self.spec_accepted += n_acc - 1
                self.spec_emitted += len(emitted)
                accepted.append(n_acc - 1)
                pos += n_acc
                tok = emitted[-1]
                if self.drafts:
                    # The last accepted row's draft: what would follow ``tok``.
                    draft = int(drafted[n_acc - 1])
            if gspan is not None and drafting:
                gspan.annotate(accepted=accepted)
            # Each round inserts its CHUNK's K/V; the final emitted token's
            # insert only happens as the next round's committed token. When
            # it completes a block (which the extended-chain save below
            # persists), one more step lands it; otherwise its block is an
            # incomplete tail with no chain key — skip the wasted wave.
            if closing:
                await self.wave.step_chunk([tok], [pos], padded)
        return out, first_token_t, emit_s

    def _verify_request(self, token_ids, table: np.ndarray) -> bool:
        """Compare the harness cache's blocks for this request against a
        fresh one-shot prefill oracle (gather-only on the shared cache)."""
        n = len(table)
        oracle_caches = self.config.kv_spec(n).make_caches()
        _, oracle_caches = self.config.steps.prefill(
            self.params,
            jnp.asarray(token_ids, dtype=jnp.int32),
            oracle_caches,
            jnp.arange(n, dtype=jnp.int32),
            self.config,
        )
        ids = jnp.asarray(table)
        for layer in range(len(self.caches)):
            for kind in range(len(self.caches[layer])):
                got = np.asarray(
                    gather_blocks(self.caches[layer][kind], ids), np.float32
                )
                want = np.asarray(oracle_caches[layer][kind], np.float32)
                if not np.allclose(
                    got, want, rtol=self.verify_tol, atol=self.verify_tol
                ):
                    return False
        return True

    # -- request lifecycle ---------------------------------------------------

    async def run_request(
        self,
        token_ids: Sequence[int],
        gen_tokens: int = 0,
        priority: int = 0,
    ) -> RequestStats:
        """One request, from admission to its acknowledgement: prefix
        fetch and install, compute of what the store did not hold, the
        save of what was computed, ``gen_tokens`` of generation, the save
        of the complete blocks the answer filled.

        The prompt's save is two phases (``_save_blocks``). Its snapshot is
        awaited before the first wave, which rewrites the last prompt
        block. Its store write waits for nothing the generation needs, so
        with ``gen_tokens > 0`` it runs as a task beside ``_generate``
        (since PR 25; no first token waits for a store write) and is
        joined after the last token, before the answer's save starts.
        Returning is the acknowledgement: every computed prompt block and
        every complete answer block has been written by then, the blocks
        are back in the pool and the stats are appended. A failed write
        raises from here; if the generation raises or the task is
        cancelled, the write is cancelled and awaited first. With
        ``gen_tokens == 0`` the save is awaited in line.

        ``priority``: the request's QoS class (wire.PRIORITY_*).
        BACKGROUND requests tag their speculative store prefetch
        background; the class is recorded on the stats so TTFT percentiles
        split by class."""
        bt = self.config.block_tokens
        if self.by_blocks:
            # The first wave decodes the prompt's last token (a recurrent
            # state absorbs a token ONCE): the compute phase lands every
            # token but that one (``landed``), so the prompt's complete
            # blocks, which a save writes and a hit may install, are those
            # of ``landed``; the prompt keeps a part-full last block.
            token_ids = list(token_ids)
            landed = token_ids[:-1]
            n_blocks = len(landed) // bt
            total_blocks = -(-(len(token_ids) + gen_tokens) // bt)
            ok = len(token_ids) > 0
        else:
            n_blocks = len(token_ids) // bt
            total_blocks = -(-(n_blocks * bt + gen_tokens) // bt)
            token_ids = landed = list(token_ids)[: n_blocks * bt]
            ok = n_blocks > 0
        # What a hit may install of the prompt. A drafting layer's slot at
        # position i is a function of token i + 1, so the LAST landed slot is
        # a function of the prompt's last token, which no block's chain of
        # hashes covers: where the landed prompt ends with a block, that
        # block is computed (its resume rewrites the slot behind it too).
        hit_limit = n_blocks - 1 if self.drafts and n_blocks * bt == len(landed) else n_blocks
        if not ok or total_blocks > self.max_req_blocks:
            raise ValueError(
                f"prompt + generation must span 1..{self.max_req_blocks} "
                "blocks (prompt in complete blocks; served a block at a time, any "
                "prompt that generates)"
            )
        self.live += 1
        self.max_live = max(self.max_live, self.live)
        if self._nobody_live is not None:  # the stretch with no request live ends here
            self._nobody_live.finish()
            self._nobody_live = None
        self.arriving += 1
        arriving = True
        # Trace root for this request (docs/observability.md): `enqueue` is
        # stamped at admission t0, `alloc_done` with its blocks in hand, a
        # hit's `primed` when its fetch pipeline is full, `install` when
        # fetched bytes land in the paged cache; a hit's layer reads are
        # its `fetch_layer` children (each the active span of its store
        # read: prefetch -> coalescer -> striped scheduler -> wire), and so
        # are the request's own phases: `pool_alloc`, `gate_wait`,
        # `install`, `compute`, `save_snapshot`, `save_io`, `generate`.
        # With tracing off each hook is a no-op call.
        rspan = tracing.start_span("engine_request")
        rtoken = tracing.bind_span(rspan)
        if rspan is not None:
            rspan.stage("enqueue")
            rspan.annotate(
                tokens=len(token_ids), blocks=n_blocks, gen_tokens=gen_tokens
            )
        # Speculative prefetch AT ENQUEUE: probe + start streaming the hit
        # prefix into host staging before BlockPool.alloc even completes —
        # the store fetch overlaps this request's own admission wait and
        # every other request's compute, and NEVER holds the device gate.
        t0 = time.perf_counter()
        prefetch = None
        prefetch_settled = True  # nothing to discard until a fetch starts
        fallback_hit: Optional[int] = None  # probe answer from a failed start_fetch
        table = None
        prompt_write: Optional[asyncio.Future] = None  # the prompt save's store write
        # One try for the whole admission (the speculative starter INCLUDED):
        # a probe that dies on a dead store must still release the live
        # count, unbind the trace context, and finish the request span —
        # otherwise the task's later ops parent under a zombie span.
        try:
            # getattr: adapters without a two-phase path (QuantizingKVAdapter)
            # simply keep the one-phase gated load below. Prefer the async
            # variant — it hops the probe RTT through an executor instead of
            # blocking this loop mid-wave (ITS-L001).
            starter = getattr(
                self.adapter, "start_fetch_async",
                getattr(self.adapter, "start_fetch", None),
            )
            # Tier consult (docs/tiering.md): a COLD-ONLY root skips the
            # staged speculative prefetch entirely — a slow pooled-cold
            # read must not reserve (and hold hostage) staging regions the
            # current wave's hot fetches need. The one-phase load below
            # reads it DIRECTLY from the cold member instead (the DAK
            # direct-access path). Network-free check (catalog knowledge).
            tier_fn = getattr(self.adapter, "tier_location", None)
            if starter is not None and tier_fn is not None:
                if tier_fn(token_ids) == "cold":
                    starter = None
                    self.tier_direct_loads += 1
                    note = getattr(self.adapter, "note_tier_direct", None)
                    if note is not None:
                        note()
            starter_is_async = asyncio.iscoroutinefunction(starter)
            if starter is not None:
                # QoS: a request the block pool cannot admit right now is
                # beyond the next wave — its speculative fetch is
                # opportunistic, so it rides BACKGROUND class and never
                # delays the current wave's decode-blocking reads. Requests
                # that can start immediately keep the FOREGROUND (untagged)
                # fetch. Only adapters that advertise the kwarg (QOS_AWARE)
                # are tagged.
                fetch_kw = {}
                if getattr(self.adapter, "QOS_AWARE", False) and (
                    self.pool.available < total_blocks
                    or priority == PRIORITY_BACKGROUND
                ):
                    fetch_kw["priority"] = PRIORITY_BACKGROUND
                try:
                    result = starter(token_ids, limit_blocks=hit_limit, **fetch_kw)
                    prefetch = await result if starter_is_async else result
                except StagingPoolExhausted as e:
                    # Admission backpressure: the staging arena is carrying a
                    # full wave already — this request takes the gated load,
                    # reusing the probe the failed start_fetch already paid.
                    self.prefetch_fallbacks += 1
                    fallback_hit = getattr(e, "hit_blocks", None)
            lookup_s = time.perf_counter() - t0  # start_fetch includes the probe
            prefetch_settled = prefetch is None or prefetch.n_blocks == 0
            table = await self.pool.alloc(total_blocks)
            if rspan is not None:
                rspan.stage("alloc_done")
            if prefetch is not None:
                # Admitted: a background-tagged speculative fetch is
                # decode-blocking from here — upgrade its remaining
                # submissions to foreground (no-op when already untagged).
                promote = getattr(prefetch, "promote", None)
                if promote is not None:
                    promote()
            prompt_table = table[:n_blocks]  # tail blocks (if any) are for generation
            # The blocks the compute phase writes: the prompt's complete ones
            # and, under a recurrent state, its part-full last.
            landed_table = table[: -(-len(landed) // bt)]
            gate_hold_us = fetch_us = 0.0
            overlap = None
            if prefetch is not None:
                # -- pipelined admission: fetch (gate-free) then install --
                hit_tokens = prefetch.hit_blocks * bt
                loaded_tokens = 0
                gate_stall_us = store_io_us = 0.0
                if prefetch.n_blocks:
                    # Wait for the fetch pipeline to fill WITHOUT the gate:
                    # the store I/O runs while other requests compute.
                    await prefetch.primed()
                    if rspan is not None:
                        rspan.stage("primed")
                    async with self.gate.exclusive(holder="install", expedite=True) as held:
                        with tracing.trace_op("install") as ispan:
                            if ispan is not None:
                                sliding, full = self.spec.hit_values(prefetch.n_blocks)
                                ispan.annotate(
                                    blocks=prefetch.n_blocks,
                                    values_window=sliding, values_full=full,
                                )
                            self.caches, loaded_tokens = await self.adapter.install_kv(
                                prefetch,
                                self.caches,
                                prompt_table[: prefetch.n_blocks],
                            )
                        if rspan is not None:
                            rspan.stage("install")
                    gate_stall_us, gate_hold_us = held.waited_us, held.held_us
                    prefetch_settled = True
                    t_end = prefetch.fetch_finished_s or time.perf_counter()
                    fetch_dur = max(t_end - prefetch.fetch_started_s, 0.0)
                    fetch_us = fetch_dur * 1e6
                    if fetch_dur > 0:
                        # Fraction of the fetch that ran before this request
                        # acquired the gate = store I/O hidden behind other
                        # work instead of serializing the device.
                        overlapped = min(t_end, held.asked_s) - prefetch.fetch_started_s
                        overlap = min(1.0, max(0.0, overlapped / fetch_dur))
                # The store's own cost: probe + gate-free fetch + the
                # install's H2D/scatter. Unlike the pre-split pipeline,
                # only the LAST term ever serializes the device.
                store_io_us = lookup_s * 1e6 + fetch_us + gate_hold_us
            else:
                # -- one-phase fallback (no start_fetch, or arena full) --
                if fallback_hit is not None:
                    hit_tokens = fallback_hit * bt
                else:
                    t_l = time.perf_counter()
                    hit_tokens = self.adapter.get_num_matched_tokens(token_ids)
                    lookup_s = time.perf_counter() - t_l
                async with self.gate.exclusive(holder="install") as held:
                    # One phase: the span holds the store fetch too.
                    with tracing.trace_op("install") as ispan:
                        self.caches, loaded_tokens = await self.adapter.load_kv(
                            token_ids, self.caches, prompt_table[:hit_limit]
                        )
                        # (after the store's read ops, which annotate the
                        # span they run under with one op's `blocks`)
                        if ispan is not None:
                            ispan.annotate(blocks=loaded_tokens // bt, one_phase=True)
                    if rspan is not None and loaded_tokens:
                        rspan.stage("install")
                gate_stall_us, gate_hold_us = held.waited_us, held.held_us
                store_io_us = lookup_s * 1e6 + gate_hold_us
            admission_us = (time.perf_counter() - t0) * 1e6
            loaded_blocks = loaded_tokens // bt
            raced = hit_tokens > 0 and loaded_tokens == 0
            if loaded_blocks * bt < len(landed):
                # The compute phase's gate wait counts toward gate_stall
                # too: misses never touch the gate at admission anymore, so
                # without this their "queued behind other requests" signal
                # (the thing gate_stall exists to expose) would read 0.
                full = loaded_blocks == 0
                async with self.gate.exclusive(holder="prefill" if full else "resume") as held:
                    gate_stall_us += held.waited_us
                    # Compute runs in an executor thread: the jitted call
                    # (and its block_until_ready) would otherwise pin the
                    # EVENT LOOP for the whole forward — freezing every
                    # other request's gate-free fetch completions, which is
                    # exactly the overlap this pipeline exists to create.
                    # The gate (held across the await) still serializes
                    # cache mutation.
                    # `compute` covers the executor hop and the call.
                    # waits_for_device says what its end means: the prefill
                    # returns when the device is done, the resume when it
                    # is DISPATCHED (its device time is first waited for by
                    # whoever touches the cache next: the save's snapshot).
                    loop = asyncio.get_running_loop()
                    with tracing.trace_op("compute") as cspan:
                        if cspan is not None:
                            cspan.annotate(
                                kind="prefill_full" if full else "chunked_resume",
                                tokens=len(landed) - loaded_blocks * bt,
                                waits_for_device=full,
                            )
                            if not full:
                                # the context pages the chunk attends
                                cspan.annotate(pages=n_blocks)

                        def compute():
                            # The thread does not inherit the span: bound
                            # here, so that what the compute records itself
                            # (`state_snapshot`) hangs under it.
                            with tracing.use_span(cspan), tracing.device_call(
                                "its.compute", cspan
                            ):
                                # A model that drafts is handed the prompt's
                                # last token too, as the operand of its
                                # drafting layer's last landed slot.
                                prompt = token_ids if self.drafts else landed
                                if full:
                                    self._prefill_full(prompt, landed_table)
                                else:
                                    self._chunked_resume(prompt, table, loaded_blocks)

                        await loop.run_in_executor(None, compute)
            prefix_ready_us = (time.perf_counter() - t0) * 1e6
            verified = None
            if self.verify:
                async with self.gate.shared(holder="verify"):
                    verified = self._verify_request(token_ids, prompt_table)
            # Save ONLY the computed suffix — the loaded prefix came from the
            # store and re-writing it would double write traffic for every
            # prefix hit. The snapshot comes BEFORE the first wave: the
            # first round re-decodes the last prompt token into the last
            # prompt block, and what is saved must stay what the prefill
            # or the resume wrote. The write has no such reason to come
            # first: with tokens to generate it runs as a task of its own
            # beside `_generate` (which stays in THIS task) and is joined
            # below.
            if loaded_blocks < n_blocks:
                computed = prompt_table[loaded_blocks:]
                if gen_tokens:
                    snapshot = await self._snapshot_blocks(
                        computed, before_first_token=True
                    )
                    # BACKGROUND while it overlaps generation (it must not
                    # delay decode-blocking reads), promoted at the join.
                    prompt_class = {"value": PRIORITY_BACKGROUND}
                    prompt_write = asyncio.ensure_future(
                        self._write_snapshot(
                            token_ids, snapshot, loaded_blocks, save_class=prompt_class
                        )
                    )
                    del snapshot  # the write's alone: HBM it frees when acknowledged
                    self.saves_overlapped += 1
                else:
                    await self._save_blocks(token_ids, computed, loaded_blocks)
            generated = None
            ttft_us = save_overlap_us = save_tail_us = ack_tail_us = 0.0
            token_emit_s: List[float] = []
            if gen_tokens:
                # In the waves from here: the first round's entry is taken by
                # the next flush, which may launch ahead beside it.
                self.arriving -= 1
                arriving = False
                generated, first_token_t, token_emit_s = await self._generate(
                    token_ids, table, gen_tokens
                )
                t_generated = time.perf_counter()
                if rspan is not None:
                    rspan.stage("generated")
                if first_token_t is not None:
                    ttft_us = (first_token_t - t0) * 1e6
                if prompt_write is not None:
                    # Join: the prompt's blocks commit before the blocks
                    # whose chain extends them, and a failed write fails
                    # the request here. From here the request is BLOCKED on
                    # the write: its unsent layers go out foreground.
                    prompt_class["value"] = PRIORITY_FOREGROUND
                    write_start, write_end = await prompt_write
                    save_overlap_us = (min(write_end, t_generated) - write_start) * 1e6
                    save_tail_us = max(write_end - t_generated, 0.0) * 1e6
                # Save the COMPLETE blocks the response filled, keyed by the
                # extended chain (prompt + generated): a follow-up turn whose
                # prompt is this conversation so far gets a full prefix hit
                # instead of recomputing the response's KV (chain hashes
                # commit to the whole prefix, connector.py).
                full_ids = token_ids + generated
                full_blocks = len(full_ids) // bt
                if full_blocks > n_blocks:
                    await self._save_blocks(
                        full_ids, table[n_blocks:full_blocks], n_blocks
                    )
                # Every block this request computed is acknowledged (the
                # attr: a span without it, a failed request's or an older
                # tree's, has no tail to read).
                if rspan is not None:
                    rspan.stage("acknowledged")
                    rspan.annotate(acknowledged=True)
                ack_tail_us = (time.perf_counter() - t_generated) * 1e6
            stats = RequestStats(
                tokens=len(token_ids),
                hit_blocks=hit_tokens // bt,
                loaded_blocks=loaded_blocks,
                computed_blocks=n_blocks - loaded_blocks,
                admission_us=admission_us,
                raced_eviction=raced,
                verified=verified,
                generated=generated,
                store_io_us=store_io_us,
                gate_stall_us=gate_stall_us,
                gate_hold_us=gate_hold_us,
                fetch_us=fetch_us,
                overlap_fraction=overlap,
                prefetched_blocks=(
                    prefetch.blocks_fetched if prefetch is not None else 0
                ),
                wasted_blocks=(
                    prefetch.wasted_blocks if prefetch is not None else 0
                ),
                prefix_ready_us=prefix_ready_us,
                ttft_us=ttft_us,
                priority=priority,
                token_emit_s=token_emit_s,
                trace_id=rspan.trace_id if rspan is not None else 0,
                save_overlap_us=save_overlap_us,
                save_tail_us=save_tail_us,
                ack_tail_us=ack_tail_us,
            )
            self.stats.append(stats)
            return stats
        except BaseException as e:
            # Explicit arm, not sys.exc_info()-in-finally: exc_info also
            # reports a CALLER's already-being-handled exception during a
            # normal return (a retry inside an except block would record a
            # successful request as failed).
            if prompt_write is not None:
                # `_generate` raised, or this task was cancelled, with the
                # prompt's write still running: cancel it AND wait for it
                # (the writer's `finally` drains the puts in flight from
                # registered host buffers) before the span closes and the
                # blocks go back to the pool. The error on its way out is
                # the request's; a write that failed too has been seen.
                prompt_write.cancel()
                await asyncio.wait([prompt_write])
                if not prompt_write.cancelled():
                    prompt_write.exception()
            if rspan is not None:
                rspan.finish(status=f"error:{type(e).__name__}")
            raise
        finally:
            if arriving:  # it never reached a wave; before anything here awaits
                self.arriving -= 1
            tracing.unbind_span(rtoken)
            if rspan is not None:
                rspan.finish()  # idempotent: an error finish above wins
            if not prefetch_settled:
                # Admission died between enqueue and install (cancellation,
                # alloc backpressure unwound, model error): the speculative
                # fetch must hand every staging slot back — accounting
                # returns to baseline, the staged bytes count as waste.
                # shield(): even if THIS task is being cancelled, the
                # discard runs to completion (in the background if need be).
                try:
                    await asyncio.shield(prefetch.discard())
                except BaseException:  # noqa: BLE001 - cleanup must not mask
                    pass
                self._prefetch_extra_fetched += prefetch.blocks_fetched
                self._prefetch_extra_wasted += prefetch.wasted_blocks
            if table is not None:
                await self.pool.free(table)
            self.live -= 1
            if self.live == 0 and tracing.enabled():
                # `no_request_live`: a trace of its own (nobody's child), from
                # here to the next admission; what the device idles under it
                # is no phase's of any request.
                self._nobody_live = tracing.Span("no_request_live")

    async def run(
        self,
        prompts: Sequence[Sequence[int]],
        concurrency: int = 4,
        gen_tokens: int = 0,
    ):
        """Run all prompts with bounded request concurrency (optionally
        generating ``gen_tokens`` greedy tokens each via lockstep wave
        decode); returns the aggregate metrics dict."""
        sem = asyncio.Semaphore(concurrency)

        async def one(p):
            async with sem:
                return await self.run_request(p, gen_tokens=gen_tokens)

        await asyncio.gather(*(one(p) for p in prompts))
        return self.metrics()

    def metrics(self) -> dict:
        """Aggregate engine-side metrics over every completed request.

        Keys (the ``engine_*`` bench-receipt vocabulary, counters-checked
        against this list): ``requests``, ``hit_rate``, ``loaded_blocks``,
        ``computed_blocks``, ``raced_evictions``; admission latency
        ``p50_admission_us`` / ``p99_admission_us`` decomposed into the
        store's own cost (``p50_store_io_us``, ``p99_store_io_us``, split
        by outcome as ``p50_store_io_hit_us`` / ``p50_store_io_miss_us``)
        vs device-gate queueing (``p50_gate_stall_us``,
        ``p99_gate_stall_us``); the two-phase admission overlap story
        (``p50_gate_hold_us``, ``p99_gate_hold_us``, ``overlap_fraction``,
        ``prefetch_waste``, ``prefetch_fallbacks``,
        ``tier_direct_loads`` — cold-only roots read DIRECTLY via the
        one-phase load, skipping staged prefetch, docs/tiering.md) and
        end-to-end prefix residency (``p50_prefix_ready_hit_us``,
        ``p50_prefix_ready_miss_us``); the recompute ledger
        (``recompute_saved_s``, ``prefill_per_block_s``); concurrency
        receipts (``max_live_requests``, ``max_concurrent_saves``,
        ``saves_overlapped`` — prompt saves whose store write ran beside
        the request's own generation); the chunked resumes of prefix hits
        (``resumes``, ``resume_tokens`` — suffix tokens they computed —
        and ``resume_pages`` — context pages their attention walked); the
        ragged wave-decode story (``decode_waves``, ``max_wave_size``,
        ``wave_buckets`` — distinct padded (B, T, P) jit buckets — and
        ``wave_pad_fraction``, the share of launched wave rows that were
        padding, ``wave_one_row_waves``, the launched waves that carried
        ONE real flat row (a lone request's steps: the waves whose dense FFN
        pads its row, models/llama.py ``_ffn``), ``wave_ahead_waves``, the
        launched waves with a row fed from the wave before them on the device
        (``WaveDecoder``, "one wave ahead"), ``wave_ahead_dropped``, a drafting
        model's slots launched ahead under a verdict that did not hold (each
        cost its request one round through the host), ``wave_pages`` /
        ``wave_pad_pages``, the flat attention
        pages launched and those of them that were the page bucket's
        padding; ``wave_layer_pages`` / ``wave_window_pages_skipped``, the
        (layer, page) pairs the waves' real rows attended and how many more
        a stack of full layers would have; ``wave_host_transfers``, the host
        arrays uploaded for the launched waves plus the blocking
        device-to-host reads made for their tokens, 2 a wave;
        ``wave_row_slices``, the slices of a wave's logits dispatched because
        somebody read the rows ``step_chunk`` handed out (``WaveRows``; a
        request that asks for its ids alone causes none: 0 in a serving
        loop); and whatever
        the model's wave step counts itself, by its own names); the device
        gate's ledger, plain monotone counters in microseconds and counts
        with ``<kind>`` and ``<waiter>`` over ``GATE_HOLDERS`` (``wave``,
        ``prefill``, ``resume``, ``install``, ``snapshot``, ``verify``):
        ``gate_held_us_<kind>`` and ``gate_holds_<kind>`` (how long that kind
        held the gate, a stretch of overlapping shared holds once, and the
        holds it ended), ``gate_waits_<waiter>`` and ``gate_wait_us_<waiter>``
        (its acquisitions and what they waited in all) and
        ``gate_wait_us_<waiter>_behind_<kind>``, ``<kind>`` also ``free``
        (the part of those waits that stood behind a hold of that kind;
        ``free``: the gate was nobody's and the waiter not yet woken), which
        add up to ``gate_wait_us_<waiter>`` exactly (``DeviceGate``);
        serving latency (``p50_ttft_us``, ``p99_ttft_us``,
        ``p99_ttft_fg_us`` — time to first generated token, overall and
        FOREGROUND-class only); generation/speculation (``generated_tokens``,
        ``spec_tokens_per_step``, ``spec_acceptance_rate``,
        ``spec_drafted_tokens``, ``spec_accepted_tokens``, ``spec_rounds``,
        ``spec_emitted_tokens``: running counts, a host drafter's or the
        model's own);
        ``all_verified``; and, over a self-healing pool, ``store_health``.
        """
        total_blocks = sum(s.hit_blocks + s.computed_blocks for s in self.stats)
        loaded = sum(s.loaded_blocks for s in self.stats)
        lat = sorted(s.admission_us for s in self.stats)
        io = sorted(s.store_io_us for s in self.stats)
        io_hit = sorted(s.store_io_us for s in self.stats if s.loaded_blocks)
        io_miss = sorted(s.store_io_us for s in self.stats if not s.loaded_blocks)
        stall = sorted(s.gate_stall_us for s in self.stats)
        # Gate HOLD is only meaningful where a load/install ran (hits, or
        # the one-phase fallback); zeros from pure misses would drown it.
        hold = sorted(s.gate_hold_us for s in self.stats if s.gate_hold_us > 0)
        overlaps = [
            s.overlap_fraction for s in self.stats if s.overlap_fraction is not None
        ]
        prefetched = (
            sum(s.prefetched_blocks for s in self.stats)
            + self._prefetch_extra_fetched
        )
        wasted = (
            sum(s.wasted_blocks for s in self.stats) + self._prefetch_extra_wasted
        )
        ready_hit = sorted(s.prefix_ready_us for s in self.stats if s.loaded_blocks)
        ready_miss = sorted(
            s.prefix_ready_us for s in self.stats if not s.loaded_blocks
        )
        ttft = sorted(s.ttft_us for s in self.stats if s.ttft_us > 0)
        ttft_fg = sorted(
            s.ttft_us for s in self.stats
            if s.ttft_us > 0 and s.priority != PRIORITY_BACKGROUND
        )

        def _p(xs, q):
            return xs[min(len(xs) - 1, int(len(xs) * q))] if xs else 0.0

        def pctl(q):
            return _p(lat, q)

        per_block = self._prefill_per_block_s or 0.0
        return {
            "requests": len(self.stats),
            "hit_rate": loaded / total_blocks if total_blocks else 0.0,
            "loaded_blocks": loaded,
            "computed_blocks": sum(s.computed_blocks for s in self.stats),
            "raced_evictions": sum(s.raced_eviction for s in self.stats),
            "p50_admission_us": pctl(0.50),
            "p99_admission_us": pctl(0.99),
            # Admission decomposed (RequestStats): the store's own cost vs
            # time queued behind other requests' compute for the device
            # gate. Optimizing the store moves the first; only engine
            # scheduling moves the second.
            "p50_store_io_us": _p(io, 0.50),
            "p99_store_io_us": _p(io, 0.99),
            # Split by outcome: a miss costs one lookup round trip; a hit
            # adds the whole load pipeline (fetch + H2D + scatter).
            "p50_store_io_hit_us": _p(io_hit, 0.50),
            "p50_store_io_miss_us": _p(io_miss, 0.50),
            "p50_gate_stall_us": _p(stall, 0.50),
            "p99_gate_stall_us": _p(stall, 0.99),
            # Two-phase admission: how long the exclusive gate was HELD for
            # installs (the only store-side phase that still serializes the
            # device), what fraction of store fetch time ran gate-free
            # (1.0 = I/O fully hidden), and the speculation's waste ratio
            # (staged blocks that never reached the device / staged blocks).
            "p50_gate_hold_us": _p(hold, 0.50),
            "p99_gate_hold_us": _p(hold, 0.99),
            "overlap_fraction": (
                sum(overlaps) / len(overlaps) if overlaps else 0.0
            ),
            "prefetch_waste": wasted / prefetched if prefetched else 0.0,
            "prefetch_fallbacks": self.prefetch_fallbacks,
            # Tiered capacity plane (docs/tiering.md): admissions that
            # skipped the staged prefetch for a cold-only root and read it
            # directly from the pooled cold tier via the one-phase load.
            "tier_direct_loads": self.tier_direct_loads,
            # End-to-end prefix residency split by outcome: the number that
            # says whether a cache hit actually beats recomputing.
            "p50_prefix_ready_hit_us": _p(ready_hit, 0.50),
            "p50_prefix_ready_miss_us": _p(ready_miss, 0.50),
            "recompute_saved_s": loaded * per_block,
            "prefill_per_block_s": per_block,
            "max_live_requests": self.max_live,
            "max_concurrent_saves": self.max_concurrent_saves,
            "saves_overlapped": self.saves_overlapped,
            "resumes": self.resumes,
            "resume_tokens": self.resume_tokens,
            "resume_pages": self.resume_pages,
            "decode_waves": self.wave.waves,
            "max_wave_size": self.wave.max_wave,
            # Distinct PADDED (B, T, P) buckets == jit cache entries for
            # the ragged wave step (jit keys on shape): the compile-count
            # story.
            "wave_buckets": sorted(self.wave.bucket_sizes),
            # Share of launched wave rows that were padding (ragged
            # assembly pads only the flat tail; the old rectangle padded
            # every short chunk to the widest one) — the attribution key
            # for the ragged win.
            "wave_pad_fraction": (
                self.wave.pad_rows / self.wave.launched_rows
                if self.wave.launched_rows
                else 0.0
            ),
            # Launched waves of one real row (of ``decode_waves``): every
            # step of a lone request, and the share of waves whose dense
            # FFN runs on a padded row (models/llama.py ``_ffn``).
            "wave_one_row_waves": self.wave.one_row_waves,
            # Launched waves (of ``decode_waves``) with at least one row that
            # took its token from the wave before it on the device: the waves
            # launched while their requests still read the last one's tokens.
            "wave_ahead_waves": self.wave.waves_ahead,
            # Of a model that drafts: the slots launched ahead that no call
            # took, their verdict guessed wrong.
            "wave_ahead_dropped": self.wave.ahead_dropped,
            # Flat attention pages the waves launched, and how many were
            # the power-of-two bucket's padding: steps the ragged kernel
            # neither computes nor fetches (tpu/paged_attention.py).
            "wave_pages": self.wave.wave_pages,
            "wave_pad_pages": self.wave.wave_pad_pages,
            "wave_layer_pages": self.wave.wave_layer_pages,
            "wave_window_pages_skipped": self.wave.wave_window_pages_skipped,
            # The waves' traffic with the device: host arrays uploaded for
            # them plus blocking device-to-host reads of their tokens. Over
            # ``decode_waves`` it reads 2 (a little under where a request's
            # closing step rides a wave alone and reads nothing back).
            "wave_host_transfers": self.wave.waves + self.wave.blocking_reads,
            # Slices of a wave's logits cut for a reader of the rows
            # ``step_chunk`` handed out (``WaveRows``): over ``decode_waves``
            # a serving loop, which asks for ids alone, reads 0.
            "wave_row_slices": self.wave.row_slices,
            # What the model's wave step counted itself (models/serving.py
            # ``aux``): an expert model's ``moe_pairs`` and
            # ``moe_distinct_experts``, a selecting model's
            # ``dsa_keys_selected`` of ``dsa_keys_in_context`` (the positions
            # its rows' attention kept of those they could have read);
            # nothing for a model that counts nothing.
            **self._step_counters(),
            # The device gate's ledger (``DeviceGate.counters``): who held
            # it for how long, and every wait cut by whom it stood behind.
            **self.gate.counters(),
            # Time to the first token, split so the FOREGROUND class's
            # tail is visible next to the mixed one.
            "p50_ttft_us": _p(ttft, 0.50),
            "p99_ttft_us": _p(ttft, 0.99),
            "p99_ttft_fg_us": _p(ttft_fg, 0.99),
            "generated_tokens": sum(
                len(s.generated) for s in self.stats if s.generated
            ),
            # Speculative decoding (drafter set): emitted tokens per verify
            # round (> 1.0 means speculation is paying), and the drafter's
            # acceptance rate. Without a drafter, rounds == tokens (1.0).
            "spec_tokens_per_step": (
                sum(len(s.generated) for s in self.stats if s.generated)
                / self.spec_rounds
                if self.spec_rounds
                else 0.0
            ),
            "spec_acceptance_rate": (
                self.spec_accepted / self.spec_drafted if self.spec_drafted else 0.0
            ),
            "spec_drafted_tokens": self.spec_drafted,
            "spec_accepted_tokens": self.spec_accepted,
            "spec_rounds": self.spec_rounds,
            "spec_emitted_tokens": self.spec_emitted,
            "all_verified": all(
                s.verified for s in self.stats if s.verified is not None
            ),
            **self._store_health(),
        }

    def _store_health(self) -> dict:
        """Failure-domain visibility at the engine's own dashboard: when the
        connector under the adapter is a self-healing pool
        (ClusterKVConnector.health), surface its per-member breaker states
        and degrade counters — the operator reading engine metrics is the
        one who needs to know WHICH cache node is sick."""
        health = getattr(
            getattr(self.adapter, "connector", None), "health", None
        )
        if not callable(health):
            return {}
        try:
            return {"store_health": health()}
        except Exception:  # noqa: BLE001 - metrics must never kill the engine
            return {}
