"""Overlapped layerwise prefill→decode handoff (the disaggregation plane).

The blocking disaggregation baseline (tests/test_engine_disagg.py) is
store-and-forward: the prefill engine computes ALL layers, saves, and only
then may the decode engine fetch ALL layers before its first step. This
module overlaps the three legs end to end:

  prefill engine                    store                   decode engine
  ─────────────────                 ─────                   ─────────────
  layer 0 compute ──ship 0──▶ keys published ──fetch 0──▶ install 0
  layer 1 compute ──ship 1──▶        ...        ──fetch 1──▶ install 1
       ...            (layer l ships WHILE l+1 computes)        ...
                                                first decode step launches
                                                once layer 0 installs; its
                                                layer-l attention waits only
                                                on layer l's install.

* ``stream_prefill`` chains the per-layer jitted ``prefill_layer`` and hands
  each layer's freshly scattered KV to ``KVConnector.stage_layer_save`` AS
  COMPUTED — layer ``l``'s store puts overlap layer ``l+1``'s compute. The
  ships are HANDOFF traffic: tagged ``wire.PRIORITY_FOREGROUND`` at the call
  site (a decode consumer is actively waiting on these exact bytes; ITS-P004
  requires disagg producers to name the class) and they carry the request's
  trace context, so ONE trace id covers prefill compute → store puts →
  decode install. Layers ship in NATURAL order 0..L-1 — layer 0 (the
  ``lookup`` sentinel) is published first, deliberately: the consumer is not
  probing (``known_hit``), and any OTHER reader that races the handoff hits
  ``KeyNotFound`` on a deeper layer, which ``load`` maps to a miss →
  recompute (cache semantics, never wrong bytes).

* ``overlapped_decode`` is the layerwise admission: ``start_fetch_async``
  with ``retry_missing_s`` (read-racing-write mode) returns per-layer
  handles, and the WATERMARK rule gates compute — the first decode step
  launches once layers ``[0, watermark)`` are installed while deeper layers
  are still in flight; inside the step, layer ``l``'s attention calls
  ``install_layer(l)`` first. ``watermark=n_layers`` degenerates to today's
  blocking fetch-all. A late/failed layer triggers the layer-chunked local
  recompute fallback (``_recompute_prefix``): never wrong bytes, counted in
  ``disagg_fallback_recomputes``, journaled as a ``disagg_fallback`` event.

* Byte identity is BY CONSTRUCTION: the watermarked and blocking paths chain
  the same jitted ``decode_wave_layer`` programs, and the streamed prefill
  and the fallback recompute chain the same jitted ``prefill_layer``
  programs — identical executables, bitwise-identical logits and caches.

* ``DisaggHarness`` is the two-engine rig: one prefill-side and one
  decode-side :class:`~.connector.KVConnector` (separate store connections,
  separate block layouts) driving the four TTFT legs the bench gates
  (overlapped / blocking fetch-all / local recompute / cold fetch), plus the
  ``python -m infinistore_tpu.disagg`` prefill subprocess role for the chaos
  test (tools/fleet.py spawn pattern; ``--stall-after-layer`` pins the
  kill -9 window mid-handoff).

Counters are the ``disagg_*`` vocabulary (ITS-C009 lockstep with the
/metrics exporter and docs/disaggregation.md).
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import dataclasses
import time
from typing import List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from . import compile_cache, telemetry, tracing, wire
from .connector import KVConnector
from .hostmesh import cpu_child_env
from .models.llama import (
    LlamaConfig,
    decode_wave_layer,
    embed_prompt,
    embed_wave,
    init_params,
    lm_logits,
    prefill_layer,
)

__all__ = [
    "DisaggCounters",
    "DisaggHarness",
    "counters",
    "demo_config",
    "demo_prompt",
    "local_decode",
    "overlapped_decode",
    "reset_counters",
    "stream_prefill",
]


class DisaggCounters:
    """The disaggregation plane's counter ledger (ITS-C009).

    One instance per process (module singleton via :func:`counters`); both
    roles bump their own side — a prefill engine counts the handoffs it
    ships, a decode engine the admissions it gates — and the manage-plane
    exporter (server.py ``_disagg_prometheus_lines``) publishes whatever
    this process accumulated. Key vocabulary (every key ``disagg_``-prefixed,
    documented in docs/disaggregation.md):

    - ``disagg_handoffs``: overlapped handoff legs this process initiated
      (producer ships + consumer admissions each count their own side).
    - ``disagg_overlap_layers``: layers whose fetch was still in flight when
      the first decode step launched AND that installed mid-step — the
      mechanism proof the bench gates on (≥1 means the first token really
      overlapped the transfer).
    - ``disagg_watermark_stalls``: residual waits the overlap could not
      hide — compute reaching a layer before its bytes (``wait_stalls``)
      plus read-racing-write re-probes (``retry_stalls``).
    - ``disagg_fallback_recomputes``: late/failed layers that fell back to
      the local layer-chunked recompute (never wrong bytes, just work).
    - ``disagg_inflight_at_first_token``: layers not yet staged when the
      first decode step launched (depth of the pipeline at launch).
    - ``disagg_wrong_bytes``: verification mismatches between a handoff
      decode and the local-recompute oracle. MUST stay 0; a nonzero value
      is a correctness bug, not a performance signal.
    """

    def __init__(self):
        # Written only on the role's engine loop (prefill ships / decode
        # admits on their own asyncio loop); the manage-plane server
        # thread snapshots via status().
        # its: guard[_c: single_writer]
        self._c = {
            "disagg_handoffs": 0,
            "disagg_overlap_layers": 0,
            "disagg_watermark_stalls": 0,
            "disagg_fallback_recomputes": 0,
            "disagg_inflight_at_first_token": 0,
            "disagg_wrong_bytes": 0,
        }

    def bump(self, key: str, n: int = 1) -> None:
        self._c[key] += n

    def status(self) -> dict:
        """Counter snapshot for /metrics and /disagg (explicit literal so
        the ITS-C009 ledger scan reads the full vocabulary here too)."""
        c = self._c
        return {
            "disagg_handoffs": c["disagg_handoffs"],
            "disagg_overlap_layers": c["disagg_overlap_layers"],
            "disagg_watermark_stalls": c["disagg_watermark_stalls"],
            "disagg_fallback_recomputes": c["disagg_fallback_recomputes"],
            "disagg_inflight_at_first_token": c["disagg_inflight_at_first_token"],
            "disagg_wrong_bytes": c["disagg_wrong_bytes"],
        }


_COUNTERS = DisaggCounters()


def counters() -> DisaggCounters:
    """This process's disagg counter ledger (what /metrics exports)."""
    return _COUNTERS


def reset_counters() -> DisaggCounters:
    """Fresh ledger (tests/bench legs isolate their counts)."""
    global _COUNTERS
    _COUNTERS = DisaggCounters()
    return _COUNTERS


def demo_config(
    n_layers: int = 6, block_tokens: int = 8, dim: int = 64,
    ffn_dim: int = 128,
) -> LlamaConfig:
    """The demo model BOTH roles must agree on: the prefill subprocess and
    the in-proc decode side derive identical params (same seed), identical
    chain hashes, and identical jitted per-layer programs from this one
    config — which is what makes the handoff byte-checkable end to end.

    ``dim``/``ffn_dim`` scale the per-layer prefill compute; the bench leg
    raises them so prefill is genuinely slower than a layer's fetch+install
    (the regime where layerwise overlap pays — with a dispatch-bound toy
    model every leg degenerates to the same store-bound chain)."""
    return LlamaConfig(
        vocab=128, dim=dim, n_layers=n_layers, n_heads=4, n_kv_heads=2,
        ffn_dim=ffn_dim, block_tokens=block_tokens, dtype=jnp.float32,
    )


def demo_prompt(config: LlamaConfig, n_blocks: int, seed: int = 0) -> List[int]:
    """Deterministic prompt of ``n_blocks`` complete blocks; ``seed`` varies
    the content (and therefore the chain hashes — each bench round uses a
    fresh prompt so its fetch really races its ship, instead of hitting the
    previous round's keys)."""
    n = n_blocks * config.block_tokens
    return ((np.arange(n) * 37 + seed * 101) % config.vocab).tolist()


# -- prefill side ------------------------------------------------------------


async def stream_prefill(
    connector,
    params,
    config: LlamaConfig,
    prompt: Sequence[int],
    caches,
    block_table: np.ndarray,
    *,
    on_layer_shipped=None,
    stall_after_layer: Optional[int] = None,
    stall_s: float = 0.0,
    crash_after_layers: Optional[int] = None,
    max_inflight_ships: int = 4,
    pace_s: float = 0.0,
):
    """Prefill the prompt layer by layer, shipping each layer's KV to the
    store AS COMPUTED: layer ``l``'s store puts overlap layer ``l+1``'s
    compute (JAX async dispatch keeps the device busy while ``ship()``
    awaits the network). Returns ``(last-token logits, caches, blocks
    written)``.

    Ships are handoff traffic: ``wire.PRIORITY_FOREGROUND`` named at the
    call site (ITS-P004 — a decode consumer is actively waiting on these
    bytes) and the caller's active span rides every ship, so the decode
    side's installs continue the same trace. Layers go out in natural order
    0..L-1 (module docstring: sentinel-first is safe here).

    ``max_inflight_ships`` bounds concurrently staged layers so the
    connector's host staging pool (sized for ~6 layer spans) never
    exhausts on deep models; the oldest ship is awaited before staging
    past the bound.

    Chaos hooks (the ``python -m infinistore_tpu.disagg`` subprocess wires
    them to flags): ``stall_after_layer=k`` makes layers ``0..k`` durable
    then sleeps ``stall_s`` — the window the chaos test kill -9s into;
    ``crash_after_layers=n`` makes the first ``n`` layers durable then
    SIGKILLs this process (no cleanup, mid-handoff by construction).
    ``on_layer_shipped(layer)`` fires after THAT layer's puts complete
    (durable when called — the subprocess prints its progress markers from
    it).

    ``pace_s`` emulates a DEDICATED prefill engine's per-layer production
    rate: after each layer's compute, sleep ``pace_s`` before shipping it.
    A real disaggregated deployment runs prefill on its own machine, so
    its compute never contends with the decode host; on a shared-core CI
    box an un-paced prefill time-slices against the decode process and a
    TTFT comparison measures scheduler contention, not pipeline overlap.
    The sleep keeps the bytes, keys, and announce protocol fully real
    (byte-identity is still checked) while leaving the core idle exactly
    when a remote engine would — the regime the bench leg measures.
    ``pace_s=0`` (the default, and all tests) disables it."""
    ds = counters()
    ds.bump("disagg_handoffs")
    span = tracing.active_span()
    if span is not None:
        span.annotate(
            handoff_layers=config.n_layers, handoff_prefix_blocks=len(block_table)
        )
    tokens = jnp.asarray(np.asarray(prompt, np.int32))
    table_dev = jnp.asarray(np.asarray(block_table), jnp.int32)
    ids = np.asarray(block_table)
    x = embed_prompt(params, tokens)
    out = list(caches)
    ships: List[asyncio.Future] = []
    pending = collections.deque()

    async def _shipped(layer: int, ship) -> int:
        written = await ship()
        if on_layer_shipped is not None:
            on_layer_shipped(layer)
        return written

    for layer in range(config.n_layers):
        x, k_cache, v_cache = prefill_layer(
            params, x, out[layer][0], out[layer][1], table_dev, config, layer
        )
        out[layer] = (k_cache, v_cache)
        if pace_s > 0.0:
            # Emulated remote-engine production rate (docstring): the
            # layer is computed; hold its ship to the paced cadence.
            await asyncio.sleep(pace_s)
        if len(pending) >= max_inflight_ships:
            await pending.popleft()
        ship = connector.stage_layer_save(
            prompt, layer, out[layer], ids,
            # HANDOFF class, named at source (ITS-P004): the decode engine
            # is already waiting on these exact bytes — background class
            # would delay the reader this ship feeds.
            priority=wire.PRIORITY_FOREGROUND,
        )
        fut = asyncio.ensure_future(_shipped(layer, ship))
        ships.append(fut)
        pending.append(fut)
        if stall_after_layer is not None and layer == stall_after_layer:
            await asyncio.gather(*ships)  # layers 0..k durable before the window
            await asyncio.sleep(stall_s)
        if crash_after_layers is not None and layer + 1 >= crash_after_layers:
            await asyncio.gather(*ships)
            import os
            import signal

            os.kill(os.getpid(), signal.SIGKILL)
        # Yield the loop so the staged ship's puts issue while the next
        # layer's dispatch proceeds — THE producer-side overlap.
        await asyncio.sleep(0)
    written = sum(await asyncio.gather(*ships))
    return lm_logits(params, x)[0, -1], out, written


# -- decode side -------------------------------------------------------------


@dataclasses.dataclass
class DecodeResult:
    """One decode leg's outcome: greedy ``tokens``, the bitwise
    ``first_logits`` the oracle comparison uses, the updated caches, the
    ``time.perf_counter()`` instant the first token's logits were ready
    (the harness subtracts its request-arrival t0 for TTFT), and the
    overlap accounting that feeds the ``disagg_*`` counters."""

    tokens: List[int]
    first_logits: np.ndarray
    caches: list
    t_first: float
    fallback: bool
    overlap_layers: int
    inflight_at_first_token: int
    watermark_stalls: int


def _recompute_prefix(params, config: LlamaConfig, prompt, caches, table_dev):
    """Layer-chunked local recompute of the whole prefix into ``table_dev``'s
    blocks — the fallback leg AND the local baseline. Chains the same jitted
    ``prefill_layer`` programs the prefill engine streams through, so the
    bytes are identical to a successful handoff (scatter touches only the
    prefix blocks: a decode step's writes into its own spare block
    survive)."""
    x = embed_prompt(params, jnp.asarray(np.asarray(prompt, np.int32)))
    out = list(caches)
    for layer in range(config.n_layers):
        x, k_cache, v_cache = prefill_layer(
            params, x, out[layer][0], out[layer][1], table_dev, config, layer
        )
        out[layer] = (k_cache, v_cache)
    return out


async def _run_decode_steps(
    params,
    config: LlamaConfig,
    state: dict,
    block_table: np.ndarray,
    first_token: int,
    start_pos: int,
    gen_tokens: int,
    max_blocks: int,
    ensure_layer=None,
    trace_events=None,
):
    """Greedy decode over ``state["out"]`` caches with the layerwise wave
    chain. ``ensure_layer(l)`` (first step only) is the watermark gate —
    it replaces entries of ``state["out"]`` under us (install donates
    layer ``l``'s arrays, fallback recomputes every layer), which is why
    the cache list lives in the shared ``state`` dict rather than a local.
    Returns ``(tokens, first_logits, t_first)``."""
    tables = jnp.asarray(np.asarray(block_table), jnp.int32)[None]
    tok = int(first_token)
    pos = start_pos
    tokens_out: List[int] = []
    first_logits = None
    t_first = 0.0
    for step in range(gen_tokens):
        x = embed_wave(params, jnp.asarray([[tok]], jnp.int32))
        positions = jnp.full((1, 1), pos, jnp.int32)
        for layer in range(config.n_layers):
            if step == 0 and ensure_layer is not None:
                await ensure_layer(layer)
            if step == 0 and trace_events is not None:
                trace_events.append(("compute", layer))
            x, k_cache, v_cache = decode_wave_layer(
                params, x, positions, state["out"][layer][0],
                state["out"][layer][1], tables, config, layer, max_blocks,
            )
            state["out"][layer] = (k_cache, v_cache)
        logits = lm_logits(params, x)[0, -1]
        if step == 0:
            first_logits = np.asarray(jax.block_until_ready(logits))
            t_first = time.perf_counter()
        tok = int(jnp.argmax(logits))
        tokens_out.append(tok)
        pos += 1
    return tokens_out, first_logits, t_first


async def overlapped_decode(
    connector,
    params,
    config: LlamaConfig,
    prompt: Sequence[int],
    caches,
    block_ids: np.ndarray,
    block_table: np.ndarray,
    first_token: int,
    *,
    watermark: int = 1,
    known_hit: Optional[int] = None,
    retry_missing_s: float = 2.0,
    retry_interval_s: float = 0.0003,
    fetch_gate=None,
    gen_tokens: int = 1,
    trace_events=None,
) -> DecodeResult:
    """Watermark-gated decode admission over an (possibly still in-flight)
    handoff prefix. ``block_ids`` are the decode engine's physical blocks
    for the prefix; ``block_table`` is the padded per-request table row
    (prefix + generation blocks) every ``decode_wave_layer`` call sees.

    The WATERMARK rule: layers ``[0, watermark)`` install before the first
    decode step launches; past the watermark, layer ``l``'s attention
    awaits ``install_layer(l)`` inline — it never reads bytes still in
    flight, and layers deeper than the one being computed keep streaming
    behind it. ``watermark=config.n_layers`` is the blocking fetch-all
    degenerate case (today's behavior, bitwise-identical logits — same
    jitted programs).

    ``known_hit`` MUST be the producer-announced block count (a store probe
    mid-handoff is racy — connector.start_fetch_async docstring);
    ``retry_missing_s`` is the read-racing-write deadline. ``fetch_gate``
    (``async fetch_gate(layer)``) is the announce-driven mode: when the
    producer can signal per-layer publication (in-process harness, or a
    control channel), layer ``l``'s store read waits for the announcement
    instead of blind re-probing — without it, every layer's fetch polls
    keys that cannot exist yet, a probe storm contending with the very
    ships it waits on. The retry deadline still rides any residual race. A layer missing
    past the deadline (or a store failure) flips the leg to the
    layer-chunked local recompute fallback — ``disagg_fallback_recomputes``
    counts it, a ``disagg_fallback`` journal event records it, and the
    bytes are identical by construction, so correctness never depends on
    the race.

    ``trace_events`` (tests): appended with ``("install", l)`` /
    ``("compute", l)`` tuples — the watermark invariant is that every
    layer's install precedes its compute."""
    n_layers = config.n_layers
    n_blocks = len(block_ids)
    wm = max(1, min(watermark, n_layers))
    ds = counters()
    ds.bump("disagg_handoffs")
    span = tracing.active_span()
    handle = await connector.start_fetch_async(
        prompt,
        limit_blocks=n_blocks,
        known_hit=known_hit if known_hit is not None else n_blocks,
        retry_missing_s=retry_missing_s,
        # TTFT-critical: the re-probe cadence bounds how long a
        # just-published layer sits before its retry lands.
        retry_interval_s=retry_interval_s,
        fetch_gate=fetch_gate,
    )
    ids = np.asarray(block_ids)
    prefix_dev = jnp.asarray(ids, jnp.int32)
    state = {"out": list(caches), "fallback": False}
    installed = [False] * n_layers
    via_handle = [False] * n_layers
    install_tasks: List[Optional[asyncio.Task]] = [None] * n_layers

    async def _install(layer: int) -> None:
        if layer > 0:
            # install_layer must be called with strictly increasing layer
            # (staging regions wrap) — chain on the previous layer's task.
            await _layer_task(layer - 1)
        if installed[layer]:
            return
        if not state["fallback"]:
            out, ok = await handle.install_layer(state["out"], ids, layer)
            # Only this layer's entry: install_layer works on a COPY of the
            # list taken before its awaits, and the compute loop has been
            # writing shallower layers' updated caches into state["out"]
            # meanwhile — replacing the whole list would drop them (the
            # first step's K/V insert at every layer whose compute
            # overlapped a deeper install).
            state["out"][layer] = out[layer]
            if ok:
                installed[layer] = True
                via_handle[layer] = True
                if trace_events is not None:
                    trace_events.append(("install", layer))
                return
            # Late/failed layer: the handle is written off (install_layer
            # cancelled the rest) — recompute the WHOLE prefix locally.
            # Layers already installed used bitwise-identical bytes, so the
            # step's partial activation chain stays valid and the loop just
            # continues from this layer over recomputed caches.
            state["fallback"] = True
            ds.bump("disagg_fallback_recomputes")
            telemetry.get_journal().emit(
                "disagg_fallback", failed_layer=layer, prefix_blocks=n_blocks
            )
            if span is not None:
                span.annotate(disagg_fallback_layer=layer)
            state["out"] = _recompute_prefix(
                params, config, prompt, state["out"], prefix_dev
            )
        for l in range(n_layers):
            if not installed[l]:
                installed[l] = True
                if trace_events is not None:
                    trace_events.append(("install", l))

    def _layer_task(layer: int) -> asyncio.Task:
        # Memoized per-layer install: the install-ahead pipeline and the
        # compute loop both await the SAME task, so a layer installs once
        # no matter who reaches it first.
        if install_tasks[layer] is None:
            install_tasks[layer] = asyncio.ensure_future(_install(layer))
        return install_tasks[layer]

    async def ensure_layer(layer: int) -> None:
        await _layer_task(layer)

    # INSTALL-AHEAD: kick every layer's install now, in order. Installs
    # (device_put + scatter) then ride BEHIND the compute loop instead of
    # serializing in front of each layer's attention — the compute side
    # only waits when it genuinely outruns the transfer (a watermark
    # stall), which is the whole point of the overlap.
    for layer in range(n_layers):
        _layer_task(layer)
    for layer in range(wm):
        await ensure_layer(layer)
    # Launch instant: what is still in flight right now is the overlap the
    # watermark bought (the blocking path would have waited all of it out).
    inflight = [
        l for l in range(n_layers) if not installed[l] and not handle.layer_ready(l)
    ]
    ds.bump("disagg_inflight_at_first_token", len(inflight))
    tokens, first_logits, t_first = await _run_decode_steps(
        params, config, state, block_table, first_token, len(prompt),
        gen_tokens, len(block_table), ensure_layer=ensure_layer,
        trace_events=trace_events,
    )
    overlap = sum(1 for l in inflight if via_handle[l])
    ds.bump("disagg_overlap_layers", overlap)
    stalls = handle.retry_stalls + handle.wait_stalls
    ds.bump("disagg_watermark_stalls", stalls)
    if span is not None:
        span.annotate(
            disagg_overlap_layers=overlap, disagg_inflight=len(inflight),
            disagg_stalls=stalls,
        )
    return DecodeResult(
        tokens=tokens,
        first_logits=first_logits,
        caches=state["out"],
        t_first=t_first,
        fallback=state["fallback"],
        overlap_layers=overlap,
        inflight_at_first_token=len(inflight),
        watermark_stalls=stalls,
    )


async def local_decode(
    params,
    config: LlamaConfig,
    prompt: Sequence[int],
    caches,
    block_ids: np.ndarray,
    block_table: np.ndarray,
    first_token: int,
    *,
    gen_tokens: int = 1,
) -> DecodeResult:
    """The no-store baseline AND the byte oracle: recompute the prefix
    locally (same jitted chain as prefill/fallback), then run the same
    decode steps. A handoff decode that disagrees bitwise with this leg's
    ``first_logits`` moved wrong bytes."""
    state = {
        "out": _recompute_prefix(
            params, config, prompt, list(caches),
            jnp.asarray(np.asarray(block_ids), jnp.int32),
        ),
        "fallback": False,
    }
    tokens, first_logits, t_first = await _run_decode_steps(
        params, config, state, block_table, first_token, len(prompt),
        gen_tokens, len(block_table),
    )
    return DecodeResult(
        tokens=tokens, first_logits=first_logits, caches=state["out"],
        t_first=t_first, fallback=False, overlap_layers=0,
        inflight_at_first_token=0, watermark_stalls=0,
    )


# -- two-engine harness ------------------------------------------------------


class DisaggHarness:
    """Two-engine prefill→decode rig over one store.

    ``make_conn`` returns a fresh CONNECTED store connection; the harness
    builds one prefill-side and one decode-side :class:`KVConnector` on
    separate connections with separate block layouts (the decode engine
    never shares the prefill engine's physical blocks — only store keys).
    Legs (each returns ``{"ttft_s", "result", ...}``; TTFT is measured from
    the leg's request-arrival instant, before any compute or fetch):

    - :meth:`run_overlapped` — streamed prefill + watermark-gated decode,
      concurrently (the handoff under test).
    - :meth:`run_blocking` — same concurrency, ``watermark=n_layers``:
      today's blocking fetch-all.
    - :meth:`run_local` — no store; local layer-chunked recompute (also the
      byte oracle).
    - :meth:`run_cold` — sequential: full prefill durable FIRST, then a
      fetch-all decode (store-and-forward).

    For the chaos leg the prefill side runs as a REAL subprocess instead:
    ``python -m infinistore_tpu.disagg --role prefill ...`` (spawned via
    tools/fleet.py) against the same store, and :meth:`run_overlapped` is
    simply not given a prefill coroutine (``prefill=False``)."""

    def __init__(
        self,
        make_conn,
        config: Optional[LlamaConfig] = None,
        *,
        num_blocks: int = 32,
        req_blocks: int = 4,
        gen_blocks: int = 1,
        seed: int = 0,
        model_id: str = "disagg-demo",
        first_token: int = 42,
    ):
        self.config = config or demo_config()
        self.num_blocks = num_blocks
        self.req_blocks = req_blocks
        self.gen_blocks = gen_blocks
        self.first_token = first_token
        self.params = init_params(self.config, jax.random.PRNGKey(seed))
        spec = self.config.kv_spec(num_blocks)
        self.prefill_kv = KVConnector(
            make_conn(), spec, model_id, max_blocks=req_blocks
        )
        self.decode_kv = KVConnector(
            make_conn(), spec, model_id, max_blocks=req_blocks
        )

    def tables(self):
        """(prefill table, decode prefix ids, decode padded table row) —
        disjoint layouts so a byte match proves store transport, not shared
        memory."""
        n = self.req_blocks
        prefill_table = np.arange(n, dtype=np.int32)
        decode_ids = np.arange(n, dtype=np.int32) + n
        gen = np.arange(self.gen_blocks, dtype=np.int32) + 2 * n
        return prefill_table, decode_ids, np.concatenate([decode_ids, gen])

    def prompt(self, seed: int = 0, n_blocks: Optional[int] = None) -> List[int]:
        return demo_prompt(self.config, n_blocks or self.req_blocks, seed=seed)

    def heterogeneous_prompts(self, count: int, seed: int = 0) -> List[List[int]]:
        """Heterogeneous prompt lengths for the ragged decode-wave workload
        (block counts cycle 1..req_blocks): what the bench leg feeds the
        continuous-batching engine to report ``engine_wave_pad_fraction``
        under a disagg-shaped mix."""
        return [
            self.prompt(seed=seed + i, n_blocks=1 + i % self.req_blocks)
            for i in range(count)
        ]

    def trace_prompts(
        self, trace, count: Optional[int] = None
    ) -> List[List[int]]:
        """Materialize a loadgen :class:`~infinistore_tpu.loadgen.Trace`
        into prompts sized for THIS harness (docs/serving_load.md): token
        lists from the trace's own seed (shared family prefixes intact),
        clamped to ``req_blocks`` so every prompt fits the harness's
        per-request table. The trace-driven counterpart of
        :meth:`heterogeneous_prompts` — one workload definition grades
        the engine waves, the ``--trace`` replay AND the disagg handoff."""
        prompts = trace.prompts(
            self.config.block_tokens, vocab=self.config.vocab,
            max_blocks=self.req_blocks,
        )
        return prompts[:count] if count is not None else prompts

    def fresh_caches(self):
        return self.config.kv_spec(self.num_blocks).make_caches()

    def drop(self, prompt) -> int:
        """Drop the prompt's keys so the next round's fetch really races its
        ship (paired bench rounds must each start cold)."""
        return self.decode_kv.drop(prompt)

    async def _handoff(
        self, prompt, *, watermark: int, gen_tokens: int,
        retry_missing_s: float, prefill: bool = True, trace_events=None,
        sequential: bool = False,
    ):
        cfg = self.config
        prefill_table, decode_ids, row = self.tables()
        t0 = time.perf_counter()
        prefill_task = None
        fetch_gate = None
        written = 0
        if prefill and sequential:
            _, _, written = await stream_prefill(
                self.prefill_kv, self.params, cfg, prompt,
                self.fresh_caches(), prefill_table,
            )  # durable before the fetch starts
        elif prefill:
            # Announce-driven handoff: the prefill side signals each
            # layer's publication, the decode side's layer-l read waits
            # for it (no probe storm). The chaos subprocess path has no
            # in-proc channel and rides the retry loop instead.
            shipped = [asyncio.Event() for _ in range(cfg.n_layers)]
            prefill_task = asyncio.ensure_future(
                stream_prefill(
                    self.prefill_kv, self.params, cfg, prompt,
                    self.fresh_caches(), prefill_table,
                    on_layer_shipped=lambda layer: shipped[layer].set(),
                )
            )

            async def fetch_gate(layer, _ev=shipped):
                await _ev[layer].wait()
        res = await overlapped_decode(
            self.decode_kv, self.params, cfg, prompt, self.fresh_caches(),
            decode_ids, row, self.first_token, watermark=watermark,
            known_hit=len(decode_ids), retry_missing_s=retry_missing_s,
            fetch_gate=fetch_gate, gen_tokens=gen_tokens,
            trace_events=trace_events,
        )
        if prefill_task is not None:
            _, _, written = await prefill_task
        return {"ttft_s": res.t_first - t0, "result": res, "written": written}

    async def run_overlapped(
        self, prompt, *, watermark: int = 1, gen_tokens: int = 1,
        retry_missing_s: float = 10.0, prefill: bool = True, trace_events=None,
    ):
        return await self._handoff(
            prompt, watermark=watermark, gen_tokens=gen_tokens,
            retry_missing_s=retry_missing_s, prefill=prefill,
            trace_events=trace_events,
        )

    async def run_blocking(
        self, prompt, *, gen_tokens: int = 1, retry_missing_s: float = 10.0,
        prefill: bool = True,
    ):
        return await self._handoff(
            prompt, watermark=self.config.n_layers, gen_tokens=gen_tokens,
            retry_missing_s=retry_missing_s, prefill=prefill,
        )

    async def run_cold(self, prompt, *, gen_tokens: int = 1):
        return await self._handoff(
            prompt, watermark=self.config.n_layers, gen_tokens=gen_tokens,
            retry_missing_s=0.0, sequential=True,
        )

    async def run_proc(
        self, proc: "PrefillProcess", prompt_seed: int, *,
        watermark: int = 1, gen_tokens: int = 1, cold: bool = False,
        retry_missing_s: float = 10.0,
    ):
        """One handoff round against a REAL prefill subprocess (the bench's
        timing mode — prefill compute genuinely parallel with decode
        fetch+install, which a single event loop cannot give). TTFT is
        measured from the ``go`` send (request arrival at the prefill
        engine). ``cold=True`` is the store-and-forward leg: wait for the
        producer's ``done`` before fetching at all."""
        prompt = demo_prompt(self.config, self.req_blocks, seed=prompt_seed)
        _, decode_ids, row = self.tables()
        rnd = proc.start_round(prompt_seed)
        t0 = time.perf_counter()
        await proc.go(prompt_seed)
        if cold:
            await rnd.done
            res = await overlapped_decode(
                self.decode_kv, self.params, self.config, prompt,
                self.fresh_caches(), decode_ids, row, self.first_token,
                watermark=self.config.n_layers, known_hit=len(decode_ids),
                retry_missing_s=0.0, gen_tokens=gen_tokens,
            )
        else:
            async def gate(layer, _r=rnd):
                await _r.shipped[layer].wait()

            res = await overlapped_decode(
                self.decode_kv, self.params, self.config, prompt,
                self.fresh_caches(), decode_ids, row, self.first_token,
                watermark=watermark, known_hit=len(decode_ids),
                retry_missing_s=retry_missing_s, fetch_gate=gate,
                gen_tokens=gen_tokens,
            )
            await rnd.done
        return {"ttft_s": res.t_first - t0, "result": res, "written": rnd.written}

    async def run_local(self, prompt, *, gen_tokens: int = 1):
        _, decode_ids, row = self.tables()
        t0 = time.perf_counter()
        res = await local_decode(
            self.params, self.config, prompt, self.fresh_caches(),
            decode_ids, row, self.first_token, gen_tokens=gen_tokens,
        )
        return {"ttft_s": res.t_first - t0, "result": res, "written": 0}

    def check_bytes(self, got: DecodeResult, oracle: DecodeResult) -> bool:
        """Bitwise first-token verification against the local-recompute
        oracle; a mismatch is wrong bytes (counted, MUST stay 0)."""
        ok = bool(np.array_equal(got.first_logits, oracle.first_logits))
        if not ok:
            counters().bump("disagg_wrong_bytes")
        return ok


# -- subprocess prefill role -------------------------------------------------


def prefill_argv(
    port: int,
    *,
    serve: bool = False,
    blocks: int = 4,
    n_layers: int = 6,
    block_tokens: int = 8,
    dim: int = 64,
    ffn_dim: int = 128,
    pace_ms: float = 0.0,
    seed: int = 0,
    prompt_seed: int = 0,
    stall_after_layer: Optional[int] = None,
    stall_s: float = 0.0,
    crash_after_layers: Optional[int] = None,
    trace_id: Optional[int] = None,
) -> List[str]:
    """argv for a prefill-engine subprocess (the canonical builder —
    tools/fleet.py's spawn helper and :meth:`PrefillProcess.spawn` both use
    it, so every caller records the exact argv it launched)."""
    import sys

    argv = [
        sys.executable, "-m", "infinistore_tpu.disagg",
        "--port", str(port), "--role", "prefill",
        "--blocks", str(blocks), "--n-layers", str(n_layers),
        "--block-tokens", str(block_tokens),
        "--dim", str(dim), "--ffn-dim", str(ffn_dim),
        "--pace-ms", str(pace_ms),
        "--seed", str(seed), "--prompt-seed", str(prompt_seed),
    ]
    if serve:
        argv.append("--serve")
    if stall_after_layer is not None:
        argv += ["--stall-after-layer", str(stall_after_layer), "--stall-s", str(stall_s)]
    if crash_after_layers is not None:
        argv += ["--crash-after-layers", str(crash_after_layers)]
    if trace_id is not None:
        argv += ["--trace-id", str(trace_id)]
    return argv


@dataclasses.dataclass
class _PrefillRound:
    """One ``go``-round's announce state: per-layer publication events (the
    decode side's ``fetch_gate`` awaits these) and the done future."""

    shipped: List[asyncio.Event]
    done: asyncio.Future
    written: int = 0


class PrefillProcess:
    """The prefill ENGINE as a separate OS process (the two-engine shape a
    real disaggregated deployment has), driven over a line protocol:

      stdin:  ``go <prompt_seed>``  — prefill+stream that prompt's KV
              ``quit``              — exit
      stdout: ``ready platform=P``  — jax up on platform P, store connected
              ``shipped <seed> <layer>`` — layer's puts durable (the
              announce channel the decode side's fetch gate consumes)
              ``done <seed> <written>``  — all layers durable

    The announcement REPLACES store re-probing for the bench legs: the
    decode process's layer-``l`` read launches when ``shipped l`` arrives,
    never before — overlap without a probe storm. Spawn via
    :meth:`spawn` (async; the bench) or tools/fleet.py's
    ``spawn_disagg_prefill`` (sync Popen; the chaos test, which kill -9s
    the process mid-handoff instead of talking to it)."""

    def __init__(self, proc, n_layers: int):
        self.proc = proc
        self.n_layers = n_layers
        self.platform = ""  # the child's JAX platform, from its ready line
        self._rounds: dict = {}
        self._reader: Optional[asyncio.Task] = None

    @classmethod
    async def spawn(
        cls, port: int, *, blocks: int = 4, n_layers: int = 6,
        block_tokens: int = 8, dim: int = 64, ffn_dim: int = 128,
        pace_ms: float = 0.0, seed: int = 0, ready_timeout_s: float = 180.0,
    ) -> "PrefillProcess":
        argv = prefill_argv(
            port, serve=True, blocks=blocks, n_layers=n_layers,
            block_tokens=block_tokens, dim=dim, ffn_dim=ffn_dim,
            pace_ms=pace_ms, seed=seed,
        )
        # This process imported jax (module top) and runs the decode side,
        # so it holds the machine's device: the child is pinned to the CPU
        # platform here, at the launch site, never by the role itself.
        proc = await asyncio.create_subprocess_exec(
            *argv, stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            env=cpu_child_env(),
        )
        self = cls(proc, n_layers)

        async def until_ready():
            while True:
                line = await proc.stdout.readline()
                if not line:
                    raise RuntimeError("prefill process exited before ready")
                parts = line.decode().split()
                if parts[:1] == ["ready"]:
                    self.platform = parts[1].partition("=")[2]
                    return

        await asyncio.wait_for(until_ready(), ready_timeout_s)
        self._reader = asyncio.ensure_future(self._read_loop())
        return self

    def start_round(self, prompt_seed: int) -> _PrefillRound:
        r = _PrefillRound(
            shipped=[asyncio.Event() for _ in range(self.n_layers)],
            done=asyncio.get_running_loop().create_future(),
        )
        self._rounds[prompt_seed] = r
        return r

    async def go(self, prompt_seed: int) -> None:
        self.proc.stdin.write(f"go {prompt_seed}\n".encode())
        await self.proc.stdin.drain()

    async def _read_loop(self) -> None:
        while True:
            line = await self.proc.stdout.readline()
            if not line:
                return
            parts = line.decode().split()
            if parts[:1] == ["shipped"] and len(parts) == 3:
                r = self._rounds.get(int(parts[1]))
                if r is not None:
                    r.shipped[int(parts[2])].set()
            elif parts[:1] == ["done"] and len(parts) == 3:
                r = self._rounds.get(int(parts[1]))
                if r is not None and not r.done.done():
                    r.written = int(parts[2])
                    r.done.set_result(r.written)

    async def close(self) -> None:
        if self._reader is not None:
            self._reader.cancel()
        try:
            self.proc.stdin.write(b"quit\n")
            await self.proc.stdin.drain()
            await asyncio.wait_for(self.proc.wait(), 10.0)
        except Exception:
            self.proc.kill()
            await self.proc.wait()


def _main(argv=None) -> int:
    """``python -m infinistore_tpu.disagg``: the prefill engine as its own
    OS process (the shape a real disaggregated deployment has; the chaos
    test kill -9s this mid-handoff). Prints ``shipped layer N`` as each
    layer's puts become durable and ``prefill done wrote=...`` at the end —
    the spawn helper (tools/fleet.py) and the chaos test key off those
    markers."""
    ap = argparse.ArgumentParser(prog="python -m infinistore_tpu.disagg")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--role", choices=["prefill"], default="prefill")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt-seed", type=int, default=0)
    ap.add_argument("--blocks", type=int, default=4)
    ap.add_argument("--n-layers", type=int, default=6)
    ap.add_argument("--block-tokens", type=int, default=8)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--ffn-dim", type=int, default=128)
    ap.add_argument("--pace-ms", type=float, default=0.0)
    ap.add_argument("--serve", action="store_true")
    ap.add_argument("--stall-after-layer", type=int, default=None)
    ap.add_argument("--stall-s", type=float, default=0.0)
    ap.add_argument("--crash-after-layers", type=int, default=None)
    ap.add_argument("--trace-id", type=int, default=None)
    args = ap.parse_args(argv)

    # The role runs on whatever platform its environment gives it: a
    # launcher that shares a machine with a chip-holding parent pins
    # JAX_PLATFORMS in the child's environment at the launch site
    # (PrefillProcess.spawn, tools/fleet.py spawn_disagg_prefill).
    compile_cache.enable()
    import infinistore_tpu as its

    cfg = demo_config(
        n_layers=args.n_layers, block_tokens=args.block_tokens,
        dim=args.dim, ffn_dim=args.ffn_dim,
    )
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    prompt = demo_prompt(cfg, args.blocks, seed=args.prompt_seed)
    conn = its.InfinityConnection(
        its.ClientConfig(
            host_addr="127.0.0.1", service_port=args.port, log_level="error"
        )
    )
    conn.connect()
    kv = KVConnector(
        conn, cfg.kv_spec(args.blocks), "disagg-demo", max_blocks=args.blocks
    )
    table = np.arange(args.blocks, dtype=np.int32)

    async def run_one(pr, on_layer_shipped) -> int:
        span = None
        if args.trace_id is not None:
            # Cross-process trace continuation: the decode side's installs
            # and this side's ships share one trace id.
            span = tracing.Span("disagg.prefill", trace_id=args.trace_id)
        with tracing.use_span(span):
            _, _, written = await stream_prefill(
                kv, params, cfg, pr, cfg.kv_spec(args.blocks).make_caches(),
                table,
                on_layer_shipped=on_layer_shipped,
                stall_after_layer=args.stall_after_layer,
                stall_s=args.stall_s,
                crash_after_layers=args.crash_after_layers,
                pace_s=args.pace_ms / 1e3,
            )
        if span is not None:
            span.finish("ok")
        return written

    if args.serve:
        # PrefillProcess's line protocol: rounds on stdin, announcements
        # on stdout (class docstring).
        import sys

        async def serve() -> None:
            loop = asyncio.get_running_loop()
            print(f"ready platform={jax.devices()[0].platform}", flush=True)
            while True:
                line = await loop.run_in_executor(None, sys.stdin.readline)
                parts = line.split()
                if not line or parts[:1] == ["quit"]:
                    return
                if parts[:1] != ["go"] or len(parts) != 2:
                    continue
                seed = int(parts[1])
                written = await run_one(
                    demo_prompt(cfg, args.blocks, seed=seed),
                    lambda layer, s=seed: print(
                        f"shipped {s} {layer}", flush=True
                    ),
                )
                print(f"done {seed} {written}", flush=True)

        asyncio.run(serve())
    else:
        written = asyncio.run(
            run_one(
                prompt,
                lambda layer: print(f"shipped layer {layer}", flush=True),
            )
        )
        print(f"prefill done wrote={written}", flush=True)
    conn.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
