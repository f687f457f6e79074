#!/usr/bin/env python3
"""One run of one cell of the benchmark, in a new process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the native core if its ``.so`` is missing, starts the store server as
its own JAX-free process, makes the weights on the device from the seed,
warms the shapes this cell's traffic uses (set-up), runs the window, checks
the outputs, prints one JSON line, stops the server. It exits non-zero, and
prints no result, unless JAX reports a TPU with the chips the cell asks for.

The engine is driven through ``ContinuousBatchingHarness.run_request`` over
``EngineKVAdapter`` -> ``KVConnector`` -> the real server. Everything that
belongs to one configuration, traffic mix or per-layer metric is a data
file found by the name in ``BENCHMARK.json`` (``configs/``, ``traffic/``,
``layer_metrics/``); nothing here names a cell, a model file or the shape of
a cache. What a block of the cache weighs is read off the caches the program
built and what a hit installs of it off the configuration's file
(``cache_geometry.py``), the useful work of a traced call comes from
the module the configuration names (``program.costs``), and a counter of the
program's is read by the name a metric file gives it. A model whose layers
choose says so in its file (``program.choices``): its checked rows are then
compared with a reference that follows the program's choices and holds each
to its own scores (``reference_and_choices``, ``compare_logits``). With
``--trace 1`` the program's own recorder is on and its spans are laid over
the profile (``span_readers.py``); with ``--trace 0`` it stays off.

How a token is timed without editing the program: the benchmark wraps
``harness.wave.step_chunk`` on its own harness instance and records, per
request, the entry time of every call. A request enters round i + 1 the
moment round i's token is on the host, so the entry of its second call is
the first token's emit time and successive entries are the gaps between
tokens (the last token's emit is not seen: 63 stamps for 64 tokens).
"""

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse
import asyncio
import dataclasses
import gc
import importlib
import json
import os
import shutil
import socket
import subprocess
import sys
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, REPO)

import readers  # noqa: E402
import span_readers  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
from cache_geometry import (  # noqa: E402
    POOL_EVICTS_FROM, CacheGeometry, hit_mismatch, pool_gib, store_layout,
)
from infinistore_tpu import tracing  # noqa: E402 - the system under test and its recorder

# Logits against the float32 reference, as multiples of the reference
# logits' rms over the compared rows. bfloat16 keeps 8 significant bits
# (unit roundoff u = 2^-9). Each layer rounds its activations about ten
# times, so L layers walk ~sqrt(10 L) u: 2.5% of the residual stream at 16
# layers, which the final norm and the head carry to the logits. The v5e
# showed 0.68% rms and 3.7% worst at 8 layers (PERF.md, PR 21), so ~1% and
# ~5% are expected here; the bounds are about 2.5x that. An 8-bit cache or
# weight (u 16x larger) or one layer left out of 16 (a quarter of the
# stream's variance) is far outside.
LOGITS_RMS_TOL = 0.025
LOGITS_MAX_TOL = 0.15
# Where the configuration's file names ``program.choices`` (a model whose
# layers choose: a top-k router) the reference follows the program's sets on
# the compared rows and says how far each set lies off its own scores: the
# largest score left out less the smallest chosen, over the rms of that
# token's centred scores. A score is a projection of the normed hidden state,
# so its error over the scores' rms is what a logit's is over the logits' rms
# (limit 0.025); a gap is a difference of two such errors, and the check takes
# the worst of rounds x sites of them, about three deviations: 4 x 0.025. CPU
# emulations at published widths read at most 0.021 and 0.018-0.046 over
# 1,024 (row, site) pairs a seed at four layers; a check reads some 40 pairs
# (PERF.md, PR 33). A program that drops a clearly better expert, or routes
# at random, is far outside; one that swaps a near-tie is inside, and is then
# held to the two limits above like any other.
CHOICE_SLACK = 0.10
DECODE_STEPS_CHECKED = 8
TRACE_SECONDS = 8.0  # the traced run profiles this long, mid-window
DOC_BASE_WARM, DOC_BASE_CHECK = 10_000_000, 20_000_000
# The recorder's ring in a traced run: ~15 spans a request and its store
# ops, two a wave; a dropped span makes the run not correct.
SPAN_CAPACITY = 1 << 18
# Counters the benchmark keeps itself (``CellRun.results``). Any other key a
# ``counter`` reader names is the program's: ``harness.metrics()`` or the
# connector's ``get_stats()``.
OWN_COUNTERS = frozenset({
    "waves", "real_rows", "window_compiles", "store_evictions", "peak_hbm_bytes", "tpot_mean_ms",
})


def fail(msg: str, code: int = 1):
    print(f"benchmarks/run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def resolve(dotted: str):
    module, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(module), attr)


def cell_of(bench: Dict, workload: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        fail(f"no workload {workload!r} in BENCHMARK.json (have {sorted(cells)})", 2)
    cell = cells[workload]
    (config,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    return cell, load_json(os.path.join(REPO, config["file"]))


def warm_answer_tokens(plan: traffic.Plan, block_tokens: int) -> int:
    """A warm-up request's answer: one block of tokens, so that the save of
    a whole answer block is warmed too, where the traffic's answers reach
    one; else the traffic's longest answer."""
    return min(block_tokens, max(r.answer_tokens for r in plan.requests))


def check_answer_tokens(plan: traffic.Plan, block_tokens: int) -> int:
    """A checked request's answer: two blocks of tokens or the traffic's
    longest answer, whichever is shorter, and never under the first token
    and the decode steps ``against_reference`` reads."""
    longest = max(r.answer_tokens for r in plan.requests)
    return max(min(2 * block_tokens, longest), DECODE_STEPS_CHECKED + 1)


def metrics_for(bench: Dict, group: str, workload: str) -> List[Dict]:
    return [
        m for m in bench[group]
        if "workloads" not in m or workload in m["workloads"]
    ]


def reference_and_choices(prog: Dict):
    """The configuration's reference module and, where its file names
    ``program.choices``, that function of the program's. The two come as a
    pair: ``choices(harness, rows) -> int [len(rows), sites, k]`` (the ids the
    timed step chose at each of the model's discrete-choice sites while it made
    those logits rows) beside the reference's ``logits_following(params,
    config, tokens, rounds, choices) -> (logits, gaps [rounds, sites])``. One
    without the other raises ``ValueError`` with both names."""
    reference = importlib.import_module(prog["reference"])
    named, follows = prog.get("choices"), hasattr(reference, "logits_following")
    if bool(named) != follows:
        raise ValueError(
            f"program.choices is {named!r} in the configuration's file and its reference module "
            f"{prog['reference']!r} has {'a' if follows else 'no'} logits_following: a program "
            f"that reports its choices and a reference that follows them come together or not at all"
        )
    return reference, resolve(named) if named else None


def compare_logits(got, ref, gaps=None, rms_tol=LOGITS_RMS_TOL, max_tol=LOGITS_MAX_TOL,
                   slack=CHOICE_SLACK):
    """The comparison with the reference, as a pure function: ``got`` and
    ``ref`` are ``[rounds, vocab]`` float32 logits, ``gaps`` the ``[rounds,
    sites]`` the reference gave where it followed the program's choices, or
    None. Returns the sentences that failed (none: the rows are held correct)
    and the numbers read, each beside its limit: rms and worst of the
    difference as multiples of the reference logits' rms, and the widest gap
    with its row and site."""
    import jax.numpy as jnp

    failed = []
    scale = float(jnp.sqrt(jnp.mean(ref * ref)))
    rms = float(jnp.sqrt(jnp.mean((got - ref) ** 2))) / scale
    worst = float(jnp.max(jnp.abs(got - ref))) / scale
    read = {"rms": rms, "rms_limit": rms_tol, "worst": worst, "worst_limit": max_tol, "ref_rms": scale}
    if gaps is not None:
        gaps = jnp.asarray(gaps, jnp.float32)
        row, site = (int(i) for i in jnp.unravel_index(jnp.argmax(gaps), gaps.shape))
        read.update(max_gap=float(gaps[row, site]), choice_slack=slack, gap_row=row, gap_site=site)
        if not read["max_gap"] <= slack:
            failed.append(
                f"the program's choice at row {row} site {site} lies {read['max_gap']:.4f} of its "
                f"scores' rms off the float32 reference's own (limit {slack})"
            )
    if not bool(jnp.all(jnp.isfinite(got))):
        failed.append("non-finite logits")
    if not (rms <= rms_tol and worst <= max_tol):
        failed.append(f"logits off the float32 reference: rms {rms:.4f} worst {worst:.4f}")
    return failed, read


# ---------------------------------------------------------------------------
# The store server: its own process, never JAX.
# ---------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def build_native_if_missing():
    so = os.path.join(REPO, "infinistore_tpu", "_native", "libinfinistore_tpu.so")
    if not os.path.exists(so):
        subprocess.run(
            ["make", "-s", "-C", os.path.join(REPO, "native"), "-j", str(os.cpu_count() or 2)],
            check=True, stdout=subprocess.DEVNULL,
        )


def start_server(pool_gib: int, unit_kib: int) -> Dict:
    from infinistore_tpu.hostmesh import cpu_child_env

    service, manage = free_port(), free_port()
    argv = [
        sys.executable, "-m", "infinistore_tpu.server", "--host", "127.0.0.1",
        "--service-port", str(service), "--manage-port", str(manage),
        "--prealloc-size", str(pool_gib), "--minimal-allocate-size", str(unit_kib),
        "--no-pin-memory", "--log-level", "error",
    ]
    proc = subprocess.Popen(argv, cwd=REPO, env=cpu_child_env())
    deadline = time.time() + 120
    while time.time() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(f"the store server exited with {proc.returncode}")
        try:
            with socket.create_connection(("127.0.0.1", service), timeout=0.3):
                return {"proc": proc, "service_port": service}
        except OSError:
            time.sleep(0.05)
    proc.kill()
    proc.wait()
    raise RuntimeError("the store server did not come up in 120 s")


def stop_server(server: Dict):
    proc = server["proc"]
    if proc.poll() is None:
        proc.send_signal(2)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
    proc.wait()


# ---------------------------------------------------------------------------
# Records.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Record:
    """What the benchmark notes about one request, from outside."""

    req: traffic.Request
    t_start: float  # due (open loop) or sent (closed loop): TTFT counts from here
    t_dispatch: float  # when the generator handed it over (open loop: due + lateness)
    t_sent: float  # when it entered the harness (open loop: after a live slot freed)
    stamps: List[float] = dataclasses.field(default_factory=list)  # step_chunk entries
    calls: List[tuple] = dataclasses.field(default_factory=list)  # (context pages, rows) per entry
    alloc_waited: bool = False
    stats: Optional[object] = None  # RequestStats
    error: Optional[str] = None
    logits: Optional[list] = None  # kept only in the correctness phase
    choices: Optional[list] = None  # beside ``logits``, where the file names ``program.choices``

    def emits(self) -> List[float]:
        """Emit times of the generated tokens seen: entry k + 1 is the
        moment token k reached the host. The extra round that lands a
        block-completing last token is not a token."""
        return self.stamps[1 : self.req.answer_tokens]


class Instruments:
    """The benchmark's taps on its own harness instance: ``step_chunk``
    entry stamps, ``BlockPool.alloc`` waits, prefill calls. Nothing in the
    program is edited; the attributes are set on the instances. ``choices``
    is the program's own function where the configuration's file names one:
    called in the check phase after every call, never in the window."""

    def __init__(self, harness, block_tokens: int, choices=None):
        import jax

        self.h = harness
        self.bt = block_tokens
        self.choices = choices
        self.by_task: Dict[object, Record] = {}
        self.keep_logits = False
        self.prefills: List[tuple] = []  # (t_start, tokens)
        self.resumes: List[tuple] = []  # (t_start, context pages, chunk rows)
        self._step_chunk = harness.wave.step_chunk
        self._alloc = harness.pool.alloc
        self._prefill_full = harness._prefill_full
        self._chunked_resume = harness._chunked_resume
        self._annotate = jax.profiler.TraceAnnotation
        harness.wave.step_chunk = self.step_chunk
        harness.pool.alloc = self.alloc
        harness._prefill_full = self.prefill_full
        harness._chunked_resume = self.chunked_resume

    async def step_chunk(self, tokens, positions, padded_table, priority=0):
        rec = self.by_task.get(asyncio.current_task())
        if rec is not None:
            rec.stamps.append(time.perf_counter())
            rec.calls.append((sum(-(-(p + 1) // self.bt) for p in positions), len(tokens)))
        rows = await self._step_chunk(tokens, positions, padded_table, priority=priority)
        if rec is not None and self.keep_logits:
            rec.logits.append(rows)
            if self.choices is not None:
                rec.choices.append(self.choices(self.h, rows))
        return rows

    async def alloc(self, n):
        rec = self.by_task.get(asyncio.current_task())
        if rec is not None and self.h.pool.available < n:
            rec.alloc_waited = True
        return await self._alloc(n)

    def prefill_full(self, token_ids, table):
        self.prefills.append((time.perf_counter(), len(token_ids)))
        with self._annotate("bench.prefill_full"):
            return self._prefill_full(token_ids, table)

    def chunked_resume(self, token_ids, table, start_block):
        # What the program counts itself (``resume_pages``, ``resume_tokens``).
        rows = len(token_ids) - start_block * self.bt
        self.resumes.append((time.perf_counter(), -(-len(token_ids) // self.bt), rows))
        with self._annotate("bench.chunked_resume"):
            return self._chunked_resume(token_ids, table, start_block)


class Compiles:
    """Compilations and compile-cache loads, as JAX reports them."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.count += 1


# ---------------------------------------------------------------------------
# The run.
# ---------------------------------------------------------------------------


class CellRun:
    def __init__(self, args, cell, config, plan, program_counters=()):
        self.args, self.cell, self.config, self.plan = args, cell, config, plan
        # Keys of the program's own counters that this cell's metrics read.
        self.program_counters = sorted(program_counters)
        self.records: List[Record] = []
        self.failed_checks: List[str] = []
        self.compared: List[Dict] = []  # what each comparison with the reference read
        # ``execute`` sets both from the file's ``program`` before the server
        # starts: the reference module, and the program's ``choices`` or None.
        self.reference = self.choices = None

    # -- set-up ---------------------------------------------------------------

    def build(self, conn, compiles):
        import jax
        import jax.numpy as jnp

        from infinistore_tpu.connector import KVConnector, token_chain_hashes
        from infinistore_tpu.engine import ContinuousBatchingHarness, EngineKVAdapter

        prog, serving = self.config["program"], self.config["serving"]
        fields = {k: self.config[v] for k, v in prog["fields"].items()}
        self.cfg = resolve(prog["config_class"])(
            block_tokens=serving["block_tokens"], dtype=jnp.bfloat16, **fields
        )
        for attr, key in prog.get("equals", {}).items():
            if getattr(self.cfg, attr) != self.config[key]:
                raise ValueError(
                    f"{attr} of the program's config is {getattr(self.cfg, attr)!r}, "
                    f"{key} of the file {self.config[key]!r}"
                )
        self.costs = importlib.import_module(prog["costs"])
        init = resolve(prog["init_params"])
        # One jitted call from the seed, on the device, in the served type.
        # The rbg generator: XLA:TPU takes about a minute to compile
        # threefry at these shapes (PERF.md, PR 21).
        key = jax.random.key(self.args.seed % (2**63), impl="rbg")
        self.params = jax.block_until_ready(jax.jit(lambda k: init(self.cfg, k))(key))
        bt = self.cfg.block_tokens
        # A traffic mix may size the cache to what it can hold at most (its
        # file says why); otherwise the configuration's own size.
        self.num_blocks = int(self.plan.params.get("cache_blocks", serving["cache_blocks"]))
        self.max_req_blocks = max(
            -(-(r.prompt_tokens + r.answer_tokens) // bt) for r in self.plan.requests
        )
        connector = KVConnector(
            conn, self.cfg.kv_spec(self.num_blocks), self.config["name"],
            max_blocks=self.max_req_blocks,
        )

        bt_, keep_host_copy = bt, self.keep_host_copy

        class CheckingAdapter(EngineKVAdapter):
            """The engine adapter, noting every block handed to a save (for
            the eviction count); in the correctness phase it also keeps a
            host copy of those blocks."""

            def __init__(self, connector):
                super().__init__(connector)
                self.chains_saved = set()
                self.saved: Dict[str, list] = {}  # chain hash -> per layer, its tensors' bytes
                self.keep = False

            async def save_kv(self, token_ids, caches, block_table, first_block=0):
                chains = token_chain_hashes(token_ids, bt_)[first_block:][: len(block_table)]
                self.chains_saved.update(chains)
                if self.keep:
                    keep_host_copy(self.saved, chains, caches, block_table)
                return await super().save_kv(
                    token_ids, caches, block_table, first_block=first_block
                )

        self.adapter = CheckingAdapter(connector)
        self.h = ContinuousBatchingHarness(
            self.adapter, self.params, self.cfg, self.num_blocks, self.max_req_blocks
        )
        # The cache as the program built it: what a block weighs and how
        # many values it puts in the store; from the file, which of its
        # tensors a hit installs in its last blocks only. The file's serving
        # numbers, which sized the server before anything was built, must agree.
        self.geometry = CacheGeometry.of(self.h.caches, serving.get("hit_installs", ()))
        self.geometry.check(serving)
        self.taps = Instruments(self.h, bt, self.choices)
        self.compiles = compiles
        self.conn = conn
        for key in set(self.program_counters) - set(self.read_program_counters()):
            # A metric file may come with the counter it reads: on a tree
            # that has no such counter yet the metric is left out of the line.
            print(f"benchmarks/run.py: counter {key!r} is neither in harness.metrics() nor in "
                  f"the connector's get_stats(): no metric of {self.cell['name']} reads it in "
                  f"this run", file=sys.stderr, flush=True)

    @staticmethod
    def keep_host_copy(saved, chains, caches, block_table):
        """The blocks handed to a save, copied to the host: one gather a
        tensor of those blocks alone, never the whole cache."""
        import jax.numpy as jnp
        import numpy as np

        from infinistore_tpu.tpu.paged import gather_blocks

        ids = jnp.asarray(np.asarray(block_table[: len(chains)]), jnp.int32)
        host = [[np.asarray(gather_blocks(t, ids)) for t in layer] for layer in caches]
        for i, chain in enumerate(chains):
            saved[chain] = [[t[i].tobytes() for t in layer] for layer in host]

    def wave_buckets(self) -> List[tuple]:
        """Every (rows, pages) bucket a wave of this traffic can land on.
        The decoder pads rows to a power of two by repeating the last row
        (its pages count again) and pages to a power of two of their sum."""
        bt = self.cfg.block_tokens
        lo = min(-(-r.prompt_tokens // bt) for r in self.plan.requests)
        hi = self.max_req_blocks
        out, rows = [], 1
        while rows < 2 * self.plan.clients:
            p = 1 << (rows * lo - 1).bit_length()
            while True:
                out.append((rows, p))
                if p >= rows * hi:
                    break
                p <<= 1
            rows <<= 1
        return out

    async def warm_waves(self):
        """One throwaway wave per bucket, launched by the decoder itself:
        ``rows`` concurrent ``step_chunk`` calls of one token each, their
        positions chosen so that the pages add up into the bucket, over a
        table of block 0 alone (the scatter rides block 0, which no request
        owns yet), and the argmax ``_generate`` takes of each row. No model
        file is named: whatever the engine launches is what gets warmed.
        These calls come from tasks of their own, so no record is stamped."""
        import jax.numpy as jnp
        import numpy as np

        bt, mrb = self.cfg.block_tokens, self.max_req_blocks
        table = np.zeros(mrb, np.int32)
        for rows, pages in self.wave_buckets():
            total = min(pages, rows * mrb)
            share = [total // rows + (i < total % rows) for i in range(rows)]
            waves = self.h.wave.waves
            got = await asyncio.gather(
                *(self.h.wave.step_chunk([0], [n * bt - 1], table) for n in share)
            )
            for logits in got:
                np.asarray(jnp.argmax(logits, axis=-1))
            if self.h.wave.waves != waves + 1 or (rows, rows, pages) not in self.h.wave.bucket_sizes:
                raise RuntimeError(
                    f"warm-up could not pin the wave bucket ({rows} rows, {pages} pages): the "
                    f"decoder launched {self.h.wave.waves - waves} wave(s), its buckets are "
                    f"{sorted(self.h.wave.bucket_sizes)}"
                )

    def prompt_classes(self) -> List[traffic.Request]:
        """The first request of each (prefix, own tokens) shape in the plan."""
        first = {}
        for r in self.plan.requests:
            first.setdefault((r.prefix_tokens, r.own_tokens), r)
        return list(first.values())

    def warm_requests(self) -> List[traffic.Request]:
        """One request per shape the traffic compiles: each prompt length
        (a miss), and for shared prefixes the same document again (a hit:
        install and chunked resume). Answers are cut to a few tokens; the
        save shapes of whole answers are warmed apart."""
        answer_tokens = warm_answer_tokens(self.plan, self.cfg.block_tokens)
        reqs = []
        for r in self.prompt_classes():
            doc = DOC_BASE_WARM + len(reqs)
            for ask in (0, 1) if r.prefix_tokens else (0,):
                reqs.append(dataclasses.replace(
                    r, index=DOC_BASE_WARM + len(reqs), doc=doc, ask=ask, due_s=None,
                    answer_tokens=answer_tokens,
                ))
        return reqs

    async def warm_saves(self):
        """The response-save shapes: one ``_save_blocks`` per distinct count
        of whole answer blocks, over blocks no request holds."""
        import numpy as np

        bt = self.cfg.block_tokens
        counts = sorted({
            (r.prompt_tokens + r.answer_tokens) // bt - r.prompt_tokens // bt
            for r in self.plan.requests
        })
        rng = np.random.default_rng([self.args.seed, 13])
        for n in counts:
            if n <= 0:
                continue
            chain = rng.integers(0, self.cfg.vocab, size=n * bt).tolist()
            table = await self.h.pool.alloc(n)
            try:
                await self.h._save_blocks(chain, table, 0)
            finally:
                await self.h.pool.free(table)

    # -- one request ----------------------------------------------------------

    async def send(self, req: traffic.Request, t_start=None, t_dispatch=None) -> Record:
        now = time.perf_counter()
        rec = Record(
            req=req, t_start=now if t_start is None else t_start,
            t_dispatch=now if t_dispatch is None else t_dispatch, t_sent=now,
        )
        if self.taps.keep_logits:
            rec.logits, rec.choices = [], []
        self.records.append(rec)
        task = asyncio.current_task()
        self.taps.by_task[task] = rec
        try:
            tokens = traffic.token_ids(req, self.args.seed, self.cfg.vocab)
            rec.stats = await self.h.run_request(tokens, gen_tokens=req.answer_tokens)
        except Exception as e:  # noqa: BLE001 - a failed request is a result
            rec.error = f"{type(e).__name__}: {e}"
            print(f"request {req.index} failed: {rec.error[:2000]}", file=sys.stderr, flush=True)
        finally:
            del self.taps.by_task[task]
        return rec

    # -- the window -----------------------------------------------------------

    async def closed_loop(self):
        opened = [asyncio.Event() for _ in range(self.plan.clients)]

        async def client(c: int):
            for i, req in enumerate(self.plan.client_list(c)):
                if self.t_close is not None and time.perf_counter() >= self.t_close:
                    return
                await self.send(req)
                if i == 0:
                    opened[c].set()
            self.ran_dry = True

        async def open_window():
            # The window opens when every client has finished its first
            # request: the system is then under its steady load.
            for ev in opened:
                await ev.wait()
            self.open_now()

        await asyncio.gather(open_window(), *(client(c) for c in range(self.plan.clients)))

    async def open_loop(self):
        lead_in = float(self.plan.params.get("lead_in_s", 0.0))
        live = asyncio.Semaphore(self.plan.clients)
        tasks = []

        async def one(req, due):
            dispatched = time.perf_counter()
            async with live:
                await self.send(req, t_start=due, t_dispatch=dispatched)

        t_open = time.perf_counter() + lead_in
        opener = asyncio.get_running_loop().call_later(lead_in, self.open_now, t_open)
        for req in self.plan.requests:
            if req.due_s >= self.args.seconds:
                break
            due = t_open + req.due_s
            delay = due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(one(req, due)))
        else:
            self.ran_dry = True
        await asyncio.gather(*tasks)
        opener.cancel()

    def open_now(self, at: Optional[float] = None):
        self.t_open = time.perf_counter() if at is None else at
        self.t_close = self.t_open + self.args.seconds
        self.at_open = self.snapshot()
        loop = asyncio.get_running_loop()
        loop.call_later(self.args.seconds, self.close_now)
        if self.args.trace:
            start = max(0.0, min(0.4 * self.args.seconds, self.args.seconds - TRACE_SECONDS - 1))
            loop.call_later(start, self.trace_start)

    def close_now(self):
        if self.at_close is None:
            self.at_close = self.snapshot()

    def snapshot(self) -> Dict[str, float]:
        w = self.h.wave
        return {
            "waves": w.waves, "real_rows": w.launched_rows - w.pad_rows,
            "compiles": self.compiles.count, "t": time.perf_counter(),
            **self.read_program_counters(),
        }

    def read_program_counters(self) -> Dict[str, float]:
        """The program's counters this cell's metrics name, as they stand:
        a key of ``harness.metrics()``, else of the connector's
        ``get_stats()`` (dotted where nested). A key that is in neither is
        left out (``readers._counter`` then gives None for its metric); one
        that is there and is no number raises. No call where none is named."""
        if not self.program_counters:
            return {}
        metrics, stats = self.h.metrics(), self.adapter.connector.get_stats()
        out = {}
        for key in self.program_counters:
            value = metrics.get(key)
            if value is None:
                value = stats
                for part in key.split("."):
                    value = value.get(part) if isinstance(value, dict) else None
            if value is None:
                continue
            if isinstance(value, bool) or not isinstance(value, (int, float)):
                raise ValueError(
                    f"counter {key!r} is no number in harness.metrics() or the connector's "
                    f"get_stats(): a metric file of {self.cell['name']} names it"
                )
            out[key] = value
        return out

    def trace_start(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        tracing.profile_clock_mark()
        self.trace_t0 = time.perf_counter()
        asyncio.get_running_loop().call_later(TRACE_SECONDS, self.trace_stop)

    def trace_stop(self):
        """Ends the profile in a thread: collecting and writing it takes
        seconds, and on the event loop that would stall every request."""
        import jax

        tracing.profile_clock_mark()
        self.trace_t1 = time.perf_counter()
        self.trace_written = asyncio.get_running_loop().run_in_executor(
            None, jax.profiler.stop_trace
        )

    async def run(self):
        """Set-up's last part (the shapes), the window, the drain."""
        self.t_open = self.t_close = self.at_close = None
        self.ran_dry = False
        self.trace_t0 = self.trace_t1 = None
        self.trace_dir = os.path.join(REPO, ".bench_out", f"trace-{self.cell['name']}")
        await self.warm_waves()
        await self.warm_saves()
        for req in self.warm_requests():
            rec = await self.send(req)
            if rec.error:
                raise RuntimeError(f"warm-up request failed: {rec.error}")
        self.records.clear()
        self.taps.prefills.clear()
        self.taps.resumes.clear()
        gc.collect()
        gc.freeze()
        if self.plan.loop == "closed":
            await self.closed_loop()
        else:
            await self.open_loop()
        if self.trace_t0 is not None:
            if self.trace_t1 is None:
                self.trace_stop()
            await self.trace_written
        if self.at_close is None:  # the lists ran dry: still close the window
            await asyncio.sleep(max(0.0, self.t_close - time.perf_counter()))
            self.close_now()

    # -- correctness, outside the window ----------------------------------------

    async def check(self):
        """Per prompt class: a miss against the float32 reference (first
        token and 8 decode steps), the same prompt again as a full hit
        (the blocks the configuration says a hit installs byte-identical to
        what the miss saved, first-token logits equal), and for shared
        prefixes a partial hit (a new question after the stored prefix: the
        traffic's own hit path) against the reference."""
        import jax.numpy as jnp
        import numpy as np

        from infinistore_tpu.connector import token_chain_hashes
        from infinistore_tpu.tpu.paged import gather_blocks

        bt = self.cfg.block_tokens
        answer_tokens = check_answer_tokens(self.plan, bt)
        self.taps.keep_logits = True
        self.adapter.keep = True
        for n, r in enumerate(self.prompt_classes(), start=1):
            label = f"prompt {r.prompt_tokens}"
            base = dataclasses.replace(
                r, index=DOC_BASE_CHECK + 3 * n, doc=DOC_BASE_CHECK + n, ask=0, due_s=None,
                answer_tokens=answer_tokens,
            )
            self.adapter.saved.clear()
            miss = await self.send(base)
            if not self.expect(miss.error is None, f"{label}: miss failed: {miss.error}"):
                continue
            tokens = traffic.token_ids(base, self.args.seed, self.cfg.vocab)
            self.expect(
                miss.stats.loaded_blocks == 0 and miss.stats.computed_blocks == len(tokens) // bt,
                f"{label}: the first ask was not a miss",
            )
            self.against_reference(label + " miss", miss, tokens)
            # Full hit: same prompt, own table; read the installed blocks back.
            held = {}
            real_install = self.adapter.install_kv

            async def install_and_keep(prefetch, caches, block_table):
                out, loaded = await real_install(prefetch, caches, block_table)
                table = np.asarray(block_table)
                # Only the blocks the policy says this hit installed.
                held["blocks"] = [
                    [
                        np.asarray(gather_blocks(t, jnp.asarray(table[blocks.start : blocks.stop], jnp.int32)))
                        for t, blocks in zip(layer, names)
                    ]
                    for layer, names in zip(out, self.geometry.installed_blocks(loaded // bt))
                ]
                return out, loaded

            self.adapter.install_kv = install_and_keep
            try:
                hit = await self.send(base)  # the same tokens: a full hit
            finally:
                del self.adapter.install_kv
            if not self.expect(hit.error is None, f"{label}: hit failed: {hit.error}"):
                continue
            n = len(tokens) // bt
            self.expect(
                hit.stats.loaded_blocks == n and hit.stats.computed_blocks == 0,
                f"{label}: the second ask loaded {hit.stats.loaded_blocks} of {n} blocks",
            )
            self.expect_fetch(label + " hit", hit.stats)
            chains = token_chain_hashes(tokens, bt)[:n]
            wrong = hit_mismatch(held.get("blocks", ()), self.adapter.saved, chains, self.geometry)
            self.expect(wrong is None, f"{label}: installed blocks: {wrong}")
            first = lambda rec: np.asarray(rec.logits[0][0], np.float32)
            self.expect(
                bool(np.array_equal(first(hit), first(miss))),
                f"{label}: hit-path and miss-path first-token logits differ",
            )
            self.expect(hit.stats.generated == miss.stats.generated, f"{label}: hit tokens differ")
            if r.prefix_tokens:
                part_req = dataclasses.replace(base, index=base.index + 2, ask=1)
                part = await self.send(part_req)
                if self.expect(part.error is None, f"{label}: partial hit failed: {part.error}"):
                    self.expect(
                        part.stats.loaded_blocks == r.prefix_tokens // bt,
                        f"{label}: partial hit loaded {part.stats.loaded_blocks} blocks",
                    )
                    self.expect_fetch(label + " partial hit", part.stats)
                    self.against_reference(
                        label + " partial hit", part,
                        traffic.token_ids(part_req, self.args.seed, self.cfg.vocab),
                    )
        self.taps.keep_logits = False
        self.adapter.keep = False
        self.adapter.saved.clear()

    def expect(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failed_checks.append(what)
            print(f"check failed: {what}", file=sys.stderr, flush=True)
        return ok

    def expect_fetch(self, label: str, stats) -> bool:
        """The fetch follows the policy too: a hit of n blocks fetched the
        store values the configuration says it installs, no more, no fewer."""
        want = self.geometry.fetched_values(stats.hit_blocks)
        return self.expect(
            stats.prefetched_blocks == want,
            f"{label}: fetched {stats.prefetched_blocks} store values for a hit of "
            f"{stats.hit_blocks} blocks, the configuration's policy names {want}",
        )

    def against_reference(self, label: str, rec: Record, tokens: List[int]):
        """Round j of the request decodes position len - 1 + j: the logits
        that chose generated token j, teacher-forced on the tokens it chose.
        Where the program reports its choices, the reference takes row 0's
        (this request's) of each round on those positions and its own
        everywhere else."""
        import jax.numpy as jnp
        import numpy as np

        rounds = DECODE_STEPS_CHECKED + 1
        got = jnp.concatenate([rows[:1] for rows in rec.logits[:rounds]]).astype(jnp.float32)
        context, gaps = tokens + rec.stats.generated[: rounds - 1], None
        if self.choices is None:
            ref = self.reference.logits(self.params, self.config, context, rounds)
        else:
            chosen = np.stack([np.asarray(c)[0] for c in rec.choices[:rounds]])
            ref, gaps = self.reference.logits_following(self.params, self.config, context, rounds, chosen)
        failed, read = compare_logits(got, ref, gaps)
        followed = "" if gaps is None else f"; widest choice gap {read['max_gap']:.5f} (slack {CHOICE_SLACK})"
        print(f"logits {label}: rms {read['rms']:.5f} worst {read['worst']:.5f} (x ref rms "
              f"{read['ref_rms']:.4f}; tol {LOGITS_RMS_TOL} / {LOGITS_MAX_TOL}){followed}",
              file=sys.stderr, flush=True)
        self.compared.append({"label": label, **read})
        for sentence in failed:
            self.expect(False, f"{label}: {sentence}")

    # -- the numbers ------------------------------------------------------------

    def request_row(self, rec: Record) -> Dict:
        """One row of the table the per-layer readers see (see readers.py)."""
        s, bt = rec.stats, self.cfg.block_tokens
        emits = rec.emits()
        row = {
            "ttft_ms": (emits[0] - rec.t_start) * 1e3 if emits else None,
            "late_ms": (rec.t_dispatch - rec.t_start) * 1e3,
            "live_wait_ms": (rec.t_sent - rec.t_dispatch) * 1e3,
            "alloc_waited": 1.0 if rec.alloc_waited else 0.0,
            "prompt_blocks": rec.req.prompt_tokens // bt,
            "hit": False,
        }
        if s is not None:
            row.update(
                hit=s.loaded_blocks > 0,
                loaded_blocks=s.loaded_blocks,
                hit_blocks=s.hit_blocks,
                fetched_values=s.prefetched_blocks,
                gate_stall_ms=s.gate_stall_us / 1e3,
                prefix_ready_ms=s.prefix_ready_us / 1e3,
                ttft_engine_ms=s.ttft_us / 1e3,
                after_ready_ms=(
                    None if row["ttft_ms"] is None else row["ttft_ms"] - s.prefix_ready_us / 1e3
                ),
                gate_hold_s=s.gate_hold_us / 1e6,
                fetch_s=s.fetch_us / 1e6,
                installed_bytes=self.geometry.installed_nbytes(s.loaded_blocks),
                # ``prefetched_blocks`` counts store values, for ``hit_blocks`` blocks.
                fetched_bytes=self.geometry.fetched_nbytes(s.prefetched_blocks, s.hit_blocks),
                # For the span readers: the request's trace, and the program's
                # emit stamps beside the benchmark's.
                trace_id=s.trace_id, emit_s=list(s.token_emit_s), bench_emit_s=emits,
            )
        return row

    def results(self, setup_s: float, peak_bytes: int) -> Dict:
        t0, t1 = self.t_open, self.t_close
        started = [r for r in self.records if t0 <= r.t_start < t1]
        rows = [self.request_row(r) for r in started if r.error is None]
        emits = [r.emits() for r in self.records]
        in_window = sum(1 for es in emits for e in es if t0 <= e < t1)
        gaps = [
            (b - a) * 1e3 for es in emits for a, b in zip(es, es[1:]) if t0 <= b < t1
        ]
        e2e = readers.end_to_end(rows, in_window, gaps, t1 - t0, setup_s)
        # What the run wrote against what the server holds: every block
        # handed to a save is one key per tensor of every layer.
        written = len(self.adapter.chains_saved)
        server = self.conn.get_stats()
        held = server["kvmap_len"]
        self.pool_usage = server.get("usage")
        for rec in started:
            if rec.error is None and rec.stats is not None and rec.stats.loaded_blocks > 0:
                self.expect_fetch(f"request {rec.req.index}", rec.stats)

        def delta(key):
            return self.at_close[key] - self.at_open[key]

        counters = {
            "waves": delta("waves"), "real_rows": delta("real_rows"),
            "window_compiles": delta("compiles"),
            "store_evictions": max(0, written * self.geometry.values_per_block - held),
            "peak_hbm_bytes": peak_bytes,
            # A named key the program does not have (yet) is in neither snapshot.
            **{key: delta(key) for key in self.program_counters if key in self.at_close},
        }
        if "tpot_mean_ms" in e2e:
            counters["tpot_mean_ms"] = e2e["tpot_mean_ms"]
        # With the recorder on: what it holds, for the span readers.
        spans, rec = None, tracing.recorder()
        if tracing.enabled() and rec is not None:
            spans = {
                "spans": rec.snapshot(), "recorded": rec.recorded, "dropped": rec.dropped,
                "window_us": [t0 * 1e6, t1 * 1e6], "profile": None,
            }
        return {
            "attempted": len(started),
            "failed": sum(1 for r in started if r.error is not None),
            "end_to_end": e2e, "rows": rows, "counters": counters, "spans": spans,
        }

    def trace_results(self, spans: Optional[Dict]) -> Optional[Dict]:
        """The profile, read once and reduced for both kinds of reader: the
        device's tables, and (into ``spans["profile"]``) the idle gaps by
        program phase. ``trace["work"]`` is the useful work of the traced
        calls, summed by the configuration's cost module over what the taps
        saw: a request's entries into waves, the prefills, and the resumes
        of prefix hits."""
        if self.trace_t0 is None:
            return None
        raw = trace_reduce.load(trace_reduce.find_xplane(self.trace_dir))
        trace = trace_reduce.reduce(raw)
        if spans is not None:
            spans["profile"] = span_readers.reduce_profile(raw, spans["spans"])
        a, b = self.trace_t0, self.trace_t1
        work = dict.fromkeys(self.costs.WORK_KEYS, 0)
        work["prefill_ktok"] = 0.0

        def add(amounts: Dict):
            for key, amount in amounts.items():
                work[key] += amount

        for rec in self.records:
            for t, (pages, rows) in zip(rec.stamps, rec.calls):
                if a <= t < b:
                    add(self.costs.wave_work(self.config, pages, rows))
        for t, n in self.taps.prefills:
            if a <= t < b:
                work["prefill_ktok"] += n / 1000.0
                add(self.costs.prefill_work(self.config, n))
        # A cost module without ``resume_work`` prices no resume.
        resume_work = getattr(self.costs, "resume_work", None)
        for t, pages, rows in self.taps.resumes if resume_work else ():
            if a <= t < b:
                add(resume_work(self.config, pages, rows))
        trace["work"] = work
        return trace


def execute(args, cell, config, plan, device, program_counters=()):
    """Server, set-up, window, checks: everything after the device is known.
    Returns the result line (without metrics), the window's results and the
    reduced trace. The server is sized from the file's ``serving`` alone
    (nothing is built yet); ``build`` holds those numbers to the caches."""
    import jax

    import infinistore_tpu as its

    # The server's allocation unit and what a block takes of its pool in
    # whole units, from the file alone: a file that cannot start a server
    # stops here. The pool holds the whole plan's working set, at that
    # weight, below the server's on-demand eviction threshold (0.8 of the
    # pool), so nothing is evicted.
    layout = store_layout(config["serving"])
    pool = pool_gib(traffic.store_bytes(plan, layout.pool_bytes_per_token))
    compiles = Compiles()
    run = CellRun(args, cell, config, plan, program_counters)
    # A file that names ``program.choices`` without a reference that follows
    # them, or the reverse, stops here too.
    run.reference, run.choices = reference_and_choices(config["program"])
    if args.trace:
        # The program's own spans, in traced runs only: end-to-end runs
        # never carry the recorder.
        tracing.configure(enabled=True, capacity=SPAN_CAPACITY)
    server = start_server(pool, layout.unit_kib)
    conn = None
    try:
        conn = its.InfinityConnection(its.ClientConfig(
            host_addr="127.0.0.1", service_port=server["service_port"], log_level="error",
        ))
        conn.connect()
        run.build(conn, compiles)

        async def whole():
            await run.run()
            stats = jax.devices()[0].memory_stats() or {}
            peak = stats.get("peak_bytes_in_use", 0)
            await run.check()
            return peak

        peak_bytes = asyncio.run(whole())
        res = run.results(run.t_open - T_PROCESS, peak_bytes)
        trace = run.trace_results(res["spans"])
    finally:
        if conn is not None:
            conn.close()
        stop_server(server)
        if args.trace:
            tracing.configure(enabled=False)

    if res["counters"]["window_compiles"]:
        run.failed_checks.append(
            f"{res['counters']['window_compiles']} compilations inside the window"
        )
    if run.ran_dry:
        run.failed_checks.append("the traffic's lists ran dry before the window closed")
    if res["spans"] and res["spans"]["dropped"]:
        run.failed_checks.append(f"the recorder dropped {res['spans']['dropped']} spans")
    if "store_unit_kib" in config["serving"] and not (
        run.pool_usage is not None and run.pool_usage < POOL_EVICTS_FROM
    ):
        run.failed_checks.append(
            f"the server's pool is {run.pool_usage} used at the close of the run, and it evicts "
            f"on demand from {POOL_EVICTS_FROM}: the pool of {pool} GiB does not hold what "
            f"the run saved at {layout.pool_units_per_block} units of {layout.unit_kib} KiB a block"
        )
    for check in run.failed_checks:
        print(f"not correct: {check}", file=sys.stderr, flush=True)
    line = {
        "correct": not run.failed_checks and res["failed"] == 0,
        "attempted": res["attempted"], "failed": res["failed"],
        "metrics": {}, "device": dict(device, memory_peak_bytes=peak_bytes),
        "workload": cell["name"], "seed": args.seed, "seconds": args.seconds,
        "server": {
            "block_kib": layout.block_kib, "pool_gib": pool, "unit_kib": layout.unit_kib,
            "pool_units_per_block": layout.pool_units_per_block, "pool_usage": run.pool_usage,
        },
        # Every number the checks compared with the reference, beside its
        # limit: ``main`` keeps this key the line's last.
        "compared": run.compared,
    }
    return line, res, trace


def detail(args, cell, line, res, layer, trace=None):
    """Everything this run could read, for whoever studies a run: every
    end-to-end metric and every per-layer metric whatever ``--trace`` says,
    and of a traced run the work and the device's tables by operation and
    program, in ``.bench_out/`` of the checkout; with the recorder on, beside it every
    span it held and the idle table by phase (install, save_snapshot,
    compute and the rest, which are no metrics). The driver reads only the
    line."""
    out = os.path.join(REPO, ".bench_out")
    os.makedirs(out, exist_ok=True)
    name = f"{cell['name']}.seed{args.seed}.trace{args.trace}"
    with open(os.path.join(out, f"{name}.{int(time.time())}.json"), "w") as f:
        json.dump({
            "line": line, "end_to_end": res["end_to_end"], "per_layer": layer,
            "counters": res["counters"], "rows": res["rows"],
            "trace": trace and {k: trace[k] for k in ("work", "ops", "modules")},
        }, f)
    if res["spans"]:
        held = {k: v for k, v in res["spans"].items() if k != "index"}
        with open(os.path.join(out, f"recorder.{name}.json"), "w") as f:
            json.dump(held, f)


def device_line(jax) -> Dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell, config = cell_of(bench, args.workload)
    import jax

    device = device_line(jax)
    if device["platform"] != "tpu" or device["count"] < cell["chips"]:
        fail(
            f"JAX found platform {device['platform']!r} with {device['count']} device(s); "
            f"cell {cell['name']} needs {cell['chips']} TPU chip(s). Nothing was measured."
        )
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if device["kind"] not in peaks:
        fail(f"no peaks for device kind {device['kind']!r} in benchmarks/peaks.json")

    from infinistore_tpu import compile_cache

    compile_cache.enable()
    # Small programs too: the store path runs dozens of sub-second ones, and
    # a warm run should find every one of them in the cache.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    build_native_if_missing()
    plan = traffic.build_plan(cell["traffic"])
    per_layer = metrics_for(bench, "per_layer", cell["name"])
    named = readers.counter_keys(m["name"] for m in per_layer) - OWN_COUNTERS
    line, res, trace = execute(args, cell, config, plan, device, named)
    view = readers.Run(
        res["rows"], res["counters"], trace, peaks[device["kind"]], spans=res["spans"]
    )
    layer = {m["name"]: readers.read_layer_metric(m["name"], view) for m in per_layer}
    if args.trace:
        for m in per_layer:
            if layer[m["name"]] is not None:
                line["metrics"][m["name"]] = {"value": layer[m["name"]], "unit": m["unit"]}
        line["device"].update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        spans = res["spans"]
        line["spans"] = {
            "recorded": spans["recorded"], "dropped": spans["dropped"], **(spans["profile"] or {}),
        }
        line["breakdown"] = {
            "device_ops": trace_reduce.top(trace["ops"]),
            "idle_gaps": trace_reduce.top(trace["idle_gaps"]),
        }
    else:
        for m in metrics_for(bench, "end_to_end", cell["name"]):
            if m["name"] in res["end_to_end"]:
                line["metrics"][m["name"]] = {
                    "value": res["end_to_end"][m["name"]], "unit": m["unit"],
                }
    line["compared"] = line.pop("compared")  # last in the line, and stderr's last lines
    detail(args, cell, line, res, layer, trace)
    for c in line["compared"]:
        print("compared " + ", ".join(f"{k} {v}" for k, v in c.items()), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
