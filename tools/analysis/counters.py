"""ITS-C*: stat-counter consistency across the observability surfaces.

A counter that exists in the data plane but never reaches an exporter is
observability drift: the operator dashboards silently stop seeing what
the code started counting (the reference ships no metrics at all;
SURVEY.md §5.1 made this a first-class goal here). This pass extracts:

- the native server's ``stats_json()`` key tree (native/src/server.cpp) —
  the source of truth the manage plane re-serves,
- the keys the manage plane's Prometheus exporter
  (``server.py _prometheus_text``) actually consumes,
- the client-side Python ledgers' keys (``qos_stats``,
  ``completion_stats``, ``data_plane_stats``, cluster ``health``/
  ``as_dict``),
- the documented vocabulary of docs/api_reference.md,

and cross-checks them:

- ITS-C001 native stats_json key not consumed by the /metrics exporter
- ITS-C002 /metrics consumes a key the native stats_json no longer emits
  (a runtime KeyError waiting for the next scrape)
- ITS-C003 counter key absent from docs/api_reference.md
- ITS-C004 manage plane no longer serves /stats verbatim from
  get_server_stats
- ITS-C005 membership/reshard counter drift: every ``membership_*`` /
  ``reshard_*`` key of the elastic-membership status snapshot
  (``Membership.status`` + ``Resharder.progress``/``__init__`` ledgers)
  must be consumed by the /metrics membership exporter
  (``server.py _membership_prometheus_lines``) — and the exporter must
  not consume keys the snapshot no longer emits (KeyError at scrape
  time); the manage plane must keep serving GET/POST ``/membership``
  from ``membership_status``.
- ITS-C006 fleet-telemetry vocabulary drift (docs/observability.md):
  every ``slo_*`` key of ``telemetry.SloEngine.status`` must be consumed
  by the /metrics SLO exporter (``server.py _slo_prometheus_lines``) and
  documented; every event kind a producer ``emit()``s must be in
  ``telemetry.EVENT_KINDS``, every kind must keep at least one producer
  and a docs row; and the manage plane must keep serving ``/slo`` and
  ``/events``.
- ITS-C007 tiered-capacity-plane vocabulary drift (docs/tiering.md):
  every ``tier_*`` key of ``tiering.TierManager.status`` must be
  consumed by the /metrics tier exporter
  (``server.py _tier_prometheus_lines``) and enumerated in
  docs/tiering.md — and the exporter must not consume keys the snapshot
  no longer emits; the manage plane must keep serving ``GET /tiers``
  from the TierManager status.
- ITS-C008 continuous-profiling / metrics-history vocabulary drift
  (docs/observability.md): every ``prof_*`` key of
  ``profiling.SamplingProfiler.status`` must be consumed by the /metrics
  profiler exporter (``server.py _prof_prometheus_lines``) and every
  ``timeseries_*`` key of ``telemetry.MetricsHistory.status`` by the
  /metrics history exporter (``_timeseries_prometheus_lines``), both
  directions, and both vocabularies documented; the manage plane must
  keep serving ``GET /profile`` from the process profiler and ``GET
  /timeseries`` from the metrics history.

- ITS-C009 disaggregated-handoff vocabulary drift
  (docs/disaggregation.md): every ``disagg_*`` key of the
  ``disagg.DisaggCounters`` ledger (``__init__`` literal + ``status``
  snapshot) must be consumed by the /metrics disagg exporter
  (``server.py _disagg_prometheus_lines``) and enumerated in
  docs/disaggregation.md — and the exporter must not consume keys the
  snapshot no longer emits; the manage plane must keep serving ``GET
  /disagg`` from the process disagg counters.

Dynamic per-op entries (``"ops": {"W": {...}}``) appear as ``ops.*`` on
both sides.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from .core import Context, Finding, register

SERVER_CPP_REL = "native/src/server.cpp"
MANAGE_REL = "infinistore_tpu/server.py"
DOCS_REL = "docs/api_reference.md"

# Client-side counter ledgers: (file, dotted function path). Keys are read
# from returned/assigned dict literals and subscript stores inside them.
LEDGERS: List[Tuple[str, str]] = [
    ("infinistore_tpu/lib.py", "InfinityConnection.qos_stats"),
    ("infinistore_tpu/lib.py", "InfinityConnection.completion_stats"),
    ("infinistore_tpu/lib.py", "InfinityConnection.ring_stats"),
    ("infinistore_tpu/lib.py", "StripedConnection.ring_stats"),
    ("infinistore_tpu/lib.py", "StripedConnection.data_plane_stats"),
    ("infinistore_tpu/lib.py", "StripedConnection.completion_stats"),
    ("infinistore_tpu/cluster.py", "_MemberHealth.as_dict"),
    ("infinistore_tpu/cluster.py", "ClusterKVConnector.health"),
    ("infinistore_tpu/engine.py", "ContinuousBatchingHarness.metrics"),
    ("infinistore_tpu/membership.py", "Membership.status"),
    ("infinistore_tpu/membership.py", "Resharder.progress"),
    ("infinistore_tpu/membership.py", "DurableLog.status"),
    ("infinistore_tpu/telemetry.py", "GossipAgent.status"),
    ("infinistore_tpu/tiering.py", "TierManager.__init__"),
    ("infinistore_tpu/tiering.py", "TierManager.status"),
    ("infinistore_tpu/profiling.py", "SamplingProfiler.status"),
    ("infinistore_tpu/telemetry.py", "MetricsHistory.status"),
    ("infinistore_tpu/disagg.py", "DisaggCounters.__init__"),
    ("infinistore_tpu/disagg.py", "DisaggCounters.status"),
]

# The elastic-membership status snapshot (ITS-C005): the dict-literal
# ledgers whose union is the membership_*/reshard_* key vocabulary, and
# the /metrics exporter function that must consume all of it.
MEMBERSHIP_REL = "infinistore_tpu/membership.py"
MEMBERSHIP_LEDGERS: List[str] = [
    "Membership.status",
    "Resharder.__init__",  # the reshard_* counter dict literal
    "Resharder.progress",
    "DurableLog.status",   # the journal_* durability counters
]
MEMBERSHIP_EXPORT_FN = "_membership_prometheus_lines"

# The fleet telemetry plane (ITS-C006, docs/observability.md): the SLO
# status ledger whose ``slo_*`` keys must reach the /metrics SLO exporter,
# the event-kind vocabulary every ``emit()`` producer must draw from (and
# every kind of which must have a producer and a docs row), and the manage
# routes that must keep serving them.
TELEMETRY_REL = "infinistore_tpu/telemetry.py"
TELEMETRY_SLO_LEDGER = "SloEngine.status"
SLO_EXPORT_FN = "_slo_prometheus_lines"
# The gossip anti-entropy agent (docs/membership.md, gossip section): its
# gossip_* status vocabulary must reach the /metrics gossip exporter both
# ways, same discipline as the SLO keys.
TELEMETRY_GOSSIP_LEDGER = "GossipAgent.status"
GOSSIP_EXPORT_FN = "_gossip_prometheus_lines"
TELEMETRY_DOCS_REL = "docs/observability.md"
TELEMETRY_PACKAGE_REL = "infinistore_tpu"

# The tiered capacity plane (ITS-C007, docs/tiering.md): the TierManager
# status ledger whose ``tier_*`` keys must reach the /metrics tier exporter
# both ways, be enumerated in the tiering docs, and keep the /tiers route.
TIERING_REL = "infinistore_tpu/tiering.py"
TIERING_LEDGERS = ["TierManager.__init__", "TierManager.status"]
TIER_EXPORT_FN = "_tier_prometheus_lines"
TIERING_DOCS_REL = "docs/tiering.md"

# The continuous-profiling + metrics-history plane (ITS-C008,
# docs/observability.md): the sampling profiler's ``prof_*`` and the
# metrics history's ``timeseries_*`` status vocabularies must reach their
# /metrics exporters both ways, be documented, and keep the ``/profile``
# and ``/timeseries`` manage routes.
PROFILING_REL = "infinistore_tpu/profiling.py"
PROFILING_LEDGERS = ["SamplingProfiler.status"]
PROF_EXPORT_FN = "_prof_prometheus_lines"
TIMESERIES_LEDGERS = ["MetricsHistory.status"]
TIMESERIES_EXPORT_FN = "_timeseries_prometheus_lines"

# The disaggregated prefill->decode handoff plane (ITS-C009,
# docs/disaggregation.md): the DisaggCounters ledger's ``disagg_*`` keys
# must reach the /metrics disagg exporter both ways, be enumerated in the
# disaggregation docs, and keep the /disagg manage route.
DISAGG_REL = "infinistore_tpu/disagg.py"
DISAGG_LEDGERS = ["DisaggCounters.__init__", "DisaggCounters.status"]
DISAGG_EXPORT_FN = "_disagg_prometheus_lines"
DISAGG_DOCS_REL = "docs/disaggregation.md"

# Trace-surface exporters (docs/observability.md): the /trace payload
# builder consumes the native ring's counters from the stats snapshot, and
# tracing.server_tick_spans consumes every per-entry tick field (its first
# argument IS the snapshot's "trace" subtree — hence the prefix). Their
# consumption unions with /metrics for the ITS-C001/C002 cross-checks:
# trace ticks reach dashboards through GET /trace, not a scrape.
TRACE_EXPORTERS: List[Tuple[str, str, str]] = [
    ("infinistore_tpu/server.py", "_trace_payload", ""),
    ("infinistore_tpu/tracing.py", "server_tick_spans", "trace"),
]


# ---------------------------------------------------------------------------
# Native side: reconstruct the stats_json() key tree from the C++ string
# concatenation. All string literals in the function body, concatenated in
# order, form a JSON skeleton ({"kvmap_len":,"spill":{...}}...); dynamic
# segments (the per-op keys) collapse to empty names, reported as "*".
# ---------------------------------------------------------------------------

_STR_LIT = re.compile(r'"((?:[^"\\]|\\.)*)"')


def native_stats_keys(ctx: Context, rel: str = SERVER_CPP_REL) -> Set[str]:
    src = ctx.read(rel)
    m = re.search(r"std::string\s+\w+::stats_json\s*\(\)\s*\{", src)
    if not m:
        return set()
    depth, end = 0, len(src)
    for j in range(m.end() - 1, len(src)):
        if src[j] == "{":
            depth += 1
        elif src[j] == "}":
            depth -= 1
            if depth == 0:
                end = j
                break
    body = src[m.end(): end]
    skeleton = "".join(
        lit.replace('\\"', '"') for lit in _STR_LIT.findall(body)
    )
    return _skeleton_keys(skeleton)


def _skeleton_keys(skeleton: str) -> Set[str]:
    keys: Set[str] = set()
    stack: List[Optional[str]] = []
    pending: Optional[str] = None
    i = 0
    while i < len(skeleton):
        c = skeleton[i]
        if c == '"':
            j = skeleton.find('"', i + 1)
            if j < 0:
                break
            name = skeleton[i + 1: j]
            if j + 1 < len(skeleton) and skeleton[j + 1] == ":":
                pending = name or "*"
                i = j + 2
                continue
            i = j + 1
            continue
        if c == "{":
            stack.append(pending)
            pending = None
            i += 1
            continue
        if c == "[":
            # Array value: the key itself is a leaf (exporters consume the
            # list), and objects INSIDE it contribute keys under
            # ``<key>.*`` (e.g. trace.entries.*.recv_us).
            if pending is not None:
                keys.add(".".join([s for s in stack if s] + [pending]))
                stack.append(pending + ".*")
            else:
                stack.append(None)
            pending = None
            i += 1
            continue
        if pending is not None and c not in " \t\n":
            # A leaf value begins (or the literal skeleton jumps straight
            # to the closing brace around a dynamic value): record the
            # dotted path BEFORE any '}' pops the enclosing group, then
            # re-examine the same character.
            keys.add(".".join([s for s in stack if s] + [pending]))
            pending = None
            continue
        if c in "}]" and stack:
            stack.pop()
        i += 1
    return keys


# ---------------------------------------------------------------------------
# Exporter side: keys _prometheus_text consumes from the stats snapshot.
# ---------------------------------------------------------------------------

def metrics_consumed_keys(ctx: Context, rel: str = MANAGE_REL,
                          fn_name: str = "_prometheus_text",
                          prefix: str = "") -> Set[str]:
    """Stats keys the named exporter function consumes (literal subscripts
    and .get()s reachable from its first argument). ``prefix`` roots the
    first argument at a subtree of the stats snapshot — e.g.
    ``tracing.server_tick_spans(server_trace)`` consumes under ``trace``."""
    tree = ast.parse(ctx.read(rel))
    fn = next(
        (
            node for node in ast.walk(tree)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            and node.name == fn_name
        ),
        None,
    )
    if fn is None:
        return set()
    arg0 = fn.args.args[0].arg if fn.args.args else "stats"
    ctx_of: Dict[str, str] = {arg0: prefix}
    consumed: Set[str] = set()

    def sub_key(node) -> Optional[Tuple[str, str]]:
        """(var, key) for NAME["key"] / NAME.get("key", ...)"""
        if (
            isinstance(node, ast.Subscript)
            and isinstance(node.value, ast.Name)
            and isinstance(node.slice, ast.Constant)
            and isinstance(node.slice.value, str)
        ):
            return node.value.id, node.slice.value
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "get"
            and isinstance(node.func.value, ast.Name)
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            return node.func.value.id, node.args[0].value
        return None

    def path_of(var: str, key: str) -> Optional[str]:
        if var not in ctx_of:
            return None
        prefix = ctx_of[var]
        return f"{prefix}.{key}" if prefix else key

    # Pass 1: context assignments (spill = stats.get("spill", {})) and loop
    # targets over a contexted iterable (for op, s in ops: -> s is ops.*).
    for node in ast.walk(fn):
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
        ):
            refs = []
            for sub in ast.walk(node.value):
                sk = sub_key(sub)
                if sk is not None and sk[0] in ctx_of:
                    refs.append(sk)
            if len(refs) == 1:
                p = path_of(*refs[0])
                if p is not None:
                    ctx_of[node.targets[0].id] = p
        if isinstance(node, ast.For):
            iter_names = {
                n.id for n in ast.walk(node.iter) if isinstance(n, ast.Name)
            }
            hit = sorted(v for v in iter_names if ctx_of.get(v))
            if hit:
                prefix = ctx_of[hit[0]] + ".*"
                targets = (
                    node.target.elts if isinstance(node.target, ast.Tuple)
                    else [node.target]
                )
                for t in targets:
                    if isinstance(t, ast.Name):
                        ctx_of.setdefault(t.id, prefix)

    # Pass 2: consumptions.
    for node in ast.walk(fn):
        sk = sub_key(node)
        if sk is not None:
            p = path_of(*sk)
            if p is not None:
                consumed.add(p)
    return consumed


# ---------------------------------------------------------------------------
# Client-side Python ledgers.
# ---------------------------------------------------------------------------

def _find_fn(tree: ast.Module, dotted: str):
    parts = dotted.split(".")
    scope, node = tree.body, None
    for part in parts:
        node = next(
            (
                n for n in scope
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef))
                and n.name == part
            ),
            None,
        )
        if node is None:
            return None
        scope = node.body
    return node


def _dict_keys(node: ast.Dict, prefix: str = "") -> Set[str]:
    out: Set[str] = set()
    for k, v in zip(node.keys, node.values):
        if isinstance(k, ast.Constant) and isinstance(k.value, str):
            path = f"{prefix}.{k.value}" if prefix else k.value
            if isinstance(v, ast.Dict):
                out |= _dict_keys(v, path)
            else:
                out.add(path)
    return out


def ledger_keys(ctx: Context, rel: str, dotted: str) -> Tuple[Set[str], int]:
    tree = ast.parse(ctx.read(rel))
    fn = _find_fn(tree, dotted)
    if fn is None:
        return set(), 0
    keys: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Dict):
            keys |= _dict_keys(node)
        if (
            isinstance(node, ast.Assign)
            and isinstance(node.targets[0], ast.Subscript)
            and isinstance(node.targets[0].slice, ast.Constant)
            and isinstance(node.targets[0].slice.value, str)
        ):
            keys.add(node.targets[0].slice.value)
    return keys, fn.lineno


# ---------------------------------------------------------------------------
# Checks.
# ---------------------------------------------------------------------------

def scan(
    ctx: Context,
    server_cpp_rel: str = SERVER_CPP_REL,
    manage_rel: str = MANAGE_REL,
    docs_rel: str = DOCS_REL,
    ledgers: Optional[List[Tuple[str, str]]] = None,
) -> List[Finding]:
    ledgers = LEDGERS if ledgers is None else ledgers
    findings: List[Finding] = []
    native = native_stats_keys(ctx, server_cpp_rel)
    consumed = metrics_consumed_keys(ctx, manage_rel)
    for rel, fn_name, prefix in TRACE_EXPORTERS:
        if ctx.exists(rel):
            consumed |= metrics_consumed_keys(
                ctx, rel, fn_name=fn_name, prefix=prefix
            )
    docs = ctx.read(docs_rel) if ctx.exists(docs_rel) else ""
    doc_words = set(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", docs))

    for key in sorted(native - consumed):
        findings.append(Finding(
            rule="ITS-C001", file=manage_rel, line=1,
            message=f"native stats_json key {key!r} is not exported by the "
                    "/metrics exporter (_prometheus_text) — silent "
                    "observability drift",
            key=f"ITS-C001:{manage_rel}:{key}",
        ))
    def is_container(key: str) -> bool:
        return any(n.startswith(key + ".") for n in native)

    for key in sorted(k for k in consumed - native if not is_container(k)):
        findings.append(Finding(
            rule="ITS-C002", file=manage_rel, line=1,
            message=f"/metrics consumes stats key {key!r} which the native "
                    "stats_json no longer emits (KeyError at scrape time)",
            key=f"ITS-C002:{manage_rel}:{key}",
        ))

    def doc_check(key: str, origin: str, file: str, line: int):
        leaf = key.rsplit(".", 1)[-1]
        if leaf == "*" or leaf in doc_words:
            return
        findings.append(Finding(
            rule="ITS-C003", file=file, line=line,
            message=f"counter key {key!r} ({origin}) is undocumented in "
                    f"{docs_rel} — enumerate it in its accessor's docstring "
                    "and regenerate the reference (tools/gen_api_docs.py)",
            key=f"ITS-C003:{file}:{origin}:{key}",
        ))

    for key in sorted(native):
        doc_check(key, "server stats_json", server_cpp_rel, 1)
    for rel, dotted in ledgers:
        keys, lineno = ledger_keys(ctx, rel, dotted)
        for key in sorted(keys):
            doc_check(key, dotted, rel, lineno)

    manage_src = ctx.read(manage_rel)
    if (
        not re.search(r'[\'"]/stats[\'"]', manage_src)
        or "get_server_stats" not in manage_src
    ):
        findings.append(Finding(
            rule="ITS-C004", file=manage_rel, line=1,
            message="manage plane must serve GET /stats verbatim from "
                    "get_server_stats (the raw counter surface /metrics "
                    "summarizes)",
            key=f"ITS-C004:{manage_rel}:stats-route",
        ))
    findings += _scan_membership(ctx, manage_rel, MEMBERSHIP_REL)
    findings += _scan_telemetry(ctx, manage_rel)
    findings += _scan_tiering(ctx, manage_rel)
    findings += _scan_profiling(ctx, manage_rel)
    findings += _scan_disagg(ctx, manage_rel)
    return findings


def _scan_disagg(
    ctx: Context,
    manage_rel: str = MANAGE_REL,
    disagg_rel: str = DISAGG_REL,
    docs_rel: str = DISAGG_DOCS_REL,
) -> List[Finding]:
    """ITS-C009: the disaggregated-handoff vocabulary in lockstep —
    ``disagg_*`` ledger keys vs the /metrics disagg exporter (both
    directions), the disaggregation docs, and the /disagg manage route
    (docs/disaggregation.md)."""
    findings: List[Finding] = []
    if not ctx.exists(disagg_rel):
        return findings
    docs = ctx.read(docs_rel) if ctx.exists(docs_rel) else ""
    doc_words = set(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", docs))

    ledger_key_set: Set[str] = set()
    ledger_line = 1
    for dotted in DISAGG_LEDGERS:
        keys, line = ledger_keys(ctx, disagg_rel, dotted)
        ledger_key_set |= {k.rsplit(".", 1)[-1] for k in keys}
        ledger_line = line or ledger_line
    ledger_key_set = {k for k in ledger_key_set if k.startswith("disagg_")}
    consumed = {
        k for k in metrics_consumed_keys(
            ctx, manage_rel, fn_name=DISAGG_EXPORT_FN
        )
        if k.startswith("disagg_")
    }
    for key in sorted(ledger_key_set - consumed):
        findings.append(Finding(
            rule="ITS-C009", file=manage_rel, line=1,
            message=f"disagg counter key {key!r} is not exported by the "
                    f"/metrics disagg exporter ({DISAGG_EXPORT_FN}) — a "
                    "handoff counter dashboards cannot see is observability "
                    "drift (docs/disaggregation.md)",
            key=f"ITS-C009:{manage_rel}:{key}",
        ))
    for key in sorted(consumed - ledger_key_set):
        findings.append(Finding(
            rule="ITS-C009", file=manage_rel, line=1,
            message=f"/metrics disagg exporter consumes key {key!r} which "
                    "the DisaggCounters snapshot no longer emits (KeyError "
                    "at scrape time)",
            key=f"ITS-C009:{manage_rel}:stale:{key}",
        ))
    for key in sorted(ledger_key_set):
        if key not in doc_words:
            findings.append(Finding(
                rule="ITS-C009", file=disagg_rel, line=ledger_line,
                message=f"disagg counter key {key!r} is undocumented in "
                        f"{docs_rel} — the handoff counter vocabulary table "
                        "must enumerate it",
                key=f"ITS-C009:{disagg_rel}:undocumented:{key}",
            ))
    manage_src = ctx.read(manage_rel)
    if (
        not re.search(r'[\'"]/disagg[\'"]', manage_src)
        or "_disagg_status" not in manage_src
    ):
        findings.append(Finding(
            rule="ITS-C009", file=manage_rel, line=1,
            message="manage plane must serve GET /disagg from the process "
                    "disagg counters — the prefill->decode handoff surface "
                    "(docs/disaggregation.md)",
            key=f"ITS-C009:{manage_rel}:disagg-route",
        ))
    return findings


def _scan_profiling(
    ctx: Context,
    manage_rel: str = MANAGE_REL,
    profiling_rel: str = PROFILING_REL,
    telemetry_rel: str = TELEMETRY_REL,
    docs_rel: str = TELEMETRY_DOCS_REL,
) -> List[Finding]:
    """ITS-C008: the continuous-profiling + metrics-history vocabulary in
    lockstep — ``prof_*`` status keys vs the /metrics profiler exporter,
    ``timeseries_*`` status keys vs the /metrics history exporter (both
    directions each), the observability docs, and the ``/profile`` +
    ``/timeseries`` manage routes (docs/observability.md)."""
    findings: List[Finding] = []
    if not ctx.exists(profiling_rel):
        return findings
    docs = ctx.read(docs_rel) if ctx.exists(docs_rel) else ""
    doc_words = set(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", docs))

    def vocabulary(rel: str, ledgers: List[str], prefix: str):
        keys: Set[str] = set()
        line = 1
        for dotted in ledgers:
            got, ln = ledger_keys(ctx, rel, dotted)
            keys |= {k.rsplit(".", 1)[-1] for k in got}
            line = ln or line
        return {k for k in keys if k.startswith(prefix)}, line

    def lockstep(keys: Set[str], line: int, rel: str, export_fn: str,
                 prefix: str, tag: str):
        consumed = {
            k for k in metrics_consumed_keys(ctx, manage_rel,
                                             fn_name=export_fn)
            if k.startswith(prefix)
        }
        for key in sorted(keys - consumed):
            findings.append(Finding(
                rule="ITS-C008", file=manage_rel, line=1,
                message=f"{tag} status key {key!r} is not exported by the "
                        f"/metrics exporter ({export_fn}) — profiling "
                        "coverage dashboards cannot see is observability "
                        "drift (docs/observability.md)",
                key=f"ITS-C008:{manage_rel}:{tag}:{key}",
            ))
        for key in sorted(consumed - keys):
            findings.append(Finding(
                rule="ITS-C008", file=manage_rel, line=1,
                message=f"/metrics exporter {export_fn} consumes key "
                        f"{key!r} which the {tag} status snapshot no "
                        "longer emits (KeyError at scrape time)",
                key=f"ITS-C008:{manage_rel}:{tag}-stale:{key}",
            ))
        for key in sorted(keys):
            if key not in doc_words:
                findings.append(Finding(
                    rule="ITS-C008", file=rel, line=line,
                    message=f"{tag} status key {key!r} is undocumented in "
                            f"{docs_rel} — the {tag} vocabulary table must "
                            "enumerate it",
                    key=f"ITS-C008:{rel}:undocumented:{key}",
                ))

    prof_keys, prof_line = vocabulary(profiling_rel, PROFILING_LEDGERS,
                                      "prof_")
    lockstep(prof_keys, prof_line, profiling_rel, PROF_EXPORT_FN,
             "prof_", "prof")
    if ctx.exists(telemetry_rel):
        ts_keys, ts_line = vocabulary(telemetry_rel, TIMESERIES_LEDGERS,
                                      "timeseries_")
        if ts_keys:
            lockstep(ts_keys, ts_line, telemetry_rel, TIMESERIES_EXPORT_FN,
                     "timeseries_", "timeseries")

    manage_src = ctx.read(manage_rel)
    if (
        not re.search(r'[\'"]/profile[\'"]', manage_src)
        or "profiling" not in manage_src
    ):
        findings.append(Finding(
            rule="ITS-C008", file=manage_rel, line=1,
            message="manage plane must serve GET /profile from the process "
                    "sampling profiler — the frame-level attribution "
                    "surface (docs/observability.md)",
            key=f"ITS-C008:{manage_rel}:profile-route",
        ))
    if (
        not re.search(r'[\'"]/timeseries[\'"]', manage_src)
        or "history" not in manage_src
    ):
        findings.append(Finding(
            rule="ITS-C008", file=manage_rel, line=1,
            message="manage plane must serve GET /timeseries from the "
                    "metrics history — the trend/sparkline surface "
                    "(docs/observability.md)",
            key=f"ITS-C008:{manage_rel}:timeseries-route",
        ))
    return findings


def _scan_tiering(
    ctx: Context,
    manage_rel: str = MANAGE_REL,
    tiering_rel: str = TIERING_REL,
    docs_rel: str = TIERING_DOCS_REL,
) -> List[Finding]:
    """ITS-C007: the tiered-capacity-plane vocabulary in lockstep —
    ``tier_*`` status keys vs the /metrics tier exporter (both
    directions), the tiering docs, and the /tiers manage route
    (docs/tiering.md)."""
    findings: List[Finding] = []
    if not ctx.exists(tiering_rel):
        return findings
    docs = ctx.read(docs_rel) if ctx.exists(docs_rel) else ""
    doc_words = set(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", docs))

    status_keys: Set[str] = set()
    status_line = 1
    for dotted in TIERING_LEDGERS:
        keys, line = ledger_keys(ctx, tiering_rel, dotted)
        status_keys |= {k.rsplit(".", 1)[-1] for k in keys}
        status_line = line or status_line
    status_keys = {k for k in status_keys if k.startswith("tier_")}
    consumed = {
        k for k in metrics_consumed_keys(
            ctx, manage_rel, fn_name=TIER_EXPORT_FN
        )
        if k.startswith("tier_")
    }
    for key in sorted(status_keys - consumed):
        findings.append(Finding(
            rule="ITS-C007", file=manage_rel, line=1,
            message=f"tier status key {key!r} is not exported by the "
                    f"/metrics tier exporter ({TIER_EXPORT_FN}) — a "
                    "capacity tier dashboards cannot see is observability "
                    "drift (docs/tiering.md)",
            key=f"ITS-C007:{manage_rel}:{key}",
        ))
    for key in sorted(consumed - status_keys):
        findings.append(Finding(
            rule="ITS-C007", file=manage_rel, line=1,
            message=f"/metrics tier exporter consumes key {key!r} which "
                    "the TierManager status snapshot no longer emits "
                    "(KeyError at scrape time)",
            key=f"ITS-C007:{manage_rel}:stale:{key}",
        ))
    for key in sorted(status_keys):
        if key not in doc_words:
            findings.append(Finding(
                rule="ITS-C007", file=tiering_rel, line=status_line,
                message=f"tier status key {key!r} is undocumented in "
                        f"{docs_rel} — the tier counter vocabulary table "
                        "must enumerate it",
                key=f"ITS-C007:{tiering_rel}:undocumented:{key}",
            ))
    manage_src = ctx.read(manage_rel)
    if (
        not re.search(r'[\'"]/tiers[\'"]', manage_src)
        or "tiering" not in manage_src
    ):
        findings.append(Finding(
            rule="ITS-C007", file=manage_rel, line=1,
            message="manage plane must serve GET /tiers from the cluster's "
                    "TierManager status — the tiered-capacity-plane "
                    "surface (docs/tiering.md)",
            key=f"ITS-C007:{manage_rel}:tiers-route",
        ))
    return findings


def _event_kinds(ctx: Context, telemetry_rel: str) -> List[str]:
    """The EVENT_KINDS tuple literal of the telemetry module."""
    tree = ast.parse(ctx.read(telemetry_rel))
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == "EVENT_KINDS"
            and isinstance(node.value, (ast.Tuple, ast.List))
        ):
            return [
                e.value for e in node.value.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            ]
    return []


def _emit_producers(ctx: Context, package_rel: str) -> List[Tuple[str, int, str]]:
    """Every ``emit("<kind literal>", ...)`` call site in the package —
    ``telemetry.emit``, ``journal.emit`` and the bare imported name all
    count: the first positional string IS the producer's kind claim."""
    out: List[Tuple[str, int, str]] = []
    for rel in ctx.walk_py(package_rel):
        try:
            tree = ast.parse(ctx.read(rel))
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            fn = node.func
            name = (
                fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name) else ""
            )
            if name != "emit":
                continue
            arg0 = node.args[0]
            if isinstance(arg0, ast.Constant) and isinstance(arg0.value, str):
                out.append((rel, node.lineno, arg0.value))
    return out


def _scan_telemetry(
    ctx: Context,
    manage_rel: str = MANAGE_REL,
    telemetry_rel: str = TELEMETRY_REL,
    docs_rel: str = TELEMETRY_DOCS_REL,
    package_rel: str = TELEMETRY_PACKAGE_REL,
) -> List[Finding]:
    """ITS-C006: the fleet-telemetry vocabulary in lockstep — ``slo_*``
    status keys vs the /metrics SLO exporter and the fleet docs, event
    kinds vs their producers and the fleet docs, and the /slo + /events
    manage routes (docs/observability.md, fleet section)."""
    findings: List[Finding] = []
    if not ctx.exists(telemetry_rel):
        return findings
    docs = ctx.read(docs_rel) if ctx.exists(docs_rel) else ""
    doc_words = set(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", docs))

    # -- slo_* status keys vs the exporter + docs ---------------------------
    status_keys, status_line = ledger_keys(
        ctx, telemetry_rel, TELEMETRY_SLO_LEDGER
    )
    status_keys = {k.rsplit(".", 1)[-1] for k in status_keys}
    status_keys = {k for k in status_keys if k.startswith("slo_")}
    consumed = {
        k for k in metrics_consumed_keys(ctx, manage_rel, fn_name=SLO_EXPORT_FN)
        if k.startswith("slo_")
    }
    for key in sorted(status_keys - consumed):
        findings.append(Finding(
            rule="ITS-C006", file=manage_rel, line=1,
            message=f"SLO status key {key!r} is not exported by the /metrics "
                    f"SLO exporter ({SLO_EXPORT_FN}) — an SLI dashboards "
                    "cannot see is observability drift "
                    "(docs/observability.md)",
            key=f"ITS-C006:{manage_rel}:{key}",
        ))
    for key in sorted(consumed - status_keys):
        findings.append(Finding(
            rule="ITS-C006", file=manage_rel, line=1,
            message=f"/metrics SLO exporter consumes key {key!r} which "
                    f"{TELEMETRY_SLO_LEDGER} no longer emits (KeyError at "
                    "scrape time)",
            key=f"ITS-C006:{manage_rel}:stale:{key}",
        ))
    for key in sorted(status_keys):
        if key not in doc_words:
            findings.append(Finding(
                rule="ITS-C006", file=telemetry_rel, line=status_line,
                message=f"SLO status key {key!r} is undocumented in "
                        f"{docs_rel} — the SLO vocabulary table must "
                        "enumerate it",
                key=f"ITS-C006:{telemetry_rel}:undocumented:{key}",
            ))

    # -- gossip_* status keys vs the exporter + docs ------------------------
    gossip_keys, gossip_line = ledger_keys(
        ctx, telemetry_rel, TELEMETRY_GOSSIP_LEDGER
    )
    gossip_keys = {k.rsplit(".", 1)[-1] for k in gossip_keys}
    gossip_keys = {k for k in gossip_keys if k.startswith("gossip_")}
    gossip_consumed = {
        k for k in metrics_consumed_keys(
            ctx, manage_rel, fn_name=GOSSIP_EXPORT_FN
        )
        if k.startswith("gossip_")
    }
    if gossip_keys or gossip_consumed:
        for key in sorted(gossip_keys - gossip_consumed):
            findings.append(Finding(
                rule="ITS-C006", file=manage_rel, line=1,
                message=f"gossip status key {key!r} is not exported by the "
                        f"/metrics gossip exporter ({GOSSIP_EXPORT_FN}) — "
                        "anti-entropy health dashboards cannot see is "
                        "observability drift (docs/membership.md)",
                key=f"ITS-C006:{manage_rel}:gossip:{key}",
            ))
        for key in sorted(gossip_consumed - gossip_keys):
            findings.append(Finding(
                rule="ITS-C006", file=manage_rel, line=1,
                message=f"/metrics gossip exporter consumes key {key!r} "
                        f"which {TELEMETRY_GOSSIP_LEDGER} no longer emits "
                        "(KeyError at scrape time)",
                key=f"ITS-C006:{manage_rel}:gossip-stale:{key}",
            ))
        for key in sorted(gossip_keys):
            if key not in doc_words:
                findings.append(Finding(
                    rule="ITS-C006", file=telemetry_rel, line=gossip_line,
                    message=f"gossip status key {key!r} is undocumented in "
                            f"{docs_rel} — the gossip vocabulary must "
                            "enumerate it",
                    key=f"ITS-C006:{telemetry_rel}:undocumented:{key}",
                ))

    # -- event kinds vs producers + docs ------------------------------------
    kinds = _event_kinds(ctx, telemetry_rel)
    produced: Dict[str, List[Tuple[str, int]]] = {}
    for rel, line, kind in _emit_producers(ctx, package_rel):
        produced.setdefault(kind, []).append((rel, line))
    for kind, sites in sorted(produced.items()):
        if kind not in kinds:
            rel, line = sites[0]
            findings.append(Finding(
                rule="ITS-C006", file=rel, line=line,
                message=f"event kind {kind!r} emitted outside the "
                        f"EVENT_KINDS vocabulary ({telemetry_rel}) — add it "
                        "there (and to the docs event table) or fix the "
                        "producer",
                key=f"ITS-C006:{rel}:unknown-kind:{kind}",
            ))
    for kind in kinds:
        if kind not in produced:
            findings.append(Finding(
                rule="ITS-C006", file=telemetry_rel, line=1,
                message=f"event kind {kind!r} has no emit() producer left — "
                        "dead vocabulary (remove it or restore the "
                        "transition-site emit)",
                key=f"ITS-C006:{telemetry_rel}:dead:{kind}",
            ))
        if kind not in doc_words:
            findings.append(Finding(
                rule="ITS-C006", file=telemetry_rel, line=1,
                message=f"event kind {kind!r} is undocumented in {docs_rel} "
                        "— the event schema table must enumerate it",
                key=f"ITS-C006:{telemetry_rel}:undocumented:{kind}",
            ))

    # -- manage routes -------------------------------------------------------
    manage_src = ctx.read(manage_rel)
    if not re.search(r'[\'"]/slo[\'"]', manage_src) or "slo_engine" not in manage_src:
        findings.append(Finding(
            rule="ITS-C006", file=manage_rel, line=1,
            message="manage plane must serve GET /slo from the telemetry "
                    "SLO engine (the burn-rate verdict surface, "
                    "docs/observability.md)",
            key=f"ITS-C006:{manage_rel}:slo-route",
        ))
    if (
        not re.search(r'[\'"]/events[\'"]', manage_src)
        or "get_journal" not in manage_src
    ):
        findings.append(Finding(
            rule="ITS-C006", file=manage_rel, line=1,
            message="manage plane must serve GET /events from the telemetry "
                    "event journal (the causal cluster-event surface, "
                    "docs/observability.md)",
            key=f"ITS-C006:{manage_rel}:events-route",
        ))
    if (
        not re.search(r'[\'"]/gossip[\'"]', manage_src)
        or "merge_remote_view" not in manage_src
    ):
        findings.append(Finding(
            rule="ITS-C006", file=manage_rel, line=1,
            message="manage plane must serve POST /gossip through the "
                    "cluster's merge_remote_view (the anti-entropy epoch "
                    "exchange, docs/membership.md)",
            key=f"ITS-C006:{manage_rel}:gossip-route",
        ))
    return findings


def _scan_membership(
    ctx: Context, manage_rel: str, membership_rel: str = MEMBERSHIP_REL
) -> List[Finding]:
    """ITS-C005: the elastic-membership status vocabulary vs the /metrics
    membership exporter and the /membership manage route."""
    findings: List[Finding] = []
    if not ctx.exists(membership_rel):
        return findings
    status_keys: Set[str] = set()
    for dotted in MEMBERSHIP_LEDGERS:
        keys, _ = ledger_keys(ctx, membership_rel, dotted)
        status_keys |= keys
    status_keys = {
        k for k in status_keys
        if k.startswith("membership_") or k.startswith("reshard_")
        or k.startswith("journal_")
    }
    consumed = metrics_consumed_keys(
        ctx, manage_rel, fn_name=MEMBERSHIP_EXPORT_FN
    )
    for key in sorted(status_keys - consumed):
        findings.append(Finding(
            rule="ITS-C005", file=manage_rel, line=1,
            message=f"membership status key {key!r} is not exported by the "
                    f"/metrics membership exporter ({MEMBERSHIP_EXPORT_FN}) "
                    "— a reshard counter dashboards cannot see is "
                    "observability drift (docs/membership.md)",
            key=f"ITS-C005:{manage_rel}:{key}",
        ))
    for key in sorted(consumed - status_keys):
        findings.append(Finding(
            rule="ITS-C005", file=manage_rel, line=1,
            message=f"/metrics membership exporter consumes key {key!r} "
                    "which the membership status snapshot no longer emits "
                    "(KeyError at scrape time)",
            key=f"ITS-C005:{manage_rel}:stale:{key}",
        ))
    manage_src = ctx.read(manage_rel)
    if (
        not re.search(r'[\'"]/membership[\'"]', manage_src)
        or "membership_status" not in manage_src
    ):
        findings.append(Finding(
            rule="ITS-C005", file=manage_rel, line=1,
            message="manage plane must serve /membership (GET view+status, "
                    "POST transitions) from membership_status — the "
                    "elastic-membership control surface "
                    "(docs/membership.md)",
            key=f"ITS-C005:{manage_rel}:membership-route",
        ))
    if (
        not re.search(r'[\'"]/bootstrap[\'"]', manage_src)
        or "bootstrap_payload" not in manage_src
    ):
        findings.append(Finding(
            rule="ITS-C005", file=manage_rel, line=1,
            message="manage plane must serve GET /bootstrap from the "
                    "cluster's bootstrap_payload — the cold-client "
                    "placement snapshot (docs/membership.md)",
            key=f"ITS-C005:{manage_rel}:bootstrap-route",
        ))
    return findings


@register("counters",
          "every stat counter reaches /stats, /metrics and the API reference (ITS-C*)",
          rule_prefix="ITS-C")
def check(ctx: Context) -> List[Finding]:
    return scan(ctx)
