"""From the program's own spans to metric values, and onto the device's clock.

The engine records a request's phases as spans (``infinistore_tpu/tracing.py``;
the tree is in ``docs/observability.md``, "Inside the engine"). Two reader
kinds read them, in the form ``readers.py`` gives its own:

``spans``          over the spans of the requests in ``rows`` (a row's
                   ``trace_id`` is its request's trace). One of three shapes:

                   ``{"span", "attrs", "child" | "less", "from", "to",
                   "round", "aggregate", "where", "scale"}``: every span of
                   that name (and those ``attrs``) is one sample: its
                   duration, or the sum of its children named ``child``
                   (0.0 where it has none: a wait that did not happen is a
                   sample too), or its duration less its children named
                   ``less``; with ``from`` / ``to``, the time between those
                   stage stamps, pair by pair, and ``round`` picks the
                   ``first`` pair, the ``rest`` whose ``to`` stamp lies in
                   the window, or ``all``.

                   ``{"parts": [<metric name>, ...], "over": <row field>,
                   "aggregate", "scale"}``: per request, what those
                   metrics' readers find in its trace, summed, over the
                   row's field.

                   ``{"skew": [<row field>, <row field>], "aggregate",
                   "scale"}``: two lists of stamps a row carries, paired;
                   the absolute differences.

``trace_idle_in``  ``{"pattern"}``: the share of the traced seconds in which
                   the device was idle and the innermost program phase that
                   covered the gap matches ``pattern`` (a span's name, or a
                   part's from ``STAGE_PHASES``).

What they read is ``readers.Run.spans``: ``{"spans": [span dicts],
"recorded", "dropped", "window_us": [open, close] on the spans' clock,
"profile": reduce_profile(...) or None}``, what the recorder held when the
run ended.

A reader returns ``None`` only where there is nothing to read: no recorder,
no span of that name, no profile. Spans that are there but waited for
nothing give 0.0.

``reduce_profile`` is the reduction behind the second kind. The recorder
stamps CLOCK_MONOTONIC, the profiler its own clock; the two
``its.clock:<monotonic_ns>`` marks the run put into the profile give the
offset (``tracing.profile_clock_offset``). Every idle gap of the first
device is then cut where a program phase starts or ends, and each piece goes
to the shortest phase that covers it (the innermost), or to ``outside``
where none does: the parts add up to the device's idle time exactly. The five regions the program records both as
a span entry and as a ``TraceAnnotation`` check the offset: their two
recordings should agree.
"""

import bisect
import re
from typing import Dict, List, Optional, Tuple

import readers
import trace_reduce
from infinistore_tpu import tracing

# Spans that hold other phases or only wait: they name no gap themselves.
CONTAINERS = ("engine_request", "generate", "gate_wait", "pool_alloc")
OUTSIDE = "outside"
# Spans that are phases only between stage stamps: a `generate` in each
# round's read-back; a `wave` cut at its stamps, because only `gate` ->
# `dispatched` is the call into the device, and assembly, the exclusive
# gate's wait and handing the rows back are host work of their own.
STAGE_PHASES = {
    "generate": (("wave_result", "token", "readback"),),
    "wave": (
        ("taken", "assembled", "wave_assemble"), ("assembled", "gate", "wave_gate_wait"),
        ("gate", "dispatched", "wave_dispatch"), ("dispatched", "resolved", "wave_resolve"),
    ),
}


# ---------------------------------------------------------------------------
# Spans.
# ---------------------------------------------------------------------------


def _index(spans: List[Dict]):
    by_trace: Dict[int, List[Dict]] = {}
    children: Dict[int, List[Dict]] = {}
    for s in spans:
        by_trace.setdefault(s["trace_id"], []).append(s)
        children.setdefault(s["parent_id"], []).append(s)
    return by_trace, children


def _stage_pairs(span: Dict, a: str, b: str) -> List[Tuple[float, float]]:
    """The k-th ``a`` stamp with the k-th ``b`` stamp."""
    first = [t for name, t in span["stages"] if name == a]
    second = [t for name, t in span["stages"] if name == b]
    return list(zip(first, second))


def _samples(view: Dict, trace_ids, p: Dict) -> Optional[Dict[int, List[float]]]:
    """Microseconds, per trace id; ``None`` when no span has the name."""
    by_trace, children = view["index"]
    w0, w1 = view["window_us"]
    out: Dict[int, List[float]] = {}
    found = False
    for tid in trace_ids:
        for s in by_trace.get(tid, ()):
            if s["name"] != p["span"]:
                continue
            if any(s["attrs"].get(k) != v for k, v in p.get("attrs", {}).items()):
                continue
            found = True
            kids = children.get(s["span_id"], ())
            if "from" in p:
                pairs = _stage_pairs(s, p["from"], p["to"])
                which = p.get("round", "all")
                if which == "first":
                    pairs = pairs[:1]
                elif which == "rest":
                    pairs = [(a, b) for a, b in pairs[1:] if w0 <= b < w1]
                values = [float(b - a) for a, b in pairs]
            elif "child" in p:
                values = [float(sum(c["duration_us"] for c in kids if c["name"] == p["child"]))]
            else:
                less = sum(c["duration_us"] for c in kids if c["name"] == p.get("less"))
                values = [float(s["duration_us"] - less)]
            out.setdefault(tid, []).extend(values)
    return out if found else None


def _trace_ids(run, where: str) -> List[int]:
    return [r["trace_id"] for r in readers._where(run.rows, where) if r.get("trace_id")]


def _scaled(values: List[float], p: Dict, empty=None) -> Optional[float]:
    value = readers.aggregate(values, p["aggregate"])
    return empty if value is None else value * p.get("scale", 1.0)


def _skew(run, p: Dict) -> Optional[float]:
    a, b = p["skew"]
    return _scaled(
        [abs(x - y) for r in run.rows if r.get(a) and r.get(b) for x, y in zip(r[a], r[b])], p
    )


def _parts(run, view: Dict, p: Dict) -> Optional[float]:
    parts = [readers.load_layer_metric(name)["reader"] for name in p["parts"]]
    shares = []
    for r in run.rows:
        if not r.get("trace_id") or not r.get(p["over"]):
            continue
        found = [_samples(view, [r["trace_id"]], part) for part in parts]
        if all(f is None for f in found):
            continue
        total = sum(
            part.get("scale", 1.0) * sum(v)
            for part, f in zip(parts, found) if f for v in f.values()
        )
        shares.append(total / r[p["over"]])
    return _scaled(shares, p)


def _spans(run, p: Dict) -> Optional[float]:
    if "skew" in p:
        return _skew(run, p)
    view = run.spans
    if not view or not view["spans"]:
        return None
    if "index" not in view:
        view["index"] = _index(view["spans"])
    if "parts" in p:
        return _parts(run, view, p)
    found = _samples(view, _trace_ids(run, p.get("where", "all")), p)
    if found is None:
        return None
    # Spans that are there with no pair inside the window: nothing waited.
    return _scaled([v for vs in found.values() for v in vs], p, empty=0.0)


def _trace_idle_in(run, p: Dict) -> Optional[float]:
    view = run.spans
    profile = view.get("profile") if view else None
    if not profile or profile["window_s"] <= 0:
        return None
    rx = re.compile(p["pattern"])
    idle = sum(s for phase, s in profile["idle_s"].items() if rx.search(phase))
    return 100.0 * idle / profile["window_s"]


KINDS = {"spans": _spans, "trace_idle_in": _trace_idle_in}
readers.KINDS.update(KINDS)


# ---------------------------------------------------------------------------
# The profile: one clock, idle gaps by program phase.
# ---------------------------------------------------------------------------


def _host_named(trace: Dict, prefix: str) -> List[Tuple[float, float, str]]:
    """``(start_ns, end_ns, name)`` of the host events whose name starts
    with ``prefix`` (zero-length ones too: the clock marks are)."""
    out = []
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(prefix):
                    out.append((start, start + dur, name))
    return sorted(out)


def phases(spans: List[Dict], offset_ns: float) -> List[Tuple[float, float, str]]:
    """The program's phases on the profile's clock: every recorded span
    under its own name, but those of ``STAGE_PHASES`` as their named
    parts, and no container."""
    out = []
    for s in spans:
        if s["name"] in STAGE_PHASES:
            out.extend(
                (tracing.to_profile_ns(a, offset_ns), tracing.to_profile_ns(b, offset_ns), name)
                for first, second, name in STAGE_PHASES[s["name"]]
                for a, b in _stage_pairs(s, first, second)
            )
        elif s["name"] not in CONTAINERS and s["end_us"] > s["start_us"]:
            out.append((
                tracing.to_profile_ns(s["start_us"], offset_ns),
                tracing.to_profile_ns(s["end_us"], offset_ns), s["name"],
            ))
    return sorted(out)


def _attribute(gaps, intervals) -> Dict[str, float]:
    """Idle seconds by phase. A gap is cut where a phase starts or ends;
    each piece goes to the shortest phase that covers it (the innermost),
    or to ``outside``."""
    starts = [i[0] for i in intervals]
    reach, best = [], float("-inf")
    for _s, end, _n in intervals:
        best = max(best, end)
        reach.append(best)
    out: Dict[str, float] = {}
    for g0, g1 in gaps:
        over = []
        i = bisect.bisect_left(starts, g1) - 1
        while i >= 0 and reach[i] > g0:
            if intervals[i][1] > g0:
                over.append(intervals[i])
            i -= 1
        cuts = sorted({g0, g1} | {t for s, e, _n in over for t in (s, e) if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            covering = [(e - s, name) for s, e, name in over if s <= a and e >= b]
            name = min(covering)[1] if covering else OUTSIDE
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def _agreement(annotations, spans: List[Dict], offset_ns: float) -> Dict[str, Dict]:
    """Per region name: how far the ``TraceAnnotation`` in the profile and
    the span's ``device_calls`` entry lie apart, start and end, in us."""
    calls: Dict[str, List[Tuple[float, float]]] = {}
    for s in spans:
        for name, t0, t1 in s["attrs"].get("device_calls", ()):
            calls.setdefault(name, []).append(
                (tracing.to_profile_ns(t0, offset_ns), tracing.to_profile_ns(t1, offset_ns))
            )
    diffs: Dict[str, Tuple[List[float], List[float]]] = {}
    for recorded in calls.values():
        recorded.sort()
    for a0, a1, name in annotations:
        recorded = calls.get(name)
        if not recorded:
            continue
        i = bisect.bisect_left(recorded, (a0,))
        near = min(
            (j for j in (i - 1, i) if 0 <= j < len(recorded)),
            key=lambda j: abs(recorded[j][0] - a0),
        )
        d_start, d_end = diffs.setdefault(name, ([], []))
        d_start.append(abs(recorded[near][0] - a0) / 1e3)
        d_end.append(abs(recorded[near][1] - a1) / 1e3)
    return {
        name: {
            "n": len(d_start),
            "start_p50_us": readers.percentile(d_start, 0.5),
            "end_p50_us": readers.percentile(d_end, 0.5),
            "end_p95_us": readers.percentile(d_end, 0.95),
        }
        for name, (d_start, d_end) in diffs.items()
    }


def reduce_profile(trace: Dict, spans: List[Dict]) -> Optional[Dict]:
    """``trace`` as ``trace_reduce.load`` gives it. ``None`` when the
    profile holds no clock mark (a program without them)."""
    ours = _host_named(trace, "its.")  # the clock marks and the five regions, in one pass
    marks = [(start, name) for start, _end, name in ours if tracing.clock_mark_ns(name) is not None]
    clock = tracing.profile_clock_offset(marks)
    if clock is None:
        return None
    offset_ns, drift_ns = clock
    devices = [p for p in trace["planes"] if trace_reduce.DEVICE_PLANE.match(p["name"])]
    ops = trace_reduce._line(devices[0], trace_reduce.OPS_LINE)
    busy = trace_reduce._union([(s, s + d) for _n, s, d in ops])
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    return {
        "offset_ns": offset_ns, "drift_ns": drift_ns, "marks": len(marks),
        "window_s": (busy[-1][1] - busy[0][0]) / 1e9,
        "idle_s": _attribute(gaps, phases(spans, offset_ns)),
        "agreement": _agreement(ours, spans, offset_ns),
    }
