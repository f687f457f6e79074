"""From a profiler trace to numbers: the one reduction every PR shares.

``load(path)`` reads an ``.xplane.pb`` (with nothing but JAX) into a plain
structure, ``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]}``; a ``.json`` file of that structure is
read as it is (the recorded trace under ``tests/data``). ``reduce(trace)``
gives:

- ``busy_s`` / ``window_s``: the union of the intervals in which an
  operation ran on the device, averaged over the device planes, and the
  length of the traced window (first to last event on any device plane);
- ``ops``: device seconds by operation name, ``modules`` by program name,
  each with its count of events;
- ``idle_gaps``: the idle seconds of the first device, by what the host was
  doing in each gap (the host event that overlaps the gap most; the shorter
  one where two overlap equally, so the innermost span names it).

Names are cut to the characters a ledger line carries (``[A-Za-z0-9_.:-]``,
runs of anything else become one ``_``), with trailing instance numbers
(``fusion.123``) dropped and the result's type and shape kept, so that one
operation at one shape is one row.
"""

import bisect
import glob
import json
import os
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# Idle gaps shorter than this are launch-to-launch latency, not something a
# host span explains; they are summed under one name.
MIN_GAP_NS = 20_000
SHORT_GAPS = "shorter_gaps"
NO_HOST_SPAN = "no_host_span"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Dict:
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events
            ]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def clean_name(name: str) -> str:
    """``%copy.5 = bf16[2048,16,8,128]{3,2,1,0} copy(...)`` ->
    ``copy_bf16_2048_16_8_128``; ``jit_verify_step_ragged(123)`` ->
    ``jit_verify_step_ragged``. The result's type and shape stay in the
    name: they tell the whole-cache copy from a row's."""
    head, sep, rest = name.strip().lstrip("%").partition(" = ")
    head = re.sub(r"[.\-_]\d+$", "", head.split("(")[0].strip())
    if sep:
        shape = re.match(r"\(?(\w+)\[([\d,]*)\]", rest)
        if shape:
            head += "_" + shape.group(1)
            if shape.group(2):
                head += "_" + shape.group(2).replace(",", "_")
    head = re.sub(r"[^A-Za-z0-9_.:\-]+", "_", head).strip("_")
    return head[:96] or "unnamed"


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def _by_name(events) -> Dict[str, List[float]]:
    out: Dict[str, List[float]] = {}
    for name, _start, dur in events:
        row = out.setdefault(clean_name(name), [0.0, 0])
        row[0] += dur / 1e9
        row[1] += 1
    return out


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _host_events(trace) -> List[Tuple[float, float, str]]:
    events = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]) or not plane["name"].startswith("/host"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if dur > 0:
                    events.append((start, start + dur, name))
    events.sort()
    return events


def _attribute_gaps(gaps, host) -> Dict[str, float]:
    starts = [e[0] for e in host]
    # Running maximum of end times: lets the backward scan stop as soon as
    # nothing earlier can still reach the gap.
    reach, best = [], float("-inf")
    for _s, end, _n in host:
        best = max(best, end)
        reach.append(best)
    out: Dict[str, float] = {}
    for g0, g1 in gaps:
        if g1 - g0 < MIN_GAP_NS:
            out[SHORT_GAPS] = out.get(SHORT_GAPS, 0.0) + (g1 - g0) / 1e9
            continue
        chosen, chosen_key = NO_HOST_SPAN, None
        i = bisect.bisect_left(starts, g1) - 1
        scanned = 0
        while i >= 0 and reach[i] > g0 and scanned < 4096:
            s, e, name = host[i]
            overlap = min(e, g1) - max(s, g0)
            if overlap > 0:
                key = (overlap, -(e - s))
                if chosen_key is None or key > chosen_key:
                    chosen, chosen_key = clean_name(name), key
            i -= 1
            scanned += 1
        out[chosen] = out.get(chosen, 0.0) + (g1 - g0) / 1e9
    return out


def reduce(trace: Dict) -> Dict:
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError(
            "the trace has no device plane: planes "
            + ", ".join(p["name"] for p in trace["planes"])
        )
    t0 = min(ev[1] for p in devices for ev in _line(p, OPS_LINE))
    t1 = max(ev[1] + ev[2] for p in devices for ev in _line(p, OPS_LINE))
    busy, unions = [], []
    for plane in devices:
        merged = _union([(s, s + d) for _n, s, d in _line(plane, OPS_LINE)])
        unions.append(merged)
        busy.append(sum(b - a for a, b in merged) / 1e9)
    first = unions[0]
    gaps = [(first[i][1], first[i + 1][0]) for i in range(len(first) - 1)]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (t1 - t0) / 1e9,
        "t0_ns": t0,
        "t1_ns": t1,
        "ops": _by_name(ev for p in devices for ev in _line(p, OPS_LINE)),
        "modules": _by_name(ev for p in devices for ev in _line(p, MODULES_LINE)),
        "idle_gaps": _attribute_gaps(gaps, _host_events(trace)),
        "devices": len(devices),
    }


def top(table: Dict, n: int = 10) -> List[List]:
    """The ``n`` rows with most seconds, as ``[[name, seconds], ...]``;
    accepts ``{name: seconds}`` and ``{name: [seconds, count]}``."""
    rows = [
        [name, v[0] if isinstance(v, (list, tuple)) else v] for name, v in table.items()
    ]
    return sorted(rows, key=lambda r: -r[1])[:n]


def matching(table: Dict[str, List[float]], pattern: str) -> Tuple[float, int]:
    """Seconds and event count of every row whose name matches ``pattern``."""
    rx = re.compile(pattern)
    rows = [v for name, v in table.items() if rx.search(name)]
    return sum(v[0] for v in rows), int(sum(v[1] for v in rows))
