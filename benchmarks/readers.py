"""From a run's records to metric values.

End-to-end metrics are computed here from the benchmark's own stamps (see
``run.py``: the entry time of every ``step_chunk`` call of a request).
Per-layer metrics are data: ``layer_metrics/<name>.json`` names one of the
few reader kinds below and its parameters. A reader that finds nothing to
read returns ``None`` and the metric is left out of the line.

What a reader is given (``Run``):

``rows``      one dict per request that STARTED inside the window (sent, or
              due), with the fields listed in ``run.py`` ``request_row``
``counters``  window deltas: the benchmark's own (``run.py``
              ``OWN_COUNTERS``) and whatever key of the program's
              ``harness.metrics()`` or of the connector's ``get_stats()``
              (dotted where nested: ``spill.dropped``) a ``counter`` reader
              of the cell names: the harness snapshots exactly those at
              window open and close (``counter_keys``)
``trace``     ``trace_reduce.reduce`` of the traced seconds, with
              ``trace["work"]``: the useful operations and bytes the
              traced calls needed, by cost-function name (``costs.py``)
``peaks``     the row of ``peaks.json`` for this device
``spans``     with the program's recorder on (``--trace 1``): what it held
              when the run ended, and where the profile puts it
              (``span_readers.py``, whose two kinds are registered here)
"""

import dataclasses
import json
import math
import os
from typing import Dict, Iterable, List, Optional, Set

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Run:
    rows: List[Dict]
    counters: Dict[str, float]
    trace: Optional[Dict]
    peaks: Dict
    spans: Optional[Dict] = None


def percentile(values: List[float], q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def aggregate(values: List[float], how: str) -> Optional[float]:
    if not values:
        return None
    if how == "mean":
        return sum(values) / len(values)
    if how == "sum":
        return float(sum(values))
    if how == "max":
        return float(max(values))
    if how.startswith("p"):
        return percentile(values, int(how[1:]) / 100.0)
    raise ValueError(f"unknown aggregate {how!r}")


def _where(rows: List[Dict], where: str) -> List[Dict]:
    if where == "all":
        return rows
    if where == "hit":
        return [r for r in rows if r["hit"]]
    if where == "miss":
        return [r for r in rows if not r["hit"]]
    raise ValueError(f"unknown filter {where!r}")


# ---------------------------------------------------------------------------
# End to end.
# ---------------------------------------------------------------------------


def end_to_end(rows: List[Dict], stamps_in_window: int, gaps_ms: List[float],
               window_s: float, setup_s: float) -> Dict[str, float]:
    """Every end-to-end metric this run can give; ``run.py`` prints those
    that ``BENCHMARK.json`` lists for the cell. A request that failed has no
    TTFT and so misses every percentile: it is not in ``rows`` with one."""
    ttft = [r["ttft_ms"] for r in rows if r["ttft_ms"] is not None]
    out = {"setup_s": setup_s}
    if ttft:
        out["ttft_p50_ms"] = percentile(ttft, 0.50)
        out["ttft_p90_ms"] = percentile(ttft, 0.90)
    if gaps_ms:
        out["tpot_mean_ms"] = sum(gaps_ms) / len(gaps_ms)
    out["tokens_per_s"] = stamps_in_window / window_s
    return out


# ---------------------------------------------------------------------------
# Per layer: the reader kinds.
# ---------------------------------------------------------------------------


def _requests(run: Run, p: Dict):
    rows = _where(run.rows, p.get("where", "all"))
    values = [r[p["field"]] for r in rows if r.get(p["field"]) is not None]
    value = aggregate(values, p["aggregate"])
    return None if value is None else value * p.get("scale", 1.0)


def _ratio(run: Run, p: Dict):
    rows = _where(run.rows, p.get("where", "all"))
    rows = [r for r in rows if r.get(p["num"]) is not None and r.get(p["den"]) is not None]
    den = sum(r[p["den"]] for r in rows)
    if not rows or den <= 0:
        return None
    return sum(r[p["num"]] for r in rows) / den * p.get("scale", 1.0)


def _counter(run: Run, p: Dict):
    if p["key"] not in run.counters:
        return None
    value = run.counters[p["key"]]
    if "per" in p:
        den = run.counters.get(p["per"], 0)
        if den <= 0:
            return None
        value = value / den
    return value * p.get("scale", 1.0)


def _trace_time(run: Run, p: Dict):
    if run.trace is None:
        return None
    seconds, events = trace_reduce.matching(run.trace[p["table"]], p["pattern"])
    if events == 0:
        return None
    per = p.get("per")
    if per == "event":
        den = events
    elif per:
        den = run.trace["work"].get(per, 0)
    else:
        den = 1
    return None if den <= 0 else seconds / den * p.get("scale", 1.0)


def _trace_roofline(run: Run, p: Dict):
    """The least time the chip could have taken for the useful work of the
    traced calls over the kernel's device time, in percent: ``cost`` over
    ``peak``, or where the reader names a second bound (``or_cost`` over
    ``or_peak``: operations beside bytes) the larger of the two, which is
    the one that binds."""
    if run.trace is None:
        return None
    seconds, events = trace_reduce.matching(run.trace["ops"], p["pattern"])
    work = run.trace["work"]
    least = work.get(p["cost"], 0) / run.peaks[p["peak"]]
    if "or_cost" in p:
        least = max(least, work.get(p["or_cost"], 0) / run.peaks[p["or_peak"]])
    if events == 0 or seconds <= 0 or least <= 0:
        return None
    return 100.0 * least / seconds


def _trace_idle(run: Run, p: Dict):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


KINDS = {
    "requests": _requests,
    "ratio": _ratio,
    "counter": _counter,
    "trace_time": _trace_time,
    "trace_roofline": _trace_roofline,
    "trace_idle": _trace_idle,
}


def load_layer_metric(name: str) -> Dict:
    with open(os.path.join(HERE, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def read_layer_metric(name: str, run: Run) -> Optional[float]:
    spec = load_layer_metric(name)
    reader = spec["reader"]
    return KINDS[reader["kind"]](run, reader)


def counter_keys(names: Iterable[str]) -> Set[str]:
    """Every counter those metrics' files name (``key`` and ``per``)."""
    readers = [load_layer_metric(name)["reader"] for name in names]
    return {r[k] for r in readers if r["kind"] == "counter" for k in ("key", "per") if k in r}


import span_readers  # noqa: E402,F401 - adds its kinds to KINDS; it imports this module
