#!/usr/bin/env python
"""Autodoc: generate docs/api_reference.md from the live package docstrings.

The reference ships Sphinx autodoc built in CI and deployed to GH Pages
(reference docs/source/api.rst, .github/workflows/deploy-docs.yml). This
environment has no sphinx, so the autodoc step is this self-contained
generator: it introspects the public surface (signatures + docstrings, the
same inputs sphinx.ext.autodoc consumes) and emits deterministic markdown.
CI runs it with --check so the committed reference can never drift from the
code; the docs-deploy workflow publishes docs/ to Pages.

Usage: python tools/gen_api_docs.py [--check]
"""

import argparse
import dataclasses
import enum
import inspect
import os
import sys
import textwrap

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "docs", "api_reference.md")

# (module, [public names]; None = every public callable/class in __all__ or
# module order). Curated so the page reads top-down like the reference's
# api.rst rather than alphabetically.
SURFACE = [
    ("infinistore_tpu.config", ["ClientConfig", "ServerConfig"]),
    ("infinistore_tpu.lib", [
        "InfinityConnection", "StripedConnection", "LocalServer",
        "start_local_server", "register_server", "unregister_server",
        "InfiniStoreException", "InfiniStoreKeyNotFound", "InfiniStoreNoMatch",
        "Logger",
    ]),
    ("infinistore_tpu.connector", ["KVConnector", "token_chain_hashes"]),
    ("infinistore_tpu.engine", [
        "EngineKVAdapter", "ContinuousBatchingHarness", "BlockPool",
        "WaveDecoder", "WaveRows", "DeviceGate", "GateHold", "RequestStats",
    ]),
    ("infinistore_tpu.cluster", [
        "ClusterKVConnector", "rendezvous_owner", "rendezvous_ranked",
        "CircuitBreaker",
    ]),
    ("infinistore_tpu.membership", [
        "MemberState", "MembershipView", "Membership", "Resharder",
        "DurableLog",
    ]),
    ("infinistore_tpu.tiering", [
        "TemperatureSketch", "TierPolicyConfig", "TierPolicy", "TierManager",
        "note_demotion_hit", "demotion_hits", "note_cold_read_us",
    ]),
    ("infinistore_tpu.faults", [
        "FaultRule", "FaultyConnection", "kill_transport", "crash_process",
    ]),
    ("infinistore_tpu.tracing", [
        "configure", "enabled", "recorder", "Span", "FlightRecorder",
        "trace_op", "start_span", "use_span", "active_span",
        "server_tick_spans", "chrome_trace_events", "stage_breakdown",
    ]),
    ("infinistore_tpu.telemetry", [
        "EventJournal", "SloObjective", "SloEngine", "FleetScraper",
        "GossipAgent", "MetricsHistory",
        "default_objectives", "cluster_spans", "cluster_chrome_events",
        "get_journal", "emit", "slo_engine", "configure_slo",
        "note_qos_aged", "metrics_http_source", "scraper_source",
        "parse_metrics_text",
    ]),
    ("infinistore_tpu.profiling", [
        "SamplingProfiler", "configure", "enabled", "profiler",
    ]),
    ("infinistore_tpu.vllm_v1", [
        "KVConnectorRole",
        "KVConnectorBase_V1",
        "InfiniStoreKVConnectorV1",
        "InfiniStoreConnectorMetadata",
    ]),
    ("infinistore_tpu.loadgen", [
        "TraceRequest", "Trace", "generate", "preset", "replay",
    ]),
    ("infinistore_tpu.disagg", [
        "DisaggCounters", "DisaggHarness", "counters", "reset_counters",
        "demo_config", "demo_prompt", "stream_prefill", "overlapped_decode",
        "local_decode",
    ]),
    ("infinistore_tpu.tpu.paged", None),
    ("infinistore_tpu.tpu.paged_attention", None),
    ("infinistore_tpu.tpu.flash_prefill", None),
    ("infinistore_tpu.tpu.chunk_attention", None),
    ("infinistore_tpu.tpu.kv_quant", [
        "quantize_kv", "dequantize_kv", "paged_decode_attention_quantized",
        "QuantizedKVConnector", "QuantizingKVAdapter",
    ]),
    ("infinistore_tpu.tpu.staging", None),
    ("infinistore_tpu.tpu.layerwise", None),
    ("infinistore_tpu.tpu.ici", None),
    ("infinistore_tpu.shaping", None),
    ("infinistore_tpu.models", None),
    ("infinistore_tpu.models.serving", None),
    ("infinistore_tpu.models.pipeline", None),
    ("infinistore_tpu.models.ring_attention", None),
    ("infinistore_tpu.models.long_context", None),
    ("infinistore_tpu.models.ulysses", None),
]


def _doc(obj) -> str:
    d = inspect.getdoc(obj)
    return d.strip() if d else "*(undocumented)*"


def _sig(obj) -> str:
    # Enum constructor signatures are a CPython implementation detail that
    # changed across 3.10 -> 3.12 ("(value, names=None, ...)" vs
    # "(*values)"); rendering one would make --check depend on the
    # interpreter that generated the file. Members are the actual surface.
    if inspect.isclass(obj) and issubclass(obj, enum.Enum):
        return "(" + ", ".join(m.name for m in obj) + ")"
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"


def _render_class(name, cls, out):
    out.append(f"### `{name}{_sig(cls) if not dataclasses.is_dataclass(cls) else ''}`\n")
    out.append(_doc(cls) + "\n")
    if dataclasses.is_dataclass(cls):
        out.append("| field | default |\n|---|---|")
        for f in dataclasses.fields(cls):
            default = (
                f.default if f.default is not dataclasses.MISSING
                else ("(factory)" if f.default_factory is not dataclasses.MISSING
                      else "(required)")
            )
            out.append(f"| `{f.name}` | `{default!r}` |")
        out.append("")
    methods = [
        (n, m) for n, m in inspect.getmembers(cls, inspect.isfunction)
        if not n.startswith("_") and n in cls.__dict__
    ]
    # Preserve definition order (autodoc default), not getmembers' sort.
    order = {n: i for i, n in enumerate(cls.__dict__)}
    for n, m in sorted(methods, key=lambda kv: order.get(kv[0], 1 << 30)):
        out.append(f"#### `{name}.{n}{_sig(m)}`\n")
        out.append(textwrap.indent(_doc(m), "") + "\n")


def _render_module(modname, names, out):
    mod = __import__(modname, fromlist=["*"])
    out.append(f"## `{modname}`\n")
    head = (inspect.getdoc(mod) or "").strip().split("\n\n")[0]
    if head:
        out.append(head + "\n")
    if names is None:
        names = getattr(mod, "__all__", None) or [
            n for n, o in vars(mod).items()
            if not n.startswith("_")
            and (inspect.isclass(o) or inspect.isfunction(o))
            # Defined HERE — re-exports would otherwise duplicate their
            # home module's section.
            and getattr(o, "__module__", "") == modname
        ]
    for n in names:
        obj = getattr(mod, n)
        if inspect.isclass(obj):
            _render_class(n, obj, out)
        elif callable(obj):
            out.append(f"### `{n}{_sig(obj)}`\n")
            out.append(_doc(obj) + "\n")


def generate() -> str:
    out = [
        "# API reference (generated)",
        "",
        "<!-- GENERATED by tools/gen_api_docs.py — do not edit. CI enforces"
        " `python tools/gen_api_docs.py --check`. -->",
        "",
        "Introspected from the live package docstrings (the autodoc step;"
        " see docs/api.md for the hand-written guide with examples).",
        "",
    ]
    for modname, names in SURFACE:
        _render_module(modname, names, out)
    return "\n".join(out).rstrip() + "\n"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="fail if docs/api_reference.md is out of date")
    args = ap.parse_args()
    text = generate()
    if args.check:
        on_disk = open(OUT).read() if os.path.exists(OUT) else ""
        if on_disk != text:
            sys.stderr.write(
                "docs/api_reference.md is stale — run python tools/gen_api_docs.py\n"
            )
            return 1
        print("docs/api_reference.md is up to date")
        return 0
    with open(OUT, "w") as f:
        f.write(text)
    print(f"wrote {OUT} ({len(text.splitlines())} lines)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
