"""What one warmed wave bucket costs a run's set-up, split by where it goes.

A benchmark cell warms one ``verify_step_ragged`` program (the packed wave
entry of ``models/serving.py``, as the decoder launches it) for every (rows,
pages) bucket its traffic can land on (``benchmarks/run.py``
``wave_buckets()``: 24 in the chat cell), so whatever a change adds to that
program's trace, lowering or load is paid a bucket. This tool stages each
bucket of a cell apart with ``jax.stages`` — ``jit(...).trace``,
``.lower``, ``.compile`` — on abstract arguments (no weights are made, no
step runs), and prints one JSON line: per bucket the three times, and for
the whole cell their sums. The compile leg is a compilation in a first run
and the persistent cache's load in a second run of the same tree (``cache_hits``
counts JAX's cache-hit events); run it twice in one call to read both.

    python3 tools/wave_setup_split.py --workload mistral7b-unshared-chat

It also counts, once, what the program holds once and what it holds a
layer: the calls of the wave layer's function and of the function around the
decode kernel, how many distinct functions those are (one each: the jits are
what makes the layers share them), and the Mosaic calls in the module.
"""

import argparse
import json
import os
import re
import sys
import time
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "benchmarks")]


def cell_buckets(workload: str):
    """The cell's program config, table width and wave buckets, as
    ``benchmarks/run.py`` derives them from the files."""
    import jax.numpy as jnp

    import run
    import traffic

    bench = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell, config = run.cell_of(bench, workload)
    plan = traffic.build_plan(cell["traffic"])
    prog, serving = config["program"], config["serving"]
    cfg = run.resolve(prog["config_class"])(
        block_tokens=serving["block_tokens"], dtype=jnp.bfloat16,
        **{k: config[v] for k, v in prog["fields"].items()},
    )
    bt = cfg.block_tokens
    mrb = max(-(-(r.prompt_tokens + r.answer_tokens) // bt) for r in plan.requests)
    blocks = int(plan.params.get("cache_blocks", serving["cache_blocks"]))
    view = types.SimpleNamespace(cfg=cfg, plan=plan, max_req_blocks=mrb)
    return cfg, mrb, blocks, run.CellRun.wave_buckets(view)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--buckets", type=int, default=0, help="only the first N (0: all)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import monitoring

    from infinistore_tpu import compile_cache
    from infinistore_tpu.models import llama, serving

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    hits = []
    monitoring.register_event_listener(
        lambda event, **_: hits.append(event) if event.endswith("cache_hits") else None
    )

    cfg, mrb, blocks, buckets = cell_buckets(args.workload)
    s = jax.ShapeDtypeStruct
    params = jax.tree.map(
        lambda x: s(x.shape, x.dtype),
        jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0))),
    )
    cache = s(cfg.kv_spec(blocks).cache_shape, cfg.dtype)
    caches = [(cache, cache)] * cfg.n_layers
    i32 = lambda *shape: s(shape, jnp.int32)

    rows_out, kernel = [], None
    for rows, pages in buckets[: args.buckets or None]:
        t0 = time.perf_counter()
        layout = serving.WaveLayout(rows=rows, tables=rows, pages=pages)
        traced = serving.verify_step_ragged.trace(
            params, i32(layout.size(mrb)), i32(serving.FEED_ROWS), caches, config=cfg,
            max_blocks=mrb, layout=layout,
        )
        t1 = time.perf_counter()
        lowered = traced.lower()
        t2 = time.perf_counter()
        n_hits = len(hits)
        lowered.compile()
        t3 = time.perf_counter()
        rows_out.append({
            "rows": rows, "pages": pages, "trace_s": t1 - t0, "lower_s": t2 - t1,
            "compile_or_load_s": t3 - t2, "cache_hit": len(hits) > n_hits,
        })
        if kernel is None:
            text = lowered.as_text()
            calls = re.findall(r"call @(\w*paged_decode_attention_pallas_ragged\w*)\(", text)
            layers = re.findall(r"call @(_wave_layer\w*)\(", text)
            kernel = {
                "mosaic_calls_in_module": text.count("tpu_custom_call"),
                "calls_of_the_kernel_function": len(calls),
                "kernel_functions": len(set(calls)),
                "calls_of_the_layer_function": len(layers),
                "layer_functions": len(set(layers)),
                "module_text_bytes": len(text),
            }
    total = lambda key: sum(r[key] for r in rows_out)
    print(json.dumps({
        "workload": args.workload, "device": jax.devices()[0].device_kind,
        "layers": cfg.n_layers, "buckets": len(rows_out),
        "trace_s": total("trace_s"), "lower_s": total("lower_s"),
        "compile_or_load_s": total("compile_or_load_s"),
        "cache_hits": sum(r["cache_hit"] for r in rows_out),
        "kernel": kernel, "per_bucket": rows_out,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
