"""What the grouped expert product costs by the tiles it is handed.

``tpu/moe.py`` ``_grouped_ffn`` multiplies a chunk's (token, expert)
pairs, sorted by expert, into the held experts' weights with the Pallas
grouped matmul (megablox ``gmm``). Its grid visits every non-empty group once
a row tile the group touches, each visit one whole ``tm x tk x tn`` pass a K
and N step, and an operand's tile is fetched when its index moves: a group
of 32 rows under a 512-row tile pays for 512, and a group's weights are read
again a visit unless K is one tile. This probe times, on the chip, the two
products of an expert layer (gate or up: ``[M, dim] x [G, dim, ffn]``; down:
``[M, ffn] x [G, ffn, dim]``, bfloat16 operands, float32 result) ALONE, at
the shapes the routed configurations under ``benchmarks/configs/`` give the
benchmark's traffic:

- a hit's question (``--question`` tokens through ``resume_chunk``);
- a miss's piece: one block where the cache keeps a state a block (the
  engine then computes a miss block by block), else the chunks
  ``moe._chunks`` cuts ``--documents`` + question tokens into.

The group sizes are a seeded uniform draw of the pairs over the ROUTER's
width, the held experts' share kept (rows past their sum are nobody's), as
``_grouped_ffn`` hands them over. The triples: row tiles ``--row-tiles`` x
the K and N tiles of the rule (``moe._gmm_tiling``), of the parent of PR 51
(``min(width, 1024)``) and of ``moe._lane_tile`` under each of
``--lane-tiles``, the distinct ones. For each one JSON line: the host's time
a call (a queue of calls, one wait), the device time of the ``gmm`` op a call
from the profiler's trace, that time's share of the matrix unit's peak (the
held pairs' 2 x K x N flops) and of the HBM's (the non-empty groups' weights
read once, the held pairs' rows read once an N tile, their result written),
``rule`` and ``parent``: whether the triple is the one the rule picks, or the
parent's picked (512 rows where the padded pairs are whole tiles of it). A
triple the compiler refuses (VMEM) is a line with ``refused``. Then a table
in markdown, one row a shape and product.

    python3 tools/gmm_tile_probe.py                      # on the chip: ~7 min
    python3 tools/gmm_tile_probe.py --experts 2 --calls 1 --interpret \\
        --configs kimi-linear-48b-a3b --row-tiles 128      # a smoke, anywhere
"""

import argparse
import glob
import importlib
import json
import math
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "benchmarks"), os.path.join(REPO, "tools")]


def resolve(path: str):
    module, _, name = path.partition(":")
    return getattr(importlib.import_module(module), name)


def routed_configs(names):
    """(name, the program's config) of every configuration file whose program
    routes (``experts_per_token`` among its fields), built as ``benchmarks/
    run.py`` builds it."""
    import jax.numpy as jnp

    for path in sorted(glob.glob(os.path.join(REPO, "benchmarks", "configs", "*.json"))):
        with open(path) as f:
            file = json.load(f)
        prog = file.get("program", {})
        if "experts_per_token" not in prog.get("fields", {}):
            continue
        if names and file["name"] not in names:
            continue
        fields = {k: file[v] for k, v in prog["fields"].items()}
        yield file["name"], resolve(prog["config_class"])(
            block_tokens=file["serving"]["block_tokens"], dtype=jnp.bfloat16, **fields
        )


def shapes(cfg, question: int, documents):
    """(what, tokens) of the grouped products the traffic makes ``cfg`` run."""
    from infinistore_tpu.tpu import moe

    out = [("hit_question", question)]
    if cfg.kv_spec(1).has_state:
        out.append(("miss_piece", cfg.block_tokens))
    else:
        sizes = sorted({moe._chunks(d + question)[1] for d in documents})
        out += [("miss_chunk", s) for s in sizes]
    return out


def parent_tiling(m: int, k: int, n: int):
    """What ``_grouped_matmul`` handed ``gmm`` before PR 51."""
    return 512 if m % 512 == 0 else 128, min(k, 1024), min(n, 1024)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="", help="configuration names, comma separated (all routed)")
    ap.add_argument("--row-tiles", default="128,256,512")
    ap.add_argument("--lane-tiles", default="1152,4096", help="the most lanes a K or N tile takes: the triples beside the rule's and the parent's")
    ap.add_argument("--question", type=int, default=128)
    ap.add_argument("--documents", default="8192,16384,32768")
    ap.add_argument("--calls", type=int, default=20, help="calls timed a triple")
    ap.add_argument("--experts", type=int, default=0, help="hold at most this many experts, the rows a group kept (a smoke)")
    ap.add_argument("--interpret", action="store_true", help="Pallas interpret mode (off the chip)")
    ap.add_argument("--seed", type=int, default=51)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out", "gmm_tile_probe.jsonl"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    import trace_reduce
    from ffn_rows_probe import device_ops
    from infinistore_tpu.tpu import moe

    device = jax.devices()[0]
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as f:
        peaks = json.load(f).get(device.device_kind)
    row_tiles = [int(t) for t in args.row_tiles.split(",")]
    whole_tiles = math.lcm(*row_tiles)  # M is whole tiles of every row tile
    lane_tiles = [int(t) for t in args.lane_tiles.split(",")]
    documents = [int(d) for d in args.documents.split(",")]
    rng = np.random.default_rng(args.seed)
    results = []
    for name, cfg in routed_configs(set(filter(None, args.configs.split(",")))):
        _, held = cfg.held
        scale = min(1.0, args.experts / held) if args.experts else 1.0
        held, router = max(1, round(held * scale)), max(1, round(cfg.n_experts * scale))
        key = jax.random.key(args.seed, impl="rbg")
        weight = jax.jit(
            lambda k, n: (jax.random.normal(key, (held, k, n), jnp.float32) / k**0.5).astype(
                jnp.bfloat16
            ),
            static_argnums=(0, 1),
        )
        products = {
            "gate_up": weight(cfg.dim, cfg.moe_ffn_dim),
            "down": weight(cfg.moe_ffn_dim, cfg.dim),
        }
        for what, tokens in shapes(cfg, args.question, documents):
            pairs = tokens * cfg.experts_per_token
            rows_a_group = pairs / cfg.n_experts
            drawn = max(1, round(pairs * scale))
            sizes = rng.multinomial(drawn, np.full(router, 1 / router))[:held]
            m = -(-drawn // whole_tiles) * whole_tiles
            group_sizes = jnp.asarray(sizes, jnp.int32)
            runs = []
            for product, rhs in products.items():
                _, k, n = rhs.shape
                lhs = jax.random.normal(key, (m, k), jnp.float32).astype(jnp.bfloat16)
                rule = moe._gmm_tiling(k, n)
                lanes = {(min(k, 1024), min(n, 1024)), rule[1:]} | {
                    (moe._lane_tile(k, most), moe._lane_tile(n, most)) for most in lane_tiles
                }
                triples = sorted({(tm, *kn) for tm in row_tiles for kn in lanes} | {rule})
                for tiling in triples:
                    def fn(lhs, rhs, group_sizes, _tiling=tiling):
                        return gmm(
                            lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
                            tiling=_tiling, interpret=args.interpret,
                        )
                    # Named so that ``clean_name`` keeps the whole name.
                    fn.__name__ = f"probe{len(results) + len(runs)}x"
                    res = {
                        "config": name, "what": what, "tokens": tokens, "pairs": pairs,
                        "rows_a_group": rows_a_group, "held": held,
                        "held_rows": int(sizes.sum()), "groups": int((sizes > 0).sum()),
                        "product": product, "k": k, "n": n,
                        "tiling": list(tiling), "rule": tiling == rule,
                        "parent": tiling == parent_tiling(pairs + (-pairs % 128), k, n),
                        "device": device.device_kind,
                    }
                    try:
                        compiled = jax.jit(fn).lower(lhs, rhs, group_sizes).compile()
                    except jax.errors.JaxRuntimeError as e:  # the compiler refuses: VMEM
                        res["refused"] = str(e).splitlines()[0][:200]
                        compiled = None
                    runs.append((res, fn.__name__, compiled, lhs, rhs))

            def call(compiled, lhs, rhs):
                for _ in range(args.calls):
                    out = compiled(lhs, rhs, group_sizes)
                out.block_until_ready()

            for res, _name, compiled, lhs, rhs in runs:
                if compiled is None:
                    continue
                call(compiled, lhs, rhs)
                t0 = time.perf_counter()
                call(compiled, lhs, rhs)
                res["host_ms"] = (time.perf_counter() - t0) / args.calls * 1e3
            with tempfile.TemporaryDirectory() as tmp:
                with jax.profiler.trace(tmp):
                    for _res, _name, compiled, lhs, rhs in runs:
                        if compiled is not None:
                            call(compiled, lhs, rhs)
                try:
                    trace = trace_reduce.load(trace_reduce.find_xplane(tmp))
                except FileNotFoundError:  # no profiler plugin: the smoke's case
                    trace = {"planes": []}
            for res, fn_name, compiled, lhs, rhs in runs:
                if compiled is not None:
                    _, ops = device_ops(trace, "jit_" + fn_name)
                    gmm_s = sum(s for op, s in ops.items() if op.startswith("gmm"))
                    if gmm_s and peaks:
                        k, n, tn = res["k"], res["n"], res["tiling"][2]
                        flops = 2 * res["held_rows"] * k * n
                        moved = (
                            res["groups"] * k * n * 2  # the weights
                            + res["held_rows"] * k * 2 * -(-n // tn)  # the rows, once an N tile
                            + res["held_rows"] * n * 4  # the result
                        )
                        res["gmm_ms"] = gmm_s * 1e3
                        res["mxu_pct"] = 100 * flops / peaks["bf16_flops_per_s"] / gmm_s
                        res["hbm_pct"] = 100 * moved / peaks["hbm_bytes_per_s"] / gmm_s
                results.append(res)
                print(json.dumps(res), flush=True)

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in results)
    print(table(results), flush=True)
    return 0


def table(results) -> str:
    """One row a (config, shape, product): every triple's time, the rule's
    and the parent's marked, the rule's shares of the two peaks."""
    rows = {}
    for r in results:
        rows.setdefault((r["config"], r["what"], r["tokens"], r["product"]), []).append(r)
    lines = [
        "| configuration | product (rows; rows a group) | K x N | device ms by (tm, tk, tn); **rule**, "
        "*parent* | rule: % of the matrix unit's peak | rule: % of the HBM's |",
        "| --- | --- | --- | --- | --- | --- |",
    ]
    for (config, what, tokens, product), rs in rows.items():
        cells = []
        for r in rs:
            ms = "refused" if "refused" in r else f"{r['gmm_ms']:.3f}" if "gmm_ms" in r else "not measured"
            cell = f"{tuple(r['tiling'])} {ms}"
            cell = f"**{cell}**" if r["rule"] else cell
            cells.append(f"*{cell}*" if r["parent"] else cell)
        rule = next((r for r in rs if r["rule"]), {})
        pct = lambda key: f"{rule[key]:.1f}" if key in rule else "not measured"
        lines.append(
            f"| `{config}` | {what} {product} ({rs[0]['pairs']:,}; {rs[0]['rows_a_group']:.0f}) | "
            f"{rs[0]['k']:,} x {rs[0]['n']:,} | {'; '.join(cells)} | {pct('mxu_pct')} | {pct('hbm_pct')} |"
        )
    return "\n".join(lines)


if __name__ == "__main__":
    sys.exit(main())
