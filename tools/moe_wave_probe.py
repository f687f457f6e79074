"""What a wave's expert product costs by what its rows chose, the bare kernel.

``tpu/moe.py`` ``_moe_wave`` streams a wave's chosen experts through one
Pallas kernel (``_moe_wave_pallas``: a grid of static slots x the tiles of an
expert's width, the slots' expert ids scalar-prefetched into the weight
blocks' index maps). What it has to read is the DISTINCT experts the rows
chose that are HELD on this chip; what it does read is whatever its grid's
steps name, and the pipeline copies a block whenever a step names another
than the step before. This probe times, on the chip, that product ALONE at the
shapes of the routed configurations under ``benchmarks/configs/`` (held /
routed experts, ``dim``, an expert's width, k), for waves of ``--rows`` rows
whose choices are a seeded uniform draw of k distinct experts a row over the
ROUTER's width. For each (configuration, rows) one JSON line: the static
slots, the mean count of held distinct experts over the draws (what must be
read), the device time of the ``moe_wave_pallas`` op a call from the
profiler's trace, how many experts' reads at the HBM's peak that time is
worth, and the held distinct experts' bytes over the time, in GB/s and as a
share of the peak. Then a table in markdown.

``--against DIR`` runs the same probe on a second tree (a ``git archive`` of
another commit unpacked in ``DIR``, ``.chipcheck/parent`` say) and puts the
two side by side: one process a tree, one after the other, because a process
that touched jax holds the chip; this process then imports no jax. The
second tree must have the kernel where this one has it (``tpu/moe.py``: PR 60
on); an older tree is timed by its own copy of this probe.

    python3 tools/moe_wave_probe.py --against .chipcheck/parent   # on the chip: ~3 min
    python3 tools/moe_wave_probe.py --experts 4 --draws 2 --calls 1 \\
        --configs glm-5 --rows 4                                  # a smoke, anywhere
"""

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def probe(args) -> list:
    """The lines of ``args.tree``'s kernel. Imports jax: one tree a process."""
    sys.path[:0] = [os.path.join(REPO, "benchmarks"), os.path.join(REPO, "tools")]
    import jax
    import jax.numpy as jnp
    import numpy as np

    import trace_reduce
    from ffn_rows_probe import device_ops
    from gmm_tile_probe import routed_configs  # puts REPO on the path: the tree goes before it

    sys.path.insert(0, os.path.abspath(args.tree))
    from infinistore_tpu.tpu import moe

    assert os.path.abspath(moe.__file__).startswith(os.path.abspath(args.tree)), moe.__file__
    device = jax.devices()[0]
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as f:
        peaks = json.load(f).get(device.device_kind)
    rng = np.random.default_rng(args.seed)
    results = []
    for name, cfg in routed_configs(set(filter(None, args.configs.split(",")))):
        if args.experts:  # a smoke: a router of at most this many, the held share kept
            routed = max(cfg.experts_per_token, min(args.experts, cfg.n_experts))
            held = max(1, cfg.held[1] * routed // cfg.n_experts)
            cfg = dataclasses.replace(
                cfg, n_experts=routed, experts_held=(0, held) if held < routed else None
            )
        first, held = cfg.held
        d, f, k = cfg.dim, cfg.moe_ffn_dim, cfg.experts_per_token
        key = jax.random.key(args.seed, impl="rbg")
        weight = jax.jit(
            lambda a, b: (jax.random.normal(key, (held, a, b), jnp.float32) / a**0.5).astype(
                jnp.bfloat16
            ),
            static_argnums=(0, 1),
        )
        w = {"w_gate": weight(d, f), "w_up": weight(d, f), "w_down_moe": weight(f, d)}
        expert_bytes = 3 * d * f * 2
        runs = []
        for rows in [int(r) for r in args.rows.split(",")]:
            ids = np.stack([
                np.stack([rng.choice(cfg.n_experts, k, replace=False) for _ in range(rows)])
                for _ in range(args.draws)
            ]).astype(np.int32)  # [draws, rows, k]
            weights = rng.random(ids.shape, np.float32)
            m = jax.random.normal(key, (rows, d), jnp.float32).astype(jnp.bfloat16)

            def fn(m, ids, weights, w, _cfg=cfg):
                return moe._moe_wave(m, ids, weights, w, _cfg)[0]

            # Named so that ``clean_name`` keeps the whole name.
            fn.__name__ = f"wave{len(results) + len(runs)}x"
            draws = [(jnp.asarray(i), jnp.asarray(c)) for i, c in zip(ids, weights)]
            compiled = jax.jit(fn).lower(m, *draws[0], w).compile()
            slots = jax.eval_shape(
                lambda i, c, _cfg=cfg: moe._wave_slots(i, c, _cfg)[0], *draws[0]
            ).shape[0]
            held_distinct = [
                len({e for e in draw.reshape(-1).tolist() if first <= e < first + held})
                for draw in ids
            ]
            runs.append((
                {
                    "tree": args.tree, "config": name, "held": held, "routed": cfg.n_experts,
                    "dim": d, "ffn": f, "tiles": f // moe._wave_f_tile(f), "k": k, "rows": rows,
                    "slots": slots, "held_distinct": float(np.mean(held_distinct)),
                    "distinct": float(np.mean([len(set(i.reshape(-1).tolist())) for i in ids])),
                    "expert_mb": expert_bytes / 1e6, "device": device.device_kind,
                },
                fn.__name__, compiled, m, draws,
            ))

        def call(compiled, m, draws):
            for _ in range(args.calls):
                for draw in draws:
                    out = compiled(m, *draw, w)
            out.block_until_ready()

        with tempfile.TemporaryDirectory() as tmp:
            for _res, _name, compiled, m, draws in runs:
                call(compiled, m, draws)  # warm
            with jax.profiler.trace(tmp):
                for _res, _name, compiled, m, draws in runs:
                    call(compiled, m, draws)
            try:
                trace = trace_reduce.load(trace_reduce.find_xplane(tmp))
            except FileNotFoundError:  # no profiler plugin: the smoke's case
                trace = {"planes": []}
        for res, fn_name, _compiled, _m, _draws in runs:
            _, ops = device_ops(trace, "jit_" + fn_name)
            kernel_s = sum(s for op, s in ops.items() if "moe_wave_pallas" in op)
            if kernel_s and peaks:
                one = expert_bytes / peaks["hbm_bytes_per_s"]
                res["kernel_ms"] = kernel_s * 1e3
                res["reads_worth"] = kernel_s / one
                res["held_gb_per_s"] = res["held_distinct"] * expert_bytes / kernel_s / 1e9
                res["hbm_pct"] = 100 * res["held_distinct"] * one / kernel_s
            results.append(res)
            print(json.dumps(res), flush=True)
    return results


def table(results) -> str:
    """One row a (configuration, rows): every tree's time and what it is
    worth, side by side in the order the trees were given."""
    trees = list(dict.fromkeys(r["tree"] for r in results))
    rows = {}
    for r in results:
        rows.setdefault((r["config"], r["rows"]), {})[r["tree"]] = r
    num = lambda r, key, fmt: format(r[key], fmt) if r and key in r else "not measured"
    lines = [
        "| configuration (held / routed, dim x width: tiles, k) | rows | held distinct of distinct | "
        + " | ".join(
            f"`{t}`: slots; ms a call; experts' reads it is worth; GB/s of held distinct (% of peak)"
            for t in trees
        ) + " |",
        "| --- | --- | --- |" + " --- |" * len(trees),
    ]
    for (config, n), by_tree in rows.items():
        r = next(iter(by_tree.values()))
        cells = [
            f"{num(x, 'slots', 'd')}; {num(x, 'kernel_ms', '.4f')}; {num(x, 'reads_worth', '.1f')}; "
            f"{num(x, 'held_gb_per_s', '.0f')} ({num(x, 'hbm_pct', '.1f')})"
            for x in (by_tree.get(t) for t in trees)
        ]
        lines.append(
            f"| `{config}` ({r['held']} / {r['routed']}, {r['dim']:,} x {r['ffn']:,}: {r['tiles']}, "
            f"{r['k']}) | {n} | {r['held_distinct']:.2f} of {r['distinct']:.2f} | " + " | ".join(cells) + " |"
        )
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=REPO, help="the checkout whose kernel is timed")
    ap.add_argument("--against", default="", help="a second checkout, timed first, in a process of its own")
    ap.add_argument("--configs", default="", help="configuration names, comma separated (all routed)")
    ap.add_argument("--rows", default="1,2,4", help="rows of a wave")
    ap.add_argument("--draws", type=int, default=16, help="seeded draws of the rows' choices a shape")
    ap.add_argument("--calls", type=int, default=4, help="calls timed a draw")
    ap.add_argument("--experts", type=int, default=0, help="a router of at most this many experts (a smoke)")
    ap.add_argument("--seed", type=int, default=59)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out", "moe_wave_probe.jsonl"))
    args = ap.parse_args()

    if args.against:
        # One process a tree: this one stays off jax, so neither child finds the chip held.
        results = []
        own = [
            "--configs", args.configs, "--rows", args.rows, "--draws", str(args.draws),
            "--calls", str(args.calls), "--experts", str(args.experts), "--seed", str(args.seed),
        ]
        for tree in (args.against, args.tree):
            with tempfile.NamedTemporaryFile(suffix=".jsonl") as out:
                subprocess.run(
                    [sys.executable, os.path.abspath(__file__), *own, "--tree", tree, "--out", out.name],
                    check=True, stdout=sys.stderr,
                )
                results += [json.loads(line) for line in open(out.name)]
    else:
        results = probe(args)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in results)
    print(table(results), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
