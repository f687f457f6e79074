"""How fast one dense FFN reads its weights, by how many rows the wave has.

``models/llama.py`` ``_ffn`` multiplies a wave's rows into ``w_gate_up [dim,
2, ffn]``. With ONE row XLA lowers that product to a multiply-and-reduce on
the vector unit, with two rows or more to a matrix-unit fusion over the same
buffer (``PERF.md`` section 6, PR 45). This probe times, on the chip, a chain
of ``--layers`` FFNs, each with weights of its own, at DeepSeek's (4096,
11008) and Mistral's (4096, 14336) widths in bfloat16. (A chain, as a wave
step is one: XLA moves ONE operand of a program towards the core ahead of
the program, and a single FFN called again and again then reads its
``w_down`` for nothing.) The forms:

- ``rows1``, ``rows2``, ``rows4``: the plain formula as the parent commit
  has it, on 1, 2 and 4 rows;
- ``rows1_pad2`` / ``_pad8`` / ``_pad16``: one row padded with zeros to R
  rows for the gate/up product, row 0 kept, ``w_down`` on the kept row;
- ``rows1_pad2_down`` / ``_pad8_down`` / ``_pad16_down``: the same with
  ``w_down`` on all R rows and row 0 kept at the end;
- ``rows1_pad8_flat``: the padded product over ``w_gate_up`` viewed as
  ``[dim, 2 * ffn]``;
- ``llama_ffn_rows1`` / ``_rows2``: ``llama._ffn`` as this tree ships it. (A
  program whose computation equals an earlier form's is served from JAX's
  compile cache under THAT form's name, and the trace then files its calls
  there: such a line carries the host's time and the compiled text's facts
  alone, and that it has no device time of its own says which form it is.)

For each it prints one JSON line: the host's time a layer (a queue of calls,
one wait), the program's device time a layer and the weights' GB/s by it,
the device operations of the program with their time a layer (the largest is
the gate/up product; its name is the profiler's, cut as ``benchmarks/
trace_reduce.py`` cuts it), and three facts of the optimised HLO: which
instruction the gate/up product became, whether a ``convolution`` (the matrix
unit) is in the program, and how many instructions COPY an array of
``w_gate_up``'s size.

    python3 tools/ffn_rows_probe.py            # on the chip: ~1 min
    python3 tools/ffn_rows_probe.py --dim 256 --ffn 344 --calls 2   # a smoke, anywhere
"""

import argparse
import bisect
import json
import math
import os
import re
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "benchmarks")]

WIDTHS = ((4096, 11008), (4096, 14336))  # deepseek-llm-7b, mistral-7b-v0.3
WEIGHTS = ("ffn_norm", "w_gate_up", "w_down")
# (label, rows, pad_to, w_down on the padded rows, w_gate_up viewed flat)
VARIANTS = (
    ("rows1", 1, 0, False, False),
    ("rows1_pad2", 1, 2, False, False),
    ("rows1_pad8", 1, 8, False, False),
    ("rows1_pad16", 1, 16, False, False),
    ("rows1_pad2_down", 1, 2, True, False),
    ("rows1_pad8_down", 1, 8, True, False),
    ("rows1_pad16_down", 1, 16, True, False),
    ("rows1_pad8_flat", 1, 8, False, True),
    ("rows2", 2, 0, False, False),
    ("rows4", 4, 0, False, False),
)


def ffn(x, norm, w_gate_up, w_down, pad_to=0, down_padded=False, flat=False):
    """The dense FFN of ``llama._ffn`` as the parent commit has it (norm,
    gate/up product, SwiGLU, down product, residual) on ``x [1, rows, dim]``,
    and its padded forms: the probe's own copy, so that its lines mean the
    same on every tree."""
    import jax
    import jax.numpy as jnp

    from infinistore_tpu.models.llama import _rms_norm

    h = _rms_norm(x, norm)
    rows = h.shape[1]
    if pad_to:
        h = jnp.pad(h, ((0, 0), (0, pad_to - rows), (0, 0)))
    if flat:
        dim, _, width = w_gate_up.shape
        gate_up = jnp.einsum("bsd,dn->bsn", h, w_gate_up.reshape(dim, 2 * width))
        gate_up = gate_up.reshape(*gate_up.shape[:2], 2, width)
    else:
        gate_up = jnp.einsum("bsd,dcf->bscf", h, w_gate_up)
    if pad_to and not down_padded:
        gate_up = gate_up[:, :rows]
    act = jax.nn.silu(gate_up[:, :, 0]) * gate_up[:, :, 1]
    out = jnp.einsum("bsf,fd->bsd", act, w_down)
    return x + out[:, :rows]


def hlo_facts(text: str, dim: int, width: int) -> dict:
    """Of a compiled program's text: the instruction of the entry computation
    that is the gate/up product (its name, result and fusion kind), whether
    the matrix unit (a ``convolution``) is in the program, and how many
    instructions of the entry computation COPY an array as large as
    ``w_gate_up`` (a copy inside a fusion is an operand's read, not a copy)."""
    entry = text[text.index("ENTRY"):]
    product = re.search(
        r"%(\S+) = (\w+\[[\d,]*\])\S* fusion\(.*kind=(\w+).*d(?:cf->bscf|n->bsn)/dot_general", entry
    )
    copies = sum(
        math.prod(int(n) for n in shape.split(",")) == dim * 2 * width
        for shape in re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", entry)
    )
    return {
        "gate_up": " ".join(product.groups()) if product else None,
        "convolution": " convolution(" in text,
        "weight_copies": copies,
    }


def device_ops(trace: dict, module: str):
    """Device seconds a call of the program ``module`` and of each operation
    that ran inside its calls: the events of "XLA Ops" that start inside an
    "XLA Modules" event of that name on the first device plane. ``(None,
    {})`` where the trace has no device plane or no such program."""
    import trace_reduce

    plane = next(
        (p for p in trace["planes"] if trace_reduce.DEVICE_PLANE.match(p["name"])), None
    )
    if plane is None:
        return None, {}
    line = lambda name: next((l["events"] for l in plane["lines"] if l["name"] == name), [])
    calls = sorted(
        (s, s + d) for n, s, d in line(trace_reduce.MODULES_LINE)
        if trace_reduce.clean_name(n) == module
    )
    if not calls:
        return None, {}
    starts = [a for a, _b in calls]
    ops = {}
    for name, start, dur in line(trace_reduce.OPS_LINE):
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < calls[i][1]:
            key = trace_reduce.clean_name(name)
            ops[key] = ops.get(key, 0.0) + dur / 1e9
    n = len(calls)
    return sum(b - a for a, b in calls) / 1e9 / n, {k: v / n for k, v in ops.items()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=0, help="one width only (with --ffn)")
    ap.add_argument("--ffn", type=int, default=0)
    ap.add_argument("--calls", type=int, default=40, help="calls timed a variant")
    ap.add_argument("--layers", type=int, default=4, help="FFNs a program chains, each its own weights")
    args = ap.parse_args()
    if bool(args.dim) != bool(args.ffn):
        ap.error("--dim and --ffn go together")

    import jax
    import jax.numpy as jnp

    import trace_reduce
    from infinistore_tpu.models import llama

    device = jax.devices()[0]
    for dim, width in ((args.dim, args.ffn),) if args.dim else WIDTHS:
        config = llama.LlamaConfig(dim=dim, ffn_dim=width, n_layers=args.layers)
        keys = iter(jax.random.split(jax.random.PRNGKey(dim + width), 2 * args.layers + 1))
        dense = lambda shape: (
            jax.random.normal(next(keys), shape, jnp.float32) / shape[0] ** 0.5
        ).astype(jnp.bfloat16)
        params = {}
        for layer in range(args.layers):
            params[f"l{layer}.ffn_norm"] = jnp.ones((dim,), jnp.bfloat16)
            params[f"l{layer}.w_gate_up"] = dense((dim, 2, width))
            params[f"l{layer}.w_down"] = dense((width, dim))
        weight_bytes = 3 * dim * width * 2 * args.layers
        x_key = next(keys)

        # A program is named width first: ``clean_name`` drops a tail of
        # ``_<digits>`` as an instance number.
        programs = []
        for label, rows, *form in VARIANTS:
            def fn(x, params, _form=form):
                for layer in range(args.layers):
                    x = ffn(x, *(params[f"l{layer}.{k}"] for k in WEIGHTS), *_form)
                return x
            fn.__name__ = f"ffn{width}_{label}"
            programs.append((label, rows, jax.jit(fn)))
        for rows in (1, 2):
            def fn(x, params):
                for layer in range(args.layers):
                    x = llama._ffn(params, layer, x, config)
                return x
            fn.__name__ = f"ffn{width}_llama_ffn_rows{rows}"
            programs.append((f"llama_ffn_rows{rows}", rows, jax.jit(fn)))

        # Compiled once, called as compiled: timed first with the profiler
        # off, then traced.
        x_of = lambda rows: jax.random.normal(x_key, (1, rows, dim), jnp.float32).astype(
            jnp.bfloat16
        )
        runs = [
            (label, rows, jitted.__name__, x_of(rows), jitted.lower(x_of(rows), params).compile())
            for label, rows, jitted in programs
        ]

        def call(compiled, x):
            for _ in range(args.calls):
                out = compiled(x, params)
            out.block_until_ready()

        results = []
        for label, rows, _name, x, compiled in runs:
            call(compiled, x)
            t0 = time.perf_counter()
            call(compiled, x)
            host_s = (time.perf_counter() - t0) / args.calls
            results.append({
                "dim": dim, "ffn": width, "layers": args.layers, "variant": label, "rows": rows,
                "host_ms_a_layer": host_s * 1e3 / args.layers,
                "hlo": hlo_facts(compiled.as_text(), dim, width),
            })

        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _label, _rows, _name, x, compiled in runs:
                    call(compiled, x)
            try:
                trace = trace_reduce.load(trace_reduce.find_xplane(tmp))
            except FileNotFoundError:  # no profiler plugin: the smoke's case
                trace = {"planes": []}
        for res, (_label, _rows, name, _x, _compiled) in zip(results, runs):
            dev_s, ops = device_ops(trace, "jit_" + name)
            if dev_s:
                res["device_ms_a_layer"] = dev_s * 1e3 / args.layers
                res["weights_gb_s"] = weight_bytes / dev_s / 1e9
                res["ops_ms_a_layer"] = {
                    op: s * 1e3 / args.layers for op, s in trace_reduce.top(ops, 5)
                }
            res["device"] = device.device_kind
            print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
