"""A fingerprint of every program a benchmark cell launches, with no chip.

A change that only MOVES traced Python (a helper to another module, a
preamble to a shared function) must leave the programs the chip runs as they
were. ``libtpu`` lowers and compiles for a ``v5e:2x2`` it does not have
(``tests/test_tpu_aot_compile.py``), so that is a fact one can print: for
every configuration file under ``benchmarks/configs/`` at its published
widths (shapes only: nothing is materialised) and every program its cells
launch,

- ``prefill`` at the traffic's shortest and longest prompt, where the model
  file has a jitted one (a model served a block at a time has none: its miss
  is its ``resume_chunk``, a piece a block);
- ``resume_chunk`` at a miss's piece (one block of tokens, where the model is
  served a block at a time) and at a 128-token question, over a table of the
  cell's longest request;
- ``serving.verify_step_ragged``, the packed wave, at a one-row, a three-row
  (a bucket of four) and the widest layout the cell's clients make (of a model
  that drafts, ``config.steps.drafts``: entries of two rows each; its chunk
  takes ``next_token``),

one JSON line ``{"row": "<configuration>/<program>/<shape>", "lowered": sha}``:
the SHA-256 of ``jitted.trace(...).lower(lowering_platforms=("tpu",))
.as_text()``. That text carries no source locations of its own, but every
Mosaic kernel rides in it as serialised MLIR that does (file, line and call
stack of every operation: a line added above a kernel's caller would show), so
each kernel's body is parsed and replaced by its location-free assembly first
(``without_locations``). Run it on two trees and ``diff`` the outputs:

    git archive <parent> | tar -x -C /root/scratch/parent
    python3 tools/program_fingerprints.py --tree /root/scratch/parent > parent.jsonl
    python3 tools/program_fingerprints.py > change.jsonl
    diff parent.jsonl change.jsonl

Where a row's lowered text differs (the order two independent operations were
traced in, say), name the ROW: it is then compiled as well and its line
carries ``"optimised"``, the SHA-256 of the optimised HLO with ``metadata={...}``
stripped. A difference there is a different program, whatever a tolerance
test says. A bare configuration name selects all of its rows, lowered alone:

    python3 tools/program_fingerprints.py --tree T glm-5 trinity-mini/prefill/s8320.mb2068

One tree a process: the tree is what ``infinistore_tpu`` and ``benchmarks``
are imported from. The dispatchers are told they are on the chip
(``jax.default_backend`` as this module's callers see it), so the Pallas
branches are what is lowered.
"""

import argparse
import base64
import functools
import hashlib
import json
import os
import re
import sys

QUESTION_TOKENS = 128


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@functools.lru_cache(maxsize=None)
def _kernel_sha(body: str) -> str:
    """The fingerprint of a Mosaic kernel's serialised body (base64 of MLIR
    bytecode) printed without debug information; a layer's kernel is every
    layer's, so each body is parsed once."""
    from jax._src.lib.mlir import ir

    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True  # the serialised form's ``stable_mosaic``
        module = ir.Module.parse(base64.b64decode(body))
        return _sha(module.operation.get_asm(enable_debug_info=False))


def without_locations(text: str) -> str:
    """``text`` (lowered StableHLO or optimised HLO) with every Mosaic kernel's
    serialised body replaced by the fingerprint of its location-free assembly."""
    canonical = lambda match: match.group(1) + _kernel_sha(match.group(2))
    # ``\22body\22: \22...`` in StableHLO's escaped string, ``"body":"...`` in HLO's.
    return re.sub(r'(body(?:\\22|"): ?(?:\\22|"))([A-Za-z0-9+/=]+)', canonical, text)


def programs(tree: str, names):
    """Yields ``(row, jitted, args, static)`` for every program of every
    configuration of ``tree`` that ``names`` selects (all where empty)."""
    sys.path[:0] = [tree, os.path.join(tree, "benchmarks")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.default_backend = lambda: "tpu"  # what ``tpu/paged.py`` and ``tpu/mla.py`` ask

    import traffic
    from infinistore_tpu.models import serving

    topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    s = functools.partial(jax.ShapeDtypeStruct, sharding=SingleDeviceSharding(topo.devices[0]))
    i32 = lambda *shape: s(shape, jnp.int32)
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        bench = json.load(f)

    def resolve(dotted):
        module, _, attr = dotted.partition(":")
        return getattr(__import__(module, fromlist=[attr]), attr)

    pow2 = lambda n: 1 << (n - 1).bit_length()
    for entry in bench["configs"]:
        name = entry["name"]
        if names and not any(n == name or n.startswith(name + "/") for n in names):
            continue
        with open(os.path.join(tree, entry["file"])) as f:
            file = json.load(f)
        prog, serve = file["program"], file["serving"]
        cfg = resolve(prog["config_class"])(
            block_tokens=serve["block_tokens"], dtype=jnp.bfloat16,
            **{k: file[v] for k, v in prog["fields"].items()},
        )
        module = sys.modules[type(cfg).__module__]
        shapes = jax.eval_shape(
            lambda k: resolve(prog["init_params"])(cfg, k), jax.random.key(0, impl="rbg")
        )
        params = jax.tree.map(lambda a: s(a.shape, a.dtype), shapes)
        blocks = serve["cache_blocks"]
        spec = cfg.kv_spec(blocks)
        caches = [
            tuple(s((blocks, *t.block_shape), t.dtype) for t in spec.layer_tensors(layer))
            for layer in range(spec.num_layers)
        ]
        bt = cfg.block_tokens
        by_blocks = spec.has_state or cfg.steps.resume_in_block
        drafts = getattr(cfg.steps, "drafts", False)  # a tree before PR 62 has no such role
        width = 2 if drafts else 1
        # A tree before PR 63 feeds the ids alone, whatever its steps draft.
        feed = serving.feed_rows(drafts) if hasattr(serving, "feed_rows") else serving.FEED_ROWS
        seen = set()
        for cell in bench["workloads"]:
            if cell["config"] != name:
                continue
            plan = traffic.build_plan(cell["traffic"])
            prompts = sorted({r.prompt_tokens for r in plan.requests})
            mb = max(-(-(r.prompt_tokens + r.answer_tokens) // bt) for r in plan.requests)
            found = []
            if not by_blocks:
                for tokens in sorted({prompts[0] // bt * bt, prompts[-1] // bt * bt}):
                    found.append((
                        f"prefill/s{tokens}", module.prefill,
                        (params, i32(tokens), caches, i32(tokens // bt)), {"config": cfg},
                    ))
            for tokens in sorted({bt, QUESTION_TOKENS} if by_blocks else {QUESTION_TOKENS}):
                found.append((
                    f"resume_chunk/s{tokens}.mb{mb}", module.resume_chunk,
                    (params, i32(tokens), i32(), caches, i32(mb)),
                    {"config": cfg, **({"next_token": i32()} if drafts else {})},
                ))
            for rows in sorted({1, 4, pow2(plan.clients)}):
                pages = pow2(rows * mb)
                window = None
                if spec.window is not None:
                    window = min(pages, rows * (spec.window // bt + 1))
                layout = serving.WaveLayout(width * rows, rows, width * pages, window)
                static = {"config": cfg, "max_blocks": mb, "layout": layout}
                found.append((
                    f"verify_step_ragged/T{rows}.P{pages}.mb{mb}", serving.verify_step_ragged,
                    (params, i32(layout.size(mb)), i32(feed), caches), static,
                ))
            for shape, jitted, args, static in found:
                row = f"{name}/{shape}"
                if row not in seen and (not names or name in names or row in names):
                    seen.add(row)
                    yield row, jitted, args, static


def lowered(jitted, args, static):
    return jitted.trace(*args, **static).lower(lowering_platforms=("tpu",))


def optimised_text(low) -> str:
    """The optimised HLO of a lowered program without what names its source:
    every ``metadata={...}`` and the tables of files, functions and stack
    frames those index."""
    text = re.sub(r", metadata=\{[^}]*\}", "", low.compile().as_text())
    tables = r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*\n"
    return without_locations(re.sub(tables, "", text, flags=re.M))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap.add_argument("--tree", default=here, help="the checkout to read (default: this one)")
    ap.add_argument("names", nargs="*", help="configurations (lowered) or rows (compiled too)")
    args = ap.parse_args()
    for row, jitted, call, static in programs(os.path.abspath(args.tree), set(args.names)):
        low = lowered(jitted, call, static)
        line = {"row": row, "lowered": _sha(without_locations(low.as_text()))}
        if row in args.names:
            line["optimised"] = _sha(optimised_text(low))
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
