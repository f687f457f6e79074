"""On the chip: the selective-scan kernel of ``infinistore_tpu/tpu/selective_scan.py``
against its XLA walk at the shapes ``benchmarks/configs/phi-4-mini-flash-reasoning.json``
gives it, with its device time a piece.

Tier-1 holds the kernel in interpret mode at toy shapes (``tests/test_sambay.py``)
and compiles it for a v5e (``tests/test_tpu_aot_compile.py``); what only the
chip shows is whether the COMPILED kernel computes what the token-by-token walk
does at 5,120 channels x 16 states over a block's 2,048 tokens and over a hit's
127-token question (relative rms error of ``y`` and of the state after the last
token, a carried state going in), and what a call costs: the milliseconds a
piece and the nanoseconds a token of the kernel, beside the walk's.

    chiprun --chips 1 -- python3 tools/selscan_kernel_check.py

Then the model's piecewise product (``sambay._proj``: float32 rows against
bf16 weights as two or three bf16 pieces a row) against float64: on the chip a
piece cut by a cast to bf16 and back is no piece (XLA:TPU keeps the float32
value through the pair, the rest is zero and the product is one bf16 pass, 1.7e-3
off; ``lax.reduce_precision`` is what cuts one), and no CPU run shows it.

One line a case, then ``{"ok": ...}``; exit code 1 unless every error is under
``--tol`` (default 2e-5: float32 on both sides, sums in another order) and the
two- and three-piece products within 1e-4 and 1e-5 of float64's.
``--rows 64 --channels 256 --interpret`` for a smoke anywhere.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from infinistore_tpu.tpu import selective_scan as ss  # noqa: E402


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2)) / (np.sqrt(np.mean(b**2)) + 1e-30))


def _ms(fn, *args, reps: int = 3) -> float:
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / reps * 1e3


def inputs(rows: int, channels: int, n_state: int, key):
    """A piece's scan inputs as the model makes them: u after a silu, dt after
    a softplus around the initialisation's 0.001-0.1, A = -(1 .. N), a state
    that is not zero."""
    ks = jax.random.split(key, 6)
    u = jax.nn.silu(jax.random.normal(ks[0], (rows, channels))).astype(jnp.bfloat16)
    bias = jnp.exp(jax.random.uniform(ks[1], (channels,), minval=np.log(1e-3), maxval=np.log(1e-1)))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (rows, channels)) + jnp.log(jnp.expm1(bias)))
    a_log = jnp.log(jnp.broadcast_to(jnp.arange(1, n_state + 1, dtype=jnp.float32), (channels, n_state)))
    b, c = jax.random.normal(ks[3], (rows, n_state)), jax.random.normal(ks[4], (rows, n_state))
    return u, dt, a_log, b, c, jnp.ones((channels,)), jax.random.normal(ks[5], (n_state, channels))


def pieces(key) -> dict:
    """``sambay._proj`` at 256 rows (two pieces) and 4 (three) against float64."""
    from infinistore_tpu.models import sambay

    x = 2.0 * jax.random.normal(key, (256, 2560), jnp.float32)
    w = (jax.random.normal(jax.random.fold_in(key, 1), (2560, 1024), jnp.float32) / 50).astype(jnp.bfloat16)
    want = np.asarray(x, np.float64) @ np.asarray(w.astype(jnp.float32), np.float64)
    proj = jax.jit(lambda rows: sambay._proj("td,df->tf", rows, w))
    return {
        "two_pieces_rel": rel(proj(x), want), "three_pieces_rel": rel(proj(x[:4]), want[:4]),
        "one_bf16_pass_rel": rel(proj(x.astype(jnp.bfloat16)), want),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, nargs="*", default=None)
    ap.add_argument("--channels", type=int, default=None)
    ap.add_argument("--tol", type=float, default=2e-5)
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args()
    with open(os.path.join(REPO, "benchmarks", "configs", "phi-4-mini-flash-reasoning.json")) as f:
        real = json.load(f)
    channels = args.channels or real["mamba_expand"] * real["hidden_size"]
    rows = args.rows or [real["serving"]["block_tokens"], 127]
    kernel = lambda *a: ss.selective_scan_pallas(*a, interpret=args.interpret)
    ok = True
    for i, r in enumerate(rows):
        case = inputs(r, channels, real["mamba_d_state"], jax.random.key(58 + i))
        (y, h), (want_y, want_h) = kernel(*case), ss.selective_scan_xla(*case)
        line = {
            "rows": r, "channels": channels, "y_rel": rel(y, want_y), "state_rel": rel(h, want_h),
            "kernel_ms": _ms(kernel, *case), "walk_ms": _ms(ss.selective_scan_xla, *case, reps=1),
        }
        line["kernel_ns_a_token"] = line["kernel_ms"] * 1e6 / r
        ok &= line["y_rel"] < args.tol and line["state_rel"] < args.tol
        print(json.dumps(line), flush=True)
    line = pieces(jax.random.key(58))
    ok &= line["two_pieces_rel"] < 1e-4 and line["three_pieces_rel"] < 1e-5
    print(json.dumps(line), flush=True)
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
