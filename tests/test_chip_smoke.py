"""chip_smoke.py off the chip: it must refuse, and its traffic must not rot.

The smoke's contract is that it FAILS wherever JAX finds no accelerator (the
driver checks exactly that before it runs it on the chip). What a CPU box
can still keep honest is the control flow of its traffic phase — the same
``run_traffic`` the chip runs at Llama-3-8B widths, here on a toy width with
the XLA fallbacks — so a harness change that breaks the smoke shows up in
tier-1 and not as a burnt chip run. Kernel parity, the Pallas-branch checks
and the four-chip phase are chip-only and are not called here.
"""

import asyncio
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

import infinistore_tpu as its

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def test_refuses_without_a_tpu_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, _SMOKE],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr  # names the platform it found
    lines = proc.stdout.strip().splitlines()
    device = json.loads(lines[0].split("device: ")[1].split(" versions")[0])
    assert (device["platform"], device["kind"]) == ("cpu", "cpu")
    assert not any(line.startswith('{"ok"') for line in lines)


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(tmp_path):
    """One rule for every entry point that compiles: where
    JAX_COMPILATION_CACHE_DIR is set, that is the cache and no code sets
    another; where it is not, it is <checkout>/.jax_cache — a fixed path."""
    probe = (
        "from infinistore_tpu import compile_cache; import jax; "
        "print(compile_cache.enable()); print(jax.config.jax_compilation_cache_dir)"
    )

    def run(env):
        out = subprocess.run(
            [sys.executable, "-c", probe], env=env, cwd=_REPO,
            capture_output=True, text=True, timeout=120, check=True,
        )
        return out.stdout.split()

    base = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    assert run(base) == [os.path.join(_REPO, ".jax_cache")] * 2
    outside = str(tmp_path / "cache")
    assert run({**base, "JAX_COMPILATION_CACHE_DIR": outside}) == [outside] * 2


def _toy_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    from infinistore_tpu.models import LlamaConfig

    cfg = LlamaConfig(
        vocab=512, dim=128, n_layers=8, n_heads=4, n_kv_heads=2, ffn_dim=256,
        block_tokens=16, dtype=jnp.bfloat16,
    )
    sizes = smoke.Sizes(
        prompt_tokens=64, suffix_tokens=32, gen_tokens=16, num_blocks=64,
        max_req_blocks=8,
    )
    return smoke, cfg, sizes


def test_donation_phase_runs_on_a_toy_width():
    from infinistore_tpu.models import init_params

    smoke, cfg, sizes = _toy_smoke()
    report = smoke.check_steps_donate(
        cfg, init_params(cfg, jax.random.PRNGKey(0)), sizes
    )
    assert report == {
        "prefill": "donated, byte-right", "decode_step": "donated, byte-right",
    }


def test_traffic_phase_runs_on_a_toy_width(server):
    smoke, cfg, sizes = _toy_smoke()
    from infinistore_tpu.models import init_params

    conn = its.InfinityConnection(its.ClientConfig(
        host_addr="127.0.0.1", service_port=server["port"], log_level="error",
    ))
    conn.connect()
    try:
        report = asyncio.run(smoke.run_traffic(
            conn, cfg, init_params(cfg, jax.random.PRNGKey(0)), sizes,
            smoke.Compiles(),
        ))
    finally:
        conn.close()
    assert report["cold"]["computed_blocks"] == [4]
    assert report["full_hit"]["loaded_blocks"] == [4]
    assert report["partial_hit"]["computed_blocks"] == [2]
    assert report["ragged_waves"]["loaded_blocks"] == [1, 2, 4, 6]
    assert report["staging_reuse"]["result"] == "byte-identical"
    assert report["steady_full_hit"]["compiles"] == 0
