"""The whole run at a toy size on the CPU: control flow, counts and checks,
with the program's recorder off (an end-to-end run) and on (what a traced
run reads besides the profile). No number from here is a device metric; the
line is never printed."""

import argparse
import json
import os

import pytest

import accepted
import readers
import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOY = {
    "name": "toy", "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "torch_dtype": "bfloat16",
    "serving": {
        "block_tokens": 16, "cache_blocks": 64, "kv_bytes_per_token": 2 * 2 * 4 * 16 * 2,
        "store_block_kib": 2,  # 16 tokens x 4 heads x 16 x 2 B
    },
}
CLOSED = {
    "loop": "closed", "clients": 3, "schedule_seed": 1, "documents_per_client": 60,
    "asks_per_document": 4, "prefix_tokens": {"32": 2, "64": 1}, "question_tokens": 16,
    "answer_tokens": 32,
}
OPEN = {
    "loop": "open", "rate_rps": 4.0, "max_live": 4, "schedule_seed": 2, "horizon_s": 20,
    "lead_in_s": 1, "prompt_tokens": {"16": 2, "32": 1}, "answer_tokens": {"16": 1, "32": 1},
}
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


# Wider heads, so that a K of one block is the server's smallest unit and the
# file may name it: 16 tokens x 8 heads x 64 x 2 B = 16 KiB.
WIDE = dict(
    TOY, hidden_size=512, num_key_value_heads=8, head_dim=64,
    serving={
        "block_tokens": 16, "cache_blocks": 64, "kv_bytes_per_token": 2 * 2 * 8 * 64 * 2,
        "store_block_kib": 16, "store_unit_kib": 16, "store_values_kib": [[4, 16]],
    },
)


def toy_run(params, seed, program_counters=(), hit_installs=None, toy=TOY, program=None):
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("a rehearsal for the sandbox; the chip runs the real cells")
    import run

    with open(os.path.join(REPO, "benchmarks", "configs", "mistral-7b-v0.3.json")) as f:
        config = dict(toy, program=dict(json.load(f)["program"], **(program or {})))
    if hit_installs is not None:
        config["serving"] = dict(toy["serving"], hit_installs=hit_installs)
    plan = (traffic._closed_plan if params["loop"] == "closed" else traffic._open_plan)("toy", params)
    args = argparse.Namespace(workload="toy", seed=seed, seconds=4.0, trace=0)
    return run.execute(
        args, {"name": "toy", "chips": 1}, config, plan, run.device_line(jax), program_counters
    )


@pytest.mark.parametrize("params", [CLOSED, OPEN], ids=["closed", "open"])
def test_toy_cell_runs_and_checks(params):
    # Two of the program's own counters by name: one of harness.metrics(),
    # one of the connector's get_stats().
    line, res, trace = toy_run(params, 2**31 + 7, ("generated_tokens", "kvmap_len"))
    assert trace is None and res["spans"] is None and res["counters"]["window_compiles"] == 0, res["counters"]
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 8, line
    # Values of 2 KiB in the server's smallest unit: four units of 16 KiB a block.
    usage = line["server"].pop("pool_usage")
    assert line["server"] == {"block_kib": 16, "pool_gib": 2, "unit_kib": 16, "pool_units_per_block": 4}
    assert 0.0 < usage < 0.1
    e2e = res["end_to_end"]
    assert e2e["ttft_p50_ms"] > 0 and e2e["tpot_mean_ms"] > 0 and e2e["tokens_per_s"] > 0
    assert res["counters"]["generated_tokens"] > 0 and res["counters"]["kvmap_len"] > 0
    hits = [r for r in res["rows"] if r["hit"]]
    if params["loop"] == "closed":
        # Which asks hit is fixed by the lists: three of four, to the request.
        assert abs(len(hits) / len(res["rows"]) - 0.75) < 0.15, (len(hits), len(res["rows"]))
        assert all(r["loaded_blocks"] == r["prompt_blocks"] - 1 for r in hits)
    else:
        assert not hits and all(r["late_ms"] >= 0 for r in res["rows"])


@pytest.mark.parametrize("params,suffix", [(CLOSED, ".reuse"), (OPEN, ".chat")], ids=["closed", "open"])
def test_toy_cell_with_spans_on(params, suffix):
    """Recorder on, as ``--trace 1`` turns it on: every span metric that
    BENCHMARK.json lists for a cell of that loop kind has a sample, and the
    run stays correct."""
    import run
    from infinistore_tpu import tracing

    rec = tracing.configure(enabled=True, capacity=run.SPAN_CAPACITY)
    try:
        line, res, trace = toy_run(params, 2**31 + 11)
    finally:
        tracing.configure(enabled=False)
    assert line["correct"] and line["failed"] == 0 and res["counters"]["window_compiles"] == 0, line
    assert rec.dropped == 0 and rec.recorded > 10 * line["attempted"]
    assert res["spans"]["dropped"] == 0 and res["spans"]["profile"] is None
    cell = "mistral7b-prefix-reuse" if suffix == ".reuse" else "mistral7b-unshared-chat"
    specs = [
        spec for spec in (readers.load_layer_metric(m["name"]) for m in run.metrics_for(BENCH, "per_layer", cell))
        if spec["reader"]["kind"] in ("spans", "trace_idle_in")
    ]
    # By name: the span metrics PR 24 brought that the cell still lists; how many more it
    # lists by now is not pinned, and each of them has to find its sample too.
    assert {m + suffix for m in ("after_ready_accounted_pct", "first_wave_wait_p50_ms", "save_snapshot_p50_ms",
                                 "decode_wave_wait_mean_ms", "idle_in_readback_pct")} <= {s["name"] for s in specs}
    view = readers.Run(res["rows"], res["counters"], trace, {}, spans=res["spans"])
    values = {s["name"]: readers.read_layer_metric(s["name"], view) for s in specs}
    for spec in specs:
        name, value = spec["name"], values[spec["name"]]
        if spec["reader"]["kind"] == "trace_idle_in":
            assert value is None, name  # no profile on the CPU
        else:
            assert value is not None and value >= 0.0, name
    # The program's emit stamps and the benchmark's patch tell the same time
    # (a loose bound: this is a shared CPU), and the four parts cover the
    # time from the prefix being ready to the first token.
    # (``emit_stamp_skew_p95_ms``'s file went with PR 55, 0.02-0.03 ms in every cell on the
    # chip; its reader as the file stood.)
    assert readers.KINDS["spans"](view, accepted.RETIRED_READERS["emit_stamp_skew_p95_ms" + suffix]) < 20.0
    assert 50.0 < values["after_ready_accounted_pct" + suffix] <= 100.5, values
    assert values["first_wave_wait_p50_ms" + suffix] > 0


@pytest.mark.parametrize("name,block_tokens,warm,check", [
    ("reuse-sessions-2k-8k", 16, 16, 32),
    ("reuse-sessions-1k-2k", 16, 16, 32),
    ("chat-replay", 16, 16, 32),
    # Blocks of 1,024 tokens under 64-token answers, which complete no block:
    # warm-up and check decode what the traffic decodes, not 1,024 and 2,048.
    ("made-up-64", 1024, 64, 64),
    ("made-up-4", 16, 4, 9),  # never under the rounds the reference comparison reads
])
def test_warm_up_and_check_answers_come_from_the_traffic(name, block_tokens, warm, check):
    import run

    if name.startswith("made-up"):
        plan = traffic._closed_plan(name, dict(CLOSED, answer_tokens=int(name.rsplit("-", 1)[1])))
    else:
        plan = traffic.build_plan(name)
    assert run.warm_answer_tokens(plan, block_tokens) == warm
    assert run.check_answer_tokens(plan, block_tokens) == check >= run.DECODE_STEPS_CHECKED + 1


def test_a_policy_the_built_caches_do_not_have_stops_the_run_at_build():
    with pytest.raises(ValueError, match=r"tensor 2 of layer 1 .* 2 layers of \[2, 2\] tensors"):
        toy_run(CLOSED, 2**31 + 13, hit_installs=[{"layers": [1], "tensor": 2, "last_blocks": 1}])


@pytest.mark.parametrize("policy,seed", [
    (None, 2**31 + 17), ([{"layers": [1], "tensor": 1, "last_blocks": 1}], 2**31 + 19),
], ids=["every_block", "last_block"])
def test_a_run_whose_install_alters_a_block_is_not_correct(monkeypatch, capfd, policy, seed):
    """The rest of a run over a timed path that is broken underneath: the
    adapter's install leaves one byte of one block of the last layer's V
    other than it was saved. Window, drain and checks run as ever, and
    ``correct`` comes out false for that reason: with the policy a file
    without the key has, and where the file calls that tensor a checkpoint
    of one block and the altered block is a hit's last."""
    import jax.numpy as jnp

    import run
    from infinistore_tpu.engine import EngineKVAdapter

    real = EngineKVAdapter.install_kv

    async def install_kv(self, prefetch, caches, block_table):
        out, loaded = await real(self, prefetch, caches, block_table)
        if loaded:
            last = int(block_table[loaded // TOY["serving"]["block_tokens"] - 1])
            k, v = out[-1]
            out = list(out[:-1]) + [(k, v.at[last, 0, 0, 0].add(jnp.asarray(1.0, v.dtype)))]
        return out, loaded

    monkeypatch.setattr(EngineKVAdapter, "install_kv", install_kv)
    line, res, _ = toy_run(CLOSED, seed, hit_installs=policy)
    assert not line["correct"] and line["failed"] == 0 and line["attempted"] >= 8, line
    assert "installed blocks: layer 1 tensor 1 block" in capfd.readouterr().err


def test_a_run_whose_fetch_does_not_follow_the_policy_is_not_correct(monkeypatch, capfd):
    """The fetch is part of ``correct``: a program that counts more store
    values fetched than the configuration says a hit installs (here two more,
    a K and a V, noted as the install returns) would read a wrong
    ``fetch_gbps``; the run says so, for the window's hits and the checks'."""
    import run
    from infinistore_tpu.engine import EngineKVAdapter

    real = EngineKVAdapter.install_kv

    async def install_kv(self, prefetch, caches, block_table):
        out = await real(self, prefetch, caches, block_table)
        prefetch.blocks_fetched += 2
        return out

    monkeypatch.setattr(EngineKVAdapter, "install_kv", install_kv)
    line, res, _ = toy_run(CLOSED, 2**31 + 23)
    assert not line["correct"] and line["failed"] == 0 and line["attempted"] >= 8, line
    err = capfd.readouterr().err
    hits = [r for r in res["rows"] if r["hit"]]
    assert hits and all(r["fetched_values"] == 4 * r["hit_blocks"] + 2 for r in hits)
    assert err.count("store values for a hit of") >= len(hits) + 2
    assert "prompt 48 partial hit: fetched 10 store values for a hit of 2 blocks, the configuration's policy names 8" in err


@pytest.mark.parametrize("usage", [None, 0.85], ids=["as-it-is", "over-the-threshold"])
def test_a_file_that_names_its_unit_has_its_pool_checked_at_the_close(monkeypatch, capfd, usage):
    """A configuration that gives ``store_unit_kib`` is held to its pool:
    under the share from which the server evicts on demand the run is
    correct, and where the server reports more it is not, with both numbers."""
    import infinistore_tpu as its

    real = its.InfinityConnection.get_stats
    if usage is not None:
        monkeypatch.setattr(its.InfinityConnection, "get_stats", lambda self: dict(real(self), usage=usage))
    line, res, _ = toy_run(CLOSED, 2**31 + 29, toy=WIDE)
    server = dict(line["server"])
    assert server.pop("pool_usage") == (usage or pytest.approx(0.05, abs=0.05))
    assert server == {"block_kib": 16, "pool_gib": 2, "unit_kib": 16, "pool_units_per_block": 4}
    assert line["failed"] == 0 and line["correct"] == (usage is None), line
    said = "the server's pool is 0.85 used at the close of the run, and it evicts on demand from 0.8" in capfd.readouterr().err
    assert said == (usage is not None)
