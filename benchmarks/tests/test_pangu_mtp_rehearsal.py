"""The ``openpangu-ultra-moe-718b`` files at a toy size on the CPU, through
``run.execute`` with the file's own ``program`` (config class, choices,
reference, costs): a model that DRAFTS, whose every round is a chunk ``[token,
draft]``, under the harness as it stands: its warm-up of bare one-token waves
pins the very buckets the drafting requests land on (nothing compiles in the
window), its check reads the draft site's gap behind the expert sites', a hit
is six latent layers a block and ONE boundary row, and the new counters are
read by the names the metric files give. Control flow, counts and checks; no
number from here is a device metric."""

import argparse
import importlib
import json
import os

import pytest

import cache_geometry
import readers
import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmarks", "configs", "openpangu-ultra-moe-718b.json")) as f:
    REAL = json.load(f)
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELL = "openpangu-mtp-long-prefix-reuse"

# The file's keys at a toy size whose values are whole KiB: a latent block of
# 16 tokens x (24 + 8) bf16 and a boundary row of 512 bf16, 1 KiB each.
LAYERS = 3
TOY = dict(
    REAL, name="toy-pangu", hidden_size=512, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=24,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=4, router_experts=8, experts_held=[0, 4],
    num_experts_per_tok=2, vocab_size=512, num_hidden_layers=LAYERS,
    serving={
        "block_tokens": 16, "cache_blocks": 64, "kv_bytes_per_token": (LAYERS + 2) * 1024 // 16,
        "store_block_kib": 1, "hit_installs": [{"layers": [LAYERS], "tensor": 1, "last_blocks": 1}],
    },
)
CLOSED = {
    "loop": "closed", "clients": 3, "schedule_seed": 7, "documents_per_client": 24,
    "asks_per_document": 4, "prefix_tokens": {"64": 2, "128": 1}, "question_tokens": 5,
    "answer_tokens": 20,
}
NEW = ("mtp_accepted_share.reuse", "mtp_tokens_per_round.reuse")


def test_the_real_files_serving_numbers_agree_with_themselves():
    """What ``run.py`` sizes the server from, before anything is built."""
    layout = cache_geometry.store_layout(REAL["serving"])
    assert (layout.unit_kib, layout.block_kib, layout.pool_units_per_block) == (16, 1152, 433)
    assert layout.pool_bytes_per_block == (REAL["serving"]["kv_bytes_per_token"] + 1) * 1024
    plan = traffic.build_plan("reuse-sessions-8k-32k-256ans")
    assert len(plan.requests) == 164 and {r.answer_tokens for r in plan.requests} == {256}
    assert sum({r.doc: r.prefix_tokens for r in plan.requests}.values()) == 589_824
    assert cache_geometry.pool_gib(traffic.store_bytes(plan, layout.pool_bytes_per_token)) == 8
    costs = importlib.import_module(REAL["program"]["costs"])
    # No ``moe_wave_bytes``: the siblings' uniform count read 98.7 and 101.4% of
    # the roofline on the chip (a chunk's two rows route alike), so the cell is
    # on no wave share of the expert kernel (the costs module says what it takes).
    assert set(costs.WORK_KEYS) == {"mla_decode_bytes", "moe_prefill_flops"}
    # A chunk [token, draft] over 9 + 9 pages, six latent layers: the context
    # ONCE, two rows' queries and mixes.
    wave = costs.wave_work(REAL, 18, 2)
    assert wave["mla_decode_bytes"] == 6 * (9 * 1024 * 1152 + 2 * 128 * (1152 + 2048))
    assert set(wave) == {"mla_decode_bytes"}
    assert abs(costs.held_choices(REAL) - 0.25) < 1e-9
    resume, miss = costs.resume_work(REAL, 9, 127), costs.prefill_work(REAL, 8319)
    assert set(miss) == set(resume) == {"moe_prefill_flops"}
    assert resume["moe_prefill_flops"] == 127 * 0.25 * 3 * 2 * 7680 * 2048 * 4
    assert costs.resume_rewrite_flops(REAL) == 2 * 7680 * (15360 + 576)


def test_the_cell_joins_the_lists_the_issue_names_and_brings_two_metrics():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "openpangu-ultra-moe-718b", "reuse-sessions-8k-32k-256ans", 1,
    )
    listed = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", ())}
    glm = {m["name"] for m in BENCH["per_layer"] if "glm5-long-prefix-reuse" in m.get("workloads", ())}
    sparse = {n for n in glm if n.startswith(("dsa_", "mla_sparse_"))}
    assert len(sparse) == 7 and not listed & sparse
    # Not GLM-5's ``mla_chunk_attn_dev_ms.reuse`` either: it reads a MISS's whole-block
    # pieces, and at 256-token answers a traced 8 s holds none more often than not (two
    # traced chip runs of two: PERF.md, PR 62); a listed metric has to be there to read.
    # Nor ``moe_held_wave_roofline.reuse``: no count of the wave's expert bytes that is
    # right for a chunk's two rows can be made from shapes alone (``costs_pangu_mtp``).
    unread = {"mla_chunk_attn_dev_ms.reuse", "moe_held_wave_roofline.reuse"}
    assert listed == (glm - sparse - unread) | set(NEW) | {
        "mla_decode_roofline.reuse", "hit_bytes_share.reuse", "hit_state_share.reuse",
    }
    for name in NEW:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s"
    assert readers.counter_keys(NEW) == {
        "spec_accepted_tokens", "spec_drafted_tokens", "spec_emitted_tokens", "spec_rounds",
    }


@pytest.mark.parametrize("key", sorted(k for k in REAL if isinstance(REAL[k], (int, float)) and not isinstance(REAL[k], bool)))
def test_every_number_of_the_catalog_row_stands_or_is_reduced(key):
    published = {
        "first_k_dense_replace": 3, "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "moe_intermediate_size": 2048, "n_routed_experts": 256,
        "n_shared_experts": 1, "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_theta": 25600000, "routed_scaling_factor": 2.5, "v_head_dim": 128, "vocab_size": 153600,
    }
    if key == "router_experts":
        assert REAL[key] == published["n_routed_experts"]
    elif key in REAL["reduced"]:
        assert REAL[key] != published[key] and REAL["published"][key] == published[key]
    else:
        assert REAL[key] == published[key]


def test_toy_pangu_cell_runs_and_checks():
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("a rehearsal for the sandbox; the chip runs the real cell")
    import run

    plan = traffic._closed_plan("toy", CLOSED)
    counters = sorted(readers.counter_keys(NEW) | {
        "hit_bytes_fetched", "hit_bytes_whole_prefix", "hit_state_bytes_fetched", "moe_pairs",
    })
    args = argparse.Namespace(workload="toy", seed=2**31 + 62, seconds=4.0, trace=0)
    line, res, _ = run.execute(
        args, {"name": "toy", "chips": 1}, TOY, plan, run.device_line(jax), counters
    )
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 4, line
    # The bare one-token waves of the warm-up pinned every bucket the drafting
    # requests' chunks of two landed on.
    assert res["counters"]["window_compiles"] == 0, res["counters"]
    # Two prompt classes x (miss, partial hit): the expert sites' gaps and,
    # last, the drafted id's over the reference's own draft logits.
    assert len(line["compared"]) == 4 and all("max_gap" in c for c in line["compared"]), line["compared"]
    c = res["counters"]
    hits = [r for r in res["rows"] if r["hit"]]
    # Every block's latents of the three main layers and the MTP layer, and ONE boundary row.
    assert hits and all(r["fetched_values"] == r["hit_blocks"] * (LAYERS + 1) + 1 for r in hits)
    assert 0 < c["hit_state_bytes_fetched"] == 1024 * len(hits) < c["hit_bytes_fetched"] < c["hit_bytes_whole_prefix"]
    # A round after a request's first verifies one draft; at a vocabulary of
    # 512 some land, and a round that accepts one emits two tokens.
    assert c["spec_rounds"] > 0 and c["spec_drafted_tokens"] > 0.8 * c["spec_rounds"], c
    assert c["spec_emitted_tokens"] == c["spec_rounds"] + c["spec_accepted_tokens"], c
    view = readers.Run(res["rows"], c, None, {})
    assert readers.read_layer_metric("mtp_tokens_per_round.reuse", view) == pytest.approx(
        c["spec_emitted_tokens"] / c["spec_rounds"]
    )
    assert readers.read_layer_metric("mtp_accepted_share.reuse", view) == pytest.approx(
        100.0 * c["spec_accepted_tokens"] / c["spec_drafted_tokens"]
    )
