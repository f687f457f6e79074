"""The work a configuration's cost module gives for a traced call is, to the
byte, what ``run.py`` ``trace_results`` computed at PR 25 (commit 145c7a6):
``n_layers x costs.ragged_decode_bytes(pages, 1, block_tokens, n_heads,
n_kv_heads, head_dim, 2)`` for a request's entry into a wave and ``n_layers x
costs.flash_prefill_flops(tokens, n_heads, head_dim)`` for a prefill. The
numbers below were taken from that commit's code."""

import importlib
import json
import os

import pytest

BENCHMARKS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = {
    "mistral-7b-v0.3": {
        "wave": {(1, 1): 1310720, (9, 1): 9699328, (137, 1): 143917056, (524, 1): 549715968,
                 (1048, 1): 1099169792},
        "prefill": {64: 545259520, 1000: 131203072000, 2176: 620907986944, 8320: 9074208931840},
    },
    "deepseek-llm-7b": {
        "wave": {(1, 1): 4177920, (9, 1): 35635200, (137, 1): 538951680, (524, 1): 2060697600,
                 (1048, 1): 4121149440},
        "prefill": {64: 511180800, 1000: 123002880000, 2176: 582101237760, 8320: 8507070873600},
    },
}
CASES = [
    (name, kind, size) for name, kinds in GOLDEN.items() for kind, sizes in kinds.items() for size in sizes
]


@pytest.mark.parametrize("name,kind,size", CASES, ids=[f"{n}-{k}-{s}" for n, k, s in CASES])
def test_named_cost_module_gives_the_parents_numbers(name, kind, size):
    with open(os.path.join(BENCHMARKS, "configs", f"{name}.json")) as f:
        config = json.load(f)
    costs = importlib.import_module(config["program"]["costs"])
    if kind == "wave":
        assert costs.wave_work(config, *size) == {"ragged_decode_bytes": GOLDEN[name][kind][size]}
    else:
        assert costs.prefill_work(config, size) == {"flash_prefill_flops": GOLDEN[name][kind][size]}
    assert set(costs.WORK_KEYS) == {
        "ragged_decode_bytes", "flash_prefill_flops", "chunk_attn_flops", "chunk_attn_bytes",
    }


def by_hand(heads, head_dim, layers, context, rows):
    """QK^T and PV, 2 x 2 x D a (row, key) pair and head, row by row: row i
    of the chunk attends the prefix and the chunk up to itself."""
    pairs = sum(context - rows + i + 1 for i in range(rows))
    return layers * 4 * heads * head_dim * pairs


@pytest.mark.parametrize("name,pages,rows,want", [
    # 2k / 4k / 8k prefixes and a 128-token question, 16-token pages, 16 layers x 32 heads x 128.
    ("mistral-7b-v0.3", 136, 128, 70_883_737_600),
    ("mistral-7b-v0.3", 264, 128, 139_603_214_336),
    ("mistral-7b-v0.3", 520, 128, 277_042_167_808),
    # 1k / 2k prefixes, 15 layers x 32 heads x 128.
    ("deepseek-llm-7b", 72, 128, 34_241_249_280),
    ("deepseek-llm-7b", 136, 128, 66_453_504_000),
    # A chunk that is the whole context is a causal prefill; one row is a decode row.
    ("mistral-7b-v0.3", 8, 128, None),
    ("mistral-7b-v0.3", 520, 1, None),
], ids=lambda v: str(v))
def test_resume_work_counts_the_causal_pairs_of_a_chunk_over_its_prefix(name, pages, rows, want):
    import costs

    with open(os.path.join(BENCHMARKS, "configs", f"{name}.json")) as f:
        config = json.load(f)
    costs_named = importlib.import_module(config["program"]["costs"])
    got = costs_named.resume_work(config, pages, rows)
    heads, d, layers = config["num_attention_heads"], config["head_dim"], config["num_hidden_layers"]
    # The context's K and V once, the chunk's Q in and O out, bf16.
    nbytes = layers * 2 * (pages * 16 * config["num_key_value_heads"] + rows * heads) * d * 2
    assert got == {"chunk_attn_flops": by_hand(heads, d, layers, pages * 16, rows), "chunk_attn_bytes": nbytes}
    if want is not None:
        assert got["chunk_attn_flops"] == want
    if pages * 16 == rows:
        assert got["chunk_attn_flops"] == layers * costs.flash_prefill_flops(rows, heads, d)
    if rows == 1:
        assert got["chunk_attn_flops"] == layers * costs.ragged_decode_flops(pages, 16, heads, d)
    assert got["chunk_attn_flops"] <= layers * 4 * heads * d * rows * pages * 16  # never over rows x keys


def test_the_resumes_kernel_is_compute_bound_under_gqa_and_memory_bound_under_mha():
    import costs

    ridge = 197e12 / 819e9  # the v5e's FLOP a byte
    gqa = costs.chunk_attn_flops(8320, 128, 32, 128) / costs.chunk_attn_bytes(8320, 128, 32, 8, 128, 2)
    mha = costs.chunk_attn_flops(2176, 128, 32, 128) / costs.chunk_attn_bytes(2176, 128, 32, 32, 128, 2)
    assert 470 < gqa < 490 and 110 < mha < 125 and mha < ridge < gqa
