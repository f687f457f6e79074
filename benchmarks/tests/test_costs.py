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
    assert set(costs.WORK_KEYS) == {"ragged_decode_bytes", "flash_prefill_flops"}
