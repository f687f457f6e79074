"""Operations and bytes the ``glm_moe_dsa`` configuration's kernels need,
summed over its layers (``costs.py`` says what the harness asks of a cost
module). Only useful work counts, and of the attention only the SELECTED
keys: the program may read every page under the selection's bias, and a share
computed from these then reads low, never above 100%.

``dsa_index_decode_bytes``   a request's entry into a wave, every layer's
                             scoring pass: the row's context pages' index
                             keys once each as they lie in the cache (128
                             values a token; a page is fetched whole), the
                             row's index queries and head weights read (the
                             scores a kernel writes for the selection to
                             read are its own choice, not counted). 128 FLOP
                             a byte of key against the v5e's 240: the bytes
                             bind.
``mla_sparse_decode_bytes``  the same entry's latent attention: the
                             ``min(context, index_topk)`` SELECTED latents
                             (``rank + rope`` values each), the absorbed
                             query read and the mix written.
``moe_wave_bytes``, ``moe_prefill_flops``   ``costs_kimi_linear``'s two over
                             this file: of a token's 8 choices over the
                             router's 256, 8 x 16 / 256 = 0.5 fall on the
                             share held here; a 4-row wave's distinct share
                             under uniform routing over 256.

Nothing of a chunk (a miss's piece, a hit's question) is counted beside the
expert products. Its latent attention is an XLA loop (no kernel, so no share:
PERF.md gives its device time from the breakdown); it would be held to ``2 x
(nope + rope + v) x heads`` a (row, SELECTED key) pair. Its scoring pass is a
kernel (``dsa_index_chunk_pallas``) and would be held to ``2 x heads x width``
a (row, key) pair with the key at or before the row, but the harness counts a
miss's work when the miss STARTS and a 32k miss outlasts a traced window, so
such a share read 0.46% in one traced run and 31.6% in the next (PERF.md, PR
56): no entry until work is counted a piece.
"""

from typing import Dict

import costs

WORK_KEYS = (
    "dsa_index_decode_bytes", "mla_sparse_decode_bytes", "moe_wave_bytes", "moe_prefill_flops",
)
WAVE_ROWS_ASSUMED = 4


def _sizes(config: Dict):
    """(layers, expert layers, block tokens, bytes a value)."""
    layers = config["num_hidden_layers"]
    return (
        layers, layers - config["first_k_dense_replace"], config["serving"]["block_tokens"],
        costs.ITEMSIZE[config["torch_dtype"]],
    )


def held_choices(config: Dict) -> float:
    """Of a token's choices, those that fall on the experts held here."""
    return config["num_experts_per_tok"] * config["n_routed_experts"] / config["router_experts"]


def wave_distinct_share(config: Dict) -> float:
    e, k = config["router_experts"], config["num_experts_per_tok"]
    return e * (1 - (1 - k / e) ** WAVE_ROWS_ASSUMED) / (WAVE_ROWS_ASSUMED * k)


def expert_bytes(config: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] * costs.ITEMSIZE[config["torch_dtype"]]


def moe_flops(config: Dict, tokens: int) -> float:
    per_pair = 3 * 2 * config["hidden_size"] * config["moe_intermediate_size"]
    return tokens * held_choices(config) * per_pair * _sizes(config)[1]


def index_query_bytes(config: Dict, rows: int) -> int:
    """The rows' index queries (the served type) and head weights (float32)."""
    heads, width = config["index_n_heads"], config["index_head_dim"]
    return rows * heads * (width * costs.ITEMSIZE[config["torch_dtype"]] + 4)


def dsa_index_decode_bytes(config: Dict, pages: int, rows: int) -> int:
    """One layer: the pages' index keys, the rows' queries and weights."""
    _, _, bt, itemsize = _sizes(config)
    return pages * bt * config["index_head_dim"] * itemsize + index_query_bytes(config, rows)


def mla_sparse_decode_bytes(config: Dict, pages: int, rows: int) -> int:
    """One layer: the selected latents a row (its context is a row's share of
    the entry's pages), the absorbed queries and the mixes (float32)."""
    _, _, bt, itemsize = _sizes(config)
    rank, width = config["kv_lora_rank"], config["kv_lora_rank"] + config["qk_rope_head_dim"]
    selected = min(pages * bt // max(rows, 1), config["index_topk"])
    heads = config["num_attention_heads"]
    return rows * (selected * width * itemsize + heads * (width * itemsize + rank * 4))


def wave_work(config: Dict, pages: int, rows: int) -> Dict[str, float]:
    layers, experts, _, _ = _sizes(config)
    return {
        "dsa_index_decode_bytes": layers * dsa_index_decode_bytes(config, pages, rows),
        "mla_sparse_decode_bytes": layers * mla_sparse_decode_bytes(config, pages, rows),
        "moe_wave_bytes": rows * held_choices(config) * expert_bytes(config) * experts
        * wave_distinct_share(config),
    }


def prefill_work(config: Dict, tokens: int) -> Dict[str, float]:
    return {"moe_prefill_flops": moe_flops(config, tokens)}


def resume_work(config: Dict, pages: int, rows: int) -> Dict[str, float]:
    """A hit's question: ``rows`` positions."""
    return {"moe_prefill_flops": moe_flops(config, rows)}
