"""Operations and bytes the ``granitemoehybrid`` configuration's kernels need,
summed over its unlike layers (``costs.py`` says what the harness asks of a
cost module, and holds the attention kernels' counts). Only useful work
counts, so a share computed from these can only read low.

A layer is a Mamba-2 mixer on a state OR grouped-query attention over K/V
(``layer_types``), and EVERY layer has the expert layer behind it. The page is
the mixer's snapshot interval (2,048 tokens), so ``pages x block_tokens`` would
count up to 2,047 keys a row that no query reads: the attention's counts are
``costs_falcon_h1``'s, over the attention layers alone.

``ragged_decode_bytes``  a request's entry into a wave, the attention layers:
                         the K and V of the keys its rows must read, once (a
                         row's last page counts for ONE key, every other page
                         whole), and each row's query and output.
``chunk_attn_flops`` /   every piece of a miss and of a hit's resume, the
``chunk_attn_bytes``     attention layers: a piece of ``r`` rows that ends a
                         context of ``c`` tokens attends ``c - r`` keys before
                         it and itself up to the diagonal, and reads the
                         context's K and V once (``costs_falcon_h1.pieces``).
``ssd_chunk_flops``      the same pieces' state-space walk, the Mamba layers
                         (``costs_falcon_h1.ssd_chunk_flops``, one group here).
                         No kernel: PERF.md gives its device time.
``ssd_step_bytes``       a wave row's state and tail, read once and written
                         once, the Mamba layers (no kernel either).
``moe_wave_bytes``       its rows' chosen experts' weights among those HELD
                         here, every layer: of a token's ``k`` choices over the
                         router's experts, ``k x held / routed`` fall on this
                         share on average (10 x 36 / 72 = 5), times the share
                         of DISTINCT experts among a 4-row wave's pairs under
                         uniform routing (``costs_afmoe``'s formula over the
                         router's width: a wave streams each once).
``moe_prefill_flops``    the grouped products of a miss's pieces or of a
                         resume: tokens x the same 5 held choices x 3 products
                         of 2 x hidden x width, every layer; an expectation
                         under uniform routing, not a count. The shared expert
                         is a dense product beside the grouped one, not counted.
"""

from typing import Dict, Tuple

import costs
from costs_falcon_h1 import pieces, ssd_chunk_flops

WORK_KEYS = (
    "ragged_decode_bytes", "chunk_attn_flops", "chunk_attn_bytes", "ssd_chunk_flops",
    "ssd_step_bytes", "moe_wave_bytes", "moe_prefill_flops",
)
WAVE_ROWS_ASSUMED = 4


def _layers(config: Dict) -> Tuple[int, int, int]:
    """(attention layers, Mamba layers, expert layers)."""
    attention = sum(kind == "attention" for kind in config["layer_types"])
    return attention, len(config["layer_types"]) - attention, config["num_hidden_layers"]


def _attention(config: Dict) -> Tuple[int, int, int, int]:
    heads = config["num_attention_heads"]
    return (
        heads, config["num_key_value_heads"], config["hidden_size"] // heads,
        costs.ITEMSIZE[config["torch_dtype"]],
    )


def held_choices(config: Dict) -> float:
    """Of a token's choices, those that fall on the experts held here."""
    return config["num_experts_per_tok"] * config["num_local_experts"] / config["router_experts"]


def wave_distinct_share(config: Dict) -> float:
    e, k = config["router_experts"], config["num_experts_per_tok"]
    return e * (1 - (1 - k / e) ** WAVE_ROWS_ASSUMED) / (WAVE_ROWS_ASSUMED * k)


def expert_bytes(config: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * config["hidden_size"] * config["intermediate_size"] * costs.ITEMSIZE[config["torch_dtype"]]


def moe_flops(config: Dict, tokens: int) -> float:
    per_pair = 3 * 2 * config["hidden_size"] * config["intermediate_size"]
    return tokens * held_choices(config) * per_pair * _layers(config)[2]


def state_bytes(config: Dict) -> int:
    """One Mamba layer's state (float32) and convolution tail (the served type)."""
    width = config["mamba_n_heads"] * config["mamba_d_head"]
    conv = width + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    tail = (config["mamba_d_conv"] - 1) * conv * costs.ITEMSIZE[config["torch_dtype"]]
    return width * config["mamba_d_state"] * 4 + tail


def chunk_work(config: Dict, context: int, rows: int) -> Dict[str, float]:
    heads, kv_heads, head_dim, itemsize = _attention(config)
    attention, mamba, _ = _layers(config)
    flops = bytes_ = 0
    for end, r in pieces(config, context, rows):
        flops += costs.chunk_attn_flops(end, r, heads, head_dim)
        bytes_ += costs.chunk_attn_bytes(end, r, heads, kv_heads, head_dim, itemsize)
    return {
        "chunk_attn_flops": attention * flops, "chunk_attn_bytes": attention * bytes_,
        "ssd_chunk_flops": mamba * ssd_chunk_flops(config, rows),
        "moe_prefill_flops": moe_flops(config, rows),
    }


def wave_work(config: Dict, pages: int, rows: int) -> Dict[str, float]:
    heads, kv_heads, head_dim, itemsize = _attention(config)
    attention, mamba, experts = _layers(config)
    keys = (pages - rows) * config["serving"]["block_tokens"] + rows
    kv = 2 * keys * kv_heads * head_dim * itemsize
    qo = 2 * rows * heads * head_dim * itemsize
    return {
        "ragged_decode_bytes": attention * (kv + qo),
        "ssd_step_bytes": mamba * rows * 2 * state_bytes(config),
        "moe_wave_bytes": rows * held_choices(config) * expert_bytes(config) * experts
        * wave_distinct_share(config),
    }


def prefill_work(config: Dict, tokens: int) -> Dict[str, float]:
    """A miss: ``tokens`` tokens from position 0, a piece a block."""
    return chunk_work(config, tokens, tokens)


def resume_work(config: Dict, pages: int, rows: int) -> Dict[str, float]:
    """A hit's resume: ``rows`` new tokens whose context ends on page
    ``pages``. The rows begin at a block boundary, so the context's last page
    holds ``(rows - 1) % block_tokens + 1`` tokens."""
    bt = config["serving"]["block_tokens"]
    return chunk_work(config, (pages - 1) * bt + (rows - 1) % bt + 1, rows)
