"""Operations and bytes the ``kimi_linear`` configuration's kernels need,
summed over its unlike layers (``costs.py`` says what the harness asks of a
cost module). Only useful work counts, so a share computed from these can
only read low.

``mla_decode_bytes``   a request's entry into a wave, the latent layers: the
                       row's context pages once each as they lie in the cache
                       (``rank + rope`` values a token; a page is fetched
                       whole, the last one too), the absorbed query read and
                       the mix written. 60 FLOP a byte: the bytes bind.
``kda_step_bytes``     the same entry's KDA layers: the row's state and tail
                       read once and written once (no kernel: PERF.md gives
                       its device time from the breakdown).
``moe_wave_bytes``     its rows' chosen experts' weights among those HELD
                       here, every expert layer: of a token's ``k`` choices
                       over the router's experts, ``k x held / routed`` fall on
                       this share on average (8 x 128 / 256 = 4), times the
                       share of DISTINCT experts among a 4-row wave's pairs
                       under uniform routing (``costs_afmoe``'s formula over
                       the router's width: a wave streams each once).
``moe_prefill_flops``  the grouped products of a miss's pieces or of a resume:
                       tokens x the same 4 held choices x 3 products of 2 x
                       hidden x width, every expert layer; an expectation
                       under uniform routing, not a count. The shared expert
                       is a dense product beside the grouped one, not counted.
"""

from typing import Dict

import costs

WORK_KEYS = ("mla_decode_bytes", "kda_step_bytes", "moe_wave_bytes", "moe_prefill_flops")
WAVE_ROWS_ASSUMED = 4


def _layers(config: Dict):
    """(KDA layers, MLA layers, expert layers)."""
    linear = config["linear_attn_config"]
    return (
        len(linear["kda_layers"]), len(linear["full_attn_layers"]),
        config["num_hidden_layers"] - config["first_k_dense_replace"],
    )


def held_choices(config: Dict) -> float:
    """Of a token's choices, those that fall on the experts held here."""
    return config["num_experts_per_token"] * config["num_experts"] / config["router_experts"]


def wave_distinct_share(config: Dict) -> float:
    e, k = config["router_experts"], config["num_experts_per_token"]
    return e * (1 - (1 - k / e) ** WAVE_ROWS_ASSUMED) / (WAVE_ROWS_ASSUMED * k)


def expert_bytes(config: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] * costs.ITEMSIZE[config["torch_dtype"]]


def moe_flops(config: Dict, tokens: int) -> float:
    per_pair = 3 * 2 * config["hidden_size"] * config["moe_intermediate_size"]
    return tokens * held_choices(config) * per_pair * _layers(config)[2]


def mla_decode_bytes(config: Dict, pages: int, rows: int) -> int:
    """One latent layer: the pages' latents, the rows' absorbed queries (the
    served type) and their mixes (float32)."""
    itemsize = costs.ITEMSIZE[config["torch_dtype"]]
    rank, width = config["kv_lora_rank"], config["kv_lora_rank"] + config["qk_rope_head_dim"]
    heads = config["num_attention_heads"]
    return pages * config["serving"]["block_tokens"] * width * itemsize + rows * heads * (
        width * itemsize + rank * 4
    )


def kda_state_bytes(config: Dict) -> int:
    """One KDA layer's state (float32) and convolution tail (the served type)."""
    linear = config["linear_attn_config"]
    h, d = linear["num_heads"], linear["head_dim"]
    tail = (linear["short_conv_kernel_size"] - 1) * 3 * h * d * costs.ITEMSIZE[config["torch_dtype"]]
    return h * d * d * 4 + tail


def wave_work(config: Dict, pages: int, rows: int) -> Dict[str, float]:
    kda, mla, experts = _layers(config)
    return {
        "mla_decode_bytes": mla * mla_decode_bytes(config, pages, rows),
        "kda_step_bytes": kda * rows * 2 * kda_state_bytes(config),
        "moe_wave_bytes": rows * held_choices(config) * expert_bytes(config) * experts
        * wave_distinct_share(config),
    }


def prefill_work(config: Dict, tokens: int) -> Dict[str, float]:
    return {"moe_prefill_flops": moe_flops(config, tokens)}


def resume_work(config: Dict, pages: int, rows: int) -> Dict[str, float]:
    return {"moe_prefill_flops": moe_flops(config, rows)}
