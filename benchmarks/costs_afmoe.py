"""Operations and bytes the ``afmoe`` configuration's kernels need, summed
over its unlike layers (``costs.py`` says what the harness asks of a cost
module; the kernel functions are that module's, so one count serves both).

Only useful work counts, and a sliding layer's is what lies inside its
window: a decode row attends the pages from its window's first on (at most
``window / block_tokens + 1``: the oldest is fetched whole though part of it
lies behind the window, as a full layer's last page is fetched whole), a
prompt's flash attention the band's pairs, a resume the keys its rows see.
A share computed from these can only read low.

The expert layer, beside the attention keys of ``costs.py``:

``moe_prefill_flops``  the grouped products of a miss or of a resume: tokens x
                       k chosen experts x 3 products of 2 x hidden x width,
                       every expert layer; exact whatever the routing. The
                       shared expert is a dense product of its own beside the
                       grouped one and is not counted: the metric that reads
                       this divides by the grouped product's device time.
``moe_wave_bytes``     a request's entry into a wave: its rows' k chosen
                       experts' weights, every expert layer, times
                       ``WAVE_DISTINCT_SHARE``: a wave streams each DISTINCT
                       expert once, and of the (row, expert) pairs of a wave
                       of four rows (the widest three clients make: three
                       rows padded to four repeat one) under uniform routing
                       ``E (1 - (1 - k / E) ** 4) / (4 k)`` are distinct. A
                       narrower wave shares less, so it reads up to 9% low
                       and never high on average (``test_costs_afmoe.py``
                       draws the waves).
"""

from typing import Dict

import costs

WORK_KEYS = costs.WORK_KEYS + ("moe_prefill_flops", "moe_wave_bytes")
SLIDING = "sliding_attention"
WAVE_ROWS_ASSUMED = 4


def _layers(config: Dict):
    """(sliding layers, full layers, expert layers)."""
    sliding = sum(t == SLIDING for t in config["layer_types"])
    return sliding, len(config["layer_types"]) - sliding, (
        config["num_hidden_layers"] - config["num_dense_layers"]
    )


def _attn(config: Dict):
    return (
        config["serving"]["block_tokens"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
        costs.ITEMSIZE[config["torch_dtype"]], config["sliding_window"],
    )


def wave_distinct_share(config: Dict) -> float:
    e, k = config["num_experts"], config["num_experts_per_tok"]
    return e * (1 - (1 - k / e) ** WAVE_ROWS_ASSUMED) / (WAVE_ROWS_ASSUMED * k)


def expert_bytes(config: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] * costs.ITEMSIZE[config["torch_dtype"]]


def moe_flops(config: Dict, tokens: int) -> int:
    _, _, experts = _layers(config)
    per_pair = 3 * 2 * config["hidden_size"] * config["moe_intermediate_size"]
    return tokens * config["num_experts_per_tok"] * per_pair * experts


def window_pages(pages: int, rows: int, block_tokens: int, window: int) -> int:
    """Of ``pages`` context pages over ``rows`` decode rows, those a sliding
    layer walks: a row's last ``window / block_tokens + 1`` at most."""
    return min(pages, rows * (window // block_tokens + 1))


def wave_work(config: Dict, pages: int, rows: int) -> Dict[str, float]:
    bt, heads, kv_heads, head_dim, itemsize, window = _attn(config)
    sliding, full, experts = _layers(config)
    decode = lambda p: costs.ragged_decode_bytes(p, rows, bt, heads, kv_heads, head_dim, itemsize)
    return {
        "ragged_decode_bytes": full * decode(pages) + sliding * decode(window_pages(pages, rows, bt, window)),
        "moe_wave_bytes": rows * config["num_experts_per_tok"] * expert_bytes(config) * experts
        * wave_distinct_share(config),
    }


def band_pairs(seq: int, window: int) -> int:
    """(query, key) pairs of a prompt of ``seq`` tokens under a sliding
    window: row i sees ``min(i + 1, window)`` keys."""
    w = min(seq, window)
    return w * (w + 1) // 2 + (seq - w) * window


def prefill_work(config: Dict, tokens: int) -> Dict[str, int]:
    _, heads, _, head_dim, _, window = _attn(config)
    sliding, full, _ = _layers(config)
    return {
        "flash_prefill_flops": full * costs.flash_prefill_flops(tokens, heads, head_dim)
        + sliding * 4 * heads * head_dim * band_pairs(tokens, window),
        "moe_prefill_flops": moe_flops(config, tokens),
    }


def chunk_window_pairs(context: int, rows: int, window: int) -> int:
    """(row, key) pairs of a chunk of ``rows`` tokens ending a context of
    ``context`` under a sliding window: row i sees ``min(context - rows + i
    + 1, window)`` keys."""
    return sum(min(context - rows + i + 1, window) for i in range(rows))


def resume_work(config: Dict, pages: int, rows: int) -> Dict[str, int]:
    bt, heads, kv_heads, head_dim, itemsize, window = _attn(config)
    sliding, full, _ = _layers(config)
    context = pages * bt
    seen = min(context, window + rows - 1)  # keys the chunk's rows see between them
    return {
        "chunk_attn_flops": full * costs.chunk_attn_flops(context, rows, heads, head_dim)
        + sliding * 4 * heads * head_dim * chunk_window_pairs(context, rows, window),
        "chunk_attn_bytes": full * costs.chunk_attn_bytes(context, rows, heads, kv_heads, head_dim, itemsize)
        + sliding * costs.chunk_attn_bytes(seen, rows, heads, kv_heads, head_dim, itemsize),
        "moe_prefill_flops": moe_flops(config, rows),
    }
