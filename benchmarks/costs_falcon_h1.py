"""Operations and bytes the ``falcon_h1`` configuration's kernels need
(``costs.py`` says what the harness asks of a cost module, and holds the
attention kernels' counts: this model's attention is grouped-query K/V through
the same two kernels). Only useful work counts, so a share computed from these
can only read low.

What differs from the Llama-shaped module is the page: the block is the
mixer's snapshot interval, 1,024 tokens, so ``pages x block_tokens`` would
count up to 1,023 keys a row that no query reads.

``ragged_decode_bytes``  a request's entry into a wave, every layer: the K and
                         V of the keys its rows must read, once, and each
                         row's query and output. The harness says how many
                         pages the rows' contexts span, not how many keys: a
                         row's last page counts for ONE key (it holds at least
                         that), every other page whole.
``chunk_attn_flops`` /   every piece of a miss and of a hit's resume, every
``chunk_attn_bytes``     layer: a piece of ``r`` rows that ends a context of
                         ``c`` tokens attends ``c - r`` keys before it and
                         itself up to the diagonal (``costs.chunk_attn_flops``),
                         and reads the context's K and V once. A miss is
                         pieces of one block each, so both its prefill and a
                         hit's resume land here, and what the harness calls a
                         resume's ``pages`` is turned back into its tokens.
``ssd_chunk_flops``      the same pieces' state-space walk (``tpu/ssd.py``):
                         per token and head the chunk's C . B row, its mix
                         with the chunk's x, what it writes to the state and
                         what it reads of it. No kernel: PERF.md gives its
                         device time from the breakdown.
``ssd_step_bytes``       a wave row's state and tail, read once and written
                         once, every layer (no kernel either).
"""

from typing import Dict, Iterator, Tuple

import costs

WORK_KEYS = (
    "ragged_decode_bytes", "chunk_attn_flops", "chunk_attn_bytes", "ssd_chunk_flops", "ssd_step_bytes",
)


def _attention(config: Dict) -> Tuple[int, int, int, int]:
    return (
        config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"],
        costs.ITEMSIZE[config["torch_dtype"]],
    )


def state_bytes(config: Dict) -> int:
    """One layer's state (float32) and convolution tail (the served type)."""
    conv = config["mamba_d_ssm"] + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    tail = (config["mamba_d_conv"] - 1) * conv * costs.ITEMSIZE[config["torch_dtype"]]
    return config["mamba_d_ssm"] * config["mamba_d_state"] * 4 + tail


def ssd_chunk_flops(config: Dict, tokens: int) -> int:
    """One layer's walk over ``tokens`` tokens: C . B (2 G Q N a token), the
    mix with the chunk's x (2 H Q P), the write to and the read of the state
    (2 H P N each), Q the chunk."""
    q, n, g = config["mamba_chunk_size"], config["mamba_d_state"], config["mamba_n_groups"]
    h, p = config["mamba_n_heads"], config["mamba_d_head"]
    return tokens * (2 * g * q * n + 2 * h * q * p + 4 * h * p * n)


def pieces(config: Dict, context: int, rows: int) -> Iterator[Tuple[int, int]]:
    """(context at the piece's end, the piece's rows) of the last ``rows``
    tokens of a context of ``context``, cut at block boundaries as the engine
    cuts them. ``context - rows`` is a whole number of blocks."""
    bt = config["serving"]["block_tokens"]
    for start in range(context - rows, context, bt):
        end = min(start + bt, context)
        yield end, end - start


def chunk_work(config: Dict, context: int, rows: int) -> Dict[str, int]:
    heads, kv_heads, head_dim, itemsize = _attention(config)
    layers = config["num_hidden_layers"]
    flops = bytes_ = 0
    for end, r in pieces(config, context, rows):
        flops += costs.chunk_attn_flops(end, r, heads, head_dim)
        bytes_ += costs.chunk_attn_bytes(end, r, heads, kv_heads, head_dim, itemsize)
    return {
        "chunk_attn_flops": layers * flops, "chunk_attn_bytes": layers * bytes_,
        "ssd_chunk_flops": layers * ssd_chunk_flops(config, rows),
    }


def wave_work(config: Dict, pages: int, rows: int) -> Dict[str, int]:
    heads, kv_heads, head_dim, itemsize = _attention(config)
    keys = (pages - rows) * config["serving"]["block_tokens"] + rows
    kv = 2 * keys * kv_heads * head_dim * itemsize
    qo = 2 * rows * heads * head_dim * itemsize
    layers = config["num_hidden_layers"]
    return {
        "ragged_decode_bytes": layers * (kv + qo),
        "ssd_step_bytes": layers * rows * 2 * state_bytes(config),
    }


def prefill_work(config: Dict, tokens: int) -> Dict[str, int]:
    """A miss: ``tokens`` tokens from position 0, a piece a block."""
    return chunk_work(config, tokens, tokens)


def resume_work(config: Dict, pages: int, rows: int) -> Dict[str, int]:
    """A hit's resume: ``rows`` new tokens whose context ends on page
    ``pages``. The rows begin at a block boundary, so the context's last page
    holds ``(rows - 1) % block_tokens + 1`` tokens."""
    bt = config["serving"]["block_tokens"]
    return chunk_work(config, (pages - 1) * bt + (rows - 1) % bt + 1, rows)
