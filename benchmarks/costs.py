"""Operations and bytes a kernel's algorithm needs, from its shapes alone.

Kept with the benchmark so that no later PR can move a roofline share by
recounting. Every kernel function returns what ONE call on one layer needs.
Only useful work counts: pages that are padding, rows that repeat the last
row and masked blocks above the causal diagonal are not work the algorithm
needs, so a share computed from these can only read low, never above 100%.

A configuration names its cost module (``program.costs``; this one serves
the Llama-shaped ones). What the harness asks of such a module:

``WORK_KEYS``                       the keys of ``trace["work"]`` it fills
``wave_work(config, pages, rows)``  one request's entry into a decode wave:
                                    ``rows`` query rows over ``pages`` real
                                    context pages
``prefill_work(config, tokens)``    one whole-prompt prefill
``resume_work(config, pages, rows)``  optional: the resume of one prefix
                                    hit, a chunk of ``rows`` new tokens whose
                                    context ends on page ``pages``

Each returns ``{key: amount}`` for ONE such call as a total over all layers,
given the configuration's file, so a model whose layers differ states its
own sum. The harness adds them up over the traced calls and knows no key.
"""

from typing import Dict

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
WORK_KEYS = ("ragged_decode_bytes", "flash_prefill_flops", "chunk_attn_flops", "chunk_attn_bytes")


def wave_work(config: Dict, pages: int, rows: int) -> Dict[str, int]:
    return {
        "ragged_decode_bytes": config["num_hidden_layers"] * ragged_decode_bytes(
            pages, rows, config["serving"]["block_tokens"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"], ITEMSIZE[config["torch_dtype"]],
        )
    }


def prefill_work(config: Dict, tokens: int) -> Dict[str, int]:
    return {
        "flash_prefill_flops": config["num_hidden_layers"] * flash_prefill_flops(
            tokens, config["num_attention_heads"], config["head_dim"]
        )
    }


def resume_work(config: Dict, pages: int, rows: int) -> Dict[str, int]:
    context, layers = pages * config["serving"]["block_tokens"], config["num_hidden_layers"]
    heads, kv_heads, head_dim = (config[k] for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    return {
        "chunk_attn_flops": layers * chunk_attn_flops(context, rows, heads, head_dim),
        "chunk_attn_bytes": layers * chunk_attn_bytes(
            context, rows, heads, kv_heads, head_dim, ITEMSIZE[config["torch_dtype"]]
        ),
    }


def ragged_decode_bytes(
    pages: int, rows: int, block_tokens: int, n_heads: int, n_kv_heads: int,
    head_dim: int, itemsize: int,
) -> int:
    """Paged decode attention over ``pages`` real context pages for ``rows``
    query rows: each page's K and V cross HBM once, each row's query is
    read and its output written. Bound by memory at every size the cells
    use (about 1 FLOP a byte per query head)."""
    kv = 2 * pages * block_tokens * n_kv_heads * head_dim * itemsize
    qo = 2 * rows * n_heads * head_dim * itemsize
    return kv + qo


def ragged_decode_flops(pages: int, block_tokens: int, n_heads: int, head_dim: int) -> int:
    """QK^T and PV over the pages' tokens for every query head."""
    return 4 * pages * block_tokens * n_heads * head_dim


def flash_prefill_flops(seq: int, n_heads: int, head_dim: int) -> int:
    """Causal flash attention over one prompt of ``seq`` tokens: QK^T and PV
    are 2 x 2 x S^2 x D a head, and the causal mask needs half of it (the
    diagonal included: S (S + 1) / 2 pairs)."""
    return 4 * n_heads * head_dim * (seq * (seq + 1) // 2)


def flash_prefill_bytes(seq: int, n_heads: int, n_kv_heads: int, head_dim: int, itemsize: int) -> int:
    """Q read and O written once, K and V read once: the least traffic."""
    return (2 * n_heads + 2 * n_kv_heads) * seq * head_dim * itemsize


def chunk_attn_flops(context: int, rows: int, n_heads: int, head_dim: int) -> int:
    """Attention of a chunk of ``rows`` new tokens, the last of a context of
    ``context`` tokens, over the paged prefix and the chunk itself: QK^T and
    PV are 2 x 2 x D a (row, key) pair and head. Row i attends the prefix
    and the chunk up to itself, ``context - rows + i + 1`` keys; the pairs
    above the chunk's diagonal are no work the algorithm needs."""
    pairs = rows * (context - rows) + rows * (rows + 1) // 2
    return 4 * n_heads * head_dim * pairs


def chunk_attn_bytes(
    context: int, rows: int, n_heads: int, n_kv_heads: int, head_dim: int, itemsize: int,
) -> int:
    """The context's K and V read once, the chunk's Q read and O written.
    At 128 rows the kernel is bound by compute where four query heads share
    a KV head (479 FLOP a byte at an 8k context against the v5e's 240) and
    by memory where one does (117 at 2k): ``chunk_attn_roofline`` takes the
    bound that binds."""
    return 2 * (context * n_kv_heads + rows * n_heads) * head_dim * itemsize
