"""Operations and bytes a kernel's algorithm needs, from its shapes alone.

Kept with the benchmark so that no later PR can move a roofline share by
recounting. Every function returns what ONE call on one layer needs; the
caller multiplies by layers and calls. Only useful work counts: pages that
are padding, rows that repeat the last row and masked blocks above the
causal diagonal are not work the algorithm needs, so a share computed from
these can only read low, never above 100%.
"""


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
