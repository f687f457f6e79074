"""Operations and bytes the ``phi4flash`` configuration's kernels need, summed
over its unlike layers (``costs.py`` says what the harness asks of a cost
module). Only useful work counts, so a share computed from these can only read
low.

With L layers, ``full = L / 2 + 1``: the even layers under ``full`` are
Mamba-1 mixers (``full / 2 + 1`` of them: 9 of 32), layer ``full`` keeps the
model's one K/V cache, and it and every cross layer past it (L / 4 layers in
all: 8) attend those SAME pages in a wave. A prompt piece attends no page.

``ragged_decode_bytes``  a request's entry into a wave: layer ``full``'s K and
                         V of the keys its rows must read (a row's last page
                         counts for ONE key, every other page whole; a pair of
                         heads is one 128-wide head, ``hidden / heads x 2``),
                         once for EACH of the L / 4 layers that read them, and
                         each layer's queries and outputs (2 P heads of 2 D: the
                         zero halves are read and written too, and are counted
                         as what the kernel is handed).
``selscan_chunk_bytes``  every piece of a miss and of a hit's resume, the
                         Mamba layers: the piece's ``u`` (the served type),
                         ``dt`` (float32), ``B`` and ``C`` read and ``y``
                         (float32) written, the state (float32) in and out.
                         The scan is bound by the vector unit (16 exponentials
                         and some hundred operations a token and 1,024
                         channels), not by these bytes: the share of the HBM
                         roofline reads low by design, and says how far.
"""

from typing import Dict, Iterator, Tuple

import costs

WORK_KEYS = ("ragged_decode_bytes", "selscan_chunk_bytes")


def _layers(config: Dict) -> Tuple[int, int]:
    """(Mamba layers, layers that read layer ``full``'s pages in a wave)."""
    layers = config["num_hidden_layers"]
    return layers // 4 + 1, layers // 4


def pieces(config: Dict, rows: int) -> Iterator[int]:
    """The rows of each piece of ``rows`` new tokens that begin at a block
    boundary, cut at block boundaries as the engine cuts them."""
    bt = config["serving"]["block_tokens"]
    for start in range(0, rows, bt):
        yield min(bt, rows - start)


def selscan_piece_bytes(config: Dict, rows: int) -> int:
    """ONE Mamba layer's scan of a piece of ``rows`` tokens."""
    channels, n = config["mamba_expand"] * config["hidden_size"], config["mamba_d_state"]
    itemsize = costs.ITEMSIZE[config["torch_dtype"]]
    streamed = rows * (channels * (itemsize + 4 + 4) + 2 * n * 4)
    return streamed + 2 * channels * n * 4


def chunk_work(config: Dict, rows: int) -> Dict[str, int]:
    mamba, _ = _layers(config)
    return {"selscan_chunk_bytes": mamba * sum(selscan_piece_bytes(config, r) for r in pieces(config, rows))}


def wave_work(config: Dict, pages: int, rows: int) -> Dict[str, int]:
    _, readers = _layers(config)
    heads, kv_pairs = config["num_attention_heads"], config["num_key_value_heads"] // 2
    pair_dim = 2 * config["hidden_size"] // heads
    itemsize = costs.ITEMSIZE[config["torch_dtype"]]
    keys = (pages - rows) * config["serving"]["block_tokens"] + rows
    kv = 2 * keys * kv_pairs * pair_dim * itemsize
    qo = 2 * rows * heads * pair_dim * itemsize
    return {"ragged_decode_bytes": readers * (kv + qo)}


def prefill_work(config: Dict, tokens: int) -> Dict[str, int]:
    """A miss: ``tokens`` tokens from position 0, a piece a block."""
    return chunk_work(config, tokens)


def resume_work(config: Dict, pages: int, rows: int) -> Dict[str, int]:
    """A hit's resume: ``rows`` new tokens from a block boundary (the pages
    before them are attended by no prompt step)."""
    del pages
    return chunk_work(config, rows)
