"""Operations and bytes the ``mellum`` configuration's kernels need, summed
over its unlike layers (``costs.py`` says what the harness asks of a cost
module). Only useful work counts, so a share computed from these can only
read low.

The stack is ``afmoe``'s in what a cost module can see: sliding and full
attention layers through the same three attention kernels, an expert layer
through the same wave kernel and grouped product; what differs (two
rotations, the router's rule, no shared expert, no dense layer) runs in no
kernel of its own. So the counts are ``costs_afmoe``'s, over this
configuration's file with the one key it lacks (no leading dense layer):

``ragged_decode_bytes``  two full layers over a request's pages, six
                         sliding ones over at most 1,024 / 16 + 1 = 65 a row
                         (``costs_afmoe.window_pages``)
``flash_prefill_flops``  two causal triangles and six bands of 1,024
                         (``costs_afmoe.band_pairs``)
``chunk_attn_flops`` /   a resume's two full and six windowed chunk
``chunk_attn_bytes``     attentions (``costs_afmoe.chunk_window_pairs``)
``moe_prefill_flops``    tokens x 8 chosen experts x 3 products of 2 x 2,304
                         x 896, eight expert layers; exact whatever the
                         routing
``moe_wave_bytes``       a request's rows' 8 chosen experts' weights (3 x
                         2,304 x 896 x 2 B = 12.4 MB each), eight expert
                         layers, times the share of distinct experts among a
                         4-row wave's pairs under uniform routing over 64
                         experts: 64 (1 - (7 / 8) ** 4) / 32 = 82.8%
                         (``costs_afmoe.wave_distinct_share``)
"""

from typing import Dict

import costs_afmoe

WORK_KEYS = costs_afmoe.WORK_KEYS


def _file(config: Dict) -> Dict:
    return {**config, "num_dense_layers": 0}


def wave_work(config: Dict, pages: int, rows: int) -> Dict[str, float]:
    return costs_afmoe.wave_work(_file(config), pages, rows)


def prefill_work(config: Dict, tokens: int) -> Dict[str, int]:
    return costs_afmoe.prefill_work(_file(config), tokens)


def resume_work(config: Dict, pages: int, rows: int) -> Dict[str, int]:
    return costs_afmoe.resume_work(_file(config), pages, rows)
