"""Operations and bytes the ``pangu_ultra_moe`` configuration's kernels need,
summed over its layers, the multi-token-prediction (MTP) layer's among them
(``costs.py`` says what the harness asks of a cost module). Only useful work
counts, so a share computed from these can only read low.

``mla_decode_bytes``   a request's entry into a wave, every layer that keeps
                       latents in a wave (the main stack's and the MTP
                       layer's): the entry's context pages once each as they
                       lie in the cache (``rank + rope`` values a token; a
                       page is fetched whole), each row's absorbed query (128
                       heads) read and its mix written. An entry of a drafting
                       model is a chunk of two rows, ``[token, draft]`` at
                       ``[p, p + 1]``, over ONE context: the kernel walks a
                       row's own table, so it reads the pages once a row, and
                       the cost counts them once an entry (``pages`` is the
                       harness's sum over the rows). A share from it reads
                       about half of what one row a request would.
``moe_prefill_flops``  the grouped products of a miss's pieces or of a resume:
                       tokens x the same 0.25 held choices x 3 products of 2 x
                       hidden x width, the MAIN stack's four expert layers (a
                       prompt writes the MTP layer's slots and runs no expert
                       of it); an expectation under uniform routing. The
                       shared expert is a dense product beside the grouped
                       one, not counted.

No ``moe_wave_bytes``, so no share of the wave's expert kernel in this cell.
The siblings count a wave row's chosen experts among those held, times the
share of DISTINCT experts among a wave's pairs under uniform, independent
routing. Here a wave's rows are three chunks of two, and a chunk's rows are one
request's consecutive positions, which route alike: the chip's
``moe_distinct_experts_share.reuse`` reads 83.4% where six independent rows
would give 92.5%, the kernel streams 1.07 experts a call where that count
assumes 1.39, and the share it gave read 98.7 and 101.4% (PERF.md, PR 62). A
count that is right needs the distinct held experts a call as MEASURED (the
program's ``moe_streamed_experts`` counter over the kernel's calls), which no
reader kind of the harness divides a trace time by: a ``benchmark`` PR's.

Nothing of a chunk's latent attention is counted (``mla_chunk_attention_pallas``
has no share in any cell: a miss's work is counted when it STARTS, and a 32k
miss outlasts a traced window; ``costs_glm_dsa`` says what that did to a
share). ``resume_rewrite_flops`` says what the one slot a hit's
resume rewrites costs beside its question: nothing the harness adds up.
"""

from typing import Dict

import costs

WORK_KEYS = ("mla_decode_bytes", "moe_prefill_flops")


def _layers(config: Dict):
    """(latent layers of a wave, expert layers of a prompt)."""
    main = config["num_hidden_layers"]
    return main + config["num_nextn_predict_layers"], main - config["first_k_dense_replace"]


def held_choices(config: Dict) -> float:
    """Of a token's choices, those that fall on the experts held here."""
    return config["num_experts_per_tok"] * config["n_routed_experts"] / config["router_experts"]


def moe_flops(config: Dict, tokens: int) -> float:
    per_pair = 3 * 2 * config["hidden_size"] * config["moe_intermediate_size"]
    return tokens * held_choices(config) * per_pair * _layers(config)[1]


def mla_decode_bytes(config: Dict, pages: int, rows: int) -> int:
    """One latent layer: the entry's context pages' latents once (``pages``
    sums the rows': a chunk's rows share their context), the rows' absorbed
    queries (the served type) and their mixes (float32)."""
    itemsize = costs.ITEMSIZE[config["torch_dtype"]]
    rank, width = config["kv_lora_rank"], config["kv_lora_rank"] + config["qk_rope_head_dim"]
    heads = config["num_attention_heads"]
    context_pages = -(-pages // max(rows, 1))
    return context_pages * config["serving"]["block_tokens"] * width * itemsize + rows * heads * (
        width * itemsize + rank * 4
    )


def resume_rewrite_flops(config: Dict) -> int:
    """The ONE slot a hit's resume rewrites: a row through W_eh (2 dim -> dim)
    and the MTP layer's W_kva (dim -> rank + rope)."""
    dim = config["hidden_size"]
    return 2 * dim * (2 * dim + config["kv_lora_rank"] + config["qk_rope_head_dim"])


def wave_work(config: Dict, pages: int, rows: int) -> Dict[str, float]:
    return {"mla_decode_bytes": _layers(config)[0] * mla_decode_bytes(config, pages, rows)}


def prefill_work(config: Dict, tokens: int) -> Dict[str, float]:
    return {"moe_prefill_flops": moe_flops(config, tokens)}


def resume_work(config: Dict, pages: int, rows: int) -> Dict[str, float]:
    """A hit's question: ``rows`` positions."""
    return {"moe_prefill_flops": moe_flops(config, rows)}
