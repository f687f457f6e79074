"""TPU data plane: HBM<->host staging, paged-KV kernels, layer-wise streaming,
and the intra-pod ICI fast path.

This package is the genuinely new part of the TPU build (SURVEY.md §5.8): the
reference moves KV blocks with GPUDirect RDMA straight out of CUDA tensors
(ibv_reg_mr on torch data_ptr, reference infinistore/test_infinistore.py
:120-122); TPU VMs expose no such path, so blocks hop HBM -> pinned host DRAM
-> DCN socket, with the HBM hop done by JAX device transfers and Pallas
gather/scatter kernels, overlapped layer-by-layer with compute the same way
the reference overlaps NIC transfer with per-layer prefill
(reference docs/source/design.rst:54-63).
"""

from .paged import (
    PagedKVCacheSpec,
    gather_blocks,
    gather_blocks_xla,
    scatter_blocks,
    scatter_blocks_xla,
)
from .chunk_attention import chunk_prefix_attention, chunk_prefix_attention_xla
from .flash_prefill import flash_prefill_attention, flash_prefill_xla
from .kv_quant import (
    QuantizedKVConnector,
    QuantizingKVAdapter,
    dequantize_kv,
    paged_decode_attention_quantized,
    quantize_kv,
)
from .paged_attention import (
    RaggedWaveMeta,
    build_ragged_wave,
    build_ragged_wave_sharded,
    paged_decode_attention_ragged,
    paged_decode_attention_ragged_sharded,
)
from .staging import HostStagingPool, StagedTransfer
from .layerwise import (
    LayerwiseKVReader,
    LayerwiseKVWriter,
    PartialReadError,
    kv_block_key,
)

__all__ = [
    "chunk_prefix_attention",
    "chunk_prefix_attention_xla",
    "flash_prefill_attention",
    "flash_prefill_xla",
    "QuantizedKVConnector",
    "QuantizingKVAdapter",
    "quantize_kv",
    "dequantize_kv",
    "paged_decode_attention_quantized",
    "RaggedWaveMeta",
    "build_ragged_wave",
    "build_ragged_wave_sharded",
    "paged_decode_attention_ragged",
    "paged_decode_attention_ragged_sharded",
    "HostStagingPool",
    "StagedTransfer",
    "PagedKVCacheSpec",
    "gather_blocks",
    "gather_blocks_xla",
    "scatter_blocks",
    "scatter_blocks_xla",
    "LayerwiseKVWriter",
    "LayerwiseKVReader",
    "PartialReadError",
    "kv_block_key",
]
