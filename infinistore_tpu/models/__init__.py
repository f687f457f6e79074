"""Demo model family: a small Llama-style transformer with a paged KV cache.

The reference ships no model code — it serves engines like vLLM through
LMCache (reference README.md:22). This package plays that engine's role
for the TPU build: a real (if small) paged-KV transformer whose prefill/decode
steps produce and consume the exact block layout the store moves, so the
prefill->decode disaggregation flow (BASELINE.md config 5) can run end-to-end
in tests and benchmarks, and the driver's graft entry has a jittable flagship
step to compile.
"""

from .afmoe import AfmoeConfig
from .llama import (
    LlamaConfig,
    decode_step,
    decode_wave_layer,
    embed_prompt,
    embed_wave,
    lm_logits,
    prefill_layer,
    verify_step_ragged,
    init_params,
    loss_fn,
    prefill,
    prefill_continue,
    resume_chunk,
    speculative_verify,
    train_step,
)
from .serving import ServingSteps

__all__ = [
    "AfmoeConfig",
    "ServingSteps",
    "LlamaConfig",
    "init_params",
    "prefill",
    "prefill_continue",
    "resume_chunk",
    "prefill_layer",
    "embed_prompt",
    "embed_wave",
    "lm_logits",
    "decode_wave_layer",
    "speculative_verify",
    "decode_step",
    "verify_step_ragged",
    "loss_fn",
    "train_step",
]
