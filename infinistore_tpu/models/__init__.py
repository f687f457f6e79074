"""The model side of the serving engine: nine model files behind one contract.

The reference ships no model code: it serves engines like vLLM through
LMCache (reference README.md:22). This package plays that engine's role for
the TPU build. ``serving.py`` is the contract (``ServingSteps``: a prompt step,
a resume step, a wave body, and the one packed program every decode wave is)
and, for a model that drafts, the drafting program behind it) and the parts
of the paged skeleton every model shares; ``layers.py`` the
mathematics two or more models use; and nine model files implement the
contract, each for a published architecture the benchmark serves at its
published widths (``benchmarks/configs/``): ``llama`` (Mistral-7B,
DeepSeek-LLM-7B), ``afmoe`` (Trinity-Mini), ``kimi_linear`` (Kimi-Linear-48B),
``falcon_h1`` (Falcon-H1-34B), ``granite_hybrid`` (Granite-4.0-H-Small),
``mellum`` (Mellum2-12B), ``glm_dsa`` (GLM-5), ``sambay``
(Phi-4-mini-flash-reasoning), ``pangu_mtp`` (openPangu-Ultra-MoE-718B). A model file imports ``serving``, ``layers`` and
``..tpu`` and no other model file; the engine names none of them
(``config.steps``). The package's own names below are ``llama.py``'s (the
disaggregation flow, the smoke and the examples run them) and the two
configuration classes the tests build most.
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
