"""Long-context prefill under sequence parallelism (ring attention inside).

The full transformer forward with the SEQUENCE sharded over an "sp" mesh
axis: each device embeds and projects only its token chunk, attention runs
as ring attention (K/V rotating, online softmax — ring_attention.py), and
the per-token ops (norms, FFN, logits) stay local — no resharding anywhere.
The outputs are exactly what the store ingests from a long-context engine:
per-layer K/V for the local token chunk, which each host's LayerwiseKVWriter
streams under its own connection (SURVEY.md §5.7: the store serves engines
that do SP; this is the engine side, end to end).

Exactness: logits and every layer's K/V equal the dense single-device
forward to float tolerance (tested) — the sharding changes the schedule,
never the math.
"""

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .layers import rope
from .llama import LlamaConfig, Params, _rms_norm
from .ring_attention import _ring_attention_local


def _local_forward(params, tokens, config: LlamaConfig, axis: str):
    """Runs INSIDE shard_map: tokens [B, S_loc] is this shard's chunk."""
    ring = jax.lax.axis_size(axis)
    rank = jax.lax.axis_index(axis)
    b, s_loc = tokens.shape
    positions = (rank * s_loc + jnp.arange(s_loc, dtype=jnp.int32))[None].repeat(
        b, axis=0
    )
    x = jnp.take(params["embed"], tokens, axis=0)
    groups = config.n_heads // config.n_kv_heads
    kvs: List[Tuple[jax.Array, jax.Array]] = []
    for layer in range(config.n_layers):
        pre = f"l{layer}."
        h = _rms_norm(x, params[pre + "attn_norm"])
        q = jnp.einsum("bsd,dhk->bshk", h, params[pre + "wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, params[pre + "wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, params[pre + "wv"])
        q = rope(q, positions, config.rope_theta)
        k = rope(k, positions, config.rope_theta)
        kvs.append((k, v))
        attn = _ring_attention_local(
            q,
            jnp.repeat(k, groups, axis=2),
            jnp.repeat(v, groups, axis=2),
            axis=axis,
            causal=True,
        )
        x = x + jnp.einsum("bshk,hkd->bsd", attn, params[pre + "wo"])
        h = _rms_norm(x, params[pre + "ffn_norm"])
        gate_up = jnp.einsum("bsd,dcf->bscf", h, params[pre + "w_gate_up"])
        ffn = jax.nn.silu(gate_up[:, :, 0]) * gate_up[:, :, 1]
        x = x + jnp.einsum("bsf,fd->bsd", ffn, params[pre + "w_down"])
    x = _rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    flat_kv = tuple(t for kv in kvs for t in kv)
    return (logits,) + flat_kv


@functools.partial(jax.jit, static_argnames=("config", "mesh", "axis"))
def prefill_ring(
    params: Params,
    tokens: jax.Array,  # [B, S] int32, S % sp_size == 0
    config: LlamaConfig,
    *,
    mesh: Mesh,
    axis: str = "sp",
):
    """Sequence-parallel prefill. Returns (logits, [(k, v) per layer]) with
    sequence dims sharded over `axis`: logits [B, S@sp, V], k/v
    [B, S@sp, n_kv_heads, head_dim]. Each shard's K/V chunk is what that
    host streams to the store (reshape to token blocks + LayerwiseKVWriter);
    dense (non-MoE) configs only."""
    if config.n_experts > 0:
        raise ValueError("prefill_ring covers the dense FFN config")
    seq_spec = P(None, axis)
    out_spec = P(None, axis, None)
    kv_spec = P(None, axis, None, None)
    n_out = 1 + 2 * config.n_layers
    fn = shard_map(
        functools.partial(_local_forward, config=config, axis=axis),
        mesh=mesh,
        in_specs=(P(), seq_spec),
        out_specs=(out_spec,) + (kv_spec,) * (n_out - 1),
    )
    tokens = jax.device_put(tokens, NamedSharding(mesh, seq_spec))
    outs = fn(params, tokens)
    logits = outs[0]
    kvs = [(outs[1 + 2 * l], outs[2 + 2 * l]) for l in range(config.n_layers)]
    return logits, kvs
