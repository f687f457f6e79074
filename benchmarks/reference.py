"""The plain reference: a Llama-style decoder's forward pass as published.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``: no
kernel, no cache, no paging, no batching, nothing imported from the program.
It follows the Hugging Face ``LlamaForCausalLM`` / ``MistralForCausalLM``
equations (the two configurations differ only in sizes):

  h   = x + Wo . softmax(causal(rope(Wq n1(x)) . rope(Wk n1(x))^T / sqrt(d))) Wv n1(x)
  out = h + Wdown (silu(Wgate n2(h)) * Wup n2(h))
  n(x) = x * rsqrt(mean(x^2) + eps) * w        logits = Whead n(final)

with rotate-half RoPE at ``rope_theta`` and grouped-query attention (each
K/V head serves ``H / KVH`` query heads). The only thing taken from the
program is the layout of its parameter dict (``l{i}.wq`` is ``[dim, H, d]``,
``l{i}.w_gate_up`` is ``[dim, 2, ffn]`` with gate first), because the
weights compared are the program's seeded ones.

Departures, each for memory alone: one layer's weights are held in float32
at a time, and attention is computed a block of queries at a time against
the whole context (the logits of an 8k prompt over 32 heads are 8.8 GB
otherwise). Neither changes the mathematics.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x: [S, heads, d]; rotate-half, as the published modelling code."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def _layer(w, x, theta, eps):
    """One decoder layer over the whole sequence x: [S, dim], float32."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    s = x.shape[0]
    positions = jnp.arange(s, dtype=jnp.int32)
    n = _rms_norm(x, w["attn_norm"], eps)
    q = _rope(jnp.einsum("sd,dhk->shk", n, w["wq"]), positions, theta)
    k = _rope(jnp.einsum("sd,dhk->shk", n, w["wk"]), positions, theta)
    v = jnp.einsum("sd,dhk->shk", n, w["wv"])
    groups = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, groups, axis=1), jnp.repeat(v, groups, axis=1)
    scale = 1.0 / np.sqrt(q.shape[-1])

    def attend(block):  # block: [QUERY_BLOCK] query positions
        qb = jnp.take(q, block, axis=0)
        logits = jnp.einsum("qhk,thk->hqt", qb, k) * scale
        mask = block[:, None] >= positions[None, :]
        probs = jax.nn.softmax(jnp.where(mask[None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thk->qhk", probs, v)

    blocks = positions.reshape(s // QUERY_BLOCK, QUERY_BLOCK)
    attn = jax.lax.map(attend, blocks).reshape(s, *q.shape[1:])
    h = x + jnp.einsum("shk,hkd->sd", attn, w["wo"])
    n = _rms_norm(h, w["ffn_norm"], eps)
    gate_up = jnp.einsum("sd,dcf->scf", n, w["w_gate_up"])
    ffn = jax.nn.silu(gate_up[:, 0]) * gate_up[:, 1]
    return h + jnp.einsum("sf,fd->sd", ffn, w["w_down"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(norm_w, head_w, x, eps):
    x = _rms_norm(x, norm_w.astype(jnp.float32), eps)
    return jnp.einsum("sd,dv->sv", x, head_w.astype(jnp.float32))


def logits(params, config: dict, tokens, last_n: int):
    """``[last_n, vocab]`` float32 logits of the last ``last_n`` positions of
    ``tokens`` under ``params`` (the program's parameter dict) and ``config``
    (the configuration file's published keys)."""
    n = len(tokens)
    padded = -(-n // QUERY_BLOCK) * QUERY_BLOCK
    # Padding sits after the real tokens: causal attention never lets a real
    # position see it, and its own outputs are dropped.
    toks = jnp.asarray(list(tokens) + [0] * (padded - n), jnp.int32)
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    names = sorted(k.split(".", 1)[1] for k in params if k.startswith("l0."))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], toks, axis=0).astype(jnp.float32)
        for layer in range(int(config["num_hidden_layers"])):
            w = {name: params[f"l{layer}.{name}"] for name in names}
            x = _layer(w, x, theta, eps)
        return _head(params["final_norm"], params["lm_head"], x[n - last_n : n], eps)
