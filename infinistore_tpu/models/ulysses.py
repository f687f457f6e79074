"""Ulysses-style all-to-all sequence parallelism (DeepSpeed-Ulysses pattern).

The other canonical long-context sharding, complementing ring attention:
instead of rotating K/V around a ring, ONE all-to-all per tensor re-shards
q/k/v from sequence-sharded [B, S/P, H, D] to head-sharded [B, S, H/P, D],
attention runs LOCALLY over the full sequence per head group (no per-step
collectives, exact softmax — no online accumulation needed), and one
all-to-all brings the output back to sequence sharding. Total comms: 4
all-to-alls per attention vs ring's P-1 permutes of K/V — Ulysses wins when
heads divide the mesh and the interconnect favors fewer, larger collectives;
ring wins when H < P or memory for the full-sequence scores is tight.

Requires n_heads % axis_size == 0 and S % axis_size == 0. Exact against
dense attention (tested, causal and full, gradients included).
"""

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _ulysses_local(q, k, v, axis: str, causal: bool):
    """Runs INSIDE shard_map: q/k/v [B, S_loc, H, D] (sequence-sharded)."""
    ring = jax.lax.axis_size(axis)
    b, s_loc, h, d = q.shape
    assert h % ring == 0, f"n_heads={h} must divide the {axis} axis ({ring})"

    def seq_to_heads(x):
        # [B, S_loc, H, D] -> [B, S, H_loc, D]: split the head dim P ways,
        # tile the pieces along sequence — after the exchange this shard
        # holds the FULL sequence for its head group.
        return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        # [B, S, H_loc, D] -> [B, S_loc, H, D]: the inverse exchange.
        return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

    qf, kf, vf = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    s = ring * s_loc
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    # Framework-wide attention contract (models/llama.py _attention): f32
    # softmax statistics, HIGHEST-precision dots (XLA's DEFAULT runs f32
    # operands in reduced-precision passes on TPU).
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk",
        qf.astype(jnp.float32),
        kf.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ) * scale
    if causal:
        cm = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(cm[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd",
        p,
        vf.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ).astype(q.dtype)
    return heads_to_seq(out)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "causal"))
def ulysses_attention(
    q: jax.Array,  # [B, S, H, D], S sharded over `axis`
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
) -> jax.Array:
    """All-to-all sequence-parallel attention; in/out sharded [B, S@sp, H, D].

    K/V head counts must equal Q's (repeat GQA heads first). See the module
    docstring for when to prefer this over ring attention.
    """
    spec = P(None, axis, None, None)
    fn = shard_map(
        functools.partial(_ulysses_local, axis=axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    sharding = NamedSharding(mesh, spec)
    return fn(*(jax.device_put(x, sharding) for x in (q, k, v)))
