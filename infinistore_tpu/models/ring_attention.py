"""Ring attention: context parallelism for long sequences over an "sp" axis.

Sequences too long for one device's HBM shard across the mesh: each device
holds a contiguous sequence chunk of Q, K, V. Attention then needs every
(q, k) pair, so K/V chunks ROTATE around the ring with `lax.ppermute` while
each device accumulates its Q-chunk's attention online (flash-attention's
numerically-safe running max/denominator), one neighbor hop per step —
bandwidth-optimal: every byte of K/V crosses each ICI link exactly once, and
XLA overlaps the permute with the local attention compute.

The store connection: long-context prefill runs under exactly this sharding,
and its KV blocks stream to the store per device shard (each host's
connection carries its sequence chunk — the layerwise writer does not care
which parallelism produced the blocks). The reference has no compute at all
(SURVEY.md §5.7: the store serves engines that do SP; this module is the
engine-side piece so the dryrun can exercise the full pattern).

Correctness oracle: equals dense softmax attention on the gathered sequence
to float tolerance (tested, causal and full).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _ring_attention_local(
    q: jax.Array,  # [B, S_loc, H, D] this shard's query chunk
    k: jax.Array,  # [B, S_loc, H, D] this shard's key chunk (will rotate)
    v: jax.Array,  # [B, S_loc, H, D]
    axis: str,
    causal: bool,
) -> jax.Array:
    ring = jax.lax.axis_size(axis)
    rank = jax.lax.axis_index(axis)
    b, s_loc, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    q32 = q.astype(jnp.float32)
    q_pos = rank * s_loc + jnp.arange(s_loc)

    # Rotate so every chunk visits every device: after step i this shard
    # holds the chunk originating at rank - i (mod ring).
    perm = tuple((i, (i + 1) % ring) for i in range(ring))

    def step(carry, i):
        m, l, o, k_cur, v_cur = carry
        src = (rank - i) % ring
        k_pos = src * s_loc + jnp.arange(s_loc)
        scores = (
            jnp.einsum(
                "bqhd,bkhd->bhqk",
                q32,
                k_cur.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            * scale
        )
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]  # [S_loc, S_loc] global
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        # exp(-inf - -inf) guards: fully-masked rows keep m at -inf; the
        # correction for them is defined as 1 (no prior mass to rescale).
        corr = jnp.where(jnp.isneginf(m_new), 1.0, jnp.exp(m - m_new))
        p = jnp.exp(scores - m_new[..., None])
        p = jnp.where(jnp.isneginf(scores), 0.0, p)
        l = l * corr + p.sum(axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd",
            p,
            v_cur.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        k_nxt = jax.lax.ppermute(k_cur, axis, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis, perm)
        return (m_new, l, o, k_nxt, v_nxt), None

    m0 = jnp.full((b, h, s_loc), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), dtype=jnp.float32)
    o0 = jnp.zeros((b, h, s_loc, d), dtype=jnp.float32)
    # The accumulators mix with per-shard data (varying over sp in
    # shard_map's manual-axes typing); their zero inits must match.
    m0, l0, o0 = (
        jax.lax.pcast(x, (axis,), to="varying") for x in (m0, l0, o0)
    )
    (m, l, o, _, _), _ = jax.lax.scan(
        step, (m0, l0, o0, k, v), jnp.arange(ring)
    )
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [B, S_loc, H, D]


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "causal"))
def ring_attention(
    q: jax.Array,  # [B, S, H, D], S sharded over `axis`
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
) -> jax.Array:
    """Sequence-parallel attention: inputs/outputs sharded [B, S@sp, H, D].

    K/V head counts must equal Q's (repeat GQA heads before the call). The
    output keeps the input sharding — downstream per-token ops (FFN, norm)
    stay sequence-parallel with no resharding.
    """
    spec = P(None, axis, None, None)
    fn = shard_map(
        functools.partial(_ring_attention_local, axis=axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    sharding = NamedSharding(mesh, spec)
    return fn(*(jax.device_put(x, sharding) for x in (q, k, v)))


def dense_attention_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """The oracle: plain softmax attention over the full sequence."""
    d = q.shape[-1]
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk",
        q.astype(jnp.float32),
        k.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    if causal:
        s = q.shape[1]
        cm = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(cm[None, None], scores, -jnp.inf)
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bhqd",
        p,
        v.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
