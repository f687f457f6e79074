"""GPipe-style pipeline parallelism over a "pp" mesh axis.

Completes the dryrun's parallelism alphabet (dp/tp/sp/ep/pp): layers split
into S stages, one stage per shard of the "pp" axis; a batch splits into M
microbatches that flow through the stages with `lax.ppermute` carrying
activations stage->stage inside a `lax.scan` over M + S - 1 ticks (the
classic GPipe fill/steady/drain schedule). Everything is one jitted SPMD
program — no host round-trips between ticks — and the math is EXACTLY the
dense forward's (tested: pp loss == loss_fn loss to float tolerance), so
gradients flow through the permutes (ppermute transposes to the reverse
permute) and a pipeline training step is just value_and_grad of this loss.

The reference has no parallelism at all (SURVEY.md §2); this exists so the
store's dryrun exercises every sharding its SPMD clients use.
"""

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .llama import LlamaConfig, Params, _block, _kv_proj, _rms_norm

# Per-layer weight names (dense FFN config; MoE adds its own, pipeline keeps
# to the dense variant for clarity).
_LAYER_WEIGHTS = ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm", "w_gate_up", "w_down")
_SHARED = ("embed", "final_norm", "lm_head")


def stack_stage_params(params: Params, config: LlamaConfig, stages: int) -> Dict:
    """Restack flat per-layer params into stage-major tensors.

    Per-layer weights become [stages, layers_per_stage, ...] (leading axis
    sharded over "pp"); embed/final_norm/lm_head stay replicated. Requires
    n_layers % stages == 0 and a dense (non-MoE) config.
    """
    if config.n_experts > 0:
        raise ValueError("pipeline demo covers the dense FFN config")
    if config.n_layers % stages != 0:
        raise ValueError(f"n_layers={config.n_layers} not divisible by {stages} stages")
    lps = config.n_layers // stages
    out: Dict = {name: params[name] for name in _SHARED}
    for w in _LAYER_WEIGHTS:
        out[w] = jnp.stack(
            [
                jnp.stack([params[f"l{s * lps + i}.{w}"] for i in range(lps)])
                for s in range(stages)
            ]
        )
    return out


def _stage_forward(stage_params, x, positions, mask, config: LlamaConfig):
    """Apply this stage's layers_per_stage layers to x (same math as the
    dense loss_fn loop, via the shared _block/_kv_proj)."""
    lps = stage_params["wq"].shape[0]
    for i in range(lps):
        layer_view = {f"l0.{w}": stage_params[w][i] for w in _LAYER_WEIGHTS}
        k, v = _kv_proj(layer_view, 0, x, positions, config)
        x = _block(layer_view, 0, x, k, v, positions, mask, config)
    return x


def pp_loss_fn(
    stacked: Dict,
    tokens: jax.Array,  # [B, S] int32, replicated
    config: LlamaConfig,
    stages: int,
    microbatches: int,
    axis: str = "pp",
) -> jax.Array:
    """Pipeline next-token loss — call INSIDE shard_map over `axis` (each
    shard's stacked per-layer weights carry a leading local dim of 1)."""
    b, s = tokens.shape
    assert b % microbatches == 0, "batch must split evenly into microbatches"
    mb = b // microbatches
    tok_mb = tokens.reshape(microbatches, mb, s)
    positions = jnp.arange(s, dtype=jnp.int32)[None].repeat(mb, axis=0)
    mask = positions[:, :, None] >= positions[:, None, :]
    stage = jax.lax.axis_index(axis)
    local = {w: stacked[w][0] for w in _LAYER_WEIGHTS}  # [lps, ...]
    perm = tuple((i, i + 1) for i in range(stages - 1))
    ticks = microbatches + stages - 1

    def tick(recv, t):
        # Stage 0 ingests microbatch t (clamped during the drain phase);
        # later stages consume what the previous stage sent last tick.
        tok_in = tok_mb[jnp.clip(t, 0, microbatches - 1)]
        x0 = jnp.take(stacked["embed"], tok_in, axis=0)
        x = jnp.where(stage == 0, x0, recv)
        y = _stage_forward(local, x, positions, mask, config)
        send = jax.lax.ppermute(y, axis, perm)
        # The last stage finishes microbatch t-(S-1) at tick t.
        h = _rms_norm(y, stacked["final_norm"])
        logits = jnp.einsum("bsd,dv->bsv", h, stacked["lm_head"]).astype(jnp.float32)
        tok_out = tok_mb[jnp.clip(t - (stages - 1), 0, microbatches - 1)]
        logp = jax.nn.log_softmax(logits[:, :-1])
        nll = -jnp.take_along_axis(logp, tok_out[:, 1:][..., None], axis=-1)[..., 0]
        valid = jnp.logical_and(t >= stages - 1, stage == stages - 1)
        return send, jnp.where(valid, nll.sum(), 0.0)

    init = jnp.zeros((mb, s, config.dim), dtype=config.dtype)
    # The carry flows through ppermute (varying over pp in shard_map's
    # manual-axes typing); the zero init must carry the same type.
    init = jax.lax.pcast(init, (axis,), to="varying")
    _, sums = jax.lax.scan(tick, init, jnp.arange(ticks))
    total = jax.lax.psum(sums.sum(), axis)  # only the last stage contributes
    return total / (b * (s - 1))


def make_pp_train_step(mesh: Mesh, config: LlamaConfig, stages: int, microbatches: int):
    """Build a jitted pipeline training step over `mesh` (must carry a "pp"
    axis of size `stages`). Returns (step, shard_params): `shard_params`
    places stage-stacked params (stack_stage_params) onto the mesh; `step`
    is (stacked, tokens) -> (new_stacked, loss) with SGD, gradients flowing
    back through the inter-stage permutes."""
    pp_size = mesh.shape.get("pp")
    if pp_size != stages:
        raise ValueError(
            f"mesh 'pp' axis has {pp_size} devices but stages={stages}; a "
            "mismatch otherwise fails deep inside shard_map with an opaque "
            "IndexError"
        )
    specs = {w: P("pp") for w in _LAYER_WEIGHTS}
    specs.update({name: P() for name in _SHARED})

    def shard_params(stacked):
        return {
            k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in stacked.items()
        }

    inner = shard_map(
        functools.partial(
            pp_loss_fn, config=config, stages=stages, microbatches=microbatches
        ),
        mesh=mesh,
        in_specs=(specs, P()),
        out_specs=P(),
    )

    @jax.jit
    def step(stacked, tokens, lr=1e-3):
        loss, grads = jax.value_and_grad(lambda p: inner(p, tokens))(stacked)
        new = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), stacked, grads)
        return new, loss

    return step, shard_params
