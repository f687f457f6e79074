"""A toy whose layers choose experts, written twice over one function.

A plain two-layer pre-norm decoder (causal softmax attention of 4 heads x 64,
no cache, no kernel) whose feed-forward is 32 experts of width 128 behind a
router that takes the top 4 of its sigmoid scores, norms their weights to sum
to one and scales them by 2.826; hidden 256, vocabulary 512. ``logits`` and
``logits_following`` are the float32 side: what a configuration's reference
module exports (``run.py`` ``reference_and_choices``). ``program`` is the same
function with every operand of every product rounded to bfloat16 and the
product taken in float32 (the CPU backend has no bf16 x bf16 -> f32 dot): what
a served bf16 model does to the same weights, standing in for the program in
``test_routed_reference.py``. Nothing of ``infinistore_tpu`` is imported.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

import choice_gaps

CONFIG = {
    "hidden_size": 256, "num_hidden_layers": 2, "num_attention_heads": 4, "head_dim": 64,
    "num_experts": 32, "num_experts_per_tok": 4, "moe_intermediate_size": 128, "route_scale": 2.826,
    "vocab_size": 512, "rms_norm_eps": 1e-6,
}


def init_params(config: dict, seed: int) -> dict:
    """Seeded float32 weights, 1 / sqrt(fan_in) normal; unit embeddings."""
    rng = np.random.default_rng(seed)
    d, h, k = config["hidden_size"], config["num_attention_heads"], config["head_dim"]
    e, f, v = config["num_experts"], config["moe_intermediate_size"], config["vocab_size"]
    normal = lambda *shape, fan_in: jnp.asarray(rng.standard_normal(shape, np.float32) / np.sqrt(fan_in))
    params = {"embed": normal(v, d, fan_in=1), "final_norm": jnp.ones(d), "head": normal(d, v, fan_in=d)}
    for i in range(config["num_hidden_layers"]):
        params[f"l{i}"] = {
            "attn_norm": jnp.ones(d), "ffn_norm": jnp.ones(d),
            "wq": normal(d, h, k, fan_in=d), "wk": normal(d, h, k, fan_in=d),
            "wv": normal(d, h, k, fan_in=d), "wo": normal(h, k, d, fan_in=h * k),
            "router": normal(d, e, fan_in=d),
            "w_gate": normal(e, d, f, fan_in=d), "w_up": normal(e, d, f, fan_in=d),
            "w_down": normal(e, f, d, fan_in=f),
        }
    return params


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


@functools.partial(jax.jit, static_argnames=("rounds", "k", "scale", "eps", "rounded"))
def _forward(params, tokens, given, rounds, k, scale, eps, rounded):
    """Logits of the last ``rounds`` positions, the sets chosen there, their
    gaps and the scores they were chosen by. ``given``: ``[rounds, sites, k]`` sets those positions take in
    place of their own top-k, or None; every other position takes its own."""
    r = _bf16 if rounded else (lambda x: x)
    dot = lambda spec, a, b: jnp.einsum(spec, r(a), r(b), precision="highest")
    s = tokens.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))
    x = jnp.take(params["embed"], tokens, axis=0)
    chosen, gaps, ranked = [], [], []
    layers = sorted(name for name in params if name.startswith("l"))
    for site, name in enumerate(layers):
        w = params[name]
        n = _norm(x, w["attn_norm"], eps)
        q, key, v = (dot("sd,dhk->shk", n, w[m]) for m in ("wq", "wk", "wv"))
        att = dot("qhk,thk->hqt", q, key) / np.sqrt(q.shape[-1])
        probs = jax.nn.softmax(jnp.where(causal[None], att, -jnp.inf), axis=-1)
        x = x + dot("shk,hkd->sd", dot("hqt,thk->qhk", probs, v), w["wo"])
        n = _norm(x, w["ffn_norm"], eps)
        scores = jax.nn.sigmoid(dot("sd,de->se", n, w["router"]))
        ids = jax.lax.top_k(scores, k)[1]
        if given is not None:
            ids = ids.at[s - rounds :].set(given[:, site])
        chosen.append(ids[s - rounds :])
        ranked.append(scores[s - rounds :])
        gaps.append(choice_gaps.gaps(scores[s - rounds :], ids[s - rounds :]))
        # The published rule: the chosen experts' own scores, normed to sum to one, scaled.
        picked = jnp.take_along_axis(scores, ids, axis=-1)
        weights = jnp.zeros_like(scores).at[jnp.arange(s)[:, None], ids].set(
            picked / jnp.sum(picked, axis=-1, keepdims=True) * scale
        )
        hidden = jax.nn.silu(dot("sd,edf->sef", n, w["w_gate"])) * dot("sd,edf->sef", n, w["w_up"])
        x = x + dot("se,sed->sd", weights, dot("sef,efd->sed", hidden, w["w_down"]))
    out = dot("sd,dv->sv", _norm(x[s - rounds :], params["final_norm"], eps), params["head"])
    return out, jnp.stack(chosen, axis=1), jnp.stack(gaps, axis=1), jnp.stack(ranked, axis=1)


def _run(params, config, tokens, rounds, given, rounded):
    return _forward(
        params, jnp.asarray(tokens, jnp.int32), given, rounds=rounds, k=config["num_experts_per_tok"],
        scale=config["route_scale"], eps=config["rms_norm_eps"], rounded=rounded,
    )


def logits(params, config: dict, tokens, rounds: int):
    """The float32 reference on its own top-k everywhere."""
    return own(params, config, tokens, rounds)[0]


def own(params, config: dict, tokens, rounds: int):
    """The float32 side's logits, its own top-k ``[rounds, sites, k]`` (best
    first) and its scores ``[rounds, sites, experts]``: for the tests."""
    out, chosen, _, scores = _run(params, config, tokens, rounds, None, False)
    return out, np.asarray(chosen), np.asarray(scores)


def logits_following(params, config: dict, tokens, rounds: int, choices):
    """The float32 reference with the last ``rounds`` positions on the given
    sets: ``(logits [rounds, vocab], gaps [rounds, sites])``."""
    sets = choice_gaps.check_sets(choices, rounds, [config["num_experts"]] * config["num_hidden_layers"])
    out, _, gaps, _ = _run(params, config, tokens, rounds, jnp.asarray(sets, jnp.int32), False)
    return out, gaps


def program(params, config: dict, tokens, rounds: int):
    """The bf16 side: ``(logits [rounds, vocab], the sets it chose [rounds, sites, k])``."""
    out, chosen, _, _ = _run(params, config, tokens, rounds, None, True)
    return out, np.asarray(chosen)
