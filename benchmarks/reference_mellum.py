"""The plain reference of the ``mellum`` configuration: the forward pass as
published, in float32 at matmul precision ``highest``.

Straightforward ``jax.numpy``: no kernel, no cache, no paging, no batching,
nothing imported from the program. The only thing taken from the program is
the layout of its parameter dict (``l{i}.wq`` is ``[dim, H, d]``,
``l{i}.w_gate`` is ``[experts, dim, width]`` ...), because the weights
compared are the program's seeded ones.

From the configuration's file (the published ``config.json``): the sizes,
``layer_types``, ``sliding_window``, ``num_experts`` / ``num_experts_per_tok``
/ ``norm_topk_prob``, ``rope_parameters`` (one entry a layer kind),
``rms_norm_eps``. From the published modelling code of the Qwen3-MoE lineage
that the config's keys are of (NOT in ``config.json``; the file lists it under
``assumed``): an RMS norm per head on q and on k, weights of ``head_dim``,
before the rotation.

  h0      = E[token]
  n       = rms(h; w_in)
  q, k    = rms_head(Wq n; w_q), rms_head(Wk n; w_k)     v = Wv n
  sliding : q, k <- rot(q, k; f, 1);       key j visible to query i iff 0 <= i - j < window
  full    : q, k <- rot(q, k; f', a);      key j visible to query i iff j <= i
  h       <- h + Wo softmax(q k^T / sqrt(d)) v   (each KV head serves H / KVH query heads)
  m       = rms(h; w_post)
  p       = softmax(Wr m) over ALL experts;  S = top-k of p;  g_e = p_e / sum_{e in S} p_e
  h       <- h + sum_{e in S} g_e Wdown_e (silu(Wgate_e m) * Wup_e m)
  logits  = Whead rms(h_L; w_final),   rms(x; w) = x * rsqrt(mean(x^2) + eps) * w

  rot(x; f, a): rotate-half, x1' = a (x1 cos(pos f) - x2 sin(pos f)),
                             x2' = a (x2 cos(pos f) + x1 sin(pos f))
  f_i  = theta ^ (-2i / d)                                       ("default")
  f'_i = (f_i / s) r_i + f_i (1 - r_i),  r_i = clip((i - low) / (high - low), 0, 1),
         low = max(floor(c(beta_fast)), 0),  high = min(ceil(c(beta_slow)), d - 1),
         c(t) = d ln(L0 / (2 pi t)) / (2 ln theta),  a = attention_factor   ("yarn")

Departures, each for memory alone (the reference runs beside the program's
weights and cache on one chip): attention is computed a block of queries at a
time, the block's q projected inside the block (a sliding layer against the
keys of its band only); an expert is applied to its own tokens only, a chunk
of tokens and a tile of rows at a time (``reference_afmoe._experts``: the
plain sum over experts two configurations share); the head is multiplied a
block of the vocabulary at a time. None changes the mathematics.

``logits_following`` is the same pass in which the last ``rounds`` positions
take the expert sets they are given (the weights still from this pass's own
probabilities) and reports how far each set lies off this pass's own scores
(``choice_gaps.gaps``). The published top-k ranks the softmax's
probabilities, whose order is the logits'; the gap is read on the logits,
the projection of the normed hidden state the harness's slack is argued for.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import choice_gaps
from reference_afmoe import _experts

QUERY_BLOCK = 64
TOKEN_CHUNK = 4096  # tokens whose (token, expert) pairs are sorted and held at a time
VOCAB_BLOCK = 8192
SLIDING = "sliding_attention"


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotation(p: dict, d: int):
    """(inverse frequencies ``[d / 2]``, the scale of cosine and sine) of one
    entry of ``rope_parameters``, after the published YaRN code."""
    base = float(p["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if p["rope_type"] == "default":
        return inv.astype(np.float32), 1.0
    if p["rope_type"] != "yarn":
        raise ValueError(f"rope_type {p['rope_type']!r} is not written out here")
    factor, orig = float(p["factor"]), float(p["original_max_position_embeddings"])

    def correction_dim(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(base))

    low, high = correction_dim(float(p["beta_fast"])), correction_dim(float(p["beta_slow"]))
    if p.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    extrapolation = 1 - ramp  # 1 where the published frequency is kept
    table = inv / factor * (1 - extrapolation) + inv * extrapolation
    scale = p.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return table.astype(np.float32), float(scale)


def _rot(x, positions, inv_freq, scale):
    """x: [S, heads, d]; rotate-half, cosine and sine times ``scale``."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.cos(ang) * scale)[:, None, :], (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("scale", "eps", "window"))
def _attention_half(w, x, inv_freq, scale, eps, window):
    """h + Wo softmax(qk) v over the whole sequence x: [S, dim] float32, S a
    multiple of QUERY_BLOCK. ``window`` None: a full layer."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    s = x.shape[0]
    positions = jnp.arange(s, dtype=jnp.int32)
    n = _rms(x, w["in_norm"], eps)
    k = _rms(jnp.einsum("sd,dhk->shk", n, w["wk"]), w["k_norm"], eps)
    k = _rot(k, positions, inv_freq, scale)
    v = jnp.einsum("sd,dhk->shk", n, w["wv"])
    kvh, d = k.shape[1], k.shape[2]
    groups = w["wq"].shape[1] // kvh
    if window is None:
        keys, span = (k, v), s
    else:
        # The keys of a block's band: positions q0 - window + 1 .. q0 + QB - 1,
        # cut out of K and V padded by ``window`` rows in front.
        front = jnp.zeros((window, kvh, d), jnp.float32)
        keys, span = (jnp.concatenate([front, k]), jnp.concatenate([front, v])), window + QUERY_BLOCK - 1

    def attend(q0):  # first query position of a block
        qpos = q0 + jnp.arange(QUERY_BLOCK, dtype=jnp.int32)
        nb = jax.lax.dynamic_slice_in_dim(n, q0, QUERY_BLOCK)
        qb = _rms(jnp.einsum("sd,dhk->shk", nb, w["wq"]), w["q_norm"], eps)
        qb = _rot(qb, qpos, inv_freq, scale).reshape(QUERY_BLOCK, kvh, groups, d)
        if window is None:
            kb, vb, kpos = keys[0], keys[1], positions
        else:
            kb = jax.lax.dynamic_slice_in_dim(keys[0], q0 + 1, span)
            vb = jax.lax.dynamic_slice_in_dim(keys[1], q0 + 1, span)
            kpos = q0 - window + 1 + jnp.arange(span, dtype=jnp.int32)
        logits = jnp.einsum("qkgd,tkd->kgqt", qb, kb) / np.float32(math.sqrt(d))
        seen = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
        if window is not None:
            seen &= qpos[:, None] - kpos[None, :] < window
        probs = jax.nn.softmax(jnp.where(seen[None, None], logits, -jnp.inf), axis=-1)
        a = jnp.einsum("kgqt,tkd->qkgd", probs, vb).reshape(QUERY_BLOCK, kvh * groups, d)
        return jnp.einsum("qhk,hkd->qd", a, w["wo"])

    starts = jnp.arange(0, s, QUERY_BLOCK, dtype=jnp.int32)
    return x + jax.lax.map(attend, starts).reshape(s, -1)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "norm_topk_prob"))
def _route(w, h, given, eps, top_k, norm_topk_prob):
    """Over h: [S, dim]: the normed input m, the router's logits [S, E], each
    position's expert ids [S, k] and combine weights [S, k], as published:
    softmax over ALL experts, the k largest, divided by their sum. ``given``:
    [rounds, k] ids for the LAST ``rounds`` positions (rounds may be 0); every
    other position takes its own top-k. The weights come from this pass's
    probabilities, for a given set too."""
    f32 = jnp.float32
    m = _rms(h, w["post_norm"].astype(f32), eps)
    logits = jnp.dot(m, w["router"].astype(f32))
    probs = jax.nn.softmax(logits, axis=-1)
    _, ids = jax.lax.top_k(probs, top_k)
    rounds = given.shape[0]
    if rounds:
        ids = jnp.concatenate([ids[: h.shape[0] - rounds], given.astype(ids.dtype)])
    chosen = jnp.take_along_axis(probs, ids, axis=1)
    if norm_topk_prob:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return m, logits, ids, chosen


def _expert_half(w, h, given, eps, top_k, norm_topk_prob):
    """The expert layer's half over h: [S, dim]. Returns (h_next, the router's
    logits [S, E])."""
    m, logits, ids, weights = _route(w, h, given, eps, top_k, norm_topk_prob)
    s = h.shape[0]
    chunk = min(s, TOKEN_CHUNK)
    pad = -s % chunk
    cut = lambda x: jnp.pad(x, ((0, pad), (0, 0)))  # padded rows weigh nothing
    mp, ip, wp = cut(m), cut(ids), cut(weights)
    held = {name: w[name] for name in ("w_gate", "w_up", "w_down_moe")}
    routed = jnp.concatenate([
        _experts(held, mp[a : a + chunk], ip[a : a + chunk], wp[a : a + chunk])
        for a in range(0, s + pad, chunk)
    ])[:s]
    return h + routed, logits


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(norm_w, head_w, x, eps):
    """Whead rms(x), a block of the vocabulary's columns at a time."""
    x = _rms(x, norm_w.astype(jnp.float32), eps)
    vocab = head_w.shape[1]
    block = min(vocab, VOCAB_BLOCK)
    if vocab % block:
        return jnp.einsum("sd,dv->sv", x, head_w.astype(jnp.float32))
    cols = lambda c0: jnp.dot(
        x, jax.lax.dynamic_slice_in_dim(head_w, c0, block, axis=1).astype(jnp.float32)
    )
    out = jax.lax.map(cols, jnp.arange(0, vocab, block, dtype=jnp.int32))  # [blocks, S, block]
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], vocab)


ATTENTION = ("in_norm", "q_norm", "k_norm", "wq", "wk", "wv", "wo")
EXPERT = ("post_norm", "router", "w_gate", "w_up", "w_down_moe")


def _forward(params, config: dict, tokens, last_n: int, choices):
    """The pass both entries share. ``choices``: None, or int [last_n, sites,
    k]. Returns (logits [last_n, vocab], gaps [last_n, sites] or None)."""
    if config["hidden_act"] != "silu" or config["attention_bias"] or config["tie_word_embeddings"]:
        raise ValueError("this reference writes out silu experts, no attention bias, an untied head")
    if set(config["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("this reference writes out a stack whose every MLP is an expert layer")
    n = len(tokens)
    padded = -(-n // QUERY_BLOCK) * QUERY_BLOCK
    # Padding sits after the real tokens: causal attention never lets a real
    # position see it, and its own outputs are dropped.
    toks = jnp.asarray(list(tokens) + [0] * (padded - n), jnp.int32)
    eps, d = float(config["rms_norm_eps"]), int(config["head_dim"])
    top_k, experts = int(config["num_experts_per_tok"]), int(config["num_experts"])
    layers = int(config["num_hidden_layers"])
    if choices is not None:
        choices = choice_gaps.check_sets(choices, last_n, [experts] * layers)
        if choices.shape[2] != top_k:
            raise ValueError(f"the sets hold {choices.shape[2]} ids, the top-k chooses {top_k}")
    tables = {kind: rotation(p, d) for kind, p in config["rope_parameters"].items()}
    of = lambda layer, names: {name: params[f"l{layer}.{name}"] for name in names}
    gaps = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], toks, axis=0).astype(jnp.float32)
        for layer in range(layers):
            kind = config["layer_types"][layer]
            inv_freq, scale = tables[kind]
            window = int(config["sliding_window"]) if kind == SLIDING else None
            x = _attention_half(of(layer, ATTENTION), x, jnp.asarray(inv_freq), scale, eps, window)
            # The real tokens alone route: the given sets sit on their last rows.
            given = np.zeros((0, top_k), np.int32) if choices is None else choices[:, layer]
            real, logits = _expert_half(
                of(layer, EXPERT), x[:n], jnp.asarray(given), eps, top_k,
                bool(config["norm_topk_prob"]),
            )
            x = jnp.concatenate([real, x[n:]])
            if choices is not None:
                gaps.append(choice_gaps.gaps(logits[n - last_n :], given))
        out = _head(params["final_norm"], params["lm_head"], x[n - last_n : n], eps)
    return out, (jnp.stack(gaps, axis=1) if choices is not None else None)


def logits(params, config: dict, tokens, last_n: int):
    """``[last_n, vocab]`` float32 logits of the last ``last_n`` positions of
    ``tokens`` under ``params`` (the program's parameter dict) and ``config``
    (the configuration file's published keys); every position routes by its
    own float32 probabilities."""
    return _forward(params, config, tokens, last_n, None)[0]


def logits_following(params, config: dict, tokens, rounds: int, choices):
    """The same pass in which the last ``rounds`` positions take the expert
    sets ``choices`` (int ``[rounds, sites, k]``, a site a layer in the
    model's order; ``ValueError`` for a set that is not ``k`` distinct ids of
    the experts) with weights from this pass's own probabilities, and every
    other position its own top-k. Returns ``(logits [rounds, vocab] float32,
    gaps [rounds, sites] float32)``, the gaps as ``choice_gaps.gaps`` defines
    them over the router's logits."""
    return _forward(params, config, tokens, rounds, np.asarray(choices))
