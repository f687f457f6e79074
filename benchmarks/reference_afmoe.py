"""The plain reference of the ``afmoe`` configuration: the forward pass as
published, in float32 at matmul precision ``highest``.

Straightforward ``jax.numpy``: no kernel, no cache, no paging, no batching,
nothing imported from the program. The only thing taken from the program is
the layout of its parameter dict (``l{i}.wq`` is ``[dim, H, d]``,
``l{i}.w_gate`` is ``[experts, dim, width]`` ...), because the weights
compared are the program's seeded ones.

From the configuration's file (the published ``config.json``): the sizes,
``layer_types``, ``sliding_window``, ``num_dense_layers``, ``num_experts`` /
``num_experts_per_tok`` / ``num_shared_experts``, ``score_func`` sigmoid,
``route_norm``, ``route_scale``, ``rope_theta``, ``rms_norm_eps``,
``mup_enabled``. From the published modelling code of ``model_type: afmoe``
(NOT in ``config.json``; the file lists each under ``assumed``):

- four RMS norms a layer: before attention, on attention's output before
  the residual add, before the MLP, on the MLP's output before its add;
- an RMS norm per head on q and on k (weights of ``head_dim``), before RoPE;
- a sigmoid output gate: ``a <- a * sigmoid(Wg n)``, ``Wg`` shaped like ``Wq``;
- RoPE (rotate-half, ``rope_theta``) on the sliding layers only; a full
  layer has no positional rotation;
- the router's selection bias ``b``: top-k ranks ``s + b``, the combine
  weights come from ``s`` alone (zeros under seeded weights);
- the embedding scaled by ``sqrt(hidden_size)`` under ``mup_enabled``.

  h0      = E[token] * sqrt(hidden)
  n       = rms(h; w_in)
  q, k    = rms_head(Wq n; w_q), rms_head(Wk n; w_k)    v = Wv n    g = Wg n
  sliding : q, k <- rope(q, k);  key j visible to query i iff 0 <= i - j < window
  full    : no rope;             key j visible to query i iff j <= i
  a       = softmax(q k^T / sqrt(d)) v   (each KV head serves H / KVH query heads)
  a       <- a * sigmoid(g);     h <- h + rms(Wo a; w_post_attn)
  m       = rms(h; w_pre_mlp)
  dense   : f = Wdown (silu(Wgate m) * Wup m)
  expert  : s = sigmoid(Wr m);  S = top-k of (s + b)
            w_e = route_scale * s_e / (sum_{e in S} s_e + 1e-20)
            f = Shared(m) + sum_{e in S} w_e Expert_e(m)
  h       <- h + rms(f; w_post_mlp)
  logits  = Whead rms(h_L; w_final),   rms(x; w) = x * rsqrt(mean(x^2) + eps) * w

Departures, each for memory alone: one layer's dense weights are held in
float32 at a time and an expert's only while its tokens pass; attention is
computed a block of queries at a time (a sliding layer against the keys of
its band only); an expert is applied to its own tokens only, a chunk of
tokens and a tile of rows at a time over the chunk's (token, expert) pairs
sorted by expert (a dense pass over 128 experts at 32k tokens is 53 TFLOP a
layer). None changes the mathematics.

``logits_following`` is the same pass in which the last ``rounds`` positions
take the expert sets they are given (the weights still from this pass's own
scores) and reports how far each set lies off these scores
(``choice_gaps.gaps``; the score the top-k ranks by is ``s + b``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

import choice_gaps

QUERY_BLOCK = 128
ROW_TILE = 256  # rows of sorted (token, expert) pairs an expert takes at a time
TOKEN_CHUNK = 4096  # tokens whose pairs are sorted and held at a time
SLIDING = "sliding_attention"


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x: [S, heads, d]; rotate-half, as the published modelling code."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("theta", "eps", "window"))
def _attention_half(w, x, theta, eps, window):
    """h + rms(Wo (softmax(qk) v * sigmoid(g))) over the whole sequence x:
    [S, dim] float32, S a multiple of QUERY_BLOCK. ``window`` None: a full
    layer (no RoPE); else a sliding one."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    s = x.shape[0]
    positions = jnp.arange(s, dtype=jnp.int32)
    n = _rms(x, w["in_norm"], eps)
    q = _rms(jnp.einsum("sd,dhk->shk", n, w["wq"]), w["q_norm"], eps)
    k = _rms(jnp.einsum("sd,dhk->shk", n, w["wk"]), w["k_norm"], eps)
    v = jnp.einsum("sd,dhk->shk", n, w["wv"])
    g = jnp.einsum("sd,dhk->shk", n, w["wg"])
    if window is not None:
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    kvh, d = k.shape[1], k.shape[2]
    groups = q.shape[1] // kvh
    scale = 1.0 / np.sqrt(d)
    if window is None:
        keys, span = (k, v), s
    else:
        # The keys of a block's band: positions q0 - window + 1 .. q0 + QB - 1,
        # cut out of K and V padded by ``window`` rows in front.
        front = jnp.zeros((window, kvh, d), jnp.float32)
        keys, span = (jnp.concatenate([front, k]), jnp.concatenate([front, v])), window + QUERY_BLOCK - 1

    def attend(q0):  # first query position of a block
        qpos = q0 + jnp.arange(QUERY_BLOCK, dtype=jnp.int32)
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK).reshape(QUERY_BLOCK, kvh, groups, d)
        if window is None:
            kb, vb, kpos = keys[0], keys[1], positions
        else:
            kb = jax.lax.dynamic_slice_in_dim(keys[0], q0 + 1, span)
            vb = jax.lax.dynamic_slice_in_dim(keys[1], q0 + 1, span)
            kpos = q0 - window + 1 + jnp.arange(span, dtype=jnp.int32)
        logits = jnp.einsum("qkgd,tkd->kgqt", qb, kb) * scale
        seen = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
        if window is not None:
            seen &= qpos[:, None] - kpos[None, :] < window
        probs = jax.nn.softmax(jnp.where(seen[None, None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", probs, vb).reshape(QUERY_BLOCK, kvh * groups, d)

    starts = jnp.arange(0, s, QUERY_BLOCK, dtype=jnp.int32)
    attn = jax.lax.map(attend, starts).reshape(s, kvh * groups, d)
    attn = attn * jax.nn.sigmoid(g)
    return x + _rms(jnp.einsum("shk,hkd->sd", attn, w["wo"]), w["post_attn_norm"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_half(w, h, eps):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    m = _rms(h, w["pre_mlp_norm"], eps)
    gate_up = jnp.einsum("sd,dcf->scf", m, w["w_gate_up"])
    f = jnp.einsum("sf,fd->sd", jax.nn.silu(gate_up[:, 0]) * gate_up[:, 1], w["w_down"])
    return h + _rms(f, w["post_mlp_norm"], eps)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "route_norm", "route_scale"))
def _route(w, h, given, eps, top_k, route_norm, route_scale):
    """Over h: [S, dim]: the normed input m, the scores the top-k ranks by
    ([S, E]: ``s + b``), each position's expert ids [S, k] and combine
    weights [S, k]. ``given``: [rounds, k] ids for the LAST ``rounds``
    positions (rounds may be 0); every other position takes its own top-k.
    The weights come from the scores alone, for a given set too."""
    f32 = jnp.float32
    m = _rms(h, w["pre_mlp_norm"].astype(f32), eps)
    scores = jax.nn.sigmoid(jnp.dot(m, w["router"].astype(f32)))
    ranked = scores + w["router_bias"].astype(f32)
    _, ids = jax.lax.top_k(ranked, top_k)
    rounds = given.shape[0]
    if rounds:
        ids = jnp.concatenate([ids[: h.shape[0] - rounds], given.astype(ids.dtype)])
    chosen = jnp.take_along_axis(scores, ids, axis=1)
    if route_norm:
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return m, ranked, ids, chosen * route_scale


@jax.jit
def _experts(w, m, ids, weights):
    """sum_{e in S} w_e Expert_e(m) for a chunk m: [C, dim] with ids and
    weights [C, k]: each expert meets its own tokens only, a tile of rows at
    a time over the (token, expert) pairs sorted by expert (expert e owns the
    rows ends[e - 1] .. ends[e] of the sorted list), its weights in float32
    while they pass."""
    f32 = jnp.float32
    c, dim = m.shape
    top_k, n_experts = ids.shape[1], w["w_gate"].shape[0]
    flat = ids.reshape(-1)
    order = jnp.argsort(flat)
    token = order // top_k
    ends = jnp.cumsum(jnp.bincount(flat, length=n_experts))
    rows = jnp.pad(jnp.take(m, token, axis=0), ((0, ROW_TILE), (0, 0)))
    index = jnp.arange(ROW_TILE)

    def expert(e, out):
        start = jnp.where(e == 0, 0, ends[e - 1])
        wg, wu, wd = (w[name][e].astype(f32) for name in ("w_gate", "w_up", "w_down_moe"))

        def tile(i, out):
            off = start + i * ROW_TILE
            x = jax.lax.dynamic_slice_in_dim(rows, off, ROW_TILE)
            y = jnp.dot(jax.nn.silu(jnp.dot(x, wg)) * jnp.dot(x, wu), wd)
            old = jax.lax.dynamic_slice_in_dim(out, off, ROW_TILE)
            mine = (off + index < ends[e])[:, None]
            return jax.lax.dynamic_update_slice_in_dim(out, jnp.where(mine, y, old), off, 0)

        return jax.lax.fori_loop(0, (ends[e] - start + ROW_TILE - 1) // ROW_TILE, tile, out)

    out = jax.lax.fori_loop(0, n_experts, expert, jnp.zeros_like(rows))[: c * top_k]
    out = out * jnp.take(weights.reshape(-1), order)[:, None]
    return jnp.zeros((c, dim), f32).at[token].add(out)


@functools.partial(jax.jit, static_argnames=("eps",))
def _expert_close(w, h, m, routed, eps):
    """h + rms(Shared(m) + routed; w_post_mlp)."""
    f32 = jnp.float32
    shared = jnp.einsum("sd,dcf->scf", m, w["ws_gate_up"].astype(f32))
    f = routed + jnp.einsum(
        "sf,fd->sd", jax.nn.silu(shared[:, 0]) * shared[:, 1], w["ws_down"].astype(f32)
    )
    return h + _rms(f, w["post_mlp_norm"].astype(f32), eps)


def _expert_half(w, h, given, eps, top_k, route_norm, route_scale):
    """The expert layer's half over h: [S, dim]. Returns (h_next, the scores
    [S, E] the top-k ranks by)."""
    m, ranked, ids, weights = _route(w, h, given, eps, top_k, route_norm, route_scale)
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
    return _expert_close(w, h, m, routed, eps), ranked


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(norm_w, head_w, x, eps):
    x = _rms(x, norm_w.astype(jnp.float32), eps)
    return jnp.einsum("sd,dv->sv", x, head_w.astype(jnp.float32))


ATTENTION = ("in_norm", "post_attn_norm", "q_norm", "k_norm", "wq", "wk", "wv", "wg", "wo")
DENSE = ("pre_mlp_norm", "post_mlp_norm", "w_gate_up", "w_down")
EXPERT = ("pre_mlp_norm", "post_mlp_norm", "router", "router_bias", "w_gate", "w_up",
          "w_down_moe", "ws_gate_up", "ws_down")


def _forward(params, config: dict, tokens, last_n: int, choices):
    """The pass both entries share. ``choices``: None, or int [last_n, sites,
    k]. Returns (logits [last_n, vocab], gaps [last_n, sites] or None)."""
    if config["score_func"] != "sigmoid" or config.get("n_group", 1) != 1:
        raise ValueError("this reference writes out sigmoid scores without a group limit")
    n = len(tokens)
    padded = -(-n // QUERY_BLOCK) * QUERY_BLOCK
    # Padding sits after the real tokens: causal attention never lets a real
    # position see it, and its own outputs are dropped.
    toks = jnp.asarray(list(tokens) + [0] * (padded - n), jnp.int32)
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    top_k, experts = int(config["num_experts_per_tok"]), int(config["num_experts"])
    layers, dense_layers = int(config["num_hidden_layers"]), int(config["num_dense_layers"])
    sites = layers - dense_layers
    if choices is not None:
        choices = choice_gaps.check_sets(choices, last_n, [experts] * sites)
        if choices.shape[2] != top_k:
            raise ValueError(f"the sets hold {choices.shape[2]} ids, the top-k chooses {top_k}")
    of = lambda layer, names: {name: params[f"l{layer}.{name}"] for name in names}
    gaps = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], toks, axis=0).astype(jnp.float32)
        if config["mup_enabled"]:
            x = x * np.float32(np.sqrt(config["hidden_size"]))
        for layer in range(layers):
            window = int(config["sliding_window"]) if config["layer_types"][layer] == SLIDING else None
            x = _attention_half(of(layer, ATTENTION), x, theta, eps, window)
            if layer < dense_layers:
                x = _dense_half(of(layer, DENSE), x, eps)
                continue
            # The real tokens alone route: the given sets sit on their last rows.
            site = layer - dense_layers
            given = np.zeros((0, top_k), np.int32) if choices is None else choices[:, site]
            real, ranked = _expert_half(
                of(layer, EXPERT), x[:n], jnp.asarray(given), eps, top_k,
                bool(config["route_norm"]), float(config["route_scale"]),
            )
            x = jnp.concatenate([real, x[n:]])
            if choices is not None:
                gaps.append(choice_gaps.gaps(ranked[n - last_n :], given))
        out = _head(params["final_norm"], params["lm_head"], x[n - last_n : n], eps)
    return out, (jnp.stack(gaps, axis=1) if choices is not None else None)


def logits(params, config: dict, tokens, last_n: int):
    """``[last_n, vocab]`` float32 logits of the last ``last_n`` positions of
    ``tokens`` under ``params`` (the program's parameter dict) and ``config``
    (the configuration file's published keys); every position routes by its
    own float32 scores."""
    return _forward(params, config, tokens, last_n, None)[0]


def logits_following(params, config: dict, tokens, rounds: int, choices):
    """The same pass in which the last ``rounds`` positions take the expert
    sets ``choices`` (int ``[rounds, sites, k]``, a site an expert layer in
    the model's order; ``ValueError`` for a set that is not ``k`` distinct
    ids of the experts) with weights from this pass's own scores, and every
    other position its own top-k. Returns ``(logits [rounds, vocab] float32,
    gaps [rounds, sites] float32)``, the gaps as ``choice_gaps.gaps`` defines
    them over ``s + b``."""
    return _forward(params, config, tokens, rounds, np.asarray(choices))
