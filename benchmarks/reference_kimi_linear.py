"""The plain reference of the ``kimi_linear`` configuration: the forward pass
as published, in float32 at matmul precision ``highest``.

Straightforward ``jax.numpy``: no kernel, no cache, no paging, no chunked
recurrence, nothing imported from the program. The only thing taken from the
program is the layout of its parameter dict (``l{i}.w_qkv`` is ``[dim, 3 H
K]``: q, k and v side by side; ``l{i}.w_kvb`` is ``[rank, H, nope + v]``;
``l{i}.w_gate`` is ``[held experts, dim, width]`` ...), because the weights
compared are the program's seeded ones.

From the configuration's file (the published ``config.json``): the sizes,
``linear_attn_config`` (which layers are KDA, 1-based; heads, head size, the
convolution's taps), the MLA ranks and head sizes, ``first_k_dense_replace``,
``num_experts_per_token``, ``moe_renormalize``, ``routed_scaling_factor``,
``moe_router_activation_func`` sigmoid, ``num_expert_group`` / ``topk_group``
1 (a plain top-k), ``rms_norm_eps``, ``mla_use_nope``. From arXiv:2510.26692
and the published modelling code (NOT in ``config.json``; the file lists
each under ``assumed``): the equations below.

  x0      = E[token]                      (pre-norm, no positional rotation)
  h       = x + mixer(rms(x; w_in))       y = h + mlp(rms(h; w_pre_mlp))
  KDA     : [q, k, v] = silu(conv([Wq n, Wk n, Wv n])), the convolution causal
            and depth-wise over the last 4 positions, no bias
            q <- q / sqrt(sum q^2 + 1e-6) / sqrt(K);  k <- k / sqrt(sum k^2 + 1e-6)
            g_t = -exp(A_log[h]) softplus(W_fb (W_fa n_t) + dt_bias)   [H, K]
            beta_t = sigmoid(W_b n_t)                                  [H]
            S' = diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
            o_t = S_t^T q_t                  (a scan, one token at a time)
            mixer = Wo (rms_head(o_t; w_o) * sigmoid(W_gb (W_ga n_t)))
  MLA     : q = Wq n;  [c, k_r] = W_kva n;  c <- rms(c; w_kv);  [k_n, v] = W_kvb c
            k = [k_n, k_r shared by all heads];  key j visible to query i iff j <= i
            mixer = Wo softmax(q k^T / sqrt(nope + rope)) v        (unabsorbed)
  dense   : Wdown (silu(Wgate m) * Wup m)
  expert  : s = sigmoid(Wr m);  S = top-k of (s + b)
            w_e = routed_scaling_factor * s_e / (sum_{e in S} s_e + 1e-20)
            f = Shared(m) + sum_{e in S, e held here} w_e Expert_e(m)
  logits  = Whead rms(y_L; w_final),   rms(x; w) = x * rsqrt(mean(x^2) + eps) * w

The share (the file's ``deployment``): this chip holds the experts
``experts_held`` = [first, count] of the router's ``router_experts`` and the
shared expert (the share that holds expert 0 counts it). A position routes
over ALL experts; the experts it chose that live elsewhere add nothing here,
in the program and in this reference alike, and that partial result goes on.
The vocabulary is the file's ``vocab_size``, a slice.

Departures, each for memory alone: one layer's weights are held in float32
at a time and an expert's only while its tokens pass; attention is computed a
block of queries at a time; an expert is applied to its own tokens only, a
chunk of tokens and a tile of rows at a time over the chunk's (token, expert)
pairs sorted by expert. None changes the mathematics.

``logits_following`` is the same pass in which the last ``rounds`` positions
take the expert sets they are given (the weights still from this pass's own
scores) and reports how far each set lies off these scores
(``choice_gaps.gaps``; the score the top-k ranks by is ``s + b``).

**The context's choices.** A KDA state is a sum over the context in which
the last few hundred tokens weigh most, so a row's logits depend on the
discrete sets those tokens chose: in float32 on both sides, letting 10% of
the context's (token, layer) pairs fall on a near-tie's other side moves a
row's logits by 2.6-5.1% of their rms (PERF.md, PR 41: measured on the chip at
the published widths), twice the limit the comparison holds a precision to.
The program therefore keeps, beside its state, the sets its last
``route_tail_tokens`` tokens chose and reports them with every row: ``choices``
is ``[rounds, sites x (1 + tail), k]``, a row's own sets first, then the sets
of the tokens before it, the nearest first, -1 where the prompt had not
begun. This pass gives the tokens before the FIRST compared row the sets the
program says they took (the later rows' context is the earlier rows, whose
own sets are followed anyway). The gaps it reports are the compared rows'
own: the oldest tokens of the tail stand on a context that is not followed,
so their scores here are off by what this paragraph began with and a gap
against them says nothing of the program; a context set that is not what the
program took still shows, in the rows' logits, which are held to 2.5%.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

import choice_gaps

QUERY_BLOCK = 128
ROW_TILE = 256  # rows of sorted (token, expert) pairs an expert takes at a time
TOKEN_CHUNK = 4096  # tokens whose pairs are sorted and held at a time


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("eps", "heads", "taps"))
def _kda_half(w, x, eps, heads, taps):
    """x + KDA(rms(x)) over the whole sequence x: [S, dim] float32, the
    recurrence a token at a time from a zero state."""
    w = _f32(w)
    s = x.shape[0]
    n = _rms(x, w["in_norm"], eps)
    pre = jnp.dot(n, w["w_qkv"])  # [S, 3 H K]
    rows = jnp.concatenate([jnp.zeros((taps - 1, pre.shape[1]), jnp.float32), pre])
    conv = sum(rows[i : i + s] * w["conv_w"][i] for i in range(taps))
    q, k, v = jnp.moveaxis(jax.nn.silu(conv).reshape(s, 3, heads, -1), 1, 0)
    dk = q.shape[-1]
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) / np.sqrt(dk), unit(k)
    decay_in = jnp.dot(jnp.dot(n, w["w_fa"]), w["w_fb"]) + w["dt_bias"]
    g = -jnp.exp(w["A_log"])[None, :, None] * jax.nn.softplus(decay_in).reshape(s, heads, dk)
    beta = jax.nn.sigmoid(jnp.dot(n, w["w_b"]))  # [S, H]

    def token(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        decayed = jnp.exp(g_t)[:, :, None] * state  # [H, K, V]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", decayed, k_t))
        state = decayed + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    zero = jnp.zeros((heads, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(token, zero, (q, k, v, g, beta))
    gate = jax.nn.sigmoid(jnp.dot(jnp.dot(n, w["w_ga"]), w["w_gb"])).reshape(s, heads, -1)
    out = _rms(o, w["o_norm"], eps) * gate
    return x + jnp.dot(out.reshape(s, -1), w["wo"])


@functools.partial(jax.jit, static_argnames=("eps", "rank", "nope"))
def _mla_half(w, x, eps, rank, nope):
    """x + MLA(rms(x)), unabsorbed, over x: [S, dim] float32, S a multiple of
    QUERY_BLOCK."""
    w = _f32(w)
    s = x.shape[0]
    n = _rms(x, w["in_norm"], eps)
    q = jnp.einsum("sd,dhk->shk", n, w["wq"])  # [S, H, nope + rope]
    kva = jnp.dot(n, w["w_kva"])
    c, k_r = _rms(kva[:, :rank], w["kv_norm"], eps), kva[:, rank:]
    kv = jnp.einsum("sr,rhd->shd", c, w["w_kvb"])  # [S, H, nope + v]
    h = q.shape[1]
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r[:, None, :], (s, h, k_r.shape[1]))], -1)
    v = kv[..., nope:]
    scale = 1.0 / np.sqrt(q.shape[-1])
    positions = jnp.arange(s, dtype=jnp.int32)

    def attend(q0):
        qpos = q0 + jnp.arange(QUERY_BLOCK, dtype=jnp.int32)
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK)
        logits = jnp.einsum("qhd,thd->hqt", qb, k) * scale
        seen = positions[None, :] <= qpos[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thd->qhd", probs, v)

    starts = jnp.arange(0, s, QUERY_BLOCK, dtype=jnp.int32)
    attn = jax.lax.map(attend, starts).reshape(s, -1)
    return x + jnp.dot(attn, w["wo"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_half(w, h, eps):
    w = _f32(w)
    m = _rms(h, w["pre_mlp_norm"], eps)
    gate_up = jnp.einsum("sd,dcf->scf", m, w["w_gate_up"])
    return h + jnp.dot(jax.nn.silu(gate_up[:, 0]) * gate_up[:, 1], w["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "renormalize", "scale"))
def _route(w, h, given, eps, top_k, renormalize, scale):
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
    if renormalize:
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return m, ranked, ids, chosen * scale


@functools.partial(jax.jit, static_argnames=("first",))
def _experts(w, m, ids, weights, first):
    """sum over the chosen experts HELD HERE (ids first .. first + count - 1,
    the leading axis of the weights) of w_e Expert_e(m), for a chunk m: [C,
    dim] with ids (among all experts) and weights [C, k]. Each held expert
    meets its own tokens only, a tile of rows at a time over the (token,
    expert) pairs sorted by expert; a pair whose expert lives elsewhere sorts
    last and adds nothing."""
    f32 = jnp.float32
    c, dim = m.shape
    top_k, count = ids.shape[1], w["w_gate"].shape[0]
    local = ids.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(local)
    token = order // top_k
    ends = jnp.cumsum(jnp.bincount(local, length=count + 1))[:count]
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

    out = jax.lax.fori_loop(0, count, expert, jnp.zeros_like(rows))[: c * top_k]
    out = out * jnp.take(weights.reshape(-1), order)[:, None]
    return jnp.zeros((c, dim), f32).at[token].add(out)


@functools.partial(jax.jit, static_argnames=("shared",))
def _expert_close(w, h, m, routed, shared):
    """h + routed (+ Shared(m) where this share counts it)."""
    if not shared:
        return h + routed
    f32 = jnp.float32
    gate_up = jnp.einsum("sd,dcf->scf", m, w["ws_gate_up"].astype(f32))
    return h + routed + jnp.dot(
        jax.nn.silu(gate_up[:, 0]) * gate_up[:, 1], w["ws_down"].astype(f32)
    )


def _expert_half(w, h, given, eps, top_k, renormalize, scale, first, shared):
    """The expert layer's half over h: [S, dim]. Returns (h_next, the scores
    [S, E] the top-k ranks by)."""
    m, ranked, ids, weights = _route(w, h, given, eps, top_k, renormalize, scale)
    s = h.shape[0]
    chunk = min(s, TOKEN_CHUNK)
    pad = -s % chunk
    cut = lambda x: jnp.pad(x, ((0, pad), (0, 0)))  # padded rows weigh nothing
    mp, ip, wp = cut(m), cut(ids), cut(weights)
    held = {name: w[name] for name in ("w_gate", "w_up", "w_down_moe")}
    routed = jnp.concatenate([
        _experts(held, mp[a : a + chunk], ip[a : a + chunk], wp[a : a + chunk], first)
        for a in range(0, s + pad, chunk)
    ])[:s]
    return _expert_close(w, h, m, routed, shared), ranked


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(norm_w, head_w, x, eps):
    x = _rms(x, norm_w.astype(jnp.float32), eps)
    return jnp.dot(x, head_w.astype(jnp.float32))


KDA_KEYS = ("in_norm", "w_qkv", "conv_w", "A_log", "dt_bias", "w_fa", "w_fb", "w_b",
            "w_ga", "w_gb", "o_norm", "wo")
MLA_KEYS = ("in_norm", "wq", "w_kva", "kv_norm", "w_kvb", "wo")
DENSE = ("pre_mlp_norm", "w_gate_up", "w_down")
EXPERT = ("pre_mlp_norm", "router", "router_bias", "w_gate", "w_up", "w_down_moe",
          "ws_gate_up", "ws_down")


def _forward(params, config: dict, tokens, last_n: int, choices):
    """The pass both entries share. ``choices``: None, or int [last_n, sites,
    k]. Returns (logits [last_n, vocab], gaps [last_n, sites] or None)."""
    if config["moe_router_activation_func"] != "sigmoid" or (
        config.get("num_expert_group", 1), config.get("topk_group", 1)
    ) != (1, 1):
        raise ValueError("this reference writes out sigmoid scores and a plain top-k")
    if not config.get("mla_use_nope", False):
        raise ValueError("this reference applies no positional rotation (mla_use_nope)")
    n = len(tokens)
    padded = -(-n // QUERY_BLOCK) * QUERY_BLOCK
    # Padding sits after the real tokens: neither a causal attention nor a
    # recurrence lets a real position see it, and its own outputs are dropped.
    toks = jnp.asarray(list(tokens) + [0] * (padded - n), jnp.int32)
    eps = float(config["rms_norm_eps"])
    linear = config["linear_attn_config"]
    top_k = int(config["num_experts_per_token"])
    layers, dense_layers = int(config["num_hidden_layers"]), int(config["first_k_dense_replace"])
    routed_over = int(config.get("router_experts", config["num_experts"]))
    first, _count = config.get("experts_held", (0, routed_over))
    shared = first == 0 and int(config["num_shared_experts"]) > 0
    sites = layers - dense_layers
    before = np.zeros((0, sites, top_k), np.int64)  # the context's sets, the oldest first
    if choices is not None:
        choices = np.asarray(choices)
        if choices.ndim == 3 and choices.shape[1] > sites and choices.shape[1] % sites == 0:
            # The first compared row's tail: the tokens before it, nearest
            # first; those before the prompt's start (-1) and beyond are none.
            tail = choices[0, sites:].reshape(-1, sites, choices.shape[2])[: n - last_n]
            began = int(np.argmax(np.any(tail < 0, axis=(1, 2)))) if np.any(tail < 0) else len(tail)
            before = tail[:began][::-1]
            choices = choices[:, :sites]
        choices = choice_gaps.check_sets(choices, last_n, [routed_over] * sites)
        if choices.shape[2] != top_k:
            raise ValueError(f"the sets hold {choices.shape[2]} ids, the top-k chooses {top_k}")
        if len(before):
            before = choice_gaps.check_sets(before, len(before), [routed_over] * sites)
    of = lambda layer, names: {name: params[f"l{layer}.{name}"] for name in names}
    gaps = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], toks, axis=0).astype(jnp.float32)
        for layer in range(layers):
            if layer + 1 in linear["kda_layers"]:
                x = _kda_half(
                    of(layer, KDA_KEYS), x, eps, int(linear["num_heads"]),
                    int(linear["short_conv_kernel_size"]),
                )
            else:
                x = _mla_half(
                    of(layer, MLA_KEYS), x, eps, int(config["kv_lora_rank"]),
                    int(config["qk_nope_head_dim"]),
                )
            if layer < dense_layers:
                x = _dense_half(of(layer, DENSE), x, eps)
                continue
            # The real tokens alone route: the given sets sit on their last rows.
            site = layer - dense_layers
            given = (
                np.zeros((0, top_k), np.int32) if choices is None
                else np.concatenate([before[:, site], choices[:, site]])
            )
            real, ranked = _expert_half(
                of(layer, EXPERT), x[:n], jnp.asarray(given), eps, top_k,
                bool(config["moe_renormalize"]), float(config["routed_scaling_factor"]),
                int(first), shared,
            )
            x = jnp.concatenate([real, x[n:]])
            if choices is not None:
                gaps.append(choice_gaps.gaps(ranked[n - len(given) :], given))
        out = _head(params["final_norm"], params["lm_head"], x[n - last_n : n], eps)
    if choices is None:
        return out, None
    return out, jnp.stack(gaps, axis=1)[len(before) :]  # the compared rows' own


def logits(params, config: dict, tokens, last_n: int):
    """``[last_n, vocab]`` float32 logits of the last ``last_n`` positions of
    ``tokens`` under ``params`` (the program's parameter dict) and ``config``
    (the configuration file's keys); every position routes by its own float32
    scores."""
    return _forward(params, config, tokens, last_n, None)[0]


def logits_following(params, config: dict, tokens, rounds: int, choices):
    """The same pass in which the last ``rounds`` positions take the expert
    sets ``choices`` (int ``[rounds, sites, k]``, a site an expert layer in
    the model's order; ``ValueError`` for a set that is not ``k`` distinct
    ids of the router's experts) with weights from this pass's own scores, and
    every other position its own top-k; where the program reports the sets of
    the tokens before a row too (``[rounds, sites x (1 + tail), k]``, module
    docstring) the tokens before the first row take those. Returns ``(logits
    [rounds, vocab] float32, gaps [rounds, sites] float32)``, the gaps of the
    rows' own sets as ``choice_gaps.gaps`` defines them over ``s + b``."""
    return _forward(params, config, tokens, rounds, np.asarray(choices))
