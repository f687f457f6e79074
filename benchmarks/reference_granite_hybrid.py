"""The plain reference of the ``granitemoehybrid`` configuration: the forward
pass as published, in float32 at matmul precision ``highest``.

Straightforward ``jax.numpy``: no kernel, no cache, no paging, no chunked
recurrence, nothing imported from the program. The only thing taken from the
program is the layout of its parameter dict (``l{i}.w_in`` is ``[dim, z | xBC
| dt]``; ``l{i}.wq`` is ``[dim, H, D]``; ``l{i}.w_gate`` is ``[held experts,
dim, width]``; ``l{i}.ws_gate_up`` is ``[dim, 2, shared width]``, gate then up
...), because the weights compared are the program's seeded ones.

From the configuration's file (the published ``config.json``): every size
(``hidden_size``, the attention's heads, ``mamba_n_heads`` / ``mamba_d_head``
/ ``mamba_d_state`` / ``mamba_n_groups`` / ``mamba_d_conv``,
``intermediate_size`` the routed expert's width, ``shared_intermediate_size``),
``layer_types``, ``num_experts_per_tok``, ``rms_norm_eps`` and the four
multipliers. From the family's published modelling code (NOT in
``config.json``; the file lists each under ``assumed``): the equations below.

  x_0     = embedding_multiplier E[token]
  h       = x + residual_multiplier mixer(rms(x; w_in))
  y       = h + residual_multiplier (sum_{e in S, e held here} g_e Expert_e(m) + Shared(m)),  m = rms(h; w_post)
  router  : l = W_r m;  S = the k largest logits;  g = softmax over those k alone
  Expert  : W_out (silu(a) b), [a, b] = W_in m;  Shared the same at its width
  Attn    : q = Wq n, k = Wk n, v = Wv n, no bias, NO rotation
            (``position_embedding_type`` nope); key j visible to query i iff
            j <= i; softmax(attention_multiplier q k^T) v, H / KVH query heads
            a KV head; Wo
  Mamba   : u = W_in n = [z | xBC | dt], no bias;  xBC <- silu(conv(xBC) + b),
            the convolution causal and depth-wise over the last
            ``mamba_d_conv`` positions;  [x, B, C] = xBC;
            dt_t = softplus(dt_t + dt_bias) a head;  a_t = exp(-exp(A_log) dt_t)
            S_t = a_t S_{t-1} + dt_t x_t B_t^T;  o_t = S_t C_t + D x_t
            (a scan, one token at a time; S [P, N] a head from zeros; head h
            reads B and C of group h // (heads / groups))
            mixer = W_out (rms(o silu(z)) w): the gate first, then the norm
            over each group's channels (one group as published: all of them)
  logits  = E rms(x_L; w_final) / logits_scaling    (the embedding, tied),
            rms(x; w) = x rsqrt(mean(x^2) + eps) w

The share (the file's ``deployment``): this chip holds the experts
``experts_held`` = [first, count] of the router's ``router_experts`` and the
shared expert (the share that holds expert 0 counts it). A position routes
over ALL experts; the experts it chose that live elsewhere add nothing here,
in the program and in this reference alike, and that partial result goes on.
The vocabulary is the file's ``vocab_size``, a slice: the logits are over it.

Departures, each for memory alone (the check runs beside the program's
weights and cache on one chip): the sequence passes a layer in segments of
``SEGMENT`` tokens that carry the mixer's state and its convolution's last
rows, or the layer's keys and values, from one to the next (the recurrence is
still a token at a time, the attention still over every earlier key); one
layer's mixer weights are held in float32 at a time and an expert's only while
its tokens pass; an expert is applied to its own tokens only, a tile of rows
at a time over the segment's (token, expert) pairs sorted by expert; attention
is computed a block of queries at a time, the head a slice of the vocabulary
at a time. None changes the mathematics.

``logits_following`` is the same pass in which the last ``rounds`` positions
take the expert sets they are given (the weights still from this pass's own
logits) and reports how far each set lies off these logits
(``choice_gaps.gaps``; the score the top-k ranks by is the logit). Where the
program reports the sets of the tokens before a row too (its
``route_tail_tokens``: ``choices`` is then ``[rounds, sites x (1 + tail), k]``,
a row's own sets first, then the sets of the tokens before it, the nearest
first, -1 where the prompt had not begun) the tokens before the FIRST compared
row take those: a recurrent state is a sum over the context in which the last
few hundred tokens weigh most, so a row's logits depend on the discrete sets
those tokens chose (``reference_kimi_linear.py`` and PERF.md, PR 41, say by how
much). The gaps reported are the compared rows' own.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

import choice_gaps

SEGMENT = 2048  # tokens a layer takes at a time
QUERY_BLOCK = 128
ROW_TILE = 256  # rows of sorted (token, expert) pairs an expert takes at a time
VOCAB_BLOCK = 12544  # the head's rows at a time

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _f32(w):
    return {k: v.astype(F32) for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("sizes",))
def _mamba(w, x, state, tail, sizes):
    """x + residual_multiplier Mamba(rms(x)) on one segment x: [S, dim]:
    returns (h, state, tail). ``state`` [H_s, P, N] and ``tail`` [taps - 1,
    conv width] as the tokens before left them."""
    eps, residual, heads, head_dim, n_state, groups, taps = sizes
    w = _f32(w)
    seg = x.shape[0]
    n = _rms(x, w["in_norm"], eps)
    width, group = heads * head_dim, groups * n_state
    u = jnp.dot(n, w["w_in"])
    z, pre, dt = u[:, :width], u[:, width : 2 * width + 2 * group], u[:, 2 * width + 2 * group :]
    dt = jax.nn.softplus(dt + w["dt_bias"])  # [S, H_s]
    rows = jnp.concatenate([tail, pre])
    conv = sum(rows[i : i + seg] * w["conv_w"][i] for i in range(taps)) + w["conv_b"]
    conv = jax.nn.silu(conv)
    xs = conv[:, :width].reshape(seg, heads, head_dim)
    per_group = heads // groups
    b = jnp.repeat(conv[:, width : width + group].reshape(seg, groups, n_state), per_group, axis=1)
    c = jnp.repeat(conv[:, width + group :].reshape(seg, groups, n_state), per_group, axis=1)
    decay = jnp.exp(-jnp.exp(w["A_log"])[None, :] * dt)  # [S, H_s]

    def token(state, at):
        x_t, b_t, c_t, dt_t, a_t = at
        state = a_t[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    state, o = jax.lax.scan(token, state, (xs, b, c, dt, decay))
    o = o + w["D"][None, :, None] * xs
    gated = (o.reshape(seg, width) * jax.nn.silu(z)).reshape(seg, groups, -1)
    gated = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
    mamba = jnp.dot(gated.reshape(seg, width) * w["ssm_norm"], w["w_out"])
    return x + residual * mamba, state, rows[seg:]


@functools.partial(jax.jit, static_argnames=("sizes",), donate_argnames=("keys", "values"))
def _attention(w, x, start, keys, values, sizes):
    """x + residual_multiplier Attn(rms(x)) on one segment at positions
    ``start ..``: returns (h, keys, values). ``keys`` / ``values``: [S_total,
    KVH, D], filled up to ``start`` and from here up to the segment's end."""
    eps, residual, heads, kv_heads, scale = sizes
    w = _f32(w)
    seg = x.shape[0]
    n = _rms(x, w["in_norm"], eps)
    q = jnp.einsum("sd,dhk->shk", n, w["wq"])
    keys = jax.lax.dynamic_update_slice_in_dim(keys, jnp.einsum("sd,dhk->shk", n, w["wk"]), start, 0)
    values = jax.lax.dynamic_update_slice_in_dim(values, jnp.einsum("sd,dhk->shk", n, w["wv"]), start, 0)
    per_kv, head_dim = heads // kv_heads, q.shape[-1]
    all_pos = jnp.arange(keys.shape[0], dtype=jnp.int32)

    def attend(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK).reshape(QUERY_BLOCK, kv_heads, per_kv, head_dim)
        qpos = start + q0 + jnp.arange(QUERY_BLOCK, dtype=jnp.int32)
        logits = jnp.einsum("qkgd,tkd->kgqt", qb, keys) * scale
        seen = all_pos[None, :] <= qpos[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None, None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", probs, values).reshape(QUERY_BLOCK, -1)

    attn = jax.lax.map(attend, jnp.arange(0, seg, QUERY_BLOCK, dtype=jnp.int32)).reshape(seg, -1)
    return x + residual * jnp.dot(attn, w["wo"]), keys, values


@functools.partial(jax.jit, static_argnames=("eps", "top_k"))
def _route(w, h, given, eps, top_k):
    """Over h: [S, dim]: the normed input m, the router's logits [S, E] (what
    the top-k ranks by), each position's expert ids [S, k] and combine weights
    [S, k]. ``given``: [S, k] ids, a row of -1 where the position takes its own
    top-k. The weights are the softmax over the chosen logits, for a given set
    too."""
    m = _rms(h, w["pre_mlp_norm"].astype(F32), eps)
    logits = jnp.dot(m, w["router"].astype(F32))
    _, ids = jax.lax.top_k(logits, top_k)
    ids = jnp.where(given[:, :1] < 0, ids, given.astype(ids.dtype))
    return m, logits, ids, jax.nn.softmax(jnp.take_along_axis(logits, ids, axis=1), axis=-1)


@functools.partial(jax.jit, static_argnames=("first",))
def _experts(w, m, ids, weights, first):
    """sum over the chosen experts HELD HERE (ids first .. first + count - 1,
    the leading axis of the weights) of g_e Expert_e(m), for a segment m: [C,
    dim] with ids (among all experts) and weights [C, k]. Each held expert
    meets its own tokens only, a tile of rows at a time over the (token,
    expert) pairs sorted by expert; a pair whose expert lives elsewhere sorts
    last and adds nothing."""
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
        wg, wu, wd = (w[name][e].astype(F32) for name in ("w_gate", "w_up", "w_down_moe"))

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
    return jnp.zeros((c, dim), F32).at[token].add(out)


@functools.partial(jax.jit, static_argnames=("shared", "residual"))
def _expert_close(w, h, m, routed, shared, residual):
    """h + residual_multiplier (routed (+ Shared(m) where this share counts it))."""
    if shared:
        gate_up = jnp.einsum("sd,dcf->scf", m, w["ws_gate_up"].astype(F32))
        routed = routed + jnp.dot(jax.nn.silu(gate_up[:, 0]) * gate_up[:, 1], w["ws_down"].astype(F32))
    return h + residual * routed


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(w, x, eps):
    return _rms(x, w.astype(F32), eps)


@jax.jit
def _head_block(rows, x):
    return jnp.dot(x, rows.astype(F32).T)


MAMBA_KEYS = ("in_norm", "w_in", "conv_w", "conv_b", "A_log", "dt_bias", "D", "ssm_norm", "w_out")
ATTENTION_KEYS = ("in_norm", "wq", "wk", "wv", "wo")
ROUTER = ("pre_mlp_norm", "router")
HELD = ("w_gate", "w_up", "w_down_moe")
SHARED = ("ws_gate_up", "ws_down")


def _forward(params, config: dict, tokens, last_n: int, choices):
    """The pass both entries share. ``choices``: None, or int [last_n, sites
    (x (1 + tail)), k]. Returns (logits [last_n, vocab], gaps [last_n, sites]
    or None)."""
    if config.get("position_embedding_type") != "nope":
        raise ValueError("this reference applies no positional encoding (position_embedding_type nope)")
    if config.get("attention_bias") or config.get("mamba_proj_bias") or not config.get("mamba_conv_bias"):
        raise ValueError("this reference writes out projections without bias and a convolution with one")
    if not config.get("tie_word_embeddings"):
        raise ValueError("this reference writes out the tied head")
    n = len(tokens)
    if last_n > min(n, SEGMENT):
        raise ValueError(f"the last {last_n} rows do not lie in the last two segments of {n} tokens")
    padded = -(-n // SEGMENT) * SEGMENT if n > SEGMENT else -(-n // QUERY_BLOCK) * QUERY_BLOCK
    seg = min(SEGMENT, padded)
    # Padding sits after the real tokens: neither a causal attention nor a
    # recurrence lets a real position see it, and its own outputs are dropped.
    toks = jnp.asarray(list(tokens) + [0] * (padded - n), jnp.int32)
    eps, residual = float(config["rms_norm_eps"]), float(config["residual_multiplier"])
    kinds = list(config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]) or set(kinds) - {"mamba", "attention"}:
        raise ValueError("layer_types names num_hidden_layers layers, each mamba or attention")
    heads, kv_heads = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    head_dim = int(config["hidden_size"]) // heads
    ssm_heads, ssm_head_dim = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    if ssm_heads * ssm_head_dim != int(config["mamba_expand"]) * int(config["hidden_size"]):
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x hidden_size")
    n_state, groups, taps = (int(config[k]) for k in ("mamba_d_state", "mamba_n_groups", "mamba_d_conv"))
    conv_width = ssm_heads * ssm_head_dim + 2 * groups * n_state
    top_k = int(config["num_experts_per_tok"])
    routed_over = int(config.get("router_experts", config["num_local_experts"]))
    first, _count = config.get("experts_held", (0, routed_over))
    shared = first == 0
    sites = len(kinds)
    # Per site, the sets given to the pass: [padded, k], -1 where a position
    # takes its own top-k.
    given = np.full((sites, padded, top_k), -1, np.int64)
    followed = 0  # the last ``followed`` real positions take given sets
    if choices is not None:
        choices = np.asarray(choices)
        before = np.zeros((0, sites, top_k), np.int64)
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
        sets = np.concatenate([before, choices])  # [followed, sites, k], the oldest first
        followed = len(sets)
        given[:, n - followed : n] = np.moveaxis(sets, 1, 0)
    mamba_sizes = (eps, residual, ssm_heads, ssm_head_dim, n_state, groups, taps)
    attention_sizes = (eps, residual, heads, kv_heads, float(config["attention_multiplier"]))
    of = lambda layer, names: {name: params[f"l{layer}.{name}"] for name in names}
    starts = range(0, padded, seg)
    gaps = []
    with jax.default_matmul_precision("highest"):
        embed = params["embed"]
        xs = [
            jnp.take(embed, toks[a : a + seg], axis=0).astype(F32) * float(config["embedding_multiplier"])
            for a in starts
        ]
        for layer, kind in enumerate(kinds):
            if kind == "mamba":
                w = of(layer, MAMBA_KEYS)
                state = jnp.zeros((ssm_heads, ssm_head_dim, n_state), F32)
                tail = jnp.zeros((taps - 1, conv_width), F32)
            else:
                w = of(layer, ATTENTION_KEYS)
                keys = jnp.zeros((padded, kv_heads, head_dim), F32)
                values = jnp.zeros_like(keys)
            router, held, shared_w = of(layer, ROUTER), of(layer, HELD), of(layer, SHARED)
            ranked = []
            for i, a in enumerate(starts):
                if kind == "mamba":
                    h, state, tail = _mamba(w, xs[i], state, tail, mamba_sizes)
                else:
                    h, keys, values = _attention(w, xs[i], jnp.int32(a), keys, values, attention_sizes)
                m, logits, ids, weights = _route(router, h, jnp.asarray(given[layer, a : a + seg]), eps, top_k)
                routed = _experts(held, m, ids, weights, int(first))
                xs[i] = _expert_close(shared_w, h, m, routed, shared, residual)
                ranked.append(logits)
            if choices is not None:
                ranked = jnp.concatenate(ranked)[n - last_n : n]
                gaps.append(choice_gaps.gaps(ranked, given[layer, n - last_n : n]))
        last = jnp.concatenate(xs[-2:])  # the compared rows may begin in the segment before
        end = n - (len(xs) - len(xs[-2:])) * seg
        x = _final_norm(params["final_norm"], last[end - last_n : end], eps)
        out = jnp.concatenate([
            _head_block(embed[a : a + VOCAB_BLOCK], x) for a in range(0, embed.shape[0], VOCAB_BLOCK)
        ], axis=1)
    out = out / float(config["logits_scaling"])
    return out, (jnp.stack(gaps, axis=1) if choices is not None else None)


def logits(params, config: dict, tokens, last_n: int):
    """``[last_n, vocab]`` float32 logits of the last ``last_n`` positions of
    ``tokens`` under ``params`` (the program's parameter dict) and ``config``
    (the configuration file's keys); every position routes by its own float32
    logits."""
    return _forward(params, config, tokens, last_n, None)[0]


def logits_following(params, config: dict, tokens, rounds: int, choices):
    """The same pass in which the last ``rounds`` positions take the expert
    sets ``choices`` (int ``[rounds, sites, k]``, a site a layer in the model's
    order; ``ValueError`` for a set that is not ``k`` distinct ids of the
    router's experts) with weights from this pass's own logits, and every other
    position its own top-k; where the program reports the sets of the tokens
    before a row too (``[rounds, sites x (1 + tail), k]``, module docstring)
    the tokens before the first row take those. Returns ``(logits [rounds,
    vocab] float32, gaps [rounds, sites] float32)``, the gaps of the rows' own
    sets as ``choice_gaps.gaps`` defines them over the router's logits."""
    return _forward(params, config, tokens, rounds, np.asarray(choices))
