"""The plain reference of the ``phi4flash`` configuration ("SambaY"): the
forward pass as published, in float32 at matmul precision ``highest``.

Straightforward ``jax.numpy``: no kernel, no cache, no paging, no pair layout,
the recurrence a token at a time, the softmaxes of a head pair one by one.
Nothing is imported from the program; the only thing taken from it is the
layout of its parameter dict (``l{i}.w_in`` is ``[dim, u | z, channels]``;
``l{i}.wq`` is ``[dim, H, D]``; ``l{i}.w1`` is ``[dim, gate | up, width]``;
``l{i}.lambdas`` is ``[lq1, lk1, lq2, lk2]`` ...), because the weights
compared are the program's seeded ones.

From the configuration's file (the published ``config.json``): ``hidden_size``,
``num_hidden_layers``, the heads, ``intermediate_size``, ``sliding_window``,
``mb_per_layer``, ``layer_norm_eps``. From the published modelling code and
the paper (NOT in ``config.json``; the file lists each under ``assumed``): the
Mamba sizes (the file's ``mamba_d_state``, ``mamba_d_conv``, ``mamba_expand``,
``mamba_dt_rank``) and the equations below. With L layers, ``full = L / 2 +
1``, LN a LayerNorm with weight and bias:

  x_0     = E[token];  x = x + Mixer_l(LN(x));  x = x + MLP_l(LN(x))
  MLP     : W_2 (u silu(g)), [g, u] = W_1 h
  kinds   : l < full: even a Mamba-1 mixer, odd a sliding differential
            attention; l = full a full differential attention; l > full: even
            a gated memory unit, odd a cross differential attention that has a
            query projection alone and reads layer ``full``'s K and V
  Mamba-1 : [u, z] = W_in h;  u <- silu(conv(u) + b), causal, depth-wise over
            the last ``mamba_d_conv`` positions;  [r, B, C] = W_x u;
            dt = softplus(W_dt r + b_dt);  A = -exp(A_log)
            h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] u_t[c] B_t[n]
            y_t[c] = sum_n h_t[c, n] C_t[n] + D[c] u_t[c]   (a scan from zeros)
            out = W_out (y silu(z)).  The last Mamba layer's y, BEFORE the
            gate, is the memory m the units past ``full`` read
  GMU     : W_out (m_t silu(W_in h_t)): a token reads m of its own position
  DiffAttn: query heads (2 p, 2 p + 1) = (q1, q2); K/V heads (2 g, 2 g + 1) =
            (k1, k2), (v1, v2); pair p reads group g = p // (pairs / groups);
            s1 = softmax(q1 k1^T / sqrt(D)), s2 = softmax(q2 k2^T / sqrt(D));
            a1 = [s1 v1 | s1 v2], a2 = [s2 v1 | s2 v2];
            o_p = (1 - L0) rms(a1 - lam a2) w over the pair's 2 D values,
            lam = exp(lq1 . lk1) - exp(lq2 . lk2) + L0, L0 = 0.8 - 0.6 exp(-0.3 l);
            the o_p side by side into W_o, with bias (as have Wq, Wk, Wv).
            Key s visible to query t iff s <= t, and in a sliding layer
            t - window < s
  logits  = LN(x_L) E^T                         (the embedding, tied)

``logits`` gives the last ``last_n`` positions' rows. Rows of the layers past
``full`` do not interact (a cross layer reads layer ``full``'s K and V, a unit
its own position's m), so it computes those layers, and layer ``full``'s own
attention, for the compared rows alone; ``logits_all_rows`` is the same
mathematics with nothing left out and nothing cut up (every layer on every
position, dense masks), for the test that holds the one to the other.

Departures of ``logits``, each for memory alone (the check runs beside the
program's weights and cache on one chip): the sequence passes a layer of the
self-decoder in segments of ``SEGMENT`` tokens that carry the scan's state and
its convolution's last rows, or the window's keys and values, from one to the
next; a sliding layer attends a block of queries at a time; one layer's
weights are held in float32 at a time; the head a slice of the vocabulary at a
time. None changes the mathematics.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

SEGMENT = 2048  # tokens a self-decoder layer takes at a time
QUERY_BLOCK = 128
VOCAB_BLOCK = 12504  # the head's rows at a time (200,064 = 16 x 12,504)

F32 = jnp.float32


def _f32(w):
    return {k: v.astype(F32) for k, v in w.items()}


def _ln(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w + b


def _mlp(w, x, eps):
    gate_up = jnp.einsum("td,dcf->tcf", _ln(x, w["mlp_norm"], w["mlp_norm_b"], eps), w["w1"])
    return x + jnp.dot(gate_up[:, 1] * jax.nn.silu(gate_up[:, 0]), w["w2"])


def _scan(w, u, r_state, sizes):
    """The selective scan of u: [S, C] (after the convolution) from the state
    ``r_state`` [C, N], a token at a time. Returns (y, state)."""
    rank, n_state = sizes
    rbc = jnp.dot(u, w["w_x"])
    dt = jax.nn.softplus(jnp.dot(rbc[:, :rank], w["w_dt"]) + w["b_dt"])
    b, c = rbc[:, rank : rank + n_state], rbc[:, rank + n_state :]
    a = -jnp.exp(w["A_log"])

    def token(h, at):
        u_t, dt_t, b_t, c_t = at
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * u_t)[:, None] * b_t[None, :]
        return h, jnp.dot(h, c_t)

    state, y = jax.lax.scan(token, r_state, (u, dt, b, c))
    return y + w["D"][None] * u, state


@functools.partial(jax.jit, static_argnames=("sizes",))
def _mamba(w, x, state, tail, sizes):
    """One segment x: [S, dim] through a Mamba-1 layer and its MLP. ``state``
    [C, N] and ``tail`` [taps - 1, C] as the tokens before left them. Returns
    (x, state, tail, y): y the scan's output before the gate."""
    eps, taps, rank, n_state = sizes
    w = _f32(w)
    seg = x.shape[0]
    uz = jnp.einsum("td,dcf->tcf", _ln(x, w["in_norm"], w["in_norm_b"], eps), w["w_in"])
    rows = jnp.concatenate([tail, uz[:, 0]])
    u = jax.nn.silu(sum(rows[i : i + seg] * w["conv_w"][i] for i in range(taps)) + w["conv_b"])
    y, state = _scan(w, u, state, (rank, n_state))
    x = x + jnp.dot(y * jax.nn.silu(uz[:, 1]), w["w_out"])
    return _mlp(w, x, eps), state, rows[seg:], y


def _project(w, n, name, bias):
    return jnp.einsum("td,dhk->thk", n, w[name]) + w[bias]


def _differential(w, q, k, v, seen, lambda_init, eps):
    """q: [Q, H, D]; k, v: [T, KVH, D]; seen: [Q, T]. The pairs' outputs side
    by side, [Q, H D]: two softmaxes a pair, each over both values of its
    group (module docstring)."""
    rows, heads, d = q.shape
    groups = k.shape[1] // 2
    per = heads // 2 // groups  # pairs a group
    q = q.reshape(rows, groups, per, 2, d)
    k, v = k.reshape(-1, groups, 2, d), v.reshape(-1, groups, 2, d)
    scale = 1.0 / np.sqrt(d)

    def softmax_of(which):
        scores = jnp.einsum("qgjd,tgd->gjqt", q[:, :, :, which], k[:, :, which]) * scale
        return jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)

    def over_both_values(probs):
        return jnp.concatenate(
            [jnp.einsum("gjqt,tgd->qgjd", probs, v[:, :, which]) for which in (0, 1)], axis=-1
        )

    a1, a2 = over_both_values(softmax_of(0)), over_both_values(softmax_of(1))
    lq1, lk1, lq2, lk2 = w["lambdas"]
    lam = jnp.exp(jnp.dot(lq1, lk1)) - jnp.exp(jnp.dot(lq2, lk2)) + lambda_init
    o = a1 - lam * a2
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * w["subln"]
    return (o * (1.0 - lambda_init)).reshape(rows, -1)


@functools.partial(jax.jit, static_argnames=("sizes",))
def _sliding(w, x, start, k_before, v_before, lambda_init, sizes):
    """One segment at positions ``start ..`` through a sliding layer and its
    MLP. ``k_before`` / ``v_before``: [window, KVH, D], the keys and values of
    the ``window`` positions before the segment (those before the prompt's
    start are masked). Returns (x, the last ``window`` keys, values)."""
    eps, window = sizes
    w = _f32(w)
    seg = x.shape[0]
    block = min(QUERY_BLOCK, seg)
    n = _ln(x, w["in_norm"], w["in_norm_b"], eps)
    q = _project(w, n, "wq", "bq")
    keys = jnp.concatenate([k_before, _project(w, n, "wk", "bk")])
    values = jnp.concatenate([v_before, _project(w, n, "wv", "bv")])

    def attend(q0):
        # Rows q0 .. q0 + block of the segment see the keys from ``window``
        # before the first of them to the last: a slice of window + block.
        qb = jax.lax.dynamic_slice_in_dim(q, q0, block)
        kb = jax.lax.dynamic_slice_in_dim(keys, q0, window + block)
        vb = jax.lax.dynamic_slice_in_dim(values, q0, window + block)
        qpos = start + q0 + jnp.arange(block)
        kpos = start + q0 - window + jnp.arange(window + block)
        seen = (kpos[None] <= qpos[:, None]) & (kpos[None] > qpos[:, None] - window) & (kpos[None] >= 0)
        return _differential(w, qb, kb, vb, seen, lambda_init, eps)

    attn = jax.lax.map(attend, jnp.arange(0, seg, block)).reshape(seg, -1)
    x = x + jnp.dot(attn, w["wo"]) + w["bo"]
    return _mlp(w, x, eps), keys[seg:], values[seg:]


@functools.partial(jax.jit, static_argnames=("eps",))
def _keys_values(w, x, eps):
    """Layer ``full``'s K and V of a segment: every position's."""
    w = _f32(w)
    n = _ln(x, w["in_norm"], w["in_norm_b"], eps)
    return _project(w, n, "wk", "bk"), _project(w, n, "wv", "bv")


@functools.partial(jax.jit, static_argnames=("eps",))
def _shared_attention(w, x, first_pos, keys, values, lambda_init, eps):
    """The compared rows x: [R, dim], at positions ``first_pos ..``, through a
    layer that attends layer ``full``'s ``keys`` / ``values`` [T, KVH, D]
    (that layer itself, or a cross layer) and its MLP."""
    w = _f32(w)
    n = _ln(x, w["in_norm"], w["in_norm_b"], eps)
    seen = jnp.arange(keys.shape[0])[None] <= (first_pos + jnp.arange(x.shape[0]))[:, None]
    attn = _differential(w, _project(w, n, "wq", "bq"), keys, values, seen, lambda_init, eps)
    return _mlp(w, x + jnp.dot(attn, w["wo"]) + w["bo"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _memory_unit(w, x, memory, eps):
    w = _f32(w)
    gate = jax.nn.silu(jnp.dot(_ln(x, w["in_norm"], w["in_norm_b"], eps), w["w_in"]))
    return _mlp(w, x + jnp.dot(memory * gate, w["w_out"]), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(w, b, x, eps):
    return _ln(x, w.astype(F32), b.astype(F32), eps)


@jax.jit
def _head_block(rows, x):
    return jnp.dot(x, rows.astype(F32).T)


def _sizes(config: dict):
    if not config.get("tie_word_embeddings") or config.get("mlp_bias") or config.get("lm_head_bias"):
        raise ValueError("this reference writes out the tied head and an MLP and a head without bias")
    layers = int(config["num_hidden_layers"])
    if layers % 4 or int(config["mb_per_layer"]) != 2:
        raise ValueError("this reference writes out whole periods of four layers, a scan layer every second")
    return {
        "layers": layers,
        "full": layers // 2 + 1,
        "eps": float(config["layer_norm_eps"]),
        "window": int(config["sliding_window"]),
        "taps": int(config["mamba_d_conv"]),
        "rank": int(config["mamba_dt_rank"]),
        "n_state": int(config["mamba_d_state"]),
        "channels": int(config["mamba_expand"]) * int(config["hidden_size"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["hidden_size"]) // int(config["num_attention_heads"]),
    }


def _lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * float(np.exp(-0.3 * layer))


def _of(params, layer: int):
    pre = f"l{layer}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def _head(params, x, eps):
    x = _final_norm(params["final_norm"], params["final_norm_b"], x, eps)
    embed = params["embed"]
    return jnp.concatenate(
        [_head_block(embed[a : a + VOCAB_BLOCK], x) for a in range(0, embed.shape[0], VOCAB_BLOCK)], axis=1
    )


def logits(params, config: dict, tokens, last_n: int):
    """``[last_n, vocab]`` float32 logits of the last ``last_n`` positions of
    ``tokens`` under ``params`` (the program's parameter dict) and ``config``
    (the configuration file's keys)."""
    z = _sizes(config)
    n, eps, full, window = len(tokens), z["eps"], z["full"], z["window"]
    seg = min(SEGMENT, -(-n // QUERY_BLOCK) * QUERY_BLOCK)
    if last_n > min(n, seg):
        raise ValueError(f"the last {last_n} rows do not lie in the last two segments of {n} tokens")
    padded = -(-n // seg) * seg
    # Padding sits after the real tokens: neither a causal attention nor a
    # recurrence lets a real position see it, and its own outputs are dropped.
    toks = jnp.asarray(list(tokens) + [0] * (padded - n), jnp.int32)
    starts = range(0, padded, seg)
    first = n - last_n  # the first compared position

    def compared(parts):  # the compared rows of a per-segment list
        both = jnp.concatenate(parts[-2:])
        end = n - (len(parts) - len(parts[-2:])) * seg
        return both[end - last_n : end]

    with jax.default_matmul_precision("highest"):
        xs = [jnp.take(params["embed"], toks[a : a + seg], axis=0).astype(F32) for a in starts]
        memory = None
        for layer in range(full):
            w = _of(params, layer)
            if layer % 2 == 0:
                state = jnp.zeros((z["channels"], z["n_state"]), F32)
                tail = jnp.zeros((z["taps"] - 1, z["channels"]), F32)
                ys = []
                for i in range(len(xs)):
                    xs[i], state, tail, y = _mamba(w, xs[i], state, tail, (eps, z["taps"], z["rank"], z["n_state"]))
                    ys.append(y)
                memory = compared(ys)  # the last Mamba layer's is what stays
            else:
                k = jnp.zeros((window, z["kv_heads"], z["head_dim"]), F32)
                v = jnp.zeros_like(k)
                for i, a in enumerate(starts):
                    xs[i], k, v = _sliding(w, xs[i], jnp.int32(a), k, v, _lambda_init(layer), (eps, window))
        w = _of(params, full)
        kv = [_keys_values(w, x, eps) for x in xs]
        keys = jnp.concatenate([k for k, _ in kv])[:n]
        values = jnp.concatenate([v for _, v in kv])[:n]
        x = compared(xs)
        for layer in range(full, z["layers"]):
            w = _of(params, layer)
            if layer % 2 == 0:
                x = _memory_unit(w, x, memory, eps)
            else:
                x = _shared_attention(w, x, jnp.int32(first), keys, values, _lambda_init(layer), eps)
        return _head(params, x, eps)


def logits_all_rows(params, config: dict, tokens):
    """``[len(tokens), vocab]``: every layer on every position, nothing cut up
    and nothing left out (dense masks, one scan over the whole sequence). For
    short sequences: what ``logits`` is held to."""
    z = _sizes(config)
    n, eps, window = len(tokens), z["eps"], z["window"]
    pos = jnp.arange(n)
    causal = pos[None] <= pos[:, None]
    band = causal & (pos[None] > pos[:, None] - window)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32), axis=0).astype(F32)
        memory = keys = values = None
        for layer in range(z["layers"]):
            w = _f32(_of(params, layer))
            h = _ln(x, w["in_norm"], w["in_norm_b"], eps)
            if layer % 2 == 0 and layer < z["full"]:
                uz = jnp.einsum("td,dcf->tcf", h, w["w_in"])
                rows = jnp.concatenate([jnp.zeros((z["taps"] - 1, z["channels"]), F32), uz[:, 0]])
                u = jax.nn.silu(sum(rows[i : i + n] * w["conv_w"][i] for i in range(z["taps"])) + w["conv_b"])
                zero = jnp.zeros((z["channels"], z["n_state"]), F32)
                memory, _ = _scan(w, u, zero, (z["rank"], z["n_state"]))
                x = x + jnp.dot(memory * jax.nn.silu(uz[:, 1]), w["w_out"])
            elif layer % 2 == 0:
                x = x + jnp.dot(memory * jax.nn.silu(jnp.dot(h, w["w_in"])), w["w_out"])
            else:
                if layer <= z["full"]:
                    keys, values = _project(w, h, "wk", "bk"), _project(w, h, "wv", "bv")
                seen = band if layer < z["full"] else causal
                attn = _differential(w, _project(w, h, "wq", "bq"), keys, values, seen, _lambda_init(layer), eps)
                x = x + jnp.dot(attn, w["wo"]) + w["bo"]
            x = _mlp(w, x, eps)
        return _head(params, x, eps)
