"""The plain reference of the ``falcon_h1`` configuration: the forward pass as
published, in float32 at matmul precision ``highest``.

Straightforward ``jax.numpy``: no kernel, no cache, no paging, no chunked
recurrence, nothing imported from the program. The only thing taken from the
program is the layout of its parameter dict (``l{i}.w_in`` is ``[dim, z | x |
B | C | dt]``; ``l{i}.wq`` is ``[dim, H, D]``; ``l{i}.w_gate_up`` is ``[dim,
2, width]``, gate then up ...), because the weights compared are the
program's seeded ones.

From the configuration's file (the published ``config.json``): every size
(``hidden_size``, the attention's heads and ``head_dim``, ``mamba_d_ssm`` /
``mamba_n_heads`` / ``mamba_d_head`` / ``mamba_d_state`` / ``mamba_n_groups``
/ ``mamba_d_conv``, ``intermediate_size``), ``rope_theta``, ``rms_norm_eps``
and every multiplier. From the family's published modelling code (NOT in
``config.json``; the file lists each under ``assumed``): where each
multiplier is applied, and the equations below. 72 identical layers as
published, the file's count here.

  x_0     = embedding_multiplier E[token]
  n       = rms(x; w_in)                       ONE norm feeds both mixers
  h       = x + ssm_out_multiplier Mamba(n) + attention_out_multiplier Attn(attention_in_multiplier n)
  y       = h + MLP(rms(h; w_pre_mlp))         the sum above is not normed
  Attn    : q = Wq a, k = key_multiplier Wk a, v = Wv a, no bias; rotary on q
            and k (the two halves of a head rotate against each other, angle
            p / theta^(2i / D), no scaling); key j visible to query i iff j <= i;
            softmax(q k^T / sqrt(D)) v, H / KVH query heads a KV head; Wo
  Mamba   : u = W_in (ssm_in_multiplier n) = [z | x | B | C | dt], the five
            segments times ``ssm_multipliers`` in that order;
            [x, B, C] <- silu(conv([x, B, C]) + b), the convolution causal and
            depth-wise over the last ``mamba_d_conv`` positions;
            dt_t = softplus(dt_t + dt_bias) a head;  a_t = exp(-exp(A_log) dt_t)
            S_t = a_t S_{t-1} + dt_t x_t B_t^T;  o_t = S_t C_t + D x_t
            (a scan, one token at a time; S [P, N] a head from zeros; head h
            reads B and C of group h // (heads / groups))
            mixer = W_out (rms_group(o silu(z)) w): the gate first
            (``mamba_norm_before_gate`` false), then the norm over each group's
            channels separately, one weight a channel
  MLP     : mlp_multipliers[1] Wdown (silu(mlp_multipliers[0] Wgate m) Wup m)
  logits  = lm_head_multiplier Whead rms(x_L; w_final),   rms(x; w) = x rsqrt(mean(x^2) + eps) w

The vocabulary is the file's ``vocab_size``, a slice (the file's
``deployment``): the logits are over it.

Departures, each for memory alone (the check runs beside the program's
weights and cache on one chip): the sequence passes a layer in segments of
``SEGMENT`` tokens that carry the mixer's state, its convolution's last rows
and the layer's keys and values from one to the next (the recurrence is still
a token at a time, the attention still over every earlier key); one layer's
mixer weights are held in float32 at a time, the MLP's a slice of its width at
a time, the head's a slice of the vocabulary at a time; attention is computed
a block of queries at a time. None changes the mathematics.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

SEGMENT = 2048  # tokens a layer takes at a time
QUERY_BLOCK = 128
MLP_SLICES = 4  # the MLP's width, a slice at a time
VOCAB_BLOCK = 16384  # the head's rows at a time

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, positions, theta):
    """x: [S, heads, D]; the halves of a head rotate against each other."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = positions.astype(F32)[:, None] * freqs  # [S, D / 2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("sizes",), donate_argnames=("keys", "values"))
def _mixers(w, x, start, state, tail, keys, values, sizes):
    """The layer's first half on one segment x: [SEGMENT, dim] at positions
    ``start ..``: returns (h, state, tail, keys, values). ``keys`` / ``values``:
    [S_total, KVH, D], filled up to ``start`` and from here up to the
    segment's end; ``state`` [H_s, P, N] and ``tail`` [taps - 1, conv width]
    as the tokens before left them."""
    (eps, theta, heads, kv_heads, head_dim, ssm_heads, ssm_head_dim, n_state, groups, taps,
     mult) = sizes
    m = dict(mult)
    w = {k: v.astype(F32) for k, v in w.items()}
    seg = x.shape[0]
    positions = start + jnp.arange(seg, dtype=jnp.int32)
    n = _rms(x, w["in_norm"], eps)

    # Attention over every key up to each query's own position.
    a = n * m["attention_in"]
    q = _rotate(jnp.einsum("sd,dhk->shk", a, w["wq"]), positions, theta)
    k = _rotate(jnp.einsum("sd,dhk->shk", a, w["wk"]) * m["key"], positions, theta)
    v = jnp.einsum("sd,dhk->shk", a, w["wv"])
    keys = jax.lax.dynamic_update_slice_in_dim(keys, k, start, 0)
    values = jax.lax.dynamic_update_slice_in_dim(values, v, start, 0)
    per_kv = heads // kv_heads
    all_pos = jnp.arange(keys.shape[0], dtype=jnp.int32)

    def attend(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK).reshape(QUERY_BLOCK, kv_heads, per_kv, head_dim)
        qpos = start + q0 + jnp.arange(QUERY_BLOCK, dtype=jnp.int32)
        logits = jnp.einsum("qkgd,tkd->kgqt", qb, keys) / np.sqrt(head_dim)
        seen = all_pos[None, :] <= qpos[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None, None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", probs, values).reshape(QUERY_BLOCK, -1)

    attn = jax.lax.map(attend, jnp.arange(0, seg, QUERY_BLOCK, dtype=jnp.int32)).reshape(seg, -1)
    attn = jnp.dot(attn, w["wo"])

    # The Mamba-2 mixer, its recurrence a token at a time.
    width = ssm_heads * ssm_head_dim
    group = groups * n_state
    u = jnp.dot(n * m["ssm_in"], w["w_in"])
    z = u[:, :width] * m["z"]
    pre = jnp.concatenate([
        u[:, width : 2 * width] * m["x"],
        u[:, 2 * width : 2 * width + group] * m["B"],
        u[:, 2 * width + group : 2 * width + 2 * group] * m["C"],
    ], axis=-1)
    dt = jax.nn.softplus(u[:, 2 * width + 2 * group :] * m["dt"] + w["dt_bias"])  # [S, H_s]
    rows = jnp.concatenate([tail, pre])
    conv = sum(rows[i : i + seg] * w["conv_w"][i] for i in range(taps)) + w["conv_b"]
    conv = jax.nn.silu(conv)
    xs = conv[:, :width].reshape(seg, ssm_heads, ssm_head_dim)
    per_group = ssm_heads // groups
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

    h = x + m["ssm_out"] * mamba + m["attention_out"] * attn
    return h, state, rows[seg:], keys, values


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(w, x, eps):
    return _rms(x, w.astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("gate",))
def _mlp_slice(w_gate_up, w_down, m, gate):
    """One slice of the MLP's width: Wdown[slice] (silu(gate Wgate[slice] m) Wup[slice] m)."""
    gate_up = jnp.einsum("sd,dcf->scf", m, w_gate_up.astype(F32))
    return jnp.dot(jax.nn.silu(gate * gate_up[:, 0]) * gate_up[:, 1], w_down.astype(F32))


@jax.jit
def _head_block(head_w, x):
    return jnp.dot(x, head_w.astype(F32))


MIXER_KEYS = ("in_norm", "wq", "wk", "wv", "wo", "w_in", "conv_w", "conv_b", "A_log", "dt_bias",
              "D", "ssm_norm", "w_out")


def logits(params, config: dict, tokens, last_n: int):
    """``[last_n, vocab]`` float32 logits of the last ``last_n`` positions of
    ``tokens`` under ``params`` (the program's parameter dict) and ``config``
    (the configuration file's keys)."""
    if not (config.get("mamba_rms_norm", True) and config.get("mamba_conv_bias", True)) or config.get(
        "mamba_norm_before_gate", False
    ):
        raise ValueError(
            "this reference writes out the gated group norm after the gate and a convolution with bias"
        )
    if any(config.get(k) for k in ("attention_bias", "mamba_proj_bias", "mlp_bias", "projectors_bias")):
        raise ValueError("this reference writes out projections without bias")
    if config.get("rope_scaling") is not None:
        raise ValueError("this reference applies the rotation unscaled")
    n = len(tokens)
    if last_n > min(n, SEGMENT):
        raise ValueError(f"the last {last_n} rows do not lie in the last two segments of {n} tokens")
    padded = -(-n // SEGMENT) * SEGMENT if n > SEGMENT else -(-n // QUERY_BLOCK) * QUERY_BLOCK
    seg = min(SEGMENT, padded)
    # Padding sits after the real tokens: neither a causal attention nor a
    # recurrence lets a real position see it, and its own outputs are dropped.
    toks = jnp.asarray(list(tokens) + [0] * (padded - n), jnp.int32)
    eps = float(config["rms_norm_eps"])
    heads, kv_heads = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    head_dim = int(config["head_dim"])
    ssm_heads, ssm_head_dim = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    if ssm_heads * ssm_head_dim != int(config["mamba_d_ssm"]):
        raise ValueError("mamba_d_ssm is not mamba_n_heads x mamba_d_head")
    n_state, groups, taps = (int(config[k]) for k in ("mamba_d_state", "mamba_n_groups", "mamba_d_conv"))
    z_m, x_m, b_m, c_m, dt_m = (float(v) for v in config["ssm_multipliers"])
    gate_m, down_m = (float(v) for v in config["mlp_multipliers"])
    mult = (
        ("attention_in", float(config["attention_in_multiplier"])),
        ("attention_out", float(config["attention_out_multiplier"])),
        ("key", float(config["key_multiplier"])), ("ssm_in", float(config["ssm_in_multiplier"])),
        ("ssm_out", float(config["ssm_out_multiplier"])),
        ("z", z_m), ("x", x_m), ("B", b_m), ("C", c_m), ("dt", dt_m),
    )
    sizes = (eps, float(config["rope_theta"]), heads, kv_heads, head_dim, ssm_heads, ssm_head_dim,
             n_state, groups, taps, mult)
    conv_width = ssm_heads * ssm_head_dim + 2 * groups * n_state
    width = int(config["intermediate_size"])
    slices = MLP_SLICES if width % MLP_SLICES == 0 else 1
    with jax.default_matmul_precision("highest"):
        embed = params["embed"]
        xs = [
            jnp.take(embed, toks[a : a + seg], axis=0).astype(F32) * float(config["embedding_multiplier"])
            for a in range(0, padded, seg)
        ]
        for layer in range(int(config["num_hidden_layers"])):
            w = {name: params[f"l{layer}.{name}"] for name in MIXER_KEYS}
            state = jnp.zeros((ssm_heads, ssm_head_dim, n_state), F32)
            tail = jnp.zeros((taps - 1, conv_width), F32)
            keys = jnp.zeros((padded, kv_heads, head_dim), F32)
            values = jnp.zeros_like(keys)
            w_gate_up, w_down = params[f"l{layer}.w_gate_up"], params[f"l{layer}.w_down"]
            step = width // slices
            for i, x in enumerate(xs):
                h, state, tail, keys, values = _mixers(
                    w, x, jnp.int32(i * seg), state, tail, keys, values, sizes
                )
                m = _norm(params[f"l{layer}.pre_mlp_norm"], h, eps)
                f = sum(
                    _mlp_slice(w_gate_up[:, :, a : a + step], w_down[a : a + step], m, gate_m)
                    for a in range(0, width, step)
                )
                xs[i] = h + down_m * f
        last = jnp.concatenate(xs[-2:])  # the compared rows may begin in the segment before
        end = n - (len(xs) - len(xs[-2:])) * seg
        x = _norm(params["final_norm"], last[end - last_n : end], eps)
        head = params["lm_head"]
        out = jnp.concatenate([
            _head_block(head[:, a : a + VOCAB_BLOCK], x) for a in range(0, head.shape[1], VOCAB_BLOCK)
        ], axis=1)
    return out * float(config["lm_head_multiplier"])
