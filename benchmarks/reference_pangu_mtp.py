"""The plain reference of the ``pangu_ultra_moe`` configuration: the forward
pass as published, and its multi-token-prediction (MTP) module, in float32 at
matmul precision ``highest``.

Straightforward ``jax.numpy``: no kernel, no cache, no paging, no absorbed
form, nothing imported from the program. The only thing taken from the program
is the layout of its parameter dict (``l{i}.w_qb`` is ``[q_lora_rank, H, nope +
rope]``; ``l{i}.w_kvb`` is ``[rank, H, nope + v]``; the MTP module's layer is
``l{num_hidden_layers}.`` and its own weights ``mtp.e_norm``, ``mtp.h_norm``,
``mtp.w_eh`` ``[2 dim, dim]``, ``mtp.final_norm``), because the weights
compared are the program's seeded ones. The router, the held experts, the
head and the norm are ``reference_kimi_linear``'s (sigmoid scores, renormalised,
scaled, a shared expert, a held share); the rotation of interleaved pairs is
``reference_glm_dsa``'s.

From the configuration's file (the published ``config.json``): the sizes, the
ranks and head sizes, ``rope_theta``, ``first_k_dense_replace``, the router's
keys, ``rms_norm_eps``, ``sandwich_norm``, ``num_nextn_predict_layers``. From
the lineage's published modelling code and report (NOT in ``config.json``; the
file lists each under ``assumed``): sigmoid scores, no groups, no selection
bias; interleaved pairs; the MTP module's equations (DeepSeek-V3 report,
section 2.2).

  layer   : h = x + rms(mixer(rms(x; w_in)); w_post_attn)
            y = h + rms(mlp(rms(h; w_pre_mlp)); w_post_mlp)
  query   : c_q = rms(W_qa n; w_qn);  q = W_qb c_q = [q_n | q_r] a head;  q_r <- R(q_r)
  keys    : [c | k_r] = W_kva n;  c <- rms(c; w_kvn);  k_r <- R(k_r) (all heads)
            [k_n | v] = W_kvb c a head;  k = [k_n | k_r]
  mixer   : Wo softmax_{s <= t}(q . k[s] / sqrt(nope + rope)) v[s]
  dense   : Wdown (silu(Wgate m) * Wup m)        expert : reference_kimi_linear's
  logits  = Whead rms(y_L; w_final)
  MTP     : u_i = W_eh [rms(E[x_{i+1}]; w_e) ; rms(y_L[i]; w_h)]
            z = Layer(u) (one more layer of the expert kind, causal over u_0..u_i,
            rotated at i);  draft logits_i = Whead rms(z_i; w_f): over x_{i+2}

The share (the file's ``deployment``): experts ``experts_held`` of the
router's ``router_experts`` and the shared expert; the vocabulary a slice.

Departures, each for memory alone (a stream of 33,152 tokens x 7,680 float32
is 1 GB, and beside 8.3 GB of weights and the cache the chip has room for
about five such): a layer's weights are cast to float32 a piece at a time; the
attention is computed a block of queries and a group of heads at a time and
added into one accumulator in place; the dense MLP and the experts a chunk of
tokens at a time; a residual add overwrites the stream it adds to. None
changes the mathematics.

``logits_following`` is the same pass in which the last ``rounds`` positions
take what the program reports a row (``[rounds, sites, k]``): the main stack's
expert sets, the MTP layer's expert set, and last ``[committed id, drafted
id, 0, ...]``. The committed id is the row's ``x_{i+1}`` in the MTP module
(the id the program's own logits chose, which the context does not hold for
the last row); the drafted id is held to this pass's own draft logits like a
router's set to its scores: the gap of the one-id set ``{drafted}`` over the
draft logits (``choice_gaps.gaps``: the best other logit less the drafted
one's, over the rms of the centred logits), the last column of the gaps it
returns. A drafting layer that read a stale slot, a wrong next token or
another position's hidden row drafts an id this pass ranks nowhere near its
best: a gap of the order of 1.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

import choice_gaps
from reference_glm_dsa import _rotate
from reference_kimi_linear import _experts, _f32, _head, _rms, _route

QUERY_BLOCK = 128
HEAD_GROUP = 8  # heads whose keys and values stand in float32 at a time
TOKEN_CHUNK = 2048  # tokens the dense MLP and the experts take at a time


@functools.partial(jax.jit, static_argnames=("eps", "rank", "rope", "theta"))
def _latents(w, x, eps, rank, rope, theta):
    """The query latent c_q [S, q_rank], the normed latent c [S, rank] and the
    rotated shared key k_r [S, rope] of the stream x: [S, dim]."""
    w = _f32(w)
    positions = jnp.arange(x.shape[0], dtype=jnp.int32)
    n = _rms(x, w["in_norm"], eps)
    c_q = _rms(jnp.dot(n, w["w_qa"]), w["q_norm"], eps)
    kva = jnp.dot(n, w["w_kva"])
    return c_q, _rms(kva[:, :rank], w["kv_norm"], eps), _rotate(kva[:, rank:], positions, theta, 0, rope)


@functools.partial(
    jax.jit, static_argnames=("nope", "rope", "theta", "heads"), donate_argnames=("acc",)
)
def _attend(w_qb, w_kvb, wo, c_q, c, k_r, first, acc, nope, rope, theta, heads):
    """``acc`` [S, dim] plus Wo's rows of ``heads`` heads from ``first`` on
    times those heads' causal attention over every position at or before the
    query. ``acc`` is overwritten."""
    f32 = jnp.float32
    s = c_q.shape[0]
    positions = jnp.arange(s, dtype=jnp.int32)
    w_qb = jax.lax.dynamic_slice_in_dim(w_qb, first, heads, axis=1).astype(f32)
    w_kvb = jax.lax.dynamic_slice_in_dim(w_kvb, first, heads, axis=1).astype(f32)
    vdim = w_kvb.shape[2] - nope
    wo = jax.lax.dynamic_slice_in_dim(wo, first * vdim, heads * vdim, axis=0).astype(f32)
    q = _rotate(jnp.einsum("sr,rhd->shd", c_q, w_qb), positions, theta, nope, rope)
    kv = jnp.einsum("sr,rhd->shd", c, w_kvb)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r[:, None, :], (s, heads, rope))], -1)
    v = kv[..., nope:]
    scale = 1.0 / np.sqrt(nope + rope)
    # The queries padded to whole blocks (a padded query sees every position
    # and its row is dropped); keys and values are the real positions alone.
    padded = -(-s // QUERY_BLOCK) * QUERY_BLOCK
    q = jnp.pad(q, ((0, padded - s), (0, 0), (0, 0)))

    def block(q0):
        qpos = q0 + jnp.arange(QUERY_BLOCK, dtype=jnp.int32)
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK)
        logits = jnp.einsum("qhd,thd->hqt", qb, k) * scale
        seen = positions[None, :] <= qpos[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thd->qhd", probs, v)

    starts = jnp.arange(0, padded, QUERY_BLOCK, dtype=jnp.int32)
    attn = jax.lax.map(block, starts).reshape(padded, -1)[:s]
    return acc + jnp.dot(attn, wo)


@functools.partial(jax.jit, static_argnames=("eps",), donate_argnames=("x",))
def _add_normed(x, branch, norm_w, eps):
    """x + rms(branch; w): a sandwich-normed layer's residual add. ``x`` is
    overwritten."""
    return x + _rms(branch, norm_w.astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_rows(w, rows, eps):
    w = _f32(w)
    m = _rms(rows, w["pre_mlp_norm"], eps)
    gate_up = jnp.einsum("sd,dcf->scf", m, w["w_gate_up"])
    return jnp.dot(jax.nn.silu(gate_up[:, 0]) * gate_up[:, 1], w["w_down"])


@functools.partial(jax.jit, static_argnames=("eps",), donate_argnames=("x",))
def _add_normed_rows(x, rows, norm_w, first, eps):
    """``x`` with ``rms(rows; w)`` added to its rows from ``first`` on: the
    residual add of a branch computed a chunk of tokens at a time (the norm is
    a row's own). ``x`` is overwritten."""
    old = jax.lax.dynamic_slice_in_dim(x, first, rows.shape[0])
    return jax.lax.dynamic_update_slice_in_dim(
        x, old + _rms(rows, norm_w.astype(jnp.float32), eps), first, 0
    )


@jax.jit
def _shared(w, m):
    f32 = jnp.float32
    gate_up = jnp.einsum("sd,dcf->scf", m, w["ws_gate_up"].astype(f32))
    return jnp.dot(jax.nn.silu(gate_up[:, 0]) * gate_up[:, 1], w["ws_down"].astype(f32))


def _expert_rows(w, held, m, ids, weights, first, shared):
    """A chunk's part of the expert layer's branch: the held experts' (+ the
    shared expert where this share counts it)."""
    routed = _experts(held, m, ids, weights, first)
    return routed + _shared(w, m) if shared else routed


def _chunks(s: int):
    return [slice(a, min(a + TOKEN_CHUNK, s)) for a in range(0, s, TOKEN_CHUNK)]


def _expert_branch(w, h, given, eps, top_k, renormalize, scale, first, shared):
    """The expert layer's branch over h: [S, dim], before its post-norm.
    Returns (branch [S, dim], the scores [S, E] the top-k ranks by)."""
    m, ranked, ids, weights = _route(w, h, given, eps, top_k, renormalize, scale)
    held = {name: w[name] for name in ("w_gate", "w_up", "w_down_moe")}
    branch = jnp.concatenate([
        _expert_rows(w, held, m[c], ids[c], weights[c], first, shared) for c in _chunks(h.shape[0])
    ])
    return branch, ranked


@functools.partial(jax.jit, static_argnames=("eps",))
def _mtp_rows(embed, w_e, w_h, w_eh, nexts, hidden, eps):
    """u for a chunk of positions: their NEXT tokens' embeddings and their
    last hidden rows, each normed, side by side through W_eh."""
    f32 = jnp.float32
    emb_next = jnp.take(embed, nexts, axis=0).astype(f32)
    both = jnp.concatenate([
        _rms(emb_next, w_e.astype(f32), eps), _rms(hidden, w_h.astype(f32), eps),
    ], axis=-1)
    return jnp.dot(both, w_eh.astype(f32))


@functools.partial(jax.jit, donate_argnames=("x",))
def _set_rows(x, rows, first):
    return jax.lax.dynamic_update_slice_in_dim(x, rows, first, 0)


LATENTS = ("in_norm", "w_qa", "q_norm", "w_kva", "kv_norm")
DENSE = ("pre_mlp_norm", "w_gate_up", "w_down")
EXPERT = ("pre_mlp_norm", "router", "router_bias", "w_gate", "w_up", "w_down_moe",
          "ws_gate_up", "ws_down")


class _Sizes:
    def __init__(self, config: dict):
        if not config["sandwich_norm"] or int(config["num_nextn_predict_layers"]) != 1:
            raise ValueError("this reference writes out sandwich norms and ONE MTP layer")
        self.eps, self.theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
        self.rank, self.nope, self.rope = (
            int(config[k]) for k in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim")
        )
        self.heads, self.top_k = int(config["num_attention_heads"]), int(config["num_experts_per_tok"])
        self.layers, self.dense = int(config["num_hidden_layers"]), int(config["first_k_dense_replace"])
        self.routed_over = int(config.get("router_experts", config["n_routed_experts"]))
        self.first, _count = config.get("experts_held", (0, self.routed_over))
        self.shared = self.first == 0 and int(config["n_shared_experts"]) > 0
        self.renormalize = bool(config["norm_topk_prob"])
        self.scale = float(config["routed_scaling_factor"])
        self.expert_sites = self.layers - self.dense + 1  # the MTP layer's router is the last


def _layer(params, z: _Sizes, layer: int, x, given):
    """One layer over the stream x: [S, dim], which it OVERWRITES (the caller
    takes the result in its place). ``given``: [rounds, k] expert ids for the
    last rounds rows (an expert layer; rounds may be 0). Returns (y, the
    ranked scores [S, E] or None)."""
    of = lambda names: {name: params[f"l{layer}.{name}"] for name in names}
    c_q, c, k_r = _latents(of(LATENTS), x, z.eps, z.rank, z.rope, z.theta)
    group = min(HEAD_GROUP, z.heads)
    mixed = jnp.zeros_like(x)
    for first in range(0, z.heads, group):
        mixed = _attend(
            params[f"l{layer}.w_qb"], params[f"l{layer}.w_kvb"], params[f"l{layer}.wo"],
            c_q, c, k_r, first, mixed, z.nope, z.rope, z.theta, group,
        )
    del c_q, c, k_r
    h = _add_normed(x, mixed, params[f"l{layer}.post_attn_norm"], z.eps)
    del x, mixed
    # The MLP half a chunk of tokens at a time, each chunk's normed branch
    # added into the stream in place (a row's branch reads its own row alone).
    post, ranked = params[f"l{layer}.post_mlp_norm"], None
    if layer < z.dense:
        w = of(DENSE)
        for c in _chunks(h.shape[0]):
            h = _add_normed_rows(h, _dense_rows(w, h[c], z.eps), post, c.start, z.eps)
        return h, None
    w = of(EXPERT)
    m, ranked, ids, weights = _route(
        w, h, jnp.asarray(given), z.eps, z.top_k, z.renormalize, z.scale
    )
    held = {name: w[name] for name in ("w_gate", "w_up", "w_down_moe")}
    for c in _chunks(h.shape[0]):
        rows = _expert_rows(w, held, m[c], ids[c], weights[c], int(z.first), z.shared)
        h = _add_normed_rows(h, rows, post, c.start, z.eps)
    return h, ranked


def _forward(params, config: dict, tokens, last_n: int, choices, draft: bool):
    """The pass every entry shares. ``choices``: None, or int [last_n, expert
    sites + 1, k] (module docstring). Returns (logits [last_n, vocab], draft
    logits [last_n, vocab] or None, gaps [last_n, sites] or None)."""
    z = _Sizes(config)
    n = len(tokens)
    if not 0 < last_n <= min(n, QUERY_BLOCK):
        raise ValueError(f"the last {last_n} of {n} positions: 1 to {QUERY_BLOCK} of them")
    toks = jnp.asarray(list(tokens), jnp.int32)
    sets = committed = drafted = None
    if choices is not None:
        choices = np.asarray(choices)
        if choices.ndim != 3 or choices.shape[:2] != (last_n, z.expert_sites + 1) or choices.shape[2] != z.top_k:
            raise ValueError(
                f"choices are of shape {choices.shape}, not [{last_n} rounds, {z.expert_sites} expert "
                f"sites and the (committed, drafted) pair, {z.top_k}]"
            )
        sets = choice_gaps.check_sets(choices[:, :-1], last_n, [z.routed_over] * z.expert_sites)
        committed, drafted = choices[:, -1, 0], choices[:, -1, 1]
        vocab = params["embed"].shape[0]
        if min(committed.min(), drafted.min()) < 0 or max(committed.max(), drafted.max()) >= vocab:
            raise ValueError(f"a committed or drafted id outside the vocabulary of {vocab}")
    none = np.zeros((0, z.top_k), np.int32)
    gaps = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], toks, axis=0).astype(jnp.float32)
        for layer in range(z.layers):
            given = none if sets is None or layer < z.dense else sets[:, layer - z.dense]
            x, ranked = _layer(params, z, layer, x, given)
            if sets is not None and ranked is not None:
                gaps.append(choice_gaps.gaps(ranked[n - last_n :], given))
            del ranked
        out = _head(params["final_norm"], params["lm_head"], x[n - last_n :], z.eps)
        if not draft:
            return out, None, (jnp.stack(gaps, axis=1) if gaps else None)
        # Each position's NEXT token: the context's own, and for the compared
        # rows the id the program committed (by this pass's own logits where
        # nothing is followed).
        nexts = np.zeros(n, np.int64)
        nexts[: n - 1] = np.asarray(tokens[1:])
        own = np.asarray(jnp.argmax(out, axis=-1))
        nexts[n - last_n : n] = own if committed is None else committed
        if committed is None:
            nexts[n - last_n : n - 1] = np.asarray(tokens[n - last_n + 1 :])
        # u takes the stream's place, a chunk of positions at a time.
        for c in _chunks(n):
            rows = _mtp_rows(
                params["embed"], params["mtp.e_norm"], params["mtp.h_norm"], params["mtp.w_eh"],
                jnp.asarray(nexts[c], jnp.int32), x[c], z.eps,
            )
            x = _set_rows(x, rows, c.start)
        given = none if sets is None else sets[:, -1]
        zed, ranked = _layer(params, z, z.layers, x, given)
        drafts = _head(params["mtp.final_norm"], params["lm_head"], zed[n - last_n :], z.eps)
        if sets is None:
            return out, drafts, None
        gaps.append(choice_gaps.gaps(ranked[n - last_n :], given))
        gaps.append(choice_gaps.gaps(drafts, jnp.asarray(drafted)[:, None]))
    return out, drafts, jnp.stack(gaps, axis=1)


def logits(params, config: dict, tokens, last_n: int):
    """``[last_n, vocab]`` float32 logits of the last ``last_n`` positions of
    ``tokens`` under ``params`` (the program's parameter dict) and ``config``
    (the configuration file's keys); every position routes by its own scores.
    The MTP module is not run: the served logits do not depend on it."""
    return _forward(params, config, tokens, last_n, None, False)[0]


def draft_logits(params, config: dict, tokens, last_n: int):
    """The MTP forward: ``(logits, draft logits)``, both ``[last_n, vocab]``
    float32, of the last ``last_n`` positions. Row i's draft logits are over
    ``x_{i+2}``, its ``x_{i+1}`` the context's own next token and, for the
    last row, the argmax of its own main logits."""
    out, drafts, _ = _forward(params, config, tokens, last_n, None, True)
    return out, drafts


def logits_following(params, config: dict, tokens, rounds: int, choices):
    """The same pass in which the last ``rounds`` positions take what the
    program reports (module docstring; ``ValueError`` for an expert set that
    is not ``k`` distinct ids of the router's experts, or an id outside the
    vocabulary). Returns ``(logits [rounds, vocab] float32, gaps [rounds,
    sites] float32)``: the main expert sites' gaps, the MTP layer's, and the
    drafted id's over this pass's draft logits."""
    out, _, gaps = _forward(params, config, tokens, rounds, np.asarray(choices), True)
    return out, gaps
