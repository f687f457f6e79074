"""The plain reference of the ``glm_moe_dsa`` configuration: the forward pass
as published, in float32 at matmul precision ``highest``.

Straightforward ``jax.numpy``: no kernel, no cache, no paging, no absorbed
form, no threshold search, nothing imported from the program. The only thing
taken from the program is the layout of its parameter dict (``l{i}.w_qb`` is
``[q_lora_rank, H, nope + rope]``; ``l{i}.w_kvb`` is ``[rank, H, nope + v]``;
``l{i}.wi_q`` is ``[q_lora_rank, Hi, Di]`` ...), because the weights compared
are the program's seeded ones. The expert half, the head and the norm are
``reference_kimi_linear``'s (the same router: sigmoid scores, a selection
bias, renormalised, scaled, a shared expert, a held share).

From the configuration's file (the published ``config.json``): the sizes, the
ranks and head sizes, ``index_n_heads`` / ``index_head_dim`` / ``index_topk``,
``rope_parameters`` (theta, ``default``: unscaled), ``rope_interleave`` and
``indexer_rope_interleave`` (both true: pairs ``(2i, 2i + 1)``),
``first_k_dense_replace``, the router's keys, ``rms_norm_eps``. From the
published modelling code (NOT in ``config.json``; the file lists each under
``assumed``): the indexer's LayerNorm (weight, bias, eps 1e-6) on its key, its
inputs (the query latent for ``q_I``, the normed hidden state for ``k_I`` and
the head weights), the head weights' scale.

  n       = rms(x; w_in)                     R = rotation of interleaved pairs by
                                             pos x theta ^ (-2i / rope)
  query   : c_q = rms(W_qa n; w_qn);  q = W_qb c_q = [q_n | q_r] a head;  q_r <- R(q_r)
  keys    : [c | k_r] = W_kva n;  c <- rms(c; w_kvn);  k_r <- R(k_r) (all heads)
            [k_n | v] = W_kvb c a head;  k = [k_n | k_r]
  indexer : q_I = W_Iq c_q a head;  k_I = LayerNorm(W_Ik n);  the first ``rope``
            values of each rotated;  w = W_Iw n / sqrt(Hi x Di)
            I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])
            S_t = top_k over s <= t of I[t, s], k = min(t + 1, index_topk)
  mixer   : x + Wo softmax_{s in S_t}(q . k[s] / sqrt(nope + rope)) v[s]
  dense   : Wdown (silu(Wgate m) * Wup m)        expert : reference_kimi_linear's
  logits  = Whead rms(y_L; w_final)

The share (the file's ``deployment``): experts ``experts_held`` of the
router's ``router_experts`` and the shared expert; the vocabulary a slice.

Departures, each for memory alone: a layer's weights are cast to float32 a
piece at a time; the selection and the attention are computed a block of
queries at a time and the attention a group of heads at a time (the sets are
chosen once a layer and kept as ids); the dense MLP a chunk of tokens at a
time. None changes the mathematics.

``logits_following`` is the same pass in which the last ``rounds`` positions
take the sets they are given, of BOTH kinds: the expert sets (``[rounds,
sites, k]``, weights still from this pass's own scores) and, where the program
reports them behind those (``[rounds, sites + layers x words / k, k]``: a
layer's selection as bits, position s bit ``s % 32`` of word ``s // 32``), the
positions each row attends at every layer. Why follow them: with seeded
weights a key's index score says nothing of its weight in the attention, the
softmax over 2,048 such keys is near uniform, and a mixer's output is the MEAN
of 2,048 random values: a forty-fifth of one value, so that ONE swapped
near-tie moves it by 3% of itself. The embedding's rows are seeded 1 /
sqrt(vocab) as the siblings', so from layer 0 on that mean IS the stream. A
bf16 program's hidden states lie about 1% off this pass's; 64-148 of a row's
2,048 places then fall the other way (the chip, PERF.md PR 56) and the logits
read 8.5-13% off this pass selecting by its own scores, 1.5-2.0% off it
following: a choice, like an expert's, not a precision. The context's
positions keep this pass's own sets: what their swaps move reaches a compared
row through 2,048 keys at once and averages out.

The gaps it returns are the EXPERT sites' (``choice_gaps.gaps`` over ``s +
b``), which the harness holds to its slack. An indexer's set is REPORTED and
held otherwise. Its gap by the same definition is a maximum over thousands of
keys, each scored against an index key that comes from a CONTEXT token's
hidden state, and a context token whose own near-ties fell the other way
carries a hidden state off this pass's for good: the widest gap then says
where such a token sat, not how the program selected (1.4-2.5 on the chip with
every kernel equal to its XLA twin, PERF.md PR 56). So each set must BE a
selection (``min(t + 1, index_topk)`` positions at or before the row) that
shares at least ``SHARED_PLACES`` of its places with this pass's own top-k (a
program that selects at random shares 6-25% at these contexts, the bf16
program 93-97%), else ``ValueError``; and every pass prints to stderr how many
sets differ, by how many places, and the widest gap: the indexer sets'
agreement of every checked prompt of every run.
"""

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

import choice_gaps
from reference_kimi_linear import EXPERT, _expert_half, _f32, _head, _rms

QUERY_BLOCK = 128
HEAD_GROUP = 8  # heads whose keys and values stand in float32 at a time
TOKEN_CHUNK = 4096  # tokens the dense MLP takes at a time
INDEX_NORM_EPS = 1e-6
SHARED_PLACES = 0.5  # of a followed selection's places, those that must be this pass's own


def _rotate(x, positions, theta, first, rope):
    """x: [S, ..., width]. The ``rope`` values from ``first`` on, as pairs
    ``(2i, 2i + 1)``, turned by ``positions x theta ^ (-2i / rope)``."""
    inv_freq = (theta ** (-np.arange(0, rope, 2) / rope)).astype(np.float32)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq  # [S, rope / 2]
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (rope // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x[..., first : first + rope].reshape(x.shape[:-1] + (rope // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return jnp.concatenate(
        [x[..., :first], turned.reshape(x.shape[:-1] + (rope,)), x[..., first + rope :]], axis=-1
    )


@functools.partial(jax.jit, static_argnames=("eps", "rank", "rope", "theta"))
def _latents(w, x, eps, rank, rope, theta):
    """The query latent c_q [S, q_rank], the normed latent c [S, rank], the
    rotated shared key k_r [S, rope], the rotated index key k_I [S, Di] and
    the indexer's head weights [S, Hi]."""
    w = _f32(w)
    positions = jnp.arange(x.shape[0], dtype=jnp.int32)
    n = _rms(x, w["in_norm"], eps)
    c_q = _rms(jnp.dot(n, w["w_qa"]), w["q_norm"], eps)
    kva = jnp.dot(n, w["w_kva"])
    c, k_r = _rms(kva[:, :rank], w["kv_norm"], eps), _rotate(kva[:, rank:], positions, theta, 0, rope)
    k = jnp.dot(n, w["wi_k"])
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + INDEX_NORM_EPS)
    k_i = _rotate(k * w["wi_k_norm"] + w["wi_k_bias"], positions, theta, 0, rope)
    hi, di = w["wi_w"].shape[1], k.shape[1]
    return c_q, c, k_r, k_i, jnp.dot(n, w["wi_w"]) / np.sqrt(hi * di)


@functools.partial(jax.jit, static_argnames=("top_k", "rope", "theta"))
def _index_block(wi_q, c_q, k_i, w_i, q0, top_k, rope, theta):
    """The ``QUERY_BLOCK`` positions from ``q0`` on: their index scores over
    every position [Q, S] (-inf past the row's own) and the ``top_k`` best of
    each as ids [Q, K], K = min(top_k, S), -1 where t + 1 < K leaves places
    empty."""
    s = c_q.shape[0]
    qpos = q0 + jnp.arange(QUERY_BLOCK, dtype=jnp.int32)
    rows = jax.lax.dynamic_slice_in_dim(c_q, q0, QUERY_BLOCK)
    q_i = _rotate(jnp.einsum("qr,rhd->qhd", rows, wi_q.astype(jnp.float32)), qpos, theta, 0, rope)
    per_head = jax.nn.relu(jnp.einsum("qhd,sd->qhs", q_i, k_i))
    scores = jnp.einsum("qhs,qh->qs", per_head, jax.lax.dynamic_slice_in_dim(w_i, q0, QUERY_BLOCK))
    scores = jnp.where(jnp.arange(s, dtype=jnp.int32)[None, :] <= qpos[:, None], scores, -jnp.inf)
    best, ids = jax.lax.top_k(scores, min(top_k, s))
    return jnp.where(jnp.isfinite(best), ids, -1), scores


@functools.partial(jax.jit, static_argnames=("top_k", "rope", "theta"))
def _select(wi_q, c_q, k_i, w_i, top_k, rope, theta):
    """Every position's set, a block of queries at a time: ids [S, K]."""
    s = c_q.shape[0]
    starts = jnp.arange(0, s, QUERY_BLOCK, dtype=jnp.int32)
    ids = jax.lax.map(
        lambda q0: _index_block(wi_q, c_q, k_i, w_i, q0, top_k, rope, theta)[0], starts
    )
    return ids.reshape(s, -1)


@functools.partial(jax.jit, static_argnames=("nope", "rope", "theta", "heads"))
def _attend(w_qb, w_kvb, wo, c_q, c, k_r, ids, first, nope, rope, theta, heads):
    """Wo's rows of ``heads`` heads from ``first`` on, times those heads'
    attention over each position's selected set: [S, dim]."""
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

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK)
        chosen = jax.lax.dynamic_slice_in_dim(ids, q0, QUERY_BLOCK)
        # The set as a mask over the positions; an empty place (-1) marks a
        # column past the last.
        cols = jnp.where(chosen < 0, s, chosen)
        seen = jnp.zeros((QUERY_BLOCK, s + 1), bool).at[
            jnp.arange(QUERY_BLOCK)[:, None], cols
        ].set(True)[:, :s]
        logits = jnp.einsum("qhd,thd->hqt", qb, k) * scale
        probs = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thd->qhd", probs, v)

    starts = jnp.arange(0, s, QUERY_BLOCK, dtype=jnp.int32)
    attn = jax.lax.map(block, starts).reshape(s, -1)
    return jnp.dot(attn, wo)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_half(w, h, eps):
    w = _f32(w)
    s = h.shape[0]
    chunk = min(s, TOKEN_CHUNK)
    pad = -s % chunk

    def mlp(rows):
        m = _rms(rows, w["pre_mlp_norm"], eps)
        gate_up = jnp.einsum("sd,dcf->scf", m, w["w_gate_up"])
        return jnp.dot(jax.nn.silu(gate_up[:, 0]) * gate_up[:, 1], w["w_down"])

    rows = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, chunk, h.shape[1])
    return h + jax.lax.map(mlp, rows).reshape(s + pad, -1)[:s]


def _given_sets(packed, layers: int, n: int, last_n: int, index_topk: int):
    """The program's selections of the last ``last_n`` of ``n`` positions out
    of their bits: per layer a list of ``last_n`` sorted id arrays.
    ``ValueError`` for a row that is not its ``min(t + 1, index_topk)``
    positions at or before itself."""
    words = np.asarray(packed, np.int64).reshape(last_n, layers, -1) & 0xFFFFFFFF
    bits = ((words[..., None] >> np.arange(32)) & 1).reshape(last_n, layers, -1)
    if bits.shape[2] < n:
        raise ValueError(f"the reported sets cover {bits.shape[2]} positions of {n}")
    out = []
    for layer in range(layers):
        rows = []
        for row in range(last_n):
            t = n - last_n + row
            ids = np.nonzero(bits[row, layer])[0]
            if len(ids) != min(t + 1, index_topk) or (len(ids) and ids[-1] > t):
                raise ValueError(
                    f"the set selected at row {row} layer {layer} holds {len(ids)} positions up "
                    f"to {ids[-1] if len(ids) else None}: not {min(t + 1, index_topk)} of the {t + 1} at or before it"
                )
            rows.append(ids)
        out.append(rows)
    return out


def _index_gaps(scores, own, given, first: int):
    """Row by row (row r stands at position ``first + r``): the given set's
    gap over the scores ``I[t, :t + 1]`` as ``choice_gaps.gaps`` defines one
    (the largest score left out less the smallest chosen, over the rms of the
    centred scores; -1 where nothing is left out), on the host, and the
    places in which the set differs from this pass's own top-k."""
    gaps, swapped = [], []
    for row, ids in enumerate(given):
        seen = scores[row, : first + row + 1]
        swapped.append(len(set(ids.tolist()) - set(own[row][own[row] >= 0].tolist())))
        chosen = np.zeros(len(seen), bool)
        chosen[ids] = True
        if chosen.all():
            gaps.append(-1.0)
        else:
            gaps.append(float((seen[~chosen].max() - seen[chosen].min()) / seen.std()))
    return gaps, swapped


LATENTS = ("in_norm", "w_qa", "q_norm", "w_kva", "kv_norm", "wi_k", "wi_k_norm", "wi_k_bias", "wi_w")
DENSE = ("pre_mlp_norm", "w_gate_up", "w_down")


def _forward(params, config: dict, tokens, last_n: int, choices, sets=None):
    """The pass every entry shares. ``choices``: None, or int [last_n, sites
    (+ the selections' bits), k]. ``sets``: None, or a list that takes, a
    layer, the ids [last_n, K] the last rows selected by their own scores and
    those scores [last_n, S]. Returns (logits [last_n, vocab], gaps [last_n,
    sites] or None)."""
    if config["scoring_func"] != "sigmoid" or (config["n_group"], config["topk_group"]) != (1, 1):
        raise ValueError("this reference writes out sigmoid scores and a plain top-k")
    if not (config["rope_interleave"] and config["indexer_rope_interleave"]):
        raise ValueError("this reference rotates interleaved pairs, for the keys and the indexer")
    if config["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError("this reference applies the unscaled rotation")
    n = len(tokens)
    if not 0 < last_n <= min(n, QUERY_BLOCK):
        raise ValueError(f"the last {last_n} of {n} positions: 1 to {QUERY_BLOCK} of them")
    padded = -(-n // QUERY_BLOCK) * QUERY_BLOCK
    # Padding sits after the real tokens: no real position sees it (its index
    # scores are masked by position), and its own outputs are dropped.
    toks = jnp.asarray(list(tokens) + [0] * (padded - n), jnp.int32)
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_parameters"]["rope_theta"])
    rank, nope, rope = (int(config[k]) for k in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim"))
    heads, index_topk = int(config["num_attention_heads"]), int(config["index_topk"])
    top_k = int(config["num_experts_per_tok"])
    layers, dense_layers = int(config["num_hidden_layers"]), int(config["first_k_dense_replace"])
    routed_over = int(config.get("router_experts", config["n_routed_experts"]))
    first, _count = config.get("experts_held", (0, routed_over))
    shared = first == 0 and int(config["n_shared_experts"]) > 0
    sites = layers - dense_layers
    selections = None
    if choices is not None:
        choices = np.asarray(choices)
        if choices.ndim == 3 and choices.shape[1] > sites:
            selections = _given_sets(choices[:, sites:], layers, n, last_n, index_topk)
            choices = choices[:, :sites]
        choices = choice_gaps.check_sets(choices, last_n, [routed_over] * sites)
        if choices.shape[2] != top_k:
            raise ValueError(f"the sets hold {choices.shape[2]} ids, the top-k chooses {top_k}")
    of = lambda layer, names: {name: params[f"l{layer}.{name}"] for name in names}
    group = min(HEAD_GROUP, heads)
    gaps, index_gaps, swapped = [], [], []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], toks, axis=0).astype(jnp.float32)
        for layer in range(layers):
            c_q, c, k_r, k_i, w_i = _latents(of(layer, LATENTS), x, eps, rank, rope, theta)
            indexer = (params[f"l{layer}.wi_q"], c_q, k_i, w_i)
            ids = _select(*indexer, index_topk, rope, theta)
            if sets is not None or selections is not None:
                q0 = min(n - last_n, padded - QUERY_BLOCK)
                _, scores = _index_block(*indexer, jnp.int32(q0), index_topk, rope, theta)
                own, scores = np.asarray(ids[n - last_n : n]), np.asarray(scores[n - last_n - q0 : n - q0])
            if sets is not None:
                sets.append((own, scores))
            if selections is not None:
                row_gaps, places = _index_gaps(scores, own, selections[layer], n - last_n)
                index_gaps.append(row_gaps)
                swapped += places
                given = np.full((last_n, ids.shape[1]), -1, np.int32)
                for row, chosen in enumerate(selections[layer]):
                    given[row, : len(chosen)] = chosen
                ids = ids.at[n - last_n : n].set(jnp.asarray(given))
            for head in range(0, heads, group):
                x = x + _attend(
                    params[f"l{layer}.w_qb"], params[f"l{layer}.w_kvb"], params[f"l{layer}.wo"],
                    c_q, c, k_r, ids, head, nope, rope, theta, group,
                )
            del c_q, c, k_r, k_i, w_i, ids, indexer
            if layer < dense_layers:
                x = _dense_half(of(layer, DENSE), x, eps)
                continue
            # The real tokens alone route: the given sets sit on their last rows.
            site = layer - dense_layers
            given = np.zeros((0, top_k), np.int32) if choices is None else choices[:, site]
            real, ranked = _expert_half(
                of(layer, EXPERT), x[:n], jnp.asarray(given), eps, top_k,
                bool(config["norm_topk_prob"]), float(config["routed_scaling_factor"]),
                int(first), shared,
            )
            x = jnp.concatenate([real, x[n:]])
            if choices is not None:
                gaps.append(choice_gaps.gaps(ranked[n - last_n :], given))
        out = _head(params["final_norm"], params["lm_head"], x[n - last_n : n], eps)
    if choices is None:
        return out, None
    if selections is not None:
        differ = sum(1 for places in swapped if places)
        print(
            f"reference_glm_dsa: of {len(swapped)} (row, layer) sets the program selected, "
            f"{differ} are not this pass's own top-k; {sum(swapped) / len(swapped):.2f} places "
            f"of {min(n, index_topk)} differ a set (most {max(swapped)}), widest gap "
            f"{max(max(row) for row in index_gaps):.4f}, context {n}",
            file=sys.stderr, flush=True,
        )
        if max(swapped) > (1 - SHARED_PLACES) * min(n - last_n + 1, index_topk):
            raise ValueError(
                f"a selection the program reports shares under {SHARED_PLACES:.0%} of its places "
                f"with the float32 reference's own top-k ({max(swapped)} differ)"
            )
    return out, jnp.stack(gaps, axis=1)


def logits(params, config: dict, tokens, last_n: int):
    """``[last_n, vocab]`` float32 logits of the last ``last_n`` positions of
    ``tokens`` under ``params`` (the program's parameter dict) and ``config``
    (the configuration file's keys); every position routes and selects by its
    own float32 scores."""
    return _forward(params, config, tokens, last_n, None)[0]


def logits_following(params, config: dict, tokens, rounds: int, choices):
    """The same pass in which the last ``rounds`` positions take the expert
    sets ``choices`` (int ``[rounds, sites, k]``, a site an expert layer in the
    model's order; ``ValueError`` for a set that is not ``k`` distinct ids of
    the router's experts) with weights from this pass's own scores and, where
    ``choices`` carries them behind the expert sets (module docstring), the
    positions the program selected at every layer (``ValueError`` for a row
    that is not ``min(t + 1, index_topk)`` positions at or before itself, or
    that shares under ``SHARED_PLACES`` of them with this pass's own top-k).
    Returns ``(logits [rounds, vocab] float32, gaps [rounds, sites] float32)``,
    the expert sites' gaps as ``choice_gaps.gaps`` defines them over ``s +
    b``."""
    return _forward(params, config, tokens, rounds, np.asarray(choices))


def selected(params, config: dict, tokens, last_n: int):
    """What the indexer chose for the last ``last_n`` positions, a layer: a
    list of ``(ids [last_n, K] with -1 in empty places, scores [last_n, S]
    float32 over the padded positions)``, for whoever compares a program's
    sets with these (tests, tools/dsa_agreement_probe.py)."""
    sets = []
    _forward(params, config, tokens, last_n, None, sets)
    return sets
