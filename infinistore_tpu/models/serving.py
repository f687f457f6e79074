"""What the serving engine asks of a model file: three steps, by role, and
whether the model DRAFTS.

``engine.py`` names no model file. A configuration object carries ``steps``
(a :class:`ServingSteps`) beside ``block_tokens`` and ``kv_spec(blocks)``, and
the harness runs what it finds there:

``prefill(params, tokens, caches, block_table, config) -> (logits, caches)``
    a miss: the whole prompt (``tokens`` ``[S]`` int32), its K/V written to the
    blocks of the table (``[ceil(S / block_tokens)]`` int32).
``resume(params, tokens, start_pos, caches, block_table, config, max_blocks)
-> (logits, caches)``
    a prefix hit's question: one request's chunk (``tokens`` ``[S_c]`` int32)
    at contiguous positions from ``start_pos`` (``[]`` int32) against the
    pages already in the cache; ``block_table`` is ``[max_blocks]`` int32,
    padded with any valid id.

    **A prompt step may hand back no logits.** The engine reads the logits of
    neither ``prefill`` nor ``resume`` (a first token comes from the first
    wave), so a model whose prompt rows need only a part of its stack returns
    ``(None, caches)`` from both: one whose later layers read an earlier
    layer's K and V and no state of their own runs its prompt through the
    layers that write a cache and stops (``models/sambay.py``). Its cache then
    names fewer layers than the model has (``kv_spec``: the layers that keep
    anything), which is all the engine and the data plane ever see of depth.
    Such a model may name a step counter for the rows its prompt pieces compute
    (``config.prompt_rows_counter``): the engine adds each piece's rows to it
    on the host, beside what the wave counts under the same name.
``wave(params, tokens, positions, row_of, pages, page_rows, page_starts,
caches, block_tables, config, max_blocks[, window_pages=]) -> (logits,
caches[, aux])``
    a decode wave's BODY: flat rows of many requests, each over its own
    pages. ``tokens``, ``positions`` (absolute) and ``row_of`` (the owning
    request, sorted) are ``[T]`` int32, the wave's chunks concatenated;
    ``pages`` ``[P]``, ``page_rows`` ``[P + 1]`` and ``page_starts`` ``[T]``
    the flat page list the attention walks (``tpu/paged_attention.py``
    ``RaggedWaveMeta``); ``block_tables`` ``[B, max_blocks]``, its rows padded
    and the rows past the real requests referenced by no flat token. Where the cache's spec names a sliding window
    (``PagedKVCacheSpec.window``) it takes a second ``(pages, page_rows,
    page_starts)`` triple, the wave's windowed page list, as
    ``window_pages``. A body may return a third value, ``aux``: ``{"rows":
    array [T, ...], "counters": {name: scalar}}``, both still on the device.

**A model that DRAFTS** says so (``ServingSteps.drafts``: a role, no model's
name), and its three steps do a little more:
    its ``wave`` body samples each row's id itself (the wave program's own
    ``argmax``), runs its drafting layer on the rows and those ids, writes
    that layer's cache at the rows' positions, and returns under
    ``aux["drafts"]`` ``[T]`` int32 what it PROPOSES for each row's token
    after next (row t's is the token that would follow ``ids[t]``). Its
    ``aux["rows"]`` carry them too, closed by ``[ids[t], drafts[t], 0, ...]``
    (the committed and the drafted id, which a reference follows like a
    router's set). The engine then sends each request's next chunk as
    ``[token, draft]`` and accepts the draft where the wave's own argmax
    confirms it (``engine.py`` ``_generate``). **Which caches take a
    drafter**: a latent or K/V cache, served by blocks or not (a slot a
    rejected row wrote is overwritten by the next round's first row); not one
    that holds a recurrent STATE (``PagedKVCacheSpec.has_state``: a rejected
    row would stay absorbed). A cache tensor of kind ``"state"`` that is a
    block's checkpoint and no recurrence (``CacheTensor.recurrent`` false: the
    hidden row at a block's last position, from which a hit rewrites the
    drafting layer's one slot that depends on the token AFTER the block) is a
    slot in this sense.

    A drafting model's ``resume`` takes one more operand, ``next_token`` (``[]``
    int32, by keyword): the token that follows the piece, the next piece's
    first or the prompt's last, which the drafting layer's slot at the piece's
    last position is a function of.

The engine does not call ``wave`` itself. Every decode wave it launches is ONE
program, :func:`verify_step_ragged` below, whose traffic with the host is one
array each way:

``verify_step_ragged(params, packed, prev_ids, caches, config, max_blocks,
layout) -> (logits, caches, ids, feed, aux)``
    ``packed`` is the wave's whole integer metadata as one ``int32`` vector
    (:func:`pack_wave`): the body's seven index arrays, ten with a window, at
    offsets that are a function of the bucket ``layout`` (:class:`WaveLayout`)
    and ``max_blocks`` alone. The program slices it at those static offsets,
    runs ``config.steps.wave`` on the pieces and returns, beside the body's
    ``logits`` ``[T, vocab]`` and ``caches``, the greedy token ids
    ``argmax(logits, -1)`` as ``[T] int32`` and the body's ``aux`` (``{}``
    where it returns none). The decoder hands each request its logits rows on
    the device and keeps the wave's ids and ``aux["rows"]`` beside them
    (``WaveDecoder.token_ids`` reads the ids back once a wave,
    ``WaveDecoder.row_aux`` gives a request its slice); it adds
    ``aux["counters"]`` up by name into ``harness.metrics()`` and reads
    neither. Of a model that drafts the ids are ``[2, T]``, the sampled ids
    over the body's ``aux["drafts"]`` (``WaveDecoder.draft_ids`` reads the
    second row of the same host copy).

    **A row may take its token from the device.** ``prev_ids`` is an earlier
    wave's ``feed``: its first :data:`FEED_ROWS` ids, padded to that many, so
    one shape whatever that wave's bucket was. A token slot of the packed
    operand that holds :func:`fed_token` ``(src)``, a negative number, is
    read as ``prev_ids[src]``; every other slot is the token itself. That is
    what lets the decoder launch a stream's next wave before the host has seen
    the token that wave starts from (``WaveDecoder``, "one wave ahead"). The
    model's wave body gets ``tokens`` as it always did. A wave with no such
    row is handed :func:`no_feed`. Of a model that drafts the feed is twice
    as long (:func:`feed_rows`): the first :data:`FEED_ROWS` ids, then the
    drafts of the same rows, so that a slot ``[token, draft]`` can be read
    whole on the device: ``fed_token(src)`` is row ``src``'s id and
    ``fed_token(src, draft=True)`` its draft.

Every step DONATES ``caches``: the caller uses the returned ones.

**What the model files' steps share is written here, once.** A model file
keeps its layers and the loop over them; around the loop:

:func:`resume_step`
    a file's ``prefill_continue`` (the ``resume`` of its ``ServingSteps``)
    from its jitted ``resume_chunk``: the table is as long as the harness
    said, and the chunk program runs.
:func:`prefill_by_blocks`
    a file's ``prefill`` where its chunk lies inside ONE block (a recurrent
    state, or ``resume_in_block``): the prompt cut at block boundaries
    through ``resume_chunk``, the very programs a hit's resume runs.
:func:`chunk_index`
    such a chunk's blocks: the one it lies in, the one before it, whether it
    starts a prompt.
:func:`wave_index`, :func:`wave_sources`, :func:`real_rows`
    a wave's rows by their tables: each row's table, the block and slot its
    token is written to; for a row with a state the block the state comes
    from; the rows that are no bucket padding.
:class:`ExpertTally`
    what a routed model's wave hands back beside its logits: the ids every
    row chose at every expert layer and the step's ``moe_*`` counters.
"""

import functools
import math
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..tpu.moe import EXPERT_COUNTERS, expert_counts


# The rows of a wave whose sampled ids the NEXT wave's program can read on the
# device: the length of ``feed`` and of ``prev_ids``, one for every bucket, so
# that the operand adds nothing to a program's jit key. A wave's rows past it
# are fed by the host, as every row was.
FEED_ROWS = 64


def fed_token(src: int, draft: bool = False) -> int:
    """The token slot that reads what row ``src`` of the wave before sampled:
    ``prev_ids[src]``, or with ``draft`` (a model that drafts) what it drafted,
    which lies :data:`FEED_ROWS` further on."""
    if not 0 <= src < FEED_ROWS:
        raise ValueError(f"only rows 0..{FEED_ROWS - 1} of a wave can feed the next, got {src}")
    return -(src + 1 + FEED_ROWS * draft)


def feed_rows(drafts: bool = False) -> int:
    """The length of a wave's ``feed``: the ids of its first :data:`FEED_ROWS`
    rows and, of a model that ``drafts``, their drafts behind them."""
    return FEED_ROWS * (2 if drafts else 1)


def no_feed(drafts: bool = False) -> jax.Array:
    """``prev_ids`` for a wave whose every token comes from the host."""
    return jnp.zeros((feed_rows(drafts),), jnp.int32)


class ServingSteps(NamedTuple):
    """A model's three steps by role (module docstring). ``prefill`` and
    ``resume`` return ``(logits, caches)``, and may return ``None`` for the
    logits: the engine reads neither's."""

    prefill: Callable
    resume: Callable
    wave: Callable
    # Whether ``resume`` takes a chunk INSIDE ONE BLOCK only. The engine then
    # cuts every prompt at block boundaries through it, a miss and a hit's
    # suffix alike, keeps the prompt's part-full last block and lands its last
    # token in the first wave: what it does for a cache with a recurrent
    # state, whose models' chunks lie in one block for the state's sake.
    resume_in_block: bool = False
    # Whether the model DRAFTS: its wave body proposes each row's token after
    # next (module docstring, "a model that drafts").
    drafts: bool = False


def resume_step(resume_chunk: Callable) -> Callable:
    """A model file's ``prefill_continue`` over its jitted ``resume_chunk``:
    ``resume``'s signature (module docstring), the table held to the static
    ``max_blocks`` the harness pads every table to. What a model's chunk
    takes beside (a drafting model's ``next_token``) passes through by keyword."""

    def prefill_continue(params, tokens, start_pos, caches, block_table, config, max_blocks,
                         **operands):
        if block_table.shape[0] != max_blocks:
            raise ValueError(
                f"block_table has {block_table.shape[0]} entries, expected "
                f"max_blocks={max_blocks} (pad the table to the static bound)"
            )
        return resume_chunk(params, tokens, start_pos, caches, block_table, config, **operands)

    return prefill_continue


def prefill_by_blocks(resume_chunk: Callable, next_token: bool = False) -> Callable:
    """A model file's ``prefill`` where a chunk lies inside one block: a miss,
    every token given, cut at block boundaries through the chunk program a
    hit's resume runs, so that each block's slot holds what stands at its end
    (a state, a tail) and a full hit's first token equals the miss's to the
    bit. ``block_table`` covers the tokens (a last block may be part full).
    With ``next_token`` (a drafting model's chunk) each piece is handed the
    token that follows it, the last piece its own last token (the engine,
    which lands a prompt's last token in a wave, hands in the tokens before it
    and names the real one). Returns (the last row's logits, or None where the
    chunk has none, caches); ``caches`` is donated."""

    def prefill(params, tokens, caches, block_table, config):
        bt = config.block_tokens
        tokens = jnp.asarray(tokens, jnp.int32)
        table = jnp.asarray(block_table, jnp.int32)
        logits = None
        last = tokens.shape[0] - 1
        for start in range(0, tokens.shape[0], bt):
            after = {"next_token": tokens[min(start + bt, last)]} if next_token else {}
            logits, caches = resume_chunk(
                params, tokens[start : start + bt], jnp.int32(start), caches, table, config,
                **after,
            )
        return None if logits is None else logits[-1], caches

    return prefill


def chunk_index(tokens, start_pos, block_table, block_tokens: int):
    """Of a chunk at positions ``start_pos ..`` that lies inside ONE block:
    (the block it lies in, the block of position ``start_pos - 1``, whose end
    state it starts from, whether it starts a prompt and there is none)."""
    if tokens.shape[0] > block_tokens:
        raise ValueError(
            f"a chunk of {tokens.shape[0]} tokens does not lie in one {block_tokens}-token block"
        )
    block = block_table[start_pos // block_tokens]
    before = block_table[jnp.maximum(start_pos - 1, 0) // block_tokens]
    return block, before, start_pos == 0


def _block_of(row_tables, blocks):
    """[T]: entry ``blocks[t]`` of row t's table."""
    return jnp.take_along_axis(row_tables, blocks[:, None], axis=1)[:, 0]


def wave_index(positions, row_of, block_tables, max_blocks: int, block_tokens: int):
    """Of a wave's T flat rows: (``row_tables`` [T, max_blocks] each row's own
    table, ``dst`` [T] the block its token's K/V or state is written to,
    ``slots`` [T] the slot within it). A row's length with its token is
    ``positions + 1``, written where a file's layers take it: XLA's schedule of
    this arithmetic follows how often it was traced
    (``tools/program_fingerprints.py``; CHANGES.md, PR 60)."""
    if block_tables.ndim != 2 or block_tables.shape[1] != max_blocks:
        raise ValueError(f"block_tables must be [B, {max_blocks}], got {block_tables.shape}")
    row_tables = jnp.take(block_tables, row_of, axis=0)
    return row_tables, _block_of(row_tables, positions // block_tokens), positions % block_tokens


def wave_sources(positions, row_tables, block_tokens: int):
    """Of rows that carry a state: (``src`` [T] the block each row's state
    comes from, position p - 1's, ``fresh`` [T] whether the row starts a
    prompt and has none). ``src`` differs from :func:`wave_index`'s ``dst``
    where the row crosses into a new block: the running state moves on and the
    block behind keeps its end state."""
    return _block_of(row_tables, jnp.maximum(positions - 1, 0) // block_tokens), positions == 0


def real_rows(positions, row_of):
    """[T] bool: a wave's rows that are no padding. The bucket's tail repeats
    its last row, so a row that equals its predecessor is padding."""
    real = jnp.concatenate([
        jnp.ones((1,), bool),
        (positions[1:] != positions[:-1]) | (row_of[1:] != row_of[:-1]),
    ])
    return real


class ExpertTally:
    """What a routed model's wave step gathers over its layers: ``add`` a
    layer's chosen ids and its ``expert_counts`` (a dense layer's are None and
    add nothing), then ``aux`` is the wave's third value. A model file adds its
    own counters to what ``aux`` returns, and names ``counters`` first in its
    configuration's ``step_counters``."""

    counters = ("moe_pairs", *EXPERT_COUNTERS)

    def __init__(self):
        self.chosen, self.counts = [], expert_counts()

    def add(self, ids, counts):
        if ids is not None:
            self.chosen.append(ids)
            self.counts = jax.tree.map(jnp.add, self.counts, counts)

    def aux(self, real, experts_per_token: int):
        """``{"rows": [T, sites, k]`` the experts every row chose at every
        expert layer IN THIS STEP, ``"counters"``: ``moe_pairs``, the (row,
        expert) pairs of the wave's ``real`` rows over its expert layers, and
        the layers' ``expert_counts`` summed``}``."""
        rows = jnp.stack(self.chosen, axis=1)
        pairs = jnp.sum(real, dtype=jnp.int32) * (len(self.chosen) * experts_per_token)
        return {"rows": rows, "counters": {"moe_pairs": pairs, **self.counts}}


class WaveLayout(NamedTuple):
    """A wave's bucket: the jit key of its program, and all that the packed
    operand's layout depends on beside ``max_blocks``."""

    rows: int  # T: flat token rows
    tables: int  # B: block-table rows
    pages: int  # P: flat attention pages
    window_pages: Optional[int] = None  # Pw: the sliding layers' pages, if any

    def fields(self, max_blocks: int) -> List[Tuple[str, Tuple[int, ...]]]:
        """(name, shape) of every piece, in the order they lie in the operand:
        the body's index arguments in its own order, then the window triple."""
        t, b, p, pw = self
        out = [
            ("tokens", (t,)),
            ("positions", (t,)),
            ("row_of", (t,)),
            ("pages", (p,)),
            ("page_rows", (p + 1,)),
            ("page_starts", (t,)),
            ("block_tables", (b, max_blocks)),
        ]
        if pw is not None:
            out += [
                ("window_pages", (pw,)),
                ("window_page_rows", (pw + 1,)),
                ("window_page_starts", (t,)),
            ]
        return out

    def size(self, max_blocks: int) -> int:
        return sum(math.prod(shape) for _, shape in self.fields(max_blocks))


def pack_wave(layout: WaveLayout, max_blocks: int, pieces: Sequence) -> np.ndarray:
    """The wave's metadata as ONE host ``int32`` vector: ``pieces`` are the
    arrays (or lists) of ``layout.fields(max_blocks)``, in that order. A fresh
    buffer a call: the runtime may read it after the launch returns."""
    fields = layout.fields(max_blocks)
    if len(pieces) != len(fields):
        raise ValueError(f"{layout} takes {len(fields)} pieces, got {len(pieces)}")
    flat = []
    for (name, shape), piece in zip(fields, pieces):
        piece = np.asarray(piece, np.int32)
        if piece.shape != shape:
            raise ValueError(f"{name} must be {shape} in {layout}, got {piece.shape}")
        flat.append(piece.reshape(-1))
    return np.concatenate(flat)


def unpack_wave(packed: jax.Array, layout: WaveLayout, max_blocks: int) -> Dict[str, jax.Array]:
    """:func:`pack_wave`'s pieces by name, cut out of the operand at static
    offsets inside the program."""
    if packed.shape != (layout.size(max_blocks),):
        raise ValueError(
            f"the packed operand of {layout} at max_blocks={max_blocks} is "
            f"[{layout.size(max_blocks)}], got {packed.shape}"
        )
    out, at = {}, 0
    for name, shape in layout.fields(max_blocks):
        n = math.prod(shape)
        out[name] = jax.lax.reshape(jax.lax.slice(packed, (at,), (at + n,)), shape)
        at += n
    return out


@functools.partial(
    jax.jit,
    static_argnames=("config", "max_blocks", "layout"),
    donate_argnames=("caches",),
)
def verify_step_ragged(
    params, packed, prev_ids, caches, config, max_blocks: int, layout: WaveLayout
):
    """THE decode wave as the engine launches it (module docstring): the
    model's own wave body, ``config.steps.wave``, between one operand in (and
    an earlier wave's ``feed``, for the rows that take their token from it)
    and the sampled ids out. The trace knows every model's wave program by this
    function's name. The body is traced as the plain function behind its own
    ``jax.jit`` (``__wrapped__``), so this program is one jit deep, as the
    body alone is: a jit nested under this one cost every wave bucket a
    quarter of a second of set-up on the chip's host (PERF.md, PR 36).
    ``caches`` is donated, declared here as the body declares it."""
    fed = feed_rows(config.steps.drafts)
    if prev_ids.shape != (fed,):
        raise ValueError(f"prev_ids is a wave's feed, [{fed}], got {prev_ids.shape}")
    f = unpack_wave(packed, layout, max_blocks)
    # A slot is a token or ``fed_token(src)``. ``lax`` calls and an index, no
    # ``jnp`` function that is a jit of its own (the set-up rule above).
    slots = f["tokens"]
    src = jax.lax.clamp(0, -slots - 1, fed - 1)
    tokens = jax.lax.select(slots < 0, prev_ids.at[src].get(mode="promise_in_bounds"), slots)
    kw = {}
    if layout.window_pages is not None:
        kw["window_pages"] = (
            f["window_pages"], f["window_page_rows"], f["window_page_starts"]
        )
    body = getattr(config.steps.wave, "__wrapped__", config.steps.wave)
    logits, caches, *aux = body(
        params, tokens, f["positions"], f["row_of"], f["pages"],
        f["page_rows"], f["page_starts"], caches, f["block_tables"], config,
        max_blocks, **kw,
    )
    ids = jax.lax.argmax(logits, 1, jnp.int32)  # what jnp.argmax(logits, -1) computes
    short = [(0, max(FEED_ROWS - layout.rows, 0), 0)]
    feed = jax.lax.pad(ids[:FEED_ROWS], jnp.int32(0), short)
    aux = dict(aux[0]) if aux else {}
    drafts = aux.pop("drafts", None)
    if config.steps.drafts:
        # The next wave may read a slot's draft where it reads its token.
        feed = jax.lax.concatenate(
            [feed, jax.lax.pad(drafts[:FEED_ROWS], jnp.int32(0), short)], 0
        )
        # The ids over the body's drafts, ONE array: a round's token and its
        # draft reach the host in one read (each blocking read costs the host
        # about as much as a dispatch).
        row = lambda a: jax.lax.expand_dims(a, (0,))
        ids = jax.lax.concatenate([row(ids), row(drafts)], 0)
    return logits, caches, ids, feed, aux
