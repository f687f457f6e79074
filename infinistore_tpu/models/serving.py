"""What the serving engine asks of a model file: three steps, by role.

``engine.py`` names no model file. A configuration object carries ``steps``
(a :class:`ServingSteps`) beside ``block_tokens`` and ``kv_spec(blocks)``, and
the harness runs what it finds there:

``prefill(params, tokens, caches, block_table, config) -> (logits, caches)``
    a miss: the whole prompt, its K/V written to the blocks of the table.
``resume(params, tokens, start_pos, caches, block_table, config, max_blocks)
-> (logits, caches)``
    a prefix hit's question: one request's chunk at contiguous positions
    against the pages already in the cache.

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
    pages. Where the cache's spec names a sliding window
    (``PagedKVCacheSpec.window``) it takes a second ``(pages, page_rows,
    page_starts)`` triple, the wave's windowed page list, as
    ``window_pages``. A body may return a third value, ``aux``: ``{"rows":
    array [T, ...], "counters": {name: scalar}}``, both still on the device.

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
    neither.

    **A row may take its token from the device.** ``prev_ids`` is an earlier
    wave's ``feed``: its first :data:`FEED_ROWS` ids, padded to that many, so
    one shape whatever that wave's bucket was. A token slot of the packed
    operand that holds :func:`fed_token` ``(src)``, a negative number, is
    read as ``prev_ids[src]``; every other slot is the token itself. That is
    what lets the decoder launch a stream's next wave before the host has seen
    the token that wave starts from (``WaveDecoder``, "one wave ahead"). The
    model's wave body gets ``tokens`` as it always did. A wave with no such
    row is handed :func:`no_feed`.

Every step DONATES ``caches``: the caller uses the returned ones.
"""

import functools
import math
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# The rows of a wave whose sampled ids the NEXT wave's program can read on the
# device: the length of ``feed`` and of ``prev_ids``, one for every bucket, so
# that the operand adds nothing to a program's jit key. A wave's rows past it
# are fed by the host, as every row was.
FEED_ROWS = 64


def fed_token(src: int) -> int:
    """The token slot that reads row ``src`` of ``prev_ids``."""
    if not 0 <= src < FEED_ROWS:
        raise ValueError(f"only rows 0..{FEED_ROWS - 1} of a wave can feed the next, got {src}")
    return -(src + 1)


def no_feed() -> jax.Array:
    """``prev_ids`` for a wave whose every token comes from the host."""
    return jnp.zeros((FEED_ROWS,), jnp.int32)


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
    if prev_ids.shape != (FEED_ROWS,):
        raise ValueError(f"prev_ids is a wave's feed, [{FEED_ROWS}], got {prev_ids.shape}")
    f = unpack_wave(packed, layout, max_blocks)
    # A slot is a token or ``fed_token(src)``. ``lax`` calls and an index, no
    # ``jnp`` function that is a jit of its own (the set-up rule above).
    slots = f["tokens"]
    src = jax.lax.clamp(0, -slots - 1, FEED_ROWS - 1)
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
    short = max(FEED_ROWS - layout.rows, 0)
    feed = jax.lax.pad(ids[:FEED_ROWS], jnp.int32(0), [(0, short, 0)])
    return logits, caches, ids, feed, aux[0] if aux else {}
