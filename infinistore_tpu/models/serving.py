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
``wave(params, tokens, positions, row_of, pages, page_rows, page_starts,
caches, block_tables, config, max_blocks[, window_pages=]) -> (logits,
caches[, aux])``
    a decode wave: flat rows of many requests, each over its own pages.
    Where the cache's spec names a sliding window (``PagedKVCacheSpec.window``)
    the decoder hands a second ``(pages, page_rows, page_starts)`` triple, the
    wave's windowed page list, as ``window_pages``. A step may return a third
    value, ``aux``: ``{"rows": array [T, ...], "counters": {name: scalar}}``,
    both still on the device. The decoder keeps each request's slice of
    ``rows`` beside the logits rows it hands back (``WaveDecoder.row_aux``)
    and adds ``counters`` up by name into ``harness.metrics()``; it reads
    neither.

Every step DONATES ``caches``: the caller uses the returned ones.
"""

from typing import Callable, NamedTuple


class ServingSteps(NamedTuple):
    prefill: Callable
    resume: Callable
    wave: Callable
