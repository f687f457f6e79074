"""The prompt save's store write runs beside the request's generation
(engine.py ``run_request``): the snapshot comes before the first wave, the
write is a task of its own, and the request returns, frees its blocks and
appends its stats only once that write is acknowledged, failed or, where the
request itself dies, cancelled AND awaited."""

import asyncio
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu import wire
from infinistore_tpu.connector import KVConnector
from infinistore_tpu.engine import ContinuousBatchingHarness, EngineKVAdapter
from infinistore_tpu.models import LlamaConfig, init_params
from infinistore_tpu.tpu.paged import gather_blocks

CFG = LlamaConfig(
    vocab=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128,
    block_tokens=8, dtype=jnp.float32,
)
NUM_BLOCKS, MAX_REQ_BLOCKS, PROMPT_BLOCKS, GEN = 8, 4, 3, 5
TIMEOUT_S = 60


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _prompt(seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab, size=PROMPT_BLOCKS * CFG.block_tokens).tolist()


class ScriptedAdapter(EngineKVAdapter):
    """The engine adapter with a script for its FIRST ``save_kv`` (a
    request's prompt save): hold it on ``release``, fail it with ``error``
    (after the hold, if both), and note what a cancellation meets. Later
    saves go straight through."""

    def __init__(self, connector, hold=False, error=None):
        super().__init__(connector)
        self.hold, self.error = hold, error
        self.release = asyncio.Event()
        self.entered = asyncio.Event()
        self.calls = 0
        self.tasks = []  # the task each save ran in
        self.saved = None  # host copy of the first save's snapshot
        self.events = []
        self.classes = []  # per save: the class cell bound around it, its value at entry

    async def save_kv(self, token_ids, caches, block_table, first_block=0):
        self.calls += 1
        self.tasks.append(asyncio.current_task())
        cell = wire.SAVE_CLASS.get()
        self.classes.append((cell, cell["value"]))
        if self.calls > 1:
            return await super().save_kv(token_ids, caches, block_table, first_block=first_block)
        self.saved = [(np.asarray(k), np.asarray(v)) for k, v in caches]
        self.entered.set()
        try:
            if self.hold:
                await self.release.wait()
        except asyncio.CancelledError:
            # What the layerwise writer's `finally` does: puts in flight
            # are waited for before the cancellation goes on.
            self.events.append("write_cancelled")
            await asyncio.sleep(0.05)
            self.events.append("write_drained")
            raise
        if self.error is not None:
            raise self.error
        return await super().save_kv(token_ids, caches, block_table, first_block=first_block)


def _harness(conn, params, model_id, **script):
    kvc = KVConnector(conn, CFG.kv_spec(NUM_BLOCKS), model_id, max_blocks=MAX_REQ_BLOCKS)
    adapter = ScriptedAdapter(kvc, **script)
    h = ContinuousBatchingHarness(adapter, params, CFG, NUM_BLOCKS, MAX_REQ_BLOCKS)
    return h, adapter, kvc


def _tap_step_chunk(h):
    """Note the task and the time of every ``step_chunk`` entry, as the
    benchmark's taps do (benchmarks/run.py ``Instruments``)."""
    entries = []
    inner = h.wave.step_chunk

    async def step_chunk(tokens, positions, padded_table, priority=0):
        entries.append((asyncio.current_task(), time.perf_counter()))
        return await inner(tokens, positions, padded_table, priority=priority)

    h.wave.step_chunk = step_chunk
    return entries


async def _until(cond):
    t_end = time.perf_counter() + TIMEOUT_S
    while not cond():
        assert time.perf_counter() < t_end, "condition never held"
        await asyncio.sleep(0.005)


def _run(coro):
    return asyncio.run(asyncio.wait_for(coro, TIMEOUT_S))


def test_first_token_while_write_held_then_byte_identical_full_hit(conn, params):
    h, adapter, kvc = _harness(conn, params, f"overlap-held-{conn.shm_active}", hold=True)
    entries = _tap_step_chunk(h)
    prompt = _prompt(1)

    async def drive():
        task = asyncio.ensure_future(h.run_request(prompt, gen_tokens=GEN))
        # Every token is out while the store has acknowledged nothing.
        await _until(lambda: len(entries) == GEN and h.wave.waves >= GEN)
        await asyncio.sleep(0.05)
        assert adapter.entered.is_set() and not task.done()
        assert h.pool.available == NUM_BLOCKS - MAX_REQ_BLOCKS  # blocks not back
        assert h.live == 1 and h.stats == [] and h._saving == 1
        assert kvc.lookup(prompt) == 0  # nothing committed
        t_release = time.perf_counter()
        adapter.release.set()
        stats = await task
        return task, stats, t_release

    async def reread():
        caches, n = await kvc.load(
            prompt, CFG.kv_spec(PROMPT_BLOCKS).make_caches(), np.arange(PROMPT_BLOCKS)
        )
        return caches, n, await h.run_request(prompt, gen_tokens=GEN)

    async def both():
        first = await drive()
        return first, await reread()

    (task, stats, t_release), (caches, n, again) = _run(both())
    # The generation stayed in the request's own task; the write did not.
    assert {t for t, _ in entries[:GEN]} == {task} and adapter.tasks[0] is not task
    assert stats.token_emit_s[-1] < t_release and entries[1][1] < t_release
    assert stats.computed_blocks == PROMPT_BLOCKS and len(stats.generated) == GEN
    assert stats.save_tail_us > 0 and stats.save_overlap_us > 0
    assert h.pool.available == NUM_BLOCKS and h.live == 0 and h._saving == 0
    # What the store holds is what the snapshot held, byte for byte.
    assert n == PROMPT_BLOCKS
    ids = jnp.arange(PROMPT_BLOCKS)
    for (k, v), (saved_k, saved_v) in zip(caches, adapter.saved):
        assert np.asarray(gather_blocks(k, ids)).tobytes() == saved_k.tobytes()
        assert np.asarray(gather_blocks(v, ids)).tobytes() == saved_v.tobytes()
    assert again.loaded_blocks == PROMPT_BLOCKS and again.computed_blocks == 0
    assert again.generated == stats.generated
    assert again.save_overlap_us == again.save_tail_us == 0.0
    assert h.metrics()["saves_overlapped"] == 1


@pytest.mark.parametrize("when", ["while_generating", "after_last_token"])
def test_failed_write_fails_the_request_and_strands_nothing(conn, params, when):
    h, adapter, kvc = _harness(
        conn, params, f"overlap-fail-{when}-{conn.shm_active}",
        hold=when == "after_last_token", error=RuntimeError("store write failed"),
    )
    entries = _tap_step_chunk(h)

    async def drive():
        failing = asyncio.ensure_future(h.run_request(_prompt(2), gen_tokens=GEN))
        await adapter.entered.wait()
        # Two more requests of four blocks each: the second finds the
        # pool of eight empty and waits for the failing one's blocks.
        waiters = [
            asyncio.ensure_future(h.run_request(_prompt(seed), gen_tokens=GEN))
            for seed in (3, 4)
        ]
        if when == "after_last_token":
            await _until(lambda: sum(t is failing for t, _ in entries) == GEN)
            await asyncio.sleep(0.05)
            adapter.release.set()
        with pytest.raises(RuntimeError, match="store write failed"):
            await failing
        return await asyncio.gather(*waiters)

    done = _run(drive())
    assert [len(s.generated) for s in done] == [GEN, GEN]
    assert h.pool.available == NUM_BLOCKS and h.live == 0 and h._saving == 0
    assert len(h.stats) == 2  # the failed request left no stats
    assert kvc.lookup(_prompt(2)) == 0 and kvc.lookup(_prompt(3)) == PROMPT_BLOCKS


@pytest.mark.parametrize("how", ["generate_raises", "request_cancelled"])
def test_dying_request_cancels_and_awaits_the_write_before_free(conn, params, how):
    h, adapter, kvc = _harness(conn, params, f"overlap-die-{how}-{conn.shm_active}", hold=True)
    free = h.pool.free

    async def noting_free(table):
        adapter.events.append("free")
        return await free(table)

    h.pool.free = noting_free
    if how == "generate_raises":
        inner = h.wave.step_chunk
        rounds = []

        async def step_chunk(*a, **kw):
            rounds.append(1)
            if len(rounds) == 3:
                raise ValueError("the model step failed")
            return await inner(*a, **kw)

        h.wave.step_chunk = step_chunk

    async def drive():
        task = asyncio.ensure_future(h.run_request(_prompt(5), gen_tokens=GEN))
        if how == "request_cancelled":
            await adapter.entered.wait()
            await _until(lambda: h.wave.waves >= 2)
            task.cancel()
        with pytest.raises(ValueError if how == "generate_raises" else asyncio.CancelledError):
            await task

    _run(drive())
    assert adapter.events == ["write_cancelled", "write_drained", "free"]
    assert h.pool.available == NUM_BLOCKS and h.live == 0 and h._saving == 0
    assert h.stats == [] and kvc.lookup(_prompt(5)) == 0


def test_prefill_only_request_awaits_its_save_in_line(conn, params):
    h, adapter, kvc = _harness(conn, params, f"overlap-prefill-{conn.shm_active}", hold=True)
    prompt = _prompt(6)

    async def drive():
        task = asyncio.ensure_future(h.run_request(prompt, gen_tokens=0))
        await adapter.entered.wait()
        await asyncio.sleep(0.05)
        assert not task.done() and h.pool.available == NUM_BLOCKS - PROMPT_BLOCKS
        adapter.release.set()
        return task, await task

    task, stats = _run(drive())
    assert adapter.tasks == [task]  # no task of its own: awaited where it stood
    assert adapter.classes[0][1] == wire.PRIORITY_FOREGROUND  # awaited: foreground from the first
    assert stats.generated is None and stats.save_overlap_us == stats.save_tail_us == 0.0
    assert stats.ack_tail_us == 0.0
    assert h.metrics()["saves_overlapped"] == 0 and kvc.lookup(prompt) == PROMPT_BLOCKS


@pytest.mark.parametrize("case", ["write_ends_first", "write_outlasts_generation"])
def test_overlap_and_tail_read_what_happened(conn, params, case):
    held = case == "write_outlasts_generation"
    h, adapter, _ = _harness(conn, params, f"overlap-{case}-{conn.shm_active}", hold=held)
    entries = _tap_step_chunk(h)

    async def drive():
        t_sent = time.perf_counter()
        task = asyncio.ensure_future(h.run_request(_prompt(7), gen_tokens=GEN))
        if held:
            await _until(lambda: len(entries) == GEN and h.wave.waves >= GEN)
            await asyncio.sleep(0.05)
            adapter.release.set()
        else:
            # Hold the LAST round instead, so that the write ends first
            # (the tap below this wrapper has noted GEN - 1 entries by then).
            inner = h.wave.step_chunk

            async def step_chunk(*a, **kw):
                if len(entries) == GEN - 1:
                    await _until(lambda: h._saving == 0)
                return await inner(*a, **kw)

            h.wave.step_chunk = step_chunk
        stats = await task
        return stats, (time.perf_counter() - t_sent) * 1e6

    stats, whole_us = _run(drive())
    assert 0 < stats.save_overlap_us < whole_us
    assert stats.save_tail_us <= stats.ack_tail_us < whole_us  # the whole tail holds the write's
    if held:
        # The request waited 50 ms and more for the acknowledgement.
        assert 0.04e6 < stats.save_tail_us < whole_us
        assert stats.save_overlap_us + stats.save_tail_us < whole_us
    else:
        assert stats.save_tail_us == 0.0
    m = h.metrics()
    assert m["saves_overlapped"] == 1 and m["max_concurrent_saves"] == 1


@pytest.mark.parametrize("case", ["held_past_the_join", "acknowledged_before_it"])
def test_class_cell_is_background_beside_generation_and_foreground_from_the_join(
    conn, params, case
):
    held = case == "held_past_the_join"
    h, adapter, kvc = _harness(conn, params, f"overlap-class-{case}-{conn.shm_active}", hold=held)
    entries = _tap_step_chunk(h)
    gen = CFG.block_tokens  # one whole answer block: a second, awaited save
    prompt = _prompt(9)

    async def drive():
        task = asyncio.ensure_future(h.run_request(prompt, gen_tokens=gen))
        await adapter.entered.wait()
        cell = adapter.classes[0][0]
        if held:
            assert cell["value"] == wire.PRIORITY_BACKGROUND  # generating: gen rounds take longer
            await _until(lambda: cell["value"] == wire.PRIORITY_FOREGROUND)  # the join
            assert len(entries) >= gen and not task.done() and adapter.calls == 1
            adapter.release.set()
        stats = await task
        assert wire.SAVE_CLASS.get() is None  # bound around the adapter call only
        return stats

    stats = _run(drive())
    (prompt_cell, at_entry), (answer_cell, answer_at_entry) = adapter.classes
    assert at_entry == wire.PRIORITY_BACKGROUND and answer_at_entry == wire.PRIORITY_FOREGROUND
    assert prompt_cell is not answer_cell
    assert prompt_cell["value"] == wire.PRIORITY_FOREGROUND  # flipped at the join, whoever won
    assert adapter.tasks[0] is not adapter.tasks[1]  # the answer's save: in the request's task
    assert stats.ack_tail_us >= stats.save_tail_us
    assert kvc.lookup(prompt + stats.generated) == PROMPT_BLOCKS + 1


def test_save_blocks_keeps_its_three_argument_form(conn, params):
    """The benchmark's warm-up calls it so: snapshot and write in turn."""
    h, adapter, kvc = _harness(conn, params, f"overlap-direct-{conn.shm_active}")
    prompt = _prompt(8)

    async def drive():
        table = await h.pool.alloc(PROMPT_BLOCKS)
        await h._save_blocks(prompt, table, 0)
        await h.pool.free(table)

    _run(drive())
    assert adapter.calls == 1 and kvc.lookup(prompt) == PROMPT_BLOCKS
    assert h.saves_overlapped == 0 and h._saving == 0
    assert adapter.classes[0][1] == wire.PRIORITY_FOREGROUND  # awaited in line
    stats = kvc.get_stats()
    assert stats["save_fg_writes"] == 1 and stats["save_fg_puts"] == stats["save_puts"] == 4
