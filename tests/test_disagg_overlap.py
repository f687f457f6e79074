"""Overlapped layerwise prefill→decode handoff (docs/disaggregation.md).

The contracts of the disagg plane, on a small real store:

- **watermark semantics**: with ``watermark=1`` the first decode step
  launches after layer 0 installs, and every deeper layer's install
  precedes its compute (the trace-event invariant) while the transfer is
  still streaming behind the step;
- **byte identity**: the overlapped and blocking legs both produce
  first-token logits bitwise equal to the local-recompute oracle
  (``check_bytes``; ``disagg_wrong_bytes`` stays 0);
- **degenerate watermark**: ``watermark=n_layers`` is today's blocking
  fetch-all — every install strictly precedes every compute;
- **fallback**: a layer missing past the retry deadline flips the leg to
  the layer-chunked local recompute — counted, journaled as a
  ``disagg_fallback`` event, and STILL byte-identical to the oracle;
- **manage-plane export**: after a handoff, /metrics carries the
  ``infinistore_disagg_*`` families and ``GET /disagg`` the snapshot
  (ITS-C009);
- **(chaos)** a prefill ENGINE subprocess kill -9'd mid-stream (layers
  0..k durable, deeper layers never arrive) degrades to the fallback
  with zero wrong bytes.
"""

import asyncio
import json
import urllib.request

import numpy as np
import pytest

import infinistore_tpu as its
from infinistore_tpu import disagg, telemetry
from tools import fleet

CFG = disagg.demo_config(n_layers=4)
REQ_BLOCKS = 2
NUM_BLOCKS = 16


@pytest.fixture(autouse=True)
def _fresh_counters():
    telemetry.reset()
    ds = disagg.reset_counters()
    yield ds
    telemetry.reset()


@pytest.fixture(scope="module")
def store():
    srv = its.start_local_server(
        prealloc_bytes=64 << 20,
        block_bytes=max(64 << 10, CFG.kv_spec(1).block_nbytes),
    )
    yield srv
    srv.stop()


@pytest.fixture()
def harness(store):
    conns = []

    def make_conn():
        c = its.InfinityConnection(its.ClientConfig(
            host_addr="127.0.0.1", service_port=store.port, log_level="error",
        ))
        c.connect()
        conns.append(c)
        return c

    h = disagg.DisaggHarness(
        make_conn, CFG, num_blocks=NUM_BLOCKS, req_blocks=REQ_BLOCKS,
    )
    yield h
    for c in conns:
        c.close()


def _event_index(events, kind, layer):
    return events.index((kind, layer))


class TestWatermark:
    def test_install_precedes_compute_per_layer(self, harness):
        """The watermark invariant: layer l's attention never reads bytes
        still in flight — its install event precedes its compute event,
        for every layer, while deeper layers stream behind the step."""
        ev = []
        res = asyncio.run(harness.run_overlapped(
            harness.prompt(seed=1), watermark=1, trace_events=ev,
        ))["result"]
        assert not res.fallback
        for layer in range(CFG.n_layers):
            assert (
                _event_index(ev, "install", layer)
                < _event_index(ev, "compute", layer)
            ), f"layer {layer} computed before its install: {ev}"
        # Layerwise admission really happened: the first compute did not
        # wait for the deepest layer's install (blocking would order ALL
        # installs first).
        assert _event_index(ev, "compute", 0) < _event_index(
            ev, "install", CFG.n_layers - 1
        )

    def test_watermark_full_degenerates_to_blocking(self, harness):
        """``watermark=n_layers`` is the blocking fetch-all: every install
        strictly precedes every compute."""
        ev = []
        res = asyncio.run(harness.run_overlapped(
            harness.prompt(seed=2), watermark=CFG.n_layers, trace_events=ev,
        ))["result"]
        assert not res.fallback
        last_install = max(
            i for i, (kind, _) in enumerate(ev) if kind == "install"
        )
        first_compute = min(
            i for i, (kind, _) in enumerate(ev) if kind == "compute"
        )
        assert last_install < first_compute
        assert res.overlap_layers == 0

    def test_watermark_clamped(self, harness):
        """Out-of-range watermarks clamp to [1, n_layers] instead of
        deadlocking or skipping the gate."""
        for wm in (0, CFG.n_layers + 7):
            res = asyncio.run(harness.run_overlapped(
                harness.prompt(seed=3), watermark=wm,
            ))["result"]
            assert not res.fallback


class TestByteIdentity:
    def test_overlapped_and_blocking_match_oracle(self, harness, _fresh_counters):
        prompt = harness.prompt(seed=4)
        oracle = asyncio.run(harness.run_local(prompt))["result"]
        over = asyncio.run(
            harness.run_overlapped(prompt, watermark=1)
        )["result"]
        harness.drop(prompt)
        blocking = asyncio.run(harness.run_blocking(prompt))["result"]
        assert harness.check_bytes(over, oracle)
        assert harness.check_bytes(blocking, oracle)
        assert not over.fallback and not blocking.fallback
        assert _fresh_counters.status()["disagg_wrong_bytes"] == 0

    def test_multi_token_decode_matches(self, harness):
        """Identity holds past the first token: the greedy continuations
        of the handoff and local legs agree token for token."""
        prompt = harness.prompt(seed=5)
        oracle = asyncio.run(
            harness.run_local(prompt, gen_tokens=4)
        )["result"]
        over = asyncio.run(
            harness.run_overlapped(prompt, watermark=1, gen_tokens=4)
        )["result"]
        assert over.tokens == oracle.tokens
        assert harness.check_bytes(over, oracle)
        # The generated tokens' own K/V, every layer: a first-step insert
        # dropped while a deeper layer was still installing leaves zeros
        # here and the tokens above can still happen to agree.
        gen_block = int(harness.tables()[2][-1])
        for layer in range(CFG.n_layers):
            for kind in (0, 1):
                np.testing.assert_array_equal(
                    np.asarray(over.caches[layer][kind][gen_block]),
                    np.asarray(oracle.caches[layer][kind][gen_block]),
                    err_msg=f"layer {layer}: generation block diverged",
                )


class TestFallback:
    def test_missing_layers_fall_back_and_stay_correct(
        self, harness, _fresh_counters
    ):
        """No producer at all: every install misses the retry deadline,
        the leg recomputes locally — counted, journaled, byte-identical."""
        prompt = harness.prompt(seed=6)
        res = asyncio.run(harness.run_overlapped(
            prompt, watermark=1, prefill=False, retry_missing_s=0.05,
        ))["result"]
        assert res.fallback
        oracle = asyncio.run(harness.run_local(prompt))["result"]
        assert harness.check_bytes(res, oracle)
        st = _fresh_counters.status()
        assert st["disagg_fallback_recomputes"] == 1
        assert st["disagg_wrong_bytes"] == 0
        kinds = [e["kind"] for e in telemetry.get_journal().snapshot()]
        assert "disagg_fallback" in kinds

    def test_fallback_journal_names_the_failed_layer(self, harness):
        asyncio.run(harness.run_overlapped(
            harness.prompt(seed=7), watermark=1, prefill=False,
            retry_missing_s=0.05,
        ))
        ev = [
            e for e in telemetry.get_journal().snapshot()
            if e["kind"] == "disagg_fallback"
        ]
        assert ev and ev[0]["attrs"]["failed_layer"] == 0
        assert ev[0]["attrs"]["prefix_blocks"] == REQ_BLOCKS


class TestManagePlane:
    def test_metrics_and_disagg_route_export_counters(self, harness, store):
        """ITS-C009's runtime half: after a handoff in this process, the
        manage plane's /metrics carries the infinistore_disagg_* families
        and GET /disagg serves the same snapshot."""
        from infinistore_tpu import lib as its_lib
        from infinistore_tpu.server import ManageServer

        asyncio.run(harness.run_overlapped(harness.prompt(seed=8)))
        cfg = its.ServerConfig(
            host="127.0.0.1", service_port=0, manage_port=1,
            prealloc_size=1, minimal_allocate_size=16, pin_memory=False,
            log_level="error",
        )

        def get(port, path):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5
            ) as r:
                return r.read().decode()

        async def run():
            manage = ManageServer(cfg)
            manage._server = await asyncio.start_server(
                manage._handle, host="127.0.0.1", port=0
            )
            port = manage._server.sockets[0].getsockname()[1]
            try:
                metrics = await asyncio.to_thread(get, port, "/metrics")
                doc = json.loads(await asyncio.to_thread(get, port, "/disagg"))
            finally:
                manage._server.close()
                await manage._server.wait_closed()
            return metrics, doc

        old = its_lib._server_handle
        its_lib._server_handle = store.handle
        try:
            metrics, doc = asyncio.run(run())
        finally:
            its_lib._server_handle = old
        st = disagg.counters().status()
        assert st["disagg_handoffs"] >= 1
        assert doc["enabled"] is True
        for key, val in st.items():
            assert doc[key] == val
            assert f"infinistore_{key} {val}" in metrics


@pytest.mark.chaos
class TestChaos:
    def test_prefill_killed_mid_stream_degrades_to_fallback(
        self, harness, store, _fresh_counters
    ):
        """kill -9 the prefill ENGINE subprocess mid-handoff: layers 0..1
        durable, deeper layers never arrive; the decode side's retry
        deadline expires and the leg recomputes — never wrong bytes.

        The kill window opens only after BOTH layers' durability markers
        (in any order — ships are concurrent, and under in-suite load
        layer 1's puts can finish before layer 0's): killing on the last
        marker alone could SIGKILL while layer 0 is still partially
        written, and the fallback would then fire at layer 0 instead of
        the first never-shipped layer (the one-flake-in-suite PR 17
        noted)."""
        member = fleet.spawn_disagg_prefill(
            store.port, blocks=REQ_BLOCKS, n_layers=CFG.n_layers,
            prompt_seed=9, stall_after_layer=1, stall_s=60.0,
        )
        try:
            fleet.read_until_markers(
                member, ["shipped layer 0", "shipped layer 1"],
                timeout_s=180.0,
            )
            assert fleet.kill_member(member) == -9
        finally:
            if member["proc"].poll() is None:
                member["proc"].kill()
        prompt = harness.prompt(seed=9)
        res = asyncio.run(harness.run_overlapped(
            prompt, watermark=1, prefill=False, retry_missing_s=0.5,
        ))["result"]
        assert res.fallback
        oracle = asyncio.run(harness.run_local(prompt))["result"]
        assert harness.check_bytes(res, oracle)
        st = _fresh_counters.status()
        assert st["disagg_fallback_recomputes"] == 1
        assert st["disagg_wrong_bytes"] == 0
        ev = [
            e for e in telemetry.get_journal().snapshot()
            if e["kind"] == "disagg_fallback"
        ]
        # The kill window pins the failed layer past the durable prefix.
        assert ev and ev[0]["attrs"]["failed_layer"] >= 2
