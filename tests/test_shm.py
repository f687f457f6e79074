"""Same-host shm fast-path behavior: activation/fallback, server-side ticket
lifetime (pending blocks freed on disconnect), clean OOM (no payload drain
needed), and on-demand mapping of auto-extended pools.

The reference gets its zero-copy local path from GPUDirect RDMA (ibv_reg_mr on
CUDA pointers, reference infinistore/test_infinistore.py:120-122); on TPU
hosts the analogue is named-shm pools mapped into the client, and these are
the behaviors that differ from the socket path.
"""

import asyncio
import socket
import struct
import time

import numpy as np
import pytest

import infinistore_tpu as its
from infinistore_tpu import wire


def _connect_raw(port: int) -> socket.socket:
    s = socket.create_connection(("127.0.0.1", port))
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return s


def _roundtrip(sock: socket.socket, op: int, body: bytes):
    sock.sendall(wire.pack_req_header(op, len(body)) + body)
    hdr = b""
    while len(hdr) < 16:
        hdr += sock.recv(16 - len(hdr))
    status, body_size, payload_size = wire.unpack_resp_header(hdr)
    resp = b""
    while len(resp) < body_size:
        resp += sock.recv(body_size - len(resp))
    return status, resp, payload_size


def test_shm_active_matches_server_capability():
    srv = its.start_local_server(prealloc_bytes=16 << 20, block_bytes=16 << 10)
    c = its.InfinityConnection(
        its.ClientConfig(host_addr="127.0.0.1", service_port=srv.port, log_level="error")
    )
    c.connect()
    assert c.shm_active is True
    c.close()
    srv.stop()

    # Server with shm disabled -> client degrades to the socket path.
    srv2 = its.start_local_server(
        prealloc_bytes=16 << 20, block_bytes=16 << 10, enable_shm=False
    )
    c2 = its.InfinityConnection(
        its.ClientConfig(host_addr="127.0.0.1", service_port=srv2.port, log_level="error")
    )
    c2.connect()
    assert c2.shm_active is False
    data = (np.arange(16 << 10) % 256).astype(np.uint8)
    dst = np.zeros_like(data)
    c2.register_mr(data)
    c2.register_mr(dst)
    asyncio.run(c2.write_cache_async([("sk", 0)], data.nbytes, data.ctypes.data))
    asyncio.run(c2.read_cache_async([("sk", 0)], data.nbytes, dst.ctypes.data))
    assert np.array_equal(data, dst)
    c2.close()
    srv2.stop()


def test_pending_put_blocks_freed_on_disconnect():
    """PutAlloc without commit pins pool blocks in the connection's ticket
    table; dropping the connection must free them (the reference analogue:
    inflight RDMA state dies with the Client struct, infinistore.cpp:967-988)."""
    srv = its.start_local_server(prealloc_bytes=16 << 20, block_bytes=16 << 10)
    from infinistore_tpu._native import lib

    assert lib.its_server_usage(srv.handle) == 0.0
    s = _connect_raw(srv.port)
    body = wire.BatchMeta(block_size=16 << 10, keys=[f"pend-{i}" for i in range(64)]).encode()
    status, resp, _ = _roundtrip(s, wire.OP_PUT_ALLOC, body)
    assert status == wire.STATUS_OK
    parsed = wire.ShmLocResp.decode(resp)
    assert len(parsed.locs) == 64
    assert len(parsed.pools) >= 1
    assert parsed.ticket != 0
    # 64 x 16KB pinned by the ticket, never committed.
    assert lib.its_server_usage(srv.handle) > 0.0
    assert lib.its_server_kvmap_len(srv.handle) == 0
    s.close()
    deadline = time.time() + 5
    while time.time() < deadline and lib.its_server_usage(srv.handle) > 0.0:
        time.sleep(0.05)
    assert lib.its_server_usage(srv.handle) == 0.0
    srv.stop()


def test_shm_oom_is_immediate_507():
    """On the shm path OOM needs no payload drain: the 507 comes back before
    any data moves, and the connection stays usable."""
    srv = its.start_local_server(prealloc_bytes=8 << 20, block_bytes=16 << 10)
    c = its.InfinityConnection(
        its.ClientConfig(host_addr="127.0.0.1", service_port=srv.port, log_level="error")
    )
    c.connect()
    assert c.shm_active
    big = np.zeros(16 << 20, dtype=np.uint8)
    c.register_mr(big)
    with pytest.raises(its.InfiniStoreException):
        asyncio.run(c.write_cache_async([("big", 0)], big.nbytes, big.ctypes.data))
    small = np.ones(4096, dtype=np.uint8)
    dst = np.zeros_like(small)
    c.register_mr(small)
    c.register_mr(dst)
    asyncio.run(c.write_cache_async([("ok", 0)], 4096, small.ctypes.data))
    asyncio.run(c.read_cache_async([("ok", 0)], 4096, dst.ctypes.data))
    assert np.array_equal(small, dst)
    c.close()
    srv.stop()


def test_stale_segment_sweep_spares_live_pools():
    """Startup sweep unlinks orphaned its.* segments (flock released = owner
    dead) but must not touch a running server's pools."""
    import os

    # Plant a fake orphan: nobody holds a lock on it.
    orphan = f"/its.999999.deadbeef.0"
    path = "/dev/shm" + orphan
    with open(path, "wb") as f:
        f.write(b"\0" * 4096)
    live = its.start_local_server(prealloc_bytes=8 << 20, block_bytes=16 << 10)
    try:
        # A second server's MM constructor runs the sweep.
        other = its.start_local_server(prealloc_bytes=8 << 20, block_bytes=16 << 10)
        other.stop()
        assert not os.path.exists(path), "orphan segment not swept"
        # The live server's pools survived: a client can still use them.
        c = its.InfinityConnection(
            its.ClientConfig(host_addr="127.0.0.1", service_port=live.port, log_level="error")
        )
        c.connect()
        assert c.shm_active
        data = np.ones(4096, dtype=np.uint8)
        dst = np.zeros_like(data)
        c.register_mr(data)
        c.register_mr(dst)
        asyncio.run(c.write_cache_async([("live", 0)], 4096, data.ctypes.data))
        asyncio.run(c.read_cache_async([("live", 0)], 4096, dst.ctypes.data))
        assert np.array_equal(data, dst)
        c.close()
    finally:
        live.stop()
        if os.path.exists(path):
            os.unlink(path)


def test_auto_extend_pool_mapped_on_demand():
    """Writes spilling into an auto-extended pool must reach the client via
    the directory embedded in responses — no re-handshake."""
    srv = its.start_local_server(
        prealloc_bytes=8 << 20,
        block_bytes=16 << 10,
        auto_increase=True,
        extend_bytes=16 << 20,
    )
    c = its.InfinityConnection(
        its.ClientConfig(host_addr="127.0.0.1", service_port=srv.port, log_level="error")
    )
    c.connect()
    assert c.shm_active
    n, block = 512, 16 << 10  # 8MB of data on an 8MB pool -> must extend
    src = np.random.randint(0, 256, size=n * block, dtype=np.uint8)
    dst = np.zeros_like(src)
    c.register_mr(src)
    c.register_mr(dst)
    pairs = [(f"x-{i}", i * block) for i in range(n)]
    asyncio.run(c.write_cache_async(pairs, block, src.ctypes.data))
    asyncio.run(c.read_cache_async(pairs, block, dst.ctypes.data))
    assert np.array_equal(src, dst)
    c.close()
    srv.stop()


def test_alloc_shm_mr_one_rtt_roundtrip():
    """alloc_shm_mr returns a server-mapped staging buffer, and batched ops on
    it ride the one-RTT PutFrom/GetInto path (the shm analogue of the
    reference's one-sided RDMA against registered client memory,
    reference src/infinistore.cpp:558-595) — verified via op counters."""
    srv = its.start_local_server(prealloc_bytes=32 << 20, block_bytes=16 << 10)
    c = its.InfinityConnection(
        its.ClientConfig(host_addr="127.0.0.1", service_port=srv.port, log_level="error")
    )
    c.connect()
    assert c.shm_active
    n, block = 16, 16 << 10
    buf = c.alloc_shm_mr(n * block)
    assert buf is not None and buf.nbytes == n * block
    src = np.random.randint(0, 256, size=n * block, dtype=np.uint8)
    buf[:] = src
    pairs = [(f"seg-{i}", i * block) for i in range(n)]
    asyncio.run(c.write_cache_async(pairs, block, buf.ctypes.data))
    buf[:] = 0
    asyncio.run(c.read_cache_async(pairs, block, buf.ctypes.data))
    assert np.array_equal(buf, src)
    ops = c.get_stats()["ops"]
    assert ops.get("F", {}).get("count", 0) >= 1  # PutFrom
    assert ops.get("I", {}).get("count", 0) >= 1  # GetInto
    c.close()
    srv.stop()


def test_alloc_shm_mr_declined_falls_back():
    """A shm-less server declines RegSegment; the buffer stays usable as a
    plain registered region and batched ops ride the socket path ('W'/'R'
    op counters, not 'F'/'I')."""
    srv = its.start_local_server(
        prealloc_bytes=16 << 20, block_bytes=16 << 10, enable_shm=False
    )
    c = its.InfinityConnection(
        its.ClientConfig(host_addr="127.0.0.1", service_port=srv.port, log_level="error")
    )
    c.connect()
    assert not c.shm_active
    block = 16 << 10
    buf = c.alloc_shm_mr(2 * block)
    assert buf is not None
    src = np.random.randint(0, 256, size=2 * block, dtype=np.uint8)
    buf[:] = src
    pairs = [("d-0", 0), ("d-1", block)]
    asyncio.run(c.write_cache_async(pairs, block, buf.ctypes.data))
    buf[:] = 0
    asyncio.run(c.read_cache_async(pairs, block, buf.ctypes.data))
    assert np.array_equal(buf, src)
    ops = c.get_stats()["ops"]
    assert ops.get("W", {}).get("count", 0) >= 1
    assert "F" not in ops and "I" not in ops
    c.close()
    srv.stop()


def test_reg_segment_rejects_undersized_shm(tmp_path):
    """The server must fstat a client-declared segment and refuse to map past
    tmpfs EOF — an undersized segment would SIGBUS the reactor on first use."""
    import os

    srv = its.start_local_server(prealloc_bytes=16 << 20, block_bytes=16 << 10)
    name = f"/its.{os.getpid()}.feedf00d.t"
    path = "/dev/shm" + name
    with open(path, "wb") as f:
        f.truncate(4096)  # claims 1MB below but backs only 4KB
    try:
        s = _connect_raw(srv.port)
        body = wire.SegMeta(seg_id=7, name=name, size=1 << 20).encode()
        status, _, _ = _roundtrip(s, wire.OP_REG_SEGMENT, body)
        assert status != wire.STATUS_OK
        # A non-its-prefixed name must be refused outright.
        with open("/dev/shm/evil.seg", "wb") as f:
            f.truncate(1 << 20)
        body = wire.SegMeta(seg_id=8, name="/evil.seg", size=1 << 20).encode()
        status, _, _ = _roundtrip(s, wire.OP_REG_SEGMENT, body)
        assert status != wire.STATUS_OK
        s.close()
    finally:
        for p in (path, "/dev/shm/evil.seg"):
            if os.path.exists(p):
                os.unlink(p)
        srv.stop()


@pytest.mark.parametrize("shm", [True, False], ids=["shm", "socket"])
def test_get_with_smaller_block_size_errors_cleanly(shm):
    """Reading a key back with a block_size smaller than the stored block
    must fail with a typed error — never scatter past the caller's slot —
    and leave the connection usable (both data planes)."""
    srv = its.start_local_server(
        prealloc_bytes=16 << 20, block_bytes=32 << 10, enable_shm=shm
    )
    c = its.InfinityConnection(
        its.ClientConfig(host_addr="127.0.0.1", service_port=srv.port, log_level="error")
    )
    c.connect()
    big = np.random.randint(0, 256, size=32 << 10, dtype=np.uint8)
    c.register_mr(big)
    asyncio.run(c.write_cache_async([("over", 0)], big.nbytes, big.ctypes.data))
    # Guard pages: canary after the undersized slot must survive the get.
    dst = np.zeros(32 << 10, dtype=np.uint8)
    dst[16 << 10 :] = 0xAB
    c.register_mr(dst)
    with pytest.raises(its.InfiniStoreException):
        asyncio.run(c.read_cache_async([("over", 0)], 16 << 10, dst.ctypes.data))
    assert np.all(dst[16 << 10 :] == 0xAB)
    # Connection stays usable.
    full = np.zeros(32 << 10, dtype=np.uint8)
    c.register_mr(full)
    asyncio.run(c.read_cache_async([("over", 0)], 32 << 10, full.ctypes.data))
    assert np.array_equal(full, big)
    c.close()
    srv.stop()


# ---------------------------------------------------------------------------
# The put pre-touch (docs/design.md, "Who faults on a put"): one thread a
# connection, started by its first shm put, keeps the pool touched ahead of
# the puts. None of these reads a clock or a fault count: they read the
# connection's own ledger (``touch_stats``) and the bytes.
# ---------------------------------------------------------------------------

MIB = 1 << 20
BLOCK = 64 << 10
ZERO_TOUCH = {"put_copy_bytes": 0, "put_touched_bytes": 0, "put_copy_us": 0, "pretouch_bytes": 0}


def _touch_conn(port: int, shm: bool = True) -> its.InfinityConnection:
    c = its.InfinityConnection(its.ClientConfig(
        host_addr="127.0.0.1", service_port=port, enable_shm=shm, log_level="error",
    ))
    c.connect()
    return c


def _put(c, prefix: str, data: np.ndarray):
    pairs = [(f"{prefix}-{i}", i * BLOCK) for i in range(data.nbytes // BLOCK)]
    c.write_cache(pairs, BLOCK, data.ctypes.data)
    return pairs


def _walked(c, at_least: int) -> int:
    """Polls until the touch thread has walked ``at_least`` bytes."""
    for _ in range(4000):
        walked = c.touch_stats()["pretouch_bytes"]
        if walked >= at_least:
            return walked
        time.sleep(0.005)
    raise AssertionError(f"the touch thread walked {walked} of {at_least} bytes")


def test_pretouch_sequential_puts_land_touched():
    """The first put is cold and starts the thread; once it has walked ahead,
    a run of sequential puts lands on touched chunks."""
    srv = its.start_local_server(prealloc_bytes=64 * MIB, block_bytes=16 << 10)
    c = _touch_conn(srv.port)
    data = np.random.randint(0, 256, size=2 * MIB, dtype=np.uint8)
    c.register_mr(data)
    assert c.touch_stats() == ZERO_TOUCH
    _put(c, "first", data)
    first = c.touch_stats()
    assert first["put_copy_bytes"] == data.nbytes
    _walked(c, 32 * MIB)
    pairs = [_put(c, f"run{r}", data) for r in range(8)]
    after = c.touch_stats()
    put = after["put_copy_bytes"] - first["put_copy_bytes"]
    warm = after["put_touched_bytes"] - first["put_touched_bytes"]
    assert put == 8 * data.nbytes
    assert warm >= 0.9 * put
    dst = np.zeros_like(data)
    c.register_mr(dst)
    for run in pairs:
        c.read_cache(run, BLOCK, dst.ctypes.data)
        assert np.array_equal(dst, data)
    c.close()
    srv.stop()


def test_pretouch_fetch_only_connection_starts_no_thread():
    """A connection that maps the pools and only reads, and one that puts
    over the socket, walk nothing: the thread belongs to the first shm put."""
    srv = its.start_local_server(prealloc_bytes=32 * MIB, block_bytes=16 << 10)
    writer = _touch_conn(srv.port)
    data = np.random.randint(0, 256, size=MIB, dtype=np.uint8)
    writer.register_mr(data)
    pairs = _put(writer, "v", data)
    reader, socket_writer = _touch_conn(srv.port), _touch_conn(srv.port, shm=False)
    assert reader.shm_active and not socket_writer.shm_active
    dst = np.zeros_like(data)
    reader.register_mr(dst)
    for _ in range(3):
        reader.read_cache(pairs, BLOCK, dst.ctypes.data)
    assert np.array_equal(dst, data)
    socket_writer.register_mr(data)
    _put(socket_writer, "s", data)
    _walked(writer, 16 * MIB)  # time enough for a thread that should not be
    assert reader.touch_stats() == ZERO_TOUCH and socket_writer.touch_stats() == ZERO_TOUCH
    for c in (writer, reader, socket_writer):
        c.close()
    srv.stop()


@pytest.mark.parametrize("puts", [False, True], ids=["no-thread", "thread"])
@pytest.mark.parametrize("how", ["close", "reconnect"])
def test_pretouch_close_and_reconnect(how, puts):
    """close() and reconnect() with and without a started thread; with one,
    close() lands while it is mid-walk (a 256 MiB pool, closed at once)."""
    srv = its.start_local_server(prealloc_bytes=256 * MIB, block_bytes=16 << 10)
    port = srv.port
    c = _touch_conn(port)
    data = np.random.randint(0, 256, size=MIB, dtype=np.uint8)
    c.register_mr(data)
    if puts:
        _put(c, "a", data)
    if how == "reconnect":
        srv.stop()
        for _ in range(50):
            try:
                srv = its.start_local_server(
                    host="127.0.0.1", service_port=port,
                    prealloc_bytes=256 * MIB, block_bytes=16 << 10,
                )
                break
            except its.InfiniStoreException:
                time.sleep(0.1)
        else:
            pytest.skip("could not rebind the port")
        with pytest.raises(its.InfiniStoreException):
            for _ in range(10):
                _put(c, "dead", data)
        c.reconnect()
        # A new handle: its ledger starts over, and its first put its thread.
        assert c.touch_stats()["pretouch_bytes"] == 0
        pairs = _put(c, "b", data)
        dst = np.zeros_like(data)
        c.register_mr(dst)
        c.read_cache(pairs, BLOCK, dst.ctypes.data)
        assert np.array_equal(dst, data)
    c.close()
    srv.stop()


def test_pretouch_far_put_moves_frontier():
    """A put that lands outside everything touched (here: below the frontier,
    in space another connection freed) is a cold put that succeeds and moves
    the frontier there; nothing depends on where the allocator puts it."""
    srv = its.start_local_server(prealloc_bytes=64 * MIB, block_bytes=16 << 10)
    other, c = _touch_conn(srv.port, shm=False), _touch_conn(srv.port)
    filler = np.random.randint(0, 256, size=8 * MIB, dtype=np.uint8)
    other.register_mr(filler)
    held = _put(other, "filler", filler)
    data = np.random.randint(0, 256, size=MIB // 2, dtype=np.uint8)
    c.register_mr(data)
    _put(c, "high", data)  # past the filler: the frontier starts there
    walked = _walked(c, 56 * MIB)  # chunks 8..63: everything past the put
    other.delete_keys([k for k, _ in held])
    before = c.touch_stats()
    pairs = _put(c, "low", data)  # into the freed head of the pool
    after = c.touch_stats()
    assert after["put_copy_bytes"] - before["put_copy_bytes"] == data.nbytes
    assert after["put_touched_bytes"] == before["put_touched_bytes"]  # cold
    _walked(c, walked + 8 * MIB)  # the frontier moved: the head is walked now
    before = c.touch_stats()
    _put(c, "low2", data)
    assert c.touch_stats()["put_touched_bytes"] - before["put_touched_bytes"] == data.nbytes
    dst = np.zeros_like(data)
    c.register_mr(dst)
    c.read_cache(pairs, BLOCK, dst.ctypes.data)
    assert np.array_equal(dst, data)
    for conn in (other, c):
        conn.close()
    srv.stop()


def test_pretouch_changes_no_byte_under_a_concurrent_writer():
    """A second connection writes the pages the first one's thread is walking
    (and its own thread walks them too): every value reads back exact."""
    srv = its.start_local_server(prealloc_bytes=192 * MIB, block_bytes=16 << 10)
    a, b = _touch_conn(srv.port), _touch_conn(srv.port)
    spark = np.random.randint(0, 256, size=BLOCK, dtype=np.uint8)
    a.register_mr(spark)
    data = np.random.randint(0, 256, size=16 * MIB, dtype=np.uint8)
    b.register_mr(data)
    _put(a, "spark", spark)  # a's thread starts walking the 192 MiB from here
    runs = [_put(b, f"w{r}", data) for r in range(8)]  # b writes 128 MiB of them
    assert a.touch_stats()["pretouch_bytes"] > 0 and b.touch_stats()["pretouch_bytes"] > 0
    _walked(a, 160 * MIB)
    dst = np.zeros_like(data)
    a.register_mr(dst)
    for run in runs:
        dst[:] = 0
        a.read_cache(run, BLOCK, dst.ctypes.data)
        assert np.array_equal(dst, data)
    for conn in (a, b):
        conn.close()
    srv.stop()


def test_pretouch_walks_an_auto_extended_pool():
    """Puts that spill into an extension pool start a frontier there: the
    thread walks more than the first pool holds."""
    srv = its.start_local_server(
        prealloc_bytes=8 * MIB, block_bytes=16 << 10, auto_increase=True,
        extend_bytes=16 * MIB,
    )
    c = _touch_conn(srv.port)
    data = np.random.randint(0, 256, size=6 * MIB, dtype=np.uint8)
    c.register_mr(data)
    runs = [_put(c, f"x{r}", data) for r in range(3)]  # 18 MiB on 8 + 16
    stats = c.touch_stats()
    assert stats["put_copy_bytes"] == 3 * data.nbytes
    _walked(c, 10 * MIB)
    dst = np.zeros_like(data)
    c.register_mr(dst)
    for run in runs:
        c.read_cache(run, BLOCK, dst.ctypes.data)
        assert np.array_equal(dst, data)
    c.close()
    srv.stop()
