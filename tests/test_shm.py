"""Same-host shm fast-path behavior: activation/fallback, server-side ticket
lifetime (pending blocks freed on disconnect), clean OOM (no payload drain
needed), and on-demand mapping of auto-extended pools.

The reference gets its zero-copy local path from GPUDirect RDMA (ibv_reg_mr on
CUDA pointers, reference infinistore/test_infinistore.py:120-122); on TPU
hosts the analogue is named-shm pools mapped into the client, and these are
the behaviors that differ from the socket path.
"""

import asyncio
import contextlib
import fcntl
import os
import re
import socket
import struct
import threading
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
# A put's copy rides the pool file's descriptor (docs/design.md, "A put's
# copy rides the pool's file"): ``pwritev`` at the location's offset, not a
# ``memcpy`` into the client's mapping, and no thread walks the pool. None of
# these reads a clock or a fault count: they read the connection's own ledger
# (``touch_stats``), the process's descriptors and threads, and the bytes.
# ---------------------------------------------------------------------------

MIB = 1 << 20
BLOCK = 64 << 10
ZERO_PUT = {
    "put_copy_bytes": 0, "put_touched_bytes": 0, "put_copy_us": 0, "pretouch_bytes": 0,
    "put_file_bytes": 0, "put_file_calls": 0, "get_file_bytes": 0,
}


def _put_conn(port: int, shm: bool = True) -> its.InfinityConnection:
    c = its.InfinityConnection(its.ClientConfig(
        host_addr="127.0.0.1", service_port=port, enable_shm=shm, log_level="error",
    ))
    c.connect()
    return c


def _put(c, prefix: str, data: np.ndarray):
    pairs = [(f"{prefix}-{i}", i * BLOCK) for i in range(data.nbytes // BLOCK)]
    c.write_cache(pairs, BLOCK, data.ctypes.data)
    return pairs


def _pool_fds(but=()) -> dict:
    """This process's descriptors on pool files (``/dev/shm/its.<pid>.<id>.<n>``;
    rings and client segments are named otherwise), those in ``but`` apart:
    fd -> path. The in-process server holds one a pool too."""
    out = {}
    for name in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{name}")
        except OSError:
            continue
        if re.fullmatch(r"/dev/shm/its\.\d+\.[0-9a-f]+\.\d+", target) and int(name) not in but:
            out[int(name)] = target
    return out


@contextlib.contextmanager
def _name_taken(path: str):
    """Holds the pool name an extension would take, so that the extension
    falls back to anonymous memory. Locked as a live segment is: another
    test's server sweeps unlocked ``its.*`` files away when it starts."""
    assert not os.path.exists(path)
    fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_RDWR, 0o600)
    fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
    try:
        yield
    finally:
        os.close(fd)
        with contextlib.suppress(FileNotFoundError):
            os.unlink(path)


def _threads() -> int:
    return len(os.listdir("/proc/self/task"))


def _reads_back(c, runs, data: np.ndarray):
    dst = np.zeros_like(data)
    c.register_mr(dst)
    for run in runs:
        dst[:] = 0
        c.read_cache(run, BLOCK, dst.ctypes.data)
        assert np.array_equal(dst, data)


def test_put_file_sequential_puts_read_back_exact():
    """Every byte of a run of puts went through the descriptor (the first put
    as well: there is nothing to warm), and every value reads back exact."""
    srv = its.start_local_server(prealloc_bytes=64 * MIB, block_bytes=16 << 10)
    c = _put_conn(srv.port)
    data = np.random.randint(0, 256, size=2 * MIB, dtype=np.uint8)
    c.register_mr(data)
    assert c.touch_stats() == ZERO_PUT
    runs = [_put(c, "first", data)]
    first = c.touch_stats()
    assert first["put_file_bytes"] == first["put_copy_bytes"] == data.nbytes
    runs += [_put(c, f"run{r}", data) for r in range(8)]
    after = c.touch_stats()
    assert after["put_file_bytes"] == after["put_copy_bytes"] == 9 * data.nbytes
    # What the accepted metric reads: no byte of a copy faulted on the reactor.
    assert after["put_touched_bytes"] == after["put_file_bytes"] and after["pretouch_bytes"] == 0
    _reads_back(c, runs, data)
    c.close()
    srv.stop()


def test_put_file_fetch_only_connection_copies_nothing_and_starts_no_thread():
    """A connection that maps the pools and only reads, and one that puts
    over the socket, copy nothing through a descriptor; a shm put starts no
    thread beside the connection's reactor."""
    srv = its.start_local_server(prealloc_bytes=32 * MIB, block_bytes=16 << 10)
    writer = _put_conn(srv.port)
    data = np.random.randint(0, 256, size=MIB, dtype=np.uint8)
    writer.register_mr(data)
    threads = _threads()
    pairs = _put(writer, "v", data)
    assert _threads() == threads
    reader, socket_writer = _put_conn(srv.port), _put_conn(srv.port, shm=False)
    assert reader.shm_active and not socket_writer.shm_active
    threads = _threads()
    for _ in range(3):
        _reads_back(reader, [pairs], data)
    socket_writer.register_mr(data)
    _put(socket_writer, "s", data)
    assert _threads() == threads
    # The reader's located gets came out through its descriptor; it put nothing.
    assert reader.touch_stats() == dict(ZERO_PUT, get_file_bytes=3 * data.nbytes)
    assert socket_writer.touch_stats() == ZERO_PUT
    for c in (writer, reader, socket_writer):
        c.close()
    srv.stop()


@pytest.mark.parametrize("puts", [False, True], ids=["no-put", "put"])
@pytest.mark.parametrize("how", ["close", "reconnect"])
def test_put_file_close_and_reconnect_close_every_descriptor(how, puts):
    """close() and reconnect(), after puts and without: fifty rounds leave
    the process with the descriptors it had (the server's own)."""
    pool = dict(prealloc_bytes=16 * MIB, block_bytes=16 << 10)
    others = _pool_fds()  # what earlier tests of this process left open
    srv = its.start_local_server(**pool)
    port = srv.port
    data = np.random.randint(0, 256, size=MIB, dtype=np.uint8)
    if how == "close":
        base = len(_pool_fds(others))
        for r in range(50):
            c = _put_conn(port)
            assert len(_pool_fds(others)) == base + 1
            if puts:
                c.register_mr(data)
                _put(c, f"a{r % 8}", data)
            c.close()
            assert len(_pool_fds(others)) == base
        srv.stop()
        return
    c = _put_conn(port)
    c.register_mr(data)
    for r in range(50):
        if puts:
            _put(c, "a", data)
        srv.stop()
        for _ in range(50):
            try:
                srv = its.start_local_server(host="127.0.0.1", service_port=port, **pool)
                break
            except its.InfiniStoreException:
                time.sleep(0.1)
        else:
            pytest.skip("could not rebind the port")
        with pytest.raises(its.InfiniStoreException):
            for _ in range(10):
                _put(c, "dead", data)
        c.reconnect()
        # A new handle: its ledger starts over; the old one's descriptor went
        # with its mapping (the server's and the new handle's are left).
        assert c.touch_stats() == ZERO_PUT
        assert len(_pool_fds(others)) == 2
    pairs = _put(c, "b", data)
    _reads_back(c, [pairs], data)
    c.close()
    assert len(_pool_fds(others)) == 1
    srv.stop()


def test_put_file_far_put_lands_where_the_allocator_says():
    """A put far from any earlier one (here: below them all, in space another
    connection freed) is a put like any other: counted, one call, exact."""
    srv = its.start_local_server(prealloc_bytes=64 * MIB, block_bytes=16 << 10)
    other, c = _put_conn(srv.port, shm=False), _put_conn(srv.port)
    filler = np.random.randint(0, 256, size=8 * MIB, dtype=np.uint8)
    other.register_mr(filler)
    held = _put(other, "filler", filler)
    data = np.random.randint(0, 256, size=MIB // 2, dtype=np.uint8)
    c.register_mr(data)
    high = _put(c, "high", data)  # past the filler
    other.delete_keys([k for k, _ in held])
    before = c.touch_stats()
    low = _put(c, "low", data)  # into the freed head of the pool
    after = c.touch_stats()
    assert after["put_file_bytes"] - before["put_file_bytes"] == data.nbytes
    assert after["put_file_calls"] - before["put_file_calls"] == 1
    _reads_back(c, [high, low], data)
    for conn in (other, c):
        conn.close()
    srv.stop()


def test_put_file_two_writers_of_one_pool_read_back_exact():
    """Two connections write the same pool file at the same time, a thread
    each: every value of either reads back exact through the other."""
    srv = its.start_local_server(prealloc_bytes=192 * MIB, block_bytes=16 << 10)
    a, b = _put_conn(srv.port), _put_conn(srv.port)
    da = np.random.randint(0, 256, size=8 * MIB, dtype=np.uint8)
    db = np.random.randint(0, 256, size=8 * MIB, dtype=np.uint8)
    a.register_mr(da)
    b.register_mr(db)
    runs = {"a": [], "b": []}

    def work(c, tag, data):
        runs[tag] = [_put(c, f"{tag}{r}", data) for r in range(8)]

    threads = [threading.Thread(target=work, args=args) for args in ((a, "a", da), (b, "b", db))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for c, data in ((a, da), (b, db)):
        stats = c.touch_stats()
        assert stats["put_file_bytes"] == stats["put_copy_bytes"] == 8 * data.nbytes
    _reads_back(a, runs["b"], db)
    _reads_back(b, runs["a"], da)
    for conn in (a, b):
        conn.close()
    srv.stop()


def test_put_file_auto_extended_pool_gets_its_own_descriptor():
    """Puts that spill into an extension pool map it on demand and keep its
    descriptor beside the first pool's; close() gives both back."""
    others = _pool_fds()
    srv = its.start_local_server(
        prealloc_bytes=8 * MIB, block_bytes=16 << 10, auto_increase=True,
        extend_bytes=16 * MIB,
    )
    c = _put_conn(srv.port)
    assert len(_pool_fds(others)) == 2  # the server's and the connection's
    data = np.random.randint(0, 256, size=6 * MIB, dtype=np.uint8)
    c.register_mr(data)
    runs = [_put(c, f"x{r}", data) for r in range(3)]  # 18 MiB on 8 + 16
    stats = c.touch_stats()
    assert stats["put_file_bytes"] == stats["put_copy_bytes"] == 3 * data.nbytes
    assert len(_pool_fds(others)) == 4 and len(set(_pool_fds(others).values())) == 2
    _reads_back(c, runs, data)
    c.close()
    assert len(_pool_fds(others)) == 2
    srv.stop()


def test_put_into_an_anonymous_pool_rides_the_socket_path():
    """An extension pool that could not be a file (its name is taken) is
    anonymous memory: it has no descriptor, the server answers a put that
    lands there with a retry, and the put goes over the socket, whole."""
    others = _pool_fds()
    srv = its.start_local_server(
        prealloc_bytes=8 * MIB, block_bytes=16 << 10, auto_increase=True,
        extend_bytes=16 * MIB,
    )
    (first,) = set(_pool_fds(others).values())
    assert first.endswith(".0")
    with _name_taken(first[: -len(".0")] + ".1"):  # the name the extension would take
        c = _put_conn(srv.port)
        data = np.random.randint(0, 256, size=6 * MIB, dtype=np.uint8)
        c.register_mr(data)
        runs = [_put(c, "x0", data)]  # fits the mapped pool: through its file
        assert c.touch_stats()["put_file_bytes"] == data.nbytes and c.shm_active
        held = _pool_fds(others)
        runs += [_put(c, f"x{r}", data) for r in (1, 2)]  # spills: over the socket
        stats = c.touch_stats()
        assert stats["put_file_bytes"] == stats["put_copy_bytes"] == data.nbytes
        assert not c.shm_active and _pool_fds(others) == held  # no descriptor more
        _reads_back(c, runs, data)
        c.close()
    srv.stop()


@pytest.mark.parametrize("op", ["put", "get"])
def test_put_file_failed_transfer_aborts_the_ticket_and_falls_back(op):
    """A ``pwritev`` / ``preadv`` that fails (the test swaps the connection's
    descriptor for one not open that way) publishes and completes nothing:
    the ticket is released, the op is sent again over the socket, and the
    value reads back whole."""
    from infinistore_tpu._native import lib

    srv = its.start_local_server(prealloc_bytes=32 * MIB, block_bytes=16 << 10)
    before = _pool_fds()
    c = _put_conn(srv.port)
    (fd,) = set(_pool_fds()) - set(before)
    data = np.random.randint(0, 256, size=MIB, dtype=np.uint8)
    c.register_mr(data)
    runs = [_put(c, "ok", data)]
    null = os.open("/dev/null", os.O_RDONLY if op == "put" else os.O_WRONLY)
    os.dup2(null, fd)  # same number, so no other file can take it meanwhile
    os.close(null)
    if op == "put":
        runs.append(_put(c, "failed", data))
    else:
        _reads_back(c, runs, data)
    stats = c.touch_stats()
    assert stats["put_file_bytes"] == stats["put_copy_bytes"] == data.nbytes  # the first put's
    assert stats["put_file_calls"] == (2 if op == "put" else 1) and stats["get_file_bytes"] == 0
    assert not c.shm_active
    runs.append(_put(c, "after", data))  # the socket path from here on
    assert c.touch_stats()["put_file_calls"] == stats["put_file_calls"]
    _reads_back(c, runs, data)
    # Nothing of the aborted ticket is left pinned on the server.
    assert lib.its_server_kvmap_len(srv.handle) == len(runs) * len(runs[0])
    for _ in range(100):  # the get's release is fire-and-forget
        if lib.its_server_usage(srv.handle) == len(runs) * data.nbytes / (32 * MIB):
            break
        time.sleep(0.01)
    assert lib.its_server_usage(srv.handle) == len(runs) * data.nbytes / (32 * MIB)
    c.close()
    srv.stop()


def test_put_file_contiguous_values_go_out_as_one_call():
    """A put's values that lie side by side in the pool go out as ONE
    vectored call whatever their number; values scattered over holes take a
    call a run."""
    srv = its.start_local_server(prealloc_bytes=64 * MIB, block_bytes=16 << 10)
    other, c = _put_conn(srv.port, shm=False), _put_conn(srv.port)
    data = np.random.randint(0, 256, size=8 * MIB, dtype=np.uint8)  # 128 values
    other.register_mr(data)
    c.register_mr(data)
    held = _put(other, "filler", data)
    runs = [_put(c, "side-by-side", data)]
    assert c.touch_stats()["put_file_calls"] == 1
    # Free every other value of the filler: 64 holes of one value each.
    other.delete_keys([k for k, _ in held[::2]])
    half = data[: 4 * MIB]
    before = c.touch_stats()
    holes = _put(c, "holes", half)
    after = c.touch_stats()
    assert after["put_file_bytes"] - before["put_file_bytes"] == half.nbytes
    assert after["put_file_calls"] - before["put_file_calls"] == len(holes)
    _reads_back(c, runs, data)
    _reads_back(c, [holes], half)
    for conn in (other, c):
        conn.close()
    srv.stop()


# ---------------------------------------------------------------------------
# The reads' copies ride the descriptor too: a located get (``GetLoc``, into
# a plain buffer) is the client's ``preadv``; a get into a client segment
# (``GetInto``) is the SERVER's, out of its own descriptor of the pool.
# ---------------------------------------------------------------------------


def test_located_gets_read_through_the_descriptor():
    """``get_file_bytes`` counts every byte a plain-buffer read copied out."""
    srv = its.start_local_server(prealloc_bytes=32 * MIB, block_bytes=16 << 10)
    writer, reader = _put_conn(srv.port), _put_conn(srv.port)
    data = np.random.randint(0, 256, size=2 * MIB, dtype=np.uint8)
    writer.register_mr(data)
    runs = [_put(writer, f"v{r}", data) for r in range(3)]
    _reads_back(reader, runs, data)
    assert reader.touch_stats()["get_file_bytes"] == 3 * data.nbytes
    assert writer.touch_stats()["get_file_bytes"] == 0
    for c in (writer, reader):
        c.close()
    srv.stop()


def test_get_into_reads_through_the_servers_descriptor():
    """A read into an ``alloc_shm_mr`` buffer is the server's copy: out of
    the pool file's descriptor, into slots that lie in another order than the
    values do in the pool; the server counts the bytes (``get_into_file_bytes``)."""
    srv = its.start_local_server(prealloc_bytes=32 * MIB, block_bytes=16 << 10)
    c = _put_conn(srv.port)
    data = np.random.randint(0, 256, size=2 * MIB, dtype=np.uint8)
    c.register_mr(data)
    pairs = _put(c, "v", data)  # the two-phase put: no mapping touched the pages
    buf = c.alloc_shm_mr(data.nbytes)
    n = len(pairs)
    buf[:] = 0
    back = [(key, (n - 1 - i) * BLOCK) for i, (key, _) in enumerate(pairs)]
    c.read_cache(back, BLOCK, buf.ctypes.data)
    assert np.array_equal(buf.reshape(n, BLOCK)[::-1].reshape(-1), data)
    buf[:] = 0
    c.read_cache(pairs, BLOCK, buf.ctypes.data)  # and side by side, as they lie
    assert np.array_equal(buf, data)
    stats = c.get_stats()
    assert stats["ops"]["I"]["count"] == 2 and stats["get_into_file_bytes"] == 2 * data.nbytes
    assert c.touch_stats()["get_file_bytes"] == 0  # the client copied nothing
    c.close()
    srv.stop()


def test_get_into_from_an_anonymous_pool_goes_through_the_mapping():
    """Blocks of a pool that is no file have no descriptor: the server copies
    them out of its memory as before, and counts only the file's bytes."""
    others = _pool_fds()
    srv = its.start_local_server(
        prealloc_bytes=8 * MIB, block_bytes=16 << 10, auto_increase=True,
        extend_bytes=16 * MIB,
    )
    (first,) = set(_pool_fds(others).values())
    with _name_taken(first[: -len(".0")] + ".1"):
        c = _put_conn(srv.port)
        buf = c.alloc_shm_mr(6 * MIB)
        data = np.random.randint(0, 256, size=6 * MIB, dtype=np.uint8)
        runs = []
        for r in range(3):  # 18 MiB on 8 + 16: the segment's puts spill too
            buf[:] = data
            runs.append(_put(c, f"x{r}", buf))
        for run in runs:
            buf[:] = 0
            c.read_cache(run, BLOCK, buf.ctypes.data)
            assert np.array_equal(buf, data)
        read = c.get_stats()["get_into_file_bytes"]
        assert 0 < read < 3 * data.nbytes and read % BLOCK == 0
        c.close()
    srv.stop()
