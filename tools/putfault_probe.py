#!/usr/bin/env python3
"""Who pays the first touch of a pool page, and what a copy through the pool
file's descriptor costs instead: the probe behind the put path
(docs/design.md, "A put's copy rides the pool's file").

The shm put is two-phase: the CLIENT copies the payload into pool pages. Up
to PR 43 it did so through its own ``MAP_SHARED`` mapping of the server's
segment, and a store through a mapping costs that mapping a fault a page the
first time; since PR 44 it copies with ``pwritev`` on the segment's
descriptor, which touches no mapping. This probe reads, on the host it runs
on and for the native client it is run against, what that copy costs by who
has touched the pages before, and a few facts about the kernel. One JSON
line on stdout; no JAX, no chip. The server runs in its own process, as
``benchmarks/run.py`` starts it (``--no-pin-memory``), and once more pinned.

``put_gbps`` (each a list, one rate a put, in put order):

``untouched``       a fresh shm connection's puts to pages nobody touched.
``client_touched``  the same keys deleted and put again by the same
                    connection: pages its own mapping has touched, where
                    the copy goes through a mapping.
``server_touched``  pages filled over the socket path (``enable_shm`` off:
                    the SERVER copies), deleted, put again by a NEW shm
                    connection, far from anything that connection put.
``server_touched_pinned``  the same against a server with its default,
                    pinned pool (``pinned`` says whether ``mlock`` held).

``get_gbps``: a fresh connection's first and second read of the same values,
through ``GetLoc`` (a plain buffer: the client copies out, through its
mapping up to PR 43, with ``preadv`` on the descriptor since) and through
``GetInto`` (an ``alloc_shm_mr`` buffer: the server copies out, likewise).

``get_gbps`` also holds ``pread_first`` / ``pread_second``: the same bytes
read with ``pread`` from the pool FILE by a process that never mapped it,
which is what a server that read a value through its descriptor would pay.

``segment``: the kernel alone, on a ``posix_fallocate``'d shm segment a child
process holds too: the rate of a copy into untouched pages, pages this
mapping READ first, pages it wrote first, pages ``MADV_POPULATE_WRITE`` took;
``pwrite_untouched`` / ``pwrite_again`` / ``pread``: the same copy through the
segment's DESCRIPTOR, to pages no mapping of this process ever touched.

``put_by_value_gbps`` (since the copy rides the pool's file; docs/design.md,
"A put's copy rides the pool's file"): the two-phase put's rate by VALUE size
(``--value-kib``, one entry a size), each put ``--put-mib`` of values, ``cold``
(pages nobody touched) and ``warm`` (the same keys deleted and put again by
the same connection), by ``one`` writer and by ``two`` (two connections, a
thread each, putting side by side; the rate is the two together, and two
writers of one pool file serialise on its inode inside ``pwrite``).

``stall`` (the hit's read that stood still, without a model): ONE connection,
as a cell's engine has; a thread loops a ``get`` of ``--stall-read-mib`` that
has been read twice, stamping each; meanwhile the connection puts
``--stall-mib`` of fresh values in ``--put-mib`` puts, and the loop runs on
for ``--stall-tail-s`` after the last acknowledgement. ``read_ms`` holds the
reads' count, p50 and max ``during`` the puts and ``after`` them; ``put_gbps``
the puts' rate together; ``counters`` the connection's ``touch_stats()`` at the
end of the puts and at the end of the tail.
"""

import argparse
import copy
import ctypes
import json
import mmap
import os
import resource
import socket
import statistics
import subprocess
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MADV_POPULATE_WRITE = 23


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def start_server(pool_gib: int, unit_kib: int, pin: bool):
    service = free_port()
    argv = [
        sys.executable, "-m", "infinistore_tpu.server", "--host", "127.0.0.1",
        "--service-port", str(service), "--manage-port", str(free_port()),
        "--prealloc-size", str(pool_gib), "--minimal-allocate-size", str(unit_kib),
        "--log-level", "error",
    ] + ([] if pin else ["--no-pin-memory"])
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(argv, cwd=REPO, env=env)
    deadline = time.time() + 120
    while time.time() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(f"the store server exited with {proc.returncode}")
        try:
            with socket.create_connection(("127.0.0.1", service), timeout=0.3):
                return proc, service
        except OSError:
            time.sleep(0.05)
    proc.kill()
    raise RuntimeError("the store server did not come up in 120 s")


def stop_server(proc):
    if proc.poll() is None:
        proc.send_signal(2)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
    proc.wait()


class Values:
    """``puts`` batches of ``blocks`` keys of ``block`` bytes under one prefix."""

    def __init__(self, prefix: str, puts: int, blocks: int, block: int):
        self.block, self.nbytes = block, blocks * block
        self.batches = [
            [(f"{prefix}.{p}.{b}", b * block) for b in range(blocks)] for p in range(puts)
        ]

    def keys(self):
        return [k for batch in self.batches for k, _ in batch]

    def part(self, lo: int, hi: int) -> "Values":
        out = copy.copy(self)
        out.batches = self.batches[lo:hi]
        return out


def connect(port: int, shm: bool):
    import infinistore_tpu as its

    conn = its.InfinityConnection(its.ClientConfig(
        host_addr="127.0.0.1", service_port=port, enable_shm=shm, log_level="error",
    ))
    conn.connect()
    return conn


def timed(call, values: Values, buf: np.ndarray):
    """GB/s of each batch of ``values`` through ``call`` (write_cache / read_cache)."""
    rates = []
    for batch in values.batches:
        t0 = time.perf_counter()
        call(batch, values.block, buf.ctypes.data)
        rates.append(values.nbytes / (time.perf_counter() - t0) / 1e9)
    return rates


def store_rates(args, pin: bool, src: np.ndarray):
    """The put rates by who touched the pages (and, unpinned, the get rates)."""
    proc, port = start_server(args.pool_gib, args.unit_kib, pin)
    out = {}
    try:
        shape = (args.puts, args.blocks, args.block_kib << 10)
        a, b = Values("a", *shape), Values("b", *shape)
        first = connect(port, shm=True)
        first.register_mr(src)
        out["shm"] = bool(first.shm_active)
        out["pinned"] = bool(first.get_stats().get("pinned"))
        if not pin:
            out["untouched"] = timed(first.write_cache, a, src)
            first.delete_keys(a.keys())
            out["client_touched"] = timed(first.write_cache, a, src)
        else:
            timed(first.write_cache, a, src)  # holds the pool's head, as above
        # Region b: the server's copy touches it, then a fresh mapping puts there.
        sock = connect(port, shm=False)
        sock.register_mr(src)
        out["socket"] = timed(sock.write_cache, b, src)
        sock.delete_keys(b.keys())
        sock.close()
        fresh = connect(port, shm=True)
        fresh.register_mr(src)
        out["server_touched"] = timed(fresh.write_cache, b, src)
        fresh.close()
        if not pin:
            out["get"] = get_rates(port, a, src)
            out["get"].update(pread_rates(proc.pid, a))
        first.close()
    finally:
        stop_server(proc)
    return out


def get_rates(port: int, values: Values, src: np.ndarray):
    """First and second read of ``values`` by connections that never read them:
    ``loc`` into a plain buffer (the client's mapping pays), ``into`` into a
    segment the server maps (the server's does). ``values`` were put through
    the two-phase path, so only their writer's mapping has touched them."""
    out = {}
    half = len(values.batches) // 2
    for name, lo, hi in (("loc", 0, half), ("into", half, len(values.batches))):
        conn = connect(port, shm=True)
        if name == "into":
            dst = conn.alloc_shm_mr(values.nbytes)
        else:
            dst = np.empty(values.nbytes, dtype=np.uint8)
            conn.register_mr(dst)
        dst[:] = 0  # the landing buffer's own first touch is not the store's
        part = values.part(lo, hi)
        out[f"{name}_first"] = timed(conn.read_cache, part, dst)
        out[f"{name}_second"] = timed(conn.read_cache, part, dst)
        out[f"{name}_exact"] = bool(np.array_equal(dst, src[: values.nbytes]))
        conn.close()
    return out


def pread_rates(server_pid: int, values: Values):
    """``values.nbytes`` a read, ``pread`` from the head of the server's first
    pool file (where ``values`` lie) by this process, which has no mapping of
    it: twice over the same bytes."""
    (name,) = [n for n in os.listdir("/dev/shm")
               if n.startswith(f"its.{server_pid}.") and n.endswith(".0")]
    buf = bytearray(values.nbytes)
    buf[:] = bytes(1) * values.nbytes  # the landing buffer's own first touch
    fd = os.open(f"/dev/shm/{name}", os.O_RDONLY)
    out = {}
    try:
        for which in ("pread_first", "pread_second"):
            rates = []
            for k in range(len(values.batches)):
                t0 = time.perf_counter()
                got = os.preadv(fd, [buf], k * values.nbytes)
                rates.append(got / (time.perf_counter() - t0) / 1e9)
            out[which] = rates
    finally:
        os.close(fd)
    return out


def value_rates(port: int, value_kib: int, put_mib: int, puts: int, src: np.ndarray):
    """One value size's put rates: cold and warm, one writer and two."""
    block = value_kib << 10
    shape = (puts, max(1, (put_mib << 20) // block), block)
    out = {}

    def run(tag: str, writers: int):
        conns = [connect(port, shm=True) for _ in range(writers)]
        sets = [Values(f"v{value_kib}.{tag}{w}", *shape) for w in range(writers)]
        for c in conns:
            c.register_mr(src)
        for phase in ("cold", "warm"):
            rates = [None] * writers

            def work(w):
                rates[w] = timed(conns[w].write_cache, sets[w], src)

            threads = [threading.Thread(target=work, args=(w,)) for w in range(writers)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if writers == 1:
                out[f"{tag}_{phase}"] = rates[0]
            else:  # the writers together: their bytes over the wall time
                out[f"{tag}_{phase}"] = [writers * puts * sets[0].nbytes / wall / 1e9]
            if phase == "cold":
                for c, v in zip(conns, sets):
                    c.delete_keys(v.keys())
        out[f"{tag}_file_share"] = share(sum_stats(conns))
        for c in conns:
            c.close()

    run("one", 1)
    run("two", 2)
    return out


def sum_stats(conns):
    total: dict = {}
    for c in conns:
        for k, v in c.touch_stats().items():
            total[k] = total.get(k, 0) + v
    return total


def share(stats: dict):
    """Percent of the put copies' bytes that went through a descriptor; None
    on a tree whose client has no such counter."""
    if "put_file_bytes" not in stats or not stats.get("put_copy_bytes"):
        return None
    return 100.0 * stats["put_file_bytes"] / stats["put_copy_bytes"]


def stall_case(port: int, args, src: np.ndarray):
    """The read that stands still while (and after) the same connection puts."""
    conn = connect(port, shm=True)
    conn.register_mr(src)
    block = args.stall_value_kib << 10
    read = Values("stall.read", 1, max(1, (args.stall_read_mib << 20) // block), block)
    dst = conn.alloc_shm_mr(read.nbytes)
    if dst is None:
        dst = np.empty(read.nbytes, dtype=np.uint8)
        conn.register_mr(dst)
    dst[:] = 0
    conn.write_cache(read.batches[0], block, src.ctypes.data)
    for _ in range(2):
        conn.read_cache(read.batches[0], block, dst.ctypes.data)
    exact = bool(np.array_equal(dst, src[: read.nbytes]))
    put_blocks = max(1, (args.put_mib << 20) // block)
    fresh = Values("stall.put", -(-(args.stall_mib << 20) // (put_blocks * block)), put_blocks,
                   block)
    stamps, stop = [], threading.Event()

    def loop():
        while not stop.is_set():
            t0 = time.perf_counter()
            conn.read_cache(read.batches[0], block, dst.ctypes.data)
            stamps.append((t0, time.perf_counter() - t0))

    reader = threading.Thread(target=loop)
    reader.start()
    time.sleep(0.2)
    t0 = time.perf_counter()
    rates = timed(conn.write_cache, fresh, src)
    acked = time.perf_counter()
    at_ack = conn.touch_stats()
    time.sleep(args.stall_tail_s)
    stop.set()
    reader.join()
    at_end = conn.touch_stats()
    conn.close()

    def summary(lat):
        if not lat:
            return {"n": 0}
        ms = [x * 1e3 for x in lat]
        return {"n": len(ms), "p50": statistics.median(ms), "max": max(ms)}

    # A read counts where it ENDS: one submitted during the puts that comes
    # back seconds after the last acknowledgement stood still after them.
    return {
        "read_bytes": read.nbytes, "put_bytes": len(fresh.batches) * fresh.nbytes,
        "value_bytes": block, "exact": exact,
        "put_gbps": len(fresh.batches) * fresh.nbytes / (acked - t0) / 1e9,
        "put_gbps_min": min(rates),
        "read_ms": {
            "during": summary([d for s, d in stamps if t0 <= s and s + d <= acked]),
            "after": summary([d for s, d in stamps if s + d > acked]),
        },
        "counters": {"at_ack": at_ack, "at_end": at_end},
        "file_share": share(at_end),
    }


def segment_rates(nbytes: int, page: int):
    """The kernel alone: a copy into a shared segment's pages by how THIS
    mapping touched them first. A child keeps the segment mapped too, as the
    server does."""
    libc = ctypes.CDLL(None, use_errno=True)
    name = f"/dev/shm/its_putfault_{os.getpid()}"
    out = {}
    fd = os.open(name, os.O_CREAT | os.O_EXCL | os.O_RDWR, 0o600)
    try:
        os.posix_fallocate(fd, 0, 5 * nbytes)
        child = subprocess.Popen(
            [sys.executable, "-c",
             "import mmap,os,sys;f=os.open(sys.argv[1],os.O_RDWR);"
             "m=mmap.mmap(f,0);print('up',flush=True);sys.stdin.read()", name],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        )
        child.stdout.readline()
        m = mmap.mmap(fd, 4 * nbytes)
        view = np.frombuffer(m, dtype=np.uint8)
        src = np.full(nbytes, 7, dtype=np.uint8)

        def copy(k):
            t0 = time.perf_counter()
            view[k * nbytes:(k + 1) * nbytes] = src
            return nbytes / (time.perf_counter() - t0) / 1e9

        out["untouched"] = copy(0)
        out["touched_again"] = copy(0)
        t0 = time.perf_counter()
        int(view[nbytes:2 * nbytes:page].sum())  # one read a page
        out["read_touch_us_per_page"] = (time.perf_counter() - t0) * 1e6 / (nbytes // page)
        out["after_read_touch"] = copy(1)
        t0 = time.perf_counter()
        view[2 * nbytes:3 * nbytes:page] |= 0  # one write a page that changes nothing
        out["write_touch_us_per_page"] = (time.perf_counter() - t0) * 1e6 / (nbytes // page)
        out["after_write_touch"] = copy(2)
        addr = ctypes.addressof(ctypes.c_char.from_buffer(m)) + 3 * nbytes
        t0 = time.perf_counter()
        rc = libc.madvise(ctypes.c_void_p(addr), ctypes.c_size_t(nbytes), MADV_POPULATE_WRITE)
        out["madv_populate_write"] = "ok" if rc == 0 else os.strerror(ctypes.get_errno())
        if rc == 0:
            out["populate_us_per_page"] = (time.perf_counter() - t0) * 1e6 / (nbytes // page)
            out["after_populate"] = copy(3)
        # The descriptor's copy, into pages beyond the mapping: no mapping of
        # this process has touched them, and none is touched by the call.
        for key in ("pwrite_untouched", "pwrite_again"):
            t0 = time.perf_counter()
            os.pwrite(fd, src, 4 * nbytes)
            out[key] = nbytes / (time.perf_counter() - t0) / 1e9
        back = bytearray(nbytes)
        t0 = time.perf_counter()
        os.preadv(fd, [back], 4 * nbytes)
        out["pread"] = nbytes / (time.perf_counter() - t0) / 1e9
        del view, src
        child.stdin.close()
        child.wait()
    finally:
        os.close(fd)
        os.unlink(name)
    return out


def minflt_counts(page: int) -> bool:
    """Whether ``getrusage`` counts the faults of a first touch on this host."""
    before = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
    m = mmap.mmap(-1, 256 * page)
    np.frombuffer(m, dtype=np.uint8)[::page] = 1
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt - before >= 128


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--pool-gib", type=int, default=2)
    ap.add_argument("--unit-kib", type=int, default=16)
    ap.add_argument("--puts", type=int, default=8, help="puts a phase")
    ap.add_argument("--blocks", type=int, default=32, help="keys a put")
    ap.add_argument("--block-kib", type=int, default=256, help="bytes a key")
    ap.add_argument("--no-pinned", action="store_true", help="skip the pinned server")
    ap.add_argument("--value-kib", type=int, nargs="*", default=[64, 2048, 8192],
                    help="value sizes of put_by_value_gbps")
    ap.add_argument("--put-mib", type=int, default=32, help="bytes a put there and in stall")
    ap.add_argument("--stall-mib", type=int, default=772, help="fresh bytes the stall case puts")
    ap.add_argument("--stall-read-mib", type=int, default=32)
    ap.add_argument("--stall-value-kib", type=int, default=2048)
    ap.add_argument("--stall-tail-s", type=float, default=6.0)
    ap.add_argument("--big-pool-gib", type=int, default=6,
                    help="pool of the server behind put_by_value_gbps and stall")
    args = ap.parse_args()

    page = os.sysconf("SC_PAGESIZE")
    nbytes = args.blocks * (args.block_kib << 10)
    src = np.random.default_rng(0).integers(0, 256, nbytes, dtype=np.uint8)
    line = {
        "probe": "putfault", "page_bytes": page, "put_bytes": nbytes,
        "ru_minflt_counts": minflt_counts(page),
        "segment": segment_rates(nbytes, page),
    }
    plain = store_rates(args, pin=False, src=src)
    line["shm"] = plain.pop("shm")
    line["get_gbps"] = plain.pop("get")
    plain.pop("pinned")
    line["put_gbps"] = plain
    if not args.no_pinned:
        pinned = store_rates(args, pin=True, src=src)
        line["pinned"] = pinned["pinned"]
        line["put_gbps"]["server_touched_pinned"] = pinned["server_touched"]
    # A put's worth of bytes for the two cases below, whatever --blocks says.
    big = np.random.default_rng(1).integers(
        0, 256, max(args.put_mib, args.stall_read_mib) << 20, dtype=np.uint8)
    proc, port = start_server(args.big_pool_gib, args.unit_kib, pin=False)
    try:
        line["put_by_value_gbps"] = {
            str(kib): value_rates(port, kib, args.put_mib, args.puts, big)
            for kib in args.value_kib
        }
        line["stall"] = stall_case(port, args, big)
    finally:
        stop_server(proc)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
