"""HBM <-> host staging for the TPU data plane.

The TPU replacement for the reference's GPUDirect path: where the reference
registers CUDA tensor memory with the NIC and lets the server RDMA straight
into HBM (reference src/libinfinistore.cpp:728 register_mr on data_ptr), TPU
VMs require an explicit device<->host hop. This module owns that hop and keeps
it to ONE host copy per direction:

- Writes ship directly from the buffer jax's async D2H lands in
  (``StagedTransfer.wait`` returns zero-copy views of the device transfer —
  no staging memcpy). The buffer is registered for the transfer's lifetime
  and the shm data plane memcpys it straight into the server pool.
- Reads land in the pool below. When the server is same-host, the pool is
  allocated via ``alloc_shm_mr`` so the server pushes blocks into it in one
  round trip (GetInto — the shm analogue of the reference's one-sided RDMA
  WRITE, reference src/infinistore.cpp:600-637) and ``jax.device_put``
  uploads straight from the segment.
"""

import math
import weakref
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np


class StagingPoolExhausted(RuntimeError):
    """`HostStagingPool.reserve` could not find a contiguous free run.

    Deliberately a distinct type: callers treat exhaustion as backpressure
    (skip the speculative prefetch, fall back to the gated load path), not
    as a bug — so it must be catchable without swallowing real errors."""


class StagingLease:
    """A reserved contiguous run of staging-pool slots.

    Handed out by ``HostStagingPool.reserve``; release() (idempotent)
    returns the slots to the pool. The lease is pure accounting — the pool's
    buffer is shared, and the lease only guarantees no OTHER reserver gets
    these slots while it is held."""

    def __init__(self, pool: "HostStagingPool", start_slot: int, num_slots: int):
        self.pool = pool
        self.start_slot = start_slot
        self.num_slots = num_slots
        self._released = False

    @property
    def offset(self) -> int:
        """Byte offset of the lease's first slot within the pool buffer."""
        return self.start_slot * self.pool.block_size

    def view(self, nbytes: Optional[int] = None) -> np.ndarray:
        """Zero-copy uint8 view of the leased span (nbytes trims the tail)."""
        span = self.num_slots * self.pool.block_size
        if nbytes is not None:
            if nbytes > span:
                raise ValueError(f"nbytes {nbytes} > leased span {span}")
            span = nbytes
        return self.pool.buf[self.offset : self.offset + span]

    def release(self) -> None:
        """Return the slots to the pool (idempotent)."""
        if not self._released:
            self._released = True
            self.pool._release_run(self.start_slot, self.num_slots)


class StagedTransfer:
    """Handle for in-flight async device->host copies.

    ``wait()`` returns host views of the transferred data without any
    further copy: ``np.asarray`` on a jax array reuses the buffer
    ``copy_to_host_async`` produced. Keep the transfer object alive until the
    network is done with the views — it anchors the jax arrays that own the
    host memory.
    """

    def __init__(self, arrays: Sequence[jax.Array]):
        self._arrays = list(arrays)
        # Kick off all D2H copies without blocking; jax overlaps them with
        # ongoing device computation.
        for arr in self._arrays:
            arr.copy_to_host_async()
        self._hosts: Optional[List[np.ndarray]] = None

    def wait(self) -> List[np.ndarray]:
        """Block until device data is host-visible; returns host arrays (one
        np.ndarray per input array), each C-contiguous: whoever ships them
        addresses block ``i`` at ``base + i * nbytes``. The view jax hands
        back is that already, zero-copy, for every array but one the TPU
        runtime returns in the device's own dimension order (an int32
        ``[4, 100, 128]`` came back with strides ``(512, 2048, 4)``: PERF.md,
        PR 47); such a one is copied once."""
        if self._hosts is None:
            self._hosts = [np.ascontiguousarray(np.asarray(arr)) for arr in self._arrays]
        return self._hosts


class RegisteredTransfer:
    """A StagedTransfer whose host buffers are registered with a connection
    for the duration of one network op: ``wait()`` registers, ``release()``
    unregisters (call after the op's future resolves)."""

    def __init__(self, transfer: StagedTransfer, conn):
        self.transfer = transfer
        self.conn = conn
        self._registered: List[np.ndarray] = []

    def wait(self) -> List[np.ndarray]:
        """Block for the D2H copies, then register the host views with the
        connection (idempotent); returns the registered views."""
        hosts = self.transfer.wait()
        if not self._registered:
            for h in hosts:
                self.conn.register_mr(h.ctypes.data, h.nbytes)
            self._registered = hosts
        return hosts

    def release(self):
        """Unregister the host views (call after the network op's future
        resolves). Best-effort on a closed connection."""
        # Best-effort cleanup: a connection closed mid-flight already cleared
        # its region list — that must not mask the transport error the
        # caller is about to see (nor abort sibling releases).
        for h in self._registered:
            try:
                self.conn.unregister_mr(h.ctypes.data)
            except Exception:
                pass
        self._registered = []


class HostStagingPool:
    """A connection-registered host buffer carved into uniform block slots
    (the client-side mirror of the server's mempool; reference clients
    allocate their own torch tensors instead and register each one,
    reference infinistore/benchmark.py:144-173).

    When ``conn`` is same-host with shm enabled, the pool is allocated via
    ``alloc_shm_mr`` so the server maps it too and batched ops ride the
    one-RTT PutFrom/GetInto path; otherwise it is a plain page-aligned
    registered buffer and ops use the socket (or two-phase shm) plane.
    """

    def __init__(self, nbytes: int, block_size: int, conn=None, align: int = 4096):
        if block_size <= 0 or nbytes < block_size:
            raise ValueError("need nbytes >= block_size > 0")
        self.block_size = block_size
        self.num_slots = nbytes // block_size
        self.conn = conn
        self.server_mapped = False
        self._nbytes = nbytes
        self._align = align
        self._shm_backed = False
        self._allocate(conn, nbytes, align)
        # Self-heal across reconnects: an ``alloc_shm_mr``-backed pool dies
        # with its connection's old segment (reconnect() unmaps it), which
        # would leave every later read/write of this pool raising against an
        # unregistered (worse: unmapped) buffer FOREVER on an otherwise
        # healed member. Re-back the pool on the fresh connection instead.
        # Weakly bound so a short-lived pool never pins itself to the
        # connection through its own listener. Consumers are safe across the
        # swap because they read ``pool.buf``/``base_ptr`` per op (and the
        # connector's coalescer re-keys on base_ptr); ops in flight across a
        # reconnect fail out with typed errors regardless.
        # A StripedConnection has no listener list of its own: its shm
        # segments live on stripe 0, so that is the reconnect that kills
        # them — attach there (alloc_shm_mr on the striped surface then
        # re-aliases stripes 1..N itself). Appended after the striped
        # connection's own _on_owner_reconnect listener, so the stale
        # sibling aliases are invalidated before this pool re-allocates.
        owner = conn
        if getattr(conn, "_reconnect_listeners", None) is None:
            stripes = getattr(conn, "conns", None)
            if stripes:
                owner = stripes[0]
        listeners = getattr(owner, "_reconnect_listeners", None)
        if listeners is not None:
            ref = weakref.WeakMethod(self._refresh_after_reconnect)
            listeners.append(lambda: (lambda m: m() if m is not None else None)(ref()))
        # Slot reservation state (reserve/release): a per-slot taken flag.
        # Reservation is OPT-IN — legacy users (_LayerRegions, benches) carve
        # the pool by fixed layout on a pool they own outright; a pool shared
        # by reservers must only be used through reserve().
        self._taken = bytearray(self.num_slots)
        self._reserved_slots = 0

    def _allocate(self, conn, nbytes: int, align: int):
        buf = None
        if conn is not None:
            buf = conn.alloc_shm_mr(nbytes)  # mmap: page-aligned by nature
            if buf is not None:
                self.server_mapped = conn.shm_active
                self._shm_backed = True
        if buf is None:
            # Over-allocate to align the base: DCN readv/writev and mlock both
            # like page-aligned bases.
            raw = np.zeros(nbytes + align, dtype=np.uint8)
            base_off = (-raw.ctypes.data) % align
            self._raw = raw  # keep alive
            buf = raw[base_off : base_off + nbytes]
            self._shm_backed = False
            if conn is not None:
                conn.register_mr(buf.ctypes.data, nbytes)
        self.buf = buf

    def _refresh_after_reconnect(self):
        """Reconnect listener: a plain registered buffer survived (the
        reconnect re-registered it), but an shm segment did not — replace it
        on the fresh connection. Slot accounting is untouched: leases stay
        valid as accounting; their STAGED BYTES are gone, exactly like the
        in-flight ops the reconnect already failed."""
        if not self._shm_backed:
            return
        self.server_mapped = False
        self._allocate(self.conn, self._nbytes, self._align)

    @property
    def slots_in_use(self) -> int:
        """Slots currently held by unreleased leases (reserve() users)."""
        return self._reserved_slots

    def reserve(self, slots: int) -> StagingLease:
        """Reserve a CONTIGUOUS run of ``slots`` slots (first fit).

        Contiguity is what lets a whole leased region ship as one network
        read and upload as one device transfer. Raises
        :class:`StagingPoolExhausted` when no run fits — callers treat that
        as backpressure, not failure."""
        if slots <= 0:
            raise ValueError("need slots > 0")
        run = 0
        for i in range(self.num_slots):
            run = 0 if self._taken[i] else run + 1
            if run == slots:
                start = i - slots + 1
                for j in range(start, start + slots):
                    self._taken[j] = 1
                self._reserved_slots += slots
                return StagingLease(self, start, slots)
        raise StagingPoolExhausted(
            f"no contiguous run of {slots} slots free "
            f"({self._reserved_slots}/{self.num_slots} reserved)"
        )

    def _release_run(self, start_slot: int, num_slots: int) -> None:
        for j in range(start_slot, start_slot + num_slots):
            self._taken[j] = 0
        self._reserved_slots -= num_slots

    @property
    def base_ptr(self) -> int:
        return self.buf.ctypes.data

    def slot_offset(self, slot: int) -> int:
        """Byte offset of a slot within the pool's registered buffer."""
        if not (0 <= slot < self.num_slots):
            raise IndexError(f"slot {slot} out of range [0, {self.num_slots})")
        return slot * self.block_size

    def slot_view(self, slot: int, nbytes: Optional[int] = None) -> np.ndarray:
        """Zero-copy uint8 view of one slot (nbytes trims the tail)."""
        off = self.slot_offset(slot)
        return self.buf[off : off + (nbytes or self.block_size)]

    def slots_for(self, arr_nbytes: int) -> int:
        """How many slots one array of arr_nbytes occupies."""
        return math.ceil(arr_nbytes / self.block_size)

    # -- device -> host ------------------------------------------------------

    def stage_out(self, arrays: Sequence[jax.Array]) -> "RegisteredTransfer":
        """Start async D2H copies; the returned transfer's ``wait()`` gives
        zero-copy registered host views to ship from (call ``release()``
        after the network op completes)."""
        if self.conn is None:
            raise ValueError("stage_out needs a connection to register with")
        return RegisteredTransfer(StagedTransfer(arrays), self.conn)

    # -- host -> device ------------------------------------------------------

    def stage_in(
        self,
        slots: Sequence[int],
        shape: Tuple[int, ...],
        dtype,
        device=None,
        sharding=None,
    ) -> List[jax.Array]:
        """Upload staged blocks back to device memory. One jax.Array per slot
        run; `device`/`sharding` select placement (defaults to the default
        device)."""
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        out = []
        target = sharding if sharding is not None else device
        for slot in slots:
            host = self.slot_view(slot, nbytes).view(dtype).reshape(shape)
            if target is not None:
                out.append(jax.device_put(host, target))
            else:
                out.append(jax.device_put(host))
        return out
