"""ctypes loader for the native core (libinfinistore_tpu.so).

Replaces the reference's pybind11 extension module
(reference src/pybind.cpp) — see native/src/c_api.cpp for why ctypes.
The library is built by `make -C native` (done automatically here when the .so
is missing or older than the sources).
"""

import ctypes
import os
import subprocess
from ctypes import (
    CFUNCTYPE,
    POINTER,
    c_char_p,
    c_double,
    c_int,
    c_int32,
    c_int64,
    c_uint8,
    c_uint32,
    c_uint64,
    c_void_p,
)

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(os.path.dirname(_HERE))
_SO_PATH = os.path.join(_HERE, "libinfinistore_tpu.so")
_NATIVE_DIR = os.path.join(_REPO, "native")

# Completion callback: (ctx, status_code). ctypes re-acquires the GIL when the
# reactor thread calls back into Python (the pybind equivalent needed explicit
# gil_scoped_acquire; here it is automatic).
COMPLETION_CB = CFUNCTYPE(None, c_void_p, c_int)
LOG_SINK_CB = CFUNCTYPE(None, c_int, c_char_p)


def _needs_build() -> bool:
    if not os.path.exists(_SO_PATH):
        return True
    if not os.path.isdir(_NATIVE_DIR):
        return False  # installed wheel: .so shipped, no sources
    so_mtime = os.path.getmtime(_SO_PATH)
    for root, _dirs, files in os.walk(_NATIVE_DIR):
        for f in files:
            if f.endswith((".cpp", ".h")) and os.path.getmtime(os.path.join(root, f)) > so_mtime:
                return True
    return False


def _build() -> None:
    subprocess.run(
        ["make", "-s", "-j", str(os.cpu_count() or 2)],
        cwd=_NATIVE_DIR,
        check=True,
        capture_output=True,
    )


if _needs_build():
    _build()

# Older glibc keeps shm_open/shm_unlink in librt; a .so built against a glibc
# that folded them into libc then fails to load with "undefined symbol:
# shm_open". Preloading librt globally resolves the symbols either way.
try:
    ctypes.CDLL("librt.so.1", mode=ctypes.RTLD_GLOBAL)
except OSError:
    pass  # no librt (musl / new glibc): the symbols live in libc already

lib = ctypes.CDLL(_SO_PATH)

# ---- logging ----
lib.its_set_log_level.argtypes = [c_int]
lib.its_set_log_sink.argtypes = [LOG_SINK_CB]
lib.its_log.argtypes = [c_int, c_char_p]

# ---- server ----
lib.its_server_create.argtypes = [
    c_char_p, c_int, c_uint64, c_uint64, c_int, c_uint64, c_int, c_double, c_double, c_int,
    c_int, c_char_p, c_uint64,
]
lib.its_server_create.restype = c_void_p
lib.its_server_start.argtypes = [c_void_p]
lib.its_server_start.restype = c_int
lib.its_server_stop.argtypes = [c_void_p]
lib.its_server_destroy.argtypes = [c_void_p]
lib.its_server_port.argtypes = [c_void_p]
lib.its_server_port.restype = c_int
lib.its_server_kvmap_len.argtypes = [c_void_p]
lib.its_server_kvmap_len.restype = c_uint64
lib.its_server_purge.argtypes = [c_void_p]
lib.its_server_purge.restype = c_uint64
lib.its_server_evict.argtypes = [c_void_p, c_double, c_double]
lib.its_server_evict.restype = c_uint64
lib.its_server_usage.argtypes = [c_void_p]
lib.its_server_usage.restype = c_double
lib.its_server_stats_json.argtypes = [c_void_p, c_char_p, c_int]
lib.its_server_stats_json.restype = c_int

# ---- client ----
# Trailing two ints: enable_ring (descriptor-ring data plane,
# docs/descriptor_ring.md) and ring_slots (0 = native default).
lib.its_conn_create.argtypes = [
    c_char_p, c_int, c_int, c_int, c_int, c_int, c_int, c_int,
]
lib.its_conn_create.restype = c_void_p
lib.its_conn_connect.argtypes = [c_void_p]
lib.its_conn_connect.restype = c_int
lib.its_conn_shm_active.argtypes = [c_void_p]
lib.its_conn_shm_active.restype = c_int
lib.its_conn_ring_active.argtypes = [c_void_p]
lib.its_conn_ring_active.restype = c_int
lib.its_conn_ring_name.argtypes = [c_void_p, c_char_p, c_int]
lib.its_conn_ring_name.restype = c_int
# Client ring ledger: posted, doorbells, full fallbacks, meta fallbacks,
# completions (lib.InfinityConnection.ring_stats).
lib.its_conn_ring_counters.argtypes = [
    c_void_p, POINTER(c_uint64), POINTER(c_uint64), POINTER(c_uint64),
    POINTER(c_uint64), POINTER(c_uint64),
]
# PR 16 mechanism ledger: batch slots, batch ops, reactor poll hits, poll
# arms (its_conn_ring_counters keeps its 5-value shape for stability).
lib.its_conn_ring_poll_counters.argtypes = [
    c_void_p, POINTER(c_uint64), POINTER(c_uint64), POINTER(c_uint64),
    POINTER(c_uint64),
]
# The shm copies' ledger: put copy bytes (all through a pool file's
# descriptor), the pwritev calls that took, us the reactor spent in the
# copies, bytes located gets read through a descriptor
# (lib.InfinityConnection.touch_stats).
lib.its_conn_put_counters.argtypes = [
    c_void_p, POINTER(c_uint64), POINTER(c_uint64), POINTER(c_uint64),
    POINTER(c_uint64),
]
# Multi-op batch grouping: bracket one event-loop tick's ring posts so a
# coalesced flush publishes as one batch slot (docs/descriptor_ring.md).
lib.its_conn_ring_group_begin.argtypes = [c_void_p]
lib.its_conn_ring_group_end.argtypes = [c_void_p]
lib.its_conn_close.argtypes = [c_void_p]
lib.its_conn_destroy.argtypes = [c_void_p]
lib.its_conn_connected.argtypes = [c_void_p]
lib.its_conn_connected.restype = c_int
lib.its_conn_register_mr.argtypes = [c_void_p, c_void_p, c_uint64]
lib.its_conn_register_mr.restype = c_int
lib.its_conn_unregister_mr.argtypes = [c_void_p, c_void_p]
lib.its_conn_unregister_mr.restype = c_int
lib.its_conn_alloc_shm_mr.argtypes = [c_void_p, c_uint64]
lib.its_conn_alloc_shm_mr.restype = c_void_p
# Trailing c_int: QoS class tag (0 = foreground/default, 1 = background —
# wire.PRIORITY_*; see docs/qos.md). The two trailing c_uint64s are the
# per-op trace context (trace id + client span id, docs/observability.md);
# 0/0 = untraced, zero extra wire bytes.
_batch_args = [
    c_void_p, c_char_p, c_uint64, c_uint32, POINTER(c_uint64), c_uint32, c_void_p,
    COMPLETION_CB, c_void_p, c_int, c_uint64, c_uint64,
]
lib.its_conn_put_batch.argtypes = _batch_args
lib.its_conn_put_batch.restype = c_int
lib.its_conn_get_batch.argtypes = _batch_args
lib.its_conn_get_batch.restype = c_int
_batch_sync_args = [
    c_void_p, c_char_p, c_uint64, c_uint32, POINTER(c_uint64), c_uint32, c_void_p, c_int,
    c_uint64, c_uint64,
]
lib.its_conn_put_batch_sync.argtypes = _batch_sync_args
lib.its_conn_put_batch_sync.restype = c_int
lib.its_conn_get_batch_sync.argtypes = _batch_sync_args
lib.its_conn_get_batch_sync.restype = c_int
lib.its_conn_tcp_put.argtypes = [c_void_p, c_char_p, c_void_p, c_uint64]
lib.its_conn_tcp_put.restype = c_int
lib.its_conn_tcp_get.argtypes = [c_void_p, c_char_p, POINTER(POINTER(c_uint8)), POINTER(c_uint64)]
lib.its_conn_tcp_get.restype = c_int
lib.its_free.argtypes = [c_void_p]
lib.its_conn_check_exist.argtypes = [c_void_p, c_char_p]
lib.its_conn_check_exist.restype = c_int
lib.its_conn_match_last_index.argtypes = [c_void_p, c_char_p, c_uint64, c_uint32]
lib.its_conn_match_last_index.restype = c_int32
lib.its_conn_delete_keys.argtypes = [c_void_p, c_char_p, c_uint64, c_uint32]
lib.its_conn_delete_keys.restype = c_int64
lib.its_conn_stat_json.argtypes = [c_void_p, c_char_p, c_int]
lib.its_conn_stat_json.restype = c_int
# Event-fd completion ring (fd owned by the Python side; never closed natively).
lib.its_conn_set_completion_fd.argtypes = [c_void_p, c_int]
lib.its_conn_drain_completions.argtypes = [
    c_void_p, POINTER(c_uint64), POINTER(c_int32), c_int,
]
lib.its_conn_drain_completions.restype = c_int
# Wakeup-coalescing counters: ring pushes vs eventfd writes (empty->non-empty
# transitions only), the completion_batch_size numerator/denominator.
lib.its_conn_completion_counters.argtypes = [
    c_void_p, POINTER(c_uint64), POINTER(c_uint64),
]

# ---- mempool (unit-test surface) ----
lib.its_mm_create.argtypes = [c_uint64, c_uint64, c_int]
lib.its_mm_create.restype = c_void_p
lib.its_mm_destroy.argtypes = [c_void_p]
lib.its_mm_allocate.argtypes = [c_void_p, c_uint64, c_uint32, POINTER(c_void_p)]
lib.its_mm_allocate.restype = c_int
lib.its_mm_deallocate.argtypes = [c_void_p, c_void_p, c_uint64]
lib.its_mm_usage.argtypes = [c_void_p]
lib.its_mm_usage.restype = c_double
lib.its_mm_extend.argtypes = [c_void_p, c_uint64]
lib.its_mm_extend.restype = c_int
lib.its_mm_total_bytes.argtypes = [c_void_p]
lib.its_mm_total_bytes.restype = c_uint64
lib.its_mm_used_bytes.argtypes = [c_void_p]
lib.its_mm_used_bytes.restype = c_uint64
lib.its_mm_pinned.argtypes = [c_void_p]
lib.its_mm_pinned.restype = c_int
