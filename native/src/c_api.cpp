// C API exported to Python over ctypes.
//
// Replaces the reference's pybind11 module (/root/reference/src/pybind.cpp) —
// pybind11 is not available in this environment, and ctypes gives the same
// properties for free: the GIL is released for the duration of every foreign
// call, and C→Python callbacks (used for async op completions, the analogue of
// pybind's callback bridging at pybind.cpp:66-80) re-acquire it automatically.
// Key lists cross the boundary as a single packed blob of (u16 len, bytes)
// entries — one memcpy on the Python side instead of per-key object traffic.
#include <cstdint>
#include <cstring>
#include <string>
#include <vector>

#include "its/client.h"
#include "its/kvstore.h"
#include "its/log.h"
#include "its/mempool.h"
#include "its/protocol.h"
#include "its/server.h"

using its::ClientConfig;
using its::Connection;
using its::MM;
using its::Server;
using its::ServerConfig;

namespace {

std::vector<std::string> parse_keys_blob(const uint8_t* blob, uint64_t blob_len,
                                         uint32_t nkeys) {
    its::WireReader r(blob, blob_len);
    std::vector<std::string> keys;
    keys.reserve(nkeys);
    for (uint32_t i = 0; i < nkeys; i++) keys.push_back(r.str());
    return keys;
}

int copy_out(const std::string& s, char* buf, int buf_len) {
    if (buf_len <= 0) return -1;
    size_t n = std::min(s.size(), static_cast<size_t>(buf_len - 1));
    memcpy(buf, s.data(), n);
    buf[n] = '\0';
    return static_cast<int>(n);
}

// Exceptions (oversized keys from WireWriter::str, malformed blobs from
// WireReader) must not unwind through the FFI boundary — that is UB under
// libffi and would abort the Python process. Each guarded call maps them to
// an error return instead.
template <typename F>
static auto guarded(F&& f, decltype(f()) err) -> decltype(f()) {
    try {
        return f();
    } catch (const std::exception& e) {
        ITS_LOG_ERROR("native call failed: %s", e.what());
        return err;
    }
}

}  // namespace

extern "C" {

void its_install_crash_handler() { its::install_crash_handler(); }

// ---- logging ----
void its_set_log_level(int level) { its::set_log_level(static_cast<its::LogLevel>(level)); }
void its_set_log_sink(its::LogSink sink) { its::set_log_sink(sink); }
void its_log(int level, const char* msg) {
    its::log_msg(static_cast<its::LogLevel>(level), "%s", msg);
}

// ---- server ----
void* its_server_create(const char* bind_addr, int port, uint64_t prealloc_bytes,
                        uint64_t block_bytes, int auto_increase, uint64_t extend_bytes,
                        int pin, double evict_min, double evict_max, int enable_shm,
                        int pacing_rate_mbps, const char* spill_dir,
                        uint64_t spill_bytes) {
    ServerConfig cfg;
    cfg.bind_addr = bind_addr;
    cfg.service_port = port;
    cfg.prealloc_bytes = prealloc_bytes;
    cfg.block_size = block_bytes;
    cfg.auto_increase = auto_increase != 0;
    cfg.extend_pool_bytes = extend_bytes;
    cfg.pin_memory = pin != 0;
    cfg.evict_min_ratio = evict_min;
    cfg.evict_max_ratio = evict_max;
    cfg.enable_shm = enable_shm != 0;
    cfg.pacing_rate_mbps = pacing_rate_mbps > 0 ? static_cast<uint32_t>(pacing_rate_mbps) : 0;
    cfg.spill_dir = spill_dir != nullptr ? spill_dir : "";
    cfg.spill_bytes = spill_bytes;
    try {
        return new Server(cfg);
    } catch (const std::exception& e) {
        ITS_LOG_ERROR("server create failed: %s", e.what());
        return nullptr;
    }
}
int its_server_start(void* s) { return static_cast<Server*>(s)->start() ? 0 : -1; }
void its_server_stop(void* s) { static_cast<Server*>(s)->stop(); }
void its_server_destroy(void* s) { delete static_cast<Server*>(s); }
int its_server_port(void* s) { return static_cast<Server*>(s)->port(); }
uint64_t its_server_kvmap_len(void* s) { return static_cast<Server*>(s)->kvmap_len(); }
uint64_t its_server_purge(void* s) { return static_cast<Server*>(s)->purge(); }
uint64_t its_server_evict(void* s, double min_r, double max_r) {
    return static_cast<Server*>(s)->evict(min_r, max_r);
}
double its_server_usage(void* s) { return static_cast<Server*>(s)->usage(); }
int its_server_stats_json(void* s, char* buf, int buf_len) {
    return copy_out(static_cast<Server*>(s)->stats_json(), buf, buf_len);
}

// ---- client ----
// ``enable_ring``/``ring_slots``: descriptor-ring data plane
// (docs/descriptor_ring.md) — batched segment ops post as shared-memory
// descriptors instead of per-op socket writes when the shm fast path is up.
// ring_slots 0 = default (its::kRingSqSlots).
void* its_conn_create(const char* host, int port, int timeout_ms, int enable_shm,
                      int op_timeout_ms, int pacing_rate_mbps, int enable_ring,
                      int ring_slots) {
    ClientConfig cfg;
    cfg.host = host;
    cfg.port = port;
    cfg.connect_timeout_ms = timeout_ms;
    cfg.op_timeout_ms = op_timeout_ms;
    cfg.enable_shm = enable_shm != 0;
    cfg.pacing_rate_mbps = pacing_rate_mbps > 0 ? static_cast<uint32_t>(pacing_rate_mbps) : 0;
    cfg.enable_ring = enable_ring != 0;
    cfg.ring_slots = ring_slots > 0 ? static_cast<uint32_t>(ring_slots) : 0;
    return new Connection(cfg);
}
int its_conn_connect(void* c) { return static_cast<Connection*>(c)->connect(); }
int its_conn_shm_active(void* c) { return static_cast<Connection*>(c)->shm_active() ? 1 : 0; }
int its_conn_ring_active(void* c) { return static_cast<Connection*>(c)->ring_active() ? 1 : 0; }
// Shm name of the connection's descriptor-ring segment (empty when
// inactive): the introspection hook the torn-descriptor tests use to map
// and tamper with the ring from outside the client.
int its_conn_ring_name(void* c, char* buf, int buf_len) {
    return copy_out(static_cast<Connection*>(c)->ring_name(), buf, buf_len);
}
// Client half of the ring ledger (lib.InfinityConnection.ring_stats):
// descriptors posted, submission doorbells sent (doze transitions only),
// ring-full + oversized-meta socket fallbacks, completions consumed.
void its_conn_ring_counters(void* c, uint64_t* posted, uint64_t* doorbells,
                            uint64_t* full_fallbacks, uint64_t* meta_fallbacks,
                            uint64_t* completions) {
    static_cast<Connection*>(c)->ring_counters(posted, doorbells, full_fallbacks,
                                               meta_fallbacks, completions);
}
// PR 16 mechanism counters: batch slots published / ops packed into them,
// reactor poll-window hits vs doorbell arms (lib.ring_stats extension —
// its_conn_ring_counters keeps its 5-value signature for ABI stability).
void its_conn_ring_poll_counters(void* c, uint64_t* batch_slots, uint64_t* batch_ops,
                                 uint64_t* poll_hits, uint64_t* poll_arms) {
    static_cast<Connection*>(c)->ring_poll_counters(batch_slots, batch_ops, poll_hits,
                                                    poll_arms);
}
// Multi-op batch grouping: the asyncio bridge brackets one event-loop
// tick's ring posts between begin/end so a whole FetchCoalescer flush
// publishes as one batch slot (docs/descriptor_ring.md). Safe no-ops when
// the ring is down.
void its_conn_ring_group_begin(void* c) {
    static_cast<Connection*>(c)->ring_group_begin();
}
void its_conn_ring_group_end(void* c) { static_cast<Connection*>(c)->ring_group_end(); }
void its_conn_close(void* c) { static_cast<Connection*>(c)->close(); }
void its_conn_destroy(void* c) { delete static_cast<Connection*>(c); }
int its_conn_connected(void* c) { return static_cast<Connection*>(c)->connected() ? 1 : 0; }
int its_conn_unregister_mr(void* c, void* ptr) {
    return static_cast<Connection*>(c)->unregister_mr(ptr);
}
int its_conn_register_mr(void* c, void* ptr, uint64_t size) {
    return static_cast<Connection*>(c)->register_mr(ptr, size);
}
// Returns the mapped base of a server-shared staging segment (one-RTT data
// plane), or NULL when the server is remote/shm-less.
void* its_conn_alloc_shm_mr(void* c, uint64_t size) {
    return static_cast<Connection*>(c)->alloc_shm_mr(size);
}

// Event-fd completion ring: the caller owns fd (never closed here); async
// batched ops submitted with cb=NULL, ctx=token complete into the ring.
void its_conn_set_completion_fd(void* c, int fd) {
    static_cast<Connection*>(c)->set_completion_fd(fd);
}
int its_conn_drain_completions(void* c, uint64_t* tokens, int32_t* codes, int cap) {
    return static_cast<Connection*>(c)->drain_completions(tokens, codes, cap);
}
// Wakeup-coalescing counters: ring pushes vs eventfd writes (the fd is
// written only on empty->non-empty transitions; see Connection::complete).
void its_conn_completion_counters(void* c, uint64_t* pushed, uint64_t* signalled) {
    static_cast<Connection*>(c)->completion_counters(pushed, signalled);
}
// The shm copies' ledger (docs/design.md, "A put's copy rides the pool's
// file"): bytes the two-phase shm put copied (all through a pool file's
// descriptor), the pwritev calls that took, the reactor's time in the copies
// (us), and the bytes located gets read through a descriptor.
void its_conn_put_counters(void* c, uint64_t* put_file_bytes, uint64_t* put_file_calls,
                           uint64_t* put_copy_us, uint64_t* get_file_bytes) {
    static_cast<Connection*>(c)->put_counters(put_file_bytes, put_file_calls, put_copy_us,
                                              get_file_bytes);
}

// ``priority``: QoS class tag (its::Priority) — 0 foreground (default
// scheduling, wire bytes unchanged), 1 background (yields to foreground in
// the server's two-level slice scheduler; see docs/qos.md).
// ``trace_id``/``trace_span``: per-op trace context (docs/observability.md)
// — 0/0 (the default/untraced case) adds ZERO wire bytes; non-zero rides
// the trailing trace extension and the server stamps recv/slice/done ticks
// for the op into its trace ring (stats_json "trace").
int its_conn_put_batch(void* c, const uint8_t* keys_blob, uint64_t blob_len, uint32_t nkeys,
                       const uint64_t* offsets, uint32_t block_size, void* base_ptr,
                       its::CompletionCb cb, void* ctx, int priority,
                       uint64_t trace_id, uint64_t trace_span) {
    return guarded([&]() -> int {
        auto keys = parse_keys_blob(keys_blob, blob_len, nkeys);
        std::vector<uint64_t> offs(offsets, offsets + nkeys);
        return static_cast<Connection*>(c)->put_batch_async(keys, offs, block_size, base_ptr,
                                                            cb, ctx,
                                                            static_cast<uint8_t>(priority),
                                                            trace_id, trace_span);
    }, -1);
}
int its_conn_get_batch(void* c, const uint8_t* keys_blob, uint64_t blob_len, uint32_t nkeys,
                       const uint64_t* offsets, uint32_t block_size, void* base_ptr,
                       its::CompletionCb cb, void* ctx, int priority,
                       uint64_t trace_id, uint64_t trace_span) {
    return guarded([&]() -> int {
        auto keys = parse_keys_blob(keys_blob, blob_len, nkeys);
        std::vector<uint64_t> offs(offsets, offsets + nkeys);
        return static_cast<Connection*>(c)->get_batch_async(keys, offs, block_size, base_ptr,
                                                            cb, ctx,
                                                            static_cast<uint8_t>(priority),
                                                            trace_id, trace_span);
    }, -1);
}
// Sync batched ops: calling thread blocks on completion (no asyncio hop) —
// the low-latency path for small fetches. Returns 0 or -status.
int its_conn_put_batch_sync(void* c, const uint8_t* keys_blob, uint64_t blob_len,
                            uint32_t nkeys, const uint64_t* offsets, uint32_t block_size,
                            void* base_ptr, int priority,
                            uint64_t trace_id, uint64_t trace_span) {
    return guarded([&]() -> int {
        auto keys = parse_keys_blob(keys_blob, blob_len, nkeys);
        std::vector<uint64_t> offs(offsets, offsets + nkeys);
        return static_cast<Connection*>(c)->put_batch(keys, offs, block_size, base_ptr,
                                                      static_cast<uint8_t>(priority),
                                                      trace_id, trace_span);
    }, -static_cast<int>(its::kStatusInvalidReq));
}
int its_conn_get_batch_sync(void* c, const uint8_t* keys_blob, uint64_t blob_len,
                            uint32_t nkeys, const uint64_t* offsets, uint32_t block_size,
                            void* base_ptr, int priority,
                            uint64_t trace_id, uint64_t trace_span) {
    return guarded([&]() -> int {
        auto keys = parse_keys_blob(keys_blob, blob_len, nkeys);
        std::vector<uint64_t> offs(offsets, offsets + nkeys);
        return static_cast<Connection*>(c)->get_batch(keys, offs, block_size, base_ptr,
                                                      static_cast<uint8_t>(priority),
                                                      trace_id, trace_span);
    }, -static_cast<int>(its::kStatusInvalidReq));
}
int its_conn_tcp_put(void* c, const char* key, const void* data, uint64_t size) {
    return guarded(
        [&]() -> int { return static_cast<Connection*>(c)->tcp_put(key, data, size); },
        -static_cast<int>(its::kStatusInvalidReq));
}
int its_conn_tcp_get(void* c, const char* key, uint8_t** out, uint64_t* out_size) {
    return guarded(
        [&]() -> int {
            size_t sz = 0;
            int rc = static_cast<Connection*>(c)->tcp_get(key, out, &sz);
            *out_size = sz;
            return rc;
        },
        -static_cast<int>(its::kStatusInvalidReq));
}
void its_free(void* p) { free(p); }
int its_conn_check_exist(void* c, const char* key) {
    return guarded([&]() -> int { return static_cast<Connection*>(c)->check_exist(key); },
                   -static_cast<int>(its::kStatusInvalidReq));
}
int32_t its_conn_match_last_index(void* c, const uint8_t* keys_blob, uint64_t blob_len,
                                  uint32_t nkeys) {
    return guarded(
        [&]() -> int32_t {
            return static_cast<Connection*>(c)->get_match_last_index(
                parse_keys_blob(keys_blob, blob_len, nkeys));
        },
        INT32_MIN);
}
int64_t its_conn_delete_keys(void* c, const uint8_t* keys_blob, uint64_t blob_len,
                             uint32_t nkeys) {
    return guarded(
        [&]() -> int64_t {
            return static_cast<Connection*>(c)->delete_keys(
                parse_keys_blob(keys_blob, blob_len, nkeys));
        },
        -static_cast<int64_t>(its::kStatusInvalidReq));
}
int its_conn_stat_json(void* c, char* buf, int buf_len) {
    return copy_out(static_cast<Connection*>(c)->stat_json(), buf, buf_len);
}

// ---- mempool (unit-test surface; the reference has no allocator tests at
// all — SURVEY.md §4 flags that as its weakest subsystem) ----
void* its_mm_create(uint64_t pool_bytes, uint64_t block_bytes, int pin) {
    try {
        return new MM(pool_bytes, block_bytes, pin != 0);
    } catch (const std::exception& e) {
        ITS_LOG_ERROR("mm create failed: %s", e.what());
        return nullptr;
    }
}
void its_mm_destroy(void* mm) { delete static_cast<MM*>(mm); }
int its_mm_allocate(void* mm, uint64_t size, uint32_t n, void** out_ptrs) {
    std::vector<its::Lease> leases;
    if (!static_cast<MM*>(mm)->allocate(size, n, nullptr, &leases)) return -1;
    for (uint32_t i = 0; i < n; i++) out_ptrs[i] = leases[i].ptr;
    return 0;
}
void its_mm_deallocate(void* mm, void* ptr, uint64_t size) {
    static_cast<MM*>(mm)->deallocate(ptr, size);
}
double its_mm_usage(void* mm) { return static_cast<MM*>(mm)->usage(); }
int its_mm_extend(void* mm, uint64_t pool_bytes) {
    return static_cast<MM*>(mm)->extend(pool_bytes) ? 0 : -1;
}
uint64_t its_mm_total_bytes(void* mm) { return static_cast<MM*>(mm)->total_bytes(); }
uint64_t its_mm_used_bytes(void* mm) { return static_cast<MM*>(mm)->used_bytes(); }
int its_mm_pinned(void* mm) { return static_cast<MM*>(mm)->pinned() ? 1 : 0; }

}  // extern "C"
