// Client library: one TCP/DCN connection to a store server, with a dedicated
// reactor thread completing pipelined async operations.
//
// TPU-native analogue of the reference's client
// (/root/reference/src/libinfinistore.h:63-119, libinfinistore.cpp): the same
// surface — connect/close, register_mr, async batched block write/read against
// one registered base pointer with (key, offset) lists and a uniform
// block_size, sync control ops (check_exist, get_match_last_index,
// delete_keys), single-key TCP put/get — and the same completion architecture
// (a background thread fires callbacks; the Python layer marshals them onto
// asyncio with call_soon_threadsafe). What changed: the reference's CQ-polling
// thread over ibverbs completions becomes an epoll reactor over the socket;
// payload moves by scatter-gather writev/readv directly between user-registered
// memory and the socket, preserving the zero-copy-on-client property of the
// one-sided RDMA design without ibverbs.
#pragma once

#include <sys/uio.h>

#include <atomic>
#include <cstdint>
#include <deque>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

#include "its/iovec_util.h"
#include "its/protocol.h"
#include "its/thread_safety.h"

namespace its {

struct ClientConfig {
    std::string host = "127.0.0.1";
    int port = 22345;
    int connect_timeout_ms = 10000;
    // Deadline for every synchronous control op (tcp_put/get, check_exist,
    // match_last_index, delete, stat): a stalled-but-connected server makes
    // the call fail with kStatusUnavailable instead of hanging the caller
    // forever. <= 0 waits indefinitely (not recommended).
    int op_timeout_ms = 30000;
    // Try the same-host shm fast path at connect: map the server's shm-backed
    // pools and move batched payloads with one memcpy instead of the socket.
    // Degrades automatically to the socket path when the server is remote or
    // shm-less.
    bool enable_shm = true;
    // Egress cap for this connection in MB/s via SO_MAX_PACING_RATE (TCP
    // internal pacing; no qdisc needed). 0 = unlimited. Production use:
    // fairness on a shared DCN link. Test use: emulating a bandwidth-capped
    // cross-host stream on loopback to exercise connection striping.
    uint32_t pacing_rate_mbps = 0;
    // Descriptor-ring data plane (docs/descriptor_ring.md): when the shm
    // fast path is up, create a shared submission/completion ring and post
    // batched segment ops as descriptors instead of per-op socket writes.
    // Degrades automatically (socket path, byte-identical) when shm is
    // unavailable or the server declines the attach.
    bool enable_ring = true;
    // Submission-slot count (power of two; 0 = kRingSqSlots default). The
    // completion ring is sized equal and in-flight ring ops are bounded by
    // it; a full ring falls back to the socket path for that op (counted —
    // ring-full backpressure, never an error).
    uint32_t ring_slots = 0;
};

using CompletionCb = void (*)(void* ctx, int code);

class Connection {
  public:
    explicit Connection(const ClientConfig& config);
    ~Connection();

    // Blocking TCP connect + reactor spawn. Returns 0 or -errno.
    int connect();
    void close();
    bool connected() const { return connected_.load(); }

    // Pin + register a local memory region; batched ops validate their base
    // pointer against registered regions (reference register_mr,
    // /root/reference/src/libinfinistore.cpp:728; unregistered base is an
    // error, :602-605).
    int register_mr(void* ptr, size_t size);
    // Drop a transfer-scoped registration (most recent region with this
    // base). In-flight ops referencing the region are unaffected: iovecs are
    // captured at submit time.
    int unregister_mr(void* ptr);

    // Allocate a shm-backed staging region the SERVER maps too: batched ops
    // whose base pointer lies inside it use the one-RTT server-pull/push
    // path (PutFrom/GetInto) — the closest analogue of the reference's
    // one-sided RDMA against client-registered memory. Returns nullptr when
    // the server is remote or shm-less (caller falls back to a normal
    // buffer + register_mr). Freed at close().
    void* alloc_shm_mr(size_t size);

    // Async batched block write: for each i, send block_size bytes from
    // base_ptr+offsets[i] under keys[i]. cb fires from the reactor thread with
    // an HTTP-like status. Returns 0 on submit, -1 if not connected /
    // unregistered base. ``priority`` is the QoS class tag (protocol.h
    // Priority): kPriorityForeground (default) leaves the wire bytes
    // untouched; kPriorityBackground marks the op for the server's
    // two-level slice scheduler (docs/qos.md).
    // ``trace_id``/``trace_span``: per-op trace context (protocol.h
    // kTraceIdNone) — 0/0 (the default) leaves the wire bytes untouched; a
    // non-zero trace id rides the trailing trace extension and the server
    // reactor stamps recv/slice/done ticks for it into its trace ring
    // (docs/observability.md). ``trace_span`` is the CLIENT span the
    // server ticks hang under (wire field trace_parent).
    int put_batch_async(const std::vector<std::string>& keys,
                        const std::vector<uint64_t>& offsets, uint32_t block_size,
                        void* base_ptr, CompletionCb cb, void* ctx,
                        uint8_t priority = kPriorityForeground,
                        uint64_t trace_id = kTraceIdNone, uint64_t trace_span = 0);
    // Async batched block read into base_ptr+offsets[i].
    int get_batch_async(const std::vector<std::string>& keys,
                        const std::vector<uint64_t>& offsets, uint32_t block_size,
                        void* base_ptr, CompletionCb cb, void* ctx,
                        uint8_t priority = kPriorityForeground,
                        uint64_t trace_id = kTraceIdNone, uint64_t trace_span = 0);

    // Sync batched ops: same pipeline, but the calling thread blocks on the
    // completion (promise wait — no event-loop hop). This is the low-latency
    // path for single-block fetches: the asyncio bridge costs ~2 extra
    // context switches per op on a single-core host, which dominates a
    // same-host block fetch (measured: ~58us async vs ~20us sync p50 at
    // 4KB). Returns 0 on success, -status on failure. On op_timeout_ms
    // expiry returns -kStatusUnavailable and abandons the wait; the op may
    // still complete server-side, and the base region must stay registered
    // and alive until close() (true for staging pools by construction).
    int put_batch(const std::vector<std::string>& keys, const std::vector<uint64_t>& offsets,
                  uint32_t block_size, void* base_ptr,
                  uint8_t priority = kPriorityForeground,
                  uint64_t trace_id = kTraceIdNone, uint64_t trace_span = 0);
    int get_batch(const std::vector<std::string>& keys, const std::vector<uint64_t>& offsets,
                  uint32_t block_size, void* base_ptr,
                  uint8_t priority = kPriorityForeground,
                  uint64_t trace_id = kTraceIdNone, uint64_t trace_span = 0);

    // Sync ops (safe to call from any thread; they ride the same pipeline).
    int tcp_put(const std::string& key, const void* data, size_t size);
    // On success fills *out (malloc'd — caller frees with free()) and *out_size.
    int tcp_get(const std::string& key, uint8_t** out, size_t* out_size);
    // Returns 1 = exists, 0 = missing, negative status on error.
    int check_exist(const std::string& key);
    // Returns match index (>= -1); INT32_MIN on transport error.
    int32_t get_match_last_index(const std::vector<std::string>& keys);
    // Returns number deleted, or negative status.
    int64_t delete_keys(const std::vector<std::string>& keys);
    // Server stats snapshot (JSON). Empty on error.
    std::string stat_json();

    // True when the same-host shm fast path is active for batched ops.
    bool shm_active() const { return shm_ok_.load(); }

    // True when the descriptor-ring data plane is active (shm fast path up,
    // ring segment attached by the server).
    bool ring_active() const { return ring_ok_.load(); }
    // Shm name of this connection's ring segment (empty when inactive).
    // Introspection surface for tests/tools — the torn-descriptor tests map
    // the segment by name and tamper with it.
    std::string ring_name() const;
    // Client-side ring ledger: descriptors posted, submission doorbells
    // sent (empty->non-empty / doze transitions only), ring-full and
    // meta-too-big socket fallbacks, completions consumed from the CQ.
    void ring_counters(uint64_t* posted, uint64_t* doorbells, uint64_t* full_fallbacks,
                       uint64_t* meta_fallbacks, uint64_t* completions) const;
    // PR 16 mechanism ledger: multi-op batch slots published / ops packed
    // into them (batch_ops / batch_slots = mean flush size the bench gates
    // on), and the reactor's adaptive poll-then-park outcome counts —
    // poll_hits (a completion landed inside the busy-poll window: no park,
    // no doorbell) vs poll_arms (window expired with ops still in flight;
    // the reactor parked and armed the doorbell).
    void ring_poll_counters(uint64_t* batch_slots, uint64_t* batch_ops,
                            uint64_t* poll_hits, uint64_t* poll_arms) const;

    // Multi-op batch grouping (docs/descriptor_ring.md). Between begin and
    // end, async batched segment ops posted by the SAME thread accumulate
    // instead of publishing one slot each; end() greedily packs the group
    // into kRingSlotFlagBatch slots (one per meta-arena-load), publishes
    // them with ONE tail store + at most one doorbell, and routes whatever
    // does not fit (ring full / in-flight cap) to the socket path, counted
    // as the usual fallbacks. Sync ops and other threads bypass an open
    // group entirely. No-ops when the ring is down; never errors.
    void ring_group_begin();
    void ring_group_end();

    // Event-fd completion ring (the low-fixed-cost asyncio bridge). When a
    // completion fd is set, async batched ops submitted with cb == nullptr
    // and ctx != nullptr complete by pushing (ctx-as-token, code) into a
    // ring and signalling the fd — the Python event loop wakes via its own
    // epoll (add_reader) and drains the whole ring in one pass, instead of
    // paying one GIL acquisition + call_soon_threadsafe hop PER op. The fd
    // is owned by the caller (typically an os.eventfd); it is never closed
    // here.
    void set_completion_fd(int fd);
    // Pop up to cap completions into tokens/codes; returns the count.
    int drain_completions(uint64_t* tokens, int32_t* codes, int cap);
    // Coalescing counters: completions pushed into the ring vs eventfd
    // writes issued. The fd is written only on an empty->non-empty ring
    // transition (a completion landing while a wakeup is already armed
    // piggybacks on it — this is what lets a burst of small gets share one
    // loop wakeup instead of arming one each), so pushed/signalled is the
    // mean completion batch per wakeup the bench reports.
    void completion_counters(uint64_t* pushed, uint64_t* signalled) const;
    // The shm copies' ledger (docs/design.md, "A put's copy rides the pool's
    // file"): bytes the two-phase shm put copied into pools (every one
    // through a pool file's descriptor), the pwritev calls that took, the
    // reactor's time in those copies, and the bytes located gets read
    // through a descriptor. All zero on a connection that never moved a
    // payload through shm.
    void put_counters(uint64_t* put_file_bytes, uint64_t* put_file_calls, uint64_t* put_copy_us,
                      uint64_t* get_file_bytes) const;

  private:
    struct Request;
    struct SyncState;
    struct ShmMap {
        char* base = nullptr;
        size_t size = 0;
        int fd = -1;  // the pool file, open for as long as the mapping stands
    };

    void reactor();
    int submit(std::unique_ptr<Request> req);
    // Route a built batched request: descriptor ring when eligible (segment
    // op, ring active, fits a slot, ring not full), else the socket pipeline.
    int submit_any(std::unique_ptr<Request> req);
    void fail_all(int code);
    bool flush_send();
    bool read_ready();
    // take_body: move rbody_ into the sync state — ONLY when this request's
    // response was actually received (fail_all / abandoned-drop completions
    // must not steal a different in-flight response's partially read body).
    void complete(std::unique_ptr<Request> req, int code, bool take_body);
    // timeout_ms < 0 = use config_.op_timeout_ms (which <= 0 waits forever);
    // on timeout returns kStatusUnavailable and abandons the wait (a late
    // response completes into shared state, FIFO matching stays intact).
    uint32_t sync_roundtrip(std::unique_ptr<Request> req, std::vector<uint8_t>* body_out,
                            uint8_t** payload_out, size_t* payload_size_out,
                            int timeout_ms = -1);
    bool base_registered(const void* base, size_t span) const;
    // Shared request construction for the batched data plane (async + sync).
    std::unique_ptr<Request> build_put(const std::vector<std::string>& keys,
                                       const std::vector<uint64_t>& offsets,
                                       uint32_t block_size, void* base_ptr,
                                       uint8_t priority, uint64_t trace_id,
                                       uint64_t trace_span);
    std::unique_ptr<Request> build_get(const std::vector<std::string>& keys,
                                       const std::vector<uint64_t>& offsets,
                                       uint32_t block_size, void* base_ptr,
                                       uint8_t priority, uint64_t trace_id,
                                       uint64_t trace_span);
    void shm_handshake();
    // Create + attach the descriptor ring (after a successful shm
    // handshake). Failure is silent degradation to the socket path.
    void ring_setup();
    void ring_teardown();
    // Try to post ``req`` as a ring descriptor. Returns 0 when posted (the
    // request is parked in ring_inflight_ until its CQE arrives) or -1 when
    // the caller must fall back to the socket path (ring full / in-flight
    // cap / descriptor body exceeds meta_stride — counted).
    int try_ring_post(std::unique_ptr<Request>* req);
    // Publish one plain (single-op) slot. Caller holds dring_mu_ and has
    // verified space + body fit. Returns whether the server needs a doorbell.
    bool ring_publish_one_locked(std::unique_ptr<Request> req)
        ITS_REQUIRES(dring_mu_);
    // Reactor-side: drain the completion ring, completing parked requests.
    // Returns false on a corrupt ring (fails the connection).
    bool drain_cq();
    // The pool's mapping and descriptor, made on first use; base == nullptr
    // where the segment cannot be opened or mapped.
    ShmMap map_pool(uint16_t pool_id, const std::string& name, uint64_t size);
    // Reactor-side: handle a PutAlloc/GetLoc response. Returns the request
    // back if it must be re-queued (put commit phase), nullptr when done.
    std::unique_ptr<Request> shm_phase(std::unique_ptr<Request> req, uint32_t status);
    void queue_release(uint64_t ticket);
    // Reactor-side: phase one of a put (write: mem[i] into the file fds[i]
    // at locs[i].offset) or a located get's copy (the other way). False
    // when a transfer failed (nothing is published or completed yet).
    bool copy_through_fd(bool write, const std::vector<ShmLoc>& locs,
                         const std::vector<int>& fds, const std::vector<iovec>& mem);

    ClientConfig config_;
    int fd_ = -1;
    int wake_fd_ = -1;
    int epoll_fd_ = -1;
    std::thread thread_;
    std::atomic<bool> connected_{false};
    std::atomic<bool> stop_{false};

    std::mutex submit_mu_;
    std::vector<std::unique_ptr<Request>> submitted_ ITS_GUARDED_BY(submit_mu_);

    // Seqlock-style counter bracketing every reactor region that touches
    // caller memory (writev from tx_payload, readv into rx_addrs, shm
    // memcpys): odd = inside a region. A timed-out sync waiter sets
    // SyncState::abandoned and then waits for this to be even, so after
    // sync_roundtrip returns the reactor can never again touch the caller's
    // buffers (regions check the flag AFTER going odd — Dekker pairing).
    std::atomic<uint64_t> io_seq_{0};
    // Abandoned one-RTT segment op: the reactor must fail the connection
    // (see SyncState::seg_op).
    std::atomic<bool> poison_{false};

    // Reactor-owned state.
    std::deque<std::unique_ptr<Request>> sendq_;
    std::deque<std::unique_ptr<Request>> awaiting_;

    // Response read state.
    RespHeader rhdr_{};
    size_t rhdr_got_ = 0;
    std::vector<uint8_t> rbody_;
    size_t rbody_got_ = 0;
    std::vector<iovec> rx_iov_;
    ScatterCursor rx_cur_;
    uint64_t rx_discard_ = 0;
    bool rx_failed_ = false;  // payload rejected client-side (drained, op errors)
    bool resp_in_progress_ = false;
    bool rx_setup_done_ = false;

    mutable std::mutex mr_mu_;
    std::vector<std::pair<const char*, size_t>> regions_ ITS_GUARDED_BY(mr_mu_);

    // Completion ring (see set_completion_fd). Pushed by the reactor (and by
    // fail_all on close), drained by the owning event loop — and, at
    // teardown, by the closing thread.
    std::atomic<int> comp_fd_{-1};
    std::mutex ring_mu_;
    std::vector<std::pair<uint64_t, int32_t>> ring_ ITS_GUARDED_BY(ring_mu_);
    // Wakeup-coalescing counters (see completion_counters).
    std::atomic<uint64_t> comp_pushed_{0};
    std::atomic<uint64_t> comp_signalled_{0};

    // Client-owned shm staging segments (one-RTT path).
    struct ClientSeg {
        char* base = nullptr;
        size_t size = 0;
        uint16_t id = 0;
        std::string name;  // empty once unlinked (server declined)
        bool server_mapped = false;
    };
    std::vector<ClientSeg> client_segs_ ITS_GUARDED_BY(mr_mu_);
    const ClientSeg* find_seg(const void* base, size_t span) const;

    // Shm fast-path state. Written at connect (handshake) and by the reactor
    // (on-demand mapping of auto-extended pools); guarded for the overlap.
    std::atomic<bool> shm_ok_{false};
    mutable std::mutex shm_mu_;
    std::unordered_map<uint16_t, ShmMap> shm_pools_ ITS_GUARDED_BY(shm_mu_);

    // The shm copies' ledger (put_counters). The copies need no state: they
    // go through ShmMap::fd, and the mapping stays for the bounds check.
    std::atomic<uint64_t> put_file_bytes_{0};
    std::atomic<uint64_t> put_file_calls_{0};
    std::atomic<uint64_t> put_copy_us_{0};
    std::atomic<uint64_t> get_file_bytes_{0};

    // Descriptor-ring state (docs/descriptor_ring.md; "dring" because the
    // PR 2 completion ring above already owns the plain ring_/ring_mu_
    // names). The view and name are written once at connect (ring_setup)
    // and torn down in close(); submit-side cursors + the in-flight map are
    // guarded by dring_mu_ (producers are arbitrary caller threads; the
    // reactor erases on completion). CQ consumption is reactor-only.
    struct RingState;
    std::unique_ptr<RingState> dring_;
    std::atomic<bool> ring_ok_{false};
    mutable std::mutex dring_mu_;
    std::unordered_map<uint64_t, std::unique_ptr<Request>> ring_inflight_
        ITS_GUARDED_BY(dring_mu_);
    uint64_t ring_next_token_ ITS_GUARDED_BY(dring_mu_) = 1;
    uint64_t ring_sq_seq_ ITS_GUARDED_BY(dring_mu_) = 0;  // descriptors posted
    // Completions consumed: reactor-only by design (drain_cq runs on the
    // reactor thread; ring_teardown zeroes it under dring_mu_ after the
    // reactor stopped) — deliberately NOT capability-annotated.
    uint64_t ring_cq_seq_ = 0;
    // Ledger (ring_counters): posted descriptors, doorbells actually sent,
    // ring-full and oversized-meta socket fallbacks, CQ completions.
    std::atomic<uint64_t> ring_posted_{0};
    std::atomic<uint64_t> ring_doorbells_{0};
    std::atomic<uint64_t> ring_full_fallbacks_{0};
    std::atomic<uint64_t> ring_meta_fallbacks_{0};
    std::atomic<uint64_t> ring_completions_{0};

    // Multi-op batch grouping (ring_group_begin/end). Owned by the thread
    // that opened the group; posts from other threads (and all sync ops)
    // bypass the group and take the plain path.
    bool group_active_ ITS_GUARDED_BY(dring_mu_) = false;
    std::thread::id group_owner_ ITS_GUARDED_BY(dring_mu_);
    std::vector<std::unique_ptr<Request>> group_reqs_ ITS_GUARDED_BY(dring_mu_);
    // PR 16 ledger (ring_poll_counters).
    std::atomic<uint64_t> ring_batch_slots_{0};
    std::atomic<uint64_t> ring_batch_ops_{0};
    std::atomic<uint64_t> ring_poll_hits_{0};
    std::atomic<uint64_t> ring_poll_arms_{0};
    // Adaptive poll state: EWMA of inter-CQE gaps + last CQE timestamp.
    // Reactor-only (updated in drain_cq, read before parking) — unguarded
    // by design, like ring_cq_seq_.
    uint64_t ring_gap_ewma_us_ = 0;
    uint64_t ring_last_cqe_us_ = 0;
};

}  // namespace its
