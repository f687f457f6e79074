// Server data plane: single-threaded epoll reactor.
//
// TPU-native analogue of the reference's libuv server
// (/root/reference/src/infinistore.cpp — Client state machine :55-109, on_read
// :887, handle_request :837, register_server :990). The reference grafts libuv
// onto uvloop inside the Python process and moves payloads with server-initiated
// one-sided RDMA; TPU VMs have no ibverbs, so here the data plane is
// cooperative zero-copy socket I/O on the DCN: requests carry metadata bodies,
// payloads are scattered straight between the socket and pinned pool blocks
// with readv/writev (no intermediate copies), and the server runs its own
// reactor thread started from Python via the C API (no uvloop dependency).
//
// Concurrency discipline matches the reference ("single thread right now",
// infinistore.cpp:1): every kv/pool mutation happens on the reactor thread.
// Control-plane calls from Python are marshalled onto the loop through an
// eventfd + closure queue and wait on a future.
#pragma once

#include <netinet/in.h>
#include <sys/uio.h>

#include <atomic>
#include <cstdint>
#include <deque>
#include <functional>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

#include "its/kvstore.h"
#include "its/mempool.h"
#include "its/protocol.h"
#include "its/thread_safety.h"

namespace its {

struct ServerConfig {
    std::string bind_addr = "0.0.0.0";
    int service_port = 22345;
    size_t prealloc_bytes = 16ull << 30;   // reference default 16GB prealloc
    size_t block_size = 64ull << 10;       // reference minimal_allocate_size 64KB
    bool auto_increase = false;            // add pools when usage > 50%
    size_t extend_pool_bytes = kExtendPoolSize;
    bool pin_memory = true;
    // On-demand eviction thresholds (reference hardcodes 0.8/0.95,
    // /root/reference/src/infinistore.cpp:52-53).
    double evict_min_ratio = 0.8;
    double evict_max_ratio = 0.95;
    // Back pools with named shm segments so same-host clients can move
    // payloads with one memcpy instead of the socket (degrades to anonymous
    // memory + socket path automatically when /dev/shm is unavailable).
    bool enable_shm = true;
    // Egress cap per accepted connection in MB/s via SO_MAX_PACING_RATE
    // (caps the server->client GET direction; the client-side knob caps
    // PUTs). 0 = unlimited. See ClientConfig::pacing_rate_mbps.
    uint32_t pacing_rate_mbps = 0;
    // File-backed spill tier (spillfile.h): evicted blocks demote to an
    // mmap'd file in spill_dir instead of being dropped, and promote back
    // on access — capacity beyond RAM. Empty dir or 0 bytes = off (evict
    // drops, the reference's behavior).
    std::string spill_dir;
    size_t spill_bytes = 0;
    // Reactor fairness: one-RTT segment ops (PutFrom/GetInto) run at most
    // ~this many bytes of pool/spill memcpy work per event-loop tick, then
    // yield so other connections are served between slices. Keeps an
    // innocent hot-path read's p99 within ~2x its uncontended value while a
    // spill-heavy batch churns (bench.py contended_* keys). Internal tuning
    // knob (C++-level; not surfaced through the CLI).
    size_t slice_bytes = 128ull << 10;
    // QoS two-level slice scheduler (docs/qos.md). While FOREGROUND work is
    // live — a foreground sliced op pending, or any foreground op seen
    // within the last bg_cooldown_us (hysteresis: engine reads arrive in
    // waves; without the cooldown, background work resumes into the tail of
    // a wave and its completions wake the background client mid-wave) — a
    // BACKGROUND-tagged op's slices are deferred, EXCEPT that one
    // background slice always runs per bg_aging_us of deferral: the
    // starvation-proof aging escape guarantees background >= slice_bytes
    // per bg_aging_us of progress under a permanent foreground flood, so
    // it always drains. Only engages when a tagged background op exists;
    // an all-untagged workload runs the exact pre-QoS FIFO round-robin.
    uint64_t bg_cooldown_us = 500;
    uint64_t bg_aging_us = 500;
};

// Per-op service counters (SURVEY.md §5.1: the reference has no tracing at
// all; we make latency/throughput first-class). Histogram buckets are log2 of
// microseconds: bucket i covers [2^i, 2^(i+1)) us.
struct OpStats {
    // HDR-style histogram: 32 sub-buckets per octave caps quantization
    // error at ~2% (base-2 octaves, 2^(1/32) ~= 1.022 steps) at 2048*8
    // bytes per op — the resolution the derived p50/p99 gauges and the
    // /metrics infinistore_op_duration_us histogram export inherit
    // (docs/observability.md).
    static constexpr int kSubBits = 5;
    static constexpr int kBuckets = 2048;

    uint64_t count = 0;
    uint64_t errors = 0;
    uint64_t bytes_in = 0;
    uint64_t bytes_out = 0;
    uint64_t total_us = 0;
    uint64_t lat_buckets[kBuckets] = {0};

    void record(uint64_t us, uint64_t in_bytes, uint64_t out_bytes, bool ok);
    double percentile_us(double q) const;
    double p50_us() const { return percentile_us(0.50); }
    double p99_us() const { return percentile_us(0.99); }
    // Inclusive upper bound (Prometheus `le`) of bucket ``idx`` in us.
    static uint64_t bucket_le_us(int idx);
};

// One traced op's server-side tick record (docs/observability.md): the
// reactor stamps these for any op whose metadata carried a non-zero trace
// id, into a bounded ring exported through stats_json()["trace"]. Stage
// names on the shared vocabulary: recv_us = server_recv, first/last_us =
// first_slice/last_slice (tracing.SERVER_TICK_STAGES).
struct TraceTick {
    uint64_t trace_id = 0;
    uint64_t parent_id = 0;  // the client span the op rode (wire trace_parent)
    uint8_t op = 0;
    uint8_t prio = 0;
    bool ok = true;
    uint64_t recv_us = 0;   // request fully read, op dispatched
    uint64_t first_us = 0;  // first payload/slice unit of work
    uint64_t last_us = 0;   // last payload/slice unit of work
    uint64_t done_us = 0;   // response enqueued (or error recorded)
    uint64_t bytes = 0;     // payload bytes moved (either direction)
};

class Server {
  public:
    explicit Server(const ServerConfig& config);
    ~Server();

    // Bind + listen + spawn the reactor thread. Returns false on bind failure.
    bool start();
    void stop();
    bool running() const { return running_.load(); }
    int port() const { return bound_port_; }  // actual port (0 in config = ephemeral)

    // Thread-safe control plane: each call runs its body on the reactor thread
    // and blocks the caller until done.
    size_t kvmap_len();
    size_t purge();
    size_t evict(double min_ratio, double max_ratio);
    double usage();
    std::string stats_json();

  private:
    struct Conn;

    void loop();
    void post(std::function<void()> fn);     // enqueue onto reactor, no wait
    void call(std::function<void()> fn);     // enqueue + wait for completion
    void accept_ready();
    void conn_readable(Conn* c);
    void conn_writable(Conn* c);
    void close_conn(Conn* c);
    void dispatch(Conn* c);
    void handle_put_batch(Conn* c);
    void handle_get_batch(Conn* c);
    void handle_tcp_put(Conn* c);
    void handle_shm(Conn* c);
    void handle_simple(Conn* c);
    // Descriptor-ring copy engine (docs/descriptor_ring.md): pop published
    // descriptors out of every attached submission ring into per-conn
    // pending queues (freeing the slots — backpressure relief), start them
    // through the same budget-sliced SegCont machinery the socket segment
    // ops use (QoS classes, aging, trace ticks all preserved), and finish
    // by publishing a completion-ring entry instead of a socket response.
    void handle_ring_attach(Conn* c);
    void drain_rings();
    bool drain_ring_conn(Conn* c);  // false = ring poisoned, close the conn
    void start_ring_descs(Conn* c);
    void start_ring_desc(Conn* c, uint8_t op, uint64_t token, SegBatchMeta m);
    void ring_push_cqe(Conn* c, uint64_t token, uint32_t status, uint64_t bytes);
    void ring_finish(Conn* c, uint32_t status, uint64_t bytes);
    bool alloc_blocks(size_t size, size_t n, std::vector<Lease>* leases);
    // Budget-sliced segment ops (see ServerConfig::slice_bytes).
    void queue_cont(Conn* c);
    void suspend_for_cont(Conn* c);
    void run_cont_slice(Conn* c);
    // GetInto's copy of blocks [first, first + count) into the client's
    // segment: through the pool file's descriptor where the pool is a file.
    void copy_out(Conn* c, char* seg_base, size_t first, size_t count);
    void run_getloc_slice(Conn* c);
    void run_putalloc_slice(Conn* c);
    // Shared promote+pin slice for GetLoc and GetInto's pin phase; the
    // validator rejects a pinned block (replies kStatusInvalidReq).
    enum class PinResult { kDone, kYield, kFinished };
    PinResult pin_slice(Conn* c,
                        const std::function<bool(size_t, const BlockRef&)>& validate);
    void finish_cont(Conn* c, uint32_t status);
    void arm_read(Conn* c, bool want_read);
    void finish_payload(Conn* c);
    void send_status(Conn* c, uint32_t status);
    void send_resp(Conn* c, uint32_t status, std::vector<uint8_t> body,
                   std::vector<iovec> payload, std::vector<BlockRef> refs);
    void send_loc_resp(Conn* c, ShmLocResp& resp,
                       const std::vector<PoolDirEntry>& dir);
    bool shm_mappable(const void* ptr, const std::vector<PoolDirEntry>& dir,
                      PoolLoc* out);
    void flush_out(Conn* c);
    void arm(Conn* c, bool want_write);
    bool ensure_capacity(size_t need_bytes);

    ServerConfig config_;
    std::unique_ptr<MM> mm_;
    std::unique_ptr<SpillFile> spill_;  // may be null (tier off)
    std::unique_ptr<KVStore> kv_;

    int epoll_fd_ = -1;
    int listen_fd_ = -1;
    int wake_fd_ = -1;
    int bound_port_ = 0;
    std::thread thread_;
    std::atomic<bool> running_{false};
    std::atomic<bool> stop_requested_{false};

    std::mutex posted_mu_;
    std::vector<std::function<void()>> posted_ ITS_GUARDED_BY(posted_mu_);

    std::unordered_map<int, std::unique_ptr<Conn>> conns_;
    // Connections with a suspended sliced segment op, split by QoS class.
    // With no BACKGROUND op suspended the foreground queue behaves exactly
    // like the old single cont_queue_; with one, foreground slices run
    // first and background slices run only when foreground is quiet
    // (cont_fg_ empty AND the bg_cooldown_us window expired) or the
    // time-based aging escape fires (see ServerConfig::bg_aging_us and
    // run_cont_pass).
    std::deque<Conn*> cont_fg_;
    std::deque<Conn*> cont_bg_;
    // Monotonic stamps driving the two-level scheduler: the last moment
    // foreground work was seen (op dispatch or fg slice — starts the
    // cooldown window) and the last background slice (drives the
    // time-based aging guarantee).
    uint64_t last_fg_us_ = 0;
    uint64_t last_bg_slice_us_ = 0;
    // Per-class QoS counters, exported under "qos" in stats_json().
    struct QosCounters {
        uint64_t fg_ops = 0;          // tagged-or-default foreground ops dispatched
        uint64_t bg_ops = 0;          // background-tagged ops dispatched
        uint64_t fg_slices = 0;       // sliced-work quanta run per class
        uint64_t bg_slices = 0;
        uint64_t bg_preempted = 0;    // slice slots (passes) bg sat out behind fg
        uint64_t bg_aged = 0;         // bg slices run via the aging escape
        void note(uint8_t prio) {
            (prio == kPriorityBackground ? bg_ops : fg_ops)++;
        }
    } qos_;
    // Count an op dispatch against its class; a foreground op also starts
    // the background-deferral cooldown window.
    void note_op(uint8_t prio);
    void run_cont_pass(int epoll_events_seen, int* idle_streak);
    void run_one_slice(Conn* c, std::deque<Conn*>* queue);
    // True while background work must yield: a foreground sliced op is
    // pending, or foreground activity was seen within the cooldown window.
    bool bg_must_defer() const;
    // Reclaim budgeting for sliced allocations: when slice_mode_ is set,
    // alloc_blocks skips the ratio sweep, caps demote iterations at
    // slice_reclaim_left_, and reports a cap-hit via slice_capped_ (the
    // caller retries next slice instead of failing the op with 507).
    bool slice_mode_ = false;
    bool slice_capped_ = false;
    size_t slice_reclaim_left_ = 0;
    // RAII scope for the above: an exception between set and clear would
    // otherwise leave slice_mode_ stuck true server-wide (silently skipping
    // the ratio evict sweep for every later allocation).
    struct SliceBudget {
        Server* s;
        SliceBudget(Server* srv, size_t budget_blocks) : s(srv) {
            s->slice_mode_ = true;
            // Slack beyond the nominal budget: a few demotes may free no
            // RAM (entries pinned by in-flight ops) through no fault of
            // this op's sizing.
            s->slice_reclaim_left_ = budget_blocks + 4;
        }
        ~SliceBudget() { s->slice_mode_ = false; }
    };
    // close_conn() defers destruction here so callers holding a Conn* across
    // a close (e.g. readable -> dispatch -> flush -> error) never dangle; the
    // reactor clears it between epoll batches.
    std::vector<std::unique_ptr<Conn>> graveyard_;
    std::unordered_map<uint8_t, OpStats> stats_;
    uint64_t conns_accepted_ = 0;
    // Bytes GetInto read out of pools through their descriptors (stats:
    // get_into_file_bytes).
    uint64_t get_file_bytes_ = 0;

    // Descriptor-ring plane: connections with an attached ring (drained
    // every loop pass) and the server half of the ring ledger
    // (stats_json()["ring"] → /metrics infinistore_ring_*).
    std::vector<Conn*> ring_conns_;
    struct RingCounters {
        uint64_t attached = 0;         // lifetime successful attaches
        uint64_t descriptors = 0;      // descriptors (ops) consumed from SQs
        uint64_t doorbells_rx = 0;     // client->server doorbell frames
        uint64_t cq_doorbells_tx = 0;  // server->client doorbell frames
        uint64_t completions = 0;      // CQEs published
        uint64_t bad_descriptors = 0;  // rejected per-descriptor (CQE 400)
        uint64_t torn_descriptors = 0; // generation-tag mismatches (fatal)
        // PR 16 mechanism ledger (docs/descriptor_ring.md): multi-op batch
        // slots consumed / ops unpacked from them, the adaptive pre-park
        // poll outcomes (hit = a descriptor landed inside the busy-poll
        // window, arm = the window expired and the park proceeded), and
        // CQEs published while the client reactor was awake — no doorbell
        // frame needed (the elision the small-op path banks on).
        uint64_t batch_slots = 0;      // kRingSlotFlagBatch slots consumed
        uint64_t batch_ops = 0;        // ops unpacked from batch slots
        uint64_t poll_hits = 0;        // poll window caught a descriptor
        uint64_t poll_arms = 0;        // poll window expired; parked
        uint64_t doorbell_elided = 0;  // CQE published to an awake client
    } ring_counters_;
    // Mirror of run_cont_pass's idle streak for the ring copy engine's
    // adaptive slice budget (see run_cont_slice).
    int idle_streak_ = 0;
    // Adaptive pre-park poll state (ring.h ring_poll_budget): EWMA of
    // descriptor inter-arrival gaps + last-arrival stamp. Reactor-only.
    uint64_t ring_gap_ewma_us_ = 0;
    uint64_t ring_last_desc_us_ = 0;

    // Reactor loop-pass phase accounting (docs/observability.md,
    // profiling section): cumulative CLOCK_MONOTONIC microseconds per
    // pass phase — the epoll wait itself, socket event dispatch,
    // descriptor-ring drain, the sliced-cont pass (slice execution plus
    // its QoS scheduling decisions), and everything else (ring
    // park/doorbell arming, timeout bookkeeping, graveyard). Exported
    // through stats_json()["prof"] -> /metrics infinistore_prof_*; the
    // cost is six vDSO clock reads per pass, amortized against the real
    // work a non-idle pass does (an idle reactor blocks 200ms per pass).
    // Reactor-thread-only, read via call() like every other counter.
    struct ProfCounters {
        uint64_t passes = 0;
        uint64_t wait_us = 0;    // blocked in epoll_wait
        uint64_t events_us = 0;  // accept/readable/writable dispatch
        uint64_t rings_us = 0;   // drain_rings descriptor consumption
        uint64_t slices_us = 0;  // run_cont_pass (slices + QoS decisions)
        uint64_t poll_us = 0;    // adaptive pre-park SQ busy-poll window
        uint64_t other_us = 0;   // park/doorbell arming, bookkeeping
    } prof_;

    // Trace tick ring (docs/observability.md): server_recv/first_slice/
    // last_slice/done stamps for ops that carried a wire trace context.
    // Reactor-thread-only (stats_json reads it via call()); untraced ops
    // never touch it beyond one per-op branch.
    static constexpr int kTraceRing = 128;
    TraceTick trace_ring_[kTraceRing];
    uint64_t trace_next_ = 0;     // total ticks ever recorded
    uint64_t trace_dropped_ = 0;  // ticks the full ring overwrote
    // Per-op stamps live on the Conn (one op in flight per connection);
    // these helpers are no-ops for untraced ops (trace_id == 0).
    void trace_begin(Conn* c, uint64_t trace_id, uint64_t parent, uint8_t prio);
    void trace_slice(Conn* c);
    void trace_finish(Conn* c, uint64_t bytes, bool ok);
};

}  // namespace its
