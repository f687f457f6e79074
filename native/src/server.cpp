#include "its/server.h"

#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <netinet/tcp.h>
#include <string.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/mman.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <time.h>
#include <unistd.h>

#include <algorithm>
#include <cmath>
#include <future>

#include "its/iovec_util.h"
#include "its/net_util.h"
#include "its/log.h"
#include "its/ring.h"
#include "its/streamcopy.h"

namespace its {

namespace {

uint64_t now_us() {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return static_cast<uint64_t>(ts.tv_sec) * 1000000ull + ts.tv_nsec / 1000;
}

// HDR-style sub-bucketed index: values < 2^kSubBits map exactly; above, the
// kSubBits bits below the MSB pick a sub-bucket within the octave.
int lat_bucket(uint64_t us) {
    constexpr int sub = OpStats::kSubBits;
    if (us < (1ull << sub)) return static_cast<int>(us);
    int msb = 63 - __builtin_clzll(us);
    int shift = msb - sub;
    int idx = (1 << sub) + (shift << sub) +
              static_cast<int>((us >> shift) & ((1 << sub) - 1));
    return idx < OpStats::kBuckets ? idx : OpStats::kBuckets - 1;
}

// Inverse bucket geometry (single source for every decoder of lat_bucket's
// index space): bucket ``idx`` covers [base, base + step).
void lat_bucket_range(int idx, uint64_t* base, uint64_t* step) {
    constexpr int sub = OpStats::kSubBits;
    if (idx < (1 << sub)) {
        *base = static_cast<uint64_t>(idx);
        *step = 1;
        return;
    }
    int group = (idx - (1 << sub)) >> sub;
    int s = (idx - (1 << sub)) & ((1 << sub) - 1);
    *base = (static_cast<uint64_t>((1 << sub) + s)) << group;
    *step = 1ull << group;
}

// Geometric midpoint of a bucket (inverse of lat_bucket).
double lat_bucket_mid(int idx) {
    uint64_t base, step;
    lat_bucket_range(idx, &base, &step);
    if (step == 1) return static_cast<double>(base);
    return static_cast<double>(base) + static_cast<double>(step) / 2.0;
}

}  // namespace

void OpStats::record(uint64_t us, uint64_t in_bytes, uint64_t out_bytes, bool ok) {
    count++;
    if (!ok) errors++;
    bytes_in += in_bytes;
    bytes_out += out_bytes;
    total_us += us;
    lat_buckets[lat_bucket(us)]++;
}

uint64_t OpStats::bucket_le_us(int idx) {
    // Inclusive integer upper bound of lat_bucket's bucket ``idx`` (the
    // Prometheus `le` the /metrics histogram export uses).
    uint64_t base, step;
    lat_bucket_range(idx, &base, &step);
    return base + step - 1;
}

double OpStats::percentile_us(double q) const {
    if (count == 0) return 0.0;
    uint64_t seen = 0;
    // Smallest value whose cumulative share reaches q (ceil, not truncate:
    // p50 of 81 samples is rank 41).
    uint64_t rank =
        static_cast<uint64_t>(std::ceil(q * static_cast<double>(count)));
    if (rank == 0) rank = 1;
    for (int i = 0; i < kBuckets; i++) {
        seen += lat_buckets[i];
        if (seen >= rank) return lat_bucket_mid(i);
    }
    return 0.0;
}

// Per-connection state machine (reference Client,
// /root/reference/src/infinistore.cpp:55-109; read states :43-47).
struct Server::Conn {
    enum class RState { kHeader, kBody, kPayload, kDrain, kSuspended };

    int fd = -1;
    bool dead = false;
    RState rstate = RState::kHeader;
    ReqHeader hdr{};
    size_t hdr_got = 0;
    std::vector<uint8_t> body;
    size_t body_got = 0;

    // Payload scatter targets for put paths: socket bytes land directly in
    // pool blocks (the zero-copy half of the old server-side RDMA READ).
    std::vector<iovec> rx_iov;
    ScatterCursor rx_cur;
    std::vector<std::string> pending_keys;
    std::vector<BlockRef> pending_blocks;
    uint64_t drain_remaining = 0;
    uint32_t drain_status = kStatusOk;

    uint8_t cur_op = 0;
    uint64_t op_start_us = 0;

    // Per-op trace stamps (docs/observability.md): set by trace_begin when
    // the metadata carried a wire trace context, published to the server's
    // tick ring by trace_finish. Zero trace_id = untraced (every stamp
    // site is a single-branch no-op).
    uint64_t trace_id = 0;
    uint64_t trace_parent = 0;
    uint64_t trace_prio = 0;
    uint64_t trace_first_us = 0;
    uint64_t trace_last_us = 0;

    struct OutMsg {
        RespHeader hdr;
        std::vector<uint8_t> body;
        std::vector<iovec> payload;
        std::vector<BlockRef> refs;  // keeps blocks alive while streaming
        size_t sent = 0;
        size_t total = 0;
    };
    std::deque<OutMsg> outq;
    bool epollout_armed = false;
    bool epollin_armed = true;

    // Budget-sliced one-RTT segment op (kOpPutFrom / kOpGetInto): the
    // reactor runs at most ServerConfig::slice_bytes of pool/spill memcpy
    // work per loop tick, so a spill-heavy batch cannot stall every other
    // connection for milliseconds (r3 VERDICT weak #5). While suspended the
    // conn's EPOLLIN is disarmed — still one op at a time per connection.
    // Two forms: PutFrom/GetInto carry full phase state; PutAlloc/GetLoc
    // (two-phase shm control ops, no server-side payload copies) suspend
    // with op only and re-dispatch from the still-buffered body next tick —
    // their only unbounded work is the reclaim/promote loop, whose partial
    // progress (demotions, promotions) persists across retries.
    struct SegCont {
        uint8_t op = 0;
        // QoS class of the op this continuation slices (protocol.h Priority):
        // decides which cont queue the conn waits in between slices.
        uint8_t prio = kPriorityForeground;
        SegBatchMeta m;
        enum class Phase { kAlloc, kPin, kCopy } phase = Phase::kAlloc;
        size_t idx = 0;     // blocks allocated (PutFrom) / pinned (GetInto)
        size_t copied = 0;  // blocks memcpy'd
        std::vector<BlockRef> blocks;
        // Descriptor-ring source (docs/descriptor_ring.md): completion goes
        // to the ring (ring_finish) instead of a socket response.
        bool from_ring = false;
        uint64_t ring_token = 0;
    };
    std::unique_ptr<SegCont> cont;
    bool queued_cont = false;

    // Attached descriptor ring (kOpRingAttach). SQ consumption and CQ
    // publication are reactor-thread-only; the client process is the other
    // side of the shared cursors (ring.h discipline). Decoded descriptors
    // wait in the per-class pending queues until the conn's single cont
    // slot frees up — foreground first.
    struct RingSrv {
        RingView view;
        uint64_t sq_seq = 0;  // descriptors consumed
        uint64_t cq_seq = 0;  // completions published
        struct PendingDesc {
            uint8_t op = 0;
            uint64_t token = 0;
            SegBatchMeta m;
        };
        std::deque<PendingDesc> pending_fg, pending_bg;
    };
    std::unique_ptr<RingSrv> ring;

    // Shm fast-path tickets. A put ticket holds allocated-but-unpublished
    // blocks between PutAlloc and PutCommit; a get ticket pins committed
    // blocks while the client copies them out of the mapped pools. Both die
    // with the connection (blocks freed / refs dropped via BlockRef).
    struct PendingPut {
        std::vector<std::string> keys;
        std::vector<BlockRef> blocks;
        // Stamped at the PutAlloc leg so the commit-time stats record spans
        // the whole logical op (alloc RTT + client memcpy + commit RTT), not
        // just the commit leg.
        uint64_t start_us = 0;
    };
    uint64_t next_ticket = 1;
    std::unordered_map<uint64_t, PendingPut> pending_puts;
    std::unordered_map<uint64_t, std::vector<BlockRef>> pending_gets;

    // Client shm segments mapped for the one-RTT pull/push path.
    struct SegMap {
        char* base = nullptr;
        size_t size = 0;
    };
    std::unordered_map<uint16_t, SegMap> segments;

    ~Conn() {
        for (auto& [id, seg] : segments)
            if (seg.base != nullptr) munmap(seg.base, seg.size);
        if (ring != nullptr && ring->view.base != nullptr)
            munmap(ring->view.base, ring->view.size);
    }

    void reset_read() {
        rstate = RState::kHeader;
        hdr_got = 0;
        body.clear();
        body_got = 0;
        rx_iov.clear();
        rx_cur.reset();
        pending_keys.clear();
        pending_blocks.clear();
        drain_remaining = 0;
    }
};

Server::Server(const ServerConfig& config) : config_(config) {
    mm_ = std::make_unique<MM>(config.prealloc_bytes, config.block_size, config.pin_memory,
                               config.enable_shm);
    if (!config.spill_dir.empty() && config.spill_bytes > 0) {
        spill_ = std::make_unique<SpillFile>(config.spill_dir, config.spill_bytes,
                                             config.block_size);
        if (!spill_->ok()) spill_.reset();  // tier disabled; already logged
    }
    kv_ = std::make_unique<KVStore>(mm_.get(), spill_.get());
    // Promotion allocates through the server's configured policy (evict
    // ratios + auto_increase extension) — same treatment as PUT allocations.
    kv_->set_promote_alloc([this](size_t size, std::vector<Lease>* leases) {
        return alloc_blocks(size, 1, leases);
    });
}

Server::~Server() { stop(); }

bool Server::start() {
    install_crash_handler();  // reference installs on register_server (:994-998)
    listen_fd_ = socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
    if (listen_fd_ < 0) return false;
    int one = 1;
    setsockopt(listen_fd_, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));

    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_port = htons(static_cast<uint16_t>(config_.service_port));
    if (inet_pton(AF_INET, config_.bind_addr.c_str(), &addr.sin_addr) != 1) {
        ITS_LOG_ERROR("bad bind address %s", config_.bind_addr.c_str());
        close(listen_fd_);
        listen_fd_ = -1;
        return false;
    }
    if (bind(listen_fd_, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) != 0 ||
        listen(listen_fd_, 128) != 0) {
        ITS_LOG_ERROR("bind/listen on %s:%d failed: %s", config_.bind_addr.c_str(),
                      config_.service_port, strerror(errno));
        close(listen_fd_);
        listen_fd_ = -1;
        return false;
    }
    socklen_t len = sizeof(addr);
    getsockname(listen_fd_, reinterpret_cast<sockaddr*>(&addr), &len);
    bound_port_ = ntohs(addr.sin_port);

    epoll_fd_ = epoll_create1(EPOLL_CLOEXEC);
    wake_fd_ = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
    epoll_event ev{};
    ev.events = EPOLLIN;
    ev.data.fd = listen_fd_;
    epoll_ctl(epoll_fd_, EPOLL_CTL_ADD, listen_fd_, &ev);
    ev.data.fd = wake_fd_;
    epoll_ctl(epoll_fd_, EPOLL_CTL_ADD, wake_fd_, &ev);

    running_.store(true);
    stop_requested_.store(false);
    thread_ = std::thread([this] { loop(); });
    ITS_LOG_INFO("server listening on %s:%d (pool %zu MB, block %zu KB)",
                 config_.bind_addr.c_str(), bound_port_, config_.prealloc_bytes >> 20,
                 config_.block_size >> 10);
    return true;
}

void Server::stop() {
    if (!running_.load()) return;
    stop_requested_.store(true);
    uint64_t one = 1;
    ssize_t rc = write(wake_fd_, &one, sizeof(one));
    (void)rc;
    if (thread_.joinable()) thread_.join();
    // The reactor has exited: now the fds it waited on can close safely.
    close(listen_fd_);
    close(wake_fd_);
    close(epoll_fd_);
    listen_fd_ = wake_fd_ = epoll_fd_ = -1;
    running_.store(false);
}

void Server::post(std::function<void()> fn) {
    {
        std::lock_guard<std::mutex> lock(posted_mu_);
        posted_.push_back(std::move(fn));
    }
    uint64_t one = 1;
    ssize_t rc = write(wake_fd_, &one, sizeof(one));
    (void)rc;
}

void Server::call(std::function<void()> fn) {
    if (std::this_thread::get_id() == thread_.get_id()) {
        fn();
        return;
    }
    if (!running_.load()) {
        // Reactor joined (or never started): state is single-threaded now,
        // run inline instead of posting to a loop that will never drain.
        fn();
        return;
    }
    std::promise<void> done;
    auto fut = done.get_future();
    post([&fn, &done] {
        fn();
        done.set_value();
    });
    fut.wait();
}

size_t Server::kvmap_len() {
    size_t n = 0;
    call([&] { n = kv_->size(); });
    return n;
}

size_t Server::purge() {
    size_t n = 0;
    call([&] { n = kv_->purge(); });
    return n;
}

size_t Server::evict(double min_ratio, double max_ratio) {
    size_t n = 0;
    call([&] { n = kv_->evict(min_ratio, max_ratio); });
    return n;
}

double Server::usage() {
    double u = 0;
    call([&] { u = mm_->usage(); });
    return u;
}

std::string Server::stats_json() {
    std::string out;
    call([&] {
        out = "{\"kvmap_len\":" + std::to_string(kv_->size()) +
              ",\"usage\":" + std::to_string(mm_->usage()) +
              ",\"total_bytes\":" + std::to_string(mm_->total_bytes()) +
              ",\"used_bytes\":" + std::to_string(mm_->used_bytes()) +
              ",\"pools\":" + std::to_string(mm_->pool_count()) +
              ",\"pinned\":" + (mm_->pinned() ? std::string("true") : std::string("false")) +
              ",\"connections\":" + std::to_string(conns_.size()) +
              ",\"conns_accepted\":" + std::to_string(conns_accepted_) +
              ",\"get_into_file_bytes\":" + std::to_string(get_file_bytes_) +
              ",\"spill\":{\"entries\":" + std::to_string(kv_->spilled_entries()) +
              ",\"bytes\":" + std::to_string(kv_->spilled_bytes()) +
              ",\"capacity\":" + std::to_string(kv_->spill_capacity()) +
              ",\"promotions\":" + std::to_string(kv_->spill_promotions()) +
              ",\"dropped\":" + std::to_string(kv_->spill_drops()) + "}" +
              // Two-class QoS scheduler counters (docs/qos.md): per-class
              // dispatch + slice counts, the scheduler's preempt/age
              // decisions, and the live suspended-op queue depths.
              ",\"qos\":{\"fg_ops\":" + std::to_string(qos_.fg_ops) +
              ",\"bg_ops\":" + std::to_string(qos_.bg_ops) +
              ",\"fg_slices\":" + std::to_string(qos_.fg_slices) +
              ",\"bg_slices\":" + std::to_string(qos_.bg_slices) +
              ",\"bg_preempted_slices\":" + std::to_string(qos_.bg_preempted) +
              ",\"bg_aged_slices\":" + std::to_string(qos_.bg_aged) +
              ",\"fg_queued\":" + std::to_string(cont_fg_.size()) +
              ",\"bg_queued\":" + std::to_string(cont_bg_.size()) +
              ",\"bg_cooldown_us\":" + std::to_string(config_.bg_cooldown_us) +
              ",\"bg_aging_us\":" + std::to_string(config_.bg_aging_us) + "}" +
              ",\"suspended_ops\":" + std::to_string(cont_fg_.size() + cont_bg_.size()) +
              // Descriptor-ring plane (docs/descriptor_ring.md): lifetime
              // attach/descriptor/doorbell/completion counters plus the
              // LIVE submission-ring depth (published-but-unconsumed) and
              // decoded-but-not-started pending depth across attached
              // conns. doorbells_rx vs descriptors is the submit-side
              // coalescing ratio the bench watches (one doorbell per doze,
              // not per op).
              ",\"ring\":{\"attached\":" + std::to_string(ring_counters_.attached) +
              ",\"conns\":" + std::to_string(ring_conns_.size()) +
              ",\"descriptors\":" + std::to_string(ring_counters_.descriptors) +
              ",\"doorbells_rx\":" + std::to_string(ring_counters_.doorbells_rx) +
              ",\"cq_doorbells_tx\":" + std::to_string(ring_counters_.cq_doorbells_tx) +
              ",\"completions\":" + std::to_string(ring_counters_.completions) +
              ",\"bad_descriptors\":" + std::to_string(ring_counters_.bad_descriptors) +
              ",\"torn_descriptors\":" + std::to_string(ring_counters_.torn_descriptors) +
              ",\"batch_slots\":" + std::to_string(ring_counters_.batch_slots) +
              ",\"batch_ops\":" + std::to_string(ring_counters_.batch_ops) +
              ",\"poll_hits\":" + std::to_string(ring_counters_.poll_hits) +
              ",\"poll_arms\":" + std::to_string(ring_counters_.poll_arms) +
              ",\"doorbell_elided\":" + std::to_string(ring_counters_.doorbell_elided) +
              ",\"sq_depth\":" + [this] {
                  uint64_t depth = 0;
                  for (Conn* rc : ring_conns_)
                      depth += ring_load_acq(&rc->ring->view.ctrl->sq_tail) -
                               rc->ring->sq_seq;
                  return std::to_string(depth);
              }() +
              ",\"pending\":" + [this] {
                  size_t pending = 0;
                  for (Conn* rc : ring_conns_)
                      pending += rc->ring->pending_fg.size() +
                                 rc->ring->pending_bg.size();
                  return std::to_string(pending);
              }() + "}" +
              // Reactor loop-pass phase accounting (docs/observability.md,
              // profiling section): where each pass's wall time went —
              // the native half of the continuous-profiling plane, the
              // per-phase denominator the /profile sampler's Python-side
              // frames do not see.
              ",\"prof\":{\"passes\":" + std::to_string(prof_.passes) +
              ",\"wait_us\":" + std::to_string(prof_.wait_us) +
              ",\"events_us\":" + std::to_string(prof_.events_us) +
              ",\"rings_us\":" + std::to_string(prof_.rings_us) +
              ",\"slices_us\":" + std::to_string(prof_.slices_us) +
              ",\"poll_us\":" + std::to_string(prof_.poll_us) +
              ",\"other_us\":" + std::to_string(prof_.other_us) + "}" +
              // Server-side trace tick ring (docs/observability.md): the
              // manage plane's /trace endpoint joins these to client spans
              // by trace id; recorded/dropped size the ring's coverage.
              ",\"trace\":{\"recorded\":" + std::to_string(trace_next_) +
              ",\"dropped\":" + std::to_string(trace_dropped_) +
              ",\"entries\":[";
        uint64_t t0 = trace_next_ > kTraceRing ? trace_next_ - kTraceRing : 0;
        for (uint64_t i = t0; i < trace_next_; i++) {
            const TraceTick& t = trace_ring_[i % kTraceRing];
            if (i != t0) out += ",";
            out += "{\"trace_id\":" + std::to_string(t.trace_id) +
                   ",\"parent_id\":" + std::to_string(t.parent_id) +
                   ",\"op\":\"" + std::string(1, static_cast<char>(t.op)) + "\"" +
                   ",\"prio\":" + std::to_string(t.prio) +
                   ",\"ok\":" + std::to_string(t.ok ? 1 : 0) +
                   ",\"recv_us\":" + std::to_string(t.recv_us) +
                   ",\"first_slice_us\":" + std::to_string(t.first_us) +
                   ",\"last_slice_us\":" + std::to_string(t.last_us) +
                   ",\"done_us\":" + std::to_string(t.done_us) +
                   ",\"bytes\":" + std::to_string(t.bytes) + "}";
        }
        out += "]},\"ops\":{";
        bool first = true;
        for (const auto& [op, s] : stats_) {
            if (!first) out += ",";
            first = false;
            out += "\"" + std::string(1, static_cast<char>(op)) + "\":{" +
                   "\"count\":" + std::to_string(s.count) +
                   ",\"errors\":" + std::to_string(s.errors) +
                   ",\"bytes_in\":" + std::to_string(s.bytes_in) +
                   ",\"bytes_out\":" + std::to_string(s.bytes_out) +
                   ",\"total_us\":" + std::to_string(s.total_us) +
                   ",\"p50_us\":" + std::to_string(s.p50_us()) +
                   ",\"p99_us\":" + std::to_string(s.p99_us()) +
                   // Sparse non-empty latency buckets as [le_us, count]
                   // pairs (le inclusive; base-2 octaves, 32 sub-buckets =
                   // ~2% resolution) — the /metrics exporter renders the
                   // cumulative infinistore_op_duration_us histogram from
                   // these, and the p50/p99 gauges above are derived from
                   // the same buckets.
                   ",\"hist_us\":[";
            bool hfirst = true;
            for (int b = 0; b < OpStats::kBuckets; b++) {
                if (s.lat_buckets[b] == 0) continue;
                if (!hfirst) out += ",";
                hfirst = false;
                out += "[" + std::to_string(OpStats::bucket_le_us(b)) + "," +
                       std::to_string(s.lat_buckets[b]) + "]";
            }
            out += "]}";
        }
        out += "}}";
    });
    return out;
}

void Server::loop() {
    constexpr int kMaxEvents = 64;
    epoll_event events[kMaxEvents];
    // Consecutive event-free ticks with sliced work pending (see
    // run_cont_pass for how the streak boosts a lone suspended op).
    int idle_streak = 0;
    while (!stop_requested_.load(std::memory_order_relaxed)) {
        uint64_t pass_t0 = now_us();
        // Pending sliced ops: poll without blocking so their next slice runs
        // right after any ready events (fairness: events first, then
        // slices). Exception: when the ONLY pending work is background
        // slices currently deferred by the foreground cooldown, sleep ~1ms
        // instead of spinning — a busy-polling reactor would burn the
        // single core exactly while the foreground wave it deferred FOR is
        // still running (events still interrupt the sleep instantly, and
        // the aging clock tolerates millisecond granularity).
        int timeout = 200;
        if (!cont_fg_.empty()) {
            timeout = 0;
        } else if (!cont_bg_.empty()) {
            timeout =
                now_us() - last_fg_us_ < config_.bg_cooldown_us ? 1 : 0;
        }
        uint64_t poll_spent = 0;
        if (timeout != 0 && !ring_conns_.empty()) {
            // Adaptive pre-park poll (docs/descriptor_ring.md): while
            // descriptors have been arriving on a fast cadence, busy-poll
            // the submission tails for ~2x the smoothed inter-arrival gap
            // before parking — a hit consumes the next flush with no
            // doorbell frame and no epoll round-trip. The window is gated
            // on a RECENT arrival, so a connection going quiet ages out of
            // polling within kRingPollRecentUs and the reactor dozes at
            // zero CPU. Socket traffic cuts the window short via a
            // zero-timeout epoll peek (level-triggered: the main wait
            // below re-reports whatever the peek saw).
            uint64_t poll_t0 = now_us();
            uint64_t budget =
                (ring_last_desc_us_ != 0 &&
                 poll_t0 - ring_last_desc_us_ <= kRingPollRecentUs)
                    ? ring_poll_budget(ring_gap_ewma_us_)
                    : 0;
            if (budget != 0) {
                uint64_t deadline = poll_t0 + budget;
                bool hit = false;
                while (!stop_requested_.load(std::memory_order_relaxed)) {
                    for (Conn* rc : ring_conns_) {
                        if (ring_load_acq(&rc->ring->view.ctrl->sq_tail) !=
                            rc->ring->sq_seq) {
                            hit = true;
                            break;
                        }
                    }
                    if (hit) break;
                    epoll_event peek;
                    if (epoll_wait(epoll_fd_, &peek, 1, 0) > 0) break;
                    if (now_us() >= deadline) break;
                    // Mandatory on a shared core: the client thread we are
                    // polling against needs cycles to publish.
                    std::this_thread::yield();
                }
                if (hit) {
                    ring_counters_.poll_hits++;
                    timeout = 0;
                } else {
                    ring_counters_.poll_arms++;
                }
                poll_spent = now_us() - poll_t0;
            }
        }
        if (timeout != 0 && !ring_conns_.empty()) {
            // About to block: park on every attached submission ring, then
            // re-check the tails — the Dekker pairing with the client's
            // descriptor publish + flag read guarantees either we see the
            // new tail here or the client sends a doorbell frame.
            for (Conn* rc : ring_conns_)
                ring_flag_park(&rc->ring->view.ctrl->srv_waiting);
            ring_fence();
            for (Conn* rc : ring_conns_) {
                if (ring_load_acq(&rc->ring->view.ctrl->sq_tail) !=
                    rc->ring->sq_seq) {
                    timeout = 0;
                    break;
                }
            }
            if (timeout == 0)
                for (Conn* rc : ring_conns_)
                    ring_flag_clear(&rc->ring->view.ctrl->srv_waiting);
        }
        uint64_t wait_t0 = now_us();
        int n = epoll_wait(epoll_fd_, events, kMaxEvents, timeout);
        uint64_t wait_t1 = now_us();
        for (Conn* rc : ring_conns_)
            ring_flag_clear(&rc->ring->view.ctrl->srv_waiting);
        if (n < 0) {
            if (errno == EINTR) {
                // The interrupted pass still blocked in epoll — book it,
                // or a signal-heavy host undercounts the wait fraction
                // the busy-poll-vs-eventfd receipt reads.
                prof_.passes++;
                prof_.wait_us += wait_t1 - wait_t0;
                prof_.poll_us += poll_spent;
                prof_.other_us += wait_t0 - pass_t0 - poll_spent;
                continue;
            }
            ITS_LOG_ERROR("epoll_wait: %s", strerror(errno));
            break;
        }
        for (int i = 0; i < n; i++) {
            int fd = events[i].data.fd;
            if (fd == listen_fd_) {
                accept_ready();
            } else if (fd == wake_fd_) {
                uint64_t buf;
                while (read(wake_fd_, &buf, sizeof(buf)) > 0) {
                }
                std::vector<std::function<void()>> fns;
                {
                    std::lock_guard<std::mutex> lock(posted_mu_);
                    fns.swap(posted_);
                }
                for (auto& fn : fns) fn();
            } else {
                auto it = conns_.find(fd);
                if (it == conns_.end()) continue;
                Conn* c = it->second.get();
                if (events[i].events & (EPOLLHUP | EPOLLERR)) {
                    close_conn(c);
                    continue;
                }
                if (events[i].events & EPOLLOUT) conn_writable(c);
                // conn_writable may close on error; re-check liveness.
                if (!c->dead && (events[i].events & EPOLLIN)) conn_readable(c);
            }
        }
        uint64_t events_t1 = now_us();
        drain_rings();
        uint64_t rings_t1 = now_us();
        run_cont_pass(n, &idle_streak);
        uint64_t slices_t1 = now_us();
        graveyard_.clear();
        // Phase ledger (docs/observability.md): the pass's wall time
        // attributed to wait / event dispatch / ring drain / cont slices,
        // with the pre-wait bookkeeping (timeout calc, ring park) and the
        // graveyard sweep under "other".
        prof_.passes++;
        prof_.wait_us += wait_t1 - wait_t0;
        prof_.events_us += events_t1 - wait_t1;
        prof_.rings_us += rings_t1 - events_t1;
        prof_.slices_us += slices_t1 - rings_t1;
        prof_.poll_us += poll_spent;
        prof_.other_us += (wait_t0 - pass_t0 - poll_spent) + (now_us() - slices_t1);
    }
    // Drain control closures posted during shutdown so no caller hangs.
    {
        std::vector<std::function<void()>> fns;
        {
            std::lock_guard<std::mutex> lock(posted_mu_);
            fns.swap(posted_);
        }
        for (auto& fn : fns) fn();
    }
    // Teardown on the reactor thread: connection fds only. The listen/wake/
    // epoll fds are closed by stop() AFTER the join — stop() writes to
    // wake_fd_ to interrupt this loop, and closing it here would race that
    // write (a recycled fd number could receive the byte; TSAN-caught).
    for (auto& [fd, c] : conns_) close(fd);
    conns_.clear();
}

void Server::accept_ready() {
    while (true) {
        int fd = accept4(listen_fd_, nullptr, nullptr, SOCK_NONBLOCK | SOCK_CLOEXEC);
        if (fd < 0) return;
        int one = 1;
        setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
        // No explicit SO_SNDBUF/SO_RCVBUF: setting them disables kernel
        // autotuning, which reaches tcp_rmem max (32MB here) and measures
        // ~30% faster than a fixed 4MB clamp on the loopback batched bench.
        set_pacing_rate(fd, config_.pacing_rate_mbps, "server accept");
        auto conn = std::make_unique<Conn>();
        conn->fd = fd;
        epoll_event ev{};
        ev.events = EPOLLIN;
        ev.data.fd = fd;
        epoll_ctl(epoll_fd_, EPOLL_CTL_ADD, fd, &ev);
        conns_.emplace(fd, std::move(conn));
        conns_accepted_++;
        ITS_LOG_DEBUG("accepted connection fd=%d", fd);
    }
}

void Server::close_conn(Conn* c) {
    if (c->dead) return;
    c->dead = true;
    if (c->cont != nullptr) {
        cont_fg_.erase(std::remove(cont_fg_.begin(), cont_fg_.end(), c),
                       cont_fg_.end());
        cont_bg_.erase(std::remove(cont_bg_.begin(), cont_bg_.end(), c),
                       cont_bg_.end());
    }
    if (c->ring != nullptr)
        ring_conns_.erase(std::remove(ring_conns_.begin(), ring_conns_.end(), c),
                          ring_conns_.end());
    epoll_ctl(epoll_fd_, EPOLL_CTL_DEL, c->fd, nullptr);
    close(c->fd);
    auto it = conns_.find(c->fd);
    if (it != conns_.end()) {
        graveyard_.push_back(std::move(it->second));
        conns_.erase(it);
    }
}

void Server::queue_cont(Conn* c) {
    if (!c->queued_cont) {
        bool bg = c->cont != nullptr && c->cont->prio == kPriorityBackground;
        (bg ? cont_bg_ : cont_fg_).push_back(c);
        c->queued_cont = true;
    }
}

// Pop + run one budget slice for the conn at the front of ``queue``,
// re-queueing it (by its op's class) when more slices remain.
void Server::run_one_slice(Conn* c, std::deque<Conn*>* queue) {
    queue->pop_front();
    c->queued_cont = false;
    if (c->dead || c->cont == nullptr) return;
    (c->cont->prio == kPriorityBackground ? qos_.bg_slices : qos_.fg_slices)++;
    run_cont_slice(c);
    if (!c->dead && c->cont != nullptr) queue_cont(c);
}

void Server::note_op(uint8_t prio) {
    qos_.note(prio);
    if (prio != kPriorityBackground) last_fg_us_ = now_us();
}

// ---------------------------------------------------------------------------
// Trace ticks (docs/observability.md). Begin on dispatch of a traced op,
// slice on every unit of payload/slice work, finish where the op's stats
// record — pushing {recv, first_slice, last_slice, done} into the ring the
// manage plane's /trace endpoint joins to client spans by trace id.
// ---------------------------------------------------------------------------

void Server::trace_begin(Conn* c, uint64_t trace_id, uint64_t parent,
                         uint8_t prio) {
    c->trace_id = trace_id;
    if (trace_id == 0) return;
    c->trace_parent = parent;
    c->trace_prio = prio;
    c->trace_first_us = 0;
    c->trace_last_us = 0;
}

void Server::trace_slice(Conn* c) {
    if (c->trace_id == 0) return;
    uint64_t now = now_us();
    if (c->trace_first_us == 0) c->trace_first_us = now;
    c->trace_last_us = now;
}

void Server::trace_finish(Conn* c, uint64_t bytes, bool ok) {
    if (c->trace_id == 0) return;
    TraceTick& t = trace_ring_[trace_next_ % kTraceRing];
    if (trace_next_ >= kTraceRing) trace_dropped_++;
    t.trace_id = c->trace_id;
    t.parent_id = c->trace_parent;
    t.op = c->cur_op;
    t.prio = static_cast<uint8_t>(c->trace_prio);
    t.ok = ok;
    t.recv_us = c->op_start_us;
    t.first_us = c->trace_first_us;
    t.last_us = c->trace_last_us;
    t.done_us = now_us();
    t.bytes = bytes;
    trace_next_++;
    c->trace_id = 0;
    c->trace_parent = 0;
}

bool Server::bg_must_defer() const {
    return !cont_fg_.empty() || now_us() - last_fg_us_ < config_.bg_cooldown_us;
}

// ---------------------------------------------------------------------------
// Descriptor-ring copy engine (docs/descriptor_ring.md). Submission rings
// are drained every loop pass: descriptors validate and queue per-conn by
// QoS class, then ride the SAME budget-sliced SegCont machinery as socket
// segment ops — fg-first scheduling, bg cooldown/aging, trace ticks, and
// the op-latency histograms all behave identically; only the completion
// leaves over the ring.
// ---------------------------------------------------------------------------

void Server::drain_rings() {
    uint64_t before = ring_counters_.descriptors;
    for (size_t i = 0; i < ring_conns_.size();) {
        Conn* c = ring_conns_[i];
        if (!drain_ring_conn(c)) {
            // Torn/corrupt descriptor: the ring is untrustworthy — close
            // the connection (the client fails over / reconnects).
            close_conn(c);
        } else {
            start_ring_descs(c);
        }
        // Either call can close_conn (CQE overflow inside the drain, error
        // CQE on a bad descriptor), which erases c from ring_conns_ — then
        // the element at i is already the NEXT conn and i must not advance.
        if (i < ring_conns_.size() && ring_conns_[i] == c) i++;
    }
    // Feed the adaptive pre-park poll: a pass that consumed descriptors
    // stamps the arrival EWMA (ring.h ring_gap_note) the next park reads.
    if (ring_counters_.descriptors != before)
        ring_gap_note(&ring_gap_ewma_us_, &ring_last_desc_us_, now_us());
}

bool Server::drain_ring_conn(Conn* c) {
    Conn::RingSrv& r = *c->ring;
    uint64_t tail = ring_load_acq(&r.view.ctrl->sq_tail);
    while (r.sq_seq < tail) {
        // Decoded-but-not-started descriptors are bounded by the ring
        // depth: a CONFORMING client caps in-flight ops at cq_slots, so
        // hitting this means a hostile/buggy peer is refilling freed slots
        // without waiting for completions. Stop consuming (sq_head stays
        // put — the natural backpressure) instead of growing an unbounded
        // heap queue; draining resumes as pending ops start.
        if (r.pending_fg.size() + r.pending_bg.size() >= r.view.cq_slots)
            break;
        RingSlot* s = r.view.slot(r.sq_seq);
        if (ring_load_acq(&s->gen) != r.sq_seq + 1) {
            // The publish discipline stores gen before tail, so a mismatch
            // under an advanced tail is a torn or corrupt descriptor.
            ring_counters_.torn_descriptors++;
            ITS_LOG_WARN("ring: torn descriptor at seq %llu fd=%d, closing",
                         static_cast<unsigned long long>(r.sq_seq), c->fd);
            return false;
        }
        uint8_t op = s->op;
        uint64_t token = s->token;
        uint32_t meta_len = s->meta_len;
        if (s->flags & kRingSlotFlagBatch) {
            // Multi-op batch slot: RingBatchHdr + count x (RingBatchEntry +
            // SegBatchMeta). Op k completes under token + k. The whole slot
            // is validated before any op is queued; a malformed slot
            // error-CQEs every token the client parked against it (when the
            // header itself is unreadable, only the base token — there is
            // nothing trustworthy to size the group by).
            const uint8_t* arena =
                reinterpret_cast<const uint8_t*>(r.view.meta_at(r.sq_seq));
            uint16_t cnt = 0;
            bool ok = meta_len >= sizeof(RingBatchHdr) &&
                      meta_len <= r.view.meta_stride;
            if (ok) {
                RingBatchHdr hdr;
                memcpy(&hdr, arena, sizeof(hdr));
                cnt = hdr.count;
                ok = cnt >= 1 && cnt <= kRingBatchMaxOps;
                if (!ok) cnt = 0;  // header untrustworthy
            }
            std::vector<Conn::RingSrv::PendingDesc> decoded;
            if (ok) {
                decoded.reserve(cnt);
                size_t off = sizeof(RingBatchHdr);
                for (uint16_t k = 0; k < cnt && ok; k++) {
                    RingBatchEntry ent;
                    if (off + sizeof(ent) > meta_len) {
                        ok = false;
                        break;
                    }
                    memcpy(&ent, arena + off, sizeof(ent));
                    off += sizeof(ent);
                    ok = (ent.op == kOpPutFrom || ent.op == kOpGetInto) &&
                         ent.meta_len <= meta_len - off;
                    if (!ok) break;
                    try {
                        SegBatchMeta m = SegBatchMeta::decode(arena + off, ent.meta_len);
                        decoded.push_back(
                            Conn::RingSrv::PendingDesc{ent.op, token + k, std::move(m)});
                    } catch (const std::exception&) {
                        ok = false;
                        break;
                    }
                    off += ent.meta_len;
                }
            }
            r.sq_seq++;
            ring_store_rel(&r.view.ctrl->sq_head, r.sq_seq);
            if (!ok) {
                uint64_t fail = cnt != 0 ? cnt : 1;
                ring_counters_.descriptors += fail;
                ring_counters_.bad_descriptors += fail;
                for (uint64_t k = 0; k < fail && !c->dead; k++)
                    ring_push_cqe(c, token + k, kStatusInvalidReq, 0);
                if (c->dead) return true;  // cqe overflow closed it
                continue;
            }
            ring_counters_.descriptors += cnt;
            ring_counters_.batch_slots++;
            ring_counters_.batch_ops += cnt;
            for (auto& d : decoded) {
                auto& q = d.m.priority == kPriorityBackground ? r.pending_bg
                                                              : r.pending_fg;
                q.push_back(std::move(d));
            }
            continue;
        }
        SegBatchMeta m;
        bool ok = (op == kOpPutFrom || op == kOpGetInto) &&
                  meta_len <= r.view.meta_stride;
        if (ok) {
            try {
                m = SegBatchMeta::decode(
                    reinterpret_cast<const uint8_t*>(r.view.meta_at(r.sq_seq)),
                    meta_len);
            } catch (const std::exception&) {
                ok = false;
            }
        }
        // Slot consumed: advance the head so the client can reuse it (the
        // decoded copy above is ours now) — this is the backpressure relief
        // that keeps a deep pipeline posting while ops are still running.
        r.sq_seq++;
        ring_store_rel(&r.view.ctrl->sq_head, r.sq_seq);
        ring_counters_.descriptors++;
        if (!ok) {
            ring_counters_.bad_descriptors++;
            ring_push_cqe(c, token, kStatusInvalidReq, 0);
            if (c->dead) return true;  // cqe overflow closed it
            continue;
        }
        auto& q = m.priority == kPriorityBackground ? r.pending_bg : r.pending_fg;
        q.push_back(Conn::RingSrv::PendingDesc{op, token, std::move(m)});
    }
    return true;
}

// Feed pending descriptors into the conn's single continuation slot —
// foreground before background (a bg descriptor never heads-of-line a
// later fg one), FIFO within a class. Invalid descriptors complete with an
// error CQE right here and the loop moves on.
void Server::start_ring_descs(Conn* c) {
    while (!c->dead && c->cont == nullptr && c->rstate == Conn::RState::kHeader &&
           c->hdr_got == 0) {
        Conn::RingSrv& r = *c->ring;
        auto& q = !r.pending_fg.empty() ? r.pending_fg : r.pending_bg;
        if (q.empty()) return;
        Conn::RingSrv::PendingDesc d = std::move(q.front());
        q.pop_front();
        start_ring_desc(c, d.op, d.token, std::move(d.m));
    }
}

void Server::start_ring_desc(Conn* c, uint8_t op, uint64_t token, SegBatchMeta m) {
    c->cur_op = op;
    c->op_start_us = now_us();
    trace_begin(c, m.trace_id, m.trace_parent, m.priority);
    size_t n = m.keys.size();
    auto seg_it = c->segments.find(m.seg_id);
    uint32_t status = kStatusOk;
    // Same validation the socket dispatch runs (handle_shm PutFrom/GetInto).
    if (n == 0 || m.block_size == 0 || n != m.offsets.size() ||
        seg_it == c->segments.end()) {
        status = kStatusInvalidReq;
    } else {
        const Conn::SegMap& seg = seg_it->second;
        for (uint64_t off : m.offsets) {
            if (off > seg.size || m.block_size > seg.size - off) {
                status = kStatusInvalidReq;
                break;
            }
        }
        if (status == kStatusOk && op == kOpGetInto) {
            for (const auto& key : m.keys) {
                if (!kv_->exists(key)) {
                    status = kStatusKeyNotFound;
                    break;
                }
            }
        }
    }
    if (status != kStatusOk) {
        stats_[op].record(now_us() - c->op_start_us, 0, 0, false);
        trace_finish(c, 0, false);
        ring_push_cqe(c, token, status, 0);
        return;
    }
    note_op(m.priority);
    auto cont = std::make_unique<Conn::SegCont>();
    cont->op = op;
    cont->prio = m.priority;
    cont->m = std::move(m);
    if (op == kOpGetInto) cont->phase = Conn::SegCont::Phase::kPin;
    cont->blocks.reserve(n);
    cont->from_ring = true;
    cont->ring_token = token;
    c->cont = std::move(cont);
    suspend_for_cont(c);  // slices run in this pass's run_cont_pass
}

void Server::ring_push_cqe(Conn* c, uint64_t token, uint32_t status, uint64_t bytes) {
    Conn::RingSrv& r = *c->ring;
    if (r.cq_seq - ring_load_acq(&r.view.ctrl->cq_head) >= r.view.cq_slots) {
        // The client bounds in-flight ring ops to cq_slots, so this can
        // only happen with a broken/hostile client: fail the connection
        // rather than overwrite an unconsumed completion.
        ITS_LOG_WARN("ring: completion ring overflow fd=%d, closing", c->fd);
        close_conn(c);
        return;
    }
    RingCqe* e = r.view.cqe(r.cq_seq);
    e->token = token;
    e->bytes = bytes;
    e->status = status;
    e->flags = 0;
    ring_store_rel(&e->gen, r.cq_seq + 1);
    r.cq_seq++;
    ring_store_rel(&r.view.ctrl->cq_tail, r.cq_seq);
    ring_counters_.completions++;
    ring_fence();
    if (ring_flag_take(&r.view.ctrl->cli_waiting)) {
        // The client reactor parked: one 16-byte doorbell frame wakes it;
        // completions landing while it is awake piggyback silently.
        ring_counters_.cq_doorbells_tx++;
        send_resp(c, kStatusRingEvent, {}, {}, {});
    } else {
        // The client is awake — inside its adaptive poll window or already
        // draining — so this completion needed no doorbell frame at all:
        // the elision the small-op fast path banks on.
        ring_counters_.doorbell_elided++;
    }
}

// Completion of a ring-sourced continuation: stats + trace tick close like
// the socket path, then a CQE instead of a response frame — and the next
// pending descriptor starts immediately (same tick, no doorbell needed).
void Server::ring_finish(Conn* c, uint32_t status, uint64_t bytes) {
    uint64_t token = c->cont->ring_token;
    uint8_t op = c->cont->op;
    bool ok = status == kStatusOk;
    stats_[op].record(now_us() - c->op_start_us, op == kOpPutFrom ? bytes : 0,
                      op == kOpGetInto ? bytes : 0, ok);
    trace_finish(c, bytes, ok);
    c->cont.reset();
    arm_read(c, true);
    c->reset_read();
    ring_push_cqe(c, token, status, bytes);
    if (!c->dead) start_ring_descs(c);
}

// One scheduling pass over the suspended sliced ops, run after each tick's
// epoll events (fairness: events first, then slices).
//
// Two-level discipline: FOREGROUND conts round-robin one slice each — with
// no background op suspended this is EXACTLY the pre-QoS single-queue
// behavior. BACKGROUND conts run a full round-robin only while foreground
// is quiet: no foreground slice pending AND no foreground op seen within
// the last bg_cooldown_us (the wave hysteresis — a decode wave's reads
// arrive microseconds apart, and resuming background between them would
// land its slices, and its completion wakeups, inside the wave).
// While deferred, background still gets ONE slice per bg_aging_us — the
// time-based, starvation-proof aging escape: background always makes
// >= slice_bytes per bg_aging_us of progress, so it drains under ANY
// foreground flood.
//
// Idle-streak boost (pre-existing): slicing costs ~6% of solo batch
// throughput in loop overhead; with exactly one suspended op and a streak
// of event-free polls, run up to 1+streak slices back-to-back. For a
// BACKGROUND cont each extra boost round first peeks epoll with zero
// timeout and stops on any ready event — a foreground request arriving
// mid-boost waits at most one slice, not the whole burst (level-triggered
// epoll re-reports the peeked event to the main loop).
void Server::run_cont_pass(int events_seen, int* idle_streak) {
    size_t total = cont_fg_.size() + cont_bg_.size();
    if (total == 0) {
        *idle_streak = 0;
        idle_streak_ = 0;
        return;
    }
    *idle_streak = events_seen == 0 ? std::min(*idle_streak + 1, 8) : 0;
    idle_streak_ = *idle_streak;  // run_cont_slice's ring budget reads this
    // A solo RING cont spends the idle boost on slice SIZE (one big slice,
    // see run_cont_slice) instead of slice COUNT — same per-tick work and
    // preemption bound, far less per-slice overhead.
    Conn* solo = total == 1
                     ? (cont_fg_.empty() ? cont_bg_.front() : cont_fg_.front())
                     : nullptr;
    bool ring_solo =
        solo != nullptr && solo->cont != nullptr && solo->cont->from_ring;
    int rounds = 1 + (total == 1 && !ring_solo ? *idle_streak : 0);
    for (int r = 0; r < rounds && !(cont_fg_.empty() && cont_bg_.empty()); r++) {
        if (r > 0 && !cont_bg_.empty()) {
            epoll_event peek;
            if (epoll_wait(epoll_fd_, &peek, 1, 0) > 0) break;
        }
        uint64_t now = now_us();
        bool fg_pending = !cont_fg_.empty();
        if (fg_pending) last_fg_us_ = now;
        for (size_t i = 0, n0 = cont_fg_.size(); i < n0 && !cont_fg_.empty(); i++)
            run_one_slice(cont_fg_.front(), &cont_fg_);
        if (cont_bg_.empty()) continue;
        if (fg_pending || now - last_fg_us_ < config_.bg_cooldown_us) {
            if (now - last_bg_slice_us_ >= config_.bg_aging_us) {
                qos_.bg_aged++;
                last_bg_slice_us_ = now;
                run_one_slice(cont_bg_.front(), &cont_bg_);
            } else {
                // One per deferred pass (a pass is one slice slot background
                // sat out), NOT per queued conn — the loop spins fast while
                // foreground slices run, and multiplying by queue depth
                // would inflate the counter by orders of magnitude.
                qos_.bg_preempted++;
            }
        } else {
            last_bg_slice_us_ = now;
            for (size_t i = 0, n0 = cont_bg_.size(); i < n0 && !cont_bg_.empty(); i++)
                run_one_slice(cont_bg_.front(), &cont_bg_);
        }
    }
}

void Server::suspend_for_cont(Conn* c) {
    c->rstate = Conn::RState::kSuspended;
    arm_read(c, false);  // the next pipelined request waits in the kernel
    queue_cont(c);
}

// One budget slice of a suspended PutAlloc. Fast path: the whole remaining
// allocation in one call (free-RAM case completes in the first slice).
// Under pressure: bank a budget-sized chunk per slice — banked BlockRefs
// cannot be stolen by concurrent allocators, so progress is monotone.
void Server::run_putalloc_slice(Conn* c) {
    trace_slice(c);
    Conn::SegCont& ct = *c->cont;
    const size_t n = ct.m.keys.size();
    const size_t bs = ct.m.block_size;
    const size_t budget_blocks = std::max<size_t>(1, config_.slice_bytes / bs);
    size_t remaining = n - ct.blocks.size();
    if (remaining > 0) {
        std::vector<Lease> leases;
        bool ok, capped_full;
        {
            SliceBudget budget(this, budget_blocks);
            ok = alloc_blocks(bs, remaining, &leases);
            capped_full = slice_capped_;
            if (!ok && remaining > budget_blocks) {
                // Bank what a budget-sized chunk can get right now.
                ok = alloc_blocks(bs, std::min(budget_blocks, remaining), &leases);
            }
        }
        if (!ok) {
            if (capped_full || slice_capped_) return;  // retry next tick
            // Reclaim ran dry: genuine 507 (banked blocks free via refs).
            finish_cont(c, kStatusOutOfMemory);
            return;
        }
        for (const auto& lease : leases)
            ct.blocks.push_back(std::make_shared<Block>(mm_.get(), lease.ptr, lease.size));
        if (ct.blocks.size() < n) return;
    }
    // Fully allocated: resolve locations against the CURRENT directory
    // (allocation may have auto-extended a pool) and reply.
    auto dir = mm_->pool_dir();
    ShmLocResp resp;
    resp.ticket = c->next_ticket++;
    resp.locs.reserve(n);
    bool mappable = true;
    for (const auto& b : ct.blocks) {
        PoolLoc loc;
        mappable = mappable && shm_mappable(b->data(), dir, &loc);
        resp.locs.push_back(
            ShmLoc{loc.pool_id, loc.offset, static_cast<uint32_t>(bs)});
    }
    if (!mappable) {
        // Blocks landed in an anonymous-fallback pool: tell the client to
        // retry over the socket path (BlockRefs free the leases).
        finish_cont(c, kStatusRetry);
        return;
    }
    Conn::PendingPut pending;
    pending.keys = std::move(ct.m.keys);
    pending.start_us = c->op_start_us;
    pending.blocks = std::move(ct.blocks);
    c->pending_puts.emplace(resp.ticket, std::move(pending));
    // The tick spans the alloc leg (the client memcpy + commit are their
    // own untraced wire ops); the op-latency stat still spans alloc->commit.
    trace_finish(c, 0, true);
    c->cont.reset();
    arm_read(c, true);
    send_loc_resp(c, resp, dir);
}

void Server::finish_cont(Conn* c, uint32_t status) {
    // Error exit: uncommitted blocks free via BlockRef; nothing touched the
    // client segment yet on any failing path (alloc/pin precede copies).
    if (c->cont->from_ring) {
        ring_finish(c, status, 0);
        return;
    }
    stats_[c->cont->op].record(now_us() - c->op_start_us, 0, 0, false);
    c->cont.reset();
    arm_read(c, true);
    c->reset_read();
    send_status(c, status);
}

// Shared promote+pin slice (GetLoc and GetInto's pin phase). The budget
// charges ACTUAL promotion work (each promotion = a spill read + possibly a
// demote), not key count: a fully RAM-resident batch is all O(1) LRU
// touches and completes in its first slice — the same reactor tick as its
// dispatch — while spill-heavy batches yield every ~half byte-budget of
// promotions. Pins persist in the continuation, so progress is monotone:
// the op completes, or reclaim genuinely runs dry (its own pins exceed
// RAM) and 507s — never a retry livelock.
Server::PinResult Server::pin_slice(
    Conn* c, const std::function<bool(size_t, const BlockRef&)>& validate) {
    Conn::SegCont& ct = *c->cont;
    const size_t n = ct.m.keys.size();
    const size_t budget_blocks =
        std::max<size_t>(1, config_.slice_bytes / ct.m.block_size);
    const size_t promote_cap = std::max<size_t>(1, budget_blocks / 2);
    // Resident gets are ~free but not literally free; cap touches per slice
    // so a huge resident batch still yields within ~tens of microseconds.
    const size_t touch_cap = std::max<size_t>(256, budget_blocks);
    const uint64_t p0 = kv_->spill_promotions();
    size_t touched = 0;
    SliceBudget budget(this, budget_blocks);
    while (ct.idx < n) {
        if (kv_->spill_promotions() - p0 >= promote_cap || touched >= touch_cap)
            return PinResult::kYield;  // slice's work done; pins kept
        BlockRef b = kv_->get(ct.m.keys[ct.idx]);  // LRU touch; promotes
        touched++;
        if (b == nullptr) {
            if (!kv_->exists(ct.m.keys[ct.idx])) {
                // Deleted between slices: a miss, not pressure (checked
                // before slice_capped_ — a plain map miss leaves the flag
                // stale).
                finish_cont(c, kStatusKeyNotFound);
                return PinResult::kFinished;
            }
            if (slice_capped_) return PinResult::kYield;  // pins kept
            // Reclaim ran dry with the key still spilled: the key is cold
            // but ALIVE (typically this op's own pins exceed RAM) — the
            // typed 512, so callers can tell "retry smaller / read via the
            // cold tier" from genuine allocation exhaustion (507).
            finish_cont(c, kStatusColdTier);
            return PinResult::kFinished;
        }
        if (!validate(ct.idx, b)) {
            finish_cont(c, kStatusInvalidReq);
            return PinResult::kFinished;
        }
        ct.blocks.push_back(std::move(b));
        ct.idx++;
    }
    return PinResult::kDone;
}

// One budget slice of a suspended GetLoc (see pin_slice for the budget
// discipline).
void Server::run_getloc_slice(Conn* c) {
    trace_slice(c);
    Conn::SegCont& ct = *c->cont;
    const size_t bs = ct.m.block_size;
    if (pin_slice(c, [bs](size_t, const BlockRef& b) {
            return b->size() <= bs;
        }) != PinResult::kDone) {
        return;
    }
    // All pinned: resolve locations against the CURRENT pool directory
    // (promotion may have auto-extended a pool) and reply.
    auto dir = mm_->pool_dir();
    ShmLocResp resp;
    resp.ticket = c->next_ticket++;
    uint64_t total = 0;
    for (const auto& b : ct.blocks) {
        PoolLoc loc;
        if (!shm_mappable(b->data(), dir, &loc)) {
            // Block lives in an anonymous-fallback pool; the client must
            // fetch over the socket path.
            finish_cont(c, kStatusRetry);
            return;
        }
        resp.locs.push_back(
            ShmLoc{loc.pool_id, loc.offset, static_cast<uint32_t>(b->size())});
        total += b->size();
    }
    c->pending_gets.emplace(resp.ticket, std::move(ct.blocks));
    stats_[kOpGetLoc].record(now_us() - c->op_start_us, 0, total, true);
    trace_finish(c, total, true);
    c->cont.reset();
    arm_read(c, true);
    send_loc_resp(c, resp, dir);
}

// One budget slice of a suspended segment op. Phases keep the original
// all-or-nothing contract: PutFrom allocates everything before copying or
// committing anything; GetInto pins (promotes) everything before the first
// segment write — a 507/400 can therefore still abort cleanly mid-op.
void Server::run_cont_slice(Conn* c) {
    Conn::SegCont& ct = *c->cont;
    if (ct.op == kOpPutAlloc) {
        run_putalloc_slice(c);
        return;
    }
    if (ct.op == kOpGetLoc) {
        run_getloc_slice(c);
        return;
    }
    auto seg_it = c->segments.find(ct.m.seg_id);
    if (seg_it == c->segments.end()) {  // unreachable: validated at dispatch
        finish_cont(c, kStatusInvalidReq);
        return;
    }
    const Conn::SegMap& seg = seg_it->second;
    const size_t n = ct.m.keys.size();
    const size_t bs = ct.m.block_size;
    // Adaptive slice budget for ring-sourced ops (docs/descriptor_ring.md):
    // when this is the ONLY pending sliced op and the loop has seen
    // event-free polls (idle_streak_), grow the quantum exponentially up to
    // 32x (4MB at the default 128KB) — per-slice fixed cost (queue churn,
    // clock reads, loop overhead) was the dominant non-copy term inside
    // first_slice->last_slice. Any epoll event resets the streak, so a
    // contending request waits at most one boosted slice (~300us at
    // streaming-store bandwidth, see streamcopy.h). Socket conts keep the
    // exact legacy budget (off-path behavior unchanged).
    size_t eff_slice_bytes = config_.slice_bytes;
    if (ct.from_ring && cont_fg_.empty() && cont_bg_.empty() && idle_streak_ > 0)
        eff_slice_bytes <<= std::min(idle_streak_, 5);
    const size_t budget_blocks = std::max<size_t>(1, eff_slice_bytes / bs);

    trace_slice(c);  // one tick per PutFrom/GetInto budget slice
    if (ct.op == kOpPutFrom) {
        if (ct.phase == Conn::SegCont::Phase::kAlloc) {
            size_t chunk = std::min(budget_blocks, n - ct.idx);
            // Re-put fast path (kvstore.h overwrite_slot): keys whose
            // current block can be overwritten in place get a nullptr
            // placeholder instead of a fresh block — the copy phase writes
            // straight into the resident block, skipping the per-key
            // lease + make_shared here and the commit + old-block free
            // there. A fresh put (no eligible keys) allocates exactly as
            // before, so the OOM-before-any-commit guarantee is unchanged
            // on that path.
            // Whole-op probe on the first slice: a fully-eligible batch
            // (the steady-state re-put) needs NO allocation at all, and the
            // probe is ~30ns/key — skip straight to the copy phase in one
            // slice instead of sweeping budget_blocks keys per tick.
            if (ct.idx == 0) {
                size_t elig = 0;
                for (size_t i = 0; i < n; i++)
                    if (kv_->overwrite_eligible(ct.m.keys[i], bs)) elig++;
                if (elig == n) {
                    ct.blocks.assign(n, nullptr);
                    ct.idx = n;
                    ct.phase = Conn::SegCont::Phase::kCopy;
                    return;
                }
            }
            size_t need = 0;
            for (size_t i = 0; i < chunk; i++)
                if (!kv_->overwrite_eligible(ct.m.keys[ct.idx + i], bs))
                    need++;
            std::vector<Lease> leases;
            // Budgeted reclaim: a capped demote pass retries next slice
            // instead of 507ing an op the spill tier could still absorb.
            bool ok = true;
            if (need != 0) {
                SliceBudget budget(this, budget_blocks);
                ok = alloc_blocks(bs, need, &leases);
            }
            if (!ok) {
                if (!slice_capped_) finish_cont(c, kStatusOutOfMemory);
                return;  // capped: demotes happened, retry next tick
            }
            size_t li = 0;
            for (size_t i = 0; i < chunk; i++) {
                if (kv_->overwrite_eligible(ct.m.keys[ct.idx + i], bs)) {
                    ct.blocks.push_back(nullptr);
                } else {
                    const Lease& l = leases[li++];
                    ct.blocks.push_back(
                        std::make_shared<Block>(mm_.get(), l.ptr, l.size));
                }
            }
            // Over-allocation corner: a key's eligibility appearing
            // BETWEEN the two sweeps (impossible single-threaded — both
            // run in this slice) would strand a lease; li==need by
            // construction, every lease is owned by a Block above.
            ct.idx += chunk;
            if (ct.idx == n) ct.phase = Conn::SegCont::Phase::kCopy;
            return;
        }
        size_t end = std::min(ct.copied + budget_blocks, n);
        while (ct.copied < end) {
            size_t k = ct.copied;
            if (ct.blocks[k] != nullptr) {
                stream_copy(ct.blocks[k]->data(), seg.base + ct.m.offsets[k],
                            bs);
                kv_->commit(ct.m.keys[k], std::move(ct.blocks[k]));
                ct.copied++;
                continue;
            }
            // Overwrite placeholder from the alloc phase: re-verify NOW —
            // eligibility can lapse between slices (eviction demoted the
            // block, or a GET pinned it).
            BlockRef dst = kv_->overwrite_slot(ct.m.keys[k], bs);
            if (dst != nullptr) {
                stream_copy(dst->data(), seg.base + ct.m.offsets[k], bs);
                ct.copied++;  // entry already committed by identity
                continue;
            }
            // Lapsed: emergency single-block alloc + legacy commit. The
            // only path where OOM can land mid-op (some keys already
            // committed) — it needs eviction or a concurrent pin to race
            // this op between slices AND reclaim to run dry.
            std::vector<Lease> leases;
            bool ok;
            {
                SliceBudget budget(this, budget_blocks);
                ok = alloc_blocks(bs, 1, &leases);
            }
            if (!ok) {
                stream_copy_fence();
                if (!slice_capped_) finish_cont(c, kStatusOutOfMemory);
                return;  // capped: demotes happened, resume here next tick
            }
            BlockRef nb =
                std::make_shared<Block>(mm_.get(), leases[0].ptr, leases[0].size);
            stream_copy(nb->data(), seg.base + ct.m.offsets[k], bs);
            kv_->commit(ct.m.keys[k], std::move(nb));
            ct.copied++;
        }
        // Order the slice's non-temporal stores before anything that
        // publishes them (ring CQE push below, a later GET's socket send).
        stream_copy_fence();
        if (ct.copied == n) {
            if (ct.from_ring) {
                ring_finish(c, kStatusOk, static_cast<uint64_t>(n) * bs);
                return;
            }
            stats_[kOpPutFrom].record(now_us() - c->op_start_us,
                                      static_cast<uint64_t>(n) * bs, 0, true);
            trace_finish(c, static_cast<uint64_t>(n) * bs, true);
            c->cont.reset();
            arm_read(c, true);
            c->reset_read();
            send_resp(c, kStatusOk, {}, {}, {});
        }
        return;
    }

    // kOpGetInto
    if (ct.phase == Conn::SegCont::Phase::kPin) {
        // Shared promotion-work budget (pin_slice); the validator adds the
        // segment bounds check the one-RTT path needs.
        PinResult r = pin_slice(c, [&ct, &seg, bs](size_t k, const BlockRef& b) {
            uint64_t off = ct.m.offsets[k];
            return b->size() <= bs && off <= seg.size && b->size() <= seg.size - off;
        });
        if (r == PinResult::kDone) ct.phase = Conn::SegCont::Phase::kCopy;
        return;
    }
    size_t chunk = std::min(budget_blocks, n - ct.copied);
    copy_out(c, seg.base, ct.copied, chunk);
    ct.copied += chunk;
    if (ct.copied == n) {
        if (ct.from_ring) {
            uint64_t total = 0;
            for (const auto& b : ct.blocks) total += b->size();
            ring_finish(c, kStatusOk, total);
            return;
        }
        std::vector<uint8_t> body;
        WireWriter w(body);
        w.u32(static_cast<uint32_t>(n));
        uint64_t total = 0;
        for (const auto& b : ct.blocks) {
            w.u32(static_cast<uint32_t>(b->size()));
            total += b->size();
        }
        stats_[kOpGetInto].record(now_us() - c->op_start_us, 0, total, true);
        trace_finish(c, total, true);
        c->cont.reset();
        arm_read(c, true);
        c->reset_read();
        send_resp(c, kStatusOk, std::move(body), {}, {});
    }
}

// A value's first read would cost THIS process a first-touch fault a page if
// it went through the pool's mapping (the two-phase put's bytes were written
// by the client, through the pool file's descriptor: no mapping has touched
// them), and the reactor is one thread: every op of every connection waits
// out those faults (docs/design.md, "A put's copy rides the pool's file").
// So where the pool is a file the copy is a preadv on its descriptor
// (file_transfer), one call a run of blocks that lie side by side in it, into
// the segment's slots; a block of an anonymous pool, and a run whose read
// failed, goes through the mapping as before.
void Server::copy_out(Conn* c, char* seg_base, size_t first, size_t count) {
    Conn::SegCont& ct = *c->cont;
    std::vector<iovec> slots(count);
    for (size_t i = 0; i < count; i++)
        slots[i] = iovec{seg_base + ct.m.offsets[first + i], ct.blocks[first + i]->size()};
    for (size_t k = 0, stop; k < count; k = stop) {
        // [k, stop): blocks that lie side by side in one pool.
        const char* src = static_cast<const char*>(ct.blocks[first + k]->data());
        uint64_t bytes = slots[k].iov_len;
        for (stop = k + 1; stop < count && ct.blocks[first + stop]->data() == src + bytes; stop++)
            bytes += slots[stop].iov_len;
        PoolLoc loc = mm_->locate(src);
        int fd = mm_->shm_fd(loc);
        if (fd >= 0 && file_transfer(/*write=*/false, fd, slots, k, stop, loc.offset)) {
            get_file_bytes_ += bytes;
            continue;
        }
        for (size_t i = k; i < stop; i++)
            stream_copy(slots[i].iov_base, ct.blocks[first + i]->data(), slots[i].iov_len);
    }
    // The client reads these bytes the moment the completion publishes;
    // drain the write-combining buffers before the CQE / response leaves.
    stream_copy_fence();
}

void Server::arm(Conn* c, bool want_write) {
    if (c->epollout_armed == want_write) return;
    epoll_event ev{};
    ev.events = (c->epollin_armed ? EPOLLIN : 0u) | (want_write ? EPOLLOUT : 0u);
    ev.data.fd = c->fd;
    epoll_ctl(epoll_fd_, EPOLL_CTL_MOD, c->fd, &ev);
    c->epollout_armed = want_write;
}

void Server::arm_read(Conn* c, bool want_read) {
    if (c->epollin_armed == want_read) return;
    epoll_event ev{};
    ev.events = (want_read ? EPOLLIN : 0u) | (c->epollout_armed ? EPOLLOUT : 0u);
    ev.data.fd = c->fd;
    epoll_ctl(epoll_fd_, EPOLL_CTL_MOD, c->fd, &ev);
    c->epollin_armed = want_read;
}

void Server::conn_readable(Conn* c) {
    while (true) {
        switch (c->rstate) {
            case Conn::RState::kHeader: {
                ssize_t r = read(c->fd, reinterpret_cast<char*>(&c->hdr) + c->hdr_got,
                                 sizeof(ReqHeader) - c->hdr_got);
                if (r == 0) {
                    close_conn(c);
                    return;
                }
                if (r < 0) {
                    if (errno == EAGAIN || errno == EWOULDBLOCK) return;
                    close_conn(c);
                    return;
                }
                c->hdr_got += static_cast<size_t>(r);
                if (c->hdr_got < sizeof(ReqHeader)) break;
                // Bad magic / oversized body closes the connection, as in the
                // reference (/root/reference/src/infinistore.cpp:910-915).
                if (c->hdr.magic != kMagic || c->hdr.body_size > kMaxBodySize) {
                    ITS_LOG_WARN("bad header from fd=%d, closing", c->fd);
                    close_conn(c);
                    return;
                }
                c->cur_op = c->hdr.op;
                c->op_start_us = now_us();
                c->body.resize(c->hdr.body_size);
                c->body_got = 0;
                c->rstate = Conn::RState::kBody;
                if (c->hdr.body_size == 0) {
                    dispatch(c);
                    if (c->dead) return;
                }
                break;
            }
            case Conn::RState::kBody: {
                ssize_t r =
                    read(c->fd, c->body.data() + c->body_got, c->body.size() - c->body_got);
                if (r == 0) {
                    close_conn(c);
                    return;
                }
                if (r < 0) {
                    if (errno == EAGAIN || errno == EWOULDBLOCK) return;
                    close_conn(c);
                    return;
                }
                c->body_got += static_cast<size_t>(r);
                if (c->body_got == c->body.size()) {
                    dispatch(c);
                    if (c->dead) return;
                }
                break;
            }
            case Conn::RState::kPayload: {
                iovec iov[64];
                size_t niov = c->rx_cur.fill(c->rx_iov, iov, 64);
                ssize_t r = readv(c->fd, iov, static_cast<int>(niov));
                if (r == 0) {
                    close_conn(c);
                    return;
                }
                if (r < 0) {
                    if (errno == EAGAIN || errno == EWOULDBLOCK) return;
                    close_conn(c);
                    return;
                }
                c->rx_cur.advance(c->rx_iov, static_cast<size_t>(r));
                trace_slice(c);  // one tick per readv of a traced put payload
                if (c->rx_cur.done(c->rx_iov)) {
                    finish_payload(c);
                    if (c->dead) return;
                }
                break;
            }
            case Conn::RState::kDrain: {
                // OOM path: the client already streamed its payload; consume
                // and discard it so the connection stays usable, then report.
                char scratch[64 << 10];
                size_t want = std::min(c->drain_remaining, sizeof(scratch));
                ssize_t r = read(c->fd, scratch, want);
                if (r == 0) {
                    close_conn(c);
                    return;
                }
                if (r < 0) {
                    if (errno == EAGAIN || errno == EWOULDBLOCK) return;
                    close_conn(c);
                    return;
                }
                c->drain_remaining -= static_cast<size_t>(r);
                if (c->drain_remaining == 0) {
                    uint32_t status = c->drain_status;
                    c->reset_read();
                    send_status(c, status);
                    if (c->dead) return;
                }
                break;
            }
            case Conn::RState::kSuspended:
                // Sliced segment op in progress: EPOLLIN is disarmed, but a
                // level-triggered event already in this tick's batch can
                // still land here — the next request waits in the kernel
                // buffer until the op completes and reads re-arm.
                return;
        }
    }
}

void Server::dispatch(Conn* c) {
    try {
        switch (c->hdr.op) {
            case kOpPutBatch:
                handle_put_batch(c);
                break;
            case kOpGetBatch:
                handle_get_batch(c);
                break;
            case kOpTcpPut:
                handle_tcp_put(c);
                break;
            case kOpShmHello:
            case kOpPutAlloc:
            case kOpPutCommit:
            case kOpGetLoc:
            case kOpRelease:
            case kOpRegSegment:
            case kOpPutFrom:
            case kOpGetInto:
                handle_shm(c);
                break;
            case kOpRingAttach:
                handle_ring_attach(c);
                break;
            case kOpRingDoorbell:
                // Submission-ring doorbell: no body, no response. The wake
                // itself is the payload — drain_rings() runs right after
                // this pass's events.
                ring_counters_.doorbells_rx++;
                c->reset_read();
                break;
            case kOpTcpGet:
            case kOpCheckExist:
            case kOpMatchLastIdx:
            case kOpDeleteKeys:
            case kOpStat:
                handle_simple(c);
                break;
            default:
                ITS_LOG_WARN("unknown op %c from fd=%d, closing", c->hdr.op, c->fd);
                close_conn(c);
                return;
        }
    } catch (const std::exception& e) {
        ITS_LOG_WARN("malformed %c request (%s), closing fd=%d", c->hdr.op, e.what(), c->fd);
        close_conn(c);
    }
}

bool Server::ensure_capacity(size_t need_bytes) {
    (void)need_bytes;
    // Proactive auto-extend above BLOCK_USAGE_RATIO, as the reference's MM
    // signals (/root/reference/src/infinistore.cpp:445, mempool.h:68-78).
    if (config_.auto_increase && mm_->need_extend()) {
        return mm_->extend(config_.extend_pool_bytes);
    }
    return true;
}

bool Server::alloc_blocks(size_t size, size_t n, std::vector<Lease>* leases) {
    slice_capped_ = false;
    // Sliced callers skip the ratio sweep: it can demote min->max ratio of
    // the whole pool in one go (unbounded memcpy work on the reactor); the
    // targeted reclaim below plus the periodic evict task cover them.
    if (!slice_mode_) kv_->evict(config_.evict_min_ratio, config_.evict_max_ratio);
    ensure_capacity(size * n);
    bool ok = mm_->allocate(size, n, nullptr, leases);
    if (!ok && config_.auto_increase && mm_->extend(config_.extend_pool_bytes)) {
        ok = mm_->allocate(size, n, nullptr, leases);
    }
    if (!ok) {
        // A batch larger than the ratio slack: reclaim exactly what it
        // needs (demote with a spill tier, drop without) rather than 507
        // with reclaimable entries present. In-flight refs may keep some
        // freed entries' RAM pinned, so re-try as long as progress is
        // possible; evict_one() draining lru_ bounds the loop. Sliced
        // callers additionally cap the demote iterations per slice and see
        // slice_capped_ (= retry next tick, not OOM).
        size_t bs = mm_->block_size();
        size_t need = ((size + bs - 1) / bs) * bs * n;  // leases are block-granular
        while (mm_->total_bytes() - mm_->used_bytes() < need) {
            if (slice_mode_ && slice_reclaim_left_ == 0) {
                slice_capped_ = true;
                return false;
            }
            if (!kv_->evict_one()) break;
            if (slice_mode_ && slice_reclaim_left_ > 0) slice_reclaim_left_--;
        }
        ok = mm_->allocate(size, n, nullptr, leases);
    }
    return ok;
}

void Server::handle_put_batch(Conn* c) {
    BatchMeta m = BatchMeta::decode(c->body.data(), c->body.size());
    size_t n = m.keys.size();
    // Trace begins at decode so even an op failing validation/404/507
    // closes its server tick (send_status finishes it as not-ok).
    trace_begin(c, m.trace_id, m.trace_parent, m.priority);
    if (n == 0 || m.block_size == 0) {
        c->reset_read();
        send_status(c, kStatusInvalidReq);
        return;
    }
    note_op(m.priority);
    uint64_t need = static_cast<uint64_t>(n) * m.block_size;
    std::vector<Lease> leases;
    if (!alloc_blocks(m.block_size, n, &leases)) {
        // Client streams payload back-to-back with the metadata (no extra
        // RTT), so on OOM we must drain it before answering 507.
        c->body.clear();
        c->rstate = Conn::RState::kDrain;
        c->drain_remaining = need;
        c->drain_status = kStatusOutOfMemory;
        return;
    }
    c->pending_keys = std::move(m.keys);
    c->pending_blocks.reserve(n);
    c->rx_iov.reserve(n);
    for (const auto& lease : leases) {
        c->pending_blocks.push_back(std::make_shared<Block>(mm_.get(), lease.ptr, lease.size));
        c->rx_iov.push_back(iovec{lease.ptr, m.block_size});
    }
    c->rstate = Conn::RState::kPayload;
    c->rx_cur.reset();
}

void Server::handle_tcp_put(Conn* c) {
    TcpPutMeta m = TcpPutMeta::decode(c->body.data(), c->body.size());
    if (m.value_length == 0) {
        c->reset_read();
        send_status(c, kStatusInvalidReq);
        return;
    }
    std::vector<Lease> leases;
    if (!alloc_blocks(m.value_length, 1, &leases)) {
        c->body.clear();
        c->rstate = Conn::RState::kDrain;
        c->drain_remaining = m.value_length;
        c->drain_status = kStatusOutOfMemory;
        return;
    }
    c->pending_keys = {std::move(m.key)};
    c->pending_blocks = {std::make_shared<Block>(mm_.get(), leases[0].ptr, leases[0].size)};
    c->rx_iov = {iovec{leases[0].ptr, m.value_length}};
    c->rstate = Conn::RState::kPayload;
    c->rx_cur.reset();
}

// Shm fast-path control ops: allocate/commit for writes, locate/release for
// reads. Payload never touches the socket — the same-host client memcpys
// straight into/out of the shm-mapped pools (zero-copy in the same sense as
// the reference's one-sided RDMA: one data movement, placed by the server).
void Server::send_loc_resp(Conn* c, ShmLocResp& resp,
                           const std::vector<PoolDirEntry>& dir) {
    // Shared tail of the loc-bearing shm responses: embed the mappable-pool
    // directory and send.
    for (const auto& e : dir)
        resp.pools.push_back(ShmPool{e.pool_id, e.shm_name, e.size});
    std::vector<uint8_t> body;
    resp.encode(body);
    c->reset_read();
    send_resp(c, kStatusOk, std::move(body), {}, {});
}

bool Server::shm_mappable(const void* ptr, const std::vector<PoolDirEntry>& dir,
                          PoolLoc* out) {
    // A location is only usable if its pool is in the shm directory; a pool
    // that fell back to anonymous memory (e.g. /dev/shm quota hit during
    // auto-extend) is reachable only via the socket path.
    *out = mm_->locate(ptr);
    if (!out->found) return false;
    for (const auto& e : dir)
        if (e.pool_id == out->pool_id) return true;
    return false;
}

void Server::handle_shm(Conn* c) {
    switch (c->hdr.op) {
        case kOpShmHello: {
            ShmLocResp resp;
            send_loc_resp(c, resp, mm_->pool_dir());
            return;
        }
        case kOpPutAlloc: {
            BatchMeta m = BatchMeta::decode(c->body.data(), c->body.size());
            trace_begin(c, m.trace_id, m.trace_parent, m.priority);
            size_t n = m.keys.size();
            if (n == 0 || m.block_size == 0 || !mm_->shm_enabled()) {
                c->reset_read();
                send_status(c, kStatusInvalidReq);
                return;
            }
            // Allocation runs budget-sliced (run_putalloc_slice): leases
            // already obtained are BANKED in the continuation as BlockRefs,
            // so progress is monotone even with other connections
            // allocating concurrently — the op completes, or reclaim runs
            // genuinely dry (507). The no-pressure case completes in its
            // first slice, same reactor tick as this dispatch.
            note_op(m.priority);
            auto cont = std::make_unique<Conn::SegCont>();
            cont->op = kOpPutAlloc;
            cont->prio = m.priority;
            cont->m.keys = std::move(m.keys);
            cont->m.block_size = m.block_size;
            cont->blocks.reserve(n);
            c->cont = std::move(cont);
            // First slice inline: the free-RAM case completes right here
            // with no suspension (no epoll re-arms, no extra tick) — unless
            // the op is BACKGROUND class and foreground work is live, in
            // which case it queues for the two-level scheduler instead of
            // jumping it.
            if (m.priority == kPriorityBackground && bg_must_defer()) {
                suspend_for_cont(c);
                return;
            }
            run_putalloc_slice(c);
            if (!c->dead && c->cont != nullptr) suspend_for_cont(c);
            return;
        }
        case kOpPutCommit: {
            TicketMeta m = TicketMeta::decode(c->body.data(), c->body.size());
            auto it = c->pending_puts.find(m.ticket);
            if (it == c->pending_puts.end()) {
                c->reset_read();
                send_status(c, kStatusInvalidReq);
                return;
            }
            uint64_t in_bytes = 0;
            auto& pending = it->second;
            uint64_t op_start = pending.start_us ? pending.start_us : c->op_start_us;
            for (size_t i = 0; i < pending.keys.size(); i++) {
                in_bytes += pending.blocks[i]->size();
                kv_->commit(pending.keys[i], std::move(pending.blocks[i]));
            }
            c->pending_puts.erase(it);
            // Account under 'p' so /stats distinguishes which plane writes
            // rode ('W' socket, 'p' shm two-phase, 'F' one-RTT segment).
            // Latency spans alloc -> commit (see PendingPut::start_us).
            stats_[kOpPutAlloc].record(now_us() - op_start, in_bytes, 0, true);
            c->reset_read();
            send_resp(c, kStatusOk, {}, {}, {});
            return;
        }
        case kOpGetLoc: {
            BatchMeta m = BatchMeta::decode(c->body.data(), c->body.size());
            trace_begin(c, m.trace_id, m.trace_parent, m.priority);
            if (m.keys.empty() || m.block_size == 0 || !mm_->shm_enabled()) {
                c->reset_read();
                send_status(c, kStatusInvalidReq);
                return;
            }
            for (const auto& key : m.keys) {
                if (!kv_->exists(key)) {
                    c->reset_read();
                    send_status(c, kStatusKeyNotFound);
                    return;
                }
            }
            // Promotion (pin) work runs budget-sliced (run_cont_slice):
            // pins persist in the continuation, so progress is monotone —
            // the op either completes or genuinely exhausts reclaim (507).
            note_op(m.priority);
            auto cont = std::make_unique<Conn::SegCont>();
            cont->op = kOpGetLoc;
            cont->prio = m.priority;
            cont->m.keys = std::move(m.keys);
            cont->m.block_size = m.block_size;
            cont->phase = Conn::SegCont::Phase::kPin;
            cont->blocks.reserve(cont->m.keys.size());
            c->cont = std::move(cont);
            // First slice inline: a RAM-resident batch completes right here
            // with no suspension (no epoll re-arms, no extra tick) — same
            // BACKGROUND deferral as PutAlloc above.
            if (m.priority == kPriorityBackground && bg_must_defer()) {
                suspend_for_cont(c);
                return;
            }
            run_getloc_slice(c);
            if (!c->dead && c->cont != nullptr) suspend_for_cont(c);
            return;
        }
        case kOpRelease: {
            TicketMeta m = TicketMeta::decode(c->body.data(), c->body.size());
            c->pending_gets.erase(m.ticket);
            c->pending_puts.erase(m.ticket);  // abort path for unmappable pools
            c->reset_read();  // fire-and-forget: no response
            return;
        }
        case kOpRegSegment: {
            SegMeta m = SegMeta::decode(c->body.data(), c->body.size());
            uint32_t status = kStatusInvalidReq;
            // Only map segments this library created (its. prefix), and only
            // when tmpfs really backs the declared size — a shorter segment
            // would SIGBUS the server on the first memcpy past EOF.
            if (mm_->shm_enabled() && m.size > 0 &&
                m.name.rfind("/its.", 0) == 0 &&
                c->segments.find(m.seg_id) == c->segments.end()) {
                int fd = shm_open(m.name.c_str(), O_RDWR, 0);
                if (fd >= 0) {
                    struct stat st;
                    if (fstat(fd, &st) == 0 &&
                        st.st_size >= static_cast<off_t>(m.size)) {
                        void* mem = mmap(nullptr, m.size, PROT_READ | PROT_WRITE,
                                         MAP_SHARED, fd, 0);
                        if (mem != MAP_FAILED) {
                            c->segments[m.seg_id] =
                                Conn::SegMap{static_cast<char*>(mem), m.size};
                            status = kStatusOk;
                        }
                    }
                    ::close(fd);
                }
            }
            c->reset_read();
            send_status(c, status);
            return;
        }
        case kOpPutFrom: {
            // Pull blocks out of the client segment, commit, single ack —
            // the reference's write path shape (server-initiated RDMA READ,
            // reference src/infinistore.cpp:558-595) on shm. Validation runs
            // here; the alloc/demote and memcpy work runs budget-sliced
            // across loop ticks (run_cont_slice) so other connections are
            // served in between.
            SegBatchMeta m = SegBatchMeta::decode(c->body.data(), c->body.size());
            trace_begin(c, m.trace_id, m.trace_parent, m.priority);
            size_t n = m.keys.size();
            auto seg_it = c->segments.find(m.seg_id);
            if (n == 0 || m.block_size == 0 || n != m.offsets.size() ||
                seg_it == c->segments.end()) {
                c->reset_read();
                send_status(c, kStatusInvalidReq);
                return;
            }
            const Conn::SegMap& seg = seg_it->second;
            for (uint64_t off : m.offsets) {
                if (off > seg.size || m.block_size > seg.size - off) {
                    c->reset_read();
                    send_status(c, kStatusInvalidReq);
                    return;
                }
            }
            note_op(m.priority);
            auto cont = std::make_unique<Conn::SegCont>();
            cont->op = kOpPutFrom;
            cont->prio = m.priority;
            cont->m = std::move(m);
            cont->blocks.reserve(n);
            c->cont = std::move(cont);
            suspend_for_cont(c);
            return;
        }
        case kOpGetInto: {
            // Push stored blocks into the client segment (RDMA WRITE
            // analogue, reference :600-637); resp body carries stored sizes.
            // Existence is checked up front; promotion (pin) and the
            // memcpys run budget-sliced, all-or-nothing before the first
            // segment write (pin phase completes before any copy).
            SegBatchMeta m = SegBatchMeta::decode(c->body.data(), c->body.size());
            trace_begin(c, m.trace_id, m.trace_parent, m.priority);
            if (m.keys.empty() || m.block_size == 0 || m.keys.size() != m.offsets.size() ||
                c->segments.find(m.seg_id) == c->segments.end()) {
                c->reset_read();
                send_status(c, kStatusInvalidReq);
                return;
            }
            for (const auto& key : m.keys) {
                if (!kv_->exists(key)) {
                    c->reset_read();
                    send_status(c, kStatusKeyNotFound);
                    return;
                }
            }
            note_op(m.priority);
            auto cont = std::make_unique<Conn::SegCont>();
            cont->op = kOpGetInto;
            cont->prio = m.priority;
            cont->m = std::move(m);
            cont->phase = Conn::SegCont::Phase::kPin;
            cont->blocks.reserve(cont->m.keys.size());
            c->cont = std::move(cont);
            suspend_for_cont(c);
            return;
        }
        default:
            c->reset_read();
            send_status(c, kStatusInvalidReq);
    }
}

// Map + validate a client-created descriptor ring. Geometry comes from the
// mapped RingCtrl itself (ring_view_init checks magic/version/struct-size
// echoes/bounds); the attach body only names the segment. Same trust rules
// as RegSegment: our own "/its." namespace, tmpfs really backing the
// declared size.
void Server::handle_ring_attach(Conn* c) {
    RingMeta m = RingMeta::decode(c->body.data(), c->body.size());
    uint32_t status = kStatusInvalidReq;
    if (mm_->shm_enabled() && c->ring == nullptr && m.size >= kRingCtrlSpan &&
        m.name.rfind("/its.", 0) == 0) {
        int fd = shm_open(m.name.c_str(), O_RDWR, 0);
        if (fd >= 0) {
            struct stat st;
            if (fstat(fd, &st) == 0 && st.st_size >= static_cast<off_t>(m.size)) {
                void* mem =
                    mmap(nullptr, m.size, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
                if (mem != MAP_FAILED) {
                    auto ring = std::make_unique<Conn::RingSrv>();
                    if (ring_view_init(&ring->view, static_cast<char*>(mem), m.size)) {
                        ring->sq_seq = ring_load_acq(&ring->view.ctrl->sq_tail);
                        ring->cq_seq = ring_load_acq(&ring->view.ctrl->cq_tail);
                        c->ring = std::move(ring);
                        ring_conns_.push_back(c);
                        ring_counters_.attached++;
                        status = kStatusOk;
                    } else {
                        munmap(mem, m.size);
                    }
                }
            }
            ::close(fd);
        }
    }
    c->reset_read();
    send_status(c, status);
}

void Server::finish_payload(Conn* c) {
    // Commit-on-transfer-complete: keys become visible only now (reference
    // commits on RDMA READ completion, /root/reference/src/infinistore.cpp:405-418).
    uint64_t in_bytes = 0;
    for (size_t i = 0; i < c->pending_keys.size(); i++) {
        in_bytes += c->pending_blocks[i]->size();
        kv_->commit(c->pending_keys[i], std::move(c->pending_blocks[i]));
    }
    uint8_t op = c->cur_op;
    uint64_t us = now_us() - c->op_start_us;
    stats_[op].record(us, in_bytes, 0, true);
    trace_finish(c, in_bytes, true);
    c->reset_read();
    send_resp(c, kStatusOk, {}, {}, {});
}

void Server::handle_get_batch(Conn* c) {
    BatchMeta m = BatchMeta::decode(c->body.data(), c->body.size());
    trace_begin(c, m.trace_id, m.trace_parent, m.priority);
    if (m.keys.empty() || m.block_size == 0) {
        c->reset_read();
        send_status(c, kStatusInvalidReq);
        return;
    }
    note_op(m.priority);
    // All keys must exist (reference read_rdma_cache,
    // /root/reference/src/infinistore.cpp:612-617)...
    for (const auto& key : m.keys) {
        if (!kv_->exists(key)) {
            c->reset_read();
            send_status(c, kStatusKeyNotFound);
            return;
        }
    }
    std::vector<BlockRef> refs;
    std::vector<iovec> payload;
    std::vector<uint8_t> body;
    WireWriter w(body);
    w.u32(static_cast<uint32_t>(m.keys.size()));
    uint64_t total = 0;
    for (const auto& key : m.keys) {
        BlockRef b = kv_->get(key);  // touches LRU (reference :629-634)
        if (b == nullptr) {  // spilled + unpromotable: cold but alive — 512
            c->reset_read();
            send_status(c, kStatusColdTier);
            return;
        }
        // ...and each stored size must fit the client's block stride (:620-624).
        if (b->size() > m.block_size) {
            c->reset_read();
            send_status(c, kStatusInvalidReq);
            return;
        }
        w.u32(static_cast<uint32_t>(b->size()));
        payload.push_back(iovec{b->data(), b->size()});
        total += b->size();
        refs.push_back(std::move(b));
    }
    uint8_t op = c->cur_op;
    uint64_t us = now_us() - c->op_start_us;
    stats_[op].record(us, 0, total, true);
    // The whole gather assembled in one pass: first and last slice coincide.
    trace_slice(c);
    trace_finish(c, total, true);
    c->reset_read();
    send_resp(c, kStatusOk, std::move(body), std::move(payload), std::move(refs));
}

void Server::handle_simple(Conn* c) {
    std::vector<uint8_t> body;
    std::vector<iovec> payload;
    std::vector<BlockRef> refs;
    uint32_t status = kStatusOk;
    WireWriter w(body);

    switch (c->hdr.op) {
        case kOpTcpGet: {
            KeyMeta m = KeyMeta::decode(c->body.data(), c->body.size());
            bool present = kv_->exists(m.key);
            BlockRef b = kv_->get(m.key);
            if (b == nullptr) {
                // Present-but-unpromotable (spill tier, RAM pressure) is
                // the typed 512 "cold but alive" — the data survives one
                // tier down; only a truly absent key is 404, and 507 stays
                // reserved for genuine allocation exhaustion.
                status = present ? kStatusColdTier : kStatusKeyNotFound;
            } else {
                payload.push_back(iovec{b->data(), b->size()});
                refs.push_back(std::move(b));
            }
            break;
        }
        case kOpCheckExist: {
            KeyMeta m = KeyMeta::decode(c->body.data(), c->body.size());
            w.u8(kv_->exists(m.key) ? 1 : 0);
            break;
        }
        case kOpMatchLastIdx: {
            KeyListMeta m = KeyListMeta::decode(c->body.data(), c->body.size());
            w.i32(kv_->match_last_index(m.keys));
            break;
        }
        case kOpDeleteKeys: {
            KeyListMeta m = KeyListMeta::decode(c->body.data(), c->body.size());
            w.u32(static_cast<uint32_t>(kv_->remove(m.keys)));
            break;
        }
        case kOpStat: {
            // stats_json() runs inline: we are on the reactor thread.
            std::string s = stats_json();
            body.assign(s.begin(), s.end());
            break;
        }
        default:
            status = kStatusInvalidReq;
    }
    uint64_t out_bytes = 0;
    for (const auto& io : payload) out_bytes += io.iov_len;
    uint8_t op = c->cur_op;
    uint64_t us = now_us() - c->op_start_us;
    stats_[op].record(us, 0, out_bytes, status == kStatusOk);
    c->reset_read();
    send_resp(c, status, std::move(body), std::move(payload), std::move(refs));
}

void Server::send_status(Conn* c, uint32_t status) {
    if (status != kStatusOk) stats_[c->cur_op].record(now_us() - c->op_start_us, 0, 0, false);
    // A traced op erroring out (404/507/400, finish_cont, drain) still
    // closes its server tick — the client span's error status gets its
    // server-side timeline either way.
    trace_finish(c, 0, status == kStatusOk);
    send_resp(c, status, {}, {}, {});
}

void Server::send_resp(Conn* c, uint32_t status, std::vector<uint8_t> body,
                       std::vector<iovec> payload, std::vector<BlockRef> refs) {
    Conn::OutMsg msg;
    msg.hdr.status = status;
    msg.hdr.body_size = static_cast<uint32_t>(body.size());
    uint64_t ptotal = 0;
    for (const auto& io : payload) ptotal += io.iov_len;
    msg.hdr.payload_size = ptotal;
    msg.body = std::move(body);
    msg.payload = std::move(payload);
    msg.refs = std::move(refs);
    msg.total = sizeof(RespHeader) + msg.body.size() + ptotal;
    c->outq.push_back(std::move(msg));
    flush_out(c);
}

void Server::flush_out(Conn* c) {
    while (!c->outq.empty()) {
        Conn::OutMsg& msg = c->outq.front();
        iovec iov[64];
        size_t niov =
            build_send_iov(&msg.hdr, sizeof(RespHeader), msg.body, msg.payload, msg.sent, iov, 64);
        if (niov == 0) {
            c->outq.pop_front();
            continue;
        }
        ssize_t r = writev_nosignal(c->fd, iov, static_cast<int>(niov));
        if (r < 0) {
            if (errno == EAGAIN || errno == EWOULDBLOCK) {
                arm(c, true);
                return;
            }
            close_conn(c);
            return;
        }
        msg.sent += static_cast<size_t>(r);
        if (msg.sent == msg.total) c->outq.pop_front();
    }
    arm(c, false);
}

void Server::conn_writable(Conn* c) { flush_out(c); }

}  // namespace its
