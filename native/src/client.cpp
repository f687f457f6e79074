#include "its/client.h"

#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <netdb.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <string.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/file.h>
#include <sys/mman.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <chrono>
#include <cstdlib>
#include <future>
#include <random>

#include "its/iovec_util.h"
#include "its/net_util.h"
#include "its/log.h"
#include "its/mempool.h"  // shm_registry_* (crash-time segment cleanup)
#include "its/ring.h"

namespace its {

// Descriptor-ring segment (docs/descriptor_ring.md): mapped view + the shm
// name needed to unlink it at close.
struct Connection::RingState {
    RingView view;
    std::string name;
};

// Shared landing zone for sync ops. The waiter and the Request each hold a
// reference, so a caller that times out can abandon the wait and a late
// completion still has a live place to write (no use-after-free).
struct Connection::SyncState {
    std::promise<void> prom;
    // Set for one-RTT segment ops (kOpPutFrom/kOpGetInto): the SERVER moves
    // bytes in the client's mapped segment, so an abandoned op cannot be
    // made safe by client-side drains — the timeout POISONS the connection
    // (see sync_roundtrip) and the segment views die with it.
    bool seg_op = false;
    uint32_t status = kStatusUnavailable;
    std::vector<uint8_t> body;
    uint8_t* payload = nullptr;  // malloc'd; freed here unless the waiter takes it
    size_t payload_size = 0;
    // Set by a timed-out waiter. From that moment the caller may free the
    // buffers the request's iovecs point at, so the reactor must never touch
    // them again: unsent requests are dropped, late get payloads are drained
    // into scratch, and a request half-streamed from caller memory fails the
    // connection (it has been wedged for op_timeout_ms anyway).
    std::atomic<bool> abandoned{false};

    ~SyncState() {
        if (payload != nullptr) free(payload);
    }
};

// RAII bracket for reactor regions that touch caller memory: io_seq_ odd
// while inside. Paired with SyncState::abandoned (see client.h io_seq_).
namespace {
uint64_t now_us() {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return static_cast<uint64_t>(ts.tv_sec) * 1000000ull + ts.tv_nsec / 1000;
}

struct IoSection {
    std::atomic<uint64_t>& seq;
    explicit IoSection(std::atomic<uint64_t>& s) : seq(s) { seq.fetch_add(1); }
    ~IoSection() { seq.fetch_add(1); }
    IoSection(const IoSection&) = delete;
    IoSection& operator=(const IoSection&) = delete;
};
}  // namespace

struct Connection::Request {
    uint8_t op = 0;
    ReqHeader hdr{};
    std::vector<uint8_t> body;
    std::vector<iovec> tx_payload;  // gather sources (user memory / caller buffer)
    size_t sent = 0;
    size_t send_total = 0;
    // Shm fast path: tx_payload/rx_addrs are memcpy endpoints, not wire
    // payload (payload_on_wire=false), and release requests expect no
    // response from the server.
    bool payload_on_wire = true;
    bool no_response = false;

    // Payload owned by the request itself (sync ops that may be abandoned on
    // timeout must not reference caller memory from tx_payload).
    std::vector<uint8_t> owned_payload;

    // get-batch scatter destinations (filled sizes arrive in the resp body)
    std::vector<char*> rx_addrs;
    uint32_t block_size = 0;
    bool alloc_rx = false;  // tcp_get/stat: malloc a payload buffer

    // async completion
    CompletionCb cb = nullptr;
    void* ctx = nullptr;

    // sync completion
    std::shared_ptr<SyncState> sync;

    // reactor-side response capture
    uint8_t* rx_buf = nullptr;
    size_t rx_buf_size = 0;

    // (Re)compute the wire framing before (re)queueing for send.
    void prime() {
        hdr = ReqHeader{kMagic, op, static_cast<uint32_t>(body.size())};
        sent = 0;
        send_total = sizeof(ReqHeader) + body.size();
        if (payload_on_wire)
            for (const auto& io : tx_payload) send_total += io.iov_len;
    }
};

Connection::Connection(const ClientConfig& config) : config_(config) {}

Connection::~Connection() { close(); }

int Connection::connect() {
    install_crash_handler();  // reference installs in setup (:245-249)
    if (connected_.load()) return 0;

    addrinfo hints{};
    hints.ai_family = AF_INET;
    hints.ai_socktype = SOCK_STREAM;
    addrinfo* res = nullptr;
    std::string port = std::to_string(config_.port);
    int rc = getaddrinfo(config_.host.c_str(), port.c_str(), &hints, &res);
    if (rc != 0 || res == nullptr) {
        ITS_LOG_ERROR("resolve %s failed: %s", config_.host.c_str(), gai_strerror(rc));
        return -EHOSTUNREACH;
    }

    fd_ = socket(AF_INET, SOCK_STREAM, 0);
    if (fd_ < 0) {
        freeaddrinfo(res);
        return -errno;
    }
    // Nonblocking connect with a poll() deadline (connect_timeout_ms).
    fcntl(fd_, F_SETFL, fcntl(fd_, F_GETFL, 0) | O_NONBLOCK);
    rc = ::connect(fd_, res->ai_addr, res->ai_addrlen);
    freeaddrinfo(res);
    if (rc != 0 && errno != EINPROGRESS) {
        rc = -errno;
        ::close(fd_);
        fd_ = -1;
        return rc;
    }
    if (rc != 0) {
        pollfd pfd{fd_, POLLOUT, 0};
        rc = poll(&pfd, 1, config_.connect_timeout_ms);
        int err = 0;
        socklen_t elen = sizeof(err);
        getsockopt(fd_, SOL_SOCKET, SO_ERROR, &err, &elen);
        if (rc <= 0 || err != 0) {
            ::close(fd_);
            fd_ = -1;
            return rc <= 0 ? -ETIMEDOUT : -err;
        }
    }
    int one = 1;
    setsockopt(fd_, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    // SO_SNDBUF/SO_RCVBUF intentionally left to kernel autotuning (see
    // server accept path).
    set_pacing_rate(fd_, config_.pacing_rate_mbps, "client");

    epoll_fd_ = epoll_create1(EPOLL_CLOEXEC);
    wake_fd_ = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
    epoll_event ev{};
    ev.events = EPOLLIN;
    ev.data.fd = fd_;
    epoll_ctl(epoll_fd_, EPOLL_CTL_ADD, fd_, &ev);
    ev.data.fd = wake_fd_;
    epoll_ctl(epoll_fd_, EPOLL_CTL_ADD, wake_fd_, &ev);

    stop_.store(false);
    connected_.store(true);
    thread_ = std::thread([this] { reactor(); });
    if (config_.enable_shm) shm_handshake();
    if (shm_ok_.load() && config_.enable_ring) ring_setup();
    ITS_LOG_DEBUG("connected to %s:%d (shm=%d ring=%d)", config_.host.c_str(), config_.port,
                  static_cast<int>(shm_ok_.load()), static_cast<int>(ring_ok_.load()));
    return 0;
}

// Probe the server's shm pool directory and map every pool. All-or-nothing:
// a partially mapped directory (e.g. cross-host client that happens to share
// an shm namespace) disables the fast path rather than risking per-op
// failures.
void Connection::shm_handshake() {
    auto req = std::make_unique<Request>();
    req->op = kOpShmHello;
    std::vector<uint8_t> body;
    // Bounded wait: connect() promises connect_timeout_ms overall; a server
    // that accepted but never answers must not hang the caller forever.
    uint32_t status =
        sync_roundtrip(std::move(req), &body, nullptr, nullptr, config_.connect_timeout_ms);
    if (status != kStatusOk || body.empty()) return;
    try {
        ShmLocResp resp = ShmLocResp::decode(body.data(), body.size());
        if (resp.pools.empty()) return;
        size_t mapped = 0;
        for (const auto& p : resp.pools)
            if (map_pool(p.pool_id, p.name, p.size).base != nullptr) mapped++;
        shm_ok_.store(mapped == resp.pools.size());
    } catch (const std::exception& e) {
        ITS_LOG_WARN("shm handshake parse failed: %s", e.what());
    }
}

Connection::ShmMap Connection::map_pool(uint16_t pool_id, const std::string& name,
                                        uint64_t size) {
    {
        std::lock_guard<std::mutex> lock(shm_mu_);
        auto it = shm_pools_.find(pool_id);
        if (it != shm_pools_.end()) return it->second;
    }
    int fd = shm_open(name.c_str(), O_RDWR, 0);
    if (fd < 0) return {};
    void* mem = mmap(nullptr, size, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
    if (mem == MAP_FAILED) {
        ::close(fd);
        return {};
    }
    // The descriptor stays open beside the mapping: the payload copies go
    // through it (shm_phase). Both go together, in close().
    std::lock_guard<std::mutex> lock(shm_mu_);
    auto [it, inserted] =
        shm_pools_.emplace(pool_id, ShmMap{static_cast<char*>(mem), size, fd});
    if (!inserted) {  // lost a race; keep the existing mapping
        munmap(mem, size);
        ::close(fd);
    }
    return it->second;
}

// Create the descriptor-ring segment and ask the server to attach it.
// Failure at any step is silent degradation: the socket path stays
// byte-identical and every batched op keeps working.
void Connection::ring_setup() {
    uint32_t slots = config_.ring_slots != 0 ? config_.ring_slots : kRingSqSlots;
    if (slots < 2 || (slots & (slots - 1)) != 0) {
        ITS_LOG_WARN("ring_slots=%u invalid (need power of two >= 2); using %u",
                     config_.ring_slots, kRingSqSlots);
        slots = kRingSqSlots;
    }
    uint64_t bytes = ring_segment_bytes(slots, slots, kRingMetaStride);
    char name[96];
    std::random_device rd;
    snprintf(name, sizeof(name), "/its.%d.%08x.ring", static_cast<int>(getpid()), rd());
    int fd = shm_open(name, O_CREAT | O_EXCL | O_RDWR, 0600);
    if (fd < 0) return;
    // Liveness marker for shm_sweep_stale (see alloc_shm_mr): the flock'd fd
    // is intentionally leaked for the connection lifetime.
    flock(fd, LOCK_EX | LOCK_NB);
    if (ftruncate(fd, static_cast<off_t>(bytes)) != 0 ||
        posix_fallocate(fd, 0, static_cast<off_t>(bytes)) != 0) {
        ::close(fd);
        shm_unlink(name);
        return;
    }
    void* mem = mmap(nullptr, bytes, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
    if (mem == MAP_FAILED) {
        ::close(fd);
        shm_unlink(name);
        return;
    }
    shm_registry_add(name);
    // The segment is zero-filled; publish the geometry (plain writes — the
    // server cannot see it until the attach below).
    RingCtrl* ctrl = static_cast<RingCtrl*>(mem);
    ctrl->magic = kRingMagic;
    ctrl->version = kRingVersion;
    ctrl->sq_slots = slots;
    ctrl->cq_slots = slots;
    ctrl->slot_bytes = sizeof(RingSlot);
    ctrl->cqe_bytes = sizeof(RingCqe);
    ctrl->meta_stride = kRingMetaStride;
    auto state = std::make_unique<RingState>();
    if (!ring_view_init(&state->view, static_cast<char*>(mem), bytes)) {
        munmap(mem, bytes);
        shm_registry_remove(name);
        shm_unlink(name);
        return;
    }
    state->name = name;
    auto req = std::make_unique<Request>();
    req->op = kOpRingAttach;
    RingMeta{name, bytes}.encode(req->body);
    uint32_t status =
        sync_roundtrip(std::move(req), nullptr, nullptr, nullptr, config_.connect_timeout_ms);
    if (status != kStatusOk) {
        munmap(mem, bytes);
        shm_registry_remove(name);
        shm_unlink(name);
        ITS_LOG_DEBUG("server declined descriptor ring (%u); socket path only", status);
        return;
    }
    dring_ = std::move(state);
    ring_ok_.store(true);
    ITS_LOG_DEBUG("descriptor ring %s attached (%u slots)", name, slots);
}

void Connection::ring_teardown() {
    ring_ok_.store(false);
    std::lock_guard<std::mutex> lock(dring_mu_);  // vs a late try_ring_post
    if (dring_ == nullptr) return;
    munmap(dring_->view.base, dring_->view.size);
    shm_registry_remove(dring_->name.c_str());
    shm_unlink(dring_->name.c_str());
    dring_.reset();
    ring_sq_seq_ = 0;
    ring_cq_seq_ = 0;
}

std::string Connection::ring_name() const {
    std::lock_guard<std::mutex> lock(dring_mu_);
    return dring_ != nullptr ? dring_->name : std::string();
}

void Connection::ring_counters(uint64_t* posted, uint64_t* doorbells,
                               uint64_t* full_fallbacks, uint64_t* meta_fallbacks,
                               uint64_t* completions) const {
    if (posted != nullptr) *posted = ring_posted_.load(std::memory_order_relaxed);
    if (doorbells != nullptr) *doorbells = ring_doorbells_.load(std::memory_order_relaxed);
    if (full_fallbacks != nullptr)
        *full_fallbacks = ring_full_fallbacks_.load(std::memory_order_relaxed);
    if (meta_fallbacks != nullptr)
        *meta_fallbacks = ring_meta_fallbacks_.load(std::memory_order_relaxed);
    if (completions != nullptr)
        *completions = ring_completions_.load(std::memory_order_relaxed);
}

// Post a built segment op as a ring descriptor: its body (the SegBatchMeta
// encoding the socket path would have sent) is copied into the slot's meta
// region and published with a generation tag — no socket write, no syscall,
// unless the server has parked itself (then exactly one doorbell frame).
int Connection::try_ring_post(std::unique_ptr<Request>* reqp) {
    Request* req = reqp->get();
    bool doorbell = false;
    {
        std::lock_guard<std::mutex> lock(dring_mu_);
        // Re-check under the lock: a concurrent close() tears the ring down
        // after failing the connection.
        if (dring_ == nullptr || !connected_.load()) return -1;
        RingView& v = dring_->view;
        if (req->body.size() > v.meta_stride) {
            ring_meta_fallbacks_.fetch_add(1, std::memory_order_relaxed);
            return -1;
        }
        // Open batch group, posted by its owning thread: capture instead of
        // publishing — ring_group_end packs the whole flush into batch
        // slots. Sync ops never join (their waiter blocks before the group
        // could flush); an op too big to share a slot with even the batch
        // header takes the plain single-op slot below.
        if (group_active_ && req->sync == nullptr &&
            group_owner_ == std::this_thread::get_id() &&
            sizeof(RingBatchHdr) + sizeof(RingBatchEntry) + req->body.size() <=
                v.meta_stride) {
            group_reqs_.push_back(std::move(*reqp));
            return 0;
        }
        uint64_t head = ring_load_acq(&v.ctrl->sq_head);
        if (ring_sq_seq_ - head >= v.sq_slots ||
            ring_inflight_.size() >= v.cq_slots) {
            // Ring-full backpressure: the op rides the socket path instead
            // of blocking the caller (the async submitter may be an event
            // loop). Counted — the bench watches this.
            ring_full_fallbacks_.fetch_add(1, std::memory_order_relaxed);
            return -1;
        }
        doorbell = ring_publish_one_locked(std::move(*reqp));
    }
    if (doorbell) {
        // The server parked in epoll: wake it with one 9-byte frame. While
        // it is awake (the common case under load), posts are socket-free.
        ring_doorbells_.fetch_add(1, std::memory_order_relaxed);
        auto db = std::make_unique<Request>();
        db->op = kOpRingDoorbell;
        db->no_response = true;
        submit(std::move(db));
    }
    return 0;
}

bool Connection::ring_publish_one_locked(std::unique_ptr<Request> req) {
    RingView& v = dring_->view;
    uint64_t seq = ring_sq_seq_;
    uint64_t token = ring_next_token_++;
    memcpy(v.meta_at(seq), req->body.data(), req->body.size());
    RingSlot* s = v.slot(seq);
    s->token = token;
    s->meta_len = static_cast<uint32_t>(req->body.size());
    s->op = req->op;
    s->flags = 0;
    s->reserved = 0;
    ring_store_rel(&s->gen, seq + 1);
    ring_inflight_.emplace(token, std::move(req));
    ring_sq_seq_ = seq + 1;
    ring_store_rel(&v.ctrl->sq_tail, seq + 1);
    ring_posted_.fetch_add(1, std::memory_order_relaxed);
    ring_fence();
    return ring_flag_take(&v.ctrl->srv_waiting);
}

void Connection::ring_group_begin() {
    std::lock_guard<std::mutex> lock(dring_mu_);
    if (group_active_) return;  // first opener wins; others post plain
    group_active_ = true;
    group_owner_ = std::this_thread::get_id();
}

void Connection::ring_group_end() {
    std::vector<std::unique_ptr<Request>> overflow;
    bool doorbell = false;
    {
        std::lock_guard<std::mutex> lock(dring_mu_);
        if (!group_active_) return;
        group_active_ = false;
        if (group_reqs_.empty()) return;
        std::vector<std::unique_ptr<Request>> reqs = std::move(group_reqs_);
        group_reqs_.clear();
        if (dring_ == nullptr || !connected_.load()) {
            overflow = std::move(reqs);
        } else {
            RingView& v = dring_->view;
            size_t i = 0;
            while (i < reqs.size()) {
                uint64_t head = ring_load_acq(&v.ctrl->sq_head);
                if (ring_sq_seq_ - head >= v.sq_slots) break;
                // Greedy pack: how many of the remaining ops share this slot
                // (meta-arena capacity, per-slot op bound, CQ in-flight cap
                // — each packed op consumes one completion entry).
                size_t fit = 0;
                size_t off = sizeof(RingBatchHdr);
                while (i + fit < reqs.size() && fit < kRingBatchMaxOps &&
                       ring_inflight_.size() + fit < v.cq_slots) {
                    size_t need = sizeof(RingBatchEntry) + reqs[i + fit]->body.size();
                    if (off + need > v.meta_stride) break;
                    off += need;
                    fit++;
                }
                if (fit == 0) break;  // in-flight cap (bodies fit by capture check)
                if (fit == 1) {
                    // A lone op posts in the plain single-op format — batch
                    // framing buys nothing and the server skips a decode hop.
                    doorbell |= ring_publish_one_locked(std::move(reqs[i]));
                    i++;
                    continue;
                }
                uint64_t seq = ring_sq_seq_;
                char* arena = v.meta_at(seq);
                uint64_t base = ring_next_token_;
                RingBatchHdr hdr{static_cast<uint16_t>(fit), 0};
                memcpy(arena, &hdr, sizeof(hdr));
                size_t w = sizeof(RingBatchHdr);
                for (size_t k = 0; k < fit; k++) {
                    Request* rq = reqs[i + k].get();
                    RingBatchEntry ent{static_cast<uint32_t>(rq->body.size()), rq->op,
                                       0, 0};
                    memcpy(arena + w, &ent, sizeof(ent));
                    memcpy(arena + w + sizeof(ent), rq->body.data(), rq->body.size());
                    w += sizeof(ent) + rq->body.size();
                }
                RingSlot* s = v.slot(seq);
                s->token = base;  // op k completes under token base + k
                s->meta_len = static_cast<uint32_t>(w);
                s->op = 0;
                s->flags = kRingSlotFlagBatch;
                s->reserved = 0;
                ring_store_rel(&s->gen, seq + 1);
                for (size_t k = 0; k < fit; k++)
                    ring_inflight_.emplace(base + k, std::move(reqs[i + k]));
                ring_next_token_ += fit;
                ring_sq_seq_ = seq + 1;
                ring_store_rel(&v.ctrl->sq_tail, seq + 1);
                ring_posted_.fetch_add(fit, std::memory_order_relaxed);
                ring_batch_slots_.fetch_add(1, std::memory_order_relaxed);
                ring_batch_ops_.fetch_add(fit, std::memory_order_relaxed);
                ring_fence();
                doorbell |= ring_flag_take(&v.ctrl->srv_waiting);
                i += fit;
            }
            // Whatever did not fit rides the socket path — the same counted
            // ring-full backpressure as the plain path, never an error.
            for (; i < reqs.size(); i++) {
                ring_full_fallbacks_.fetch_add(1, std::memory_order_relaxed);
                overflow.push_back(std::move(reqs[i]));
            }
        }
    }
    if (doorbell) {
        ring_doorbells_.fetch_add(1, std::memory_order_relaxed);
        auto db = std::make_unique<Request>();
        db->op = kOpRingDoorbell;
        db->no_response = true;
        submit(std::move(db));
    }
    if (!overflow.empty()) {
        // Inline the submit() enqueue so a refused op (connection already
        // failed) can still be completed instead of silently dropped, and
        // the whole spill shares one reactor wake.
        size_t queued = 0;
        for (auto& r : overflow) {
            bool sent = false;
            {
                std::lock_guard<std::mutex> lock(submit_mu_);
                if (connected_.load()) {
                    r->prime();
                    submitted_.push_back(std::move(r));
                    sent = true;
                }
            }
            if (sent)
                queued++;
            else
                complete(std::move(r), static_cast<int>(kStatusUnavailable),
                         /*take_body=*/false);
        }
        if (queued > 0) {
            uint64_t one = 1;
            ssize_t rc = write(wake_fd_, &one, sizeof(one));
            (void)rc;
        }
    }
}

void Connection::ring_poll_counters(uint64_t* batch_slots, uint64_t* batch_ops,
                                    uint64_t* poll_hits, uint64_t* poll_arms) const {
    if (batch_slots != nullptr)
        *batch_slots = ring_batch_slots_.load(std::memory_order_relaxed);
    if (batch_ops != nullptr)
        *batch_ops = ring_batch_ops_.load(std::memory_order_relaxed);
    if (poll_hits != nullptr)
        *poll_hits = ring_poll_hits_.load(std::memory_order_relaxed);
    if (poll_arms != nullptr)
        *poll_arms = ring_poll_arms_.load(std::memory_order_relaxed);
}

// Reactor-side completion-ring drain. Returns false only on a corrupt ring
// (generation-tag mismatch / unknown token), which fails the connection.
bool Connection::drain_cq() {
    if (!ring_ok_.load(std::memory_order_acquire)) return true;
    RingView& v = dring_->view;
    while (ring_load_acq(&v.ctrl->cq_tail) != ring_cq_seq_) {
        RingCqe* e = v.cqe(ring_cq_seq_);
        if (ring_load_acq(&e->gen) != ring_cq_seq_ + 1) {
            ITS_LOG_ERROR("ring: torn completion at seq %llu",
                          static_cast<unsigned long long>(ring_cq_seq_));
            return false;
        }
        uint64_t token = e->token;
        uint32_t status = e->status;
        std::unique_ptr<Request> req;
        {
            std::lock_guard<std::mutex> lock(dring_mu_);
            auto it = ring_inflight_.find(token);
            if (it != ring_inflight_.end()) {
                req = std::move(it->second);
                ring_inflight_.erase(it);
            }
        }
        ring_cq_seq_++;
        ring_store_rel(&v.ctrl->cq_head, ring_cq_seq_);
        if (req == nullptr) {
            ITS_LOG_ERROR("ring: completion for unknown token");
            return false;
        }
        ring_completions_.fetch_add(1, std::memory_order_relaxed);
        // Feed the adaptive poll budget: back-to-back completions pull the
        // gap EWMA toward zero (poll hard), a quiet ring pushes it past the
        // cap (park immediately). Reactor-owned state, no lock.
        ring_gap_note(&ring_gap_ewma_us_, &ring_last_cqe_us_, now_us());
        complete(std::move(req), static_cast<int>(status), /*take_body=*/false);
    }
    return true;
}

int Connection::submit_any(std::unique_ptr<Request> req) {
    if (ring_ok_.load(std::memory_order_acquire) &&
        (req->op == kOpPutFrom || req->op == kOpGetInto)) {
        if (try_ring_post(&req) == 0) return 0;
    }
    return submit(std::move(req));
}

void Connection::close() {
    if (fd_ < 0) return;
    stop_.store(true);
    uint64_t one = 1;
    ssize_t rc = write(wake_fd_, &one, sizeof(one));
    (void)rc;
    if (thread_.joinable()) thread_.join();
    ::close(fd_);
    ::close(wake_fd_);
    ::close(epoll_fd_);
    fd_ = wake_fd_ = epoll_fd_ = -1;
    connected_.store(false);
    shm_ok_.store(false);
    ring_teardown();  // in-flight ring ops were failed by the reactor's fail_all
    {
        std::lock_guard<std::mutex> lock(shm_mu_);
        for (auto& [id, m] : shm_pools_) {
            munmap(m.base, m.size);
            ::close(m.fd);
        }
        shm_pools_.clear();
    }
    std::lock_guard<std::mutex> lock(mr_mu_);
    for (auto& seg : client_segs_) {
        munmap(seg.base, seg.size);
        if (!seg.name.empty()) {
            shm_unlink(seg.name.c_str());
            shm_registry_remove(seg.name.c_str());
        }
    }
    client_segs_.clear();
    regions_.clear();
}

int Connection::register_mr(void* ptr, size_t size) {
    // Best-effort pin: mlock failure (RLIMIT_MEMLOCK in containers) degrades
    // to unpinned but the region is still registered for validation. Warn
    // once — per-transfer registrations would otherwise spam the log.
    if (mlock(ptr, size) != 0) {
        static std::atomic<bool> warned{false};
        if (!warned.exchange(true))
            ITS_LOG_WARN("mlock(%zu) failed (%s); regions registered unpinned", size,
                         strerror(errno));
    }
    std::lock_guard<std::mutex> lock(mr_mu_);
    regions_.emplace_back(static_cast<const char*>(ptr), size);
    return 0;
}

int Connection::unregister_mr(void* ptr) {
    // Drops the most recent region with this base (transfer-scoped
    // registrations of short-lived host buffers; the reference instead keeps
    // an ever-growing MR cache, reference src/libinfinistore.cpp:702-733).
    std::lock_guard<std::mutex> lock(mr_mu_);
    for (auto it = regions_.rbegin(); it != regions_.rend(); ++it) {
        if (it->first == static_cast<const char*>(ptr)) {
            const char* base = it->first;
            size_t size = it->second;
            regions_.erase(std::next(it).base());
            // munlock unpins whole pages no matter how many registrations
            // cover them, so a duplicate/overlapping registration must keep
            // its pages pinned when this one goes. Subtract every surviving
            // region (expanded to page boundaries, since a shared boundary
            // page must also stay pinned) and unpin only what remains.
            const size_t pg = static_cast<size_t>(sysconf(_SC_PAGESIZE));
            std::vector<std::pair<const char*, const char*>> unpin{
                {base, base + size}};
            for (const auto& [rs, rsz] : regions_) {
                const char* lo = reinterpret_cast<const char*>(
                    reinterpret_cast<uintptr_t>(rs) / pg * pg);
                const char* hi = reinterpret_cast<const char*>(
                    (reinterpret_cast<uintptr_t>(rs + rsz) + pg - 1) / pg * pg);
                std::vector<std::pair<const char*, const char*>> next;
                for (auto [a, b] : unpin) {
                    if (hi <= a || lo >= b) {
                        next.emplace_back(a, b);
                        continue;
                    }
                    if (a < lo) next.emplace_back(a, lo);
                    if (hi < b) next.emplace_back(hi, b);
                }
                unpin.swap(next);
            }
            for (auto [a, b] : unpin)
                munlock(const_cast<char*>(a), static_cast<size_t>(b - a));
            return 0;
        }
    }
    return -1;
}

bool Connection::base_registered(const void* base, size_t span) const {
    const char* p = static_cast<const char*>(base);
    std::lock_guard<std::mutex> lock(mr_mu_);
    for (const auto& [start, size] : regions_) {
        if (p >= start && p + span <= start + size) return true;
    }
    return false;
}

const Connection::ClientSeg* Connection::find_seg(const void* base, size_t span) const {
    const char* p = static_cast<const char*>(base);
    std::lock_guard<std::mutex> lock(mr_mu_);
    for (const auto& seg : client_segs_) {
        if (seg.server_mapped && p >= seg.base && p + span <= seg.base + seg.size)
            return &seg;
    }
    return nullptr;
}

void* Connection::alloc_shm_mr(size_t size) {
    if (!config_.enable_shm || !connected_.load() || size == 0) return nullptr;
    static std::atomic<uint32_t> counter{0};
    uint32_t seq = counter.fetch_add(1);
    char name[96];
    std::random_device rd;
    snprintf(name, sizeof(name), "/its.%d.%08x.c%u", static_cast<int>(getpid()), rd(), seq);
    int fd = shm_open(name, O_CREAT | O_EXCL | O_RDWR, 0600);
    if (fd < 0) return nullptr;
    // Liveness marker for shm_sweep_stale, taken before the (possibly long)
    // fallocate so a server starting concurrently cannot sweep the segment
    // mid-setup.
    flock(fd, LOCK_EX | LOCK_NB);
    if (ftruncate(fd, static_cast<off_t>(size)) != 0 ||
        posix_fallocate(fd, 0, static_cast<off_t>(size)) != 0) {
        ::close(fd);
        shm_unlink(name);
        return nullptr;
    }
    void* mem = mmap(nullptr, size, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
    if (mem == MAP_FAILED) {
        ::close(fd);
        shm_unlink(name);
        return nullptr;
    }
    // Leak fd intentionally: it holds the flock for the connection lifetime
    // (closed implicitly at process exit; the segment itself is unlinked in
    // close()).
    shm_registry_add(name);

    ClientSeg seg;
    seg.base = static_cast<char*>(mem);
    seg.size = size;
    seg.name = name;
    seg.id = static_cast<uint16_t>(seq);  // process-unique (mod 64k), per-conn on the server

    // Ask the server to map it; a remote/shm-less server answers non-200 and
    // we fall back to a plain (still registered) buffer.
    auto req = std::make_unique<Request>();
    req->op = kOpRegSegment;
    SegMeta{seg.id, seg.name, static_cast<uint64_t>(size)}.encode(req->body);
    uint32_t status =
        sync_roundtrip(std::move(req), nullptr, nullptr, nullptr, config_.connect_timeout_ms);
    std::lock_guard<std::mutex> lock(mr_mu_);
    regions_.emplace_back(seg.base, size);  // valid base for every path
    if (status == kStatusOk) {
        seg.server_mapped = true;
        ITS_LOG_DEBUG("shm segment %s (%zu bytes) registered with server", name, size);
    } else {
        shm_registry_remove(name);
        shm_unlink(name);  // mapping stays valid locally until munmap
        seg.name.clear();
        ITS_LOG_DEBUG("server declined shm segment (%u); using plain buffer", status);
    }
    client_segs_.push_back(seg);
    return mem;
}

int Connection::submit(std::unique_ptr<Request> req) {
    req->prime();
    {
        std::lock_guard<std::mutex> lock(submit_mu_);
        if (!connected_.load()) return -1;
        submitted_.push_back(std::move(req));
    }
    uint64_t one = 1;
    ssize_t rc = write(wake_fd_, &one, sizeof(one));
    (void)rc;
    return 0;
}

std::unique_ptr<Connection::Request> Connection::build_put(
    const std::vector<std::string>& keys, const std::vector<uint64_t>& offsets,
    uint32_t block_size, void* base_ptr, uint8_t priority, uint64_t trace_id,
    uint64_t trace_span) {
    if (keys.empty() || keys.size() != offsets.size()) return nullptr;
    uint64_t span = 0;
    for (uint64_t off : offsets) span = std::max(span, off + block_size);
    if (!base_registered(base_ptr, span)) {
        ITS_LOG_ERROR("put_batch: base pointer not inside a registered region");
        return nullptr;
    }
    auto req = std::make_unique<Request>();
    if (const ClientSeg* seg = find_seg(base_ptr, span)) {
        // One-RTT server-pull: the server memcpys straight out of the
        // mapped segment and commits; nothing else to do client-side.
        req->op = kOpPutFrom;
        SegBatchMeta m;
        m.block_size = block_size;
        m.seg_id = seg->id;
        m.keys = keys;
        m.priority = priority;
        m.trace_id = trace_id;
        m.trace_parent = trace_span;
        m.offsets.reserve(offsets.size());
        uint64_t base_off = static_cast<char*>(base_ptr) - seg->base;
        for (uint64_t off : offsets) m.offsets.push_back(base_off + off);
        m.encode(req->body);
        req->payload_on_wire = false;
    } else {
        bool shm = shm_ok_.load();
        req->op = shm ? kOpPutAlloc : kOpPutBatch;
        req->payload_on_wire = !shm;  // shm: blocks are memcpy'd after PutAlloc
        BatchMeta meta{block_size, keys, priority, trace_id, trace_span};
        meta.encode(req->body);
        req->tx_payload.reserve(keys.size());
        for (uint64_t off : offsets)
            req->tx_payload.push_back(iovec{static_cast<char*>(base_ptr) + off, block_size});
    }
    return req;
}

int Connection::put_batch_async(const std::vector<std::string>& keys,
                                const std::vector<uint64_t>& offsets, uint32_t block_size,
                                void* base_ptr, CompletionCb cb, void* ctx,
                                uint8_t priority, uint64_t trace_id, uint64_t trace_span) {
    auto req = build_put(keys, offsets, block_size, base_ptr, priority, trace_id,
                         trace_span);
    if (req == nullptr) return -1;
    req->cb = cb;
    req->ctx = ctx;
    return submit_any(std::move(req));
}

int Connection::put_batch(const std::vector<std::string>& keys,
                          const std::vector<uint64_t>& offsets, uint32_t block_size,
                          void* base_ptr, uint8_t priority, uint64_t trace_id,
                          uint64_t trace_span) {
    auto req = build_put(keys, offsets, block_size, base_ptr, priority, trace_id,
                         trace_span);
    if (req == nullptr) return -static_cast<int>(kStatusInvalidReq);
    uint32_t status = sync_roundtrip(std::move(req), nullptr, nullptr, nullptr);
    return status == kStatusOk ? 0 : -static_cast<int>(status);
}

std::unique_ptr<Connection::Request> Connection::build_get(
    const std::vector<std::string>& keys, const std::vector<uint64_t>& offsets,
    uint32_t block_size, void* base_ptr, uint8_t priority, uint64_t trace_id,
    uint64_t trace_span) {
    if (keys.empty() || keys.size() != offsets.size()) return nullptr;
    uint64_t span = 0;
    for (uint64_t off : offsets) span = std::max(span, off + block_size);
    if (!base_registered(base_ptr, span)) {
        ITS_LOG_ERROR("get_batch: base pointer not inside a registered region");
        return nullptr;
    }
    auto req = std::make_unique<Request>();
    if (const ClientSeg* seg = find_seg(base_ptr, span)) {
        // One-RTT server-push into the mapped segment; sizes land in-place.
        req->op = kOpGetInto;
        SegBatchMeta m;
        m.block_size = block_size;
        m.seg_id = seg->id;
        m.keys = keys;
        m.priority = priority;
        m.trace_id = trace_id;
        m.trace_parent = trace_span;
        m.offsets.reserve(offsets.size());
        uint64_t base_off = static_cast<char*>(base_ptr) - seg->base;
        for (uint64_t off : offsets) m.offsets.push_back(base_off + off);
        m.encode(req->body);
    } else {
        req->op = shm_ok_.load() ? kOpGetLoc : kOpGetBatch;
        BatchMeta meta{block_size, keys, priority, trace_id, trace_span};
        meta.encode(req->body);
        req->block_size = block_size;
        req->rx_addrs.reserve(keys.size());
        for (uint64_t off : offsets)
            req->rx_addrs.push_back(static_cast<char*>(base_ptr) + off);
    }
    return req;
}

int Connection::get_batch_async(const std::vector<std::string>& keys,
                                const std::vector<uint64_t>& offsets, uint32_t block_size,
                                void* base_ptr, CompletionCb cb, void* ctx,
                                uint8_t priority, uint64_t trace_id, uint64_t trace_span) {
    auto req = build_get(keys, offsets, block_size, base_ptr, priority, trace_id,
                         trace_span);
    if (req == nullptr) return -1;
    req->cb = cb;
    req->ctx = ctx;
    return submit_any(std::move(req));
}

int Connection::get_batch(const std::vector<std::string>& keys,
                          const std::vector<uint64_t>& offsets, uint32_t block_size,
                          void* base_ptr, uint8_t priority, uint64_t trace_id,
                          uint64_t trace_span) {
    auto req = build_get(keys, offsets, block_size, base_ptr, priority, trace_id,
                         trace_span);
    if (req == nullptr) return -static_cast<int>(kStatusInvalidReq);
    uint32_t status = sync_roundtrip(std::move(req), nullptr, nullptr, nullptr);
    return status == kStatusOk ? 0 : -static_cast<int>(status);
}

uint32_t Connection::sync_roundtrip(std::unique_ptr<Request> req,
                                    std::vector<uint8_t>* body_out, uint8_t** payload_out,
                                    size_t* payload_size_out, int timeout_ms) {
    auto state = std::make_shared<SyncState>();
    state->seg_op = req->op == kOpPutFrom || req->op == kOpGetInto;
    req->sync = state;
    auto fut = state->prom.get_future();
    if (submit_any(std::move(req)) != 0) return kStatusUnavailable;
    bool forever = false;
    if (timeout_ms < 0) {
        // Default deadline from config; config <= 0 opts into wait-forever.
        timeout_ms = config_.op_timeout_ms;
        forever = timeout_ms <= 0;
    }
    if (!forever) {
        if (fut.wait_for(std::chrono::milliseconds(timeout_ms)) !=
            std::future_status::ready) {
            // Abandon: the Request keeps the shared state alive, so a late
            // response completes harmlessly and FIFO matching stays intact.
            // The flag tells the reactor the caller's buffers are off-limits
            // from here on (see SyncState::abandoned) — but the reactor may
            // be INSIDE a buffer-touching region right now, so wait for
            // io_seq_ to go even before returning. Regions check the flag
            // after going odd, so once we observe even here no later region
            // can touch the buffers (Dekker pairing; regions are one
            // nonblocking syscall or a bounded memcpy loop, so this wait is
            // microseconds).
            state->abandoned.store(true);
            uint64_t s = io_seq_.load();
            if (s & 1) {
                // Wait for THIS section to exit (any change: a later section
                // entered after our store and so already sees the flag).
                while (io_seq_.load() == s) std::this_thread::yield();
            }
            if (state->seg_op) {
                // Segment-path op: the server reads/writes the mapped
                // segment directly, so an in-flight request cannot be
                // neutralized client-side. Poison the connection — the
                // reactor fails everything and the caller must reallocate
                // its alloc_shm_mr views (they never survive a dead
                // connection anyway).
                ITS_LOG_WARN("abandoned segment op; failing connection");
                poison_.store(true);
                uint64_t one = 1;
                ssize_t rc = ::write(wake_fd_, &one, sizeof(one));
                (void)rc;
                // Wait for the reactor to actually fail the connection so
                // the caller observes a DETERMINISTIC state (is_connected
                // false -> recovery paths take the reconnect branch, never
                // a racy retry of the poisoned op). Bounded: the reactor
                // checks poison_ every loop tick.
                for (int spin = 0; connected_.load() && spin < 4000; spin++)
                    std::this_thread::sleep_for(std::chrono::milliseconds(1));
            }
            return kStatusUnavailable;
        }
    } else {
        fut.wait();
    }
    if (body_out != nullptr) *body_out = std::move(state->body);
    if (payload_out != nullptr) {
        *payload_out = state->payload;
        *payload_size_out = state->payload_size;
        state->payload = nullptr;  // ownership to the caller
    }
    return state->status;
}

int Connection::tcp_put(const std::string& key, const void* data, size_t size) {
    auto req = std::make_unique<Request>();
    req->op = kOpTcpPut;
    TcpPutMeta meta{key, size};
    meta.encode(req->body);
    // Own a copy of the payload: sync ops can time out and be abandoned
    // while the reactor is still streaming the request — the iovec must not
    // reference caller memory the caller may free after the error returns.
    // The copy is a deliberate tax on this single-key convenience path;
    // bulk data belongs on the batched zero-copy API (register_mr +
    // put_batch_async), which keeps caller ownership until completion.
    req->owned_payload.assign(static_cast<const uint8_t*>(data),
                              static_cast<const uint8_t*>(data) + size);
    req->tx_payload.push_back(iovec{req->owned_payload.data(), size});
    uint32_t status = sync_roundtrip(std::move(req), nullptr, nullptr, nullptr);
    return status == kStatusOk ? 0 : -static_cast<int>(status);
}

int Connection::tcp_get(const std::string& key, uint8_t** out, size_t* out_size) {
    auto req = std::make_unique<Request>();
    req->op = kOpTcpGet;
    KeyMeta meta{key};
    meta.encode(req->body);
    req->alloc_rx = true;
    uint32_t status = sync_roundtrip(std::move(req), nullptr, out, out_size);
    return status == kStatusOk ? 0 : -static_cast<int>(status);
}

int Connection::check_exist(const std::string& key) {
    auto req = std::make_unique<Request>();
    req->op = kOpCheckExist;
    KeyMeta meta{key};
    meta.encode(req->body);
    std::vector<uint8_t> body;
    uint32_t status = sync_roundtrip(std::move(req), &body, nullptr, nullptr);
    if (status != kStatusOk || body.empty()) return -static_cast<int>(status);
    return body[0] != 0 ? 1 : 0;
}

int32_t Connection::get_match_last_index(const std::vector<std::string>& keys) {
    auto req = std::make_unique<Request>();
    req->op = kOpMatchLastIdx;
    KeyListMeta meta{keys};
    meta.encode(req->body);
    std::vector<uint8_t> body;
    uint32_t status = sync_roundtrip(std::move(req), &body, nullptr, nullptr);
    if (status != kStatusOk || body.size() < 4) return INT32_MIN;
    WireReader r(body.data(), body.size());
    return r.i32();
}

int64_t Connection::delete_keys(const std::vector<std::string>& keys) {
    auto req = std::make_unique<Request>();
    req->op = kOpDeleteKeys;
    KeyListMeta meta{keys};
    meta.encode(req->body);
    std::vector<uint8_t> body;
    uint32_t status = sync_roundtrip(std::move(req), &body, nullptr, nullptr);
    if (status != kStatusOk || body.size() < 4) return -static_cast<int64_t>(status);
    WireReader r(body.data(), body.size());
    return r.u32();
}

std::string Connection::stat_json() {
    auto req = std::make_unique<Request>();
    req->op = kOpStat;
    std::vector<uint8_t> body;
    uint32_t status = sync_roundtrip(std::move(req), &body, nullptr, nullptr);
    if (status != kStatusOk) return "";
    return std::string(body.begin(), body.end());
}

void Connection::set_completion_fd(int fd) { comp_fd_.store(fd); }

void Connection::completion_counters(uint64_t* pushed, uint64_t* signalled) const {
    if (pushed != nullptr) *pushed = comp_pushed_.load(std::memory_order_relaxed);
    if (signalled != nullptr) *signalled = comp_signalled_.load(std::memory_order_relaxed);
}

int Connection::drain_completions(uint64_t* tokens, int32_t* codes, int cap) {
    std::lock_guard<std::mutex> lock(ring_mu_);
    int n = static_cast<int>(std::min<size_t>(cap, ring_.size()));
    for (int i = 0; i < n; i++) {
        tokens[i] = ring_[i].first;
        codes[i] = ring_[i].second;
    }
    ring_.erase(ring_.begin(), ring_.begin() + n);
    return n;
}

void Connection::complete(std::unique_ptr<Request> req, int code, bool take_body) {
    if (req->sync != nullptr) {
        req->sync->status = static_cast<uint32_t>(code);
        // Only a request whose response was actually received may take
        // rbody_ — completions from fail_all or an abandoned-drop would
        // otherwise move out a DIFFERENT response's partially read body and
        // desync the stream.
        if (take_body) req->sync->body = std::move(rbody_);
        req->sync->payload = req->rx_buf;
        req->sync->payload_size = req->rx_buf_size;
        req->rx_buf = nullptr;
        req->sync->prom.set_value();
    } else if (req->cb != nullptr) {
        req->cb(req->ctx, code);
    } else if (comp_fd_.load() >= 0 && req->ctx != nullptr) {
        // Ring mode: push, then signal — the drainer reads the fd BEFORE
        // popping, so a push after its pop re-arms the fd and no completion
        // is ever stranded. Coalescing: the fd is written only when the
        // ring transitions empty -> non-empty. A non-empty ring means a
        // wakeup is already armed (or a drain is mid-flight, which clears
        // the fd first and then pops EVERYTHING under ring_mu_, so this
        // push is either seen by that drain or re-signalled by the next
        // empty-transition push) — completions landing in between, e.g. a
        // burst of small (<16KB) gets streaming back-to-back off one
        // socket, piggyback on the armed wakeup instead of paying one
        // eventfd syscall (and one loop wake) each.
        bool was_empty;
        {
            std::lock_guard<std::mutex> lock(ring_mu_);
            was_empty = ring_.empty();
            ring_.emplace_back(reinterpret_cast<uint64_t>(req->ctx), code);
        }
        comp_pushed_.fetch_add(1, std::memory_order_relaxed);
        if (was_empty) {
            comp_signalled_.fetch_add(1, std::memory_order_relaxed);
            uint64_t one = 1;
            ssize_t rc = ::write(comp_fd_.load(), &one, sizeof(one));
            (void)rc;
        }
    }
    if (req->rx_buf != nullptr) free(req->rx_buf);
}

void Connection::fail_all(int code) {
    {
        std::lock_guard<std::mutex> lock(submit_mu_);
        connected_.store(false);
        for (auto& req : submitted_) sendq_.push_back(std::move(req));
        submitted_.clear();
    }
    // Ring-posted ops: connected_ is false now, so no new descriptor can be
    // parked after this drain (try_ring_post checks under ring_mu_).
    std::vector<std::unique_ptr<Request>> ring_ops;
    {
        std::lock_guard<std::mutex> lock(dring_mu_);
        ring_ops.reserve(ring_inflight_.size() + group_reqs_.size());
        for (auto& [token, req] : ring_inflight_) ring_ops.push_back(std::move(req));
        ring_inflight_.clear();
        // An open batch group holds captured-but-unpublished ops; they die
        // with the connection like any other in-flight request.
        for (auto& req : group_reqs_) ring_ops.push_back(std::move(req));
        group_reqs_.clear();
        group_active_ = false;
    }
    for (auto& req : ring_ops) complete(std::move(req), code, /*take_body=*/false);
    while (!awaiting_.empty()) {
        auto req = std::move(awaiting_.front());
        awaiting_.pop_front();
        complete(std::move(req), code, /*take_body=*/false);
    }
    while (!sendq_.empty()) {
        auto req = std::move(sendq_.front());
        sendq_.pop_front();
        complete(std::move(req), code, /*take_body=*/false);
    }
}

bool Connection::flush_send() {
    if (poison_.load()) return false;  // abandoned segment op: stop sending
    static const std::vector<iovec> kNoPayload;
    while (!sendq_.empty()) {
        Request* req = sendq_.front().get();
        // Section covers the abandoned check AND the writev reading from
        // tx_payload: a timed-out waiter blocks until we exit it.
        IoSection sec(io_seq_);
        if (req->sync != nullptr && req->sync->abandoned.load()) {
            // Only a request whose WIRE payload gathers from caller memory
            // is dangerous to send. Everything else proceeds normally even
            // when abandoned — in particular a queued kOpPutCommit (body
            // only) MUST still go out, or the server-side ticket's pinned
            // pool blocks leak; late responses are drained/completed into
            // the shared SyncState.
            bool refs_caller = req->payload_on_wire && !req->tx_payload.empty() &&
                               req->owned_payload.empty();
            if (refs_caller && req->sent == 0) {
                // Never reached the wire: drop it whole — the server never
                // saw it, so FIFO response matching is unaffected and there
                // is no server-side state to clean up.
                auto dead = std::move(sendq_.front());
                sendq_.pop_front();
                complete(std::move(dead), static_cast<int>(kStatusUnavailable),
                         /*take_body=*/false);
                continue;
            }
            if (refs_caller && req->sent < req->send_total) {
                // Half-streamed from caller memory the caller may have freed
                // after the timeout. Abandoning mid-frame would desync the
                // protocol; the only safe move is to fail the connection.
                ITS_LOG_ERROR("abandoned sync op mid-stream; failing connection");
                return false;
            }
        }
        iovec iov[64];
        const std::vector<iovec>& wire_payload =
            req->payload_on_wire ? req->tx_payload : kNoPayload;
        size_t niov = build_send_iov(&req->hdr, sizeof(ReqHeader), req->body, wire_payload,
                                     req->sent, iov, 64);
        ssize_t r = writev_nosignal(fd_, iov, static_cast<int>(niov));
        if (r < 0) {
            if (errno == EAGAIN || errno == EWOULDBLOCK) {
                epoll_event ev{};
                ev.events = EPOLLIN | EPOLLOUT;
                ev.data.fd = fd_;
                epoll_ctl(epoll_fd_, EPOLL_CTL_MOD, fd_, &ev);
                return true;
            }
            return false;
        }
        req->sent += static_cast<size_t>(r);
        if (req->sent == req->send_total) {
            if (req->no_response) {
                sendq_.pop_front();  // fire-and-forget (release)
            } else {
                awaiting_.push_back(std::move(sendq_.front()));
                sendq_.pop_front();
            }
        }
    }
    epoll_event ev{};
    ev.events = EPOLLIN;
    ev.data.fd = fd_;
    epoll_ctl(epoll_fd_, EPOLL_CTL_MOD, fd_, &ev);
    return true;
}

bool Connection::read_ready() {
    if (poison_.load()) return false;
    while (true) {
        if (!resp_in_progress_) {
            ssize_t r = read(fd_, reinterpret_cast<char*>(&rhdr_) + rhdr_got_,
                             sizeof(RespHeader) - rhdr_got_);
            if (r == 0) return false;
            if (r < 0) return errno == EAGAIN || errno == EWOULDBLOCK;
            rhdr_got_ += static_cast<size_t>(r);
            if (rhdr_got_ < sizeof(RespHeader)) continue;
            if (rhdr_.status == kStatusRingEvent) {
                // Unsolicited completion-ring doorbell: not matched to an
                // in-flight request — drain the CQ and keep reading.
                if (rhdr_.body_size != 0 || rhdr_.payload_size != 0) {
                    ITS_LOG_ERROR("protocol error: ring doorbell with body");
                    return false;
                }
                rhdr_got_ = 0;
                if (!drain_cq()) return false;
                continue;
            }
            if (awaiting_.empty() || rhdr_.body_size > kMaxBodySize) {
                ITS_LOG_ERROR("protocol error: unexpected response");
                return false;
            }
            if (rhdr_.status < 100 || rhdr_.status > 599) {
                // HTTP-like status range (protocol.h). Anything else is a
                // desynced or hostile stream — fail the connection rather
                // than complete ops with a bogus code (a status of 0 would
                // collide with "success" returns up the stack).
                ITS_LOG_ERROR("protocol error: invalid status %u", rhdr_.status);
                return false;
            }
            rbody_.resize(rhdr_.body_size);
            rbody_got_ = 0;
            resp_in_progress_ = true;
            rx_setup_done_ = false;
        }

        Request* req = awaiting_.front().get();
        if (rbody_got_ < rbody_.size()) {
            ssize_t r = read(fd_, rbody_.data() + rbody_got_, rbody_.size() - rbody_got_);
            if (r == 0) return false;
            if (r < 0) return errno == EAGAIN || errno == EWOULDBLOCK;
            rbody_got_ += static_cast<size_t>(r);
            if (rbody_got_ < rbody_.size()) continue;
        }
        if (!rx_setup_done_) {
            // Body complete (possibly empty): set up payload reception once.
            rx_setup_done_ = true;
            rx_iov_.clear();
            rx_cur_.reset();
            rx_discard_ = 0;
            rx_failed_ = false;
            if (rhdr_.payload_size > 0) {
                if (req->sync != nullptr && req->sync->abandoned.load()) {
                    // The waiter timed out; its buffers may be gone. Drain.
                    rx_discard_ = rhdr_.payload_size;
                } else if (req->op == kOpGetBatch && rhdr_.status == kStatusOk) {
                    WireReader rd(rbody_.data(), rbody_.size());
                    uint32_t n = rd.u32();
                    if (n != req->rx_addrs.size()) {
                        ITS_LOG_ERROR("get_batch: size list mismatch");
                        return false;
                    }
                    for (uint32_t i = 0; i < n; i++) {
                        uint32_t sz = rd.u32();
                        // A stored block larger than the caller's slot must
                        // not scatter past rx_addrs[i]: fail the op and
                        // drain the payload instead of overflowing.
                        if (sz > req->block_size) {
                            ITS_LOG_ERROR(
                                "get_batch: stored block (%u) exceeds requested "
                                "block_size (%u)", sz, req->block_size);
                            rx_iov_.clear();
                            rx_discard_ = rhdr_.payload_size;
                            rx_failed_ = true;
                            break;
                        }
                        rx_iov_.push_back(iovec{req->rx_addrs[i], sz});
                    }
                } else if (req->alloc_rx && rhdr_.status == kStatusOk) {
                    req->rx_buf = static_cast<uint8_t*>(malloc(rhdr_.payload_size));
                    req->rx_buf_size = rhdr_.payload_size;
                    rx_iov_.push_back(iovec{req->rx_buf, rhdr_.payload_size});
                } else {
                    rx_discard_ = rhdr_.payload_size;
                }
            }
        }

        // Payload phase.
        if (rx_discard_ > 0) {
            char scratch[64 << 10];
            ssize_t r = read(fd_, scratch, std::min<uint64_t>(rx_discard_, sizeof(scratch)));
            if (r == 0) return false;
            if (r < 0) return errno == EAGAIN || errno == EWOULDBLOCK;
            rx_discard_ -= static_cast<uint64_t>(r);
            if (rx_discard_ > 0) continue;
        } else if (!rx_cur_.done(rx_iov_)) {
            // Section covers the abandoned check AND the readv scattering
            // into rx_addrs: a timed-out waiter blocks until we exit it.
            IoSection sec(io_seq_);
            if (req->sync != nullptr && req->sync->abandoned.load()) {
                // Timed out mid-scatter: stop touching the caller's buffers
                // and drain the rest of the payload into scratch.
                rx_discard_ = rx_cur_.remaining(rx_iov_);
                rx_iov_.clear();
                rx_cur_.reset();
                continue;
            }
            iovec iov[64];
            size_t niov = rx_cur_.fill(rx_iov_, iov, 64);
            ssize_t r = readv(fd_, iov, static_cast<int>(niov));
            if (r == 0) return false;
            if (r < 0) return errno == EAGAIN || errno == EWOULDBLOCK;
            rx_cur_.advance(rx_iov_, static_cast<size_t>(r));
            if (!rx_cur_.done(rx_iov_)) continue;
        }

        // Response fully received.
        auto done = std::move(awaiting_.front());
        awaiting_.pop_front();
        resp_in_progress_ = false;
        rhdr_got_ = 0;
        if (rx_failed_) {
            rx_failed_ = false;
            complete(std::move(done), static_cast<int>(kStatusInternal),
                     /*take_body=*/true);
        } else if (done->op == kOpPutAlloc || done->op == kOpGetLoc) {
            auto requeue = shm_phase(std::move(done), rhdr_.status);
            if (requeue != nullptr) sendq_.push_back(std::move(requeue));
            if (!sendq_.empty() && !flush_send()) return false;
        } else {
            complete(std::move(done), static_cast<int>(rhdr_.status),
                     /*take_body=*/true);
        }
    }
}

// Handle a shm fast-path response on the reactor thread: move the payload
// between user memory and the pools, through the pool file's descriptor
// (copy_through_fd), then either requeue the request as a commit (put) or
// release the server-side pins and complete (get).
std::unique_ptr<Connection::Request> Connection::shm_phase(std::unique_ptr<Request> req,
                                                           uint32_t status) {
    bool put = req->op == kOpPutAlloc;
    // Convert back to the socket-path op: the request body (BatchMeta) and
    // payload endpoints are identical, so the op byte is the only change.
    auto fall_back = [this, put](std::unique_ptr<Request> r) {
        shm_ok_.store(false);
        ITS_LOG_WARN("shm fast path degraded; retrying over the socket");
        r->op = put ? kOpPutBatch : kOpGetBatch;
        r->payload_on_wire = true;
        r->prime();
        return r;
    };
    if (status == kStatusRetry) {
        // Server placed (or stored) the blocks in a pool that is not shm-
        // mappable (e.g. /dev/shm quota forced an anonymous extend pool).
        return fall_back(std::move(req));
    }
    if (status != kStatusOk) {
        complete(std::move(req), static_cast<int>(status), /*take_body=*/true);
        return nullptr;
    }
    ShmLocResp resp;
    try {
        resp = ShmLocResp::decode(rbody_.data(), rbody_.size());
    } catch (const std::exception& e) {
        ITS_LOG_ERROR("shm response parse failed: %s", e.what());
        complete(std::move(req), static_cast<int>(kStatusInternal),
                 /*take_body=*/true);
        return nullptr;
    }
    size_t n = resp.locs.size();
    bool ok = put ? n == req->tx_payload.size() : n == req->rx_addrs.size();
    std::vector<int> fd_of(n);  // the pool file each location lies in
    std::vector<iovec> mem(n);  // and the caller's memory it is copied from / to
    for (size_t i = 0; ok && i < n; i++) {
        const ShmLoc& l = resp.locs[i];
        ShmMap pool;
        {
            std::lock_guard<std::mutex> lock(shm_mu_);
            auto it = shm_pools_.find(l.pool_id);
            if (it != shm_pools_.end()) pool = it->second;
        }
        if (pool.base == nullptr) {
            // Auto-extended pool: map on demand from the embedded directory.
            for (const auto& p : resp.pools) {
                if (p.pool_id == l.pool_id) {
                    pool = map_pool(p.pool_id, p.name, p.size);
                    break;
                }
            }
        }
        // On gets, a stored block larger than the caller's slot must not
        // overflow rx_addrs[i]: that is a size-contract violation, not a
        // mapping problem — fail the op (no socket retry: that path would
        // face the same oversized payload).
        if (!put && l.size > req->block_size) {
            ITS_LOG_ERROR("shm get: stored block (%u) exceeds requested block_size (%u)",
                          l.size, req->block_size);
            queue_release(resp.ticket);
            complete(std::move(req), static_cast<int>(kStatusInternal),
                 /*take_body=*/true);
            return nullptr;
        }
        // Bounds-check against the mapping: a malformed location must not
        // drive a copy out of the pool (the socket path bounds everything
        // through validated iovecs; this is the shm equivalent).
        size_t span = put ? req->tx_payload[i].iov_len : static_cast<size_t>(l.size);
        if (pool.base == nullptr || l.offset > pool.size || span > pool.size - l.offset) {
            ok = false;
            break;
        }
        fd_of[i] = pool.fd;
        mem[i] = put ? req->tx_payload[i] : iovec{req->rx_addrs[i], span};
    }
    if (!ok) {
        queue_release(resp.ticket);  // abort: drop the server-side ticket
        return fall_back(std::move(req));
    }
    // Section covers the abandoned check AND the copies against caller
    // memory: a timed-out waiter blocks until we exit it (bounded loop).
    IoSection sec(io_seq_);
    if (req->sync != nullptr && req->sync->abandoned.load()) {
        // Timed-out waiter: tx_payload/rx_addrs point at memory the caller
        // may have freed — abort the ticket instead of copying.
        queue_release(resp.ticket);
        complete(std::move(req), static_cast<int>(kStatusUnavailable),
                 /*take_body=*/true);
        return nullptr;
    }
    uint64_t t0 = put ? now_us() : 0;
    bool copied = copy_through_fd(put, resp.locs, fd_of, mem);
    if (put) put_copy_us_.fetch_add(now_us() - t0, std::memory_order_relaxed);
    if (!copied) {
        // A put has published nothing and a get has completed nothing: drop
        // the ticket and run the whole op again over the socket.
        queue_release(resp.ticket);
        return fall_back(std::move(req));
    }
    if (put) {
        // Phase 2: publish the keys (commit-on-copy-complete).
        req->op = kOpPutCommit;
        req->body.clear();
        TicketMeta{resp.ticket}.encode(req->body);
        req->tx_payload.clear();
        req->prime();
        return req;
    }
    queue_release(resp.ticket);
    complete(std::move(req), static_cast<int>(kStatusOk), /*take_body=*/true);
    return nullptr;
}

void Connection::put_counters(uint64_t* put_file_bytes, uint64_t* put_file_calls,
                              uint64_t* put_copy_us, uint64_t* get_file_bytes) const {
    *put_file_bytes = put_file_bytes_.load(std::memory_order_relaxed);
    *put_file_calls = put_file_calls_.load(std::memory_order_relaxed);
    *put_copy_us = put_copy_us_.load(std::memory_order_relaxed);
    *get_file_bytes = get_file_bytes_.load(std::memory_order_relaxed);
}

// The copy of a two-phase put (write) or of a located get. The pool is a
// posix_fallocate'd tmpfs file: its pages exist, only THIS process's mapping
// of them is cold, and an access through a cold mapping costs a first-touch
// fault a page on whichever thread makes it (docs/design.md, "A put's copy
// rides the pool's file"). pwritev / preadv copy to and from the same pages
// inside the system call and touch no page-table entry of anybody.
// Locations that lie side by side in one pool (a first-fit allocator hands a
// put's keys out so) go out as ONE call. An error returns false with nothing
// published or completed: every byte of a put is in the file before the
// caller sends the commit.
bool Connection::copy_through_fd(bool write, const std::vector<ShmLoc>& locs,
                                 const std::vector<int>& fds, const std::vector<iovec>& mem) {
    for (size_t first = 0, end; first < mem.size(); first = end) {
        // [first, end): one run of locations that are contiguous in one file.
        uint64_t bytes = mem[first].iov_len;
        for (end = first + 1; end < mem.size() && fds[end] == fds[first] &&
                              locs[end].offset == locs[first].offset + bytes;
             end++)
            bytes += mem[end].iov_len;
        uint64_t calls = 0;
        bool ok = file_transfer(write, fds[first], mem, first, end, locs[first].offset, &calls);
        if (write) put_file_calls_.fetch_add(calls, std::memory_order_relaxed);
        if (!ok) {
            ITS_LOG_WARN("shm %s: the pool file's descriptor failed (%s)", write ? "put" : "get",
                         strerror(errno));
            return false;
        }
        (write ? put_file_bytes_ : get_file_bytes_).fetch_add(bytes, std::memory_order_relaxed);
    }
    return true;
}

void Connection::queue_release(uint64_t ticket) {
    auto rel = std::make_unique<Request>();
    rel->op = kOpRelease;
    TicketMeta{ticket}.encode(rel->body);
    rel->no_response = true;
    rel->prime();
    sendq_.push_back(std::move(rel));
}

void Connection::reactor() {
    constexpr int kMaxEvents = 8;
    epoll_event events[kMaxEvents];
    bool ok = true;
    auto dispatch = [&](int n) {
        for (int i = 0; i < n && ok; i++) {
            int fd = events[i].data.fd;
            if (fd == wake_fd_) {
                uint64_t buf;
                while (read(wake_fd_, &buf, sizeof(buf)) > 0) {
                }
                {
                    std::lock_guard<std::mutex> lock(submit_mu_);
                    for (auto& req : submitted_) sendq_.push_back(std::move(req));
                    submitted_.clear();
                }
                ok = flush_send();
            } else {
                if (events[i].events & (EPOLLHUP | EPOLLERR)) {
                    ok = false;
                    break;
                }
                if (events[i].events & EPOLLOUT) ok = flush_send();
                if (ok && (events[i].events & EPOLLIN)) ok = read_ready();
            }
        }
    };
    while (ok && !stop_.load(std::memory_order_relaxed)) {
        if (poison_.load()) break;  // abandoned segment op: fail everything
        int timeout = 200;
        if (ring_ok_.load(std::memory_order_acquire)) {
            if (!drain_cq()) break;
            // Adaptive poll-then-park (docs/descriptor_ring.md): with ring
            // ops in flight and completions arriving on a fast cadence,
            // busy-poll the CQ for ~2x the smoothed inter-CQE gap before
            // arming the doorbell — a hit completes the op with no park, no
            // doorbell frame, no epoll wake. Socket/wake traffic is served
            // inside the window (zero-timeout epoll), so posting and
            // payload streaming are never starved by the spin. An idle ring
            // (nothing in flight) or a slow cadence yields a zero budget:
            // straight to the parked doze, zero CPU.
            bool inflight;
            {
                std::lock_guard<std::mutex> lock(dring_mu_);
                inflight = !ring_inflight_.empty();
            }
            if (inflight) {
                uint64_t budget = ring_poll_budget(ring_gap_ewma_us_);
                bool hit = false;
                if (budget != 0) {
                    uint64_t deadline = now_us() + budget;
                    while (ok && !stop_.load(std::memory_order_relaxed) &&
                           !poison_.load()) {
                        if (ring_load_acq(&dring_->view.ctrl->cq_tail) !=
                            ring_cq_seq_) {
                            hit = true;
                            break;
                        }
                        int pn = epoll_wait(epoll_fd_, events, kMaxEvents, 0);
                        if (pn > 0) dispatch(pn);
                        if (now_us() >= deadline) break;
                        // Mandatory on a shared core: the server thread we
                        // are polling against needs cycles to publish.
                        std::this_thread::yield();
                    }
                }
                if (!ok) break;
                if (hit) {
                    ring_poll_hits_.fetch_add(1, std::memory_order_relaxed);
                    if (!drain_cq()) break;
                    continue;
                }
                ring_poll_arms_.fetch_add(1, std::memory_order_relaxed);
            }
            // Park-then-recheck (Dekker pairing with the server's CQE
            // publish + flag read): either we see the new tail here, or the
            // server sees cli_waiting and sends a doorbell frame.
            ring_flag_park(&dring_->view.ctrl->cli_waiting);
            ring_fence();
            if (ring_load_acq(&dring_->view.ctrl->cq_tail) != ring_cq_seq_) {
                ring_flag_clear(&dring_->view.ctrl->cli_waiting);
                if (!drain_cq()) break;
                // The recheck hit, so the flag is DOWN: a CQE published
                // while we slept would send no doorbell. Poll instead of
                // blocking — the next loop iteration re-parks properly
                // (the server's loop() applies the same discipline).
                timeout = 0;
            }
        }
        int n = epoll_wait(epoll_fd_, events, kMaxEvents, timeout);
        if (ring_ok_.load(std::memory_order_acquire)) {
            ring_flag_clear(&dring_->view.ctrl->cli_waiting);
            if (!drain_cq()) break;
        }
        if (n < 0) {
            if (errno == EINTR) continue;
            break;
        }
        dispatch(n);
    }
    fail_all(kStatusUnavailable);
}

}  // namespace its
