// Native-level unit tests for the allocator, KV/LRU store, wire codec, and a
// full in-process client<->server loopback pass. The reference ships zero C++
// tests (SURVEY.md §4 calls its hardware-gated test strategy the weakest
// subsystem); this binary runs in CI under ASAN too (`make check-asan`), which
// the Python/ctypes suite cannot do.
//
// Deliberately dependency-free (no gtest in the image): tiny CHECK macro,
// main() runs every case, nonzero exit on failure.
#include <dirent.h>
#include <fcntl.h>
#include <string.h>
#include <sys/eventfd.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <chrono>
#include <cmath>
#include <cstdio>
#include <string>
#include <thread>
#include <vector>

#include "its/client.h"
#include "its/kvstore.h"
#include "its/log.h"
#include "its/mempool.h"
#include "its/protocol.h"
#include "its/ring.h"
#include "its/server.h"

static std::atomic<int> g_failures{0};

#define CHECK(cond)                                                            \
    do {                                                                       \
        if (!(cond)) {                                                         \
            fprintf(stderr, "FAIL %s:%d: %s\n", __FILE__, __LINE__, #cond);    \
            g_failures++;                                                      \
        }                                                                      \
    } while (0)

using namespace its;

static void test_mempool_basic() {
    MemoryPool pool(1 << 20, 4 << 10, /*pin=*/false);
    CHECK(pool.total_blocks() == 256);
    void* a = pool.allocate(4 << 10);
    void* b = pool.allocate(12 << 10);  // 3 contiguous blocks
    CHECK(a != nullptr && b != nullptr && a != b);
    CHECK(pool.used_blocks() == 4);
    CHECK(pool.deallocate(a, 4 << 10));
    CHECK(!pool.deallocate(a, 4 << 10));  // double free detected
    char foreign[64];
    CHECK(!pool.deallocate(foreign, 64));  // foreign pointer rejected
    CHECK(pool.deallocate(b, 12 << 10));
    CHECK(pool.used_blocks() == 0);
}

static void test_mempool_exhaustion_and_rollback() {
    MM mm(64 << 10, 16 << 10, false);  // 4 blocks
    std::vector<Lease> leases;
    CHECK(mm.allocate(16 << 10, 3, nullptr, &leases));
    std::vector<Lease> more;
    // 2 more can't fit: all-or-nothing must roll back, freeing nothing held.
    CHECK(!mm.allocate(16 << 10, 2, nullptr, &more));
    CHECK(more.empty());
    CHECK(mm.used_bytes() == 3 * (16 << 10));
    for (const auto& l : leases) mm.deallocate(l);
    CHECK(mm.used_bytes() == 0);
    // Extend adds capacity.
    CHECK(mm.extend(64 << 10));
    std::vector<Lease> big;
    CHECK(mm.allocate(16 << 10, 7, nullptr, &big));
    for (const auto& l : big) mm.deallocate(l);
}

static void test_kvstore_lru_eviction() {
    MM mm(64 << 10, 16 << 10, false);  // 4 blocks
    KVStore kv(&mm);
    auto put = [&](const std::string& key) {
        std::vector<Lease> l;
        if (!mm.allocate(16 << 10, 1, nullptr, &l)) return false;
        kv.commit(key, std::make_shared<Block>(&mm, l[0].ptr, l[0].size));
        return true;
    };
    CHECK(put("a") && put("b") && put("c") && put("d"));
    CHECK(kv.size() == 4);
    CHECK(kv.get("a") != nullptr);  // touch "a": now most-recent
    // Pool full (usage 1.0 >= max 0.9): evict to min 0.5 -> 2 evictions,
    // oldest-first means "b" and "c" go, "a" stays.
    size_t evicted = kv.evict(0.5, 0.9);
    CHECK(evicted == 2);
    CHECK(kv.exists("a"));
    CHECK(!kv.exists("b"));
    CHECK(!kv.exists("c"));
    CHECK(kv.exists("d"));
    // match_last_index under the prefix property.
    std::vector<std::string> chain = {"a", "d", "zz"};
    CHECK(kv.match_last_index(chain) == 1);
    CHECK(kv.match_last_index({"nope"}) == -1);
    CHECK(kv.purge() == 2);
    CHECK(mm.used_bytes() == 0);  // refcount returned every block
}

static void test_kvstore_overwrite_slot() {
    MM mm(64 << 10, 16 << 10, false);  // 4 blocks
    KVStore kv(&mm);
    auto put = [&](const std::string& key) {
        std::vector<Lease> l;
        CHECK(mm.allocate(16 << 10, 1, nullptr, &l));
        kv.commit(key, std::make_shared<Block>(&mm, l[0].ptr, l[0].size));
    };
    put("a");
    put("b");
    // Resident, size-matched, only-reference: eligible, and the fast path
    // hands back the committed block itself (copy lands in place).
    CHECK(kv.overwrite_eligible("a", 16 << 10));
    BlockRef slot = kv.overwrite_slot("a", 16 << 10);
    CHECK(slot != nullptr && slot == kv.get("a"));
    // overwrite_slot touched "a": with the pool full, a one-entry evict
    // (4 -> 3 blocks = 0.75 usage <= 0.8) must take the colder "b".
    slot.reset();
    put("c");
    put("d");
    CHECK(kv.evict(0.8, 0.9) == 1);
    CHECK(kv.exists("a") && !kv.exists("b"));
    // Size mismatch and missing key: ineligible, no slot.
    CHECK(!kv.overwrite_eligible("a", 8 << 10));
    CHECK(kv.overwrite_slot("a", 8 << 10) == nullptr);
    CHECK(!kv.overwrite_eligible("nope", 16 << 10));
    // A pinned reader (outstanding BlockRef) blocks the in-place path —
    // mutating the block would tear that reader's snapshot.
    BlockRef pinned = kv.get("a");
    CHECK(!kv.overwrite_eligible("a", 16 << 10));
    CHECK(kv.overwrite_slot("a", 16 << 10) == nullptr);
    pinned.reset();
    CHECK(kv.overwrite_eligible("a", 16 << 10));
    kv.purge();
    CHECK(mm.used_bytes() == 0);
}

static void test_wire_codec_roundtrip() {
    BatchMeta m;
    m.block_size = 4096;
    m.keys = {"k1", "", std::string(300, 'x')};
    std::vector<uint8_t> buf;
    m.encode(buf);
    BatchMeta d = BatchMeta::decode(buf.data(), buf.size());
    CHECK(d.block_size == 4096 && d.keys == m.keys);

    ShmLocResp r;
    r.ticket = 0xdeadbeefcafe;
    r.locs = {{1, 65536, 4096}, {0, 0, 1}};
    r.pools = {{0, "/its.1.2.0", 1 << 20}};
    buf.clear();
    r.encode(buf);
    ShmLocResp rd = ShmLocResp::decode(buf.data(), buf.size());
    CHECK(rd.ticket == r.ticket && rd.locs.size() == 2 && rd.pools.size() == 1);
    CHECK(rd.locs[0].offset == 65536 && rd.pools[0].name == "/its.1.2.0");

    // Truncated body must throw, not read OOB (ASAN-visible if it did).
    bool threw = false;
    try {
        BatchMeta::decode(buf.data(), 3);
    } catch (const std::exception&) {
        threw = true;
    }
    CHECK(threw);
}

static void test_loopback_end_to_end(bool enable_shm) {
    ServerConfig scfg;
    scfg.bind_addr = "127.0.0.1";
    scfg.service_port = 0;
    scfg.prealloc_bytes = 16 << 20;
    scfg.block_size = 16 << 10;
    scfg.pin_memory = false;
    scfg.enable_shm = enable_shm;
    Server server(scfg);
    CHECK(server.start());

    ClientConfig ccfg;
    ccfg.host = "127.0.0.1";
    ccfg.port = server.port();
    ccfg.enable_shm = enable_shm;
    Connection conn(ccfg);
    CHECK(conn.connect() == 0);
    CHECK(conn.shm_active() == enable_shm);

    const size_t n = 8, bs = 16 << 10;
    std::vector<char> src(n * bs), dst(n * bs, 0);
    for (size_t i = 0; i < src.size(); i++) src[i] = static_cast<char>(i * 31 + 7);
    conn.register_mr(src.data(), src.size());
    conn.register_mr(dst.data(), dst.size());

    std::vector<std::string> keys;
    std::vector<uint64_t> offs;
    for (size_t i = 0; i < n; i++) {
        keys.push_back("blk" + std::to_string(i));
        offs.push_back(i * bs);
    }
    std::atomic<int> code{-1};
    auto cb = [](void* ctx, int c) { static_cast<std::atomic<int>*>(ctx)->store(c); };
    CHECK(conn.put_batch_async(keys, offs, bs, src.data(), cb, &code) == 0);
    for (int i = 0; i < 500 && code.load() == -1; i++)
        std::this_thread::sleep_for(std::chrono::milliseconds(2));
    CHECK(code.load() == 200);

    code.store(-1);
    CHECK(conn.get_batch_async(keys, offs, bs, dst.data(), cb, &code) == 0);
    for (int i = 0; i < 500 && code.load() == -1; i++)
        std::this_thread::sleep_for(std::chrono::milliseconds(2));
    CHECK(code.load() == 200);
    CHECK(memcmp(src.data(), dst.data(), src.size()) == 0);

    // Control ops.
    CHECK(conn.check_exist("blk0") == 1);
    CHECK(conn.check_exist("nope") == 0);
    CHECK(conn.get_match_last_index({"blk0", "blk1", "missing"}) == 1);
    // TCP single-key path + typed miss.
    CHECK(conn.tcp_put("tk", src.data(), 1024) == 0);
    uint8_t* out = nullptr;
    size_t out_size = 0;
    CHECK(conn.tcp_get("tk", &out, &out_size) == 0);
    CHECK(out_size == 1024 && memcmp(out, src.data(), 1024) == 0);
    free(out);
    CHECK(conn.tcp_get("missing", &out, &out_size) == -404);
    CHECK(conn.delete_keys({"blk0", "tk", "ghost"}) == 2);
    CHECK(server.kvmap_len() == n - 1);

    conn.close();
    server.stop();
}

// How many descriptors this process holds on pool files (/dev/shm/its.*,
// the descriptor rings apart).
static size_t pool_fds() {
    size_t n = 0;
    DIR* d = opendir("/proc/self/fd");
    CHECK(d != nullptr);
    while (dirent* e = readdir(d)) {
        char link[256];
        std::string path = std::string("/proc/self/fd/") + e->d_name;
        ssize_t len = readlink(path.c_str(), link, sizeof(link) - 1);
        if (len <= 0) continue;
        std::string target(link, static_cast<size_t>(len));
        if (target.rfind("/dev/shm/its.", 0) == 0 && target.find(".ring") == std::string::npos)
            n++;
    }
    closedir(d);
    return n;
}

// The shm copies ride the pool file's descriptor (client.h): every byte of
// a two-phase put is counted as written through it, side-by-side values go
// out as one call, a second writer's bytes and the first's read back exact
// (the located gets through the reader's descriptor), a connection that only
// reads puts nothing, and close() gives back every descriptor, after puts
// or not; the same object connects and puts again.
static void test_put_file() {
    ServerConfig scfg;
    scfg.bind_addr = "127.0.0.1";
    scfg.service_port = 0;
    scfg.prealloc_bytes = 96 << 20;
    scfg.block_size = 16 << 10;
    scfg.pin_memory = false;
    scfg.enable_shm = true;
    Server server(scfg);
    CHECK(server.start());
    size_t fds0 = pool_fds();  // the server's own
    ClientConfig ccfg;
    ccfg.host = "127.0.0.1";
    ccfg.port = server.port();
    Connection writer(ccfg), second(ccfg), reader(ccfg);
    CHECK(writer.connect() == 0 && second.connect() == 0 && reader.connect() == 0);
    CHECK(writer.shm_active() && reader.shm_active());
    CHECK(pool_fds() == fds0 + 3);  // one a mapped pool a connection

    const size_t n = 32, bs = 64 << 10;  // 2 MiB a put
    std::vector<char> src(n * bs), dst(n * bs, 0);
    for (size_t i = 0; i < src.size(); i++) src[i] = static_cast<char>(i * 131 + 5);
    for (Connection* c : {&writer, &second, &reader}) {
        c->register_mr(src.data(), src.size());
        c->register_mr(dst.data(), dst.size());
    }
    auto keys_of = [&](const std::string& prefix) {
        std::vector<std::string> keys;
        for (size_t i = 0; i < n; i++) keys.push_back(prefix + std::to_string(i));
        return keys;
    };
    std::vector<uint64_t> offs;
    for (size_t i = 0; i < n; i++) offs.push_back(i * bs);
    uint64_t filed = 0, calls = 0, copy_us = 0, got = 0;

    writer.put_counters(&filed, &calls, &copy_us, &got);
    CHECK(filed == 0 && calls == 0);
    // An empty pool hands a put's 32 values out side by side: one call.
    CHECK(writer.put_batch(keys_of("first"), offs, bs, src.data()) == 0);
    writer.put_counters(&filed, &calls, &copy_us, &got);
    CHECK(filed == n * bs && calls == 1);
    // Two writers of one pool file, turn by turn.
    for (int r = 0; r < 8; r++) {
        CHECK(second.put_batch(keys_of("s" + std::to_string(r) + "-"), offs, bs, src.data()) == 0);
        CHECK(writer.put_batch(keys_of("w" + std::to_string(r) + "-"), offs, bs, src.data()) == 0);
    }
    writer.put_counters(&filed, &calls, &copy_us, &got);
    CHECK(filed == 9 * n * bs);
    second.put_counters(&filed, &calls, &copy_us, &got);
    CHECK(filed == 8 * n * bs);
    for (int r = 0; r < 8; r++) {
        memset(dst.data(), 0, dst.size());
        CHECK(reader.get_batch(keys_of("s" + std::to_string(r) + "-"), offs, bs, dst.data()) == 0);
        CHECK(memcmp(src.data(), dst.data(), src.size()) == 0);
        CHECK(reader.get_batch(keys_of("w" + std::to_string(r) + "-"), offs, bs, dst.data()) == 0);
        CHECK(memcmp(src.data(), dst.data(), src.size()) == 0);
    }
    // A connection that only reads holds its descriptor, puts nothing, and
    // copied every located get out through it.
    reader.put_counters(&filed, &calls, &copy_us, &got);
    CHECK(filed == 0 && calls == 0 && copy_us == 0);
    CHECK(got == 16 * n * bs);
    reader.close();
    second.close();
    CHECK(pool_fds() == fds0 + 1);
    // Close, connect again on the same object, put again (close() drops the
    // registrations with the mappings), over and over: no descriptor stays.
    auto again = [&] {
        writer.close();
        CHECK(pool_fds() == fds0);
        CHECK(writer.connect() == 0);
        writer.register_mr(src.data(), src.size());
        writer.register_mr(dst.data(), dst.size());
    };
    again();
    server.purge();
    for (int r = 0; r < 20; r++) {
        CHECK(writer.put_batch(keys_of("again"), offs, bs, src.data()) == 0);
        again();
    }
    CHECK(pool_fds() == fds0 + 1);
    CHECK(writer.put_batch(keys_of("third"), offs, bs, src.data()) == 0);
    memset(dst.data(), 0, dst.size());
    CHECK(writer.get_batch(keys_of("third"), offs, bs, dst.data()) == 0);
    CHECK(memcmp(src.data(), dst.data(), src.size()) == 0);
    writer.close();
    CHECK(pool_fds() == fds0);
    server.stop();
}

static void test_spill_tier_demote_promote() {
    // KVStore + SpillFile: evict demotes to the file, get promotes back,
    // bytes survive the round trip, slots are freed on delete/overwrite,
    // and a full spill file drops only the coldest entries.
    MM mm(8 * 64 << 10, 64 << 10, /*pin=*/false);  // 8 blocks of RAM
    SpillFile spill("/tmp", 32 * 64 << 10, 64 << 10);
    CHECK(spill.ok());
    KVStore kv(&mm, &spill);

    auto put = [&](const std::string& key, char fill) {
        std::vector<Lease> leases;
        CHECK(mm.allocate(64 << 10, 1, [](void*, size_t) {}, &leases));
        memset(leases[0].ptr, fill, 64 << 10);
        kv.commit(key, std::make_shared<Block>(&mm, leases[0].ptr, 64 << 10));
    };

    for (int i = 0; i < 24; i++) {
        kv.evict(0.5, 0.9);  // the server's on-demand pattern
        put("k" + std::to_string(i), static_cast<char>('a' + i));
    }
    CHECK(kv.size() == 24);               // nothing lost: 8 RAM + 16 spilled
    CHECK(kv.spilled_entries() >= 16);
    CHECK(kv.spill_drops() == 0);

    // Promote an old (spilled) entry; its bytes must be intact.
    BlockRef b = kv.get("k0");
    CHECK(b != nullptr);
    CHECK(static_cast<char*>(b->data())[0] == 'a');
    CHECK(static_cast<char*>(b->data())[(64 << 10) - 1] == 'a');
    CHECK(kv.spill_promotions() == 1);

    // Control ops: spilled entries are present without promotion.
    uint64_t promos = kv.spill_promotions();
    CHECK(kv.exists("k1"));
    std::vector<std::string> chain;
    for (int i = 0; i < 24; i++) chain.push_back("k" + std::to_string(i));
    CHECK(kv.match_last_index(chain) == 23);
    CHECK(kv.spill_promotions() == promos);

    // Delete frees spill slots.
    size_t bytes_before = kv.spilled_bytes();
    CHECK(bytes_before > 0);
    CHECK(kv.remove({"k1", "k2"}) == 2);
    CHECK(kv.spilled_bytes() < bytes_before);

    // Fill far beyond RAM+spill: the coldest spilled entries drop, the
    // newest stay readable.
    for (int i = 100; i < 200; i++) {
        kv.evict(0.5, 0.9);
        put("z" + std::to_string(i), static_cast<char>(i));
    }
    CHECK(kv.spill_drops() > 0);
    BlockRef newest = kv.get("z199");
    CHECK(newest != nullptr);
    CHECK(static_cast<char*>(newest->data())[7] == static_cast<char>(199));
    kv.purge();
    CHECK(kv.spilled_bytes() == 0);
}

static void test_abandoned_sync_ops_stress(bool enable_shm) {
    // The documented timeout contract: after a sync op raises, the caller
    // may unregister and FREE the buffer — the reactor must never touch it
    // again (SyncState::abandoned + io_seq_ Dekker pairing, client.cpp).
    // Regime: 16MB ops (several ms of streaming/memcpy) against a 1ms
    // deadline, so ops are abandoned unsent, mid-stream, mid-scatter, and
    // awaiting a late response. Each iteration frees its buffer immediately
    // — under ASAN/TSAN any late reactor touch is a hard failure. A
    // mid-stream put abandonment intentionally fails the connection; the
    // loop reconnects, covering that path too.
    ServerConfig scfg;
    scfg.bind_addr = "127.0.0.1";
    scfg.service_port = 0;
    scfg.prealloc_bytes = 256 << 20;
    scfg.block_size = 64 << 10;
    scfg.pin_memory = false;
    scfg.enable_shm = enable_shm;
    Server server(scfg);
    CHECK(server.start());

    const size_t n = 64, bs = 256 << 10;  // 16MB per op
    std::vector<std::string> keys;
    std::vector<uint64_t> offs;
    for (size_t i = 0; i < n; i++) {
        keys.push_back("ab" + std::to_string(i));
        offs.push_back(i * bs);
    }

    // Seed the keys with a patient connection so gets have data to return.
    {
        ClientConfig seed_cfg;
        seed_cfg.host = "127.0.0.1";
        seed_cfg.port = server.port();
        seed_cfg.enable_shm = enable_shm;
        Connection seed(seed_cfg);
        CHECK(seed.connect() == 0);
        std::vector<char> src(n * bs, 'S');
        seed.register_mr(src.data(), src.size());
        CHECK(seed.put_batch(keys, offs, bs, src.data()) == 0);
        seed.close();
    }

    ClientConfig ccfg;
    ccfg.host = "127.0.0.1";
    ccfg.port = server.port();
    ccfg.enable_shm = enable_shm;
    ccfg.op_timeout_ms = 1;
    auto conn = std::make_unique<Connection>(ccfg);
    CHECK(conn->connect() == 0);

    int fails = 0, oks = 0, reconnects = 0;
    for (int it = 0; it < 40; it++) {
        auto buf = std::make_unique<std::vector<char>>(n * bs,
                                                       static_cast<char>(it));
        conn->register_mr(buf->data(), buf->size());
        int rc = (it & 1) ? conn->get_batch(keys, offs, bs, buf->data())
                          : conn->put_batch(keys, offs, bs, buf->data());
        rc == 0 ? oks++ : fails++;
        // The documented sequence after a timeout: unregister, scribble,
        // free. If the reactor still holds an iovec into this memory, the
        // sanitizers see the touch after the delete below.
        conn->unregister_mr(buf->data());
        memset(buf->data(), 0xDD, 4096);
        buf.reset();
        if (rc != 0) {
            // Mid-stream abandonment fails the connection by design; a
            // fresh connection also covers connect/teardown under churn.
            conn->close();
            conn = std::make_unique<Connection>(ccfg);
            CHECK(conn->connect() == 0);
            reconnects++;
        }
    }
    // Let any last late responses land (and be drained) while the final
    // connection is still alive.
    std::this_thread::sleep_for(std::chrono::milliseconds(100));
    CHECK(fails > 0);  // the abandoned regime was actually exercised
    conn->close();
    server.stop();
    (void)oks;
    (void)reconnects;
}

// Eventfd completion ring: concurrent pushes from the reactor against a
// draining "event loop" thread, fd signalling semantics, and fail-all
// delivery through the ring. Runs under ASAN and TSAN in CI — this is the
// cross-thread structure the Python asyncio bridge relies on.
static void test_completion_ring(bool enable_shm) {
    ServerConfig scfg;
    scfg.bind_addr = "127.0.0.1";
    scfg.service_port = 0;
    scfg.prealloc_bytes = 16 << 20;
    scfg.block_size = 16 << 10;
    scfg.pin_memory = false;
    scfg.enable_shm = enable_shm;
    Server server(scfg);
    CHECK(server.start());

    ClientConfig ccfg;
    ccfg.host = "127.0.0.1";
    ccfg.port = server.port();
    ccfg.enable_shm = enable_shm;
    Connection conn(ccfg);
    CHECK(conn.connect() == 0);

    int efd = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
    CHECK(efd >= 0);
    conn.set_completion_fd(efd);

    const size_t n = 4, bs = 16 << 10;
    std::vector<char> src(n * bs);
    for (size_t i = 0; i < src.size(); i++) src[i] = static_cast<char>(i * 13 + 3);
    conn.register_mr(src.data(), src.size());
    std::vector<std::string> keys;
    std::vector<uint64_t> offs;
    for (size_t i = 0; i < n; i++) {
        keys.push_back("ring" + std::to_string(i));
        offs.push_back(i * bs);
    }

    // Drainer thread = the event loop: waits on the fd, drains tokens.
    const int kOps = 200;
    std::atomic<bool> stop{false};
    std::atomic<int> drained{0};
    std::atomic<int> ok_codes{0};
    std::thread drainer([&] {
        uint64_t tokens[32];
        int32_t codes[32];
        while (!stop.load()) {
            uint64_t v;
            if (read(efd, &v, sizeof(v)) < 0)
                std::this_thread::sleep_for(std::chrono::microseconds(200));
            int got;
            while ((got = conn.drain_completions(tokens, codes, 32)) > 0) {
                for (int i = 0; i < got; i++) {
                    drained.fetch_add(1);
                    if (codes[i] == 200) ok_codes.fetch_add(1);
                    CHECK(tokens[i] >= 1 && tokens[i] <= kOps);
                }
            }
        }
    });

    // Ring-mode submits: cb = nullptr, ctx = token.
    for (int i = 1; i <= kOps; i++) {
        CHECK(conn.put_batch_async(keys, offs, bs, src.data(), nullptr,
                                   reinterpret_cast<void*>(static_cast<uintptr_t>(i))) == 0);
        if (i % 16 == 0) {
            // Throttle so the in-flight window stays modest.
            for (int spin = 0; spin < 2000 && drained.load() < i - 32; spin++)
                std::this_thread::sleep_for(std::chrono::milliseconds(1));
        }
    }
    for (int spin = 0; spin < 5000 && drained.load() < kOps; spin++)
        std::this_thread::sleep_for(std::chrono::milliseconds(1));
    CHECK(drained.load() == kOps);
    CHECK(ok_codes.load() == kOps);

    // fail_all delivery: submit, then close the connection — every pending
    // op must surface through the ring with a non-200 code (or have
    // completed 200 first), never vanish.
    int before = drained.load();
    int accepted = 0;
    for (int i = 1; i <= 8; i++) {
        if (conn.put_batch_async(keys, offs, bs, src.data(), nullptr,
                                 reinterpret_cast<void*>(static_cast<uintptr_t>(i))) == 0)
            accepted++;
    }
    CHECK(accepted == 8);  // a rejected submit never enters the ring
    conn.close();  // reactor joined: completions (success or 503) are in the ring
    uint64_t tokens[32];
    int32_t codes[32];
    int got, total_after = 0;
    while ((got = conn.drain_completions(tokens, codes, 32)) > 0) total_after += got;
    // Drainer may have consumed some first; between both, all 8 resolved.
    stop.store(true);
    drainer.join();
    int resolved = drained.load() - before + total_after;
    CHECK(resolved == accepted);

    close(efd);
    server.stop();
}

static void test_qos_wire_priority_tag() {
    // The QoS class tag is an OPTIONAL trailing byte: an untagged
    // (foreground) body must be byte-identical to the pre-QoS encoding,
    // and a tagged body is that encoding plus exactly one byte.
    BatchMeta m;
    m.block_size = 4096;
    m.keys = {"a", "b"};
    std::vector<uint8_t> untagged;
    m.encode(untagged);
    m.priority = kPriorityBackground;
    std::vector<uint8_t> tagged;
    m.encode(tagged);
    CHECK(tagged.size() == untagged.size() + 1);
    CHECK(memcmp(tagged.data(), untagged.data(), untagged.size()) == 0);
    CHECK(tagged.back() == kPriorityBackground);
    CHECK(BatchMeta::decode(untagged.data(), untagged.size()).priority ==
          kPriorityForeground);
    CHECK(BatchMeta::decode(tagged.data(), tagged.size()).priority ==
          kPriorityBackground);

    SegBatchMeta sm;
    sm.block_size = 4096;
    sm.seg_id = 3;
    sm.keys = {"k"};
    sm.offsets = {65536};
    std::vector<uint8_t> s0;
    sm.encode(s0);
    sm.priority = kPriorityBackground;
    std::vector<uint8_t> s1;
    sm.encode(s1);
    CHECK(s1.size() == s0.size() + 1 && s1.back() == kPriorityBackground);
    CHECK(SegBatchMeta::decode(s0.data(), s0.size()).priority ==
          kPriorityForeground);
    SegBatchMeta sd = SegBatchMeta::decode(s1.data(), s1.size());
    CHECK(sd.priority == kPriorityBackground && sd.offsets == sm.offsets);
}

static long long stat_counter(const std::string& json, const char* key) {
    std::string needle = std::string("\"") + key + "\":";
    size_t at = json.find(needle);
    if (at == std::string::npos) return -1;
    return atoll(json.c_str() + at + needle.size());
}

static void test_trace_wire_context() {
    // The trace context is a SECOND trailing optional extension after the
    // QoS byte (docs/observability.md): untraced stays byte-identical to
    // the pre-trace encoding; a traced FOREGROUND op gains exactly the
    // priority byte + 16 trace bytes (the priority byte must be forced so
    // the trailing-optional decode walk stays unambiguous).
    BatchMeta m;
    m.block_size = 4096;
    m.keys = {"a", "b"};
    std::vector<uint8_t> plain;
    m.encode(plain);
    m.trace_id = 0x1122334455667788ull;
    m.trace_parent = 0x99aabbccddeeff00ull;
    std::vector<uint8_t> traced;
    m.encode(traced);
    CHECK(traced.size() == plain.size() + 1 + 16);
    CHECK(memcmp(traced.data(), plain.data(), plain.size()) == 0);
    CHECK(traced[plain.size()] == kPriorityForeground);
    BatchMeta d = BatchMeta::decode(traced.data(), traced.size());
    CHECK(d.trace_id == m.trace_id && d.trace_parent == m.trace_parent);
    CHECK(d.priority == kPriorityForeground);
    CHECK(BatchMeta::decode(plain.data(), plain.size()).trace_id ==
          kTraceIdNone);

    // Background + traced composes: priority byte carries the class.
    SegBatchMeta sm;
    sm.block_size = 4096;
    sm.seg_id = 1;
    sm.keys = {"k"};
    sm.offsets = {0};
    sm.priority = kPriorityBackground;
    sm.trace_id = 42;
    sm.trace_parent = 7;
    std::vector<uint8_t> sb;
    sm.encode(sb);
    SegBatchMeta sd = SegBatchMeta::decode(sb.data(), sb.size());
    CHECK(sd.priority == kPriorityBackground && sd.trace_id == 42 &&
          sd.trace_parent == 7);
}

static void test_trace_ring_loopback(bool enable_shm) {
    // A traced batched op must land one ordered tick record in the
    // server's trace ring (stats_json "trace"), joined by trace id, while
    // untraced ops leave the ring untouched.
    ServerConfig scfg;
    scfg.bind_addr = "127.0.0.1";
    scfg.service_port = 0;
    scfg.prealloc_bytes = 16 << 20;
    scfg.block_size = 16 << 10;
    scfg.pin_memory = false;
    scfg.enable_shm = enable_shm;
    Server server(scfg);
    CHECK(server.start());
    ClientConfig ccfg;
    ccfg.host = "127.0.0.1";
    ccfg.port = server.port();
    ccfg.enable_shm = enable_shm;
    Connection conn(ccfg);
    CHECK(conn.connect() == 0);

    const size_t n = 4, bs = 16 << 10;
    std::vector<char> buf(n * bs, 'x');
    conn.register_mr(buf.data(), buf.size());
    std::vector<std::string> keys;
    std::vector<uint64_t> offs;
    for (size_t i = 0; i < n; i++) {
        keys.push_back("tr" + std::to_string(i));
        offs.push_back(i * bs);
    }
    // Untraced put: no tick.
    CHECK(conn.put_batch(keys, offs, bs, buf.data()) == 0);
    CHECK(stat_counter(server.stats_json(), "recorded") == 0);
    // Traced get: one tick, ordered, with the op's bytes.
    const uint64_t tid = 0xfeedbeef, span = 0x1234;
    CHECK(conn.get_batch(keys, offs, bs, buf.data(), kPriorityForeground,
                         tid, span) == 0);
    std::string js = server.stats_json();
    CHECK(stat_counter(js, "recorded") == 1);
    CHECK(js.find("\"trace_id\":" + std::to_string(tid)) != std::string::npos);
    CHECK(js.find("\"parent_id\":" + std::to_string(span)) != std::string::npos);
    size_t at = js.find("\"entries\":[{");
    CHECK(at != std::string::npos);
    std::string entry = js.substr(at);
    long long recv = stat_counter(entry, "recv_us");
    long long first = stat_counter(entry, "first_slice_us");
    long long last = stat_counter(entry, "last_slice_us");
    long long done = stat_counter(entry, "done_us");
    CHECK(recv > 0 && recv <= first && first <= last && last <= done);
    CHECK(stat_counter(entry, "bytes") ==
          static_cast<long long>(n * bs));
    conn.close();
    server.stop();
}

static void test_qos_two_level_scheduler() {
    // Reactor-level QoS: a BACKGROUND-tagged batch must (a) complete under
    // a PERMANENT foreground flood — the time-based aging escape makes
    // starvation impossible by construction — (b) be byte-correct despite
    // running entirely from preempted/aged slices, and (c) show up in the
    // scheduler's per-class counters.
    ServerConfig scfg;
    scfg.bind_addr = "127.0.0.1";
    scfg.service_port = 0;
    scfg.prealloc_bytes = 32 << 20;
    scfg.block_size = 16 << 10;
    scfg.pin_memory = false;
    scfg.enable_shm = true;
    Server server(scfg);
    CHECK(server.start());

    ClientConfig ccfg;
    ccfg.host = "127.0.0.1";
    ccfg.port = server.port();
    Connection bg(ccfg), fg(ccfg);
    CHECK(bg.connect() == 0 && fg.connect() == 0);

    const size_t n = 64, bs = 16 << 10;
    std::vector<char> bgbuf(n * bs), rdbuf(n * bs, 0), fgbuf(bs, 'f');
    for (size_t i = 0; i < bgbuf.size(); i++)
        bgbuf[i] = static_cast<char>(i * 13 + 5);
    bg.register_mr(bgbuf.data(), bgbuf.size());
    bg.register_mr(rdbuf.data(), rdbuf.size());
    fg.register_mr(fgbuf.data(), fgbuf.size());
    std::vector<std::string> keys;
    std::vector<uint64_t> offs;
    for (size_t i = 0; i < n; i++) {
        keys.push_back("bgk" + std::to_string(i));
        offs.push_back(i * bs);
    }
    CHECK(fg.put_batch({"hot"}, {0}, bs, fgbuf.data()) == 0);

    std::atomic<bool> stop{false};
    std::thread flood([&] {
        while (!stop.load())
            fg.get_batch({"hot"}, {0}, bs, fgbuf.data());
    });

    std::atomic<int> code{-1};
    auto cb = [](void* ctx, int c) { static_cast<std::atomic<int>*>(ctx)->store(c); };
    CHECK(bg.put_batch_async(keys, offs, bs, bgbuf.data(), cb, &code,
                             kPriorityBackground) == 0);
    for (int i = 0; i < 2500 && code.load() == -1; i++)
        std::this_thread::sleep_for(std::chrono::milliseconds(2));
    CHECK(code.load() == 200);  // completed DURING the flood (aging)
    stop.store(true);
    flood.join();

    // Byte-correctness under preemption: every block survived intact.
    CHECK(bg.get_batch(keys, offs, bs, rdbuf.data(), kPriorityBackground) == 0);
    CHECK(memcmp(bgbuf.data(), rdbuf.data(), bgbuf.size()) == 0);

    std::string st = server.stats_json();
    CHECK(stat_counter(st, "bg_ops") >= 2);  // the tagged put + read-back
    CHECK(stat_counter(st, "fg_ops") >= 2);  // seed put + flood reads
    // The scheduler actually deferred (or aged) background work at least
    // once under the flood — the mechanism ran, not just the bookkeeping.
    CHECK(stat_counter(st, "bg_preempted_slices") +
              stat_counter(st, "bg_aged_slices") > 0);

    bg.close();
    fg.close();
    server.stop();
}

// ---------------------------------------------------------------------------
// Descriptor-ring data plane (docs/descriptor_ring.md). These cases run the
// REAL cross-process protocol in-process (client reactor + server reactor on
// their own threads, the ring header genuinely shared state) — which is
// exactly what check-tsan exists to validate.
// ---------------------------------------------------------------------------

static ClientConfig ring_ccfg(int port, uint32_t ring_slots,
                              bool enable_ring = true) {
    ClientConfig c;
    c.host = "127.0.0.1";
    c.port = port;
    c.enable_ring = enable_ring;
    c.ring_slots = ring_slots;
    return c;
}

static ServerConfig ring_scfg(size_t prealloc = 32 << 20) {
    ServerConfig s;
    s.bind_addr = "127.0.0.1";
    s.service_port = 0;
    s.prealloc_bytes = prealloc;
    s.block_size = 16 << 10;
    s.pin_memory = false;
    s.enable_shm = true;
    return s;
}

static void test_ring_wrap_and_disable() {
    // Cursor wrap: a tiny 4-slot ring must survive many times its depth in
    // sequential ops (seq % slots indexing, head-gated slot reuse), stay
    // byte-correct, and count every descriptor. A ring-disabled connection
    // against the same server must keep working over the socket path with
    // ZERO ring traffic.
    Server server(ring_scfg());
    CHECK(server.start());
    Connection conn(ring_ccfg(server.port(), /*ring_slots=*/4));
    CHECK(conn.connect() == 0);
    CHECK(conn.shm_active());
    CHECK(conn.ring_active());
    CHECK(!conn.ring_name().empty());

    const size_t n = 4, bs = 16 << 10;
    char* seg = static_cast<char*>(conn.alloc_shm_mr(n * bs));
    CHECK(seg != nullptr);
    std::vector<std::string> keys;
    std::vector<uint64_t> offs;
    for (size_t i = 0; i < n; i++) {
        keys.push_back("wr" + std::to_string(i));
        offs.push_back(i * bs);
    }
    const int rounds = 10;  // 20 descriptors through 4 slots = 5 wraps
    for (int r = 0; r < rounds; r++) {
        for (size_t i = 0; i < n * bs; i++)
            seg[i] = static_cast<char>(i * 7 + r);
        CHECK(conn.put_batch(keys, offs, bs, seg) == 0);
        memset(seg, 0, n * bs);
        CHECK(conn.get_batch(keys, offs, bs, seg) == 0);
        bool ok = true;
        for (size_t i = 0; i < n * bs && ok; i++)
            ok = seg[i] == static_cast<char>(i * 7 + r);
        CHECK(ok);
    }
    uint64_t posted = 0, doorbells = 0, full = 0, meta = 0, comps = 0;
    conn.ring_counters(&posted, &doorbells, &full, &meta, &comps);
    CHECK(posted == 2 * rounds);
    CHECK(comps == 2 * rounds);
    CHECK(full == 0 && meta == 0);
    std::string st = server.stats_json();
    CHECK(stat_counter(st, "descriptors") == 2 * rounds);
    CHECK(stat_counter(st, "completions") == 2 * rounds);
    CHECK(stat_counter(st, "torn_descriptors") == 0);
    CHECK(stat_counter(st, "attached") == 1);

    // Ring disabled: same ops, socket path, no ring traffic.
    Connection off(ring_ccfg(server.port(), 0, /*enable_ring=*/false));
    CHECK(off.connect() == 0);
    CHECK(off.shm_active());
    CHECK(!off.ring_active());
    CHECK(off.ring_name().empty());
    char* seg2 = static_cast<char*>(off.alloc_shm_mr(bs));
    CHECK(seg2 != nullptr);
    memset(seg2, 'z', bs);
    CHECK(off.put_batch({"offk"}, {0}, bs, seg2) == 0);
    memset(seg2, 0, bs);
    CHECK(off.get_batch({"offk"}, {0}, bs, seg2) == 0);
    CHECK(seg2[0] == 'z' && seg2[bs - 1] == 'z');
    uint64_t p2 = 1;
    off.ring_counters(&p2, nullptr, nullptr, nullptr, nullptr);
    CHECK(p2 == 0);
    CHECK(stat_counter(server.stats_json(), "attached") == 1);  // still just conn's

    off.close();
    conn.close();
    server.stop();
}

static void test_ring_full_backpressure() {
    // A 2-slot ring under a 16-op async burst: the in-flight bound (==
    // cq_slots) forces most ops onto the socket path. Backpressure must be
    // a COUNTED fallback, never an error — every op completes 200 and the
    // bytes land.
    Server server(ring_scfg());
    CHECK(server.start());
    Connection conn(ring_ccfg(server.port(), /*ring_slots=*/2));
    CHECK(conn.connect() == 0);
    CHECK(conn.ring_active());

    const size_t nops = 16, bs = 16 << 10;
    char* seg = static_cast<char*>(conn.alloc_shm_mr(nops * bs));
    CHECK(seg != nullptr);
    for (size_t i = 0; i < nops * bs; i++) seg[i] = static_cast<char>(i * 11 + 3);
    std::atomic<int> done{0};
    auto cb = [](void* ctx, int c) {
        if (c == 200) static_cast<std::atomic<int>*>(ctx)->fetch_add(1);
    };
    for (size_t i = 0; i < nops; i++)
        CHECK(conn.put_batch_async({"bp" + std::to_string(i)}, {i * bs}, bs, seg,
                                   cb, &done) == 0);
    for (int i = 0; i < 2500 && done.load() < static_cast<int>(nops); i++)
        std::this_thread::sleep_for(std::chrono::milliseconds(2));
    CHECK(done.load() == static_cast<int>(nops));

    uint64_t posted = 0, full = 0, meta = 0, comps = 0;
    conn.ring_counters(&posted, nullptr, &full, &meta, &comps);
    CHECK(posted + full + meta == nops);
    CHECK(full >= 1);       // the burst actually hit the bound
    CHECK(posted >= 1);     // and the ring still carried work
    CHECK(comps == posted); // every ring op completed via CQE

    // Read-back through the ring confirms both paths committed.
    std::vector<std::string> keys;
    std::vector<uint64_t> offs;
    for (size_t i = 0; i < nops; i++) {
        keys.push_back("bp" + std::to_string(i));
        offs.push_back(i * bs);
    }
    std::vector<char> want(seg, seg + nops * bs);
    memset(seg, 0, nops * bs);
    CHECK(conn.get_batch(keys, offs, bs, seg) == 0);
    CHECK(memcmp(seg, want.data(), nops * bs) == 0);

    conn.close();
    server.stop();
}

static void test_ring_doorbell_coalescing() {
    // Submit-side doze/wake discipline: descriptors posted while the
    // server is AWAKE must not pay a doorbell — only a post that finds the
    // parked flag set sends one (the PR 2 empty->non-empty rule,
    // submission half). A burst of bare small ops on this single-core box
    // ping-pongs (each doorbell's eventfd wake hands the CPU to the
    // server, which finishes the op and re-dozes before the next post), so
    // the test pins the server awake with one LARGE head op first: its
    // doorbell unparks the server, whose sliced copy provably outlasts the
    // burst posting loop, and the small posts behind it must then be pure
    // shared memory — zero doorbell frames.
    Server server(ring_scfg());
    CHECK(server.start());
    Connection conn(ring_ccfg(server.port(), /*ring_slots=*/64));
    CHECK(conn.connect() == 0);
    CHECK(conn.ring_active());

    const size_t nops = 32, nbig = 1024, bs = 16 << 10;  // head op: 16MB
    char* seg = static_cast<char*>(conn.alloc_shm_mr((nbig + nops) * bs));
    CHECK(seg != nullptr);
    memset(seg, 'd', (nbig + nops) * bs);
    std::atomic<int> done{0};
    auto cb = [](void* ctx, int c) {
        if (c == 200) static_cast<std::atomic<int>*>(ctx)->fetch_add(1);
    };
    std::vector<std::string> bigkeys;
    std::vector<uint64_t> bigoffs;
    for (size_t i = 0; i < nbig; i++) {
        bigkeys.push_back("big" + std::to_string(i));
        bigoffs.push_back(i * bs);
    }
    CHECK(conn.put_batch_async(bigkeys, bigoffs, bs, seg, cb, &done) == 0);
    for (size_t i = 0; i < nops; i++)
        CHECK(conn.put_batch_async({"db" + std::to_string(i)},
                                   {(nbig + i) * bs}, bs, seg, cb, &done) == 0);
    for (int i = 0; i < 2500 && done.load() < static_cast<int>(nops) + 1; i++)
        std::this_thread::sleep_for(std::chrono::milliseconds(2));
    CHECK(done.load() == static_cast<int>(nops) + 1);

    uint64_t posted = 0, doorbells = 0, full = 0, meta = 0, comps = 0;
    conn.ring_counters(&posted, &doorbells, &full, &meta, &comps);
    CHECK(posted == nops + 1 && full == 0 && meta == 0 && comps == nops + 1);
    // The head op's doorbell plus rare re-doze stragglers (expect 1-2; a
    // descheduled posting thread can let the head op finish mid-burst and
    // re-doze a few times under load) — but never one per op, which is
    // the syscall-per-op regression this plane removes. Half the burst is
    // the loosest bound that still separates the two regimes.
    CHECK(doorbells >= 1);
    CHECK(2 * doorbells < posted);
    std::string st = server.stats_json();
    CHECK(stat_counter(st, "doorbells_rx") == static_cast<long long>(doorbells));
    // CQ-side doorbells can never exceed published completions.
    CHECK(stat_counter(st, "cq_doorbells_tx") <= stat_counter(st, "completions"));
    // Every published completion either paid a CQ doorbell or was elided
    // because the client consumer was awake — the two must account for all
    // of them, and the burst completing behind the sliced head op has to
    // land at least one CQE inside the client's adaptive poll window.
    long long elided = stat_counter(st, "doorbell_elided");
    CHECK(elided >= 1);
    CHECK(stat_counter(st, "cq_doorbells_tx") + elided ==
          stat_counter(st, "completions"));

    conn.close();
    server.stop();
}

static void test_ring_torn_descriptor_rejected() {
    // Generation-tag validation: an advanced sq_tail whose slot gen was
    // never published (a torn/corrupt descriptor) must poison the ring —
    // the server counts it and closes the connection rather than decode
    // garbage. The tamperer maps the segment by name exactly like a buggy
    // second writer would.
    Server server(ring_scfg());
    CHECK(server.start());
    Connection conn(ring_ccfg(server.port(), /*ring_slots=*/8));
    CHECK(conn.connect() == 0);
    CHECK(conn.ring_active());
    std::string name = conn.ring_name();
    CHECK(!name.empty());

    int fd = shm_open(name.c_str(), O_RDWR, 0);
    CHECK(fd >= 0);
    struct stat stbuf {};
    CHECK(fstat(fd, &stbuf) == 0);
    void* mem = mmap(nullptr, static_cast<size_t>(stbuf.st_size),
                     PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
    CHECK(mem != MAP_FAILED);
    ::close(fd);
    RingView view;
    CHECK(ring_view_init(&view, static_cast<char*>(mem),
                         static_cast<uint64_t>(stbuf.st_size)));
    // Publish a tail advance with NO gen write: the consumer must see
    // gen != seq+1 under an advanced tail.
    uint64_t tail = ring_load_acq(&view.ctrl->sq_tail);
    ring_store_rel(&view.ctrl->sq_tail, tail + 1);

    // Nudge the server with socket traffic until it notices; the conn dies.
    bool dead = false;
    for (int i = 0; i < 2500 && !dead; i++) {
        conn.check_exist("poke");  // outcome irrelevant — generates events
        dead = !conn.connected();
        std::this_thread::sleep_for(std::chrono::milliseconds(2));
    }
    CHECK(dead);
    std::string st = server.stats_json();
    CHECK(stat_counter(st, "torn_descriptors") == 1);
    CHECK(stat_counter(st, "conns") == 0);  // detached on close
    munmap(mem, static_cast<size_t>(stbuf.st_size));
    conn.close();
    server.stop();
}

static void test_ring_qos_ordering_and_trace() {
    // QoS on the ring path: pending descriptors start foreground-first
    // (a later fg op never waits behind queued bg descriptors), and a
    // traced ring op stamps the same ordered server ticks as the socket
    // path (recv <= first_slice <= last_slice <= done).
    Server server(ring_scfg());
    CHECK(server.start());
    Connection conn(ring_ccfg(server.port(), /*ring_slots=*/16));
    CHECK(conn.connect() == 0);
    CHECK(conn.ring_active());

    const size_t nbg = 64, bs = 16 << 10;  // 1MB per bg op = 8 default slices
    char* seg = static_cast<char*>(conn.alloc_shm_mr((3 * nbg + 1) * bs));
    CHECK(seg != nullptr);
    memset(seg, 'q', (3 * nbg + 1) * bs);
    // Completion order via a shared counter captured per-op.
    static std::atomic<int> g_order_next;
    static std::atomic<int> g_order_seq[4];
    g_order_next.store(0);
    for (auto& s : g_order_seq) s.store(-1);
    auto cb2 = [](void* ctx, int c) {
        if (c == 200)
            static_cast<std::atomic<int>*>(ctx)->store(g_order_next.fetch_add(1));
    };
    std::vector<std::string> bgkeys[3];
    std::vector<uint64_t> bgoffs[3];
    for (int b = 0; b < 3; b++)
        for (size_t i = 0; i < nbg; i++) {
            bgkeys[b].push_back("qb" + std::to_string(b) + "_" + std::to_string(i));
            bgoffs[b].push_back((b * nbg + i) * bs);
        }
    const uint64_t tid = 0xabcd1234, span = 0x77;
    for (int b = 0; b < 3; b++)
        CHECK(conn.put_batch_async(bgkeys[b], bgoffs[b], bs, seg, cb2,
                                   &g_order_seq[b], kPriorityBackground) == 0);
    CHECK(conn.put_batch_async({"qfg"}, {3 * nbg * bs}, bs, seg, cb2,
                               &g_order_seq[3], kPriorityForeground, tid,
                               span) == 0);
    for (int i = 0; i < 2500 && g_order_next.load() < 4; i++)
        std::this_thread::sleep_for(std::chrono::milliseconds(2));
    CHECK(g_order_next.load() == 4);
    // At most one bg op can already be running when the fg descriptor
    // lands, so foreground completes first or second — never behind the
    // whole background queue.
    CHECK(g_order_seq[3].load() <= 1);
    CHECK(g_order_seq[2].load() > g_order_seq[3].load());

    std::string st = server.stats_json();
    CHECK(stat_counter(st, "bg_ops") >= 3);
    CHECK(stat_counter(st, "recorded") == 1);  // the traced fg op's tick
    size_t at = st.find("\"entries\":[{");
    CHECK(at != std::string::npos);
    std::string entry = st.substr(at);
    long long recv = stat_counter(entry, "recv_us");
    long long first = stat_counter(entry, "first_slice_us");
    long long last = stat_counter(entry, "last_slice_us");
    long long done_us = stat_counter(entry, "done_us");
    CHECK(recv > 0 && recv <= first && first <= last && last <= done_us);
    CHECK(st.find("\"trace_id\":" + std::to_string(tid)) != std::string::npos);

    conn.close();
    server.stop();
}

static void test_ring_batch_slot_wrap() {
    // Multi-op batch slots: a group_begin/end window packs every same-thread
    // async op into ONE slot (RingBatchHdr + per-op RingBatchEntry frames in
    // the slot's meta arena), and the batch format must survive cursor wrap
    // on a tiny ring exactly like the single-op format — byte-correct, every
    // op CQE'd under token base+k, both sides' batch ledgers in lockstep.
    Server server(ring_scfg());
    CHECK(server.start());
    Connection conn(ring_ccfg(server.port(), /*ring_slots=*/4));
    CHECK(conn.connect() == 0);
    CHECK(conn.ring_active());

    const size_t per = 4, rounds = 12, bs = 16 << 10;  // 12 slots / 4 = 3 wraps
    char* seg = static_cast<char*>(conn.alloc_shm_mr(per * rounds * bs));
    CHECK(seg != nullptr);
    for (size_t i = 0; i < per * rounds * bs; i++)
        seg[i] = static_cast<char>(i * 13 + 5);
    std::atomic<int> done{0};
    auto cb = [](void* ctx, int c) {
        if (c == 200) static_cast<std::atomic<int>*>(ctx)->fetch_add(1);
    };
    for (size_t r = 0; r < rounds; r++) {
        conn.ring_group_begin();
        for (size_t i = 0; i < per; i++) {
            size_t k = r * per + i;
            CHECK(conn.put_batch_async({"bw" + std::to_string(k)}, {k * bs}, bs,
                                       seg, cb, &done) == 0);
        }
        uint64_t mid = 1;
        conn.ring_counters(&mid, nullptr, nullptr, nullptr, nullptr);
        CHECK(mid == r * per);  // captured, not posted, until the window closes
        conn.ring_group_end();
        for (int w = 0; w < 2500 && done.load() < static_cast<int>((r + 1) * per);
             w++)
            std::this_thread::sleep_for(std::chrono::milliseconds(2));
        CHECK(done.load() == static_cast<int>((r + 1) * per));
    }
    uint64_t posted = 0, full = 0, meta = 0, comps = 0;
    conn.ring_counters(&posted, nullptr, &full, &meta, &comps);
    CHECK(posted == rounds * per && comps == rounds * per);
    CHECK(full == 0 && meta == 0);
    uint64_t bslots = 0, bops = 0;
    conn.ring_poll_counters(&bslots, &bops, nullptr, nullptr);
    CHECK(bslots == rounds);       // one slot per flush window...
    CHECK(bops == rounds * per);   // ...carrying the whole window's ops
    std::string st = server.stats_json();
    CHECK(stat_counter(st, "descriptors") == static_cast<long long>(rounds * per));
    CHECK(stat_counter(st, "batch_slots") == static_cast<long long>(rounds));
    CHECK(stat_counter(st, "batch_ops") == static_cast<long long>(rounds * per));
    CHECK(stat_counter(st, "torn_descriptors") == 0);
    CHECK(stat_counter(st, "bad_descriptors") == 0);

    // Read-back through one sync multi-key get (sync ops never join a batch
    // window — the waiter would block before the window could flush).
    std::vector<char> want(seg, seg + per * rounds * bs);
    std::vector<std::string> keys;
    std::vector<uint64_t> offs;
    for (size_t k = 0; k < per * rounds; k++) {
        keys.push_back("bw" + std::to_string(k));
        offs.push_back(k * bs);
    }
    memset(seg, 0, per * rounds * bs);
    CHECK(conn.get_batch(keys, offs, bs, seg) == 0);
    CHECK(memcmp(seg, want.data(), per * rounds * bs) == 0);
    uint64_t bslots2 = 0;
    conn.ring_poll_counters(&bslots2, nullptr, nullptr, nullptr);
    CHECK(bslots2 == bslots);

    conn.close();
    server.stop();
}

static void test_ring_batch_slot_torn_rejected() {
    // Malformed batch slots: a correctly published (gen-tagged) slot whose
    // batch payload is garbage must be rejected with error CQEs — counted as
    // bad_descriptors, never decoded into ops. An untrustworthy header
    // (count out of range) can only fail the base token; a trustworthy count
    // with truncated entries fails every token in the group. Either way the
    // client sees a completion for a token it never issued and fails the
    // connection — the same containment as a torn generation tag.
    for (int variant = 0; variant < 2; variant++) {
        Server server(ring_scfg());
        CHECK(server.start());
        Connection conn(ring_ccfg(server.port(), /*ring_slots=*/8));
        CHECK(conn.connect() == 0);
        CHECK(conn.ring_active());
        std::string name = conn.ring_name();
        CHECK(!name.empty());

        int fd = shm_open(name.c_str(), O_RDWR, 0);
        CHECK(fd >= 0);
        struct stat stbuf {};
        CHECK(fstat(fd, &stbuf) == 0);
        void* mem = mmap(nullptr, static_cast<size_t>(stbuf.st_size),
                         PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
        CHECK(mem != MAP_FAILED);
        ::close(fd);
        RingView view;
        CHECK(ring_view_init(&view, static_cast<char*>(mem),
                             static_cast<uint64_t>(stbuf.st_size)));
        uint64_t seq = ring_load_acq(&view.ctrl->sq_tail);
        // variant 0: count=0 — header untrustworthy, one error CQE on the
        // base token. variant 1: count=3 but zero entry bytes behind the
        // header — all three tokens error-CQE'd.
        RingBatchHdr hdr{static_cast<uint16_t>(variant == 0 ? 0 : 3), 0};
        memcpy(view.meta_at(seq), &hdr, sizeof(hdr));
        RingSlot* s = view.slot(seq);
        s->token = 0xdead0000;
        s->meta_len = sizeof(RingBatchHdr);
        s->op = 0;
        s->flags = kRingSlotFlagBatch;
        s->reserved = 0;
        ring_store_rel(&s->gen, seq + 1);
        ring_store_rel(&view.ctrl->sq_tail, seq + 1);

        bool dead = false;
        for (int i = 0; i < 2500 && !dead; i++) {
            conn.check_exist("poke");  // outcome irrelevant — generates events
            dead = !conn.connected();
            std::this_thread::sleep_for(std::chrono::milliseconds(2));
        }
        CHECK(dead);  // the unknown-token CQE poisons the client side
        std::string st = server.stats_json();
        CHECK(stat_counter(st, "bad_descriptors") == (variant == 0 ? 1 : 3));
        CHECK(stat_counter(st, "torn_descriptors") == 0);
        CHECK(stat_counter(st, "batch_slots") == 0);  // malformed != batched
        munmap(mem, static_cast<size_t>(stbuf.st_size));
        conn.close();
        server.stop();
    }
}

static void test_ring_batch_slot_qos_ordering() {
    // QoS across ONE batch slot: the server decodes the whole slot before
    // starting any op and queues per priority class, so a foreground op
    // packed BEHIND background ops in the same slot still starts first —
    // batching must not flatten priorities into slot order.
    Server server(ring_scfg());
    CHECK(server.start());
    Connection conn(ring_ccfg(server.port(), /*ring_slots=*/16));
    CHECK(conn.connect() == 0);
    CHECK(conn.ring_active());

    constexpr size_t nbg = 3;
    const size_t nblk = 64, bs = 16 << 10;  // 1MB per bg op = 8 default slices
    char* seg = static_cast<char*>(conn.alloc_shm_mr((nbg * nblk + 1) * bs));
    CHECK(seg != nullptr);
    memset(seg, 'b', (nbg * nblk + 1) * bs);
    static std::atomic<int> g_bseq_next;
    static std::atomic<int> g_bseq[nbg + 1];
    g_bseq_next.store(0);
    for (auto& s : g_bseq) s.store(-1);
    auto cb = [](void* ctx, int c) {
        if (c == 200)
            static_cast<std::atomic<int>*>(ctx)->store(g_bseq_next.fetch_add(1));
    };
    conn.ring_group_begin();
    for (size_t b = 0; b < nbg; b++) {
        std::vector<std::string> keys;
        std::vector<uint64_t> offs;
        for (size_t i = 0; i < nblk; i++) {
            keys.push_back("bq" + std::to_string(b) + "_" + std::to_string(i));
            offs.push_back((b * nblk + i) * bs);
        }
        CHECK(conn.put_batch_async(keys, offs, bs, seg, cb, &g_bseq[b],
                                   kPriorityBackground) == 0);
    }
    CHECK(conn.put_batch_async({"bqfg"}, {nbg * nblk * bs}, bs, seg, cb,
                               &g_bseq[nbg], kPriorityForeground) == 0);
    conn.ring_group_end();
    for (int i = 0; i < 2500 && g_bseq_next.load() < static_cast<int>(nbg) + 1; i++)
        std::this_thread::sleep_for(std::chrono::milliseconds(2));
    CHECK(g_bseq_next.load() == static_cast<int>(nbg) + 1);
    // The slot lands whole, so nothing can be running when the fg op is
    // queued: foreground completes strictly first, background keeps FIFO.
    CHECK(g_bseq[nbg].load() == 0);
    for (size_t b = 0; b < nbg; b++)
        CHECK(g_bseq[b].load() == static_cast<int>(b) + 1);

    uint64_t bslots = 0, bops = 0;
    conn.ring_poll_counters(&bslots, &bops, nullptr, nullptr);
    CHECK(bslots == 1 && bops == nbg + 1);
    std::string st = server.stats_json();
    CHECK(stat_counter(st, "batch_slots") == 1);
    CHECK(stat_counter(st, "batch_ops") == static_cast<long long>(nbg) + 1);
    CHECK(stat_counter(st, "bg_ops") >= static_cast<long long>(nbg));

    conn.close();
    server.stop();
}

static void test_opstats_percentile_accuracy() {
    // The HDR-style histogram must report percentiles within ~3% — 32
    // sub-buckets per octave (kSubBits=5, ~2.2% quantization) feed both
    // the derived p50/p99 gauges and the /metrics duration histogram
    // (docs/observability.md).
    for (uint64_t center : {7ull, 23ull, 150ull, 1234ull, 87654ull}) {
        OpStats s;
        std::vector<uint64_t> vals;
        for (int d = -40; d <= 40; d++) {
            uint64_t us = static_cast<uint64_t>(
                static_cast<double>(center) * (1.0 + 0.004 * d));
            vals.push_back(us);
            s.record(us, 0, 0, true);
        }
        std::sort(vals.begin(), vals.end());
        double true_p50 = static_cast<double>(vals[vals.size() / 2]);
        double got = s.p50_us();
        double err = std::abs(got - true_p50) / true_p50;
        CHECK(err <= 0.03);
    }
    OpStats empty;
    CHECK(empty.p50_us() == 0.0);
    OpStats one;
    one.record(100, 0, 0, true);
    CHECK(std::abs(one.p99_us() - 100.0) / 100.0 <= 0.03);
    // bucket_le_us is the inverse upper bound of the bucketing: every
    // recorded value must fall at or below its bucket's `le`, and the
    // `le` sequence the /metrics histogram renders must be monotone.
    OpStats hb;
    for (uint64_t us : {0ull, 5ull, 31ull, 32ull, 1000ull, 123456ull})
        hb.record(us, 0, 0, true);
    uint64_t prev_le = 0;
    uint64_t seen = 0;
    for (int b = 0; b < OpStats::kBuckets; b++) {
        if (hb.lat_buckets[b] == 0) continue;
        uint64_t le = OpStats::bucket_le_us(b);
        CHECK(le >= prev_le);
        prev_le = le;
        seen += hb.lat_buckets[b];
    }
    CHECK(seen == hb.count);
    CHECK(OpStats::bucket_le_us(0) == 0 && OpStats::bucket_le_us(31) == 31);
}

int main() {
    set_log_level(LogLevel::kError);
    test_opstats_percentile_accuracy();
    test_mempool_basic();
    test_mempool_exhaustion_and_rollback();
    test_kvstore_lru_eviction();
    test_kvstore_overwrite_slot();
    test_spill_tier_demote_promote();
    test_wire_codec_roundtrip();
    test_qos_wire_priority_tag();
    test_trace_wire_context();
    test_trace_ring_loopback(/*enable_shm=*/true);
    test_trace_ring_loopback(/*enable_shm=*/false);
    test_qos_two_level_scheduler();
    test_ring_wrap_and_disable();
    test_ring_full_backpressure();
    test_ring_doorbell_coalescing();
    test_ring_torn_descriptor_rejected();
    test_ring_qos_ordering_and_trace();
    test_ring_batch_slot_wrap();
    test_ring_batch_slot_torn_rejected();
    test_ring_batch_slot_qos_ordering();
    test_loopback_end_to_end(/*enable_shm=*/true);
    test_loopback_end_to_end(/*enable_shm=*/false);
    test_put_file();
    test_completion_ring(/*enable_shm=*/true);
    test_completion_ring(/*enable_shm=*/false);
    test_abandoned_sync_ops_stress(/*enable_shm=*/true);
    test_abandoned_sync_ops_stress(/*enable_shm=*/false);
    if (g_failures == 0) {
        printf("native tests: all passed\n");
        return 0;
    }
    fprintf(stderr, "native tests: %d failure(s)\n", g_failures.load());
    return 1;
}
