// Pinned host-DRAM memory pool with a first-fit bitmap allocator.
//
// TPU-native analogue of the reference's mempool (/root/reference/src/mempool.h
// :19-91, mempool.cpp:29-196): one 4KB-aligned region per pool, carved into
// fixed-size blocks tracked by a uint64 bitmap (64 blocks per word, ctz scan),
// contiguous multi-block allocation, batched n-way allocation, double-free
// detection, and an `MM` front that manages multiple pools and signals when a
// new pool should be added (auto-extend). Differences from the reference:
// instead of ibv_reg_mr (no ibverbs on TPU VMs) the region is pinned with
// mlock() so the kernel never pages it out under the DCN send/recv data plane,
// and registration metadata is kept for the staging layer rather than for an
// RDMA rkey.
#pragma once

#include <cstddef>
#include <cstdint>
#include <functional>
#include <memory>
#include <string>
#include <vector>

#include "its/bitmap_alloc.h"

namespace its {

// Reference constants (/root/reference/src/mempool.h:11-13).
constexpr double kBlockUsageRatio = 0.5;      // MM signals extend above this
constexpr size_t kExtendPoolSize = 10ull << 30;  // +10GB per auto-extend pool
constexpr size_t kExtendBlockSize = 64ull << 10;

class MemoryPool {
  public:
    // pool_size must be a multiple of block_size; block_size a power of two.
    // When shm_name is non-empty the region is a named POSIX shm segment
    // (shm_open + mmap MAP_SHARED) so same-host clients can map the pool and
    // move payloads with one memcpy, no socket — the TPU-host analogue of the
    // reference's GPUDirect zero-copy registration (ibv_reg_mr on device
    // pointers). Empty name = anonymous private memory as before.
    MemoryPool(size_t pool_size, size_t block_size, bool pin = true,
               const std::string& shm_name = "");
    ~MemoryPool();

    MemoryPool(const MemoryPool&) = delete;
    MemoryPool& operator=(const MemoryPool&) = delete;

    // Allocate `size` bytes as ceil(size/block_size) *contiguous* blocks.
    // Returns nullptr when no contiguous run is free.
    void* allocate(size_t size);
    // Free a pointer previously returned by allocate(). Aborts the call (logs
    // and returns false) on double-free or foreign pointers.
    bool deallocate(void* ptr, size_t size);

    bool contains(const void* ptr) const {
        const char* p = static_cast<const char*>(ptr);
        return p >= base_ && p < base_ + pool_size_;
    }

    size_t block_size() const { return block_size_; }
    size_t total_blocks() const { return alloc_.total; }
    size_t used_blocks() const { return alloc_.used; }
    void* base() const { return base_; }
    size_t size() const { return pool_size_; }
    bool pinned() const { return pinned_; }
    // Empty when the pool is anonymous (shm backing unavailable/disabled).
    const std::string& shm_name() const { return shm_name_; }
    // The segment's descriptor, -1 for an anonymous pool: a copy out of the
    // pool can ride it instead of this process's mapping (Server's GetInto).
    int shm_fd() const { return shm_backed_ ? shm_fd_ : -1; }

  private:
    char* base_ = nullptr;
    size_t pool_size_;
    size_t block_size_;
    bool pinned_ = false;
    bool shm_backed_ = false;
    int shm_fd_ = -1;  // kept open: holds the liveness flock for sweep
    std::string shm_name_;
    BitmapAlloc alloc_;  // shared first-fit bitmap (bitmap_alloc.h)
};

// A (pool, ptr, size) lease. Deallocation goes back to the owning pool.
struct Lease {
    void* ptr = nullptr;
    size_t size = 0;
    MemoryPool* pool = nullptr;
};

// Crash-safety for named shm segments: every live segment is tracked in a
// small global registry so the fatal-signal handler can unlink them (tmpfs
// pages otherwise outlive the process). SIGKILL can't be caught, so MM also
// sweeps /dev/shm for segments of dead pids at startup.
void shm_registry_add(const char* name);
void shm_registry_remove(const char* name);
void shm_registry_unlink_all();  // async-signal-safe
void shm_sweep_stale();

// One entry of the shm pool directory advertised to same-host clients.
struct PoolDirEntry {
    uint16_t pool_id = 0;
    std::string shm_name;  // empty = not mappable (anonymous pool)
    uint64_t size = 0;
};

// A (pool_id, offset) pair locating a block inside the shm directory.
struct PoolLoc {
    uint16_t pool_id = 0;
    uint64_t offset = 0;
    bool found = false;
};

// Multi-pool manager (reference MM, /root/reference/src/mempool.h:54-91).
class MM {
  public:
    // use_shm: back pools with named shm segments (falls back to anonymous
    // memory with a warning if /dev/shm is unavailable).
    MM(size_t initial_pool_size, size_t block_size, bool pin = true, bool use_shm = false);

    // Batched n-way allocation: invokes cb(ptr, lease_index) for each of the n
    // leases as they are placed (reference MM::allocate's callback shape,
    // /root/reference/src/mempool.cpp:159). Returns false — allocating
    // nothing — if the full batch cannot be satisfied.
    bool allocate(size_t size, size_t n, const std::function<void(void*, size_t)>& cb,
                  std::vector<Lease>* out);
    void deallocate(const Lease& lease);
    // Free by raw pointer: finds the owning pool. Used by the KV layer.
    void deallocate(void* ptr, size_t size);

    // Add one more pool (auto-extend). Returns false on allocation failure.
    bool extend(size_t pool_size);

    // Fraction of blocks in use across all pools, in [0, 1].
    double usage() const;
    // True when usage is above kBlockUsageRatio — caller should extend.
    bool need_extend() const { return usage() > kBlockUsageRatio; }

    size_t block_size() const { return block_size_; }
    size_t total_bytes() const;
    size_t used_bytes() const;
    size_t pool_count() const { return pools_.size(); }
    bool pinned() const;

    // Shm directory for the same-host fast path. Empty when use_shm is off
    // or the backing fell back to anonymous memory.
    std::vector<PoolDirEntry> pool_dir() const;
    bool shm_enabled() const { return shm_prefix_ != nullptr; }
    // Translate a pool pointer into (pool_id, offset) for the directory.
    PoolLoc locate(const void* ptr) const;
    // The descriptor of the pool file `loc` lies in, -1 where the pool is
    // anonymous memory (or loc was not found).
    int shm_fd(const PoolLoc& loc) const {
        return loc.found ? pools_[loc.pool_id]->shm_fd() : -1;
    }

  private:
    std::string next_shm_name();

    size_t block_size_;
    bool pin_;
    std::unique_ptr<std::string> shm_prefix_;  // null = shm off
    std::vector<std::unique_ptr<MemoryPool>> pools_;
};

}  // namespace its
