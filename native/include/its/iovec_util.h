// Resumable scatter/gather iovec helpers, shared by the server and client
// reactors. Both sides move payloads with partial readv/writev calls that must
// resume mid-iovec; keeping the offset arithmetic in one place means a fix
// lands everywhere at once.
#pragma once

#include <errno.h>
#include <sys/uio.h>

#include <algorithm>
#include <cstddef>
#include <cstdint>
#include <vector>

namespace its {

// Progress cursor over a scatter list (receive side).
struct ScatterCursor {
    size_t idx = 0;
    size_t off = 0;

    void reset() { idx = off = 0; }
    bool done(const std::vector<iovec>& v) const { return idx >= v.size(); }

    // Fill `out` (capacity max_iov) with the remaining regions; returns count.
    size_t fill(const std::vector<iovec>& v, iovec* out, size_t max_iov) const {
        size_t n = std::min(v.size() - idx, max_iov);
        if (n == 0) return 0;
        out[0].iov_base = static_cast<char*>(v[idx].iov_base) + off;
        out[0].iov_len = v[idx].iov_len - off;
        for (size_t i = 1; i < n; i++) out[i] = v[idx + i];
        return n;
    }

    // Bytes not yet received across the remaining regions.
    uint64_t remaining(const std::vector<iovec>& v) const {
        uint64_t n = 0;
        for (size_t i = idx; i < v.size(); i++) n += v[i].iov_len;
        return n - off;
    }

    // Consume nbytes of progress.
    void advance(const std::vector<iovec>& v, size_t nbytes) {
        while (nbytes > 0) {
            size_t left = v[idx].iov_len - off;
            size_t take = std::min(nbytes, left);
            off += take;
            nbytes -= take;
            if (off == v[idx].iov_len) {
                idx++;
                off = 0;
            }
        }
    }
};

// Move the regions v[first, end) to (write) or from ONE contiguous range of a
// file that starts at `off`: as few vectored calls as kMaxIov a call allows,
// a short transfer resumed where it stopped, EINTR retried. False on an
// error or an early end of file; `calls`, if given, counts the system calls.
inline bool file_transfer(bool write, int fd, const std::vector<iovec>& v, size_t first,
                          size_t end, uint64_t off, uint64_t* calls = nullptr) {
    constexpr size_t kMaxIov = 256;
    iovec iov[kMaxIov];
    ScatterCursor cur{first, 0};
    while (true) {
        while (cur.idx < end && cur.off == v[cur.idx].iov_len) cur = {cur.idx + 1, 0};
        if (cur.idx >= end) return true;
        int cnt = static_cast<int>(cur.fill(v, iov, std::min(kMaxIov, end - cur.idx)));
        ssize_t moved = write ? pwritev(fd, iov, cnt, static_cast<off_t>(off))
                              : preadv(fd, iov, cnt, static_cast<off_t>(off));
        if (moved < 0 && errno == EINTR) continue;
        if (calls != nullptr) ++*calls;
        if (moved <= 0) return false;
        cur.advance(v, static_cast<size_t>(moved));
        off += static_cast<uint64_t>(moved);
    }
}

// Build the remaining iovec view of a framed message (fixed header, metadata
// body, then payload regions) given `sent` bytes already written.
// Returns the number of iovecs placed in `out`.
inline size_t build_send_iov(const void* hdr, size_t hdr_len, const std::vector<uint8_t>& body,
                             const std::vector<iovec>& payload, size_t sent, iovec* out,
                             size_t max_iov) {
    size_t niov = 0;
    size_t off = sent;
    if (off < hdr_len) {
        out[niov++] = iovec{const_cast<char*>(static_cast<const char*>(hdr)) + off,
                            hdr_len - off};
        off = 0;
    } else {
        off -= hdr_len;
    }
    if (niov < max_iov && off < body.size()) {
        out[niov++] = iovec{const_cast<uint8_t*>(body.data()) + off, body.size() - off};
        off = 0;
    } else {
        off -= std::min(off, body.size());
    }
    for (size_t i = 0; i < payload.size() && niov < max_iov; i++) {
        size_t len = payload[i].iov_len;
        if (off >= len) {
            off -= len;
            continue;
        }
        out[niov++] = iovec{static_cast<char*>(payload[i].iov_base) + off, len - off};
        off = 0;
    }
    return niov;
}

}  // namespace its
