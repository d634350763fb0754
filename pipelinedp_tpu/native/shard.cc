// Host re-shard of the mesh path: a stable counting scatter by shard.
//
// sharded_fused_aggregate places every row on the device fmix32(pid) %
// n_dev and pads each shard to a common length. A stable argsort of the
// shard ids does that grouping in O(n log n) (numpy's stable sort of a
// 4-byte key is timsort); the shard space is tiny, so a counting sort
// does it in two linear passes:
//
//   pdp_shard_assign   one pass over pid: the shard id of each row (as
//                      uint8) and the rows per shard;
//   pdp_shard_scatter  one pass over the rows for all columns: row i
//                      goes to the next free row of its shard, so rows
//                      keep their input order inside a shard, exactly as
//                      the stable argsort ordered them.
//
// The scatter walks the rows in blocks: it takes each row's destination
// once per block, then copies every column of the block with the
// destinations in hand, so the per-shard cursors are advanced once per
// row rather than once per row and column. The pad tail of each shard
// is left to the caller.
//
// Build: compiled on first use by pipelinedp_tpu/native/__init__.py
// (_build_shared_lib) with the same g++ recipe as secure_noise.cc.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <vector>

namespace {

// murmur3's 32-bit finalizer, as ops/segment.fmix32.
inline uint32_t fmix32(uint32_t x) {
  x ^= x >> 16;
  x *= 0x85EBCA6Bu;
  x ^= x >> 13;
  x *= 0xC2B2AE35u;
  x ^= x >> 16;
  return x;
}

template <typename Reduce>
void assign(const uint32_t* pid, int64_t n, Reduce reduce, uint8_t* shard,
            int64_t* counts) {
  for (int64_t i = 0; i < n; ++i) {
    const uint32_t d = reduce(fmix32(pid[i]));
    shard[i] = static_cast<uint8_t>(d);
    ++counts[d];
  }
}

constexpr int64_t kBlock = 2048;

// Rows of a fixed width W bytes: the copy compiles to one load and store.
template <int64_t W>
void copy_block(const uint8_t* src, const int64_t* row, int64_t m,
                uint8_t* dst) {
  for (int64_t t = 0; t < m; ++t) {
    std::memcpy(dst + W * row[t], src + W * t, W);
  }
}

void copy_block_any(const uint8_t* src, const int64_t* row, int64_t m,
                    int64_t width, uint8_t* dst) {
  for (int64_t t = 0; t < m; ++t) {
    std::memcpy(dst + width * row[t], src + width * t, width);
  }
}

}  // namespace

extern "C" {

// shard[i] = fmix32(pid[i]) % n_dev; counts[d] += rows of shard d.
// counts must hold n_dev zeros. Returns 0, or -1 when n_dev is not in
// [1, 256] (shard ids are uint8).
int32_t pdp_shard_assign(const uint32_t* pid, int64_t n, int32_t n_dev,
                         uint8_t* shard, int64_t* counts) {
  if (n_dev < 1 || n_dev > 256) return -1;
  const uint32_t m = static_cast<uint32_t>(n_dev);
  if ((m & (m - 1)) == 0) {
    const uint32_t mask = m - 1;
    assign(pid, n, [mask](uint32_t h) { return h & mask; }, shard, counts);
  } else {
    assign(pid, n, [m](uint32_t h) { return h % m; }, shard, counts);
  }
  return 0;
}

// For each of the k columns, copies row i of src[c] (width[c] bytes) to
// row start[shard[i]] + (its rank among the earlier rows of its shard)
// of dst[c]. start holds each shard's first destination row; every dst
// must be large enough for every shard.
void pdp_shard_scatter(const uint8_t* const* src, uint8_t* const* dst,
                       const int64_t* width, int32_t k, int64_t n,
                       const uint8_t* shard, int32_t n_dev,
                       const int64_t* start) {
  std::vector<int64_t> cursor(start, start + n_dev);
  int64_t row[kBlock];
  for (int64_t i0 = 0; i0 < n; i0 += kBlock) {
    const int64_t m = std::min(kBlock, n - i0);
    for (int64_t t = 0; t < m; ++t) row[t] = cursor[shard[i0 + t]]++;
    for (int32_t c = 0; c < k; ++c) {
      const int64_t w = width[c];
      const uint8_t* s = src[c] + w * i0;
      switch (w) {
        case 1: copy_block<1>(s, row, m, dst[c]); break;
        case 2: copy_block<2>(s, row, m, dst[c]); break;
        case 4: copy_block<4>(s, row, m, dst[c]); break;
        case 8: copy_block<8>(s, row, m, dst[c]); break;
        default: copy_block_any(s, row, m, w, dst[c]);
      }
    }
  }
}

}  // extern "C"
