// batch_assembler: native minibatch assembly for the loader hot path.
//
// The reference's data-plane hot paths are native (CL/CUDA kernels fed by
// C-backed numpy ops); this keeps the rebuilt loader's per-step work native
// too (SURVEY.md 2.4 rebuild mapping).  Exposed as a plain C ABI for ctypes
// (the environment has no pybind11).  All functions are thread-parallel.
//
// Build (loader/native.py does, once a source digest):
//   g++ -O3 -shared -fPIC -std=c++17 -o libbatch_assembler.so
//       batch_assembler.cc -pthread

#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

#if defined(__x86_64__)
#include <tmmintrin.h>
#endif

namespace {

// run fn(begin, end) over [0, n) split across hardware threads
template <typename Fn>
void parallel_for(int64_t n, Fn fn) {
  unsigned hw = std::thread::hardware_concurrency();
  int64_t n_threads = hw ? static_cast<int64_t>(hw) : 4;
  if (n_threads > n) n_threads = n > 0 ? n : 1;
  int64_t chunk = (n + n_threads - 1) / n_threads;
  std::vector<std::thread> threads;
  for (int64_t t = 0; t < n_threads; ++t) {
    int64_t begin = t * chunk;
    int64_t end = begin + chunk < n ? begin + chunk : n;
    if (begin >= end) break;
    threads.emplace_back([=] { fn(begin, end); });
  }
  for (auto& th : threads) th.join();
}

// One row of a flipped crop, 3 bytes a pixel: drow[j] = srow[n - 1 - j].
// The pixel's width is a constant here, so the moves are inlined (the
// general loop below calls memcpy once a pixel with a runtime size).
inline void flip_row_c3(const uint8_t* srow, uint8_t* drow, int64_t n) {
  const uint8_t* s = srow + (n - 1) * 3;
  for (int64_t j = 0; j < n; ++j, s -= 3, drow += 3) {
    drow[0] = s[0];
    drow[1] = s[1];
    drow[2] = s[2];
  }
}

#if defined(__x86_64__)
// The same row five pixels a turn: a 16-byte load whose FIRST byte belongs
// to the pixel left of the five, one byte shuffle, a 16-byte store whose
// LAST byte falls on the next pixel of the destination and is written
// again by the next turn or the tail.  A turn runs only while six or more
// pixels remain, so the load never starts before the row's window and the
// store never ends past the destination row: the last row of a
// memory-mapped file and the last row of the output are safe.
__attribute__((target("ssse3")))
void flip_row_c3_ssse3(const uint8_t* srow, uint8_t* drow, int64_t n) {
  const __m128i reverse = _mm_setr_epi8(13, 14, 15, 10, 11, 12, 7, 8, 9,
                                        4, 5, 6, 1, 2, 3, -1);
  int64_t left = n;  // source pixels [0, left) still to place
  for (; left >= 6; left -= 5, drow += 15) {
    __m128i v = _mm_loadu_si128(
        reinterpret_cast<const __m128i*>(srow + (left - 5) * 3 - 1));
    _mm_storeu_si128(reinterpret_cast<__m128i*>(drow),
                     _mm_shuffle_epi8(v, reverse));
  }
  flip_row_c3(srow, drow, left);
}

#endif

// chosen once, from what the CPU says it has: the library is built with
// plain -O3 and cached by its source's digest, so it may be loaded on
// another machine than the one that built it
using FlipRowC3 = void (*)(const uint8_t*, uint8_t*, int64_t);
FlipRowC3 pick_flip_row_c3() {
#if defined(__x86_64__)
  __builtin_cpu_init();
  if (__builtin_cpu_supports("ssse3")) return flip_row_c3_ssse3;
#endif
  return flip_row_c3;
}
const FlipRowC3 kFlipRowC3 = pick_flip_row_c3();

}  // namespace

extern "C" {

// Gather rows: out[i, :] = data[indices[i], :].  f32, row-major.
void gather_rows_f32(const float* data, int64_t feat, const int64_t* indices,
                     int64_t batch, float* out) {
  parallel_for(batch, [&](int64_t begin, int64_t end) {
    for (int64_t i = begin; i < end; ++i) {
      std::memcpy(out + i * feat, data + indices[i] * feat,
                  static_cast<size_t>(feat) * sizeof(float));
    }
  });
}

// Gather rows from uint8 storage with affine normalize:
// out[i, j] = data[indices[i], j] / scale + shift.
// Keeps the dataset in u8 (4x less host RAM) and converts per batch.
void gather_rows_u8_normalize(const uint8_t* data, int64_t feat,
                              const int64_t* indices, int64_t batch,
                              float scale, float shift, float* out) {
  float inv = 1.0f / scale;
  parallel_for(batch, [&](int64_t begin, int64_t end) {
    for (int64_t i = begin; i < end; ++i) {
      const uint8_t* src = data + indices[i] * feat;
      float* dst = out + i * feat;
      for (int64_t j = 0; j < feat; ++j) dst[j] = src[j] * inv + shift;
    }
  });
}

// Gather random/center crops (optionally h-flipped) from packed u8 images.
// data: [n_imgs, H, W, C] u8; per sample i: copy the window
// data[indices[i], oy[i]:oy[i]+out_h, ox[i]:ox[i]+out_w, :] into
// out[i, :, :, :], reversing the W axis when flip[i] != 0.  Output stays u8 —
// the affine normalize runs on-device (fused into the XLA step), so the
// host->device transfer is 4x smaller than f32.
void crop_gather_u8(const uint8_t* data, int64_t h, int64_t w, int64_t c,
                    const int64_t* indices, const int64_t* oy,
                    const int64_t* ox, const uint8_t* flip, int64_t batch,
                    int64_t out_h, int64_t out_w, uint8_t* out) {
  const int64_t img = h * w * c;
  const int64_t out_img = out_h * out_w * c;
  parallel_for(batch, [&](int64_t begin, int64_t end) {
    for (int64_t i = begin; i < end; ++i) {
      const uint8_t* src = data + indices[i] * img + (oy[i] * w + ox[i]) * c;
      uint8_t* dst = out + i * out_img;
      if (!flip[i]) {
        for (int64_t r = 0; r < out_h; ++r)
          std::memcpy(dst + r * out_w * c, src + r * w * c,
                      static_cast<size_t>(out_w) * c);
      } else if (c == 3) {
        for (int64_t r = 0; r < out_h; ++r)
          kFlipRowC3(src + r * w * 3, dst + r * out_w * 3, out_w);
      } else {
        for (int64_t r = 0; r < out_h; ++r) {
          const uint8_t* srow = src + r * w * c;
          uint8_t* drow = dst + r * out_w * c;
          for (int64_t col = 0; col < out_w; ++col)
            std::memcpy(drow + col * c, srow + (out_w - 1 - col) * c,
                        static_cast<size_t>(c));
        }
      }
    }
  });
}

// Whether crop_gather_u8 reverses a flipped row of c-byte pixels sixteen
// bytes at a time (1) or pixel by pixel (0): for the caller's counter.
int32_t crop_flip_is_wide(int64_t c) {
  return c == 3 && kFlipRowC3 != flip_row_c3;
}

// Plain u8 row gather (no conversion): feeds the u8->device path where the
// normalize happens on-device instead of on-host.
void gather_rows_u8_raw(const uint8_t* data, int64_t feat,
                        const int64_t* indices, int64_t batch, uint8_t* out) {
  parallel_for(batch, [&](int64_t begin, int64_t end) {
    for (int64_t i = begin; i < end; ++i) {
      std::memcpy(out + i * feat, data + indices[i] * feat,
                  static_cast<size_t>(feat));
    }
  });
}

// In-place affine normalize of an f32 block (mean/disp style per-feature).
// out[i, j] = (out[i, j] - mean[j]) * inv_disp[j]
void normalize_rows_f32(float* data, int64_t rows, int64_t feat,
                        const float* mean, const float* inv_disp) {
  parallel_for(rows, [&](int64_t begin, int64_t end) {
    for (int64_t i = begin; i < end; ++i) {
      float* row = data + i * feat;
      for (int64_t j = 0; j < feat; ++j)
        row[j] = (row[j] - mean[j]) * inv_disp[j];
    }
  });
}

}  // extern "C"
