// fedtpu native codec — host-side kernels for the DCN-edge wire path.
//
// The reference's only "native" muscle is in its dependencies (gRPC C-core,
// protobuf, ATen — SURVEY §2c); its own compression is transport gzip over
// base64 (src/server.py:104-107). fedtpu's edge codec instead ships sparse
// top-k / int8 payloads; the selection and packing below are the host-side
// hot loops (the on-device path uses Pallas kernels, fedtpu/ops/pallas_kernels.py).
//
// Build: on first use by fedtpu/native.py (g++ -O3 -shared -fPIC), under a
// file name hashed from this source and the flags.
// ABI: plain C, loaded via ctypes (no pybind11 in this environment).

#include <algorithm>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <vector>

extern "C" {

// k-th largest |x| over n elements (k >= 1): the keep-threshold for top-k
// sparsification. O(n) average via nth_element, vs O(n log n) for a sort.
float fedtpu_kth_magnitude(const float* x, int64_t n, int64_t k) {
  if (n <= 0) return 0.0f;
  if (k < 1) k = 1;
  if (k > n) k = n;
  std::vector<float> mag(n);
  for (int64_t i = 0; i < n; ++i) mag[i] = std::fabs(x[i]);
  std::nth_element(mag.begin(), mag.begin() + (k - 1), mag.end(),
                   std::greater<float>());
  return mag[k - 1];
}

// Pack entries with |x| >= thresh into (idx, vals); returns count written
// (capped at cap). Single pass, branch-light.
int64_t fedtpu_pack_sparse(const float* x, int64_t n, float thresh,
                           int32_t* idx, float* vals, int64_t cap) {
  int64_t m = 0;
  for (int64_t i = 0; i < n && m < cap; ++i) {
    float v = x[i];
    if (std::fabs(v) >= thresh) {
      idx[m] = static_cast<int32_t>(i);
      vals[m] = v;
      ++m;
    }
  }
  return m;
}

// Scatter (idx, vals) into out[n]; out must be zero-initialised by caller.
void fedtpu_unpack_sparse(const int32_t* idx, const float* vals, int64_t nnz,
                          float* out) {
  for (int64_t i = 0; i < nnz; ++i) out[idx[i]] = vals[i];
}

// Symmetric int8 quantisation: round(x / scale) clamped to [-127, 127].
// scale == 0 (all-zero input) yields all-zero codes.
void fedtpu_quant_int8(const float* x, int64_t n, float scale, int8_t* out) {
  if (scale <= 0.0f) {
    std::memset(out, 0, static_cast<size_t>(n));
    return;
  }
  const float inv = 1.0f / scale;
  for (int64_t i = 0; i < n; ++i) {
    float q = std::nearbyint(x[i] * inv);
    q = q > 127.0f ? 127.0f : (q < -127.0f ? -127.0f : q);
    out[i] = static_cast<int8_t>(q);
  }
}

void fedtpu_dequant_int8(const int8_t* x, int64_t n, float scale, float* out) {
  for (int64_t i = 0; i < n; ++i) out[i] = scale * static_cast<float>(x[i]);
}

// Fused residual update for error feedback on the edge: given the dense
// delta d and threshold t, write kept entries to (idx, vals) and the dropped
// mass to residual (residual[i] = d[i] where |d[i]| < t, else 0).
int64_t fedtpu_pack_sparse_with_residual(const float* d, int64_t n,
                                         float thresh, int32_t* idx,
                                         float* vals, int64_t cap,
                                         float* residual) {
  int64_t m = 0;
  for (int64_t i = 0; i < n; ++i) {
    float v = d[i];
    if (std::fabs(v) >= thresh && m < cap) {
      idx[m] = static_cast<int32_t>(i);
      vals[m] = v;
      residual[i] = 0.0f;
      ++m;
    } else {
      residual[i] = v;
    }
  }
  return m;
}

}  // extern "C"
