// Parallel host binning: raw feature matrix -> group-local bin matrix.
//
// Native rebuild of the reference's ingestion hot loop
// (DatasetLoader::ExtractFeaturesFromMemory -> Dataset::PushOneRow ->
// BinMapper::ValueToBin, src/io/dataset_loader.cpp:1004 + bin.h:522-556,
// parallelized with OpenMP like the reference's TextReader pipeline). The
// Python layer (data/dataset.py:_bin_rows) keeps a vectorized numpy
// fallback; this path must match it bit-for-bit — semantics:
//
//   numerical: searchsorted(bounds[:n_search], v, side=left) clipped to
//     n_search-1, where n_search = num_bin - (missing_type == NaN);
//     NaN -> last bin when missing_type == NaN, else binned as 0.0;
//   categorical: int(value) (toward zero) looked up in a LUT,
//     NaN/negative/overflow -> num_bin - 1;
//   EFB bundles: group-local sentinel 0, sub-features stacked at
//     local offsets, rows at a sub-feature's most_freq bin skipped,
//     LATER sub-features overwrite earlier ones on conflict.
#include <cmath>
#include <cstdint>
#include <cstdlib>

#if defined(_OPENMP)
#include <omp.h>
#endif

// Uniform-grid accelerator for the per-feature boundary search: LUT cell j
// holds lower_bound(bounds, b0 + j*step), so a value's true bin index is
// bracketed by [LUT[j]-1, LUT[j+1]+1] (the -1/+1 absorb float round-off in
// the cell computation) and the binary search runs over a handful of
// entries instead of the full boundary array. Quantile-built boundaries
// spread ~255 entries over the value span, so with 8x as many LUT cells a
// typical bracket holds 0-2 boundaries; the dependent-load compare chain
// of the full search (~175 cycles/cell measured on this host) collapses
// to one multiply + one LUT load + a couple of compares.
static const int32_t kLutCells = 2048;

struct FeatLut {
  double b0;
  double inv_step;
  int32_t idx[kLutCells + 1];
  int32_t usable;   // 0 when the span is degenerate (single finite bound)
};

extern "C" {

// searchsorted(bounds, v, side=left): first i with bounds[i] >= v.
// Branchless: bin boundaries make the comparison direction
// data-dependent and unpredictable, so the classic branching search
// pays ~8 mispredicts per cell (measured ~200 cycles/cell); conditional
// moves bring it to the pure compare-chain cost.
static inline int32_t lower_bound_idx(const double* bounds, int32_t n,
                                      double v) {
  const double* base = bounds;
  int32_t len = n;
  while (len > 1) {
    int32_t half = len >> 1;
    base = (base[half - 1] < v) ? base + half : base;  // cmov
    len -= half;
  }
  int32_t idx = static_cast<int32_t>(base - bounds);
  return idx + (len == 1 && idx < n && base[0] < v ? 1 : 0);
}

static void build_feat_lut(FeatLut* fl, const double* bounds,
                           int32_t n_search) {
  fl->usable = 0;
  if (n_search < 4) return;
  // span the finite boundary range; the trailing bound is typically +inf
  int32_t last = n_search - 1;
  while (last > 0 && !std::isfinite(bounds[last])) --last;
  double b0 = bounds[0], b1 = bounds[last];
  if (!(std::isfinite(b0) && std::isfinite(b1) && b1 > b0)) return;
  double step = (b1 - b0) / kLutCells;
  if (!(step > 0.0)) return;
  fl->b0 = b0;
  fl->inv_step = 1.0 / step;
  for (int32_t j = 0; j <= kLutCells; ++j) {
    fl->idx[j] = lower_bound_idx(bounds, n_search, b0 + j * step);
  }
  fl->usable = 1;
}

static inline int32_t lut_lower_bound(const FeatLut* fl,
                                      const double* bounds,
                                      int32_t n_search, double v) {
  double jf = (v - fl->b0) * fl->inv_step;
  if (!(jf >= 0.0)) return v <= bounds[0] ? 0 : lower_bound_idx(
      bounds, n_search, v);
  if (jf >= kLutCells) {
    // past the last finite bound: a short search over the tail
    int32_t lo = fl->idx[kLutCells] > 0 ? fl->idx[kLutCells] - 1 : 0;
    return lo + lower_bound_idx(bounds + lo, n_search - lo, v);
  }
  int32_t j = static_cast<int32_t>(jf);
  int32_t lo = fl->idx[j] > 0 ? fl->idx[j] - 1 : 0;
  int32_t hi = fl->idx[j + 1] + 1;   // +-1 absorb float round-off
  if (hi > n_search) hi = n_search;
  return lo + lower_bound_idx(bounds + lo, hi - lo, v);
}

static inline int32_t value_to_bin(
    double v, int32_t num_bin, int32_t missing_type, int32_t is_cat,
    const double* bounds, const int32_t* lut, int64_t lut_size,
    const FeatLut* fl) {
  if (is_cat) {
    if (std::isnan(v) || !std::isfinite(v)) return num_bin - 1;
    // range-check BEFORE the cast: float->int conversion of a value
    // outside int64's range is UB in C++, while the numpy fallback's
    // astype(int64) saturates and maps to num_bin - 1
    if (!(v >= 0.0 && v < static_cast<double>(lut_size))) return num_bin - 1;
    int64_t iv = static_cast<int64_t>(v);  // toward zero, like numpy astype
    return lut[iv];
  }
  if (std::isnan(v)) {
    if (missing_type == 2) return num_bin - 1;
    v = 0.0;
  }
  int32_t n_search = num_bin - (missing_type == 2 ? 1 : 0);
  int32_t idx = (fl != nullptr && fl->usable)
      ? lut_lower_bound(fl, bounds, n_search, v)
      : lower_bound_idx(bounds, n_search, v);
  return idx < n_search - 1 ? idx : n_search - 1;
}

// out element width selected by out_bytes in {1, 2, 4}
static inline void store_bin(void* out, int32_t out_bytes, int64_t pos,
                             int64_t val) {
  if (out_bytes == 1) {
    static_cast<uint8_t*>(out)[pos] = static_cast<uint8_t>(val);
  } else if (out_bytes == 2) {
    static_cast<uint16_t*>(out)[pos] = static_cast<uint16_t>(val);
  } else {
    static_cast<int32_t*>(out)[pos] = static_cast<int32_t>(val);
  }
}

void bin_rows(const double* X, int64_t n, int64_t stride, int32_t G,
              const int32_t* group_ptr, const int32_t* feat_col,
              const int32_t* feat_numbin, const int32_t* feat_mostfreq,
              const int32_t* feat_missing, const int32_t* feat_iscat,
              const int64_t* bounds_ptr, const double* bounds,
              const int64_t* lut_ptr, const int32_t* lut,
              void* out, int32_t out_bytes, int64_t out_stride) {
  int32_t K = group_ptr[G];
  // LUT construction costs ~2k searches per feature: only worth it when
  // the row count amortizes it, and degrade to the plain search when the
  // allocation fails (wide one-hot matrices can make K huge)
  FeatLut* fluts = nullptr;
  if (n >= 4096) {
    fluts = static_cast<FeatLut*>(malloc(sizeof(FeatLut) * K));
  }
  if (fluts != nullptr) {
    // per-feature builds are independent; wide one-hot matrices make K
    // large enough that a serial build would rival the binning itself
#if defined(_OPENMP)
#pragma omp parallel for schedule(static)
#endif
    for (int32_t k = 0; k < K; ++k) {
      fluts[k].usable = 0;
      if (!feat_iscat[k]) {
        int32_t n_search = feat_numbin[k] - (feat_missing[k] == 2 ? 1 : 0);
        build_feat_lut(&fluts[k], bounds + bounds_ptr[k], n_search);
      }
    }
  }

#if defined(_OPENMP)
#pragma omp parallel for schedule(static)
#endif
  for (int64_t i = 0; i < n; ++i) {
    const double* row = X + i * stride;
    for (int32_t g = 0; g < G; ++g) {
      int32_t k0 = group_ptr[g], k1 = group_ptr[g + 1];
      int64_t val;
      if (k1 - k0 == 1) {
        int32_t k = k0;
        val = value_to_bin(row[feat_col[k]], feat_numbin[k],
                           feat_missing[k], feat_iscat[k],
                           bounds + bounds_ptr[k], lut + lut_ptr[k],
                           lut_ptr[k + 1] - lut_ptr[k],
                           fluts ? &fluts[k] : nullptr);
      } else {
        val = 0;  // group-local sentinel (default) bin
        int64_t local = 1;
        for (int32_t k = k0; k < k1; ++k) {
          int32_t b = value_to_bin(row[feat_col[k]], feat_numbin[k],
                                   feat_missing[k], feat_iscat[k],
                                   bounds + bounds_ptr[k],
                                   lut + lut_ptr[k],
                                   lut_ptr[k + 1] - lut_ptr[k],
                                   fluts ? &fluts[k] : nullptr);
          if (b != feat_mostfreq[k]) {
            val = local + b;
          }
          local += feat_numbin[k];
        }
      }
      store_bin(out, out_bytes, i * out_stride + g, val);
    }
  }
  free(fluts);
}

// CSR by stored values: the same `out` as bin_rows on the dense matrix,
// cell for cell, from a canonical CSR (columns ascending in a row, no
// duplicates). A row's value in a group is decided by the LAST feature of
// the group (in group order) whose bin leaves its most frequent bin, where
// a feature without a stored value sits in the bin a zero falls in; only
// the stored entries are binned, the zeros' bins once per feature.
// data_bytes in {4, 8}: float32 or float64 stored values.
void bin_csr(const void* data, int32_t data_bytes, const int32_t* indices,
             const int64_t* indptr, int64_t n, int64_t ncols, int32_t G,
             const int32_t* group_ptr, const int32_t* feat_col,
             const int32_t* feat_numbin, const int32_t* feat_mostfreq,
             const int32_t* feat_missing, const int32_t* feat_iscat,
             const int64_t* bounds_ptr, const double* bounds,
             const int64_t* lut_ptr, const int32_t* lut,
             void* out, int32_t out_bytes, int64_t out_stride) {
  const float* data32 = static_cast<const float*>(data);
  const double* data64 = static_cast<const double*>(data);

  int32_t K = group_ptr[G];
  auto bin_of = [&](int32_t k, double v) {
    return value_to_bin(v, feat_numbin[k], feat_missing[k], feat_iscat[k],
                        bounds + bounds_ptr[k], lut + lut_ptr[k],
                        lut_ptr[k + 1] - lut_ptr[k], nullptr);
  };
  // column -> flat feature index (-1: a column no group uses)
  int32_t* col_k = static_cast<int32_t*>(malloc(sizeof(int32_t) * ncols));
  int32_t* feat_group = static_cast<int32_t*>(malloc(sizeof(int32_t) * K));
  int64_t* feat_local = static_cast<int64_t*>(malloc(sizeof(int64_t) * K));
  int32_t* zero_bin = static_cast<int32_t*>(malloc(sizeof(int32_t) * K));
  // a row's value in a group before any stored value is seen: the zero's
  // bin for a feature alone, the sentinel 0 in a bundle
  int64_t* group_zero = static_cast<int64_t*>(malloc(sizeof(int64_t) * G));
  // per bundle: does any feature's zero leave its most frequent bin?
  int32_t* group_zout = static_cast<int32_t*>(malloc(sizeof(int32_t) * G));
  for (int64_t c = 0; c < ncols; ++c) col_k[c] = -1;
  for (int32_t g = 0; g < G; ++g) {
    int32_t k0 = group_ptr[g], k1 = group_ptr[g + 1];
    bool multi = k1 - k0 > 1;
    int64_t local = multi ? 1 : 0;
    group_zero[g] = 0;
    group_zout[g] = 0;
    for (int32_t k = k0; k < k1; ++k) {
      col_k[feat_col[k]] = k;
      feat_group[k] = g;
      feat_local[k] = local;
      zero_bin[k] = bin_of(k, 0.0);
      if (!multi) {
        group_zero[g] = zero_bin[k];
      } else if (zero_bin[k] != feat_mostfreq[k]) {
        group_zout[g] = 1;
      }
      local += feat_numbin[k];
    }
  }

#if defined(_OPENMP)
#pragma omp parallel
#endif
  {
    // the winning feature of each group in this row, and its value
    int32_t* win_k = static_cast<int32_t*>(malloc(sizeof(int32_t) * G));
    int64_t* win_v = static_cast<int64_t*>(malloc(sizeof(int64_t) * G));
    // stored[k] == i + 1: feature k has a stored value in row i
    int64_t* stored = static_cast<int64_t*>(calloc(K, sizeof(int64_t)));
#if defined(_OPENMP)
#pragma omp for schedule(static)
#endif
    for (int64_t i = 0; i < n; ++i) {
      for (int32_t g = 0; g < G; ++g) {
        win_k[g] = -1;
        win_v[g] = group_zero[g];
      }
      for (int64_t e = indptr[i]; e < indptr[i + 1]; ++e) {
        int64_t c = indices[e];
        if (c < 0 || c >= ncols) continue;
        int32_t k = col_k[c];
        if (k < 0) continue;
        double v = data_bytes == 4 ? static_cast<double>(data32[e])
                                   : data64[e];
        int32_t b = bin_of(k, v);
        int32_t g = feat_group[k];
        stored[k] = i + 1;
        if (group_ptr[g + 1] - group_ptr[g] == 1) {
          win_v[g] = b;
        } else if (b != feat_mostfreq[k] && k > win_k[g]) {
          win_k[g] = k;
          win_v[g] = feat_local[k] + b;
        }
      }
      for (int32_t g = 0; g < G; ++g) {
        int64_t val = win_v[g];
        if (group_zout[g]) {
          // a zero that leaves the most frequent bin writes too, unless
          // the row stores a value there: the last such feature after the
          // stored winner takes the cell (none: the group's sentinel)
          int32_t k0 = group_ptr[g], k1 = group_ptr[g + 1];
          for (int32_t k = k1 - 1; k > win_k[g] && k >= k0; --k) {
            if (zero_bin[k] != feat_mostfreq[k] && stored[k] != i + 1) {
              val = feat_local[k] + zero_bin[k];
              break;
            }
          }
        }
        store_bin(out, out_bytes, i * out_stride + g, val);
      }
    }
    free(win_k);
    free(win_v);
    free(stored);
  }
  free(col_k);
  free(feat_group);
  free(feat_local);
  free(zero_bin);
  free(group_zero);
  free(group_zout);
}

int32_t binrows_num_threads() {
#if defined(_OPENMP)
  return omp_get_max_threads();
#else
  return 1;
#endif
}

}  // extern "C"
