"""Binned training dataset: host-side construction, device-side layout.

TPU-native rebuild of the reference data layer (include/LightGBM/dataset.h:333,
src/io/dataset.cpp, feature_group.h:21). Differences by design:

  * The binned matrix is one dense [num_data, num_groups] integer array of
    group-local bins living in TPU HBM (row-sharded over the mesh in
    distributed mode) instead of per-group Bin objects with dense/sparse/4-bit
    variants — HBM bandwidth is the constraint, so the narrowest dtype that
    fits a group's bin count is chosen (uint8/uint16/int32).
  * EFB (exclusive feature bundling, reference src/io/dataset.cpp:41-314)
    keeps its greedy conflict-bounded grouping, but a bundled group reserves
    group-local bin 0 as the "all features at default" sentinel, and each
    sub-feature keeps its full local bin range. Rows never write a
    sub-feature's most_freq bin; histograms for bundled features are repaired
    from leaf totals exactly like the reference's FixHistogram
    (src/io/dataset.cpp:1410) — see ops/split.fix_histogram.
  * Metadata (labels/weights/query boundaries/init_score) mirrors
    include/LightGBM/dataset.h:41 and src/io/metadata.cpp.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..config import Config
from ..telemetry import events as telemetry_events
from ..utils.log import Log
from .bin_mapper import BinMapper, BinType, kZeroThreshold

MAX_GROUP_BINS = 256  # keep bundled groups addressable by uint8 (GPU ref: 256)


class Metadata:
    """Labels, weights, query boundaries, init scores (dataset.h:41)."""

    def __init__(self, num_data: int):
        self.num_data = num_data
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None  # [nq+1] int32
        self.init_score: Optional[np.ndarray] = None

    def set_label(self, label) -> None:
        label = np.ascontiguousarray(label, dtype=np.float32).reshape(-1)
        if len(label) != self.num_data:
            Log.fatal("Length of label (%d) != num_data (%d)"
                      % (len(label), self.num_data))
        self.label = label

    def set_weight(self, weight) -> None:
        if weight is None:
            self.weight = None
            return
        weight = np.ascontiguousarray(weight, dtype=np.float32).reshape(-1)
        if len(weight) != self.num_data:
            Log.fatal("Length of weight (%d) != num_data (%d)"
                      % (len(weight), self.num_data))
        self.weight = weight

    def set_query(self, group) -> None:
        """group: per-query sizes (LightGBM convention) or boundaries."""
        if group is None:
            self.query_boundaries = None
            return
        group = np.ascontiguousarray(group, dtype=np.int64).reshape(-1)
        if group.sum() == self.num_data:
            self.query_boundaries = np.concatenate(
                [[0], np.cumsum(group)]).astype(np.int32)
        elif len(group) and group[0] == 0 and group[-1] == self.num_data:
            self.query_boundaries = group.astype(np.int32)
        else:
            Log.fatal("Sum of query counts (%d) != num_data (%d)"
                      % (group.sum(), self.num_data))

    def set_init_score(self, init_score) -> None:
        if init_score is None:
            self.init_score = None
            return
        self.init_score = np.ascontiguousarray(
            init_score, dtype=np.float64).reshape(-1)

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1

    @property
    def query_weights(self) -> Optional[np.ndarray]:
        """Mean row weight per query (Metadata::LoadQueryWeights,
        src/io/metadata.cpp:455-469); None without weights or queries."""
        if self.weight is None or self.query_boundaries is None:
            return None
        qb = self.query_boundaries
        sums = np.add.reduceat(self.weight.astype(np.float64), qb[:-1])
        return (sums / np.diff(qb)).astype(np.float32)


class SampleCols:
    """Per-feature sampled (values, row-indices) — the reference's own
    sample representation (DatasetLoader::CostructFromSampleData takes
    sample_values/sample_indices per feature, src/io/dataset_loader.cpp:528)
    — so sparse inputs sample without densifying."""

    def __init__(self, values, rows, total):
        self.values = values
        self.rows = rows
        self.total = total


# columns from which BinMapper.find_bin runs on a thread pool
_FIND_BIN_THREADS_MIN_FEATURES = 64


def _sample_data(X: np.ndarray, sample_cnt: int, seed: int) -> np.ndarray:
    n = X.shape[0]
    if n <= sample_cnt:
        return X
    rng = np.random.default_rng(seed)
    idx = rng.choice(n, size=sample_cnt, replace=False)
    idx.sort()
    return X[idx]


def _greedy_bundle(nonzero_masks: List[np.ndarray], order: List[int],
                   num_bins: List[int], total_sample: int,
                   max_conflict_cnt: int) -> List[List[int]]:
    """Greedy conflict-bounded bundling (reference FindGroups,
    src/io/dataset.cpp:97-234, simplified: no GPU bin cap branch, no random
    search-group subsampling — the search set is all compatible groups)."""
    groups: List[List[int]] = []
    marks: List[np.ndarray] = []
    conflict_used: List[int] = []
    group_bins: List[int] = []
    for fidx in order:
        nz = nonzero_masks[fidx]
        cnt = int(nz.sum())
        placed = False
        for gid in range(len(groups)):
            if group_bins[gid] + num_bins[fidx] + 1 > MAX_GROUP_BINS:
                continue
            rest = max_conflict_cnt - conflict_used[gid]
            if rest < 0:
                continue
            conflict = int((marks[gid] & nz).sum())
            if conflict <= rest and conflict <= cnt // 2:
                groups[gid].append(fidx)
                marks[gid] |= nz
                conflict_used[gid] += conflict
                group_bins[gid] += num_bins[fidx]
                placed = True
                break
        if not placed:
            groups.append([fidx])
            marks.append(nz.copy())
            conflict_used.append(0)
            group_bins.append(num_bins[fidx] + 1)
    return groups


def nibble_slot_partition(widths):
    """(wide, pairs, leftover): the shared 4-bit slot-assignment policy.

    Groups whose bin count fits 4 bits pair up two per byte slot; the
    rest keep full byte slots. ONE implementation feeds both storage
    packers — BinnedDataset.device_pack_plan (HBM v1 storage) and the
    persist payload plan (ops/grow_persist._payload_plan) — so the
    pairing threshold/order cannot drift between them.
    """
    G = len(widths)
    narrow = [g for g in range(G) if widths[g] <= 16]
    wide = [g for g in range(G) if widths[g] > 16]
    pairs = [(narrow[i], narrow[i + 1])
             for i in range(0, len(narrow) - 1, 2)]
    leftover = narrow[-1] if len(narrow) % 2 else None
    return wide, pairs, leftover


class BinnedDataset:
    """The binned training matrix + per-feature metadata (dataset.h:333)."""

    def __init__(self):
        self.num_data: int = 0
        self.num_total_features: int = 0
        self.feature_names: List[str] = []
        self.bin_mappers: List[BinMapper] = []        # per original feature
        self.used_features: List[int] = []            # original idx, non-trivial
        self.inner_of: Dict[int, int] = {}            # original -> inner
        self.groups: List[List[int]] = []             # inner feature ids
        self.metadata: Optional[Metadata] = None
        # host arrays describing the device layout
        self.binned: Optional[np.ndarray] = None      # [N, G] narrow dtype
        self.group_offset: Optional[np.ndarray] = None  # [G] i32
        self.group_of: Optional[np.ndarray] = None    # [F_inner] i32
        self.bin_start: Optional[np.ndarray] = None   # [F_inner] i32 global
        self.bin_end: Optional[np.ndarray] = None
        self.most_freq_bin: Optional[np.ndarray] = None
        self.default_bin: Optional[np.ndarray] = None
        self.missing_type_arr: Optional[np.ndarray] = None
        self.is_categorical: Optional[np.ndarray] = None
        self.monotone: Optional[np.ndarray] = None
        self.penalty: Optional[np.ndarray] = None
        self.needs_fix: Optional[np.ndarray] = None   # bundled features
        self.total_bins: int = 0
        # multi-value (ELL row-sparse) storage, the MultiValBin/SparseBin
        # analog — populated instead of `binned` when the dense [N, G]
        # matrix would dwarf the per-row non-default entries
        # (ref src/io/multi_val_sparse_bin.hpp, sparse_bin.hpp)
        self.is_multival: bool = False
        self.ell_grp: Optional[np.ndarray] = None     # [N, K] group ids
        self.ell_bin: Optional[np.ndarray] = None     # [N, K] local bins

    # ------------------------------------------------------------------
    @classmethod
    def from_matrix(cls, X, config: Config,
                    categorical_features: Sequence[int] = (),
                    label=None, weight=None, group=None, init_score=None,
                    feature_names: Optional[List[str]] = None,
                    reference: Optional["BinnedDataset"] = None,
                    ) -> "BinnedDataset":
        """Build from an in-memory matrix (reference
        DatasetLoader::CostructFromSampleData, src/io/dataset_loader.cpp:528).

        If `reference` is given (a validation set aligned to a train set),
        its BinMappers and grouping are reused
        (LoadFromFileAlignWithOtherDataset, dataset_loader.cpp:230).
        """
        X = np.ascontiguousarray(X, dtype=np.float64)
        n, nf = X.shape
        ds = cls()
        ds.num_data = n
        ds.num_total_features = nf
        ds.feature_names = feature_names or ["Column_%d" % i for i in range(nf)]
        ds.metadata = Metadata(n)
        if label is not None:
            ds.metadata.set_label(label)
        ds.metadata.set_weight(weight)
        ds.metadata.set_query(group)
        ds.metadata.set_init_score(init_score)

        if reference is not None:
            ds.bin_mappers = reference.bin_mappers
            ds.used_features = reference.used_features
            ds.inner_of = reference.inner_of
            ds.groups = reference.groups
            ds._finish_layout_like(reference)
            ds._push_matrix(X)
            return ds

        cat_set = set(int(c) for c in categorical_features)
        sample = _sample_data(X, config.bin_construct_sample_cnt,
                              config.data_random_seed)
        with telemetry_events.scope("io::FindBinAndGroup", category="io",
                                    always=True):
            ds._construct_from_sample(sample, n, config, cat_set)
        with telemetry_events.scope("io::PushMatrix(binning)", category="io",
                                    always=True):
            ds._push_matrix(X)
        return ds

    def _construct_from_sample(self, sample: np.ndarray, n: int,
                               config: Config, cat_set) -> None:
        """BinMapper construction + EFB grouping + layout from a row sample
        (DatasetLoader::CostructFromSampleData, dataset_loader.cpp:528)."""
        ds = self
        nf = ds.num_total_features
        total_sample = (sample.total if isinstance(sample, SampleCols)
                        else sample.shape[0])
        filter_cnt = max(
            int(config.min_data_in_leaf * total_sample / max(n, 1)), 1)

        forced: Dict[int, List[float]] = _load_forced_bins(
            config.forcedbins_filename, nf)

        def _col(f):
            if isinstance(sample, SampleCols):
                return sample.values[f]
            return sample[:, f]

        mbbf = list(config.max_bin_by_feature)
        if mbbf and len(mbbf) != nf:
            Log.fatal("max_bin_by_feature has %d entries for %d features"
                      % (len(mbbf), nf))

        def _mapper(f):
            col = _col(f)
            nonzero = col[(np.abs(col) > kZeroThreshold) | np.isnan(col)]
            m = BinMapper()
            m.find_bin(
                nonzero, total_sample,
                int(mbbf[f]) if mbbf else config.max_bin,
                config.min_data_in_bin,
                filter_cnt, pre_filter=bool(config.feature_pre_filter),
                bin_type=BinType.CATEGORICAL if f in cat_set else BinType.NUMERICAL,
                use_missing=config.use_missing,
                zero_as_missing=config.zero_as_missing,
                forced_upper_bounds=forced.get(f, ()))
            return m

        if nf >= _FIND_BIN_THREADS_MIN_FEATURES:
            # a feature's mapper hangs on its own column alone, and most of
            # its time is a sort of the sampled values, which numpy runs
            # without the interpreter's lock: 2,000 dense columns of a
            # 200,000-row sample take a minute on one thread
            with ThreadPoolExecutor(min(os.cpu_count() or 1, 16)) as pool:
                ds.bin_mappers = list(pool.map(_mapper, range(nf)))
        else:
            ds.bin_mappers = [_mapper(f) for f in range(nf)]

        ds.used_features = [f for f in range(nf) if not ds.bin_mappers[f].is_trivial]
        if not ds.used_features:
            Log.warning("There are no meaningful features, as all feature "
                        "values are constant.")
        ds.inner_of = {f: i for i, f in enumerate(ds.used_features)}

        # ---- EFB grouping over inner features -------------------------
        inner_mappers = [ds.bin_mappers[f] for f in ds.used_features]
        n_inner = len(inner_mappers)
        if config.enable_bundle and n_inner > 1:
            with telemetry_events.scope("io::FindGroups(EFB)", category="io",
                                        always=True):
                ds.groups = ds._find_groups(sample, inner_mappers,
                                            total_sample, config)
        else:
            ds.groups = [[i] for i in range(n_inner)]
        # run record: the newest construct's grouping (set, not summed)
        telemetry_events.clear_counts_prefix("io::efb_")
        telemetry_events.count("io::efb_groups", float(len(ds.groups)),
                               category="io")
        telemetry_events.count(
            "io::efb_bundled_features",
            float(sum(len(g) for g in ds.groups if len(g) > 1)),
            category="io")

        ds._finish_layout(config)

    def _find_groups(self, sample, inner_mappers, total_sample: int,
                     config: Config) -> List[List[int]]:
        """EFB: the rows of the sample outside each feature's most frequent
        bin, then the greedy conflict-bounded bundling over them."""
        nz_masks = []
        for i, f in enumerate(self.used_features):
            mapper = inner_mappers[i]
            if isinstance(sample, SampleCols):
                bins = mapper.value_to_bin(sample.values[f])
                # a row without a stored value sits where a zero falls
                mask = np.full(total_sample,
                               mapper.default_bin != mapper.most_freq_bin)
                mask[sample.rows[f]] = bins != mapper.most_freq_bin
                nz_masks.append(mask)
            else:
                bins = mapper.value_to_bin(sample[:, f])
                nz_masks.append(bins != mapper.most_freq_bin)
        order = sorted(range(len(inner_mappers)),
                       key=lambda i: -int(nz_masks[i].sum()))
        max_conflict = int(total_sample / 10000
                           + config.max_conflict_rate * total_sample)
        return _greedy_bundle(
            nz_masks, order, [m.num_bin for m in inner_mappers],
            total_sample, max_conflict)

    @classmethod
    def from_sparse(cls, X, config: Config,
                    categorical_features: Sequence[int] = (),
                    label=None, weight=None, group=None, init_score=None,
                    feature_names: Optional[List[str]] = None,
                    reference: Optional["BinnedDataset"] = None,
                    ) -> "BinnedDataset":
        """CSR ingest: sample -> bin mappers -> binning of the stored values
        only, never materializing the dense [n, features] matrix (the
        reference pushes a sparse row's nonzeros through
        Dataset::PushOneRow the same way, src/io/dataset_loader.cpp:
        714-1004). Host memory: the binned output [n, groups] (the numpy
        fallback also makes one CSC copy of the input). The multi-value
        (ELL) layout still bins dense row chunks of ~256 MB."""
        import scipy.sparse as sp  # noqa: F401 — import guard: a clear ImportError beats a tocsr AttributeError
        X = X.tocsr()
        if not X.has_canonical_format:
            # sorted columns, duplicates summed: what todense() would read
            X = X.copy()
            X.sum_duplicates()
        n, nf = X.shape
        ds = cls()
        ds.num_data = n
        ds.num_total_features = nf
        ds.feature_names = (feature_names
                            or ["Column_%d" % i for i in range(nf)])
        ds.metadata = Metadata(n)
        if label is not None:
            ds.metadata.set_label(label)
        ds.metadata.set_weight(weight)
        ds.metadata.set_query(group)
        ds.metadata.set_init_score(init_score)

        if reference is None:
            cat_set = set(int(c) for c in categorical_features)
            cnt = int(config.bin_construct_sample_cnt)
            if n <= cnt:
                samp = X
                total = n
            else:
                rng = np.random.default_rng(config.data_random_seed)
                idx = rng.choice(n, size=cnt, replace=False)
                idx.sort()
                samp = X[idx]
                total = cnt
            sc = samp.tocsc()
            vals = [sc.data[sc.indptr[f]:sc.indptr[f + 1]].astype(np.float64)
                    for f in range(nf)]
            rows = [sc.indices[sc.indptr[f]:sc.indptr[f + 1]]
                    for f in range(nf)]
            with telemetry_events.scope("io::FindBinAndGroup",
                                        category="io", always=True):
                ds._construct_from_sample(SampleCols(vals, rows, total),
                                          n, config, cat_set)
        else:
            ds.bin_mappers = reference.bin_mappers
            ds.used_features = reference.used_features
            ds.inner_of = reference.inner_of
            ds.groups = reference.groups
            ds._finish_layout_like(reference)

        with telemetry_events.scope("io::PushSparse(binning)",
                                    category="io", always=True):
            if ds._choose_multival(config, X):
                # stream into the multi-value layout: host memory is
                # bounded by one dense chunk + the non-default entries
                # (the dense [n, G] matrix is never materialized)
                G = len(ds.groups)
                chunk = max(1024, int(2 ** 25 / max(nf, 1)))
                gd = ds.group_default_bins()
                buf = np.zeros((chunk, G), dtype=ds._bin_dtype())
                coo = []
                for a in range(0, n, chunk):
                    b = min(a + chunk, n)
                    Xc = np.asarray(X[a:b].todense(), dtype=np.float64)
                    ds._bin_rows(Xc, buf[:b - a])
                    coo.append(ds._dense_chunk_to_coo(buf[:b - a], a, gd))
                ds._assemble_ell(
                    coo, n,
                    force=str(getattr(config, "tpu_multival",
                                      "auto")).lower() == "force")
            else:
                ds._push_sparse(X)
        return ds

    def _push_sparse(self, X) -> None:
        """Quantize a canonical CSR by its stored values: the same `binned`
        as `_bin_rows` on the dense matrix, cell for cell. Every row of a
        group starts where a zero falls (the group's default bin 0 in a
        bundle, the zero's own bin alone), and only rows with a stored
        value in one of the group's features are written."""
        n = X.shape[0]
        binned = np.zeros((n, len(self.groups)), dtype=self._bin_dtype())
        self.binned = binned
        if self._push_sparse_native(X, binned):
            return
        csc = X.tocsc()
        for gid, feats in enumerate(self.groups):
            multi = len(feats) > 1
            col = binned[:, gid]
            local = 1 if multi else 0
            for i in feats:
                f = self.used_features[i]
                m = self.bin_mappers[f]
                lo, hi = csc.indptr[f], csc.indptr[f + 1]
                rows = csc.indices[lo:hi]
                b = m.value_to_bin(csc.data[lo:hi])
                if not multi:
                    col[:] = m.default_bin      # where a zero falls
                    col[rows] = b
                else:
                    if m.default_bin != m.most_freq_bin:
                        # the rows WITHOUT a stored value are the ones that
                        # leave the most frequent bin: the dense route
                        # writes them all, over the group's earlier features
                        kept = col[rows]
                        col[:] = local + m.default_bin
                        col[rows] = kept
                    nz = b != m.most_freq_bin
                    col[rows[nz]] = local + b[nz]
                local += m.num_bin

    def _push_sparse_native(self, X, out: np.ndarray) -> bool:
        """C++/OpenMP binning of the CSR's rows by their stored values
        (native/binrows.cpp:bin_csr); False -> use numpy."""
        from ..native import load
        import ctypes
        lib = load("binrows", extra_flags=("-fopenmp",))
        if lib is None or not self.groups:
            return False
        m = self._native_bin_meta()
        data = X.data
        if data.dtype not in (np.float32, np.float64):
            data = data.astype(np.float64)
        data = np.ascontiguousarray(data)
        indices = np.ascontiguousarray(X.indices, dtype=np.int32)
        indptr = np.ascontiguousarray(X.indptr, dtype=np.int64)
        p = ctypes.c_void_p

        def arr(a):
            return a.ctypes.data_as(p)
        lib.bin_csr(arr(data), ctypes.c_int32(data.dtype.itemsize),
                    arr(indices), arr(indptr),
                    ctypes.c_int64(X.shape[0]), ctypes.c_int64(X.shape[1]),
                    ctypes.c_int32(len(self.groups)),
                    arr(m["group_ptr"]), arr(m["feat_col"]),
                    arr(m["feat_numbin"]), arr(m["feat_mostfreq"]),
                    arr(m["feat_missing"]), arr(m["feat_iscat"]),
                    arr(m["bounds_ptr"]), arr(m["bounds"]),
                    arr(m["lut_ptr"]), arr(m["lut"]),
                    out.ctypes.data_as(p),
                    ctypes.c_int32(out.dtype.itemsize),
                    ctypes.c_int64(out.shape[1]))
        return True

    def _choose_multival(self, config: Config, X=None) -> bool:
        """Pick the multi-value (ELL) device layout when the dense [N, G]
        matrix would dwarf the per-row non-default entries — the
        reference's MultiValBin decision re-derived for static-shape HBM
        storage (Dataset::TestMultiThreadingMethod / sparse_threshold,
        src/io/dataset.cpp:350-430)."""
        mode = str(getattr(config, "tpu_multival", "auto")).lower()
        if mode in ("off", "false", "0"):
            return False
        if mode == "force":
            return True
        if X is None:
            return False
        G = len(self.groups)
        if G < 64:
            return False
        e_row = X.nnz / max(1, X.shape[0])
        dense_bytes = G * np.dtype(self._bin_dtype()).itemsize
        grp_dt, bin_dt = self._ell_dtypes()
        ell_bytes = ((e_row + 1.0)
                     * (np.dtype(grp_dt).itemsize + np.dtype(bin_dt).itemsize))
        return dense_bytes > 4.0 * ell_bytes

    @classmethod
    def from_matrix_with_mappers(cls, X, config: Config,
                                 mappers, label=None, weight=None,
                                 group=None, init_score=None,
                                 feature_names=None) -> "BinnedDataset":
        """Build a shard dataset from PRE-AGREED BinMappers (distributed
        loading: parallel/distributed.distributed_bin_mappers). EFB is
        off — each feature is its own group — so every rank derives the
        identical layout from the identical mappers and sharded histogram
        psums line up bin-for-bin."""
        X = np.ascontiguousarray(X, dtype=np.float64)
        n, nf = X.shape
        if len(mappers) != nf:
            Log.fatal("%d mappers for %d features" % (len(mappers), nf))
        ds = cls()
        ds.num_data = n
        ds.num_total_features = nf
        ds.feature_names = (list(feature_names) if feature_names
                            else ["Column_%d" % i for i in range(nf)])
        ds.metadata = Metadata(n)
        if label is not None:
            ds.metadata.set_label(label)
        ds.metadata.set_weight(weight)
        ds.metadata.set_query(group)
        ds.metadata.set_init_score(init_score)
        ds.bin_mappers = list(mappers)
        ds.used_features = [f for f in range(nf)
                            if not ds.bin_mappers[f].is_trivial]
        ds.inner_of = {f: i for i, f in enumerate(ds.used_features)}
        ds.groups = [[i] for i in range(len(ds.used_features))]
        ds._finish_layout(config)
        ds._push_matrix(X)
        return ds

    @classmethod
    def from_text_two_round(cls, filename: str, config: Config,
                            categorical_features: Sequence[int] = ()
                            ) -> "BinnedDataset":
        """Two-pass streaming file load (two_round, DatasetLoader::
        LoadFromFile sample-from-file branch, dataset_loader.cpp:168-274):
        pass 1 reservoir-samples rows for binning and collects the small
        metadata columns; pass 2 streams chunks straight into the binned
        matrix — the full float matrix is never materialized."""
        from .loader import _sidecar, iter_text_chunks
        rng = np.random.default_rng(config.data_random_seed)
        cap = int(config.bin_construct_sample_cnt)
        sample_rows: List[np.ndarray] = []
        seen = 0
        labels, weights, groups_col = [], [], []
        names = None
        group_is_sizes = False
        full_X = None
        for chunk in iter_text_chunks(filename, config):
            if names is None:
                names = chunk.feature_names
            if getattr(chunk, "group_is_sizes", False):
                # LibSVM fallback: one full chunk — keep it so pass 2 does
                # not re-parse the file
                group_is_sizes = True
                full_X = chunk.X
            labels.append(chunk.label)
            if chunk.weight is not None:
                weights.append(chunk.weight)
            if chunk.group is not None:
                groups_col.append(chunk.group)
            m = chunk.X.shape[0]
            # chunk-reservoir: keep each row with prob cap/(seen+m) and
            # evict uniformly (approximate reservoir, exact in expectation)
            if seen + m <= cap:
                sample_rows.append(chunk.X)
            else:
                k = max(0, cap - max(seen, 0)) if seen < cap else 0
                take = rng.random(m) < cap / (seen + m)
                take[:k] = True
                sample_rows.append(chunk.X[take])
            seen += m
        n = seen
        sample = np.concatenate(sample_rows) if sample_rows else np.zeros((0, 1))
        if sample.shape[0] > cap:
            sample = sample[rng.choice(sample.shape[0], cap, replace=False)]

        ds = cls()
        ds.num_data = n
        ds.num_total_features = sample.shape[1]
        ds.feature_names = names or ["Column_%d" % i
                                     for i in range(ds.num_total_features)]
        ds.metadata = Metadata(n)
        ds.metadata.set_label(np.concatenate(labels) if labels else
                              np.zeros(n, np.float32))
        if weights:
            ds.metadata.set_weight(np.concatenate(weights))
        if groups_col:
            gids = np.concatenate(groups_col)
            if group_is_sizes:    # LibSVM fallback already returns sizes
                ds.metadata.set_query(gids)
            else:
                change = np.nonzero(np.diff(gids) != 0)[0]
                bounds = np.concatenate([[0], change + 1, [len(gids)]])
                ds.metadata.set_query(np.diff(bounds))
        else:
            # sidecar files, same as the one-round loader
            g_sc = _sidecar(filename, ".query", None)
            if g_sc is not None:
                ds.metadata.set_query(g_sc)
        if not weights:
            w_sc = _sidecar(filename, ".weight", None)
            if w_sc is not None:
                ds.metadata.set_weight(w_sc)
        from .loader import load_init_sidecar
        i_sc = load_init_sidecar(filename)
        if i_sc is not None:
            ds.metadata.set_init_score(i_sc)
        ds._construct_from_sample(sample, n, config,
                                  set(int(c) for c in categorical_features))

        G = len(ds.groups)
        binned = np.zeros((n, G), dtype=ds._bin_dtype())
        if full_X is not None:
            ds._bin_rows(full_X, binned)
        else:
            row = 0
            for chunk in iter_text_chunks(filename, config):
                m = chunk.X.shape[0]
                ds._bin_rows(chunk.X, binned[row:row + m])
                row += m
        ds.binned = binned
        return ds

    # ------------------------------------------------------------------
    def _finish_layout(self, config: Config) -> None:
        inner_mappers = [self.bin_mappers[f] for f in self.used_features]
        n_inner = len(inner_mappers)
        G = len(self.groups)
        self.group_of = np.zeros(n_inner, dtype=np.int32)
        self.bin_start = np.zeros(n_inner, dtype=np.int32)
        self.bin_end = np.zeros(n_inner, dtype=np.int32)
        self.needs_fix = np.zeros(n_inner, dtype=bool)
        self.group_offset = np.zeros(G, dtype=np.int32)
        offset = 0
        for gid, feats in enumerate(self.groups):
            self.group_offset[gid] = offset
            multi = len(feats) > 1
            local = 1 if multi else 0    # local bin 0 = group default sentinel
            for i in feats:
                m = inner_mappers[i]
                self.group_of[i] = gid
                self.bin_start[i] = offset + local
                self.bin_end[i] = offset + local + m.num_bin
                self.needs_fix[i] = multi
                local += m.num_bin
            offset += local
        self.total_bins = int(offset)

        self.most_freq_bin = np.array(
            [m.most_freq_bin for m in inner_mappers], dtype=np.int32)
        self.default_bin = np.array(
            [m.default_bin for m in inner_mappers], dtype=np.int32)
        self.missing_type_arr = np.array(
            [m.missing_type for m in inner_mappers], dtype=np.int32)
        self.is_categorical = np.array(
            [m.is_categorical for m in inner_mappers], dtype=bool)
        mono = np.zeros(n_inner, dtype=np.int32)
        if config.monotone_constraints:
            mc = config.monotone_constraints
            for i, f in enumerate(self.used_features):
                if f < len(mc):
                    mono[i] = mc[f]
        self.monotone = mono
        pen = np.ones(n_inner, dtype=np.float64)
        if config.feature_contri:
            fc = config.feature_contri
            for i, f in enumerate(self.used_features):
                if f < len(fc):
                    pen[i] = fc[f]
        self.penalty = pen

    def _finish_layout_like(self, ref: "BinnedDataset") -> None:
        for attr in ("group_of", "bin_start", "bin_end", "needs_fix",
                     "group_offset", "total_bins", "most_freq_bin",
                     "default_bin", "missing_type_arr", "is_categorical",
                     "monotone", "penalty"):
            setattr(self, attr, getattr(ref, attr))

    def _bin_dtype(self):
        widths = []
        for feats in self.groups:
            multi = len(feats) > 1
            w = (1 if multi else 0) + sum(
                self.bin_mappers[self.used_features[i]].num_bin for i in feats)
            widths.append(w)
        return np.uint8 if max(widths, default=1) <= 256 else (
            np.uint16 if max(widths) <= 65536 else np.int32)

    def _native_bin_meta(self):
        """Flattened per-feature metadata for the C++ binning kernel
        (native/binrows.cpp); built once and cached."""
        if getattr(self, "_nb_meta", None) is not None:
            return self._nb_meta
        gp = [0]
        cols, nb, mf, mt, cat = [], [], [], [], []
        bptr, bvals = [0], []
        lptr, lvals = [0], []
        for feats in self.groups:
            for i in feats:
                f = self.used_features[i]
                m = self.bin_mappers[f]
                cols.append(f)
                nb.append(m.num_bin)
                mf.append(m.most_freq_bin)
                mt.append(int(m.missing_type))
                cat.append(int(m.is_categorical))
                if m.is_categorical:
                    lvals.append(m.categorical_lut())
                    bvals.append(np.zeros(0))
                else:
                    bvals.append(np.asarray(m.bin_upper_bound, np.float64))
                    lvals.append(np.zeros(0, np.int32))
                bptr.append(bptr[-1] + len(bvals[-1]))
                lptr.append(lptr[-1] + len(lvals[-1]))
            gp.append(len(cols))
        self._nb_meta = dict(
            group_ptr=np.asarray(gp, np.int32),
            feat_col=np.asarray(cols, np.int32),
            feat_numbin=np.asarray(nb, np.int32),
            feat_mostfreq=np.asarray(mf, np.int32),
            feat_missing=np.asarray(mt, np.int32),
            feat_iscat=np.asarray(cat, np.int32),
            bounds_ptr=np.asarray(bptr, np.int64),
            bounds=(np.concatenate(bvals) if bvals
                    else np.zeros(0)).astype(np.float64),
            lut_ptr=np.asarray(lptr, np.int64),
            lut=(np.concatenate(lvals) if lvals
                 else np.zeros(0)).astype(np.int32),
        )
        return self._nb_meta

    def _bin_rows_native(self, X: np.ndarray, out: np.ndarray) -> bool:
        """C++/OpenMP binning (native/binrows.cpp); False -> use numpy."""
        from ..native import load
        import ctypes
        if not out.flags["C_CONTIGUOUS"]:
            return False
        lib = load("binrows", extra_flags=("-fopenmp",))
        if lib is None:
            return False
        m = self._native_bin_meta()
        X = np.ascontiguousarray(X, dtype=np.float64)
        p = ctypes.c_void_p

        def arr(a):
            return a.ctypes.data_as(p)
        lib.bin_rows(arr(X), ctypes.c_int64(X.shape[0]),
                     ctypes.c_int64(X.shape[1]),
                     ctypes.c_int32(len(self.groups)),
                     arr(m["group_ptr"]), arr(m["feat_col"]),
                     arr(m["feat_numbin"]), arr(m["feat_mostfreq"]),
                     arr(m["feat_missing"]), arr(m["feat_iscat"]),
                     arr(m["bounds_ptr"]), arr(m["bounds"]),
                     arr(m["lut_ptr"]), arr(m["lut"]),
                     out.ctypes.data_as(p),
                     ctypes.c_int32(out.dtype.itemsize),
                     ctypes.c_int64(out.shape[1]))
        return True

    def _bin_rows(self, X: np.ndarray, out: np.ndarray) -> None:
        """Quantize a row block into group-local bins (writes `out`)."""
        if out.dtype.itemsize in (1, 2, 4) and self._bin_rows_native(X, out):
            return
        n = X.shape[0]
        dtype = out.dtype
        for gid, feats in enumerate(self.groups):
            multi = len(feats) > 1
            if not multi:
                i = feats[0]
                f = self.used_features[i]
                m = self.bin_mappers[f]
                out[:, gid] = m.value_to_bin(X[:, f]).astype(dtype)
            else:
                col = np.zeros(n, dtype=np.int64)
                local = 1
                for i in feats:
                    f = self.used_features[i]
                    m = self.bin_mappers[f]
                    b = m.value_to_bin(X[:, f])
                    nz = b != m.most_freq_bin
                    col[nz] = local + b[nz]
                    local += m.num_bin
                out[:, gid] = col.astype(dtype)

    def _push_matrix(self, X: np.ndarray) -> None:
        """Quantize the full matrix into group-local bins."""
        n = X.shape[0]
        G = len(self.groups)
        binned = np.zeros((n, G), dtype=self._bin_dtype())
        self._bin_rows(X, binned)
        self.binned = binned

    def add_features_from(self, other: "BinnedDataset") -> None:
        """Merge another dataset's features into this one (reference
        Dataset::AddFeaturesFrom, src/io/dataset.cpp:1465). Both must hold
        the same rows; the other's feature groups are appended with their
        global bin ranges shifted past this dataset's."""
        if self.num_data != other.num_data:
            Log.fatal("Cannot add features from a dataset with a different "
                      "number of rows (%d vs %d)"
                      % (other.num_data, self.num_data))
        if self.binned is None or other.binned is None:
            Log.fatal("Both datasets must be constructed before "
                      "add_features_from")
        nf0 = self.num_total_features
        ni0 = len(self.used_features)
        G0 = len(self.groups)
        tb0 = self.total_bins
        self.bin_mappers = list(self.bin_mappers) + list(other.bin_mappers)
        self.feature_names = (list(self.feature_names)
                              + list(other.feature_names))
        self.used_features = (list(self.used_features)
                              + [nf0 + f for f in other.used_features])
        self.inner_of = {f: i for i, f in enumerate(self.used_features)}
        self.groups = (list(self.groups)
                       + [[ni0 + i for i in g] for g in other.groups])
        self.num_total_features += other.num_total_features
        self.group_of = np.concatenate([self.group_of,
                                        other.group_of + G0])
        self.bin_start = np.concatenate([self.bin_start,
                                         other.bin_start + tb0])
        self.bin_end = np.concatenate([self.bin_end, other.bin_end + tb0])
        self.needs_fix = np.concatenate([self.needs_fix, other.needs_fix])
        self.group_offset = np.concatenate([self.group_offset,
                                            other.group_offset + tb0])
        self.total_bins += other.total_bins
        for attr in ("most_freq_bin", "default_bin", "missing_type_arr",
                     "is_categorical", "monotone", "penalty"):
            setattr(self, attr, np.concatenate([getattr(self, attr),
                                                getattr(other, attr)]))
        dt = np.promote_types(self.binned.dtype, other.binned.dtype)
        self.binned = np.concatenate(
            [self.binned.astype(dt, copy=False),
             other.binned.astype(dt, copy=False)], axis=1)
        # compiled programs are shaped by the old layout
        if hasattr(self, "_scan_cache"):
            self._scan_cache = {}
        if hasattr(self, "_mm_scan_cache"):
            self._mm_scan_cache = {}
        if hasattr(self, "_device_layout_cache"):
            self._device_layout_cache = {}
        if hasattr(self, "_device_meta_cache"):
            self._device_meta_cache = {}
        self._group_default_cache = None

    # ------------------------------------------------------------------
    @property
    def num_features(self) -> int:
        return len(self.used_features)

    @property
    def has_bundles(self) -> bool:
        return bool(self.needs_fix is not None and self.needs_fix.any())

    def group_widths(self) -> np.ndarray:
        """[G] total bins per storage group (incl. the bundle sentinel) —
        the geometry the storage pack plans (device_pack_plan here, the
        persist payload plan in ops/grow_persist) key off."""
        return np.diff(np.append(np.asarray(self.group_offset, np.int64),
                                 int(self.total_bins)))

    def real_threshold(self, inner_feature: int, bin_threshold: int) -> float:
        """Local bin -> model-text threshold value (Tree uses upper bounds)."""
        f = self.used_features[inner_feature]
        return self.bin_mappers[f].bin_to_value(int(bin_threshold))

    # -- binary cache (reference Dataset::SaveBinaryFile, dataset.cpp:890,
    # and DatasetLoader::LoadFromBinFile / CheckCanLoadFromBin,
    # dataset_loader.cpp:179-274). Format: npz with a versioned magic — the
    # semantics match (skip text parsing + FindBin entirely on reload), the
    # encoding is numpy-native instead of the reference's hand-rolled blob.
    BINARY_MAGIC = "lightgbm_tpu.dataset.v1"

    def save_binary(self, path: str) -> None:
        import json
        meta = self.metadata
        arrays = {
            "magic": np.frombuffer(self.BINARY_MAGIC.encode(), np.uint8),
            "group_offset": self.group_offset,
            "group_of": self.group_of,
            "bin_start": self.bin_start,
            "bin_end": self.bin_end,
            "needs_fix": self.needs_fix,
            "most_freq_bin": self.most_freq_bin,
            "default_bin": self.default_bin,
            "missing_type_arr": self.missing_type_arr,
            "is_categorical": self.is_categorical,
            "monotone": self.monotone,
            "penalty": self.penalty,
            "used_features": np.asarray(self.used_features, np.int32),
            "total_bins": np.asarray([self.total_bins], np.int64),
            "num_total_features": np.asarray([self.num_total_features],
                                             np.int64),
            "structure": np.frombuffer(json.dumps({
                "groups": [list(map(int, g)) for g in self.groups],
                "feature_names": list(self.feature_names),
                "mappers": [m.to_state() for m in self.bin_mappers],
            }).encode(), np.uint8),
        }
        if self.is_multival:
            arrays["ell_grp"] = self.ell_grp
            arrays["ell_bin"] = self.ell_bin
        else:
            arrays["binned"] = self.binned
        if meta is not None:
            for k in ("label", "weight", "query_boundaries", "init_score"):
                v = getattr(meta, k)
                if v is not None:
                    arrays["meta_" + k] = v
        with open(path, "wb") as f:
            np.savez_compressed(f, **arrays)
        Log.info("Saved binary dataset to %s" % path)

    @staticmethod
    def is_binary_file(path: str) -> bool:
        try:
            with open(path, "rb") as f:
                head = f.read(4)
            if head[:2] != b"PK":
                return False
            with np.load(path) as z:
                magic = bytes(z["magic"]).decode()
            return magic == BinnedDataset.BINARY_MAGIC
        except Exception:
            return False

    def layout_matches(self, other: "BinnedDataset") -> bool:
        """True when both datasets share the exact binning layout (bin
        boundaries, grouping, feature set) — i.e. a binary cache of a
        reference-aligned validation set is still valid against this
        reference."""
        if (self.total_bins != other.total_bins
                or self.used_features != other.used_features
                or self.groups != other.groups
                or self.num_total_features != other.num_total_features):
            return False

        import json

        def norm(state):
            return json.dumps(state, sort_keys=True, default=str)
        return all(norm(a.to_state()) == norm(b.to_state())
                   for a, b in zip(self.bin_mappers, other.bin_mappers))

    @classmethod
    def from_binary(cls, path: str) -> "BinnedDataset":
        import json
        from .bin_mapper import BinMapper
        ds = cls()
        with np.load(path) as z:
            magic = bytes(z["magic"]).decode()
            if magic != cls.BINARY_MAGIC:
                Log.fatal("%s is not a lightgbm_tpu binary dataset" % path)
            struct = json.loads(bytes(z["structure"]).decode())
            if "ell_grp" in z.files:
                ds.ell_grp = z["ell_grp"]
                ds.ell_bin = z["ell_bin"]
                ds.is_multival = True
            else:
                ds.binned = z["binned"]
            ds.group_offset = z["group_offset"]
            ds.group_of = z["group_of"]
            ds.bin_start = z["bin_start"]
            ds.bin_end = z["bin_end"]
            ds.needs_fix = z["needs_fix"]
            ds.most_freq_bin = z["most_freq_bin"]
            ds.default_bin = z["default_bin"]
            ds.missing_type_arr = z["missing_type_arr"]
            ds.is_categorical = z["is_categorical"]
            ds.monotone = z["monotone"]
            ds.penalty = z["penalty"]
            ds.used_features = [int(x) for x in z["used_features"]]
            ds.total_bins = int(z["total_bins"][0])
            ds.num_total_features = int(z["num_total_features"][0])
            meta_arrays = {k[5:]: z[k] for k in z.files
                           if k.startswith("meta_")}
        ds.groups = [list(g) for g in struct["groups"]]
        ds.feature_names = list(struct["feature_names"])
        ds.bin_mappers = [BinMapper.from_state(d) for d in struct["mappers"]]
        ds.inner_of = {f: i for i, f in enumerate(ds.used_features)}
        ds.num_data = int((ds.ell_grp if ds.is_multival
                           else ds.binned).shape[0])
        ds.metadata = Metadata(ds.num_data)
        for k, v in meta_arrays.items():
            setattr(ds.metadata, k, v)
        Log.info("Loaded binary dataset from %s (%d rows, %d features)"
                 % (path, ds.num_data, ds.num_total_features))
        return ds

    # ------------------------------------------------------------------
    def fix_info(self):
        """FixInfo arrays for features whose histogram omits a bin and
        needs reconstruction from leaf totals (ops.split.fix_histogram).
        Dense layout: only EFB-bundled features (their most_freq rows sit
        in the group sentinel). Multi-value layout: EVERY feature — each
        group's default bin is not materialized (the reference's
        multi-val histograms have the same contract,
        src/io/dataset.cpp:1198 + FixHistogram:1410)."""
        import jax.numpy as jnp
        from ..ops.grow import FixInfo
        if self.is_multival:
            idx = np.arange(self.num_features)
        else:
            idx = np.nonzero(self.needs_fix)[0]
        return FixInfo(
            mf_global=jnp.asarray((self.bin_start[idx]
                                   + self.most_freq_bin[idx]).astype(np.int32)),
            start=jnp.asarray(self.bin_start[idx]),
            end=jnp.asarray(self.bin_end[idx]),
        )

    # -- multi-value (ELL row-sparse) layout ---------------------------
    def group_default_bins(self) -> np.ndarray:
        """[G] bin omitted from multi-value storage per group: the single
        feature's most_freq bin, or the 0 sentinel for EFB bundles.
        Cached — Tree.predict_leaf_binned asks once per leaf level."""
        cached = getattr(self, "_group_default_cache", None)
        if cached is not None and len(cached) == len(self.groups):
            return cached
        G = len(self.groups)
        out = np.zeros(G, dtype=np.int32)
        for g, feats in enumerate(self.groups):
            if len(feats) == 1:
                out[g] = int(self.most_freq_bin[feats[0]])
        self._group_default_cache = out
        return out

    def _ell_dtypes(self):
        G = len(self.groups)
        widths = self.group_widths()
        grp_dt = np.uint16 if G < 0xFFFF else np.int32
        bin_dt = (np.uint8 if (len(widths) == 0 or widths.max() <= 0xFF)
                  else (np.uint16 if widths.max() <= 0xFFFF else np.int32))
        return grp_dt, bin_dt

    def _assemble_ell(self, coo_chunks, n: int, force: bool = False) -> bool:
        """COO chunk list [(row_global, grp, bin)] -> padded [N, K] ELL
        arrays (pad entry: grp = G); chunks must cover disjoint contiguous
        row ranges (both callers chunk by rows). Sets is_multival and
        returns True — unless the padded width K (set by the DENSEST row,
        not the mean the chooser estimated from) would make ELL as large
        as the dense matrix, in which case it densifies instead and
        returns False. `force` (tpu_multival=force) skips that guard."""
        G = len(self.groups)
        grp_dt, bin_dt = self._ell_dtypes()
        counts = np.zeros(n, dtype=np.int64)
        for rows, _, _ in coo_chunks:
            np.add.at(counts, rows, 1)
        K = max(1, int(counts.max()) if n else 1)
        entry_bytes = np.dtype(grp_dt).itemsize + np.dtype(bin_dt).itemsize
        if (not force
                and K * entry_bytes
                >= G * np.dtype(self._bin_dtype()).itemsize):
            Log.warning("multi-value layout abandoned: one row holds %d "
                        "non-default entries, padding every row that wide "
                        "would exceed the dense [N, %d] matrix" % (K, G))
            self._densify_from_coo(coo_chunks, n)
            return False
        self.ell_grp = np.full((n, K), G, dtype=grp_dt)
        self.ell_bin = np.zeros((n, K), dtype=bin_dt)
        for rows, grp, bn in coo_chunks:
            # entries arrive row-sorted; each entry's slot is its
            # occurrence index within its row
            first = np.ones(len(rows), dtype=bool)
            first[1:] = rows[1:] != rows[:-1]
            pos = np.arange(len(rows)) - np.maximum.accumulate(
                np.where(first, np.arange(len(rows)), 0))
            self.ell_grp[rows, pos] = grp.astype(grp_dt)
            self.ell_bin[rows, pos] = bn.astype(bin_dt)
        self.is_multival = True
        self.binned = None
        return True

    def _densify_from_coo(self, coo_chunks, n: int) -> None:
        """Rebuild the dense [N, G] matrix from non-default COO entries
        plus per-group defaults (the _assemble_ell fallback)."""
        gd = self.group_default_bins()
        binned = np.tile(gd.astype(self._bin_dtype()), (n, 1))
        for rows, grp, bn in coo_chunks:
            binned[rows, grp] = bn.astype(self._bin_dtype())
        self.binned = binned
        self.is_multival = False

    def _dense_chunk_to_coo(self, binned_chunk: np.ndarray, row0: int,
                            group_default: np.ndarray):
        """Non-default entries of one dense binned chunk as row-sorted
        (global row, group, bin) COO arrays."""
        rr, gg = np.nonzero(binned_chunk != group_default[None, :])
        return (rr.astype(np.int64) + row0, gg.astype(np.int32),
                binned_chunk[rr, gg].astype(np.int32))

    def to_multival(self) -> None:
        """Convert a dense-binned dataset to the multi-value layout in
        place (tpu_multival=force; tests and post-hoc conversion)."""
        if self.is_multival or self.binned is None:
            return
        gd = self.group_default_bins()
        chunks = []
        step = max(1, int(2 ** 24 / max(1, len(self.groups))))
        for a in range(0, self.num_data, step):
            chunks.append(self._dense_chunk_to_coo(
                self.binned[a:a + step], a, gd))
        self._assemble_ell(chunks, self.num_data, force=True)

    def host_group_bins(self, rows: np.ndarray, g: np.ndarray) -> np.ndarray:
        """Per-row group-local bin for (row, group) pairs from either
        layout — the host-side analog of ops.grow._multival_col, used by
        Tree.predict_leaf_binned."""
        if not self.is_multival:
            return self.binned[rows, g].astype(np.int64)
        eg = self.ell_grp[rows].astype(np.int64)         # [R, K]
        eb = self.ell_bin[rows].astype(np.int64)
        match = eg == np.asarray(g)[:, None]
        found = match.any(axis=1)
        raw = np.where(match, eb, 0).sum(axis=1)
        gd = self.group_default_bins()
        return np.where(found, raw, gd[np.asarray(g)])

    def device_pack_plan(self, config: Config):
        """Nibble-packing plan for HBM storage (the Dense4bitsBin analog,
        src/io/dense_nbits_bin.hpp): pairs of logical groups whose width
        fits 4 bits share one storage byte. Returns None when packing is
        off or fewer than 2 groups qualify; else (storage_of [G_l],
        shift [G_l], n_storage, unpack_mask [G_l])."""
        if not bool(config.tpu_4bit_packing) or self.binned is None:
            return None
        G = len(self.groups)
        widths = self.group_widths()
        wide, pairs, leftover = nibble_slot_partition(widths)
        if G - len(wide) < 2:       # fewer than 2 narrow groups: no pairs
            return None
        storage_of = np.zeros(G, dtype=np.int32)
        shift = np.zeros(G, dtype=np.int32)
        sc = 0
        for g in wide:
            storage_of[g] = sc
            sc += 1
        for a, b in pairs:          # two narrow groups per storage column
            storage_of[a] = sc
            storage_of[b] = sc
            shift[b] = 4
            sc += 1
        if leftover is not None:
            storage_of[leftover] = sc
            sc += 1
        # any narrow group's values fit in 4 bits, so &15 is safe even for
        # an unpaired trailing one; wide groups pass through unmasked
        mask = np.where(widths <= 16, 15, 0x7FFFFFFF).astype(np.int32)
        return storage_of, shift, sc, mask

    @staticmethod
    def _device_key(config: Config):
        # the only config knobs the device layout depends on
        return (str(getattr(config, "tpu_multival", "auto")).lower(),
                bool(config.tpu_4bit_packing))

    def device_meta(self, config: Config):
        """The host part of the device layout: the tpu_multival=force
        conversion, the nibble-packing plan (sets self.device_packed for
        the learner's GrowConfig) and the per-feature FeatureMeta, a few KB
        on the device. The binned rows stay on the host.

        Cached per (tpu_multival, tpu_4bit_packing), as to_device."""
        key = self._device_key(config)
        cache = getattr(self, "_device_meta_cache", None)
        if cache is None:
            cache = self._device_meta_cache = {}
        hit = cache.get(key)
        if hit is None:
            if (not self.is_multival and self.binned is not None
                    and key[0] == "force"):
                self.to_multival()
            plan = None if self.is_multival else self.device_pack_plan(config)
            # sentinel bins (bundled group bin 0) belong to no feature; they
            # are assigned feature 0, which is safe: they lie outside every
            # feature's [bin_start, bin_end) so the scan's range masks
            # exclude them.
            owner = np.full(self.total_bins, -1, dtype=np.int32)
            for i in range(self.num_features):
                owner[self.bin_start[i]:self.bin_end[i]] = i
            feat_id = np.where(owner < 0, 0, owner).astype(np.int32)
            hit = cache[key] = (self._feature_meta(feat_id), plan)
        self.device_packed = hit[1] is not None
        return hit[0]

    def to_device(self, config: Config):
        """Produce (DataLayout, FeatureMeta) jnp structures: the binned rows
        on the device, for the v1 growers, and device_meta(config).

        Cached per (tpu_multival, tpu_4bit_packing) so B boosters sweeping
        over one Dataset share a single HBM-resident copy of the binned
        matrix instead of re-uploading it per member."""
        meta = self.device_meta(config)
        key = self._device_key(config)
        cache = getattr(self, "_device_layout_cache", None)
        if cache is None:
            cache = self._device_layout_cache = {}
        layout = cache.get(key)
        if layout is None:
            telemetry_events.count("tree_learner::layout_placements",
                                   category="tree_learner")
            layout = cache[key] = self._build_device_layout(
                self._device_meta_cache[key][1])
        return layout, meta

    def _build_device_layout(self, plan):
        import jax.numpy as jnp
        from ..ops.grow import DataLayout
        if self.is_multival:
            return DataLayout(
                # placeholder dense matrix: the multival grower never
                # reads it, but downstream sharding specs expect 2D
                bins=jnp.zeros((self.num_data, 1), jnp.uint8),
                group_offset=jnp.asarray(self.group_offset),
                group_of=jnp.asarray(self.group_of),
                most_freq_bin=jnp.asarray(self.most_freq_bin),
                ell_grp=jnp.asarray(self.ell_grp),
                ell_bin=jnp.asarray(self.ell_bin),
                group_default=jnp.asarray(self.group_default_bins()),
            )
        if plan is not None:
            storage_of, shift, n_storage, mask = plan
            storage = np.zeros((self.num_data, n_storage),
                               dtype=self.binned.dtype)
            for g in range(len(self.groups)):
                np.bitwise_or(
                    storage[:, storage_of[g]],
                    (self.binned[:, g].astype(np.int64)
                     << int(shift[g])).astype(self.binned.dtype),
                    out=storage[:, storage_of[g]])
            return DataLayout(
                bins=jnp.asarray(storage),
                group_offset=jnp.asarray(self.group_offset),
                group_of=jnp.asarray(self.group_of),
                most_freq_bin=jnp.asarray(self.most_freq_bin),
                unpack_col=jnp.asarray(storage_of),
                unpack_shift=jnp.asarray(shift),
                unpack_mask=jnp.asarray(mask),
            )
        return DataLayout(
            bins=jnp.asarray(self.binned),
            group_offset=jnp.asarray(self.group_offset),
            group_of=jnp.asarray(self.group_of),
            most_freq_bin=jnp.asarray(self.most_freq_bin),
        )

    def _feature_meta(self, feat_id):
        import jax.numpy as jnp
        from ..ops.split import FeatureMeta
        return FeatureMeta(
            feat_id=jnp.asarray(feat_id),
            bin_start=jnp.asarray(self.bin_start),
            bin_end=jnp.asarray(self.bin_end),
            missing_type=jnp.asarray(self.missing_type_arr),
            default_bin=jnp.asarray(self.default_bin),
            monotone=jnp.asarray(self.monotone),
            is_categorical=jnp.asarray(self.is_categorical),
            penalty=jnp.asarray(self.penalty),
        )


def _load_forced_bins(filename: str, num_features: int) -> Dict[int, List[float]]:
    """forcedbins_filename JSON: [{"feature": i, "bin_upper_bound": [...]}]."""
    if not filename:
        return {}
    import json
    with open(filename) as fh:
        spec = json.load(fh)
    out: Dict[int, List[float]] = {}
    for entry in spec:
        out[int(entry["feature"])] = [float(x) for x in entry["bin_upper_bound"]]
    return out
