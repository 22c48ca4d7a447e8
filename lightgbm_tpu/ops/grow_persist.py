"""Persistent-payload tree grower: the TPU fast path for boosting.

Builds whole boosting batches on device with ZERO per-row gathers/scatters:
the binned rows, label, row id, gradient and hessian live in ONE transposed
u32 payload matrix (ops/pallas_grow.py) that stays leaf-partitioned across
an entire K-iteration scan. Replaces, for the fast-path configuration, the
v1 partitioned grower (ops/grow.py grow_tree_partitioned) plus the
row-ordered score/gradient plumbing around it:

  * per split: ONE fused kernel call (split_pass) does the partition,
    the smaller-child histogram and the exact left-count — the reference's
    DataPartition::Split + ConstructHistograms pair
    (src/treelearner/serial_tree_learner.cpp:690-775);
  * per-leaf state, best-split candidates and split records are single
    [L, K] f32 matrices — two dynamic row writes per split instead of the
    ~56 separate [L]-array updates of v1;
  * histograms use the padded [G, 256] layout end to end, so the dense
    scan kernel input is a reshape (no gather) and the leaf-wise
    subtraction trick (hist_larger = parent - smaller,
    serial_tree_learner.cpp:290-298) stays [TBp, 2] arithmetic;
  * the score update is segment-ordered: leaves partition the payload into
    contiguous segments, so "score += leaf_output[leaf_of_row]" becomes a
    255-element scatter of value deltas at segment starts + one cumsum —
    no [N] gather by leaf id (GBDT::UpdateScore, src/boosting/gbdt.cpp:459);
  * gradients are computed in payload order from the label row; the score
    vector itself is a payload row (it must permute with the rows), and
    scores return to row order ONCE per batch via a single scatter through
    the carried row ids.

Numerics: f32 accumulation everywhere (the reference GPU learner's
gpu_use_dp=false trade); trees match the v1 f32 grower up to f32 summation
order. Gated by treelearner.serial.can_persist_scan — anything outside the
fast path (monotone, f64, numerical groups over 256 bins) takes the v1
path; sample weights ride as a payload row, EFB bundles decode in the
split kernel with an in-eval FixHistogram, lambdarank computes
payload-position gradients, and categorical columns (up to 512 bins, in
16-bit slots) split by a left-set bitset that the eval's cat_scan finds
(ops/split.find_best_split_categorical) and split_pass reads.
Bagging and GOSS run INSIDE the scan as payload transforms
(make_bag_transform), and the whole driver also runs sharded under
shard_map (make_persist_grower's axis_name) with in-loop histogram psum —
plain data-parallel or PV-tree voting (winner-window-only reduction).
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import events as telemetry
from ..telemetry.health import (H_INF_HIST, H_NAN_GRAD, H_NAN_HESS,
                                HEALTH_LEN, NUM_HEALTH)
from ..utils.log import Log
from .grow import TreeArrays
from .pallas_grow import (HIST_UNROLL_MAX_GROUPS, N_SCALARS, S_CAT, S_CW0,
                          S_DB, S_DL, S_LE, S_LS, S_MASK, S_MF, S_MT, S_NB,
                          S_NCH, S_NL, S_S0, S_SH, S_SMALL_L, S_THR, S_WG,
                          VMEM_CAP, hist_loops_groups, hist_plan,
                          make_root_hist, make_split_pass, plane_health,
                          seg_hist_vmem_bytes, split_pass_vmem_bytes)
from .pallas_scan import (ScanLayout, margin_bucket_index, scan_pair,
                          topk_vote_indices)
from .quantize import plane_psum, quant_tag, vote_allgather
from .split import (K_MIN_SCORE, SplitParams, find_best_split_categorical,
                    find_best_split_numerical,
                    find_best_split_numerical_batch, fix_histogram)

I32 = jnp.int32
U32 = jnp.uint32
F32 = jnp.float32
BOOL = jnp.bool_

def _f32r(row):
    return jax.lax.bitcast_convert_type(row, F32)


# payload row count up to which f32 leaf state holds exact integer counts
EXACT_F32_ROWS = 1 << 24

# device stats vector the scan driver returns: [level_programs,
# level_fallback_splits, iter_launches] + the numerics health vector
# (NaN-grad/NaN-hess/Inf-hist counts + the split-margin histogram
# buckets — telemetry/health.py owns the layout). iter_launches counts
# the compiled-program launches the fused boosting path dispatched (one
# per scan-driver invocation + one per payload score-delta apply) — the
# numerator of the launches_per_iter bench key. Carried through the
# scan as i32 and flushed ONCE at finalize (serial.flush_level_stats);
# the health tail is all-zero when the grower is built with
# health=False (tpu_numerics_stats=off).
STAT_LEVELS, STAT_FALLBACK, STAT_ITER_LAUNCH = 0, 1, 2
STAT_HEALTH0 = 3
STATS_LEN = STAT_HEALTH0 + HEALTH_LEN

# deepest max_depth the level-parallel phase takes on: the frontier-slot
# matrices are sized 2^(max_depth-1) and the no-bind certificate's
# capacity terms are exact f32 powers of two up to here
LEVEL_MAX_DEPTH = 16


def can_level_grow(gc) -> bool:
    """Static gate for the level-parallel growth phase.

    The level program batches a whole tree level into one fused
    partition + one batched split-find, driven by a bounded loop over
    depths — so it needs a finite max_depth to size the slot matrices.
    Voting-parallel keeps the per-split path (its per-leaf vote/psum
    protocol is pairwise); forced splits prescribe a split ORDER, which
    is exactly what the level batch abstracts away. Leaf-wise
    (num_leaves-constrained) semantics are preserved dynamically: the
    in-program no-bind certificate hands the tree to the per-split tail
    the moment gain-ordered admission could be budget-truncated
    (see make_persist_grower's level loop)."""
    return (1 <= int(gc.max_depth) <= LEVEL_MAX_DEPTH
            and int(gc.num_leaves) >= 4
            and gc.parallel_mode != "voting"
            and int(gc.n_forced) == 0)


class PersistPackError(ValueError):
    """A dataset geometry the persist payload pack plan cannot express.

    Raised by build_assets instead of a bare NotImplementedError so
    callers can fall back to the v1 grower loudly but gracefully;
    treelearner.serial.can_persist_scan pre-checks via persist_pack_ok, so
    user-facing paths never see this as a crash."""


def _group_widths(dataset) -> np.ndarray:
    """[G] bin count per storage group — BinnedDataset.group_widths()."""
    return np.asarray(dataset.group_widths(), np.int64)


# bins a categorical group may have: a 16-bit slot, two histogram rows
MAX_CAT_BINS = 512


def _cat_groups(dataset) -> np.ndarray:
    """[G] bool: the group holds one categorical feature and nothing else."""
    G = len(dataset.group_offset)
    is_cat = np.asarray(dataset.is_categorical, bool)
    group_of = np.asarray(dataset.group_of, np.int64)
    out = np.zeros(G, bool)
    members = np.bincount(group_of, minlength=G)
    cats = np.bincount(group_of, weights=is_cat.astype(np.int64),
                       minlength=G)
    out[(members == 1) & (cats == 1)] = True
    return out


def persist_pack_ok(dataset):
    """(ok, reason) — can the payload pack plan express this dataset?

    The plan covers any dense-binned layout with <= 256 bins per group
    (byte slots, 4-bit slots for <= 16-bin groups) and categorical groups
    of up to MAX_CAT_BINS bins (16-bit slots); device_packed v1 storage is
    fine because the payload packs independently from dataset.binned.
    Multi-value (ELL) layouts, numerical groups of more than 256 bins
    (their threshold scan would span two histogram rows) and categorical
    groups past MAX_CAT_BINS are the remaining v1-only geometries."""
    if getattr(dataset, "is_multival", False) or dataset.binned is None:
        return False, "multi-value (ELL) datasets have no dense payload"
    widths = _group_widths(dataset)
    if not len(widths) or int(widths.max()) <= 256:
        return True, ""
    cat = _cat_groups(dataset)
    num_wide = widths[~cat]
    if len(num_wide) and int(num_wide.max()) > 256:
        return False, ("numerical group width %d > 256 bins: its threshold "
                       "scan would span two histogram rows"
                       % int(num_wide.max()))
    if int(widths.max()) > MAX_CAT_BINS:
        return False, ("categorical group width %d > %d bins exceeds the "
                       "payload's 16-bit slot plan"
                       % (int(widths.max()), MAX_CAT_BINS))
    return True, ""


def _payload_plan(widths):
    """Per-group payload storage plan: (plan, nbw).

    plan[g] = (word_row, bit_shift, value_mask): groups whose bin count
    fits 4 bits share a byte slot in nibble pairs (the Dense4bitsBin
    analog, src/io/dense_nbits_bin.hpp, applied to the PERSIST payload),
    groups of more than 256 bins (categorical ones: persist_pack_ok) take
    a 16-bit slot, half a word, first, and everything else gets a full
    byte — 4 byte slots per u32 payload word. With no narrow and no wide
    groups this reproduces the historical byte-per-group layout exactly.
    The split/seg/root kernels and the XLA emulation decode through
    (word, shift, mask), so the plan is the single source of truth for
    payload bin storage."""
    from ..data.dataset import nibble_slot_partition
    G = len(widths)
    wide, pairs, leftover = nibble_slot_partition(widths)
    plan = [None] * G
    slot = 0                       # byte-slot counter (4 per u32 word)
    for g in wide:
        if widths[g] > 256:        # two byte slots, at shift 0 or 16
            plan[g] = (slot // 4, (slot % 4) * 8, 0xFFFF)
            slot += 2
    for g in wide:
        if widths[g] <= 256:
            plan[g] = (slot // 4, (slot % 4) * 8, 255)
            slot += 1
    for a, b in pairs:
        w, sh = slot // 4, (slot % 4) * 8
        plan[a] = (w, sh, 15)
        plan[b] = (w, sh + 4, 15)
        slot += 1
    if leftover is not None:
        plan[leftover] = (slot // 4, (slot % 4) * 8, 15)
        slot += 1
    nbw = max((slot + 3) // 4, 1)
    return tuple(plan), nbw

# leaf-state matrix columns
LS_SG, LS_SH, LS_CNT, LS_VAL, LS_DEPTH, LS_START, LS_NROWS, LS_PAD = range(8)
# best-candidate matrix columns
(BC_GAIN, BC_FEAT, BC_THR, BC_DL, BC_LSG, BC_LSH, BC_RSG, BC_RSH,
 BC_LCNT, BC_RCNT, BC_LOUT, BC_ROUT) = range(12)
# split-record matrix columns
(TR_LEAF, TR_FEAT, TR_THR, TR_DL, TR_GAIN, TR_IVAL, TR_ICNT, TR_PAD) = range(8)
# integer leaf-state columns (large_counts: the i32 matrix beside lstate)
LI_CNT, LI_START, LI_NROWS, LI_PAD = range(4)


class PersistAssets(NamedTuple):
    """Per-dataset device arrays + static geometry for the persist path."""
    pay0: jnp.ndarray          # [WPA, NP] u32 (bins words + label + rid)
    dec_word: jnp.ndarray      # [F] i32 payload word row per feature
    dec_shift: jnp.ndarray     # [F] i32
    dec_mask: jnp.ndarray      # [F] i32
    nb: jnp.ndarray            # [F] i32 per-feature bin count
    mt: jnp.ndarray            # [F] i32 missing type
    db: jnp.ndarray            # [F] i32 default bin
    ls: jnp.ndarray            # [F] i32 group-local byte range start (EFB)
    le: jnp.ndarray            # [F] i32 range end
    mf: jnp.ndarray            # [F] i32 most_freq (feature-local) bin
    geometry: tuple            # (WPA, NP, G, plan, nbw, n, C, CR, K,
    #                          #  has_w) static
    efb: tuple                 # host-side np layout for the eval closure:
    #                          # (group_of [F], ls [F], nb [F], mf [F],
    #                          #  needs_fix [F] bool, bundled flag)


def persist_input_contract(n: int, g_max: float = 1.0,
                           h_max: float = 0.25) -> dict:
    """Value-range contract for the persist driver's traced state (the
    analysis/dataflow seeder reads this): row counts in ``[0, n]``,
    per-row gradients capped by the objective, hessians NONNEGATIVE and
    capped — the invariant every split-gain denominator (``H + lambda``)
    leans on, and the one the quantization certifier needs to bound the
    ReduceScatter payload scales (plane sums <= n * cap)."""
    return {
        "counts": (0.0, float(n)),
        "grad": (-float(g_max), float(g_max)),
        "hess": (0.0, float(h_max)),
        "grad_plane": (-float(n) * float(g_max), float(n) * float(g_max)),
        "hess_plane": (0.0, float(n) * float(h_max)),
    }


def payload_weight_row(nbw: int, num_scores: int,
                       score64: bool = False) -> int:
    """Row index of the optional weight row == live-row count without it
    (bins | label | rid | grad | hess | score*K [| snapshot*K]).
    score64 doubles the score/snapshot rows (f64 as u32 word pairs — the
    widened kernel mode's boosting state, matching the v1 f64 score
    buffer bit for bit)."""
    K = num_scores
    SR = 2 if score64 else 1
    return nbw + 4 + SR * K + (SR * K if K > 1 else 0)


def _fit_chunk(lanes: int, footprint) -> int:
    """``lanes`` halved (floor 1,024) until ``footprint(lanes)``, a
    kernel's unclipped scoped-VMEM request in bytes, is under the cap."""
    while lanes > 1024 and footprint(lanes) >= VMEM_CAP:
        lanes //= 2
    return lanes


def _payload_geometry(n: int, nbw: int, G: int, C: int = 0, CR: int = 0,
                      num_scores: int = 1, has_weight: bool = False,
                      score64: bool = False, loop_groups=None):
    """(WPA, C, CR, NP). Payload rows: bins words | label | rid | grad |
    hess | score*K [| snapshot*K when K > 1] [| weight]. nbw comes from
    the pack plan (_payload_plan — nibble-packed narrow groups shrink it
    below the historical (G+3)//4). Multiclass (K = num_class trees
    per iteration) carries one score row per class plus an iteration-start
    snapshot block: the reference computes all K classes' gradients from
    the PRE-iteration scores (GBDT::Boosting once per TrainOneIter,
    src/boosting/gbdt.cpp:152,338-420), so per-class softmax grads read
    the snapshot while per-class score updates land in the live rows.
    Weighted datasets append one f32 weight row that rides the partition;
    unweighted payloads pay nothing. score64 widens the score rows to
    u32 pairs (the XLA kernel mode's f64 boosting state).

    C (the chunk of split_pass and seg_hist) and CR (root_hist's) follow
    from the row's width and the group count when not given: the kernels
    raise the Mosaic scoped-VMEM limit to their footprint (v5e carries
    128MB), so chunks are sized for DMA-latency amortization, not the
    16MB default — every chunk waits for its read and pays a step's fixed
    scalar work — and start at 16384 lanes (8192 past 56 payload words:
    split_pass holds nine chunk-sized buffers); a row too wide for that
    halves them until the kernels' own footprint formulas
    (pallas_grow.split_pass_vmem_bytes, seg_hist_vmem_bytes) fit the cap
    unclipped: 2,048 lanes for C at 512 words (2,000 dense byte columns).
    loop_groups: pallas_grow.hist_loops_groups of the plan (None: of a
    byte plan in group order)."""
    K = num_scores
    WP = payload_weight_row(nbw, K, score64) + (1 if has_weight else 0)
    WPA = ((WP + 7) // 8) * 8
    looped = (G > HIST_UNROLL_MAX_GROUPS if loop_groups is None
              else bool(loop_groups))
    if C <= 0:
        C = _fit_chunk(16384 if WPA <= 56 else 8192, lambda c: max(
            split_pass_vmem_bytes(WPA, c + 128, G, cap=None),
            seg_hist_vmem_bytes(WPA, c + 128, G, looped, cap=None)))
    if CR <= 0:
        CR = _fit_chunk(16384, lambda c: seg_hist_vmem_bytes(
            WPA, c, G, looped, cap=None))
    NP = max(((n + 127) // 128 + 2) * 128 + C + 256,
             ((n + CR - 1) // CR) * CR)
    return WPA, C, CR, NP


def _pack_payload(binned: np.ndarray, labels: np.ndarray, n: int,
                  WPA: int, NP: int, nbw: int, rid_offset: int,
                  rid_sentinel: int, plan=None, weights=None,
                  weight_row: int = 0, out=None):
    """One shard's payload matrix from its binned rows + labels, packed
    per `plan` (byte or nibble slots — _payload_plan). Row ids
    are GLOBAL (shard offset baked in): the bag transforms hash them, so
    draws must agree between serial and sharded runs; finalize_scores
    subtracts the shard offset back out. ``out``: a zeroed [WPA, NP] view
    to pack into (the shard's lanes of the sharded payload)."""
    G = binned.shape[1]
    pay = np.zeros((WPA, NP), np.uint32) if out is None else out
    if plan is None:
        plan = tuple((g // 4, (g % 4) * 8, 255) for g in range(G))
    col = binned.astype(np.uint32)
    for g, (w, sh, mk) in enumerate(plan):
        np.bitwise_or(pay[w, :n],
                      (col[:, g] & np.uint32(mk)) << np.uint32(sh),
                      out=pay[w, :n])
    pay[nbw, :n] = np.ascontiguousarray(
        labels.astype(np.float32)).view(np.uint32)
    pay[nbw + 1, :n] = rid_offset + np.arange(n, dtype=np.uint32)
    pay[nbw + 1, n:] = rid_sentinel          # dropped at finalize
    if weights is not None:
        pay[weight_row, :n] = np.ascontiguousarray(
            weights.astype(np.float32)).view(np.uint32)
    return pay


@telemetry.timed("ops::BuildPersistPayload(pack)", category="ops",
                 always=True)
def build_assets(dataset, labels: np.ndarray, C: int = 0,
                 CR: int = 0, num_shards: int = 1,
                 num_scores: int = 1,
                 use_weight_row: bool = True,
                 score64: bool = False) -> PersistAssets:
    """Host-side payload construction (once per dataset).

    dataset: BinnedDataset whose geometry persist_pack_ok admits.
    Sample weights (metadata.weight) ride as one extra payload row — see
    _payload_geometry.
    With num_shards > 1 the rows are cut into equal contiguous blocks
    (num_data % num_shards == 0 required; the sharded fast-path gate checks
    this) and pay0 holds the per-shard payloads concatenated on the lane
    axis — shard k's payload at lanes [k*NP, (k+1)*NP). Row ids are GLOBAL
    everywhere (the bag transforms hash them, so draws must agree between
    serial and sharded runs); finalize_scores subtracts the shard offset.
    geometry describes ONE shard, which is what the per-device program
    sees under shard_map.
    """
    n_total = int(dataset.num_data)
    if n_total % num_shards:
        raise ValueError("persist sharding needs equal row shards")
    n = n_total // num_shards
    ok, why = persist_pack_ok(dataset)
    if not ok:
        # can_persist_scan pre-checks this; a direct caller gets the
        # typed error (and the reason) instead of a bare crash
        raise PersistPackError("persist payload pack plan unavailable: "
                               + why)
    binned = dataset.binned          # [n_total, G] narrow int storage
    G = binned.shape[1]
    plan, nbw = _payload_plan(_group_widths(dataset))
    labels = np.asarray(labels)
    # pos-mode objectives (lambdarank) take weights through their own
    # gradient args — the caller then skips the payload row entirely
    # (use_weight_row=False) so no dead row rides every partition
    weight = dataset.metadata.weight if use_weight_row else None
    weight = None if weight is None else np.asarray(weight)
    has_w = weight is not None
    # the kernels histogram one 256-bin row per group, two per wide one
    hplan = hist_plan(plan)
    WPA, C, CR, NP = _payload_geometry(
        n, nbw, len(hplan), C, CR, num_scores, has_w, score64,
        loop_groups=hist_loops_groups(len(hplan), hplan))
    # run record: the newest payload's geometry (set, not summed):
    # categorical groups and the wide (16-bit slot) ones among them
    telemetry.clear_counts_prefix(
        ("ops::payload_words", "ops::chunk_lanes", "ops::root_chunk_lanes",
         "ops::cat_groups", "ops::wide_groups"))
    telemetry.count("ops::payload_words", WPA, category="ops")
    telemetry.count("ops::chunk_lanes", C, category="ops")
    telemetry.count("ops::root_chunk_lanes", CR, category="ops")
    telemetry.count("ops::cat_groups", float(_cat_groups(dataset).sum()),
                    category="ops")
    telemetry.count("ops::wide_groups", float(len(hplan) - G),
                    category="ops")
    K = num_scores
    weight_row = payload_weight_row(nbw, K, score64)
    # every shard packs into its own lanes of the one matrix, the shards
    # side by side on threads (numpy's loops release the lock): at 40M x
    # 67 four shards in turn, then a copy to join them, took a minute
    pay = np.zeros((WPA, num_shards * NP), np.uint32)

    def pack(k):
        _pack_payload(binned[k * n:(k + 1) * n],
                      labels[k * n:(k + 1) * n], n, WPA, NP,
                      nbw, rid_offset=k * n,
                      rid_sentinel=n_total, plan=plan,
                      weights=(weight[k * n:(k + 1) * n]
                               if has_w else None),
                      weight_row=weight_row,
                      out=pay[:, k * NP:(k + 1) * NP])

    if num_shards == 1:
        pack(0)
    else:
        with ThreadPoolExecutor(num_shards) as pool:
            list(pool.map(pack, range(num_shards)))
    F = dataset.num_features
    # feature f's storage slot lives in plan[group_of[f]]; its bins
    # occupy the group-local range [ls, le) (bundled groups put several
    # features plus the local-bin-0 sentinel in one byte)
    group_of = dataset.group_of.astype(np.int32)
    ls = (dataset.bin_start - dataset.group_offset[group_of]) \
        .astype(np.int32)
    nb_np = (dataset.bin_end - dataset.bin_start).astype(np.int32)
    mf_np = dataset.most_freq_bin.astype(np.int32)
    mt_np = dataset.missing_type_arr.astype(np.int32)
    db_np = dataset.default_bin.astype(np.int32)
    needs_fix = np.asarray(dataset.needs_fix, dtype=bool)
    bundled = bool(G != F or needs_fix.any() or np.any(ls != 0))
    # per-feature decode scalars come from the PLAN (nibble groups carry
    # mask 15 and 4-bit shifts; byte groups the historical 255/byte ones)
    plan_arr = np.asarray(plan, np.int32)            # [G, 3]
    # pay0 stays a HOST array: the sharded caller device_puts it with a
    # per-shard layout (materializing the whole payload on one device
    # first would spike that device's HBM by the full dataset size)
    return PersistAssets(
        pay0=pay,
        dec_word=jnp.asarray(plan_arr[group_of, 0]),
        dec_shift=jnp.asarray(plan_arr[group_of, 1]),
        dec_mask=jnp.asarray(plan_arr[group_of, 2]),
        nb=jnp.asarray(nb_np),
        mt=jnp.asarray(mt_np),
        db=jnp.asarray(db_np),
        ls=jnp.asarray(ls),
        le=jnp.asarray(ls + nb_np),
        mf=jnp.asarray(mf_np),
        geometry=(WPA, NP, G, tuple(plan), nbw, n, C, CR,
                  num_scores, has_w, score64),
        efb=(group_of, ls, nb_np, mf_np, needs_fix, bundled,
             mt_np, db_np),
    )


# ---------------------------------------------------------------------------
# pure-XLA kernel emulation (CPU fallback + sharding tests)
# ---------------------------------------------------------------------------

def _xla_planes(pay, plan, G: int, grad, hess):
    """The emulation's [G * 256] (grad, hess) planes, f64, over the
    histogram rows of ``plan`` (pallas_grow.hist_plan): a row's bin less
    its base, where the row holds it; the rest of a wide group's bins
    belong to its other row and drop."""
    gh = jnp.zeros(G * 256, jnp.float64)
    hh = jnp.zeros(G * 256, jnp.float64)
    for g, p in enumerate(plan):
        w, sh, mk = p[:3]
        if mk > 255:
            b = ((pay[w] >> U32(sh)) & U32(mk)).astype(I32) \
                - (p[3] if len(p) > 3 else 0)
            bg = jnp.where((b >= 0) & (b < 256), b + g * 256, G * 256)
            gh = gh.at[bg].add(grad, mode="drop")
            hh = hh.at[bg].add(hess, mode="drop")
        else:
            bg = ((pay[w] >> U32(sh)) & U32(mk)).astype(I32) + g * 256
            gh = gh.at[bg].add(grad)
            hh = hh.at[bg].add(hess)
    return gh, hh


def make_xla_split_pass(WPA: int, NP: int, G: int, plan, nbw: int,
                        out_dtype=F32, cat_words: int = 0):
    """jnp reference implementation of the split_pass kernel contract:
    same (pay', (gh, hh), n_left) outputs, with the partitioned segment in
    stable original order (left rows first). Row order within a segment is
    an implementation detail both impls are free over — histograms, counts
    and segment CONTENTS are what the grower depends on. Histograms
    accumulate in f64; out_dtype=f64 (the widened kernel mode) hands the
    f64 values through so the grower's gain ordering matches the v1 f64
    scan, out_dtype=f32 rounds like the Mosaic kernels (and keeps
    per-shard partial sums + psum matching a whole-data sum to f32
    round-off — the sharding equivalence tests rely on this).
    cat_words: the categorical arm of make_split_pass, on the same
    scalars."""
    grad_row = nbw + 2

    def split_pass(pay, scal):
        n_l = scal[S_NL]
        s0 = scal[S_S0]
        lane = jnp.arange(NP, dtype=I32)
        in_seg = (lane >= s0) & (lane < s0 + n_l)
        word = jnp.take(pay, scal[S_WG], axis=0)
        b_raw = ((word >> scal[S_SH].astype(U32))
                 & scal[S_MASK].astype(U32)).astype(I32)
        in_r = (b_raw >= scal[S_LS]) & (b_raw < scal[S_LE])
        b = jnp.where(in_r, b_raw - scal[S_LS], scal[S_MF])
        cmp_left = b <= scal[S_THR]
        is_na = (scal[S_MT] == 2) & (b == scal[S_NB] - 1)
        is_zero = (scal[S_MT] == 1) & (b == scal[S_DB])
        gd = is_na | is_zero
        go_left = jnp.where(gd, scal[S_DL] > 0, cmp_left)
        if cat_words:
            words = scal[S_CW0:S_CW0 + cat_words]
            cw = jnp.take(words, jnp.clip(b >> 5, 0, cat_words - 1))
            in_set = (((cw >> (b & 31)) & 1) == 1) & ((b >> 5) < cat_words)
            go_left = jnp.where(scal[S_CAT] > 0, in_set, go_left)
        gl = in_seg & go_left
        gr = in_seg & ~go_left
        nL = jnp.sum(gl, dtype=I32)
        rank_l = jnp.cumsum(gl.astype(I32)) - 1
        rank_r = jnp.cumsum(gr.astype(I32)) - 1
        target = jnp.where(gl, s0 + rank_l,
                           jnp.where(gr, s0 + nL + rank_r, lane))
        pay2 = jnp.zeros_like(pay).at[:, target].set(pay,
                                                     unique_indices=True)
        hm = in_seg & (go_left == (scal[S_SMALL_L] > 0))
        grad = jnp.where(hm, _f32r(pay[grad_row]), 0.0).astype(jnp.float64)
        hess = jnp.where(hm, _f32r(pay[grad_row + 1]), 0.0) \
            .astype(jnp.float64)
        gh, hh = _xla_planes(pay, plan, G, grad, hess)
        return pay2, (gh.astype(out_dtype), hh.astype(out_dtype)), nL

    return split_pass


def make_xla_root_hist(WPA: int, NP: int, G: int, plan, nbw: int, n: int,
                       out_dtype=F32):
    """jnp reference implementation of the root_hist kernel contract
    (f64 accumulation, see make_xla_split_pass)."""
    grad_row = nbw + 2

    def root_hist(pay):
        live = jnp.arange(NP, dtype=I32) < n
        grad = jnp.where(live, _f32r(pay[grad_row]), 0.0) \
            .astype(jnp.float64)
        hess = jnp.where(live, _f32r(pay[grad_row + 1]), 0.0) \
            .astype(jnp.float64)
        gh, hh = _xla_planes(pay, plan, G, grad, hess)
        return gh.astype(out_dtype), hh.astype(out_dtype)

    return root_hist


class _PState(NamedTuple):
    s: jnp.ndarray
    done: jnp.ndarray
    pay: jnp.ndarray           # [WPA, NP] u32
    gh: jnp.ndarray            # [L, TBe] EV gradient histogram plane
    #                          # (TBe = G*256 group planes on the kernel
    #                          # path, the flat [total_bins] v1 layout in
    #                          # the widened XLA mode)
    hh: jnp.ndarray            # [L, TBe] EV hessian histogram plane
    lstate: jnp.ndarray        # [L, 8] ST
    best: jnp.ndarray          # [L, 12] EV
    tree: jnp.ndarray          # [L, 8] ST
    levels: jnp.ndarray        # i32: level programs run for this tree
    health: jnp.ndarray        # [HEALTH_LEN] i32 numerics health vector
    #                          # (nan/inf counts + split-margin buckets;
    #                          # telemetry/health.py layout)
    # large_counts only (rows at or past EXACT_F32_ROWS): the integer
    # columns of lstate and tree, exact in i32; None otherwise
    lint: Optional[jnp.ndarray] = None    # [L, 4] i32, LI_* columns
    tint: Optional[jnp.ndarray] = None    # [L] i32 TR_ICNT by split
    # categorical layouts only: the left-set bitsets of each leaf's best
    # candidate and of each split (i32 words; None otherwise)
    bcat: Optional[jnp.ndarray] = None    # [L, NW] by leaf
    tcat: Optional[jnp.ndarray] = None    # [L, NW] by split


# ---------------------------------------------------------------------------
# device-side bagging / GOSS (payload transforms)
# ---------------------------------------------------------------------------

def _hash_uniform(rid, wkey):
    """Stateless per-row uniform in [0, 1) from (row id, window key): a
    murmur3-style integer finalizer. Rows permute across iterations but the
    row id rides the payload, so the same window key reproduces the same
    per-ROW draw regardless of position — bagging_freq windows behave like
    the reference's cached bag (gbdt.cpp:210-244) without a mask row.

    Known quirk, deliberately kept: the raw u32->f32 cast rounds hash
    values >= 2^32 - 128 UP, so u == 1.0 about one draw in 2^25 — for
    bagging that merely drops a row that a true [0, 1) draw would keep
    with probability `fraction` (a ~3e-8 rate bias, no invariant
    broken). The quantizer's noise (ops/quantize._lane_uniform) uses
    an exact 24-bit conversion instead because u == 1.0 WOULD break
    its zero-preservation invariant; changing this hash to match would
    silently re-draw every historical bag, so the two stay separate."""
    x = rid.astype(U32) ^ wkey[0]
    x = x * U32(0x85EB_CA6B)
    x = x ^ (x >> 13)
    x = (x + wkey[1]) * U32(0xC2B2_AE35)
    x = x ^ (x >> 16)
    return x.astype(F32) * F32(1.0 / 4294967296.0)


def _kth_largest(vals: jnp.ndarray, live: jnp.ndarray, k, axis_name=None):
    """EXACT k-th largest of the non-negative f32 `vals` over live lanes
    (global over `axis_name` when set): a 32-round radix select on the
    monotone u32 bit pattern of non-negative floats. Matches the value a
    full sort would pick (ties included), with only [1]-sized psums over
    the mesh — the sharded replacement for jnp.sort(s)[n - k]."""
    bits = jax.lax.bitcast_convert_type(vals, U32)

    def body(i, t):
        cand = t | (U32(1) << (U32(31) - i.astype(U32)))
        cnt = jnp.sum((bits >= cand) & live, dtype=I32)
        if axis_name is not None:
            cnt = jax.lax.psum(cnt, axis_name)
        return jnp.where(cnt >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, body, U32(0))
    return jax.lax.bitcast_convert_type(t, F32)


def make_goss_weight_fn(n_total: int, top_rate: float, other_rate: float,
                        skip_iters: int, axis_name=None):
    """Shared GOSS per-row weighting (goss.hpp:75-131): rows above the
    GLOBAL top_rate |g*h| threshold kept at weight 1, the rest kept with
    probability other_rate/(1-top_rate) amplified by (1-top_rate)/
    other_rate; warmup iterations (< skip_iters) keep every row. One
    implementation serves the persist bag transform AND the multihost
    scan so the sampling constants cannot drift.

    Returns fn(s, live, u, it) -> w [same shape as s] f32, where s is
    |g*h| (non-negative, zero on dead lanes), u a per-row uniform draw.
    """
    if top_rate + other_rate >= 1.0:
        Log.fatal("The sum of top_rate and other_rate cannot be 1.0")
    top_k = max(1, int(n_total * top_rate))
    p_rest = min(1.0, (n_total * other_rate) / max(n_total - top_k, 1))
    amp = (n_total - top_k) / max(n_total * other_rate, 1.0)

    def fn(s, live, u, it):
        thr = _kth_largest(s, live, top_k, axis_name)
        big = live & (s >= thr)
        w = jnp.where(big, F32(1.0),
                      jnp.where(u < F32(p_rest), F32(amp), F32(0.0)))
        w = jnp.where(live, w, F32(0.0))
        return jnp.where(it < skip_iters, live.astype(F32), w)

    return fn


def make_bag_transform(bag_spec, geometry, axis_name=None,
                       num_shards: int = 1):
    """Payload transform applied after the gradient fill: scales/zeroes the
    grad+hess rows per row and returns the in-bag count.

    bag_spec (static):
      ("none",)
      ("bagging", fraction, pos_fraction, neg_fraction)    — per-row
        bernoulli at the window key (balanced bagging splits by the label
        row, gbdt.cpp:210-244 / ResetBaggingConfig)
      ("goss", top_rate, other_rate, skip_iters)           — rows with
        |g*h| above the top_rate threshold kept; the rest kept with
        probability other_rate/(1-top_rate) and amplified by
        (1-top_rate)/other_rate (goss.hpp:75-124; bernoulli where the
        reference samples exactly other_k — same expectation). Sampling
        starts after skip_iters (goss.hpp:126-131). The threshold is the
        GLOBAL top_k-th |g*h| (radix select with psum'd counts), so
        sharded runs redraw the identical bag.

    axis_name/num_shards: set by the sharded persist learner — GOSS's
    order statistic and the bag fractions are over the GLOBAL row count.

    Returns fn(pay, wkey [2]u32, it i32) -> (pay', bag_cnt f32 local).
    """
    WPA, NP, G, plan, nbw, n, C, CR = geometry[:8]
    n_total = n * max(num_shards, 1)
    grad_row = nbw + 2
    mode = bag_spec[0]

    def none_fn(pay, wkey, it):
        return pay, jnp.asarray(n, F32)

    if mode == "none":
        return none_fn

    def apply_w(pay, w):
        g = _f32r(pay[grad_row]) * w
        h = _f32r(pay[grad_row + 1]) * w
        gh = jax.lax.bitcast_convert_type(jnp.stack([g, h]), U32)
        pay = jax.lax.dynamic_update_slice(
            pay, gh, (jnp.asarray(grad_row, I32), jnp.asarray(0, I32)))
        return pay, jnp.sum((w > 0).astype(F32))

    if mode == "bagging":
        _, fraction, pos_f, neg_f = bag_spec
        balanced = pos_f < 1.0 or neg_f < 1.0

        def bag_fn(pay, wkey, it):
            live = jnp.arange(NP, dtype=I32) < n
            u = _hash_uniform(pay[nbw + 1], wkey)
            if balanced:
                pos = _f32r(pay[nbw]) > 0
                keep = jnp.where(pos, u < F32(pos_f), u < F32(neg_f))
            else:
                keep = u < F32(fraction)
            w = (keep & live).astype(F32)
            return apply_w(pay, w)

        return bag_fn

    if mode == "goss":
        _, top_rate, other_rate, skip_iters = bag_spec
        wfn = make_goss_weight_fn(n_total, top_rate, other_rate,
                                  skip_iters, axis_name)

        def goss_fn(pay, wkey, it):
            live = jnp.arange(NP, dtype=I32) < n
            g = _f32r(pay[grad_row])
            h = _f32r(pay[grad_row + 1])
            s = jnp.where(live, jnp.abs(g * h), 0.0)
            u = _hash_uniform(pay[nbw + 1], wkey)
            return apply_w(pay, wfn(s, live, u, it))

        return goss_fn

    raise ValueError("unknown bag mode %r" % (mode,))


def make_persist_grower(assets: PersistAssets, meta, gc,
                        interpret: bool = False, axis_name=None,
                        kernel_impl: str = "pallas",
                        stat_from_scan: bool = False,
                        large_counts=None, fix=None,
                        level_mode: str = "auto",
                        health: bool = True,
                        quant=None, comm_overlap: bool = False,
                        cat=None):
    """Build grow/score/gradient closures for one dataset + grow config.

    gc: GrowConfig (num_leaves, max_depth, num_features, scan_width used).
    Returns an object with .grow(pay, params, fmask), .apply_scores,
    .fill_grad, .finalize_scores.

    level_mode: "auto" enables the LEVEL-PARALLEL growth phase whenever
    can_level_grow(gc) holds — an entire tree level (multi-leaf
    partition, smaller-child histograms, batched best-split find for
    every frontier child) runs as ONE compiled region per level, driven
    by a bounded loop over depths, so a tree costs ~max_depth device
    program launches instead of ~num_leaves-1. Leaf-wise semantics are
    preserved exactly: frontier leaves admit in gain order, and an
    in-program NO-BIND certificate (remaining leaf budget >= the
    depth-limited completion capacity of the positive-gain frontier)
    hands the tree to the per-split tail the moment best-first admission
    could be budget-truncated — the tail is the historical per-split
    loop, so truncated trees match it split for split. "off" forces the
    per-split path everywhere.

    fix: FixInfo (ops/grow.FixInfo) for EFB-bundled datasets — the
    widened XLA kernel mode applies Dataset::FixHistogram at histogram
    STORE time exactly like the v1 grower (the Mosaic path keeps the
    in-kernel fix residual).

    health: accumulate the device-side numerics health vector (NaN/Inf
    counts over gradients/hessians/histogram planes + the log-bucketed
    split-margin histogram — best gain minus runner-up at every split
    decision, the geometry the quant_certify budgets protect) in the
    scan carry next to the level stats: a few fused VPU reductions per
    split, zero extra launches, zero host syncs (the transfer audit's
    contract). False zeroes the health tail of the stats vector
    (tpu_numerics_stats=off — the overhead-pin escape hatch).

    quant: optional ops/quantize.HistQuant — the cross-device
    histogram-plane reductions (root/level/split psums, the voting
    winner-window reduce) ship int16 stochastic-rounded codes instead of
    full-width floats (ROADMAP item 2; the spec must carry a green
    quant_certify certificate, asserted by
    parallel/distributed.resolve_hist_quant). Rank-uniform seeds per
    (iteration, stage, plane) keep the reconstructed global planes
    bit-identical on every rank. Inert when axis_name is None.

    comm_overlap: double-buffer the level program's plane reductions as
    two staged half-batches — the reduce of half A is dispatched before
    half B's planes are touched, so on hardware with async collectives
    the wire time of A hides under B's accumulate/quantize compute.
    Bit-identical to the single full-batch reduce (rows reduce
    independently; the stochastic-rounding noise is seeded by GLOBAL
    slot position).

    stat_from_scan: leaf counts come from the scan's hessian-derived
    rounding (the reference's cnt_factor recovery,
    feature_histogram.hpp:772-790) instead of the kernel's exact
    partition counts. Required under bagging/GOSS, where out-of-bag rows
    still ride the payload segments and the geometric counts no longer
    equal the statistical ones; grow() then takes the exact in-bag root
    count from the bag transform.

    large_counts: keep row counts and segment positions in i32 beside the
    f32 leaf state (see the note at ``ST`` below). None: from
    EXACT_F32_ROWS rows on, by this payload's own n; a sharded caller
    passes the choice of the GLOBAL row count, since the counts it holds
    are global.

    axis_name: when set, the grower body runs per-shard under shard_map
    over that mesh axis with rows sharded — the data-parallel learner over
    the persist path. Exactly like the v1 sharded grower (and the
    reference's ReduceScatter at data_parallel_tree_learner.cpp:163-234),
    only the per-split smaller-child histogram planes, the left counts and
    the root sums cross devices: leaf STATISTICS (sums, counts, gains,
    split choices) are global, while payload GEOMETRY (segment starts/
    lengths) stays shard-local. Every shard then takes identical split
    decisions from the identical global state — SPMD without divergence.

    kernel_impl: "pallas" (TPU Mosaic kernels) or "xla" (the jnp reference
    implementation — CPU fallback and what the 8-device CPU-mesh sharding
    tests run). The xla mode is WIDENED: f64 histogram planes in the v1
    flat [total_bins] layout, f64 leaf state and the v1 f64 split-find
    (find_best_split_numerical), plus f64 payload score rows — so its
    split ordering and leaf values match the v1 f64 grower bit for bit
    (the fix for the historical persist-vs-v1 tie-flip on noise-gain
    splits). The Mosaic path keeps the f32 fast-path trade
    (gpu_use_dp=false) unchanged.

    cat: the learner's CatLayout (ops/split.py; the v1 grower's, over the
    flat [total_bins] layout; None or empty: no categorical feature).
    With categorical features each candidate leaf also takes its best
    categorical split
    (find_best_split_categorical under the ``cat_scan`` scope: the v1
    grower's own arithmetic, f64 in the widened mode, f32 on the kernel
    planes) and the better of the two wins as in the v1 merge; the chosen
    left set rides the best-candidate and split records as a bitset of
    ceil(cat_width / 32) i32 words, reaches split_pass as scalars
    (S_CAT, S_CW0) and becomes TreeArrays.is_cat / cat_mask. Single
    device, unbundled layouts, per-split growth: the learner's gate
    (can_persist_scan) keeps categorical datasets off the sharded
    learners and the bundled layouts, and the level phase is off.
    """
    WPA, NP, G, plan, nbw, n, C, CR = assets.geometry[:8]
    K = assets.geometry[8] if len(assets.geometry) > 8 else 1
    has_w = bool(assets.geometry[9]) if len(assets.geometry) > 9 else False
    score64 = bool(assets.geometry[10]) \
        if len(assets.geometry) > 10 else False
    wide = kernel_impl == "xla"
    if wide != score64:
        raise ValueError("persist payload score layout does not match the "
                         "kernel mode: build_assets(score64=%r) but "
                         "kernel_impl=%r (the widened XLA mode needs f64 "
                         "score rows)" % (score64, kernel_impl))
    F = gc.num_features
    L = gc.num_leaves
    W = 256
    # the kernels' histogram rows: one per group, two for a 16-bit group
    # (pallas_grow.hist_plan); group g's rows start at hrow_np[g]
    hplan = hist_plan(plan)
    GH = len(hplan)
    hrow_np = np.cumsum([0] + [2 if p[2] > 255 else 1 for p in plan])[:-1]
    TBp = GH * W
    has_cat = cat is not None and int(cat.cat_feature.shape[0]) > 0
    if has_cat and axis_name is not None:
        raise ValueError("categorical features on the persist path run on "
                         "one device (can_persist_scan refuses the sharded "
                         "learners)")
    # the left set's bitset words, scalars of split_pass from S_CW0 on
    CW = int(cat.gather_idx.shape[1]) if has_cat else 0
    NW = -(-CW // 32)
    EV = jnp.float64 if wide else F32   # histogram/eval dtype
    # the leaf-state/tree-record matrices carry integer counts and
    # payload positions in float lanes; f32 is integer-exact only to
    # 2^24. From EXACT_F32_ROWS rows on (``large_counts``; sharded
    # callers pass the GLOBAL row count's choice, since the counts are
    # global where the positions are the shard's) those columns live in
    # i32 beside the matrices (_PState.lint / .tint) and nothing reads
    # their float lanes: no emulated f64 on the chip (PERF.md section 7
    # row 0b: the f64 state this replaces lost a few rows of the counts
    # in every tree at 31.5M rows).
    # EXACT above 2^24: every leaf's and node's row count (partition
    # counts, psum'd in i32), segment starts and lengths.
    # ESTIMATE-GRADE above 2^24, as the reference's cnt_factor recovery
    # is at any size: the scan's hessian-derived counts (f32). They gate
    # min_data_in_leaf, choose which child is histogrammed and are the
    # leaf counts under bagging / GOSS. At a 40M-row root a candidate's
    # small side is a difference of f32 sums of ~1e6 (ulp 0.125) at ~0.03
    # a row, so min_data_in_leaf=20 is enforced there to within a few
    # rows; deeper nodes' sums shrink and the gate sharpens with them.
    # The recorded counts stay the partition's, exact. The widened XLA
    # mode is f64 throughout (native off the chip, exact to 2^53).
    ST = jnp.float64 if wide else F32
    if large_counts is None:
        large_counts = n >= EXACT_F32_ROWS
    big = bool(large_counts) and not wide

    def icol(row_f, row_i, col_f, col_i):
        """An integer column of the leaf state as i32: from the i32
        matrix's row(s) under large_counts, else from the float lanes."""
        return row_i[..., col_i] if big else row_f[..., col_f].astype(I32)
    # level-parallel phase sizing: up to S_MAXL splitting leaves per
    # level program (the widest frontier a depth-bounded tree can
    # present), 2*S_MAXL children per batched split-find
    use_level = (level_mode != "off" and can_level_grow(gc)
                 and not has_cat)
    md = int(gc.max_depth)
    S_MAXL = min(1 << max(md - 1, 0), L - 1) if use_level else 1
    T_MAXL = NP // max(C, 1) + 3 * S_MAXL + 4
    level_pass = None
    level_seg = None
    if kernel_impl == "xla":
        split_pass = make_xla_split_pass(WPA, NP, GH, hplan, nbw,
                                         out_dtype=EV, cat_words=NW)
        root_hist = make_xla_root_hist(WPA, NP, GH, hplan, nbw, n,
                                       out_dtype=EV)
        seg_hist = None     # the emulated pass builds the histogram
    else:
        from .pallas_grow import (_unpack_hist as _unpack_hist_v,
                                  make_level_pass, make_level_seg_hist,
                                  make_seg_hist)
        # every score/snapshot/weight row must ride the partition
        wp_live = payload_weight_row(nbw, K, score64) + (1 if has_w else 0)
        # the smaller child's histogram is built AFTER the pass, by
        # seg_hist over the child's contiguous segment, at every group
        # count. The chip decided it (PERF.md, PR 35; expo.train_steady,
        # 16 groups): with the histogram inside the pass over all lanes
        # of the parent's chunks a traced launch spent 12.20 s in
        # split_pass; over the compacted slot only (pallas_grow.
        # _slot_hist) 7.22 s; with seg_hist 6.59 + 0.51 s, 4,064 more
        # launches a launch of 16 trees included, and the launches after
        # it 1-3% shorter again. Both feed the same parent-minus-smaller
        # subtraction.
        split_pass = make_split_pass(WPA, NP, GH, hplan, nbw, C=C,
                                     interpret=interpret, wp_live=wp_live,
                                     _skip_hist=True, cat_words=NW)
        seg_hist = make_seg_hist(WPA, NP, GH, hplan, nbw, C=C,
                                 interpret=interpret)
        root_hist = make_root_hist(WPA, NP, GH, hplan, nbw, n, C=CR,
                                   interpret=interpret)
        if use_level:
            # built ONCE here, invoked inside the traced level loop —
            # never constructed per level (JG004's no-pallas-in-loop)
            level_pass = make_level_pass(
                WPA, NP, G, plan, nbw, S_MAXL, T_MAXL, C=C,
                interpret=interpret, wp_live=wp_live, _skip_hist=True)
            level_seg = make_level_seg_hist(WPA, NP, G, plan, nbw,
                                            S_MAXL, T_MAXL, C=C,
                                            interpret=interpret)
    grad_row = nbw + 2
    SR = 2 if score64 else 1       # payload rows per score value
    score_row = nbw + 4            # class k's score rows at +SR*k
    snap_row = nbw + 4 + SR * K    # class k's snapshot rows (K > 1 only)
    weight_row = payload_weight_row(nbw, K, score64)  # only when has_w

    # PV-tree voting-parallel (voting_parallel_tree_learner.cpp:153-344):
    # histogram planes stay shard-LOCAL; per split each shard proposes
    # its top_k features from a LOCAL gain scan, the proposals cross the
    # wire as a small top-k INDEX allgather (the LightSplitInfo exchange,
    # :321 — k i32 words per rank per leaf, not an [F]-plane vote psum),
    # and only the globally voted 2k winners' bin windows are reduced
    # before the real scan
    voting = axis_name is not None and gc.parallel_mode == "voting"
    K_TOP = min(max(int(gc.top_k), 1), F)
    N_WIN = min(2 * K_TOP, F)
    if axis_name is None:
        quant = None      # unsharded: no wire, no quantization noise

    def _global_vote(local_gains):
        """PV-Tree vote over the wire: per-rank top-k proposal indices
        -> vote_allgather -> rank-uniform winner ranking. Ties keep the
        smaller feature id and the 2k quota always fills (GlobalVoting,
        voting_parallel_tree_learner.cpp:153-184). Returns win_idx
        [B, N_WIN] — identical on every rank."""
        B = local_gains.shape[0]
        neg = jnp.asarray(K_MIN_SCORE, local_gains.dtype)
        prop = topk_vote_indices(local_gains, K_TOP, F, neg)  # [B, K_TOP]
        gath = vote_allgather("allgather:vote_topk", prop,
                              axis_name)                      # [S, B, K]
        Sn = gath.shape[0]
        bidx = jnp.broadcast_to(jnp.arange(B, dtype=I32)[None, :, None],
                                (Sn, B, K_TOP))
        votes = jnp.zeros((B, F), I32).at[bidx, gath].add(
            1, mode="drop")            # F-sentinel proposals drop out
        rank_key = votes * F - jnp.arange(F, dtype=I32)[None]
        _, win_idx = jax.lax.top_k(rank_key, N_WIN)
        return win_idx

    # padded meta for the dense scan: feature f's window sits inside its
    # storage group's [G, 256] block at the group-local offset (ls = 0 and
    # group_of = identity when nothing is bundled, i.e. flat f*W)
    (group_of_np, ls_np, nb_np, mf_np, needs_fix_np, bundled,
     mt_np, db_np) = assets.efb
    win_start_np = (hrow_np[group_of_np] * W + ls_np).astype(np.int32)
    # feature f's first histogram row where a categorical layout moves it
    # off row f (a wide group's second row; a group order not the
    # features'); a cat-free layout keeps the group rows as they are
    feat_rows = hrow_np[group_of_np] if has_cat else None
    if feat_rows is not None and GH == G \
            and np.array_equal(feat_rows, np.arange(F)):
        feat_rows = None
    pad_meta = meta._replace(
        bin_start=jnp.asarray(win_start_np),
        bin_end=jnp.asarray(win_start_np + nb_np))
    has_fix = bool(needs_fix_np.any())
    if wide:
        # widened mode keeps the histogram planes in the v1 grower's FLAT
        # [total_bins] layout: the kernels' [G, 256] group planes gather
        # through lane_of_bin right after each kernel call, and from
        # there fix/subtract/eval run the exact v1 ops in the exact v1
        # order (find_best_split_numerical on f64 — the tie-flip fix)
        bs_np = np.asarray(meta.bin_start, np.int64)
        be_np = np.asarray(meta.bin_end, np.int64)
        TBW = int(be_np.max()) if F else 1
        lane_np = np.zeros(TBW, np.int64)
        for f_ in range(F):
            lane_np[bs_np[f_]:be_np[f_]] = (
                win_start_np[f_] + np.arange(be_np[f_] - bs_np[f_]))
        lane_of_bin = jnp.asarray(lane_np.astype(np.int32))
        TBe = TBW
        if has_fix and fix is None:
            raise ValueError("widened persist mode on an EFB-bundled "
                             "dataset needs the FixInfo (pass fix=)")
    else:
        lane_of_bin = None
        TBe = TBp
    W_scan = max(int(gc.scan_width), 1)
    if has_cat:
        cat_ids_np = np.asarray(cat.cat_feature, np.int64)
        is_cat_np = np.zeros(F, bool)
        is_cat_np[cat_ids_np] = True
        is_cat_f = jnp.asarray(is_cat_np)
        if not wide:
            # the v1 layout's windows moved onto the kernels' planes
            cat = cat._replace(gather_idx=jnp.asarray(np.clip(
                win_start_np[cat_ids_np][:, None] + np.arange(CW), 0,
                TBp - 1).astype(np.int32)))

    def cat_scan(g2, h2, sgs, shs, cnts, params, fmask):
        """[B] best categorical candidates (SplitCandidate) of the leaves
        whose [B, TBe] histogram rows are g2/h2: the v1 grower's
        find_best_split_categorical, in EV."""
        with jax.named_scope("cat_scan"):
            hist = jnp.stack([g2, h2], axis=-1)
            return jax.vmap(
                lambda h_, sg_, sh_, nd_: find_best_split_categorical(
                    h_, sg_, sh_, nd_, cat, meta, params, -jnp.inf,
                    jnp.inf, fmask, use_mc=False, use_dp=wide,
                    steps=gc.max_cat_threshold))(
                hist, sgs.astype(EV), shs.astype(EV), cnts.astype(I32))

    def cat_bits(mask):
        """[B, CW] bool left set -> [B, NW] i32 bitset words."""
        B = mask.shape[0]
        m = jnp.pad(mask, ((0, 0), (0, NW * 32 - CW))) \
            .reshape(B, NW, 32).astype(U32)
        w = jnp.sum(m << jnp.arange(32, dtype=U32), axis=-1, dtype=U32)
        return jax.lax.bitcast_convert_type(w, I32)

    def merge_cat(mat, cc, depths):
        """The [B, 12] best-candidate matrix with each leaf's categorical
        candidate merged in (SplitInfo::operator>, as merge_candidates:
        higher gain, ties to the smaller feature id), and the [B, NW]
        bitsets of the winners (zeros where a numerical split won)."""
        neg = jnp.asarray(K_MIN_SCORE, EV)
        ok = cc.gain > neg
        if gc.max_depth > 0:
            ok &= depths.astype(EV) < gc.max_depth
        row = jnp.stack([
            jnp.where(ok, cc.gain, neg),
            jnp.where(ok, cc.feature, -1).astype(EV),
            jnp.zeros_like(cc.gain), jnp.zeros_like(cc.gain),
            cc.left_sum_grad, cc.left_sum_hess,
            cc.right_sum_grad, cc.right_sum_hess,
            cc.left_count.astype(EV), cc.right_count.astype(EV),
            cc.left_output, cc.right_output], axis=1).astype(EV)
        ng, nf = mat[:, BC_GAIN], mat[:, BC_FEAT]
        cg, cf = row[:, BC_GAIN], row[:, BC_FEAT]
        win = (cg > ng) | ((cg == ng) & (cf >= 0) & ((nf < 0) | (cf < nf)))
        return (jnp.where(win[:, None], row, mat),
                jnp.where(win[:, None], cat_bits(cc.cat_mask), 0))

    def to_flat(plane):
        """Kernel-layout [..., G*256] plane -> eval-layout [..., TBe]."""
        if not wide:
            return plane
        return jnp.take(plane, lane_of_bin, axis=-1)

    def fix_store(g_pl, h_pl, sgs, shs):
        """Dataset::FixHistogram at histogram STORE time (v1 order:
        fix the computed child, then subtract) — widened mode only; the
        Mosaic kernels repair in-kernel at eval. Accepts [TBe] or
        [B, TBe] planes with matching scalar/[B] sums."""
        if not (wide and has_fix):
            return g_pl, h_pl

        def one(g_, h_, sg_, sh_):
            hist = fix_histogram(jnp.stack([g_, h_], axis=-1), sg_, sh_,
                                 fix.mf_global, fix.start, fix.end,
                                 max_w=W_scan, use_dp=True)
            return hist[:, 0], hist[:, 1]

        if g_pl.ndim == 1:
            return one(g_pl, h_pl, sgs, shs)
        return jax.vmap(one)(g_pl, h_pl, sgs.astype(EV), shs.astype(EV))
    if bundled and not wide:
        # bundle-native split scan: static per-lane window masks over the
        # [G, 256] group planes, derived ONCE per payload geometry and
        # reused across every level and tree (the per-feature path
        # re-gathered [2, F, 256] copies and re-applied FixHistogram
        # tensors per split — at Expo's 700 features from 16 groups that
        # was a 44x duplication on the hottest fixed cost)
        from .pallas_scan import (BM_VALID_F, BM_VALID_R,
                                  build_block_scan_meta, scan_blocks)
        with telemetry.scope("ops::BuildBlockScanMeta", category="ops",
                             always=True):
            blk = build_block_scan_meta(
                group_of_np, ls_np, nb_np, mt_np, db_np, mf_np,
                needs_fix_np, np.asarray(meta.penalty, np.float64), G, W)
        Gp, Wp = blk["masks"].shape[1:]
        blk_masks0 = jnp.asarray(blk["masks"])
        blk_owner = jnp.asarray(
            np.where(blk["has_owner"], blk["owner"], 0)
            .reshape(-1).astype(np.int32))
        blk_has = jnp.asarray(blk["has_owner"].astype(np.float32))
        forced_right_np = jnp.asarray((mt_np == 2) & (nb_np <= 2))
        ls_f32 = jnp.asarray(ls_np.astype(np.float32))

        class _BlockTreeLayout:
            """Per-tree view of the cached block masks (fmask folded)."""

            def __init__(self, fmask):
                fm_lane = (jnp.take(fmask.astype(F32),
                                    blk_owner).reshape(Gp, Wp) * blk_has)
                self.masks = blk_masks0.at[BM_VALID_R:BM_VALID_F + 1] \
                                       .multiply(fm_lane[None])

    def eval_batch_wide(g2, h2, sgs, shs, cnts, depths, params,
                        fmask, tag):
        """Widened split-find: the v1 f64 scan, batched over leaves.

        g2/h2: [B, TBe] f64, the leaves' flat histogram rows themselves
        (the caller holds them; nothing is read back from the [L, TBe]
        planes); sgs/shs/cnts/depths: [B]. Returns [B, 12] f64 BC matrix.
        Ordering, tie-breaks, count recovery and leaf outputs come from
        find_best_split_numerical itself, so they match the v1 grower
        bit for bit given identical histograms."""
        sgs = sgs.astype(jnp.float64)
        shs = shs.astype(jnp.float64)
        nd = cnts.astype(I32)
        fmask_b = None
        if voting:
            # PV-tree in the flat layout: each shard scans its LOCAL
            # planes with 1/S-scaled thresholds, the top-k proposals
            # cross as a small index allgather, and ONLY the globally
            # voted winners' bin windows are reduced — a compact
            # [B, 2, N_WIN, W_scan] buffer over the wire (int16 codes
            # under quantization), never the full planes.
            B = g2.shape[0]
            Sn_f = jax.lax.psum(jnp.asarray(1.0, jnp.float64), axis_name)
            Sn_i = Sn_f.astype(I32)
            local_sg = jnp.sum(g2, axis=1) / jnp.float64(max(F, 1))
            local_sh = jnp.sum(h2, axis=1) / jnp.float64(max(F, 1)) \
                + jnp.float64(2e-15)
            local_cnt = jnp.round(
                local_sh * nd.astype(jnp.float64)
                / jnp.maximum(shs, jnp.float64(1e-12))).astype(I32)
            p_local = params._replace(
                min_data_in_leaf=jnp.maximum(
                    params.min_data_in_leaf // jnp.maximum(Sn_i, 1), 1),
                min_sum_hessian_in_leaf=(
                    params.min_sum_hessian_in_leaf / Sn_f))
            lg_all = jax.vmap(lambda g_, h_, sg_, sh_, nd_:
                              find_best_split_numerical(
                                  jnp.stack([g_, h_], axis=-1), sg_, sh_,
                                  nd_, meta, p_local, -jnp.inf, jnp.inf,
                                  fmask, F, use_mc=False, max_w=W_scan,
                                  use_dp=True, use_l1=gc.use_l1,
                                  use_mds=gc.use_mds,
                                  feat_gains_only=True))(
                g2, h2, local_sg, local_sh, local_cnt)        # [B, F]
            win_idx = _global_vote(lg_all)                    # [B, N_WIN]
            arB = jnp.arange(B, dtype=I32)[:, None]
            winb = jnp.zeros((B, F), BOOL).at[arB, win_idx].set(True)
            # compact winner-window exchange: gather the voted features'
            # [bs, be) bin windows out of the flat planes, reduce that
            # buffer, scatter back; everything else stays shard-local
            bs_w = meta.bin_start[win_idx].astype(I32)        # [B, N_WIN]
            wid_w = (meta.bin_end[win_idx]
                     - meta.bin_start[win_idx]).astype(I32)
            lane_ar = jnp.arange(W_scan, dtype=I32)[None, None, :]
            lane = bs_w[:, :, None] + lane_ar    # [B, N_WIN, W_scan]
            lvalid = lane_ar < wid_w[:, :, None]
            gidx = jnp.clip(lane, 0, TBe - 1).reshape(B, -1)
            gw = jnp.take_along_axis(g2, gidx, axis=1) \
                .reshape(B, N_WIN, W_scan)
            hw = jnp.take_along_axis(h2, gidx, axis=1) \
                .reshape(B, N_WIN, W_scan)
            gw = jnp.where(lvalid, gw, 0.0)
            hw = jnp.where(lvalid, hw, 0.0)
            rg, rh = plane_psum("psum:vote_windows", gw, hw, axis_name,
                                quant, tag)
            scat = jnp.where(lvalid, lane, TBe)   # out-of-range drops
            arB3 = jnp.broadcast_to(arB[:, :, None], lane.shape)
            g2 = g2.at[arB3, scat].set(rg, mode="drop")
            h2 = h2.at[arB3, scat].set(rh, mode="drop")
            fmask_b = fmask[None, :] & winb                    # [B, F]
        hist = jnp.stack([g2, h2], axis=-1)                    # [B, TBe, 2]
        if fmask_b is None:
            cand = find_best_split_numerical_batch(
                hist, sgs, shs, nd, meta, params, fmask, F,
                use_dp=True, use_l1=gc.use_l1, use_mds=gc.use_mds,
                max_w=W_scan)
        else:
            cand = jax.vmap(lambda h_, sg_, sh_, nd_, fm_:
                            find_best_split_numerical(
                                h_, sg_, sh_, nd_, meta, params,
                                -jnp.inf, jnp.inf, fm_, F, use_mc=False,
                                max_w=W_scan, use_dp=True,
                                use_l1=gc.use_l1, use_mds=gc.use_mds))(
                hist, sgs, shs, nd, fmask_b)
        gain = cand.gain.astype(EV)
        if gc.max_depth > 0:
            gain = jnp.where(depths.astype(EV) < gc.max_depth, gain,
                             jnp.asarray(K_MIN_SCORE, EV))
        return jnp.stack([
            gain,
            cand.feature.astype(EV),
            cand.threshold.astype(EV),
            cand.default_left.astype(EV),
            cand.left_sum_grad.astype(EV), cand.left_sum_hess.astype(EV),
            cand.right_sum_grad.astype(EV),
            cand.right_sum_hess.astype(EV),
            cand.left_count.astype(EV), cand.right_count.astype(EV),
            cand.left_output.astype(EV), cand.right_output.astype(EV),
        ], axis=1)                                             # [B, 12]

    def eval_batch(g2, h2, sgs, shs, cnts, depths, params,
                   layout, tag):
        """Best splits for a BATCH of leaves from their histogram rows
        (g2/h2: [B, TBe] — separate grad/hess rows so no strided channel
        slices exist anywhere). The caller hands over the rows it holds:
        a gather of them by an index vector out of the [L, TBe] planes
        copies both planes whole on TPU (2 GB a split at 2,000 columns).

        sgs/shs/cnts/depths: [B]. Historically B was the (left, right)
        pair of one split; the level program feeds every frontier child
        of a level at once. Returns a [B, 12] EV best-candidate matrix.
        """
        B = g2.shape[0]
        p32 = params.cast(F32)
        sg = sgs.astype(F32)
        sh = shs.astype(F32) + F32(2e-15)
        cnt = cnts.astype(F32)
        l2 = p32.lambda_l2.astype(F32)
        cf = cnt / sh
        gain_shift = sg * sg / (sh + l2)
        mgs = gain_shift + p32.min_gain_to_split.astype(F32)
        md_ = p32.min_data_in_leaf.astype(F32)
        mh = p32.min_sum_hessian_in_leaf.astype(F32)

        def finish(gain_b, best_f, t_b, use_f_b, lg, lh, lc, forced_r):
            """Shared assembly of the [B, 12] best-candidate matrix."""
            best_valid = jnp.isfinite(gain_b)
            if gc.max_depth > 0:
                best_valid &= depths.astype(F32) < gc.max_depth
            rg = sg - lg
            rh = sh - lh
            rc = cnt - lc
            lo = -lg / (lh + l2)
            ro = -rg / (rh + l2)
            default_left = (~use_f_b) & (~forced_r)
            neg = jnp.asarray(K_MIN_SCORE, F32)
            return jnp.stack([
                jnp.where(best_valid, gain_b, neg),
                jnp.where(best_valid, best_f.astype(F32), -1.0),
                jnp.where(best_valid, t_b, 0.0),
                jnp.where(best_valid, default_left, True).astype(F32),
                lg, lh, rg, rh,
                jnp.floor(lc + 0.5), jnp.floor(rc + 0.5),
                lo, ro], axis=1)                        # [B, 12]

        if bundled:
            # bundle-native path: scan the [G, 256] group planes directly
            # (scan_blocks) — no per-feature gather, no per-split fix
            # tensors; masks come precomputed from the cached layout. The
            # kernel returns per-GROUP results with ABSOLUTE block-lane
            # thresholds; the owner map recovers the feature id.
            gbB = jnp.pad(g2.reshape(B, G, W),
                          ((0, 0), (0, Gp - G), (0, Wp - W)))
            hbB = jnp.pad(h2.reshape(B, G, W),
                          ((0, 0), (0, Gp - G), (0, Wp - W)))
            scal9 = jnp.stack([
                sg, sh, cnt, cf,
                jnp.broadcast_to(md_, (B,)), jnp.broadcast_to(mh, (B,)),
                mgs, jnp.broadcast_to(l2, (B,)),
                shs.astype(F32)], axis=1)
            outB = scan_blocks(scal9, gbB, hbB, layout.masks,
                               do_fix=has_fix, interpret=interpret)
            gains_g = outB[:, 0, :]                    # [B, Gp]
            best_g = jnp.argmax(gains_g, axis=1)

            def takeg(row):
                return jnp.take_along_axis(outB[:, row, :],
                                           best_g[:, None], axis=1)[:, 0]
            gain_b = takeg(0)
            t_abs = takeg(1)
            use_f_b = takeg(2) > 0.5
            lg, lh, lc = takeg(3), takeg(4), takeg(5)
            t_i = jnp.clip(t_abs, 0, Wp - 1).astype(I32)
            best_f = jnp.take(blk_owner, best_g.astype(I32) * Wp + t_i)
            t_b = t_abs - jnp.take(ls_f32, best_f)
            return finish(gain_b, best_f, t_b, use_f_b, lg, lh, lc,
                          jnp.take(forced_right_np, best_f))

        pad_f = ((0, 0), (0, layout.Fp - G), (0, 0))
        valid_r, valid_f = layout.valid_r, layout.valid_f
        if voting:
            # local proposal scan: 1/S-scaled thresholds on the LOCAL
            # planes with exact local sums (each row lands in one bin of
            # each of the G groups, so plane_sum / G = local leaf sum)
            Sn = jax.lax.psum(jnp.asarray(1.0, F32), axis_name)
            local_sg = jnp.sum(g2, axis=1) / F32(max(G, 1))
            local_sh = jnp.sum(h2, axis=1) / F32(max(G, 1)) + F32(2e-15)
            local_cnt = jnp.round(local_sh * cnt
                                  / jnp.maximum(sh, F32(1e-12)))
            scal_l = jnp.stack([
                local_sg, local_sh, local_cnt, local_cnt / local_sh,
                jnp.broadcast_to(jnp.maximum(jnp.floor(md_ / Sn), 1.0),
                                 (B,)),
                jnp.broadcast_to(mh / Sn, (B,)),
                local_sg * local_sg / (local_sh + l2)
                + p32.min_gain_to_split.astype(F32),
                jnp.broadcast_to(l2, (B,))], axis=1)
            gb_l = jnp.pad(g2.reshape(B, G, W), pad_f)
            hb_l = jnp.pad(h2.reshape(B, G, W), pad_f)
            out_l = scan_pair(scal_l, gb_l, hb_l, layout.keep_r,
                              layout.keep_f, valid_r, valid_f, layout.aux,
                              interpret=interpret)
            local_gains = out_l[:, 0, :][:, :F]        # [B, F]
            # the vote exchange: a [B, K_TOP] index allgather (not an
            # [F]-plane psum), winners ranked identically on every rank
            win_idx = _global_vote(local_gains)        # [B, N_WIN]
            # the ACTUAL communication compression: gather only the 2k
            # winners' bin windows, reduce that compact buffer (int16
            # codes under quantization), and scatter back —
            # [B, 2, N_WIN, W] over the wire instead of the full
            # [B, 2, TBp] planes (CopyLocalHistogram + ReduceScatter,
            # voting_parallel_tree_learner.cpp:186-243)
            g3 = g2.reshape(B, G, W)
            h3 = h2.reshape(B, G, W)
            gw = jnp.take_along_axis(g3, win_idx[:, :, None], axis=1)
            hw = jnp.take_along_axis(h3, win_idx[:, :, None], axis=1)
            rg, rh = plane_psum("psum:vote_windows", gw, hw, axis_name,
                                quant, tag)
            ar2 = jnp.arange(B, dtype=I32)[:, None]
            g2 = g3.at[ar2, win_idx].set(rg).reshape(B, TBp)
            h2 = h3.at[ar2, win_idx].set(rh).reshape(B, TBp)
            winb = jnp.zeros((B, F), BOOL).at[ar2, win_idx].set(True)
            winp = jnp.pad(winb, ((0, 0), (0, layout.Fp - G)))
            valid_r = valid_r[None] * winp[:, :, None].astype(F32)
            valid_f = valid_f[None] * winp[:, :, None].astype(F32)
        if feat_rows is not None:
            # feature f's first histogram row at row f: a wide group's
            # second row carries no numerical feature, and a layout with
            # categorical groups need not keep its groups in feature order
            g2 = jnp.take(g2.reshape(B, GH, W), feat_rows, axis=1)
            h2 = jnp.take(h2.reshape(B, GH, W), feat_rows, axis=1)
        gb = jnp.pad(g2.reshape(B, G, W), pad_f)
        hb = jnp.pad(h2.reshape(B, G, W), pad_f)
        scal = jnp.stack([
            sg, sh, cnt, cf,
            jnp.broadcast_to(md_, (B,)), jnp.broadcast_to(mh, (B,)),
            mgs, jnp.broadcast_to(l2, (B,))], axis=1)
        out = scan_pair(scal, gb, hb, layout.keep_r, layout.keep_f,
                        valid_r, valid_f, layout.aux,
                        interpret=interpret)
        gains = out[:, 0, :]
        best_f = jnp.argmax(gains, axis=1)

        def take(row):
            return jnp.take_along_axis(out[:, row, :], best_f[:, None],
                                       axis=1)[:, 0]
        gain_b = take(0)
        t_b = take(1)
        use_f_b = take(2) > 0.5
        lg = take(3)
        lh = take(4)
        lc = take(5)
        return finish(gain_b, best_f, t_b, use_f_b, lg, lh, lc,
                      layout.forced_right[best_f])

    def evalB(g2, h2, sgs, shs, cnts, depths, params, layout,
              fmask, tag=None):
        """Eval dispatcher: the widened v1 f64 find in xla mode, the
        fused Mosaic scan kernels otherwise. g2/h2: the [B, TBe]
        histogram rows. ``tag`` seeds the voting winner-window
        quantization (rank-uniform, per grow stage). With categorical
        features: (matrix, [B, NW] left-set bitsets)."""
        if wide:
            mat = eval_batch_wide(g2, h2, sgs, shs, cnts, depths,
                                  params, fmask, tag)
        else:
            mat = eval_batch(g2, h2, sgs, shs, cnts, depths, params,
                             layout, tag)
        if not has_cat:
            return mat
        pc = params.cast(EV)
        return merge_cat(mat, cat_scan(g2, h2, sgs, shs, cnts, pc, fmask),
                         depths)

    # quantization-seed stage ids: root 0, level programs 1..md (+1 per
    # level), per-split tail STAGE_SPLIT0 + s — disjoint ranges so every
    # reduce of a tree draws independent rounding noise
    STAGE_SPLIT0 = LEVEL_MAX_DEPTH + 2

    def root_totals(pay, rhist):
        """The root's (sum_grad, sum_hess) [2] as the root histogram holds
        them: the sum of the first group's plane (every row lies in one
        bin of every group); in the widened mode the payload rows' own
        f64 sums, the v1 grower's. root_hist once kept running f32
        totals, one add a chunk: on the first tree every hessian is the
        same number, so every chunk adds the same number and its rounding
        into the growing total has one sign, step after step. At 31.5M
        rows that drifted sum_hess by -126 and +216 of 7.67M on two seeds
        (32 at 15.75M). The scan takes a node's left side as its total
        less the right side's bins, so a total that is not its own
        histogram's leaves the difference with the leftmost child at every
        split, undiminished: it ended as half of a 972-row leaf's hessian
        (an output of 4.5 where 2.0 was due) and as an empty leaf that
        passed min_sum_hessian_in_leaf=100 (PERF.md section 7 row 0b). A
        bin's addends differ from chunk to chunk, so the planes round
        without a sign, and totals taken from them agree with what the
        scan subtracts from them."""
        if wide:
            live = jnp.arange(NP, dtype=I32) < n
            return jnp.stack([
                jnp.sum(jnp.where(live, _f32r(pay[grad_row + r]), 0.0)
                        .astype(jnp.float64)) for r in (0, 1)])
        W0 = W * (2 if plan[0][2] > 255 else 1)   # group 0's rows
        return jnp.stack([jnp.sum(rhist[0][:W0]), jnp.sum(rhist[1][:W0])])

    def grow(pay, params: SplitParams, fmask, bag_cnt=None, it=None):
        """Grow one tree in place; returns (pay', lstate, tree, num_leaves,
        root_value, stats) where stats = [level_programs,
        fallback_splits] i32. bag_cnt: shard-local in-bag row count from
        the bag transform (None = every live row in bag). ``it`` (the
        boosting iteration, rank-uniform) seeds the quantized reduces'
        stochastic rounding; None = 0 (single-tree callers)."""
        it_q = jnp.asarray(0 if it is None else it, I32)
        layout = (None if wide else
                  (_BlockTreeLayout(fmask) if bundled
                   else ScanLayout(pad_meta, fmask, F, W, TBp)))
        rhist = root_hist(pay)
        sums = root_totals(pay, rhist)
        gh0 = to_flat(rhist[0])
        hh0 = to_flat(rhist[1])
        CT = I32 if big else ST      # dtype of a row count
        root_cnt = (jnp.asarray(n, CT) if bag_cnt is None
                    else bag_cnt.astype(CT))
        if axis_name is not None:
            # root Allreduce (data_parallel_tree_learner.cpp:120-145);
            # voting keeps the PLANES local — only scalar stats go global
            sums = jax.lax.psum(sums, axis_name)
            root_cnt = jax.lax.psum(root_cnt, axis_name)
            if not voting:
                gh0, hh0 = plane_psum("psum:hist_root", gh0, hh0,
                                      axis_name, quant,
                                      quant_tag(it_q, 0))
        sum_grad = sums[0]
        sum_hess = sums[1]
        gh0, hh0 = fix_store(gh0, hh0, sum_grad.astype(EV),
                             sum_hess.astype(EV))
        pE = params.cast(EV)
        root_out = -sum_grad.astype(EV) \
            / (sum_hess.astype(EV) + pE.lambda_l2.astype(EV))
        gh = jnp.zeros((L, TBe), EV).at[0].set(gh0)
        hh = jnp.zeros((L, TBe), EV).at[0].set(hh0)
        lstate = jnp.zeros((L, 8), ST).at[0].set(
            jnp.asarray([0, 0, 0, 0, 0, 0, 0, 0], ST)
            .at[LS_SG].set(sum_grad.astype(ST))
            .at[LS_SH].set(sum_hess.astype(ST))
            .at[LS_CNT].set(root_cnt.astype(ST))
            .at[LS_VAL].set(root_out.astype(ST))
            .at[LS_NROWS].set(jnp.asarray(n, ST)))
        pair0 = evalB(jnp.stack([gh0, gh0]), jnp.stack([hh0, hh0]),
                      jnp.stack([sum_grad, sum_grad]),
                      jnp.stack([sum_hess, sum_hess]),
                      jnp.stack([root_cnt, root_cnt]),
                      jnp.zeros((2,), F32), params, layout, fmask,
                      quant_tag(it_q, STAGE_SPLIT0 - 1))
        if has_cat:
            pair0, bits0 = pair0
        best = jnp.full((L, 12), K_MIN_SCORE, EV).at[0].set(pair0[0])
        health0 = jnp.zeros((HEALTH_LEN,), I32)
        if health:
            # root planes are the first histogram the run trusts; a NaN
            # here (poisoned gradients, a broken psum) taints every
            # split below it
            health0 = health0.at[H_INF_HIST].add(plane_health(gh0, hh0))
        # depth gate for the root itself: evalB checked depth 1
        state = _PState(
            s=jnp.asarray(1, I32),
            done=jnp.asarray(False),
            pay=pay,
            gh=gh,
            hh=hh,
            lstate=lstate,
            best=best,
            tree=jnp.zeros((L, 8), ST),
            levels=jnp.asarray(0, I32),
            health=health0,
        )
        if big:
            state = state._replace(
                lint=jnp.zeros((L, 4), I32).at[0].set(
                    jnp.zeros((4,), I32).at[LI_CNT].set(root_cnt)
                    .at[LI_NROWS].set(n)),
                tint=jnp.zeros((L,), I32))
        if has_cat:
            state = state._replace(
                bcat=jnp.zeros((L, NW), I32).at[0].set(bits0[0]),
                tcat=jnp.zeros((L, NW), I32))

        # ---- level-parallel phase: one fused program per tree level ----
        if use_level:
            arS = jnp.arange(S_MAXL, dtype=I32)

            def level_cond(st: _PState):
                """Run another batched level only while gain-ordered
                admission provably cannot be truncated by the leaf
                budget: remaining budget >= the depth-limited completion
                capacity sum((2^(md-d_i)) - 1) of the positive-gain
                frontier. With num_leaves >= 2^max_depth this holds for
                every level (pure level growth); otherwise the per-split
                tail takes over exactly where best-first admission could
                start to differ."""
                gains = st.best[:, BC_GAIN]
                alive = jnp.arange(L, dtype=I32) < st.s
                pos = alive & (gains > 0)
                cntp = jnp.sum(pos, dtype=I32)
                depth = st.lstate[:, LS_DEPTH].astype(I32)
                cap_i = jnp.left_shift(
                    jnp.asarray(1, I32),
                    jnp.clip(md - depth, 0, LEVEL_MAX_DEPTH)) - 1
                cap = jnp.sum(jnp.where(pos, cap_i, 0), dtype=I32)
                return ((~st.done) & (st.s < L) & (cntp > 0)
                        & (cntp <= S_MAXL) & ((L - st.s) >= cap))

            def level_body(st: _PState) -> _PState:
                gains = st.best[:, BC_GAIN]
                alive = jnp.arange(L, dtype=I32) < st.s
                pos = alive & (gains > 0)
                cntp = jnp.sum(pos, dtype=I32)
                # gain-ordered admission: slot j takes the j-th best
                # frontier leaf (argsort is stable, so exact ties keep
                # the smaller leaf id — the per-split argmax rule)
                key = jnp.where(pos, gains, jnp.asarray(K_MIN_SCORE, EV))
                order = jnp.argsort(-key).astype(I32)
                slots = order[:S_MAXL]                     # [S] leaf ids
                act = arS < cntp
                bl = st.best[slots]                        # [S, 12]
                lsb = st.lstate[slots]                     # [S, 8]
                lib = st.lint[slots] if big else None      # [S, 4]
                feat = jnp.maximum(bl[:, BC_FEAT].astype(I32), 0)
                s0 = icol(lsb, lib, LS_START, LI_START)
                n_l = jnp.where(act, icol(lsb, lib, LS_NROWS, LI_NROWS), 0)
                smaller_is_left = bl[:, BC_LCNT] <= bl[:, BC_RCNT]
                nch = (n_l + C - 1) // C
                scal_mat = jnp.stack([
                    nch, s0, n_l,
                    assets.dec_word[feat], assets.dec_shift[feat],
                    assets.dec_mask[feat], assets.nb[feat],
                    assets.mt[feat], assets.db[feat],
                    bl[:, BC_THR].astype(I32), bl[:, BC_DL].astype(I32),
                    smaller_is_left.astype(I32),
                    assets.ls[feat], assets.le[feat], assets.mf[feat],
                    jnp.zeros_like(n_l)], axis=1).astype(I32)  # [S, 16]
                if kernel_impl == "xla":
                    # emulation: the fused multi-leaf partition as a
                    # dynamic-trip loop of per-slot reference passes
                    # (semantically ONE level program; the Mosaic path
                    # below is literally one launch)
                    def sbody(jj, carry):
                        payc, gs, hs, cs = carry
                        pay2_, hist_, nl_ = split_pass(payc, scal_mat[jj])
                        return (pay2_, gs.at[jj].set(to_flat(hist_[0])),
                                hs.at[jj].set(to_flat(hist_[1])),
                                cs.at[jj].set(nl_))
                    pay2, sm_g, sm_h, n_lefts = jax.lax.fori_loop(
                        0, cntp, sbody,
                        (st.pay, jnp.zeros((S_MAXL, TBe), EV),
                         jnp.zeros((S_MAXL, TBe), EV),
                         jnp.zeros((S_MAXL,), I32)))
                    act_h = act & (n_l > 0)
                else:
                    steps = jnp.where(n_l > 0, nch + 2, 0)
                    ends = jnp.cumsum(steps, dtype=I32)
                    base = ends - steps
                    so = jnp.minimum(jnp.searchsorted(
                        ends, jnp.arange(T_MAXL, dtype=I32),
                        side="right").astype(I32), S_MAXL - 1)
                    pay2, _, n_lefts = level_pass(
                        st.pay, scal_mat, so, base, ends[S_MAXL - 1])
                    # zero-step slots (active leaf, empty shard-local
                    # segment) leave the kernel's count output
                    # UNDEFINED — the per-split tail's `ran` guard,
                    # mirrored here before anything is summed or psum'd
                    act_h = act & (n_l > 0)
                n_lefts = jnp.where(act_h, n_lefts, 0)
                if level_seg is not None:
                    # batched post-partition smaller-child segment
                    # histograms (one launch)
                    start_sm = jnp.where(smaller_is_left, s0,
                                         s0 + n_lefts)
                    len_sm = jnp.where(
                        act, jnp.where(smaller_is_left, n_lefts,
                                       n_l - n_lefts), 0)
                    nch_s = (len_sm + C - 1) // C
                    steps_s = jnp.where(len_sm > 0, nch_s, 0)
                    ends_s = jnp.cumsum(steps_s, dtype=I32)
                    base_s = ends_s - steps_s
                    so_s = jnp.minimum(jnp.searchsorted(
                        ends_s, jnp.arange(T_MAXL, dtype=I32),
                        side="right").astype(I32), S_MAXL - 1)
                    scal_s = jnp.stack(
                        [nch_s, start_sm, len_sm,
                         jnp.zeros_like(len_sm)], axis=1).astype(I32)
                    hist_raw = level_seg(pay2, scal_s, so_s, base_s,
                                         ends_s[S_MAXL - 1])
                    sm_g, sm_h = jax.vmap(_unpack_hist_v)(hist_raw)
                    act_h = act & (len_sm > 0)
                sm_g = jnp.where(act_h[:, None], sm_g, 0.0)
                sm_h = jnp.where(act_h[:, None], sm_h, 0.0)
                if axis_name is not None:
                    # ONE per-level histogram reduction for every
                    # splitting leaf at once — int16 codes over the wire
                    # under tpu_hist_quant (the collective batching +
                    # payload compression ROADMAP item 2 rides on)
                    ltag = quant_tag(it_q, 1 + st.levels)
                    if comm_overlap and S_MAXL >= 2:
                        # double-buffered halves: the reduce of half A
                        # is dispatched before half B's planes are
                        # touched — async collectives hide A's wire
                        # time under B's accumulate/quantize. The noise
                        # seed is the GLOBAL slot position, so staged
                        # and unstaged reduces are bit-identical.
                        H = S_MAXL // 2
                        ra_g, ra_h = plane_psum(
                            "psum:hist_level", sm_g[:H], sm_h[:H],
                            axis_name, quant, ltag, lane_offset=0)
                        rb_g, rb_h = plane_psum(
                            "psum:hist_level", sm_g[H:], sm_h[H:],
                            axis_name, quant, ltag,
                            lane_offset=H * TBe)
                        sm_g = jnp.concatenate([ra_g, rb_g])
                        sm_h = jnp.concatenate([ra_h, rb_h])
                    else:
                        sm_g, sm_h = plane_psum(
                            "psum:hist_level", sm_g, sm_h, axis_name,
                            quant, ltag)
                if stat_from_scan:
                    left_cnt = bl[:, BC_LCNT].astype(I32)
                    right_cnt = bl[:, BC_RCNT].astype(I32)
                else:
                    left_cnt = (jax.lax.psum(n_lefts, axis_name)
                                if axis_name is not None else n_lefts)
                    right_cnt = (jnp.where(
                        act, icol(lsb, lib, LS_CNT, LI_CNT), 0) - left_cnt)
                sm_sg = jnp.where(smaller_is_left, bl[:, BC_LSG],
                                  bl[:, BC_RSG])
                sm_sh = jnp.where(smaller_is_left, bl[:, BC_LSH],
                                  bl[:, BC_RSH])
                sm_g, sm_h = fix_store(sm_g, sm_h, sm_sg, sm_sh)
                hv = st.health
                if health:
                    # one split-margin per admitted split: slot j's gain
                    # minus the next-best candidate (the next admitted
                    # leaf, or 0 when nothing else would split) — the
                    # decision gap quantization noise must not collapse.
                    # key[order] is the descending gain-ordered frontier
                    # the admission itself used; masked planes are
                    # checked POST-psum so every shard counts the same
                    # global histogram.
                    svals = key[order]
                    marg = (svals[:S_MAXL]
                            - jnp.maximum(svals[1:S_MAXL + 1],
                                          jnp.asarray(0.0, EV)))
                    mb = margin_bucket_index(marg)
                    hv = hv.at[NUM_HEALTH + mb].add(act.astype(I32)) \
                           .at[H_INF_HIST].add(plane_health(sm_g, sm_h))
                par_g = st.gh[slots]
                par_h = st.hh[slots]
                big_g = par_g - sm_g
                big_h = par_h - sm_h
                sl = smaller_is_left[:, None]
                actc = act[:, None]
                left_g = jnp.where(sl, sm_g, big_g)
                left_h = jnp.where(sl, sm_h, big_h)
                right_g = jnp.where(sl, big_g, sm_g)
                right_h = jnp.where(sl, big_h, sm_h)
                vgl, vgr, vhl, vhr = jax.lax.optimization_barrier(
                    (jnp.where(actc, left_g, par_g),
                     jnp.where(actc, right_g, jnp.zeros_like(right_g)),
                     jnp.where(actc, left_h, par_h),
                     jnp.where(actc, right_h, jnp.zeros_like(right_h))))
                new_ids = jnp.where(act, st.s + arS, L)   # L -> dropped
                gh = st.gh.at[slots].set(vgl) \
                          .at[new_ids].set(vgr, mode="drop")
                hh = st.hh.at[slots].set(vhl) \
                          .at[new_ids].set(vhr, mode="drop")

                depth_child = lsb[:, LS_DEPTH] + jnp.asarray(1, ST)
                row_l = jnp.stack([
                    bl[:, BC_LSG].astype(ST), bl[:, BC_LSH].astype(ST),
                    left_cnt.astype(ST), bl[:, BC_LOUT].astype(ST),
                    depth_child, s0.astype(ST), n_lefts.astype(ST),
                    jnp.zeros_like(depth_child)], axis=1)
                row_s = jnp.stack([
                    bl[:, BC_RSG].astype(ST), bl[:, BC_RSH].astype(ST),
                    right_cnt.astype(ST), bl[:, BC_ROUT].astype(ST),
                    depth_child, (s0 + n_lefts).astype(ST),
                    (n_l - n_lefts).astype(ST),
                    jnp.zeros_like(depth_child)], axis=1)
                lstate = st.lstate.at[slots].set(
                    jnp.where(actc, row_l, lsb)) \
                    .at[new_ids].set(row_s, mode="drop")

                rec = jnp.stack([
                    slots.astype(ST), bl[:, BC_FEAT].astype(ST),
                    bl[:, BC_THR].astype(ST), bl[:, BC_DL].astype(ST),
                    bl[:, BC_GAIN].astype(ST), lsb[:, LS_VAL],
                    lsb[:, LS_CNT], jnp.zeros_like(lsb[:, LS_VAL])],
                    axis=1)
                tree_idx = jnp.where(act, st.s - 1 + arS, L)
                tree = st.tree.at[tree_idx].set(rec, mode="drop")
                ints = {}
                if big:
                    zi = jnp.zeros_like(n_lefts)
                    irow_l = jnp.stack([left_cnt, s0, n_lefts, zi], axis=1)
                    irow_s = jnp.stack([right_cnt, s0 + n_lefts,
                                        n_l - n_lefts, zi], axis=1)
                    ints = dict(
                        lint=st.lint.at[slots].set(
                            jnp.where(actc, irow_l, lib))
                        .at[new_ids].set(irow_s, mode="drop"),
                        tint=st.tint.at[tree_idx].set(
                            lib[:, LI_CNT], mode="drop"))

                # batched split-find for EVERY new child of the level
                # (an inactive slot's right row is zeros; its result is
                # dropped below)
                sgs_b = jnp.concatenate([bl[:, BC_LSG], bl[:, BC_RSG]])
                shs_b = jnp.concatenate([bl[:, BC_LSH], bl[:, BC_RSH]])
                cnts_b = jnp.concatenate([left_cnt, right_cnt])
                depths_b = jnp.concatenate([depth_child, depth_child])
                pairs = evalB(jnp.concatenate([vgl, vgr]),
                              jnp.concatenate([vhl, vhr]), sgs_b, shs_b,
                              cnts_b, depths_b, params,
                              layout, fmask,
                              quant_tag(it_q, 1 + st.levels))  # [2S, 12]
                best = st.best.at[slots].set(
                    jnp.where(actc, pairs[:S_MAXL], bl)) \
                    .at[new_ids].set(pairs[S_MAXL:], mode="drop")
                return st._replace(
                    s=st.s + cntp, pay=pay2, gh=gh, hh=hh,
                    lstate=lstate, best=best, tree=tree,
                    levels=st.levels + 1, health=hv, **ints)

            state = jax.lax.while_loop(level_cond, level_body, state)
        s_after_level = state.s

        def cond(st: _PState):
            return (~st.done) & (st.s < L)

        def body(st: _PState) -> _PState:
            gains = st.best[:, BC_GAIN]
            l = jnp.argmax(gains).astype(I32)
            do = gains[l] > 0.0
            s = st.s
            bl = st.best[l]
            ls = st.lstate[l]
            li = st.lint[l] if big else None
            f = jnp.maximum(bl[BC_FEAT].astype(I32), 0)
            smaller_is_left = bl[BC_LCNT] <= bl[BC_RCNT]
            s0 = icol(ls, li, LS_START, LI_START)
            n_l = jnp.where(do, icol(ls, li, LS_NROWS, LI_NROWS), 0)
            # one stack in S_* slot order (see pallas_grow) instead of 15
            # chained dynamic updates on the [N_SCALARS] vector
            scal = jnp.stack([
                (n_l + C - 1) // C,                  # S_NCH
                s0,                                  # S_S0
                n_l,                                 # S_NL
                assets.dec_word[f],                  # S_WG
                assets.dec_shift[f],                 # S_SH
                assets.dec_mask[f],                  # S_MASK
                assets.nb[f],                        # S_NB
                assets.mt[f],                        # S_MT
                assets.db[f],                        # S_DB
                bl[BC_THR].astype(I32),              # S_THR
                bl[BC_DL].astype(I32),               # S_DL
                smaller_is_left.astype(I32),         # S_SMALL_L
                assets.ls[f],                        # S_LS
                assets.le[f],                        # S_LE
                assets.mf[f],                        # S_MF
            ]).astype(I32)
            if has_cat:
                # S_CAT, then the left set's words from S_CW0 on
                scal = jnp.concatenate(
                    [scal, is_cat_f[f].astype(I32)[None], st.bcat[l]])
            pay, hist_sm, n_left = split_pass(st.pay, scal)
            # n_l == 0 skips the kernel (zero grid steps) and leaves its
            # histogram/count outputs undefined; mask before sums/psum
            ran = n_l > 0
            n_left = jnp.where(ran, n_left, 0)
            if seg_hist is not None:
                # post-partition smaller-child segment histogram; the
                # smaller side is chosen from GLOBAL stats (S_SMALL_L), so
                # sharded runs histogram the same child on every shard
                start_sm = jnp.where(smaller_is_left, s0, s0 + n_left)
                len_sm = jnp.where(smaller_is_left, n_left, n_l - n_left)
                sm_g, sm_h = seg_hist(pay, start_sm, len_sm)
                ran_h = len_sm > 0
            else:
                sm_g, sm_h = hist_sm
                ran_h = ran
            sm_g = jnp.where(ran_h, to_flat(sm_g), 0.0)
            sm_h = jnp.where(ran_h, to_flat(sm_h), 0.0)
            n_right = n_l - n_left
            if axis_name is not None and not voting:
                # per-split histogram reduction
                # (data_parallel_tree_learner.cpp:163-234) — int16 codes
                # over the wire under tpu_hist_quant; n_left/n_right
                # stay shard-local for the payload segment geometry.
                # Voting mode skips this: planes stay local and the eval
                # reduces only the globally voted features' windows
                sm_g, sm_h = plane_psum(
                    "psum:hist_split", sm_g, sm_h, axis_name, quant,
                    quant_tag(it_q, STAGE_SPLIT0 + s))
            if stat_from_scan:
                # bagged: geometric segment counts include out-of-bag rows;
                # the scan's hessian-derived counts are the statistics
                left_cnt = bl[BC_LCNT].astype(I32)
                right_cnt = bl[BC_RCNT].astype(I32)
            else:
                left_cnt = (jax.lax.psum(n_left, axis_name)
                            if axis_name is not None else n_left)
                right_cnt = (jnp.where(do, icol(ls, li, LS_CNT, LI_CNT), 0)
                             - left_cnt)
            sm_sg = jnp.where(smaller_is_left, bl[BC_LSG], bl[BC_RSG])
            sm_sh = jnp.where(smaller_is_left, bl[BC_LSH], bl[BC_RSH])
            sm_g, sm_h = fix_store(sm_g, sm_h, sm_sg, sm_sh)
            hv = st.health
            if health:
                # split margin = chosen gain minus the best alternative
                # on the frontier (0 when no alternative would split):
                # the decision gap the quant_certify budget bounds
                others = jnp.where(jnp.arange(L, dtype=I32) == l,
                                   jnp.asarray(K_MIN_SCORE, EV), gains)
                marg = gains[l] - jnp.maximum(jnp.max(others),
                                              jnp.asarray(0.0, EV))
                hv = hv.at[NUM_HEALTH + margin_bucket_index(marg)] \
                       .add(do.astype(I32)) \
                       .at[H_INF_HIST].add(
                           jnp.where(do, plane_health(sm_g, sm_h), 0))
            par_g = st.gh[l]
            par_h = st.hh[l]
            big_g = par_g - sm_g
            big_h = par_h - sm_h
            left_g = jnp.where(smaller_is_left, sm_g, big_g)
            left_h = jnp.where(smaller_is_left, sm_h, big_h)
            right_g = jnp.where(smaller_is_left, big_g, sm_g)
            right_h = jnp.where(smaller_is_left, big_h, sm_h)
            vgl, vgr, vhl, vhr = jax.lax.optimization_barrier(
                (jnp.where(do, left_g, par_g),
                 jnp.where(do, right_g, jnp.zeros_like(right_g)),
                 jnp.where(do, left_h, par_h),
                 jnp.where(do, right_h, jnp.zeros_like(right_h))))
            gh = st.gh.at[l].set(vgl).at[s].set(vgr)
            hh = st.hh.at[l].set(vhl).at[s].set(vhr)

            depth_child = (ls[LS_DEPTH] + 1.0).astype(ST)
            pair = evalB(
                jnp.stack([vgl, vgr]), jnp.stack([vhl, vhr]),
                jnp.stack([bl[BC_LSG], bl[BC_RSG]]),
                jnp.stack([bl[BC_LSH], bl[BC_RSH]]),
                jnp.stack([left_cnt, right_cnt]),
                jnp.stack([depth_child, depth_child]), params, layout,
                fmask, quant_tag(it_q, STAGE_SPLIT0 + s))
            cats = {}
            if has_cat:
                pair, pbits = pair
                cats = dict(
                    bcat=st.bcat.at[l].set(
                        jnp.where(do, pbits[0], st.bcat[l]))
                    .at[s].set(jnp.where(do, pbits[1], st.bcat[s])),
                    tcat=st.tcat.at[s - 1].set(
                        jnp.where(do, st.bcat[l], st.tcat[s - 1])))
            best = st.best.at[l].set(jnp.where(do, pair[0], st.best[l])) \
                          .at[s].set(jnp.where(do, pair[1], st.best[s]))

            row_l = jnp.zeros((8,), ST) \
                .at[LS_SG].set(bl[BC_LSG].astype(ST)) \
                .at[LS_SH].set(bl[BC_LSH].astype(ST)) \
                .at[LS_CNT].set(left_cnt.astype(ST)) \
                .at[LS_VAL].set(bl[BC_LOUT].astype(ST)) \
                .at[LS_DEPTH].set(depth_child) \
                .at[LS_START].set(s0.astype(ST)) \
                .at[LS_NROWS].set(n_left.astype(ST))
            row_s = jnp.zeros((8,), ST) \
                .at[LS_SG].set(bl[BC_RSG].astype(ST)) \
                .at[LS_SH].set(bl[BC_RSH].astype(ST)) \
                .at[LS_CNT].set(right_cnt.astype(ST)) \
                .at[LS_VAL].set(bl[BC_ROUT].astype(ST)) \
                .at[LS_DEPTH].set(depth_child) \
                .at[LS_START].set((s0 + n_left).astype(ST)) \
                .at[LS_NROWS].set(n_right.astype(ST))
            lstate = st.lstate.at[l].set(jnp.where(do, row_l, st.lstate[l])) \
                              .at[s].set(jnp.where(do, row_s, st.lstate[s]))

            rec = jnp.zeros((8,), ST) \
                .at[TR_LEAF].set(l.astype(ST)) \
                .at[TR_FEAT].set(bl[BC_FEAT].astype(ST)) \
                .at[TR_THR].set(bl[BC_THR].astype(ST)) \
                .at[TR_DL].set(bl[BC_DL].astype(ST)) \
                .at[TR_GAIN].set(bl[BC_GAIN].astype(ST)) \
                .at[TR_IVAL].set(ls[LS_VAL]) \
                .at[TR_ICNT].set(ls[LS_CNT])
            tree = st.tree.at[s - 1].set(
                jnp.where(do, rec, st.tree[s - 1]))
            ints = {}
            if big:
                zi = jnp.zeros((), I32)
                irow_l = jnp.stack([left_cnt, s0, n_left, zi])
                irow_s = jnp.stack([right_cnt, s0 + n_left, n_right, zi])
                ints = dict(
                    lint=st.lint.at[l].set(jnp.where(do, irow_l, li))
                    .at[s].set(jnp.where(do, irow_s, st.lint[s])),
                    tint=st.tint.at[s - 1].set(
                        jnp.where(do, li[LI_CNT], st.tint[s - 1])))
            return st._replace(
                s=s + do.astype(I32), done=~do, pay=pay,
                gh=gh, hh=hh, lstate=lstate, best=best, tree=tree,
                health=hv, **ints, **cats)

        final = jax.lax.while_loop(cond, body, state)
        # the iter-launch slot is the DRIVER's (one bump per compiled
        # program invocation, not per tree) — grow leaves it zero
        stats = jnp.concatenate(
            [jnp.stack([final.levels, final.s - s_after_level,
                        jnp.zeros((), I32)]),
             final.health])
        tree = final.tree
        if big:
            tree = (tree, final.tint)
        if has_cat:
            # the split records' left sets ride beside them, for
            # to_tree_arrays alone
            tree = (tree, final.tcat)
        if big:
            # the integer columns ride beside their matrices: the pairs
            # are what apply_scores* and to_tree_arrays take
            return (final.pay, (final.lstate, final.lint),
                    tree, final.s, root_out, stats)
        return (final.pay, final.lstate, tree, final.s, root_out,
                stats)

    def _read_score(pay, cls=0, base_row=None):
        """Class `cls` score row(s) as a float vector ([NP]): f64 word
        pairs in the widened mode (bit-compatible with the v1 f64 score
        buffer), f32 bitcast otherwise."""
        r = (score_row if base_row is None else base_row) + SR * cls
        if score64:
            return jax.lax.bitcast_convert_type(
                pay[r:r + 2].T, jnp.float64)
        return _f32r(pay[r])

    def _write_score(pay, sc, cls=0, base_row=None):
        r = (score_row if base_row is None else base_row) + SR * cls
        if score64:
            w = jax.lax.bitcast_convert_type(
                sc.astype(jnp.float64), U32).T           # [2, NP]
        else:
            w = jax.lax.bitcast_convert_type(sc.astype(F32), U32)[None]
        return jax.lax.dynamic_update_slice(
            pay, w, (jnp.asarray(r, I32), jnp.asarray(0, I32)))

    def to_tree_arrays(lstate, tree, num_leaves) -> TreeArrays:
        """The host-facing TreeArrays pytree (models.tree.Tree input).
        The widened mode hands f64 leaf values/gains through (v1 f64
        parity); the Mosaic fast path stays f32 (gpu_use_dp=false)."""
        ft = jnp.float64 if wide else F32
        if has_cat:
            tree, tcat = tree
        if big:
            (lstate, lint), (tree, tint) = lstate, tree

        def split_feature():
            return jnp.where(
                jnp.arange(L - 1, dtype=I32) < num_leaves - 1,
                tree[:L - 1, TR_FEAT].astype(I32), -1)

        def split_is_cat():
            sf = split_feature()
            return is_cat_f[jnp.maximum(sf, 0)] & (sf >= 0)

        def split_cat_mask():
            bits = (tcat[:L - 1, :, None]
                    >> jnp.arange(32, dtype=I32)) & 1     # [L-1, NW, 32]
            return ((bits.reshape(L - 1, NW * 32)[:, :CW] == 1)
                    & split_is_cat()[:, None])
        return TreeArrays(
            num_leaves=num_leaves,
            split_leaf=tree[:L - 1, TR_LEAF].astype(I32),
            split_feature=split_feature(),
            threshold=tree[:L - 1, TR_THR].astype(I32),
            default_left=tree[:L - 1, TR_DL] > 0.5,
            gain=tree[:L - 1, TR_GAIN].astype(ft),
            is_cat=(split_is_cat() if has_cat
                    else jnp.zeros((L - 1,), BOOL)),
            cat_mask=(split_cat_mask() if has_cat
                      else jnp.zeros((L - 1, gc.cat_width), BOOL)),
            internal_value=tree[:L - 1, TR_IVAL].astype(ft),
            internal_count=(tint[:L - 1] if big else
                            tree[:L - 1, TR_ICNT].astype(I32)),
            leaf_value=lstate[:, LS_VAL].astype(ft),
            leaf_count=(lint[:, LI_CNT] if big else
                        lstate[:, LS_CNT].astype(I32)),
            leaf_weight=lstate[:, LS_SH].astype(ft),
            row_leaf=jnp.zeros((0,), I32),
        )

    def _int_segments(lint, num_leaves):
        """large_counts: (segment starts [L] i32 with NP where the slot
        holds no live segment, so that they sort last; live mask)."""
        live = ((lint[:, LI_NROWS] > 0)
                & (jnp.arange(L, dtype=I32) < num_leaves))
        return jnp.where(live, lint[:, LI_START], NP), live

    def apply_scores(pay, lstate, num_leaves, shrink, cls=0, exact=False):
        """score-row of class `cls` += shrink * leaf_value[leaf_of_position]
        via segment deltas: leaves partition positions into contiguous
        runs. The widened mode gathers the per-leaf f64 product directly
        (leaf of a position by searchsorted over live segment starts) so
        each row's update is the same leaf_value * shrink product — and
        the same single f64 add — as the v1 score updater.

        ``exact`` (f32 scores): each row adds its leaf's output bit for
        bit as the model text holds it, the host's f64 Shrinkage product
        rounded once to f32, carried down the segments as int32 bits (a
        telescoping sum of f32 deltas rounds each row's update by a few
        ulps). The ranking fill orders a query's rows by score, and a
        score an ulp off orders a near-tie otherwise than a walk of the
        model does."""
        if big:
            lstate, lint = lstate
        else:
            starts = lstate[:, LS_START]
            nrows = lstate[:, LS_NROWS]
            live = (nrows > 0) & (jnp.arange(L, dtype=I32) < num_leaves)
        if score64:
            vals = lstate[:, LS_VAL] * shrink.astype(ST)
            key = jnp.where(live, starts, jnp.inf)
            order = jnp.argsort(key)
            # searchsorted needs the MASKED starts: dead slots carry raw
            # start 0 and would break monotonicity at the tail, silently
            # mapping the last segments onto a dead slot whenever a tree
            # finishes under the leaf budget
            sstart = key[order]
            svals = vals[order]
            slive = live[order]
            pos = jnp.arange(NP, dtype=I32).astype(ST)
            idx = jnp.clip(jnp.searchsorted(sstart, pos, side="right")
                           - 1, 0, L - 1)
            upd = jnp.where(slive[idx], svals[idx], 0.0)
            sc = _read_score(pay, cls)
            sc = sc + jnp.where(num_leaves > 1, upd, 0.0)
            return _write_score(pay, sc, cls)
        if exact:
            vals = jax.lax.bitcast_convert_type(
                (lstate[:, LS_VAL].astype(jnp.float64) * shrink)
                .astype(F32), I32)
        else:
            vals = (lstate[:, LS_VAL] * shrink.astype(ST)).astype(F32)
        if big:
            starts, live = _int_segments(lint, num_leaves)
            key = starts
        else:
            key = jnp.where(live, starts, jnp.inf)
        order = jnp.argsort(key)
        sv = vals[order]
        live_o = live[order]
        prev = jnp.concatenate([jnp.zeros((1,), sv.dtype), sv[:-1]])
        # int32 differences and sums wrap, and telescope back exactly
        delta = jnp.where(live_o, sv - prev, jnp.zeros((), sv.dtype))
        pos = jnp.where(live_o, starts[order].astype(I32), NP)
        upd = jnp.zeros((NP,), sv.dtype).at[pos].add(delta, mode="drop")
        cum = jnp.cumsum(upd)
        if exact:
            cum = jax.lax.bitcast_convert_type(cum, F32)
        sc = _read_score(pay, cls)
        sc = sc + jnp.where(num_leaves > 1, cum, 0.0)
        return _write_score(pay, sc, cls)

    def apply_scores_avg(pay, lstate, num_leaves, t, inv, bias, cls=0):
        """RF running-average score update (rf.hpp:103-160) fused into
        the scan: the host sequence is score *= t; score +=
        (leaf_value + bias)[leaf_of_position]; score *= 1/(t+1), with
        `bias` (the constant init score) folded into the gathered leaf
        value exactly as the host's tree.add_bias mutates the tree
        BEFORE its leaf gather — one f64 add, then the same two
        multiplies and one add per row as the three ScoreUpdater
        dispatches it replaces. 1-leaf trees leave the average
        untouched (the reference appends a stub and keeps going)."""
        if big:
            lstate, lint = lstate
            key, live = _int_segments(lint, num_leaves)
            pos = jnp.arange(NP, dtype=I32)
        else:
            starts = lstate[:, LS_START]
            nrows = lstate[:, LS_NROWS]
            live = (nrows > 0) & (jnp.arange(L, dtype=I32) < num_leaves)
            key = jnp.where(live, starts, jnp.inf)
            pos = jnp.arange(NP, dtype=I32).astype(ST)
        vals = lstate[:, LS_VAL]
        # host add_bias only fires for |init| > eps; skip the +0.0 too
        # so a -0.0 leaf keeps its sign exactly like the host path
        vals = jnp.where(bias != 0.0, vals + bias.astype(ST), vals)
        order = jnp.argsort(key)
        sstart = key[order]
        svals = vals[order]
        slive = live[order]
        idx = jnp.clip(jnp.searchsorted(sstart, pos, side="right") - 1,
                       0, L - 1)
        upd = jnp.where(slive[idx], svals[idx], 0.0)
        sc = _read_score(pay, cls)
        sc2 = ((sc * t.astype(sc.dtype) + upd.astype(sc.dtype))
               * inv.astype(sc.dtype))
        sc = jnp.where(num_leaves > 1, sc2, sc)
        return _write_score(pay, sc, cls)

    def _rid_pos(pay):
        """(shard-local row id, live mask) for row-order <-> payload-order
        gathers; dead lanes carry the total-row sentinel."""
        rid = pay[nbw + 1].astype(I32)
        if axis_name is not None:
            rid = rid - jax.lax.axis_index(axis_name).astype(I32) * n
        live = jnp.arange(NP, dtype=I32) < n
        return jnp.minimum(rid, n - 1), live

    def add_score_delta(pay, delta_row, cls=0):
        """Class `cls` score row += a host-computed ROW-ordered delta
        ([n], f64), gathered through the rid row — ONE add per row in
        the payload score dtype, the exact ScoreUpdater.add_score_np
        contract, so DART's drop/normalize deltas land bit-identically
        on the payload carry (widened mode) instead of forcing the
        scores off-device between trees."""
        idx, live = _rid_pos(pay)
        sc = _read_score(pay, cls)
        d = jnp.where(live, delta_row.astype(sc.dtype)[idx], 0.0)
        return _write_score(pay, sc + d, cls)

    def apply_row_weights(pay, w_row):
        """Multiply the payload grad/hess rows by a host-computed
        per-row weight vector in ROW order ([n] f32; RF's host-RNG bag
        masks, per-iteration mode weights), gathered through the rid
        row. Returns (pay', in-bag count) — the same contract as the
        device bag transforms (make_bag_transform), so the grow call
        wires identically. f32(g) * m equals f32(g * m) for the 0/1
        masks this carries, keeping host-path bit parity."""
        idx, live = _rid_pos(pay)
        w = jnp.where(live, w_row.astype(F32)[idx], 0.0)
        g = _f32r(pay[grad_row]) * w
        h = _f32r(pay[grad_row + 1]) * w
        gh = jax.lax.bitcast_convert_type(jnp.stack([g, h]), U32)
        pay = jax.lax.dynamic_update_slice(
            pay, gh, (jnp.asarray(grad_row, I32), jnp.asarray(0, I32)))
        return pay, jnp.sum((w > 0).astype(F32))

    def _write_grads(pay, g, h):
        live = jnp.arange(NP, dtype=I32) < n
        g = jnp.where(live, g.astype(F32), 0.0)
        h = jnp.where(live, h.astype(F32), 0.0)
        gh = jax.lax.bitcast_convert_type(jnp.stack([g, h]), U32)
        return jax.lax.dynamic_update_slice(
            pay, gh, (jnp.asarray(grad_row, I32), jnp.asarray(0, I32)))

    def wire_bytes_model(levels: int, splits: int, trees: int):
        """(actual, fullwidth) estimated per-shard payload bytes for the
        histogram exchanges of a batch: ``trees`` trees that ran
        ``levels`` level programs and ``splits`` per-split reduces.

        The model mirrors the plane_psum/vote_allgather call sites
        exactly — data-parallel ships one (g, h) plane pair per root and
        per split plus an [S_MAXL, TBe] pair batch per level program;
        voting ships a [K_TOP] index allgather plus a compact
        [2, N_WIN, W] winner-window pair per eval (root + every split).
        ``fullwidth`` is what the historical full-width data-parallel
        exchange would ship for the same tree geometry — the
        denominator of ``hist_compress_ratio``. Reduction-algorithm
        constant factors (ring vs tree) are identical on both sides and
        cancel in the ratio."""
        if axis_name is None:
            return 0, 0
        bpe_full = 8 if wide else 4
        bpe = (quant.wire_bytes_per_value if quant is not None
               else bpe_full)
        full = (trees + splits) * 2 * TBe * bpe_full
        if voting:
            evals = trees + splits               # one B=2 eval each
            vote_b = 2 * K_TOP * 4               # top-k index allgather
            win_elems = 2 * 2 * N_WIN * (W_scan if wide else W)
            actual = evals * (vote_b + win_elems * bpe)
        else:
            elems = ((trees + splits) + levels * S_MAXL) * 2 * TBe
            actual = elems * bpe
            full = full + levels * S_MAXL * 2 * TBe * bpe_full
        return int(actual), int(full)

    def grad_health(pay):
        """[2] i32 non-finite counts over the live (grad, hess) payload
        rows — the ``numerics::nan_grad``/``nan_hess`` device probe the
        scan driver folds into the stats vector right after each
        gradient fill. Shard-LOCAL counts (each shard owns different
        rows); the driver psums the pair once per batch when sharded so
        the replicated stats output stays replicated."""
        live = jnp.arange(NP, dtype=I32) < n
        g = _f32r(pay[grad_row])
        h = _f32r(pay[grad_row + 1])
        return jnp.stack([
            jnp.sum(live & ~jnp.isfinite(g), dtype=I32),
            jnp.sum(live & ~jnp.isfinite(h), dtype=I32)])

    def _apply_weight(g, h, pay):
        """Per-row weight multiply AFTER the objective's unweighted
        gradients — the reference objectives' uniform weighted form
        (e.g. binary_objective.hpp GetGradients: response * weight)."""
        if not has_w:
            return g, h
        w = _f32r(pay[weight_row])
        return g * w, h * w

    def _read_scores_block(pay, base_row):
        """[K, NP] float view of a score/snapshot block."""
        if score64:
            return jax.lax.bitcast_convert_type(
                pay[base_row:base_row + 2 * K].reshape(K, 2, NP)
                .transpose(0, 2, 1), jnp.float64)
        return jax.lax.bitcast_convert_type(
            pay[base_row:base_row + K], F32)

    def fill_grad(pay, payload_grad_fn):
        label = jax.lax.bitcast_convert_type(pay[nbw], F32)
        # widened mode hands the f64 score through: dtype-following
        # objectives then compute f64 gradients and _write_grads rounds
        # once to f32 — the exact v1 gradient pipeline
        score = _read_score(pay)
        g, h = payload_grad_fn(score, label)
        g, h = _apply_weight(g, h, pay)
        return _write_grads(pay, g, h)

    def snapshot_scores(pay):
        """Copy the live score rows into the snapshot block (iteration
        start): all K class gradients read pre-iteration scores."""
        return jax.lax.dynamic_update_slice(
            pay, pay[score_row:score_row + SR * K],
            (jnp.asarray(snap_row, I32), jnp.asarray(0, I32)))

    def fill_grad_multi(pay, payload_grad_fn_multi, cls):
        """Class `cls` gradients from the snapshot score block."""
        label = jax.lax.bitcast_convert_type(pay[nbw], F32)
        scores = _read_scores_block(pay, snap_row)      # [K, NP]
        g, h = payload_grad_fn_multi(scores, label, cls)
        g, h = _apply_weight(g, h, pay)
        return _write_grads(pay, g, h)

    def fill_grad_const(pay, payload_grad_fn, c):
        """RF gradient fill: the reference computes gradients ONCE from
        the constant init score (rf.hpp:81-101), never from the running
        average the score rows hold — broadcast the traced scalar as
        the score vector and run the objective's device kernel on it,
        leaving the live payload scores untouched. Elementwise in
        (score, label), so payload order reproduces the host's
        row-order gradients bit for bit."""
        label = jax.lax.bitcast_convert_type(pay[nbw], F32)
        score = jnp.full((NP,), c, dtype=SDT)
        g, h = payload_grad_fn(score, label)
        g, h = _apply_weight(g, h, pay)
        return _write_grads(pay, g, h)

    def finalize_scores(pay):
        """Payload-order scores -> row order (one scatter per batch);
        [n] for one class, [K, n] for multiclass. Row ids are global;
        sharded runs subtract the shard offset (dead lanes carry the
        total-row sentinel and always land out of range)."""
        rid = pay[nbw + 1].astype(I32)
        if axis_name is not None:
            rid = rid - jax.lax.axis_index(axis_name).astype(I32) * n
        if K == 1:
            score = _read_score(pay)
            return jnp.zeros((n,), score.dtype).at[rid].set(
                score, mode="drop", unique_indices=True)
        scores = _read_scores_block(pay, score_row)
        return jnp.zeros((K, n), scores.dtype).at[:, rid].set(
            scores, mode="drop", unique_indices=True)

    def fill_grad_pos(pay, pos_grad_fn, gargs):
        """Payload-position gradient mode: the objective computes (g, h)
        directly in PAYLOAD order from (score, rid, live) — lambdarank
        sorts the scores into its padded query slots by row id and sorts
        the lambdas straight back by lane, skipping the row-order round
        trip of fill_grad_row. The live rows are lanes 0..n-1."""
        rid = pay[nbw + 1].astype(I32)
        score = _read_score(pay)
        live = jnp.arange(NP, dtype=I32) < n
        # pos-mode fns own their weighting (they get the weights through
        # gargs in whatever layout suits them — lambdarank multiplies the
        # padded plane BEFORE its f32 cast, matching the row-order path
        # bit for bit); the payload weight row is NOT applied here
        g, h = pos_grad_fn(score, rid, live, *gargs)
        return _write_grads(pay, g, h)

    def fill_grad_row(pay, grad_fn, gargs):
        """Row-order gradient mode for objectives whose gradients need
        global row structure (lambdarank's query groups, xentropy weights):
        scores scatter to row order, the objective's own grad_fn runs
        there, and the results gather back through the rid row. Costs one
        [n] scatter + one [NP] gather per tree — still payload-resident
        everywhere else."""
        score_rowo = finalize_scores(pay).astype(jnp.float64)
        g, h = grad_fn(score_rowo, *gargs)
        rid = pay[nbw + 1].astype(I32)
        live = jnp.arange(NP, dtype=I32) < n
        idx = jnp.minimum(rid, n - 1)
        g = jnp.where(live, g.astype(F32)[idx], 0.0)
        h = jnp.where(live, h.astype(F32)[idx], 0.0)
        gh = jax.lax.bitcast_convert_type(jnp.stack([g, h]), U32)
        return jax.lax.dynamic_update_slice(
            pay, gh, (jnp.asarray(grad_row, I32), jnp.asarray(0, I32)))

    SDT = jnp.float64 if score64 else F32   # payload score value dtype

    def set_scores(pay, score_pos):
        """Write payload-order score rows ([NP] or [K, NP])."""
        sc = score_pos.astype(SDT)
        if sc.ndim == 1:
            sc = sc[None, :]
        if score64:
            w = jax.lax.bitcast_convert_type(sc, U32) \
                .transpose(0, 2, 1).reshape(SR * K, NP)
        else:
            w = jax.lax.bitcast_convert_type(sc, U32)
        return jax.lax.dynamic_update_slice(
            pay, w, (jnp.asarray(score_row, I32), jnp.asarray(0, I32)))

    @jax.jit
    def init_carry(pay, score0_row):
        """Fresh carry from the pristine payload + a row-ordered score
        vector ([n] or [K, n], any float dtype). One fused device program
        instead of an eager op chain (one dispatch, no [K, NP]
        intermediates on the host's clock)."""
        s0 = score0_row.astype(SDT).reshape(K, n)
        sc = jnp.zeros((K, NP), SDT).at[:, :n].set(s0)
        return set_scores(pay, sc)

    class _Grower:
        pass

    gr = _Grower()
    gr.grow = grow
    gr.to_tree_arrays = to_tree_arrays
    gr.apply_scores = apply_scores
    gr.fill_grad = fill_grad
    gr.fill_grad_pos = fill_grad_pos
    gr.fill_grad_row = fill_grad_row
    gr.fill_grad_multi = fill_grad_multi
    gr.fill_grad_const = fill_grad_const
    gr.apply_scores_avg = apply_scores_avg
    gr.apply_row_weights = apply_row_weights
    gr.add_score_delta = add_score_delta
    gr.snapshot_scores = snapshot_scores
    gr.finalize_scores = finalize_scores
    gr.set_scores = set_scores
    gr.init_carry = init_carry
    gr.NP = NP
    gr.n = n
    gr.nbw = nbw
    gr.K = K
    gr.score64 = score64
    gr.wide = wide
    # which mechanisms this grower's splits go through (the learner
    # counts trees by them: blockscan_trees, inpass_hist_trees)
    gr.block_scan = bool(bundled and not wide)
    gr.inpass_hist = seg_hist is None
    # past 56 payload words the chunk follows from the row's width
    # (_payload_geometry): wide_payload_trees
    gr.wide_payload = WPA > 56
    # row counts at or past 2^24 ride i32 beside the f32 state:
    # large_count_trees
    gr.large_counts = big
    # categorical splits in this grower's eval: cat_persist_trees
    gr.has_cat = has_cat
    gr.use_level = use_level
    gr.S_MAXL = S_MAXL
    gr.health = health
    gr.axis_name = axis_name
    gr.voting = voting
    gr.quant = quant
    gr.comm_overlap = bool(comm_overlap)
    gr.wire_bytes_model = wire_bytes_model
    gr.reduced_feature_frac = (N_WIN / max(F, 1) if voting else 1.0)
    gr.grad_health = grad_health
    gr._root_hist = root_hist
    gr._root_totals = root_totals
    gr._pad_meta = pad_meta
    # the kernels as built for this geometry (tests/test_chip_compile.py
    # compiles exactly these for the described chip); None where the
    # geometry or mode does not use one
    gr._split_pass = split_pass
    gr._seg_hist = seg_hist
    gr._level_pass = level_pass
    gr._level_seg = level_seg
    gr.T_MAXL = T_MAXL
    return gr


def make_scan_driver(gr, gc, k: int, grad_fn, grad_mode: str = "payload",
                     wrap_jit: bool = True, bag_fn=None,
                     mode: str = "gbdt"):
    """K fused boosting iterations over the persistent payload.

    grad_fn is baked statically; grad_mode selects its contract:
    'payload' takes (score_pos, label_pos); 'pos' takes
    (score_pos, rid, live, *gargs) all in payload order (lambdarank's
    scatter-through-rid mode); 'row' takes (score_row, *gargs) — the
    objective's standard grad function fed by a per-tree scatter/gather
    through the rid row. Returns fn(pay, fmasks [k, F], wkeys [k, 2]u32,
    iters [k]i32, params, shrink, gargs) -> (pay', stacked TreeArrays,
    stats [STATS_LEN] i32 = summed [level_programs,
    level_fallback_splits, iter_launches] + the numerics health vector
    (NaN/Inf counts + split-margin buckets, telemetry/health layout)
    over the batch — the learner converts them to telemetry
    counters/histograms at finalize time, keeping the dispatch fully
    async).

    bag_fn: optional make_bag_transform closure run between the gradient
    fill and the grow (bagging masks / GOSS weights applied to the payload
    grad rows; its in-bag count feeds the root statistics).

    mode='rf' compiles the random-forest iteration instead: gradients
    from the constant init score (fill_grad_const), host-RNG bag masks
    applied as traced per-iteration [n] weight vectors, and the
    running-average score dance (apply_scores_avg) riding the scan —
    signature run(pay, fmasks [k, F], bagw [k, n] f32, aux [k, 2] f64
    = (total_iter, 1/(total_iter+1)), iters [k]i32, params, bias) with
    `bias` the objective's constant init score. Serial-learner only
    (the booster gates it).

    wrap_jit=False returns the untraced body for callers that wrap it
    themselves (the sharded learner puts it under shard_map and jits with
    payload donation outside).
    """

    K = getattr(gr, "K", 1)
    use_health = bool(getattr(gr, "health", True))

    def _add_grad_health(stats, pay):
        """Fold the post-fill gradient probe into the stats vector
        (non-finite grad/hess counts — numerics::nan_grad/nan_hess)."""
        if not use_health:
            return stats
        gh2 = gr.grad_health(pay)
        return stats.at[STAT_HEALTH0 + H_NAN_GRAD].add(gh2[0]) \
                    .at[STAT_HEALTH0 + H_NAN_HESS].add(gh2[1])

    def run_rf(pay, fmasks, bagw, aux, iters, params, bias):
        def body(pay, per):
            fmask, w_row, ax, it = per
            with jax.named_scope("fill_grad"):
                pay = gr.fill_grad_const(pay, grad_fn, bias)
            gh2 = gr.grad_health(pay) if use_health else None
            with jax.named_scope("bag_transform"):
                pay, bag_cnt = gr.apply_row_weights(pay, w_row)
            with jax.named_scope("grow"):
                pay, lstate, tree, nl, _root, stats = gr.grow(
                    pay, params, fmask, bag_cnt=bag_cnt, it=it)
            if gh2 is not None:
                stats = stats.at[STAT_HEALTH0 + H_NAN_GRAD].add(gh2[0]) \
                             .at[STAT_HEALTH0 + H_NAN_HESS].add(gh2[1])
            with jax.named_scope("apply_scores"):
                pay = gr.apply_scores_avg(pay, lstate, nl, ax[0], ax[1],
                                          bias)
            with jax.named_scope("to_tree_arrays"):
                out = gr.to_tree_arrays(lstate, tree, nl)
            return pay, (out, stats)
        payK, (stacked, stats_k) = jax.lax.scan(
            body, pay, (fmasks, bagw, aux, iters), length=k)
        stats = jnp.sum(stats_k, axis=0).at[STAT_ITER_LAUNCH].add(1)
        return payK, stacked, stats

    if mode == "rf":
        if wrap_jit:
            return telemetry.launch_wrapper(
                jax.jit(run_rf, donate_argnums=(0,)),
                "ops::persist_scan(launch)", category="ops",
                histogram="ops::persist_program_wall", always=True, k=k)
        return run_rf

    def run(pay, fmasks, wkeys, iters, params, shrink, gargs):
        def body(pay, per):
            fmask, wkey, it = per
            if K > 1:
                # one iteration = K class trees from one score snapshot
                # (GBDT::TrainOneIter, gbdt.cpp:338-420: gradients for
                # every class come from the pre-iteration scores)
                pay = gr.snapshot_scores(pay)
                outs = []
                stats = jnp.zeros((STATS_LEN,), jnp.int32)
                for cls in range(K):
                    with jax.named_scope("fill_grad"):
                        pay = gr.fill_grad_multi(pay, grad_fn, cls)
                    stats = _add_grad_health(stats, pay)
                    bag_cnt = None
                    if bag_fn is not None:
                        # same window key for every class: one bag per
                        # iteration, as in the reference
                        with jax.named_scope("bag_transform"):
                            pay, bag_cnt = bag_fn(pay, wkey, it)
                    with jax.named_scope("grow"):
                        pay, lstate, tree, nl, _root, tstats = gr.grow(
                            pay, params, fmask[cls], bag_cnt=bag_cnt,
                            it=it * K + cls)
                    stats = stats + tstats
                    with jax.named_scope("apply_scores"):
                        pay = gr.apply_scores(pay, lstate, nl, shrink, cls)
                    with jax.named_scope("to_tree_arrays"):
                        outs.append(gr.to_tree_arrays(lstate, tree, nl))
                out = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
                return pay, (out, stats)
            # device-side names for the trace and the HLO dump (metadata
            # only: the program is the same)
            with jax.named_scope("fill_grad"):
                if grad_mode == "pos":
                    pay = gr.fill_grad_pos(pay, grad_fn, gargs)
                elif grad_mode == "row":
                    pay = gr.fill_grad_row(pay, grad_fn, gargs)
                else:
                    pay = gr.fill_grad(pay, grad_fn)
            # probe the objective's RAW gradients (pre-bag: a bag zero
            # cannot launder an Inf into an unremarkable 0, and NaN*0
            # is NaN anyway)
            gh2 = gr.grad_health(pay) if use_health else None
            bag_cnt = None
            if bag_fn is not None:
                with jax.named_scope("bag_transform"):
                    pay, bag_cnt = bag_fn(pay, wkey, it)
            with jax.named_scope("grow"):
                pay, lstate, tree, nl, _root, stats = gr.grow(
                    pay, params, fmask, bag_cnt=bag_cnt, it=it)
            if gh2 is not None:
                stats = stats.at[STAT_HEALTH0 + H_NAN_GRAD].add(gh2[0]) \
                             .at[STAT_HEALTH0 + H_NAN_HESS].add(gh2[1])
            with jax.named_scope("apply_scores"):
                pay = gr.apply_scores(pay, lstate, nl, shrink,
                                      exact=grad_mode == "pos")
            with jax.named_scope("to_tree_arrays"):
                out = gr.to_tree_arrays(lstate, tree, nl)
            return pay, (out, stats)
        payK, (stacked, stats_k) = jax.lax.scan(
            body, pay, (fmasks, wkeys, iters), length=k)
        if K > 1:
            # [k, K, ...] -> [k*K, ...]: trees in (iteration, class) order,
            # the model list layout the booster materializes
            stacked = jax.tree.map(
                lambda a: a.reshape((a.shape[0] * a.shape[1],)
                                    + a.shape[2:]), stacked)
        stats = jnp.sum(stats_k, axis=0).at[STAT_ITER_LAUNCH].add(1)
        if use_health and getattr(gr, "axis_name", None) is not None:
            # the gradient probe counted shard-LOCAL rows; one tiny psum
            # per BATCH keeps the replicated stats output replicated.
            # Data-parallel margins/inf_hist derive from post-psum
            # global planes and are already identical on every shard —
            # but VOTING keeps its histogram planes shard-local, so
            # there the inf_hist slot is local too and must ride the
            # same psum (an Inf on one shard's plane would otherwise be
            # silently dropped by the replicated out-spec). The
            # iter-launch slot stays OUT of the psum: every shard bumps
            # it identically, so it is already replicated
            hi = (STAT_HEALTH0 + NUM_HEALTH
                  if getattr(gr, "voting", False)
                  else STAT_HEALTH0 + H_INF_HIST)
            part = jax.lax.psum(stats[STAT_HEALTH0:hi], gr.axis_name)
            stats = stats.at[STAT_HEALTH0:hi].set(part)
        return payK, stacked, stats

    if wrap_jit:
        # histogram= streams each program invocation's host wall into
        # the log-bucketed registry: one sample per compiled k-iteration
        # program (the level phase fuses every tree level into it), so
        # the launch-cost DISTRIBUTION across the run is queryable —
        # p99 outliers here are recompiles/host stalls the scalar
        # total would average away
        return telemetry.launch_wrapper(
            jax.jit(run, donate_argnums=(0,)),
            "ops::persist_scan(launch)", category="ops",
            histogram="ops::persist_program_wall", always=True, k=k)
    return run
