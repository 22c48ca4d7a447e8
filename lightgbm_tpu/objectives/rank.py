"""Learning-to-rank objectives: LambdaRank-NDCG and XE-NDCG.

TPU-native rebuild of src/objective/rank_objective.hpp. The reference
parallelizes over queries with OpenMP and walks O(n^2) document pairs per
query (LambdarankNDCG::GetGradientsForOneQuery, rank_objective.hpp:139-232);
here queries are packed into a padded [num_queries, max_len] layout and the
pair loop becomes a vmapped [P, P] pairwise tensor computation, chunked with
lax.map to bound memory. XE-NDCG (rank_objective.hpp:288-352) is O(n) per
query and is expressed with segment sums over the flat row axis — no padding.

Deliberate deviation from the reference (documented for the parity tests):
the 1M-entry sigmoid lookup table (:237-257) is replaced by exact sigmoid
evaluation — on TPU computing exp is cheaper than a 1M-gather, and it is
strictly more accurate. XE-NDCG's per-query Random stream (:305-312) is
reproduced BIT-EXACTLY: the host advances the reference's LCG per query
(RankXENDCG._next_floats) and ships each iteration's draws to the jitted
gradient function, so the golden parity suite matches the reference's
stochastic gradients too.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..metrics.dcg import (cal_max_dcg_at_k, check_label, default_label_gain)
from ..telemetry import events as telemetry
from ..utils.log import Log
from .base import K_EPSILON, ObjectiveFunction, register


def _pack_queries(query_boundaries: np.ndarray):
    """[nq+1] boundaries -> (row_index [Q, P] padded with -1, valid [Q, P])."""
    nq = len(query_boundaries) - 1
    counts = np.diff(query_boundaries)
    P = int(counts.max()) if nq else 1
    idx = np.full((nq, P), -1, dtype=np.int32)
    for q in range(nq):
        c = counts[q]
        idx[q, :c] = np.arange(query_boundaries[q], query_boundaries[q + 1],
                               dtype=np.int32)
    return idx, (idx >= 0)


class RankingObjective(ObjectiveFunction):
    """Base: per-query gradient computation (rank_objective.hpp:25-94)."""

    def __init__(self, config):
        super().__init__(config)
        self.seed = int(config.objective_seed)
        self.query_boundaries = None
        self.num_queries = 0

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            Log.fatal("Ranking tasks require query information")
        self.query_boundaries = metadata.query_boundaries
        self.num_queries = metadata.num_queries


@register
class LambdarankNDCG(RankingObjective):
    name = "lambdarank"

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        self.norm = bool(config.lambdarank_norm)
        self.truncation_level = int(config.lambdarank_truncation_level)
        lg = list(config.label_gain)
        self.label_gain = (np.asarray(lg, dtype=np.float64) if lg
                           else default_label_gain())
        if self.sigmoid <= 0.0:
            Log.fatal("Sigmoid param %f should be greater than zero"
                      % self.sigmoid)
        self._chunk = 0     # queries per lax.map step; 0 = size by memory

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        check_label(self.label, len(self.label_gain))
        inv = np.zeros(self.num_queries)
        qb = self.query_boundaries
        for q in range(self.num_queries):
            m = cal_max_dcg_at_k(self.truncation_level,
                                 self.label[qb[q]:qb[q + 1]], self.label_gain)
            inv[q] = 1.0 / m if m > 0.0 else 0.0
        self.inverse_max_dcgs = inv
        self._qidx, self._qvalid = _pack_queries(qb)
        # row -> padded position (q*P + offset): the row-order grad_fn
        # returns the padded [Q, P] lambdas to row order with one gather
        # (queries are contiguous row ranges so this map is static)
        P = self._qidx.shape[1]
        counts = np.diff(qb)
        qid = np.repeat(np.arange(self.num_queries, dtype=np.int64), counts)
        self._inv_pos = (qid * P + (np.arange(self.num_data, dtype=np.int64)
                                    - qb[qid])).astype(np.int32)
        # the payload fill's sort keys of the padding: row r sorts at 2r,
        # and the P - count slots of query q at 2 * qb[q+1] - 1, right
        # after its last row (none when every query is P long)
        self._fill_key = np.repeat(2 * qb[1:].astype(np.int64) - 1,
                                   P - counts).astype(np.int32)
        # padded per-slot statics for the payload-position gradient mode:
        # labels never change, so the [Q, P] label/gain/weight planes are
        # computed once and only SCORES move per iteration
        safe = np.maximum(self._qidx, 0)
        self._lab_pad = np.where(self._qvalid, self.label[safe], 0.0) \
            .astype(np.float32)
        self._gains_pad = self.label_gain[self._lab_pad.astype(np.int64)] \
            .astype(np.float64)
        self._w_pad = (np.where(self._qvalid, self.weight[safe], 0.0)
                       .astype(np.float32)
                       if self.weight is not None else None)
        if self._chunk <= 0:
            # budget the [chunk, P, P] pairwise intermediates to ~256MB:
            # tiny chunks turn lax.map into hundreds of sequential
            # dispatch-bound steps (a 256-query chunk at P=73 is 5MB of
            # work per step — measured 10x slower than 2 big steps)
            P = max(int(self._qidx.shape[1]), 1)
            self._chunk = max(256, min(self.num_queries,
                                       (256 << 20) // (P * P * 4)))

    def _pairwise_flat(self):
        """Shared pairwise core: fn(s_q [Q, P], l_q, qvalid, inv_max_dcgs,
        gains_q, discounts) -> (lam_flat, hess_flat) over the padded slots
        (chunk-padded queries appended at the end; callers index by padded
        position, which never reaches the pad)."""
        sigmoid = self.sigmoid
        norm = self.norm
        chunk = self._chunk
        # f64 on TPU is emulated op-by-op; the pairwise tensors dominate
        # this objective, so compute them in f32 on accelerators (the
        # reference itself trades exactness here with its 1M-entry sigmoid
        # table, rank_objective.hpp:237-257). CPU keeps f64 for the
        # reference-parity suite.
        import jax as _jax
        ct = (jnp.float64 if _jax.default_backend() == "cpu"
              else jnp.float32)

        def one_query(scores_q, labels_q, valid_q, inv_max_dcg, gains_q,
                      disc_from_rank):
            """Pairwise lambdas of one padded query.

            scores_q/labels_q/valid_q: [P]; returns ([P] lambdas, [P] hess).
            Mirrors rank_objective.hpp:139-232 with masks replacing the
            `continue` conditions.
            """
            P = scores_q.shape[0]
            neg_inf = jnp.asarray(-jnp.inf, scores_q.dtype)
            s = jnp.where(valid_q, scores_q, neg_inf)
            # per-row discount WITHOUT a gather (TPU gathers serialize):
            # sort rows by descending score carrying the row index, then
            # sort back by row index carrying the rank's discount — two
            # payload-carrying sorts replace argsort+argsort+table-gather
            iota = jnp.arange(P, dtype=jnp.int32)
            neg_s, row_of_rank = jax.lax.sort((-s, iota), num_keys=1,
                                              is_stable=True)
            _, disc = jax.lax.sort((row_of_rank, disc_from_rank[:P]),
                                   num_keys=1, is_stable=True)
            n_valid = jnp.sum(valid_q.astype(jnp.int32))
            best_score = -neg_s[0]
            # the last valid rank's score picked by a masked max, not an
            # index: vmapped, a traced index is a gather
            worst_score = -jnp.max(jnp.where(
                iota == jnp.maximum(n_valid - 1, 0), neg_s, -jnp.inf))

            # pairwise [P, P]: i = high row, j = low row
            lab = labels_q.astype(jnp.int32)
            gain = gains_q                        # [P] label gain per row
            d_score = s[:, None] - s[None, :]
            pair_valid = (valid_q[:, None] & valid_q[None, :]
                          & (lab[:, None] > lab[None, :]))
            dcg_gap = gain[:, None] - gain[None, :]
            paired_disc = jnp.abs(disc[:, None] - disc[None, :])
            delta_pair_ndcg = dcg_gap * paired_disc * inv_max_dcg
            if norm:
                delta_pair_ndcg = jnp.where(
                    best_score != worst_score,
                    delta_pair_ndcg / (0.01 + jnp.abs(d_score)),
                    delta_pair_ndcg)
            p_lambda = 1.0 / (1.0 + jnp.exp(d_score * sigmoid))
            p_hess = p_lambda * (1.0 - p_lambda)
            p_lambda = -sigmoid * delta_pair_ndcg * p_lambda
            p_hess = sigmoid * sigmoid * delta_pair_ndcg * p_hess
            p_lambda = jnp.where(pair_valid, p_lambda, 0.0)
            p_hess = jnp.where(pair_valid, p_hess, 0.0)

            lambdas = jnp.sum(p_lambda, axis=1) - jnp.sum(p_lambda, axis=0)
            hess = jnp.sum(p_hess, axis=1) + jnp.sum(p_hess, axis=0)
            sum_lambdas = -2.0 * jnp.sum(p_lambda)
            if norm:
                norm_factor = jnp.where(
                    sum_lambdas > 0,
                    jnp.log2(1 + sum_lambdas) / sum_lambdas, 1.0)
                lambdas = lambdas * norm_factor
                hess = hess * norm_factor
            return lambdas, hess

        def core(s_q, l_q, qvalid, inv_max_dcgs, gains_q, discounts):
            Q, P = s_q.shape
            s_q = s_q.astype(ct)
            gains_q = gains_q.astype(ct)
            inv_max_dcgs = inv_max_dcgs.astype(ct)
            discounts = discounts.astype(ct)

            def chunk_fn(args):
                sq, lq, vq, inv, gq = args
                return jax.vmap(one_query, in_axes=(0, 0, 0, 0, 0, None))(
                    sq, lq, vq, inv, gq, discounts)

            # chunk the query axis to bound the [chunk, P, P] intermediate
            pad_q = (-Q) % chunk
            def padq(x):
                return jnp.pad(x, ((0, pad_q),) + ((0, 0),) * (x.ndim - 1))
            sq, lq, vq, gq = padq(s_q), padq(l_q), padq(qvalid), padq(gains_q)
            inv = jnp.pad(inv_max_dcgs, (0, pad_q))
            nchunks = (Q + pad_q) // chunk
            resh = lambda x: x.reshape((nchunks, chunk) + x.shape[1:])
            lam_c, hes_c = jax.lax.map(
                chunk_fn, (resh(sq), resh(lq), resh(vq), resh(inv), resh(gq)))
            return lam_c.reshape(-1), hes_c.reshape(-1)
        return core

    def grad_fn(self):
        core = self._pairwise_flat()

        def fn(score, label, weight, qidx, qvalid, inv_max_dcgs, label_gain,
               discounts, inv_pos):
            safe_idx = jnp.maximum(qidx, 0)
            s_q = score[safe_idx]                       # [Q, P]
            l_q = label[safe_idx]
            gains_q = label_gain[l_q.astype(jnp.int32)]
            lam, hes = core(s_q, l_q, qvalid, inv_max_dcgs, gains_q,
                            discounts)
            # padded [Q, P] -> flat rows with one gather (each row occupies
            # exactly one padded position)
            g = lam[inv_pos]
            h = hes[inv_pos]
            if weight is not None:
                g = g * weight
                h = h * weight
            return g.astype(jnp.float32), h.astype(jnp.float32)
        return fn

    # the payload fill sorts on 2 * row id in int32
    POS_FILL_MAX_ROWS = 1 << 30

    def payload_pos_fn(self):
        """Payload-order gradient mode for the persist fast path: scores
        arrive in PAYLOAD order with their global row ids, and the rows
        move between lane order and the padded [Q, P] slots by two sorts,
        with no scatter and no gather (on a TPU v5e both serialize: a
        [2, n] scatter took 61 ns a row). The first sorts (row key, score
        bits, lane) into slot order, the second sorts (slot lane, lambda,
        hessian) back to lane order — no row-order round trip (the
        reference has no analog: its gradient buffer is always
        row-ordered, rank_objective.hpp:98-137). The driver keeps the
        live rows in lanes 0..n-1, so `live` is not read. None past
        POS_FILL_MAX_ROWS rows, whose doubled row ids overflow int32: the
        driver then takes the row-order fill."""
        n = self.num_data
        if n >= self.POS_FILL_MAX_ROWS:
            return None
        core = self._pairwise_flat()

        def fn(score, rid, live, lab_pad, qvalid, inv_max_dcgs, gains_pad,
               discounts, fill_key, w_pad):
            Q, P = lab_pad.shape
            QP = Q * P
            NP = score.shape[0]
            F = fill_key.shape[0]                   # QP - n padding slots
            # into slot order: row r sorts at key 2r and the padding of
            # query q right after its last row, each slot carrying its
            # score and its lane. The scores ride as their own bits (one
            # word a float32, two a float64), never the lane ids as
            # floats: an int32 under 2^23 read as a float32 is a denormal,
            # which XLA flushes to zero on the CPU and the TPU. Padding
            # slots score 0 from lane NP; the keys are distinct but for
            # the padding's, whose operands are equal, so no tie-break
            key = jnp.concatenate([2 * rid[:n], fill_key])
            bits = jax.lax.bitcast_convert_type(score[:n], jnp.int32) \
                .reshape(n, -1).T                       # [W, n]
            W = bits.shape[0]
            bits = jnp.concatenate([bits, jnp.zeros((W, F), jnp.int32)], 1)
            lane = jnp.concatenate([jnp.arange(n, dtype=jnp.int32),
                                    jnp.full((F,), NP, jnp.int32)])
            _, *words, slot_lane = jax.lax.sort(
                (key, *bits, lane), num_keys=1, is_stable=False)
            sp = jax.lax.bitcast_convert_type(jnp.stack(words, 1),
                                              score.dtype).reshape(QP)
            lam, hes = core(sp.reshape(Q, P), lab_pad, qvalid, inv_max_dcgs,
                            gains_pad, discounts)
            lam = lam[:QP]
            hes = hes[:QP]
            if w_pad is not None:
                # weights multiply BEFORE the f32 cast, exactly as the
                # row-order grad_fn does (rank_objective.hpp:165-170) —
                # pos-mode fns own their weighting; the grower's payload
                # weight row is not applied in pos mode
                lam = lam * w_pad.reshape(-1)
                hes = hes * w_pad.reshape(-1)
            # back to lane order: lanes 0..n-1 come first, the padding
            # (lane NP) last; the dead lanes past n read 0
            _, g, h = jax.lax.sort(
                (slot_lane, lam.astype(jnp.float32), hes.astype(jnp.float32)),
                num_keys=1, is_stable=False)
            return (jnp.pad(g[:n], (0, NP - n)),
                    jnp.pad(h[:n], (0, NP - n)))
        return fn

    def _pos_grad_args(self):
        # device constants cached: persist_grad_args runs once per fused
        # K-iteration batch, and these [Q, P]/[n] planes never change
        cached = getattr(self, "_pos_args_dev", None)
        if cached is None:
            P = self._qidx.shape[1]
            from ..metrics.dcg import _DISCOUNT_CACHE
            # run record: the padded query layout the fill works on (set,
            # not summed); slots over rows is the padding's cost
            telemetry.clear_counts_prefix(("objective::rank_queries",
                                           "objective::rank_query_slots"))
            telemetry.count("objective::rank_queries",
                            float(self.num_queries), category="objective")
            telemetry.count("objective::rank_query_slots",
                            float(self._qidx.size), category="objective")
            cached = self._pos_args_dev = (
                jnp.asarray(self._lab_pad), jnp.asarray(self._qvalid),
                jnp.asarray(self.inverse_max_dcgs),
                jnp.asarray(self._gains_pad),
                jnp.asarray(_DISCOUNT_CACHE[:P]),
                jnp.asarray(self._fill_key),
                (jnp.asarray(self._w_pad) if self._w_pad is not None
                 else None))
        return cached

    def _grad_args(self):
        weight = jnp.asarray(self.weight) if self.weight is not None else None
        P = self._qidx.shape[1]
        from ..metrics.dcg import _DISCOUNT_CACHE
        return (jnp.asarray(self.label), weight, jnp.asarray(self._qidx),
                jnp.asarray(self._qvalid), jnp.asarray(self.inverse_max_dcgs),
                jnp.asarray(self.label_gain),
                jnp.asarray(_DISCOUNT_CACHE[:P]),
                jnp.asarray(self._inv_pos))

    def to_string(self):
        return self.name


@register
class RankXENDCG(RankingObjective):
    name = "rank_xendcg"

    def device_gradients(self):
        # per-iteration fresh randomization cannot ride the fused
        # K-iteration scan (its traced inputs are fixed across the
        # batch): host-only, on the ONE capability surface
        return None

    # the reference's LCG (include/LightGBM/utils/random.h:101-110):
    # x = 214013 x + 2531011 (mod 2^32); NextFloat = ((x>>16) & 0x7fff)/2^15
    _LCG_A = np.uint32(214013)
    _LCG_B = np.uint32(2531011)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        qb = self.query_boundaries
        # flat row -> query id for segment ops
        qid = np.zeros(self.num_data, dtype=np.int32)
        for q in range(self.num_queries):
            qid[qb[q]:qb[q + 1]] = q
        self._qid = qid
        self._counts = np.diff(qb).astype(np.int32)
        # reference-exact per-query Random streams (rands_[i] = Random(seed+i),
        # rank_objective.hpp:300): vectorized k-step LCG jump tables so draw
        # j of a query reads the state after j+1 advances
        self._lcg_x = (np.uint32(self.seed)
                       + np.arange(self.num_queries, dtype=np.uint32))
        kmax = int(self._counts.max()) if len(self._counts) else 1
        A = np.empty(kmax + 1, dtype=np.uint32)
        C = np.empty(kmax + 1, dtype=np.uint32)
        A[0], C[0] = np.uint32(1), np.uint32(0)
        with np.errstate(over="ignore"):
            for k in range(kmax):
                A[k + 1] = self._LCG_A * A[k]
                C[k + 1] = self._LCG_A * C[k] + self._LCG_B
        self._lcg_A, self._lcg_C = A, C
        self._pos_in_query = (np.arange(self.num_data, dtype=np.int64)
                              - qb[qid]).astype(np.int64)

    def _next_floats(self) -> np.ndarray:
        """One iteration's [num_data] NextFloat() draws, bit-identical to
        the reference's sequential per-query stream."""
        j1 = self._pos_in_query + 1
        with np.errstate(over="ignore"):
            v = (self._lcg_A[j1] * self._lcg_x[self._qid]
                 + self._lcg_C[j1])
            cnt = self._counts.astype(np.int64)
            self._lcg_x = (self._lcg_A[cnt] * self._lcg_x
                           + self._lcg_C[cnt])
        return (((v >> np.uint32(16)) & np.uint32(0x7FFF))
                .astype(np.float32) / np.float32(32768.0)).astype(np.float64)

    def grad_fn(self):
        num_queries = self.num_queries
        num_data = self.num_data

        def seg_sum(x, qid):
            return jax.ops.segment_sum(x, qid, num_segments=num_queries)

        def seg_max(x, qid):
            return jax.ops.segment_max(x, qid, num_segments=num_queries)

        def fn(score, label, weight, qid, counts, g_rand):
            # masked softmax per query (Common::Softmax over each query)
            mx = seg_max(score, qid)
            e = jnp.exp(score - mx[qid])
            rho = e / seg_sum(e, qid)[qid]

            phi = jnp.power(2.0, jnp.floor(label).astype(jnp.float64)) - g_rand
            sum_labels = jnp.maximum(K_EPSILON, seg_sum(phi, qid))
            l1 = -phi / sum_labels[qid] + rho
            sum_l1 = seg_sum(l1, qid)
            l2 = (sum_l1[qid] - l1) / (1.0 - rho)
            sum_l2 = seg_sum(l2, qid)
            l3 = (sum_l2[qid] - l2) / (1.0 - rho)
            lambdas_multi = l1 + rho * l2 + rho * rho * l3
            # single-document queries: l2/l3 terms are zero (cnt<=1 branch)
            single = (counts[qid] <= 1)
            lambdas = jnp.where(single, l1, lambdas_multi)
            hess = rho * (1.0 - rho)
            if weight is not None:
                lambdas = lambdas * weight
                hess = hess * weight
            return lambdas.astype(jnp.float32), hess.astype(jnp.float32)
        return fn

    def get_gradients(self, score):
        # fresh randomization each iteration (reference draws from per-query
        # Random streams each GetGradients call, rank_objective.hpp:305-312)
        if getattr(self, "_jit_fn", None) is None:
            self._jit_fn = jax.jit(self.grad_fn())
            weight = jnp.asarray(self.weight) if self.weight is not None else None
            self._jit_args = (jnp.asarray(self.label), weight,
                              jnp.asarray(self._qid), jnp.asarray(self._counts))
        return self._jit_fn(score, *self._jit_args,
                            jnp.asarray(self._next_floats()))

    def to_string(self):
        return self.name
