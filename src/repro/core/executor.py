"""MHQ execution engine: the three strategies + two-phase multi-vector flow.

Strategies (paper §3.4, TPU-adapted per DESIGN.md §2):
  * filter_first  — evaluate Q_S over all rows, gather ≤ max_candidates
                    qualifying rows, score only those (scalar-index path);
  * index_scan    — rewrite the MHQ into one single-vector filtered IVF
                    subquery per column (k_i, nprobe, max_scan, iterative),
                    merge the candidates, re-rank by the full weighted score;
  * single_index  — heavily skewed weights: search only the dominant column,
                    re-rank by the full score.

``iterative`` implements pgvector's iterative_scan as nprobe doubling while
the filtered result underfills k (bounded by the engine's nprobe cap).

Engine personalities (§5.4): Milvus/OpenSearch expose no max_scan_tuples /
iterative_scan, so those knobs pin to engine defaults — the learned
optimizer is constrained to each engine's search space.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.query import (
    BEAM_GRID, ExecutionPlan, HOP_GRID, MHQ, PRECISION_GRID, SubqueryParams,
)
from repro.vectordb import flat, graph, ivf, predicates
from repro.vectordb.table import Table, similarity

NEG = -1e30

# Shape-bucketing primitives. These live here (not serve/batch) because the
# candidate-union width vocabulary is part of PLAN SEMANTICS shared by the
# sequential and batched executors — both must build the same union for the
# parity contract to hold. serve/batch re-exports them unchanged.
K_BUCKET_FLOOR = 16  # smallest padded top-k bucket
CANDIDATE_PAD_FLOOR = 64  # smallest padded candidate-slot bucket


def next_bucket(n: int, floor: int = 1) -> int:
    """Smallest power-of-two bucket ≥ n (≥ floor)."""
    b = floor
    while b < n:
        b <<= 1
    return b


def pow2_at_most(n: int) -> int:
    b = 1
    while b * 2 <= n:
        b <<= 1
    return b


@dataclasses.dataclass(frozen=True)
class EngineCaps:
    """What the underlying engine exposes (paper §5 setup / §5.4)."""
    name: str
    max_scan_tuples: bool = True
    iterative_scan: bool = True
    per_column_params: bool = True  # can k_i / nprobe differ per column?
    nprobe_cap: int = 64
    default_max_scan: int = 32768


PGVECTOR = EngineCaps("pgvector")
MILVUS = EngineCaps("milvus", max_scan_tuples=False, iterative_scan=False)
OPENSEARCH = EngineCaps("opensearch", max_scan_tuples=False, iterative_scan=False)
ENGINES = {e.name: e for e in (PGVECTOR, MILVUS, OPENSEARCH)}


def _dedup_topk(rows, score, *, k, total):
    """Top-k over candidate scores with duplicate row ids suppressed by
    keeping only the first occurrence (sort-based). rows: (total,), -1 =
    empty slot."""
    valid = rows >= 0
    order = jnp.argsort(rows)
    sorted_rows = rows[order]
    first = jnp.concatenate([jnp.ones((1,), bool),
                             sorted_rows[1:] != sorted_rows[:-1]])
    keep = jnp.zeros((total,), bool).at[order].set(first) & valid
    masked = jnp.where(keep, score, NEG)
    top_s, top_i = jax.lax.top_k(masked, k)
    ids = jnp.where(top_s > NEG / 2, rows[top_i], -1)
    return ids, top_s


@partial(jax.jit, static_argnames=("k", "n_vec", "metric", "total"))
def _rerank(vectors, pred_mask_rows, rows, qs, w, *, k, n_vec, metric, total):
    """Re-rank the union of candidate rows by the full weighted score.

    rows: (total,) candidate ids, -1 = empty."""
    n = vectors[0].shape[0]
    rows_c = jnp.clip(rows, 0, n - 1)
    score = jnp.zeros((total,), jnp.float32)
    for i in range(n_vec):
        score = score + w[i] * similarity(qs[i], vectors[i][rows_c], metric)
    return _dedup_topk(rows, score, k=k, total=total)


@partial(jax.jit, static_argnames=("k", "total"))
def rerank_scored(row_scores, rows, *, k, total):
    """``_rerank`` with the full weighted row scores precomputed (the
    batched path's per-column GEMMs already hold every candidate's score)."""
    n = row_scores.shape[0]
    score = row_scores[jnp.clip(rows, 0, n - 1)]
    return _dedup_topk(rows, score, k=k, total=total)


# Reciprocal-rank fusion across per-column candidate lists (multi-column
# index_scan unions). Truncating each column at its top-k_i loses rows that
# rank just below k_i in EVERY column yet carry the best weighted score on
# weight-skewed queries — and the subquery probes already ranked a wider
# list (the padded top-k bucket), whose tail was previously discarded. The
# union therefore keeps the exact per-column top-k_i block (the engine
# contract) and fills its pad bucket with the rows the combined column
# rankings like best: score(row) = Σ_cols 1/(RRF_K + rank_col(row)).
RRF_K = 60  # standard reciprocal-rank-fusion constant
RRF_MIN_EXTRA = 16  # fused-extra slots guaranteed per multi-column union


def rrf_union_total(sum_ki: int) -> int:
    """Static union width for a multi-column candidate union: the exact
    per-column top-k_i block plus ≥ RRF_MIN_EXTRA fused-extra slots,
    power-of-two bucketed so the width vocabulary stays finite."""
    return next_bucket(sum_ki + RRF_MIN_EXTRA, CANDIDATE_PAD_FLOOR)


def subquery_width(k_i: int, max_scan: int) -> int:
    """Probe width of one column's subquery: the padded top-k bucket, so
    the list carries a ranked tail beyond k_i for RRF fusion to draw from.
    One formula for both executors — the fused extras must be computed
    from identical lists for batched/sequential parity."""
    return min(next_bucket(k_i, K_BUCKET_FLOOR), max_scan)


@partial(jax.jit, static_argnames=("kis", "n_extra", "rrf_k"))
def rrf_extras(lists, *, kis, n_extra, rrf_k=RRF_K):
    """Top-``n_extra`` candidates by reciprocal-rank fusion of the columns'
    ranked tails, excluding rows already in some column's top-k_i block.

    ``lists``: per-column (B, ks_i) ranked candidate ids, -1 = empty slot
    (each column's FULL probed ranking, top-k_i prefix included so a row's
    fused score sees all of its ranks). ``kis``: static per-column included
    widths. Returns (B, n_extra) ids, -1 padded, best-fused first.

    Cross-column dedup sums every occurrence's contribution: sort slots by
    row id and segment-sum each run of equal ids. Contributions are
    integers (2^30 // (rrf_k + 1 + rank)), so the sums are exact and
    independent of summation order: rows with equal contributions tie
    exactly, and ``top_k`` over the id-sorted runs breaks the tie by row id
    on every backend."""
    sc_parts, inc_parts = [], []
    for lst, ki in zip(lists, kis):
        valid = lst >= 0
        contrib = jnp.int32(1 << 30) // (
            rrf_k + 1 + jnp.arange(lst.shape[1], dtype=jnp.int32))
        sc_parts.append(jnp.where(valid, contrib[None, :], 0))
        inc_parts.append(valid & (jnp.arange(lst.shape[1]) < ki)[None, :])
    rows = jnp.concatenate(list(lists), axis=1)
    sc = jnp.concatenate(sc_parts, axis=1)
    inc = jnp.concatenate(inc_parts, axis=1).astype(jnp.int32)
    order = jnp.argsort(rows, axis=1)
    rs = jnp.take_along_axis(rows, order, axis=1)
    b, w = rs.shape
    run = jnp.cumsum(jnp.concatenate(
        [jnp.zeros((b, 1), jnp.int32),
         (rs[:, 1:] != rs[:, :-1]).astype(jnp.int32)], axis=1), axis=1)

    def per_run(op, x):
        return jax.vmap(lambda v, r: op(v, r, num_segments=w))(
            jnp.take_along_axis(x, order, axis=1), run)

    fused = per_run(jax.ops.segment_sum, sc)  # (B, runs), id-sorted
    n_inc = per_run(jax.ops.segment_sum, inc)
    run_row = per_run(jax.ops.segment_max, rows)
    fused = jnp.where((run_row >= 0) & (n_inc == 0), fused, -1)
    ne = min(n_extra, fused.shape[1])
    top_s, top_j = jax.lax.top_k(fused, ne)
    out = jnp.where(top_s > 0, jnp.take_along_axis(run_row, top_j, axis=1),
                    -1)
    if ne < n_extra:
        out = jnp.pad(out, ((0, 0), (0, n_extra - ne)), constant_values=-1)
    return out.astype(jnp.int32)


def legalize_for_shard(k_i: int, nprobe: int, max_scan: int, *,
                       n_shards: int, shard_len: int,
                       n_clusters: int) -> tuple[int, int, int]:
    """Split one subquery's GLOBAL probing budget across ``n_shards``.

    The learned plan's knobs describe a whole-table search; under the
    per-shard IVF path every shard probes its own (smaller) index, so the
    scan budget is divided across shards (ceil, floored at the per-shard
    candidate count so a shard can always fill its slice of the merge) and
    nprobe is clamped to the per-shard cluster count. Returns the per-shard
    ``(k_i, nprobe, max_scan)`` — all static, so they join the group key and
    the jit cache stays bounded the same way the single-device grids do."""
    ms = min(shard_len, max(1, min(k_i, shard_len), -(-max_scan // n_shards)))
    return min(k_i, ms), max(1, min(nprobe, n_clusters)), ms


def plan_columns(q: MHQ, plan: ExecutionPlan) -> tuple:
    """Vector columns a plan actually searches (shared by the sequential and
    batched executors so candidate generation can never drift)."""
    if plan.strategy == "single_index":
        return (plan.dominant,)
    return tuple(i for i in range(q.n_vec) if q.weights[i] > 0.0)


def legal_knob(grid: tuple, value: int) -> int:
    """Smallest grid entry ≥ value (grid max when none) — how the graph
    beam/hop knobs snap onto their static grids at legalization time."""
    for g in grid:
        if g >= value:
            return g
    return grid[-1]


class HybridExecutor:
    """Binds a table + per-column IVF indexes + an engine personality.

    ``graphs``: optional per-column ``vectordb.graph.GraphIndex`` tuple —
    when bound, plans may pick the third ("graph") strategy; when absent,
    legalization rewrites graph plans to index_scan so a plan learned
    against a graph-bearing deployment stays executable everywhere."""

    def __init__(self, table: Table, indexes: list,
                 engine: EngineCaps = PGVECTOR, *, graphs=None):
        self.table = table
        self.indexes = indexes
        self.engine = engine
        self.graphs = tuple(graphs) if graphs is not None else None

    # -- plan legalization ---------------------------------------------------

    def legalize(self, plan: ExecutionPlan) -> ExecutionPlan:
        """Clamp a plan to what the engine personality supports, and every
        candidate budget to the table — the legalized ``max_scan`` /
        ``max_candidates`` are what the batched executor's scoring
        dispatcher weighs against ``n_rows``."""
        e = self.engine
        subs = []
        base = plan.subqueries[0]
        for s in plan.subqueries:
            if not e.per_column_params:
                s = dataclasses.replace(s, k_mult=base.k_mult, nprobe=base.nprobe)
            if not e.max_scan_tuples:
                s = dataclasses.replace(s, max_scan=e.default_max_scan)
            if not e.iterative_scan:
                s = dataclasses.replace(s, iterative=False)
            s = dataclasses.replace(s, nprobe=min(s.nprobe, e.nprobe_cap))
            subs.append(s)
        # precision legalization: unknown values pin to fp32, and
        # filter_first always scores fp32 (its gather is the plan — there
        # is no candidate tier for the int8 replica to accelerate), so the
        # batched group keys never split on a precision that can't act.
        prec = plan.precision if plan.precision in PRECISION_GRID else "fp32"
        if plan.strategy == "filter_first":
            prec = "fp32"
        strategy = plan.strategy
        beam, hops = plan.beam_width, plan.n_hops
        if strategy == "graph":
            if self.graphs is None:
                # no graph tier bound: the nearest executable strategy is
                # the per-column probe union the graph plan approximates
                strategy = "index_scan"
            else:
                # graph candidates come from the fp32 routing walk + one
                # fused extraction — there is no int8 candidate tier
                prec = "fp32"
                beam = legal_knob(BEAM_GRID, beam)
                hops = legal_knob(HOP_GRID, hops)
        return dataclasses.replace(
            plan, strategy=strategy, subqueries=tuple(subs), precision=prec,
            beam_width=beam, n_hops=hops,
            max_candidates=min(plan.max_candidates, self.table.n_rows))

    # -- execution -------------------------------------------------------------

    def execute(self, q: MHQ, plan: ExecutionPlan):
        """-> (ids (k,), scores (k,)) numpy arrays."""
        plan = self.legalize(plan)
        t = self.table
        w = jnp.asarray(q.weights, jnp.float32)
        if plan.strategy == "filter_first":
            ids, scores, _, _ = flat.filter_first(
                tuple(t.vectors), t.scalars, q.predicates,
                tuple(q.query_vectors), w, t.schema.metric,
                k=q.k, max_candidates=plan.max_candidates, n_vec=q.n_vec)
            return ids, scores

        cols = plan_columns(q, plan)

        cand, wide = [], []
        for i in cols:
            sp = plan.subqueries[i]
            k_i = min(sp.k_mult * q.k, t.n_rows)
            ks = subquery_width(k_i, min(sp.max_scan, t.n_rows)) \
                if len(cols) > 1 else k_i
            if plan.strategy == "graph":
                # predicate-aware beam walk over the column's proximity
                # graph; the returned list is already filtered + ranked,
                # so it slots into the same RRF union + rerank as IVF
                ids_i, _, _, _ = graph.search(
                    self.graphs[i], t.gather_rows((i,)), q.predicates,
                    q.query_vectors[i], beam_width=plan.beam_width,
                    n_hops=plan.n_hops, k=ks)
            else:
                ids_i = self._subquery(i, q, k_i, sp,
                                       precision=plan.precision, width=ks)
            wide.append(ids_i)
            cand.append(ids_i[:k_i])
        rows = jnp.concatenate(cand)
        if len(cols) > 1:
            # multi-column union: RRF-fused extras from the probed tails
            # (identical construction to serve/batch._union_candidates, so
            # batched/sequential parity is preserved by both improving)
            kis = tuple(int(c.shape[0]) for c in cand)
            total = rrf_union_total(int(rows.shape[0]))
            extras = rrf_extras(tuple(wd[None, :] for wd in wide), kis=kis,
                                n_extra=total - int(rows.shape[0]))
            rows = jnp.concatenate([rows, extras[0]])
        total = int(rows.shape[0])
        return _rerank(tuple(t.vectors), None, rows, tuple(q.query_vectors), w,
                       k=q.k, n_vec=q.n_vec, metric=t.schema.metric, total=total)

    def _subquery(self, i: int, q: MHQ, k_i: int, sp: SubqueryParams,
                  precision: str = "fp32", width: int | None = None):
        """One single-vector filtered subquery, with iterative re-expansion.

        ``width`` (≥ k_i) widens the returned ranked list — top-k is
        prefix-consistent, so slots beyond k_i are the column's ranked tail
        for RRF fusion; underfill and re-expansion still key on k_i.

        ``precision == "int8"`` probes the same slots but scores them from
        the column's int8 replica, exact-reranking the top-α·k survivors in
        fp32 (``ivf.search_local_batch_int8`` at batch 1). The qualified
        count driving re-expansion comes from the exact fp32 scalar
        predicates either way, so the doubling ladder is precision-blind."""
        t = self.table
        kw = width or k_i
        nprobe = sp.nprobe
        while True:
            nprobe = min(nprobe, self.indexes[i].n_clusters, self.engine.nprobe_cap)
            max_scan = min(sp.max_scan, t.n_rows)
            if precision == "int8":
                ids_b, _, _, nq_b = ivf.search_local_batch_int8(
                    self.indexes[i], t.gather_rows((i,)),
                    t.gather_rows((i,), int8=True),
                    predicates.stack([q.predicates]),
                    q.query_vectors[i][None, :],
                    nprobe=nprobe, max_scan=max_scan, k=kw)
                ids, n_qual = ids_b[0], nq_b[0]
            else:
                ids, scores, n_scored, n_qual = ivf.search(
                    self.indexes[i], t.vectors[i], t.scalars, q.predicates,
                    q.query_vectors[i], nprobe=nprobe, max_scan=max_scan, k=kw)
            if not sp.iterative:
                return ids
            # boomlint: ignore[HS001] one sync per re-expansion round is the
            # sequential iterative_scan contract (the batched path amortizes
            # it per group — serve/batch._batched_subquery)
            if int(n_qual) >= k_i or nprobe >= min(self.indexes[i].n_clusters,
                                                   self.engine.nprobe_cap):
                return ids
            nprobe *= 2  # iterative_scan: relaxed re-expansion

    # -- measured execution ----------------------------------------------------

    def execute_timed(self, q: MHQ, plan: ExecutionPlan, *, repeats: int = 1):
        """Returns (ids, scores, seconds). Call once to warm the jit cache
        before timing loops."""
        ids, scores = self.execute(q, plan)  # warm + result
        jax.block_until_ready(scores)
        t0 = time.perf_counter()
        for _ in range(repeats):
            ids, scores = self.execute(q, plan)
            jax.block_until_ready(scores)
        dt = (time.perf_counter() - t0) / repeats
        return np.asarray(ids), np.asarray(scores), dt


def recall_at_k(ids, gt_ids) -> float:
    got = set(int(i) for i in np.asarray(ids) if i >= 0)
    gt = [int(i) for i in np.asarray(gt_ids) if i >= 0]
    if not gt:
        return 1.0
    return len(got.intersection(gt)) / len(gt)
