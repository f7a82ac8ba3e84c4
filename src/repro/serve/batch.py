"""Batched MHQ execution: grouped, vmapped serving of many hybrid queries.

The sequential path (``HybridExecutor.execute``) pays one dispatch + host
sync per query, so throughput on small-to-mid tables is dominated by
per-query overhead rather than by scoring work. This module converts the hot
path into a batch-parallel one:

  * queries are grouped by (strategy, legalized per-column subquery params,
    k) — every query in a group runs the *same* static-shape kernel, so the
    group executes as one vmapped call over the query axis;
  * scoring is DENSE per chunk: one multithreaded GEMM computes every row's
    similarity for the whole batch, and search / filter-first / rerank
    kernels gather f32 *scores* instead of (max_scan, d) vector tensors —
    on CPU the vmapped vector gather is the dominant cost, and for wide
    columns it materializes hundreds of MB the single-query jit fuses away;
  * candidate counts, top-k widths and the batch axis are padded to
    power-of-two buckets, so the jit cache stays bounded instead of
    recompiling per distinct ``total`` / batch size;
  * pgvector-style ``iterative_scan`` re-expansion runs per *group*: one
    host sync reads the whole group's qualified counts, and only the
    still-underfilled subset re-selects slots at a doubled nprobe (the
    dense scores are reused, so re-expansion never re-scores vectors).

Per-query results match the sequential executor's exactly in structure and
up to float reduction order in values: the GEMM accumulates the same dots
as the gathered matvec but in a different blocking, so scores can differ in
the last ulp and near-exact ties may order differently. Bucketed top-k
widths are sliced back to the exact k (``lax.top_k`` is sorted, so the
prefix equals the narrower call), and padded candidate slots carry id -1,
which the dedupe/rerank masking already handles.

``ServingEngine`` is the deployment-shaped wrapper: it chops a request
stream into batches, drives ``BoomHQ.execute_batch`` (one fused optimizer
dispatch + one grouped execution pass per batch) and accounts QPS/recall.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.executor import (
    CANDIDATE_PAD_FLOOR, EngineCaps, HybridExecutor, K_BUCKET_FLOOR, PGVECTOR,
    legalize_for_shard, next_bucket, plan_columns, pow2_at_most, recall_at_k,
    rerank_scored, rrf_extras, rrf_union_total, subquery_width,
)
from repro.core.query import (
    BEAM_GRID, ExecutionPlan, HOP_GRID, KMULT_GRID, MAX_SCAN_GRID, MHQ,
    NPROBE_GRID,
)
from repro.kernels.gather_score import gather_score_topk, merge_topk_unique
from repro.kernels.shapes import GRAPH_ENTRY_POINTS, GRAPH_SEED_FACTOR
from repro.vectordb import flat, graph, histogram, ivf, predicates
from repro.vectordb.distributed import (
    build_sharded_ivf, sharded_batch_topk, sharded_ivf_topk, sharded_topk_ref,
)
from repro.vectordb.predicates import eval_mask
from repro.vectordb.table import Table, similarity

# Dense-score budget: each chunk holds (batch, n_rows) f32 score matrices
# per active vector column; chunks are sized so batch · n_rows stays under
# this many slots (32 MB/column at the cap).
SLOT_BUDGET = 1 << 23
MAX_BATCH_KERNEL = 64  # widest vmapped execution kernel

# scoring paths the per-group dispatcher chooses between
DENSE = "dense"
CANDIDATE_LOCAL = "candidate_local"
# sharded-group routes: plan-driven per-shard IVF probing, or no fan-out at
# all (the group runs the plain single-device path when shards are too
# small to amortize the merge)
SHARDED_LOCAL = "sharded_local"
SINGLE_DEVICE = "single_device"

# histogram-estimated static gather caps (the sharded candidate-local path):
# cap = next_bucket(margin · estimated_max + slack), with overflow
# escalation re-running only the queries whose true count exceeds the cap
CAP_MARGIN = 1.5
CAP_SLACK = 32


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Scoring-path cost model: dense vs candidate-local, plus the sharded
    three-way route.

    The dense path runs one GEMM over ALL rows per vector column and group
    chunk — per-batch cost ∝ ``n_rows``, and (measured) essentially
    batch-size independent while B ≤ the chunk cap: the GEMM streams the
    table once either way. The candidate-local path gathers and scores
    only each query's legalized candidate budget, paying a FIXED per-batch
    overhead (probe slot selection dispatch, kernel launch, re-expansion
    host syncs) on top of the ``batch · scan`` gather work. Candidate-local
    wins when

        batch · scan + overhead  ≤  crossover · n_rows

    The constant term is what closes the ROADMAP's small-batch mispredict:
    without it the model sends every tiny batch candidate-local (B·scan
    shrinks with B but the fixed cost does not). Both constants are
    calibrated by ``benchmarks/kernels_bench.py`` (``crossover_sweep`` /
    ``overhead_sweep``) and the defaults are the values measured on this
    CPU container; a TPU backend with the Mosaic kernel should recalibrate
    ``crossover`` upward and ``overhead`` downward.

    ``choose_sharded`` adds the sharded three-way: groups over a sharded
    table run plan-driven per-shard IVF probing (``SHARDED_LOCAL``) when
    the same inequality holds at the global scale (the probe work is split
    across shards but the fixed overhead is paid once per batch), the
    exact per-shard dense scan otherwise — and skip the fan-out entirely
    (``SINGLE_DEVICE``) when shards are smaller than ``min_shard_rows``,
    where the O(shards·k) merge costs more than it saves.

    The crossover is PER PRECISION: the int8 candidate tier gathers 1-byte
    elements (4× less memory traffic in the heavy stage) but pays an extra
    fixed cost per batch — the exact fp32 rerank of the top-α·k survivors
    is a second kernel dispatch. So ``crossover_int8 > crossover``: the
    candidate-local region widens — int8 groups stay candidate-local at
    scan budgets that would have pushed fp32 groups dense. Both int8
    constants are measured by the same ``kernels_bench`` sweeps run
    against the quantized path
    (``benchmarks/results/quantized_crossover.json``); on this container
    the measured fixed intercept is LOWER than fp32's in gathered-row
    units (the rerank dispatch is small next to the cheaper per-row
    gather the intercept is normalized by).

    ``force`` pins every group to one path (benchmarks and dispatcher
    tests): dense-flavored forces pin dense, local-flavored forces pin the
    context's local path."""

    crossover: float = 0.136
    overhead: float = 2048.0  # per-batch fixed cost, in gathered-row units
    crossover_int8: float = 0.545  # measured: results/quantized_crossover.json
    overhead_int8: float = 3350.0  # measured, same calibration run
    # graph tier: graph_row_cost converts visited-row budgets into
    # probed-slot units so the three tiers compare on one axis;
    # overhead_graph is the per-batch fixed cost of the walk dispatch
    # (n_hops sequential hop steps, not amortizable over the batch).
    # Measured by benchmarks/serving.py --graph
    # (benchmarks/results/graph_index.json), unit-anchored on the dense
    # exact scan's per-batch wall time. A visited graph row comes out
    # CHEAPER than one gathered-row unit — the per-hop neighbor gathers
    # vectorize across the whole query batch — which is why, once a graph
    # tier is bound, the fitted surface leaves probing only the cases the
    # planner routes to it for recall (or when a column has no graph).
    graph_row_cost: float = 0.216
    overhead_graph: float = 328.3
    min_shard_rows: int = 4096
    force: Optional[str] = None

    def constants(self, precision: str = "fp32") -> tuple[float, float]:
        """(crossover, overhead) of one precision tier."""
        if precision == "int8":
            return self.crossover_int8, self.overhead_int8
        return self.crossover, self.overhead

    def choose_strategy(self, *, batch: int, graph_scan: int,
                        probe_scan: int, n_rows: int) -> str:
        """Measured graph-vs-probe-vs-exact crossover at the STRATEGY level
        (the scoring-path crossovers above route a group once its strategy
        is fixed; this compares the strategies themselves, in the same
        gathered-row cost units):

          exact  ≈ crossover · n_rows          (one dense GEMM per column)
          probe  ≈ batch · probe_scan + overhead
          graph  ≈ batch · graph_scan · graph_row_cost + overhead_graph

        Returns the cheapest of {"exact", "index_scan", "graph"}. The
        planner uses it as a guard: recall is the rewriter's job, so this
        only breaks ties the learned heads are indifferent about (e.g. the
        skew-guard fallback path)."""
        costs = {
            "exact": self.crossover * n_rows,
            "index_scan": batch * probe_scan + self.overhead,
            "graph": batch * graph_scan * self.graph_row_cost
            + self.overhead_graph,
        }
        return min(costs, key=costs.get)

    def choose(self, *, batch: int, scan: int, n_rows: int,
               precision: str = "fp32") -> str:
        if self.force is not None:
            return CANDIDATE_LOCAL \
                if self.force in (CANDIDATE_LOCAL, SHARDED_LOCAL) else DENSE
        xo, oh = self.constants(precision)
        if batch * scan + oh <= xo * n_rows:
            return CANDIDATE_LOCAL
        return DENSE

    def choose_sharded(self, *, batch: int, scan: int, n_rows: int,
                       n_shards: int) -> str:
        if self.force is not None:
            if self.force in (CANDIDATE_LOCAL, SHARDED_LOCAL):
                return SHARDED_LOCAL
            return self.force  # DENSE or SINGLE_DEVICE
        if n_rows // max(1, n_shards) < self.min_shard_rows:
            return SINGLE_DEVICE
        return SHARDED_LOCAL if self.choose(
            batch=batch, scan=scan, n_rows=n_rows) == CANDIDATE_LOCAL \
            else DENSE


class ScoringDispatcher:
    """Per-execution-group scoring-path dispatch + decision log.

    Every group chunk asks :meth:`choose` before executing; the decision
    (group label, batch, candidate budget, chosen path) is recorded so
    serving reports can surface which path served the traffic
    (``ServeReport.path_counts``) and tests can assert the crossover is
    honored per group."""

    # decision log ring size: long-running servers (AsyncServingEngine never
    # drains the log) keep only the most recent window; counts stay exact
    MAX_DECISIONS = 4096

    def __init__(self, n_rows: int, cost_model: Optional[CostModel] = None):
        self.n_rows = int(n_rows)
        self.cost_model = cost_model if cost_model is not None else CostModel()
        self.counts: dict = {}
        self.decisions: deque = deque(maxlen=self.MAX_DECISIONS)

    def pins_dense(self, prefer_dense: bool) -> bool:
        """The paid-for-GEMM rule, held in ONE place: when a chunk's dense
        score matrices were already computed (the planner wanted them),
        gathering rows from them is strictly cheaper than re-scoring
        candidates from raw vectors — pin the chunk dense unless the cost
        model explicitly forces a path."""
        return prefer_dense and self.cost_model.force is None

    def choose(self, *, batch: int, scan: int, group=None,
               force: Optional[str] = None,
               prefer_dense: bool = False,
               precision: str = "fp32") -> str:
        if force is None and self.pins_dense(prefer_dense):
            force = DENSE
        path = force if force is not None else self.cost_model.choose(
            batch=batch, scan=scan, n_rows=self.n_rows, precision=precision)
        self.decisions.append(
            {"group": group, "batch": batch, "scan": scan, "path": path,
             "precision": precision})
        self.counts[path] = self.counts.get(path, 0) + 1
        return path

    def choose_sharded(self, *, batch: int, scan: int, n_shards: int,
                       group=None, prefer_dense: bool = False) -> str:
        """Route one sharded plan-driven group: per-shard IVF probing,
        exact per-shard dense scan, or no fan-out (single-device). A
        ``SINGLE_DEVICE`` decision delegates to the plain chunk path,
        which records its own inner dense/candidate-local decision. The
        paid-for-GEMM rule applies here too: when the batch's dense score
        matrices already exist, the exact sharded scan over them is
        strictly cheaper than re-scoring candidates from raw vectors."""
        if self.pins_dense(prefer_dense):
            path = DENSE
        else:
            path = self.cost_model.choose_sharded(
                batch=batch, scan=scan, n_rows=self.n_rows,
                n_shards=n_shards)
        self.decisions.append(
            {"group": group, "batch": batch, "scan": scan, "path": path})
        self.counts[path] = self.counts.get(path, 0) + 1
        return path

    def take(self) -> tuple[dict, list]:
        """Return (counts, recent decisions) accumulated since the last
        take, and reset both."""
        counts, decisions = self.counts, list(self.decisions)
        self.counts = {}
        self.decisions.clear()
        return counts, decisions


# Registered static-shape vocabularies. Every shape-bearing static argument
# a serving-path jit is called with must come from one of these grids, a
# power-of-two ``next_bucket`` value, or one of the two floors (the floors,
# ``next_bucket``/``pow2_at_most`` and the candidate-union width formulas
# live in core/executor — plan semantics shared with the sequential path —
# and are re-exported here) — that bound on distinct shapes is what bounds
# compile count, and boomlint (repro.analysis, rule RC001) checks call
# sites against this registry.
SHAPE_GRIDS = {
    "clause": predicates.CLAUSE_GRID,
    "nprobe": NPROBE_GRID,
    "max_scan": MAX_SCAN_GRID,
    "kmult": KMULT_GRID,
    "beam": BEAM_GRID,
    "hops": HOP_GRID,
}


def pad_selection(sel: np.ndarray) -> np.ndarray:
    """Pad a (non-empty) query-index selection to its power-of-two bucket
    by repeating the first element — the shared scaffolding of every
    subset-retry path (escalation, overflow re-gather, re-expansion):
    padding lanes compute a duplicate result that callers slice away."""
    bb = next_bucket(len(sel))
    return np.concatenate([sel, np.full(bb - len(sel), sel[0])])


def warm_bucket_ladder(execute_batch, queries: list, batch_size: int) -> None:
    """Warm the jit caches across the batch-bucket ladder.

    Arrival-driven serving (serve/queue.py) cuts batches at many sizes and
    each padded bucket is a distinct static shape; one untimed pass per
    power-of-two bucket — through ``next_bucket(batch_size)``, so a
    non-power-of-two batch_size still warms its top bucket — keeps cold
    compiles out of measured (and deadline-bounded) serving."""
    b = 1
    while b <= next_bucket(batch_size) and queries:
        execute_batch(queries[: min(b, len(queries))])
        b <<= 1


# ---------------------------------------------------------------------------
# vmapped kernels (batch axis = queries; one compile per static bucket)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("metric",))
def _dense_scores(vectors, q_b, *, metric):
    """(B, n) similarities of every row against every query in the batch —
    ONE GEMM instead of B (max_scan, d) vector gathers. All downstream
    kernels gather f32 scores, not d-dim vectors."""
    return jax.vmap(lambda q: similarity(q, vectors, metric))(q_b)


def compute_batch_scores(table: Table, queries: list[MHQ]) -> tuple:
    """Per-column (B_bucket, n) dense similarity matrices for a query batch
    (batch axis padded to a power-of-two bucket by repeating the first
    query). Computed ONCE per batch and shared by the batched optimizer
    (pre-probe features) and the batched executor (search / filter-first /
    rerank scoring)."""
    bb = next_bucket(len(queries))
    qpad = list(queries) + [queries[0]] * (bb - len(queries))
    return tuple(
        _dense_scores(table.vectors[i],
                      jnp.stack([q.query_vectors[i] for q in qpad]),
                      metric=table.schema.metric)
        for i in range(table.schema.n_vec))


@partial(jax.jit, static_argnames=("nprobe", "max_scan", "k"))
def _search_batch(index, scores_b, scalars, pred_b, q_b, *, nprobe, max_scan,
                  k):
    def one(rs, pred, qv):
        return ivf.search_scored(index, rs, scalars, pred, qv,
                                 nprobe=nprobe, max_scan=max_scan, k=k)

    return jax.vmap(one)(scores_b, pred_b, q_b)


@partial(jax.jit, static_argnames=("k", "max_candidates"))
def _filter_first_batch(w_scores_b, scalars, pred_b, *, k, max_candidates):
    def one(rs, pred):
        return flat.filter_first_scored(rs, scalars, pred, k=k,
                                        max_candidates=max_candidates)

    return jax.vmap(one)(w_scores_b, pred_b)


@partial(jax.jit, static_argnames=("k", "total"))
def _rerank_batch(w_scores_b, rows_b, *, k, total):
    def one(rs, rows):
        return rerank_scored(rs, rows, k=k, total=total)

    return jax.vmap(one)(w_scores_b, rows_b)


@jax.jit
def _eval_mask_batch(pred_b, scalars):
    """(B,) stacked predicates × (n, M) scalars -> (B, n) bool masks."""
    return jax.vmap(lambda p: eval_mask(p, scalars))(pred_b)


@jax.jit
def _selectivity_batch(hists, pred_b):
    """(B,) histogram selectivity estimates for a stacked predicate batch —
    a tiny pure-stats computation (no table reads), so syncing it to size a
    static gather cap costs microseconds, not a device round-trip through
    the (B, n) mask kernel."""
    return jax.vmap(
        lambda p: histogram.estimate_selectivity(hists, p))(pred_b)


@partial(jax.jit, static_argnames=("k", "metric"))
def _gather_rerank_batch(rows_b, rows, q_b, w_b, *, k, metric):
    """Candidate-local weighted re-rank: fused gather+score+dedup+top-k over
    the candidate union — no (B, n) weighted score matrix."""
    return gather_score_topk(rows_b, rows, q_b, w_b, None, k=k,
                             metric=metric)


@partial(jax.jit, static_argnames=("size",))
def _qualifying_rows_batch(mask_b, *, size):
    """(B, n) bool masks -> (B, size) qualifying row ids, -1 padded."""
    return jax.vmap(lambda m: flat.compact_rows(m, size, -1))(mask_b)


NEG = -1e30


@partial(jax.jit, static_argnames=("shard_len", "k", "metric"))
def _sharded_exact_retry(vectors, scalars, pred_b, q_b, w_b, need_b, *,
                         shard_len, k, metric):
    """Exact weighted filtered top-k over each query's underfilled
    shard-subset: dense scores for the retry subset (one GEMM per column),
    the predicate mask ANDed with the per-query shard-allow mask (rows of
    well-filled shards contribute nothing — their probed top-k stands),
    then one top-k. Used when the escalated queries span most shards: one
    batched retry beats a per-shard dispatch loop."""
    n = scalars.shape[0]
    s_count = need_b.shape[1]
    ws = jnp.zeros((w_b.shape[0], n), jnp.float32)
    for i, v in enumerate(vectors):
        ws = ws + w_b[:, i, None] * jax.vmap(
            lambda q, vv=v: similarity(q, vv, metric))(q_b[i])
    shard_of = jnp.minimum(jnp.arange(n, dtype=jnp.int32) // shard_len,
                           s_count - 1)
    allow = need_b[:, shard_of]
    mask = jax.vmap(lambda p: eval_mask(p, scalars))(pred_b) & allow
    masked = jnp.where(mask, ws, NEG)
    top_s, top_i = jax.lax.top_k(masked, k)
    ids = jnp.where(top_s > NEG / 2, top_i, -1)
    return ids.astype(jnp.int32), top_s


# ---------------------------------------------------------------------------
# batched executor
# ---------------------------------------------------------------------------

class BatchedHybridExecutor:
    """Executes a list of (MHQ, ExecutionPlan) pairs with grouped vmapped
    kernels. Produces per-query results identical to ``HybridExecutor``.

    With ``n_shards > 1`` (or a bound ``mesh``) the executor additionally
    exposes the CROSS-SHARD paths (:meth:`execute_batch_sharded`): formed
    batches fan out over contiguous table shards. Without plans, every
    clause-bucket group runs the EXACT per-shard scan (mask + local top-k
    over the dense score matrices, one O(shards · k) merge). With learned
    plans, index-strategy groups are dispatcher-routed three ways: the
    plan-driven per-shard IVF probing path (``ShardedIVF`` — each shard
    probes its own index with the group's shard-legalized knobs and reranks
    candidate-locally inside the shard), the exact per-shard dense scan, or
    the plain single-device path when shards are too small to amortize the
    fan-out. A real mesh runs both sharded paths under ``shard_map``;
    without one the logical-shard reference kernels keep the identical
    semantics on a single device.
    """

    def __init__(self, table: Table, indexes: list,
                 engine: EngineCaps = PGVECTOR, *, n_shards: int = 1,
                 mesh=None, shard_axes=("data",),
                 cost_model: Optional[CostModel] = None, hists=None,
                 graphs=None):
        self.table = table
        self.indexes = indexes
        self.engine = engine
        self.graphs = tuple(graphs) if graphs is not None else None
        self.hists = hists  # selectivity stats for static gather caps
        self.dispatcher = ScoringDispatcher(table.n_rows, cost_model)
        self.mesh = mesh
        self.shard_axes = shard_axes if isinstance(shard_axes, tuple) \
            else (shard_axes,)
        if mesh is not None:
            n_shards = 1
            for a in self.shard_axes:
                n_shards *= mesh.shape[a]
            if table.n_rows % n_shards:
                raise ValueError(
                    f"table rows {table.n_rows} not divisible over "
                    f"{n_shards} mesh shards")
        self.n_shards = max(1, int(n_shards))
        self._shard_fns: dict = {}  # k -> jit'd shard_map kernel
        self._sivf: dict = {}  # col -> ShardedIVF (lazy, per shard config)
        self._sivf_fns: dict = {}  # (group key, act) -> jit'd probe kernel
        # query indices (positions in the last execute_batch_sharded call)
        # whose merged probe result underfilled and took the exact
        # shard-subset retry — benchmarks segment the probe-served tier
        # from the escalation tax with this; callers may clear it
        self.escalated: set = set()
        # real (unpadded) queries of filter-first chunks, by the method
        # their candidate compaction took (flat.compaction_method)
        self.counts = {"ff_rows_search": 0, "ff_rows_scatter": 0}
        self._seq = HybridExecutor(table, indexes, engine, graphs=graphs)

    def legalize(self, plan: ExecutionPlan) -> ExecutionPlan:
        return self._seq.legalize(plan)

    # -- grouping ----------------------------------------------------------

    def _group_key(self, q: MHQ, plan: ExecutionPlan):
        """Everything that determines the static shape of the group kernel.

        filter_first groups on (k, max_candidates); index groups on the
        active columns and their effective (k_i, nprobe, max_scan,
        iterative) — all grid-valued, so the number of groups (and thus
        compiled kernels) stays small. The legalized DNF clause bucket
        (CLAUSE_GRID) joins both keys: every query in a group then stacks
        to one static (B, C, M) predicate shape, and mixed-complexity
        batches split into at most len(CLAUSE_GRID) extra groups. The
        plan's candidate-tier precision (PRECISION_GRID) joins the index
        key: int8 and fp32 groups compile different scoring kernels AND
        take different cost-model crossovers, so they must never share a
        chunk (legalization pins filter_first to fp32, so its key carries
        no precision component).
        """
        cb = predicates.clause_bucket(q.predicates)
        if plan.strategy == "filter_first":
            return ("ff", cb, q.k, plan.max_candidates)
        n = self.table.n_rows
        if plan.strategy == "graph":
            # graph groups key on the legalized (beam_width, n_hops) pair —
            # grid-valued (BEAM_GRID/HOP_GRID), they fix the static
            # candidate-pool shape of the routing trace — plus each active
            # column's k_i. Precision is pinned fp32 by legalization; it
            # rides in the key slot so _run_chunk_local unpacks uniformly.
            subs = tuple((i, min(plan.subqueries[i].k_mult * q.k, n),
                          plan.beam_width, plan.n_hops)
                         for i in plan_columns(q, plan))
            return ("gr", cb, q.k, subs, "fp32")
        subs = []
        for i in plan_columns(q, plan):
            sp = plan.subqueries[i]
            np0 = min(sp.nprobe, self.indexes[i].n_clusters,
                      self.engine.nprobe_cap)
            subs.append((i, min(sp.k_mult * q.k, n), np0,
                         min(sp.max_scan, n), sp.iterative))
        return ("ix", cb, q.k, tuple(subs), plan.precision)

    def _group_scan(self, key) -> int:
        """Per-query, per-active-column candidate budget of a group — the
        ``scan`` the cost model weighs against ``n_rows``.

        Both sides of the crossover scale with the group's active columns —
        dense runs one (B, n) GEMM per active column, candidate-local
        gathers each column's budget (and the rerank union gathers every
        active column per row) — so the comparison must be per column:
        filter_first's cap already is (every active column is gathered for
        each of the ``max_candidates`` rows), and index groups divide the
        summed per-column budgets by the column count. Legalization clamped
        every term (max_scan/max_candidates capped at the table size)."""
        if key[0] == "ff":
            return int(key[3])
        subs = key[3]
        if key[0] == "gr":
            # a graph subquery's budget is the rows its walk can visit:
            # entry points + qualifying seeds + hops · beam · degree
            tot = sum(GRAPH_ENTRY_POINTS + GRAPH_SEED_FACTOR * bw
                      + nh * bw * self.graphs[col].degree
                      for (col, _, bw, nh) in subs)
            return max(1, tot // max(1, len(subs)))
        return max(1, sum(s[3] for s in subs) // max(1, len(subs)))

    # -- execution ---------------------------------------------------------

    def execute_batch(self, queries: list[MHQ], plans: list[ExecutionPlan],
                      *, scores_b: Optional[tuple] = None
                      ) -> list[tuple[np.ndarray, np.ndarray]]:
        """-> one (ids (k,), scores (k,)) numpy pair per query, in order.

        ``scores_b``: optional per-column (B_bucket, n) dense similarity
        matrices from ``compute_batch_scores`` (row j = queries[j]); when
        given, chunks gather their rows from it instead of re-running the
        GEMMs."""
        assert len(queries) == len(plans)
        plans = [self.legalize(p) for p in plans]
        out: list = [None] * len(queries)
        groups: dict = {}
        for j, (q, p) in enumerate(zip(queries, plans)):
            groups.setdefault(self._group_key(q, p), []).append(j)
        chunk = pow2_at_most(max(1, min(
            MAX_BATCH_KERNEL, SLOT_BUDGET // max(self.table.n_rows, 1))))
        for key, idxs in groups.items():
            for s in range(0, len(idxs), chunk):
                part = idxs[s: s + chunk]
                self._run_chunk(key, [queries[j] for j in part], part, out,
                                bucket_cap=chunk, scores_b=scores_b)
        return out

    # -- cross-shard execution ---------------------------------------------

    def execute_batch_sharded(self, queries: list[MHQ],
                              plans: Optional[list[ExecutionPlan]] = None, *,
                              scores_b: Optional[tuple] = None
                              ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Cross-shard fan-out of a formed batch.

        Without ``plans`` (the exact mode): queries group by (legalized
        clause bucket, k) so every group stacks to one static (B, C, M)
        predicate shape, then each group runs as an EXACT sharded masked
        top-k — every shard masks + local-top-k's its slice of the dense
        score matrices and one O(shards · k) merge yields the global
        result. Underfill there can only mean fewer than k rows genuinely
        qualify.

        With learned ``plans``: groups form exactly like the single-device
        batched path (strategy + legalized grid params + clause bucket),
        and every index-strategy group is routed three ways by the cost
        model (``choose_sharded``): the PLAN-DRIVEN per-shard IVF probing
        path (each shard probes its own index with the group's
        shard-legalized knobs — the learned nprobe/max_scan finally
        operative at shard scale), the exact per-shard dense scan, or the
        plain single-device path when shards are too small to amortize the
        fan-out. filter_first groups keep the exact sharded scan (their
        plan IS the full filtered gather).
        """
        out: list = [None] * len(queries)
        chunk = pow2_at_most(max(1, min(
            MAX_BATCH_KERNEL, SLOT_BUDGET // max(self.table.n_rows, 1))))
        if plans is None:
            groups: dict = {}
            for j, q in enumerate(queries):
                groups.setdefault(
                    (predicates.clause_bucket(q.predicates), q.k),
                    []).append(j)
            for (_, k), idxs in groups.items():
                for s in range(0, len(idxs), chunk):
                    part = idxs[s: s + chunk]
                    self._run_chunk_sharded(
                        [queries[j] for j in part], part, out, k=k,
                        bucket_cap=chunk, scores_b=scores_b)
            return out
        assert len(plans) == len(queries)
        plans = [self.legalize(p) for p in plans]
        groups = {}
        for j, (q, p) in enumerate(zip(queries, plans)):
            groups.setdefault(self._group_key(q, p), []).append(j)
        for key, idxs in groups.items():
            for s in range(0, len(idxs), chunk):
                part = idxs[s: s + chunk]
                qs = [queries[j] for j in part]
                if key[0] == "ff":
                    self._run_chunk_sharded(qs, part, out, k=key[2],
                                            bucket_cap=chunk,
                                            scores_b=scores_b)
                    continue
                if key[0] == "gr":
                    # the sealed graph is one whole-table adjacency, not a
                    # per-shard structure — graph groups always run the
                    # single-device candidate-local walk, whose visited-row
                    # budget is tiny next to any sharded scan
                    self._run_chunk(key, qs, part, out, bucket_cap=chunk,
                                    scores_b=scores_b)
                    continue
                bb = min(next_bucket(len(part)), chunk)
                path = self.dispatcher.choose_sharded(
                    batch=bb, scan=self._group_scan(key),
                    n_shards=self.n_shards,
                    group=("sharded-ivf",) + key[:3],
                    prefer_dense=scores_b is not None)
                if path == SINGLE_DEVICE:
                    self._run_chunk(key, qs, part, out, bucket_cap=chunk,
                                    scores_b=scores_b)
                elif path == SHARDED_LOCAL:
                    self._run_chunk_sharded_ivf(key, qs, part, out,
                                                bucket_cap=chunk)
                else:
                    self._run_chunk_sharded(qs, part, out, k=key[2],
                                            bucket_cap=chunk,
                                            scores_b=scores_b)
        return out

    def _shard_fn(self, k: int):
        """shard_map kernel for this mesh, one jit per k."""
        if k not in self._shard_fns:
            self._shard_fns[k] = sharded_batch_topk(
                self.mesh, self.shard_axes, k=k)
        return self._shard_fns[k]

    # -- plan-driven per-shard IVF probing ----------------------------------

    def _sivf_col(self, col: int):
        """This shard config's per-shard IVF of one column (lazy). Each
        shard keeps the bound index's FULL cluster count — S× finer
        granularity relative to its rows — because the per-shard slot
        budget is the global ``max_scan`` split S ways, and finer clusters
        target those fewer slots much better (measured on the 500k suite:
        probe-tier recall 0.08 → 0.22 and +57% QPS vs dividing C by S).
        The 1-shard configuration reuses the bound index verbatim, so it
        is bit-for-bit the single-device candidate-local path."""
        if col not in self._sivf:
            base = self.indexes[col]
            sivf = build_sharded_ivf(
                self.table.vectors[col], self.n_shards,
                n_clusters=base.n_clusters,
                seed=col, metric=self.table.schema.metric, base_index=base)
            if self.mesh is not None:
                sivf = sivf.placed(self.mesh, self.shard_axes)
            self._sivf[col] = sivf
        return self._sivf[col]

    def _sivf_fn(self, key, act: tuple):
        """jit'd per-shard probing kernel for one (group key, active-column
        set) — all plan params are shard-legalized here, so the static
        grid stays as bounded as the single-device group keys."""
        fkey = (key, act)
        if fkey not in self._sivf_fns:
            k, subs = key[2], key[3]
            shard_subs, total = [], 0
            for (col, k_i, np0, ms, it) in subs:
                sivf = self._sivf_col(col)
                k_s, np_s, ms_s = legalize_for_shard(
                    k_i, np0, ms, n_shards=self.n_shards,
                    shard_len=sivf.shard_len, n_clusters=sivf.n_clusters)
                ks = subquery_width(k_s, ms_s)
                shard_subs.append((act.index(col), k_s, ks, np_s, ms_s, it))
                total += k_s
            pad_total = (rrf_union_total(total) if len(shard_subs) > 1
                         else next_bucket(total, CANDIDATE_PAD_FLOOR))
            self._sivf_fns[fkey] = sharded_ivf_topk(
                self.n_shards, self.mesh, self.shard_axes,
                subs=tuple(shard_subs), k=k,
                metric=self.table.schema.metric, pad_total=pad_total)
        return self._sivf_fns[fkey]

    def _run_chunk_sharded_ivf(self, key, qs: list[MHQ], part: list[int],
                               out: list, *, bucket_cap: int):
        """One plan-driven sharded group chunk: per-shard IVF probing with
        the group's shard-legalized knobs, candidate-local rerank inside
        each shard, one O(shards · k) merge — no dense score matrix is
        ever built. Per-shard BOUNDARY escalation afterwards: a shard that
        kept a full local top-k whose weakest kept score sits at-or-above
        the merged k-th (its truncated local k+1-th row may belong in the
        global top-k) re-runs as an exact masked top-k over ONLY that
        shard-subset's rows; merged underfill keeps the old escalate-all
        fallback. Shards whose boundary is strictly below the merged
        cutoff provably contributed everything relevant and are never
        rescanned."""
        t = self.table
        k, subs = key[2], key[3]  # per-shard probing scores fp32 — the
        # int8 tier targets the single-device candidate-local path, so an
        # int8-precision group routed here keeps the exact scoring
        bb = min(next_bucket(len(qs)), bucket_cap)
        pred_b, qv_b, w_b = self._stack_inputs(qs, bb)
        qsb, wsub, act = self._active_columns(qs, qv_b, w_b)
        sivfs = [self._sivf_col(col) for (col, *_r) in subs]
        fn = self._sivf_fn(key, act)
        ids, scores, fill, bnd, starved = fn(
            tuple(s.centroids for s in sivfs),
            tuple(s.sorted_rows for s in sivfs),
            tuple(s.offsets for s in sivfs),
            t.gather_rows(act), pred_b, qsb, wsub)
        # fill/boundary and the merged ids ride along with the results in
        # one transfer — no mid-chunk host round-trip gates the kernels.
        # The finer trigger fixes "escalation never bites": the merged
        # result almost never underfills (other shards pad it out), so
        # probe losses inside a DOMINANT shard went unnoticed. A shard
        # whose weakest kept score reaches the merged cutoff had its
        # whole contribution rank globally — its probing budget, not the
        # data, bound what it surfaced (a full local top-k was truncated;
        # a shorter one means the probe itself starved) — and only that
        # shard-subset pays the exact retry. A shard strictly below the
        # cutoff provably surfaced everything relevant.
        fill_np = np.asarray(fill)
        bnd_np = np.asarray(bnd)
        ids_np0 = np.asarray(ids)
        sc_np0 = np.asarray(scores)
        under = (ids_np0 >= 0).sum(axis=1) < k  # (bb,) merged underfill
        kth = sc_np0[:, -1]  # merged k-th score (NEG when underfilled)
        need = under[:, None] & (fill_np < k)
        if fill_np.shape[1] > 1:
            # S=1 stays bit-for-bit the single-device candidate-local path:
            # the lone shard's local top-k IS the merge, so its boundary
            # always sits at the cutoff and carries no signal
            need |= ~under[:, None] & (bnd_np >= kth[:, None])
            # a shard whose probe of an iterative subquery qualified fewer
            # than k_i rows is where the single-device path re-expands
            # nprobe (iterative_scan): it takes the exact retry instead
            need |= np.asarray(starved)
        need[len(qs):] = False  # padding queries never escalate
        self.escalated.update(part[j] for j in np.flatnonzero(
            need.any(axis=1)))
        if need.any():
            ids, scores = self._escalate_shards(
                ids, scores, need, k=k, pred_b=pred_b,
                vecs=tuple(t.vectors[i] for i in act), qsb=qsb, wsub=wsub)
            ids_np = np.asarray(ids)
        else:
            ids_np = ids_np0  # already on host — don't transfer twice
        scores_np = np.asarray(scores)
        for pos, j in enumerate(part):
            out[j] = (ids_np[pos], scores_np[pos])

    def _escalate_shards(self, ids, scores, need: np.ndarray, *, k: int,
                         pred_b, vecs: tuple, qsb: tuple, wsub):
        """Exact retry on the underfilled shard-subset: the escalated
        queries re-run as one dense masked top-k restricted (allow mask)
        to the rows of their underfilled shards (``_sharded_exact_retry``
        — streaming the rows once beats gathering qualifying rows at
        arbitrary width), and a dedup-by-id merge folds the escalated
        candidates into the probed results. Probe-found rows keep the
        probe path's exact float scores through the merge (first
        occurrence wins), so escalation can only ADD rows, never perturb
        the well-filled shards' results."""
        t = self.table
        s_count = need.shape[1]
        shard_len = -(-t.n_rows // s_count)
        sel = np.flatnonzero(need.any(axis=1))
        sel_p = pad_selection(sel)
        cur_ids = ids[jnp.asarray(sel_p)]
        cur_sc = scores[jnp.asarray(sel_p)]
        # ONE batched dense retry for the whole subset, shard scope
        # enforced by the allow mask. Under the boundary trigger the mask
        # is genuinely strict: typically a single dominant shard per
        # escalated query, so only shard_len rows are rescanned — the
        # well-filled shards never pay the retry.
        rq_j = jnp.asarray(sel_p)
        need_p = np.array(need[sel_p])
        need_p[len(sel):] = False  # padding rows draw nothing
        e_ids, e_sc = _sharded_exact_retry(
            vecs, t.scalars, predicates.take(pred_b, sel_p),
            tuple(q[rq_j] for q in qsb), wsub[rq_j],
            jnp.asarray(need_p),
            shard_len=min(shard_len, t.n_rows), k=k,
            metric=t.schema.metric)
        cur_ids, cur_sc = merge_topk_unique(
            jnp.concatenate([cur_ids, e_ids], axis=1),
            jnp.concatenate([cur_sc, e_sc], axis=1), k)
        sel_j = jnp.asarray(sel)
        ids = ids.at[sel_j].set(cur_ids[: len(sel)])
        scores = scores.at[sel_j].set(cur_sc[: len(sel)])
        return ids, scores

    def _run_chunk_sharded(self, qs: list[MHQ], part: list[int], out: list,
                           *, k: int, bucket_cap: int,
                           scores_b: Optional[tuple] = None):
        """One sharded group chunk, dispatcher-routed.

        The sharded scan is EXACT, so its candidate-local variant must be
        too: the per-query qualifying-row count (from the predicate masks,
        which cost no GEMM) is the group's candidate budget — when it
        clears the crossover, the chunk runs as an exact fused gather+score
        over only the qualifying rows instead of the dense (bb, n)
        weighted-score scan. The gather width is a STATIC cap estimated
        from the selectivity histograms (margin + slack over the largest
        per-query estimate), so no host sync gates the kernels; the true
        counts ride back with the results, and any query whose count
        overflowed the cap re-runs at the exact width (overflow
        escalation) — under-shooting estimates cost one retry, never
        exactness. Without histograms the old one-sync-per-chunk sizing
        remains. A bound device mesh pins the group to the dense shard_map
        kernel (the fan-out IS the point there); the decision is still
        recorded."""
        t = self.table
        bb = min(next_bucket(len(qs)), bucket_cap)
        pred_b, qv_b, w_b = self._stack_inputs(qs, bb)
        if self.mesh is not None:
            self.dispatcher.choose(batch=bb, scan=t.n_rows,
                                   group=("sharded-mesh", k), force=DENSE)
            _, weighted_scores = self._chunk_scores(
                qs, part, bb, qv_b, w_b, scores_b)
            out_ids, out_scores = self._shard_fn(k)(
                weighted_scores(), t.scalars, pred_b)
        else:
            mask = _eval_mask_batch(pred_b, t.scalars)
            prefer_dense = scores_b is not None
            n_qual = None
            estimated = False
            if self.dispatcher.pins_dense(prefer_dense):
                mc = t.n_rows  # candidate-local impossible: skip the sync
            elif self.hists is not None:
                # histogram-estimated static cap — stats only, no (bb, n)
                # mask reduction blocks the host before the gather launches
                est = float(np.max(np.asarray(
                    _selectivity_batch(self.hists, pred_b)))) * t.n_rows
                mc = min(next_bucket(max(
                    int(np.ceil(est * CAP_MARGIN)) + CAP_SLACK, k, 1)),
                    next_bucket(t.n_rows))
                estimated = mc < next_bucket(t.n_rows)
            else:
                # one host sync per chunk sizes the candidate-local gather
                n_qual = np.asarray(jnp.sum(mask, axis=1))
                mc = min(next_bucket(max(int(n_qual.max()), k, 1)),
                         next_bucket(t.n_rows))
            path = self.dispatcher.choose(batch=bb, scan=mc,
                                          group=("sharded", k),
                                          prefer_dense=prefer_dense)
            if path == CANDIDATE_LOCAL:
                qsb, wsub, act = self._active_columns(qs, qv_b, w_b)
                rows = t.gather_rows(act)
                rows_b = _qualifying_rows_batch(mask, size=mc)
                out_ids, out_scores, _ = _gather_rerank_batch(
                    rows_b, rows, qsb, wsub, k=k, metric=t.schema.metric)
                if estimated:
                    # true counts ride back with the result transfer
                    if n_qual is None:
                        n_qual = np.asarray(jnp.sum(mask, axis=1))
                    over = np.flatnonzero(n_qual[: len(qs)] > mc)
                    if over.size:
                        out_ids, out_scores = self._regather_overflow(
                            mask, n_qual, over, out_ids, out_scores,
                            rows, qsb, wsub, k=k)
            else:
                _, weighted_scores = self._chunk_scores(
                    qs, part, bb, qv_b, w_b, scores_b)
                out_ids, out_scores = sharded_topk_ref(
                    weighted_scores(), mask, k=k, n_shards=self.n_shards)
        ids_np, scores_np = np.asarray(out_ids), np.asarray(out_scores)
        for pos, j in enumerate(part):
            out[j] = (ids_np[pos], scores_np[pos])

    def _regather_overflow(self, mask, n_qual: np.ndarray, over: np.ndarray,
                           out_ids, out_scores, rows, qsb, wsub, *, k: int):
        """Overflow escalation of the histogram-capped exact gather: the
        queries whose true qualifying count exceeded the static cap re-run
        at their exact width, so an under-shooting estimate can never drop
        qualifying rows."""
        t = self.table
        sel_p = pad_selection(over)
        sel_j = jnp.asarray(sel_p)
        mc2 = min(next_bucket(max(int(n_qual[over].max()), k, 1)),
                  next_bucket(t.n_rows))
        rows2 = _qualifying_rows_batch(
            jnp.asarray(mask)[sel_j], size=mc2)
        ids2, sc2, _ = _gather_rerank_batch(
            rows2, rows, tuple(q[sel_j] for q in qsb), wsub[sel_j],
            k=k, metric=t.schema.metric)
        sel = jnp.asarray(over)
        out_ids = jnp.asarray(out_ids).at[sel].set(ids2[: len(over)])
        out_scores = jnp.asarray(out_scores).at[sel].set(sc2[: len(over)])
        return out_ids, out_scores

    def _stack_inputs(self, qs: list[MHQ], bb: int):
        """Batch inputs padded (by repeating the first query) to bucket bb."""
        qpad = qs + [qs[0]] * (bb - len(qs))
        pred_b = predicates.stack([q.predicates for q in qpad])
        qv_b = tuple(jnp.stack([q.query_vectors[i] for q in qpad])
                     for i in range(self.table.schema.n_vec))
        w_b = jnp.asarray([q.weights for q in qpad], jnp.float32)
        return pred_b, qv_b, w_b

    def _chunk_scores(self, qs: list[MHQ], part: list[int], bb: int,
                      qv_b: tuple, w_b, scores_b: Optional[tuple]):
        """(col_scores, weighted_scores) closures for one chunk, gathering
        rows of the whole-batch dense matrices when ``scores_b`` is given."""
        t = self.table
        n_vec = t.schema.n_vec
        w_np = np.asarray([q.weights for q in qs], np.float32)
        scores_cache: dict = {}
        rows_idx = jnp.asarray(
            part + [part[0]] * (bb - len(part))) if scores_b is not None \
            else None

        def col_scores(i):
            if i not in scores_cache:
                scores_cache[i] = scores_b[i][rows_idx] \
                    if scores_b is not None else \
                    _dense_scores(t.vectors[i], qv_b[i],
                                  metric=t.schema.metric)
            return scores_cache[i]

        def weighted_scores():
            """Σ_i w_i · sim_i over every column some query weights."""
            ws = None
            for i in range(n_vec):
                if not np.any(np.abs(w_np[:, i]) > 0):
                    continue  # exact: a zero weight contributes exactly 0
                s = w_b[:, i, None] * col_scores(i)
                ws = s if ws is None else ws + s
            return ws if ws is not None \
                else jnp.zeros((bb, t.n_rows), jnp.float32)

        return col_scores, weighted_scores

    def _run_chunk(self, key, qs: list[MHQ], part: list[int], out: list,
                   *, bucket_cap: int, scores_b: Optional[tuple] = None):
        t = self.table
        bb = min(next_bucket(len(qs)), bucket_cap)
        precision = key[4] if key[0] in ("ix", "gr") else "fp32"
        # graph groups have no dense variant: the walk's whole point is to
        # touch O(hops·beam·degree) rows, so a (B, n) score matrix buys
        # nothing — they pin candidate-local (the decision is still logged)
        force = CANDIDATE_LOCAL if key[0] == "gr" else None
        path = self.dispatcher.choose(batch=bb, scan=self._group_scan(key),
                                      group=key[:3], force=force,
                                      prefer_dense=scores_b is not None,
                                      precision=precision)
        pred_b, qv_b, w_b = self._stack_inputs(qs, bb)
        if key[0] == "ff":
            method = flat.compaction_method(t.n_rows, key[3])
            self.counts[f"ff_rows_{method}"] += len(qs)

        if path == CANDIDATE_LOCAL:
            out_ids, out_scores = self._run_chunk_local(
                key, qs, pred_b, qv_b, w_b)
        else:
            col_scores, weighted_scores = self._chunk_scores(
                qs, part, bb, qv_b, w_b, scores_b)
            if key[0] == "ff":
                _, _, k, mc = key
                out_ids, out_scores, _, _ = _filter_first_batch(
                    weighted_scores(), t.scalars, pred_b,
                    k=k, max_candidates=mc)
            else:
                k, subs = key[2], key[3]
                cand = [self._batched_subquery(col, col_scores(col), pred_b,
                                               qv_b[col], k_i, np0, ms, it)
                        for (col, k_i, np0, ms, it) in subs]
                rows_b = self._union_candidates(cand, subs)
                out_ids, out_scores = _rerank_batch(
                    weighted_scores(), rows_b, k=k, total=rows_b.shape[1])
        ids_np, scores_np = np.asarray(out_ids), np.asarray(out_scores)
        for pos, j in enumerate(part):
            out[j] = (ids_np[pos], scores_np[pos])

    def _run_chunk_local(self, key, qs: list[MHQ], pred_b, qv_b, w_b):
        """Candidate-local execution of one group chunk: only the legalized
        candidate budget is ever gathered/scored — no (bb, n) score matrix.
        Subqueries run through ``ivf.search_local_batch`` (or its int8
        two-stage variant when the group's plan precision says so — the
        candidate union that reaches the final weighted rerank below is
        then already fp32-exact per column) and the re-rank / filter-first
        through the fused gather+score kernel path."""
        t = self.table
        if key[0] == "ff":
            _, _, k, mc = key
            out_ids, out_scores, _, _ = flat.filter_first_local_batch(
                t.gather_rows(), pred_b, qv_b, w_b, k=k,
                max_candidates=mc, n_vec=t.schema.n_vec,
                metric=t.schema.metric)
            return out_ids, out_scores
        k, subs, precision = key[2], key[3], key[4]
        if key[0] == "gr":
            cand = [self._graph_subquery(col, pred_b, qv_b[col], k_i, bw, nh)
                    for (col, k_i, bw, nh) in subs]
        else:
            cand = [self._batched_subquery(col, None, pred_b, qv_b[col], k_i,
                                           np0, ms, it, local=True,
                                           precision=precision)
                    for (col, k_i, np0, ms, it) in subs]
        rows_b = self._union_candidates(cand, subs)
        qsb, wsub, act = self._active_columns(qs, qv_b, w_b)
        out_ids, out_scores, _ = _gather_rerank_batch(
            rows_b.astype(jnp.int32), t.gather_rows(act), qsb, wsub,
            k=k, metric=t.schema.metric)
        return out_ids, out_scores

    def _active_columns(self, qs: list[MHQ], qv_b: tuple, w_b):
        """Restrict (queries, weights) to columns some query in the chunk
        actually weights — a zero weight contributes exactly 0, so the
        candidate-local re-rank need not gather those columns at all.
        Returns (queries, weights, active column ids)."""
        w_np = np.asarray([q.weights for q in qs], np.float32)
        act = tuple(i for i in range(self.table.schema.n_vec)
                    if np.any(np.abs(w_np[:, i]) > 0))
        qsb = tuple(qv_b[i] for i in act)
        wsub = w_b[:, jnp.asarray(act, jnp.int32)] if act else w_b[:, :0]
        return qsb, wsub, act

    @staticmethod
    def _pad_candidates(cand: list):
        """Concat per-column candidate ids and pad the union to a
        power-of-two bucket (-1 = empty slot)."""
        rows_b = jnp.concatenate(cand, axis=1)
        total = next_bucket(rows_b.shape[1], CANDIDATE_PAD_FLOOR)
        if total > rows_b.shape[1]:
            rows_b = jnp.pad(rows_b, ((0, 0), (0, total - rows_b.shape[1])),
                             constant_values=-1)
        return rows_b

    def _union_candidates(self, cand_wide: list, subs):
        """Candidate union of one ix-group chunk from the columns' WIDE
        ranked lists: each column's exact top-k_i block (the engine
        contract — those rows are always reranked), then, for multi-column
        groups, RRF-fused extras drawn from the probed tails filling the
        padded bucket (``executor.rrf_extras``). A global top-k row can
        rank below top-k_i in every column on weight-skewed queries; the
        fused extras recover it when its COMBINED ranks are strong, at
        zero extra probing cost — the tails were already ranked. Widths
        are all derived from the static group key, so the jit cache stays
        bounded; single-column groups keep the plain truncate-and-pad
        union (fusion of one ranking is that ranking)."""
        kis = tuple(s[1] for s in subs)
        cand = [cw[:, :ki] for cw, ki in zip(cand_wide, kis)]
        if len(cand_wide) < 2:
            return self._pad_candidates(cand)
        base = jnp.concatenate(cand, axis=1)
        sum_ki = base.shape[1]
        extras = rrf_extras(tuple(cand_wide), kis=kis,
                            n_extra=rrf_union_total(sum_ki) - sum_ki)
        return jnp.concatenate([base, extras], axis=1)

    def _graph_subquery(self, col: int, pred_b, q_b, k_i: int,
                        beam_width: int, n_hops: int):
        """One column's predicate-aware graph walk for the whole chunk.
        Returns ranked candidate ids at the padded probe width (bb, ks),
        ks ≥ k_i — the same contract as ``_batched_subquery``, so the RRF
        union and rerank downstream are strategy-agnostic. No re-expansion
        ladder: the walk's budget is fixed by (beam_width, n_hops) and
        underfill escalation happens at the plan level (default_plan)."""
        t = self.table
        ks = subquery_width(k_i, t.n_rows)
        ids, _, _, _ = graph.search_local_batch(
            self.graphs[col], t.gather_rows((col,)), pred_b, q_b,
            beam_width=beam_width, n_hops=n_hops, k=ks)
        return ids

    def _batched_subquery(self, col: int, rs_b, pred_b, q_b, k_i: int,
                          nprobe: int, max_scan: int, iterative: bool,
                          *, local: bool = False, precision: str = "fp32"):
        """One column's filtered subquery for the whole chunk, with grouped
        iterative re-expansion. Returns ranked candidate ids at the FULL
        padded probe width (bb, ks), ks ≥ k_i: callers take the top-k_i
        prefix as the exact union block and feed the tail to RRF fusion
        (``_union_candidates``).

        Dense mode (``local=False``): ``rs_b`` (bb, n) holds the column's
        dense scores, so re-expansion rounds never re-score vectors — only
        re-select slots. Candidate-local mode gathers and scores only the
        probed slots (``ivf.search_local_batch``); re-expansion re-gathers
        the underfilled subset at the doubled nprobe. Each round narrows to
        the still-underfilled SUBSET (padded to its own power-of-two
        bucket), so — like the sequential doubling loop — the extra probing
        work scales with how many queries underfill, not with the group
        size."""
        t, index = self.table, self.indexes[col]
        cap = min(index.n_clusters, self.engine.nprobe_cap)
        ks = subquery_width(k_i, max_scan)

        def probe(np_, pred, qb, rs):
            if local and precision == "int8":
                ids_, _, _, nq = ivf.search_local_batch_int8(
                    index, t.gather_rows((col,)),
                    t.gather_rows((col,), int8=True), pred, qb,
                    nprobe=np_, max_scan=max_scan, k=ks)
            elif local:
                ids_, _, _, nq = ivf.search_local_batch(
                    index, t.gather_rows((col,)), pred, qb,
                    nprobe=np_, max_scan=max_scan, k=ks)
            else:
                ids_, _, _, nq = _search_batch(
                    index, rs, t.scalars, pred, qb,
                    nprobe=np_, max_scan=max_scan, k=ks)
            return ids_, nq

        ids, n_qual = probe(nprobe, pred_b, q_b, rs_b)
        if not iterative:
            return ids
        done = np.asarray(n_qual) >= k_i  # ONE host sync per group round
        # boomlint: ignore[HS001] `done` is already a host-side numpy mask
        # (transferred once above) — this bool() costs no device sync
        while not bool(done.all()) and nprobe < cap:
            nprobe = min(2 * nprobe, cap)
            sel = np.flatnonzero(~done)
            sel_p = pad_selection(sel)
            pred_sub = predicates.take(pred_b, sel_p)
            ids2, nq2 = probe(nprobe, pred_sub, q_b[sel_p],
                              rs_b[sel_p] if rs_b is not None else None)
            ids = ids.at[jnp.asarray(sel)].set(ids2[: len(sel)])
            # boomlint: ignore[HS001] one sync per re-expansion round is
            # the iterative contract (the round count is the doubling
            # ladder, not the batch size — same shape as
            # HybridExecutor._subquery)
            done[sel] = np.asarray(nq2)[: len(sel)] >= k_i
        return ids


# ---------------------------------------------------------------------------
# serving front-end
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeReport:
    n_queries: int
    n_batches: int
    seconds: float
    qps: float
    mean_recall: Optional[float] = None
    recalls: Optional[list] = None
    # async deadline-aware serving (serve/queue.py) dispositions/latency
    n_timed_out: int = 0
    p50_ms: Optional[float] = None
    p99_ms: Optional[float] = None
    # per-group scoring-path dispatch counts ({"dense": .., "candidate_local": ..})
    path_counts: Optional[dict] = None
    # tiered streaming ingest (vectordb/tiered.py): rows inserted, background
    # hot→cold compactions completed, and the cold epoch at report time
    n_inserted: int = 0
    n_compactions: int = 0
    epoch: int = 0
    # semantic result cache (serve/semcache.py): requests resolved at submit
    # time without execution
    n_cache_hits: int = 0
    # multi-tenant serving: {tenant_id: {n_queries, n_ok, n_timed_out,
    # n_cache_hits, mean_recall, qps}} — None key is untenanted traffic
    tenants: Optional[dict] = None

    def describe(self) -> str:
        rec = f", mean recall {self.mean_recall:.3f}" \
            if self.mean_recall is not None else ""
        lat = f", p50 {self.p50_ms:.1f}ms / p99 {self.p99_ms:.1f}ms" \
            if self.p50_ms is not None and self.p99_ms is not None else ""
        to = f", {self.n_timed_out} timed out" if self.n_timed_out else ""
        paths = ""
        if self.path_counts:
            paths = ", paths " + "/".join(
                f"{name}×{cnt}" for name, cnt in sorted(self.path_counts.items()))
        ingest = f", {self.n_inserted} inserted over {self.n_compactions} " \
            f"compactions (epoch {self.epoch})" if self.n_inserted else ""
        cache = f", {self.n_cache_hits} cache hits" if self.n_cache_hits else ""
        tnt = f", {len(self.tenants)} tenants" \
            if self.tenants and len(self.tenants) > 1 else ""
        return (f"{self.n_queries} queries in {self.seconds:.2f}s over "
                f"{self.n_batches} batches ({self.qps:.1f} QPS{rec}{lat}{to}"
                f"{paths}{ingest}{cache}{tnt})")


class ServingEngine:
    """Deployment-shaped batched serving over a fitted ``BoomHQ``.

    Each batch costs ONE fused optimizer dispatch (vmapped features + heads
    + argmax) and one grouped execution pass — versus 2·B host round-trips
    for the per-query loop.
    """

    def __init__(self, boomhq, *, batch_size: int = 32):
        self.bq = boomhq
        self.batch_size = batch_size

    def warmup(self, queries: list[MHQ]) -> None:
        """Populate the jit caches so served batches measure steady state."""
        if queries:
            self.bq.execute_batch(list(queries[: self.batch_size]))

    def serve(self, queries: list[MHQ], *, gt_ids=None
              ) -> tuple[list, ServeReport]:
        """Run the stream in batches. ``gt_ids`` (optional, one ground-truth
        id array per query) enables recall accounting."""
        dispatcher = self._dispatcher()
        if dispatcher is not None:
            dispatcher.take()  # drop warmup decisions from the report
        results: list = []
        n_batches = 0
        t0 = time.perf_counter()
        for s in range(0, len(queries), self.batch_size):
            results.extend(self.bq.execute_batch(
                queries[s: s + self.batch_size]))
            n_batches += 1
        seconds = time.perf_counter() - t0
        recalls = None
        if gt_ids is not None:
            recalls = [recall_at_k(ids, gt)
                       for (ids, _), gt in zip(results, gt_ids)]
        counts = dispatcher.take()[0] if dispatcher is not None else None
        report = ServeReport(
            n_queries=len(queries), n_batches=n_batches, seconds=seconds,
            qps=len(queries) / max(seconds, 1e-9),
            mean_recall=float(np.mean(recalls)) if recalls is not None else None,
            recalls=recalls, path_counts=counts or None)
        return results, report

    def _dispatcher(self) -> Optional[ScoringDispatcher]:
        return getattr(self.bq._batched_executor(), "dispatcher", None)
