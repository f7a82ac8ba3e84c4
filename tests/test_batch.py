"""Batched serving subsystem: sequential/batched/cross-shard parity, counter
semantics, linear IVF inserts, and the single rewriter decode path."""

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

import jax.numpy as jnp

from repro.bench import datasets, queries
from repro.core.boomhq import BoomHQ, BoomHQConfig
from repro.core.executor import HybridExecutor, plan_columns, recall_at_k
from repro.core.query import ExecutionPlan, SubqueryParams, default_plan
from repro.core.rewriter import MHQRewriter, RewriterConfig, candidate_plans
from repro.serve.batch import (
    BatchedHybridExecutor, ServingEngine, next_bucket, pow2_at_most,
)
from repro.vectordb import flat, ivf
from repro.vectordb.predicates import Predicates, clause_bucket


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------

def test_filter_first_qualified_count_uncapped(tiny_table):
    """n_qualified must be the TRUE qualifying-row count, not min(count,
    max_candidates) — escalation logic reads it."""
    t = tiny_table
    pred = Predicates.none(t.schema.n_scalar)  # everything qualifies
    cap = 64
    assert t.n_rows > cap
    w = jnp.asarray([1.0] + [0.0] * (t.schema.n_vec - 1), jnp.float32)
    _, _, n_scored, n_qual = flat.filter_first(
        tuple(t.vectors), t.scalars, pred,
        tuple(v[0] for v in t.vectors), w, t.schema.metric,
        k=5, max_candidates=cap, n_vec=t.schema.n_vec)
    assert int(n_scored) == cap  # scoring is capped by the gather width
    assert int(n_qual) == t.n_rows  # the true count is not


def _extend_reference(index, new_vectors, first_new_row):
    """The seed's per-row append semantics (quadratic), kept as the oracle."""
    d = (jnp.sum(index.centroids * index.centroids, axis=1)[None, :]
         - 2.0 * (new_vectors @ index.centroids.T))
    assign = np.asarray(jnp.argmin(d, axis=1))
    rows = np.arange(first_new_row, first_new_row + new_vectors.shape[0],
                     dtype=np.int32)
    old_rows = np.asarray(index.sorted_rows)
    old_off = np.asarray(index.offsets)
    buckets = [old_rows[old_off[c]: old_off[c + 1]]
               for c in range(index.n_clusters)]
    for r, a in zip(rows, assign):
        buckets[a] = np.append(buckets[a], r)
    counts = np.array([len(b) for b in buckets])
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return np.concatenate(buckets).astype(np.int32), offsets


def test_ivf_extend_matches_seed_semantics(rng):
    vecs = jnp.asarray(rng.normal(size=(800, 16)), jnp.float32)
    index = ivf.build(vecs, 12, seed=0)
    new = jnp.asarray(rng.normal(size=(137, 16)), jnp.float32)
    ref_rows, ref_off = _extend_reference(index, new, 800)
    ext = ivf.extend(index, new, 800)
    np.testing.assert_array_equal(np.asarray(ext.sorted_rows), ref_rows)
    np.testing.assert_array_equal(np.asarray(ext.offsets), ref_off)


def test_ivf_extend_empty_clusters(rng):
    """Regroup must survive clusters that own zero rows."""
    vecs = jnp.asarray(rng.normal(size=(30, 8)) + 5.0, jnp.float32)
    index = ivf.build(vecs, 8, seed=1)
    new = jnp.asarray(rng.normal(size=(4, 8)) + 5.0, jnp.float32)
    ext = ivf.extend(index, new, 30)
    ref_rows, ref_off = _extend_reference(index, new, 30)
    np.testing.assert_array_equal(np.asarray(ext.sorted_rows), ref_rows)
    np.testing.assert_array_equal(np.asarray(ext.offsets), ref_off)
    assert sorted(np.asarray(ext.sorted_rows).tolist()) == list(range(34))


def test_rrf_extras_fuses_and_excludes():
    """Unit semantics of the RRF fusion kernel: contributions of a row's
    occurrences across columns SUM (dedup), rows already inside a column's
    top-k_i block are excluded from the extras, and the output is ranked
    best-fused first with -1 padding."""
    from repro.core.executor import rrf_extras

    # col A ranking: [10 11 | 20 21 30]   (k_i = 2, tail after |)
    # col B ranking: [12 13 | 21 20 -1]
    a = jnp.asarray([[10, 11, 20, 21, 30]])
    b = jnp.asarray([[12, 13, 21, 20, -1]])
    ex = np.asarray(rrf_extras((a, b), kis=(2, 2), n_extra=4))
    # 20: 1/63 + 1/64;  21: 1/64 + 1/63  (tie, id-order breaks it)
    # 30: 1/65 single-column;  included rows 10..13 must not appear
    assert ex.tolist() == [[20, 21, 30, -1]]

    # a two-column row beats a better-single-rank row when combined:
    # 40 at tail ranks (3, 3) vs 50 at tail rank 3 in one column only
    a2 = jnp.asarray([[1, 2, 40, 50]])
    b2 = jnp.asarray([[3, 4, 40, -1]])
    ex2 = np.asarray(rrf_extras((a2, b2), kis=(2, 2), n_extra=2))
    assert ex2.tolist() == [[40, 50]]


def _skew_weight_fixture():
    """A fixture where the global weighted top-k provably needs rows that
    rank BELOW top-k_i in every column: 'generalist' rows sit at per-column
    ranks 11-14 (k_i = 10) in both columns, but their weighted score beats
    every single-column specialist."""
    from repro.vectordb.table import ScalarCol, Table, TableSchema, VectorCol

    rng = np.random.default_rng(17)
    n, d, m, k = 200, 8, 2, 10
    va = rng.normal(size=(n, d)).astype(np.float32) * 0.01
    vb = rng.normal(size=(n, d)).astype(np.float32) * 0.01
    for j in range(10):   # specialists: top-10 of exactly one column
        va[j, 0] = 10.0 - 0.05 * j
        vb[10 + j, 0] = 10.0 - 0.05 * j
    for j in range(4):    # generalists: rank 11-14 in BOTH columns
        va[20 + j, 0] = 8.5 - 0.01 * j
        vb[20 + j, 0] = 8.5 - 0.01 * j
    schema = TableSchema(
        vector_cols=(VectorCol("v0", d), VectorCol("v1", d)),
        scalar_cols=tuple(ScalarCol(f"s{i}", "num") for i in range(m)))
    t = Table.from_numpy(
        schema, [va, vb], rng.uniform(0, 1, (n, m)).astype(np.float32))
    qa = np.zeros(d, np.float32)
    qa[0] = 1.0
    from repro.core.query import MHQ

    q = MHQ(query_vectors=(jnp.asarray(qa), jnp.asarray(qa)),
            weights=(0.7, 0.3), predicates=Predicates.none(m), k=k)
    w_scores = 0.7 * (va @ qa) + 0.3 * (vb @ qa)
    oracle = set(np.argsort(-w_scores)[:k].tolist())
    # fixture validity: some oracle rows are outside BOTH per-column top-k_i
    top_a = set(np.argsort(-(va @ qa))[:k].tolist())
    top_b = set(np.argsort(-(vb @ qa))[:k].tolist())
    missed = oracle - top_a - top_b
    assert missed == {20, 21, 22, 23}
    return t, q, oracle


def test_rrf_fusion_skew_weight_oracle_floor():
    """Satellite regression: on weight-skewed queries a global top-k row can
    rank below top-k_i in every column, so the truncated per-column union
    loses it no matter how exact the rerank is (recall capped at 0.6 on this
    fixture). RRF (k=60) fusion over the probed tails must recover the
    full oracle top-k — in the batched index_scan path AND the sequential
    executor (parity: both build the same union)."""
    t, q, oracle = _skew_weight_fixture()
    idx = [ivf.build(v, 8, seed=i, metric=t.schema.metric)
           for i, v in enumerate(t.vectors)]
    plan = ExecutionPlan("index_scan", tuple(
        SubqueryParams(k_mult=1, nprobe=8, max_scan=256, iterative=False)
        for _ in range(2)))

    (ids_b, scores_b), = BatchedHybridExecutor(t, idx).execute_batch(
        [q], [plan])
    got_b = set(int(i) for i in np.asarray(ids_b) if i >= 0)
    assert len(got_b & oracle) == q.k, (
        f"batched union missed {sorted(oracle - got_b)} — RRF extras did "
        f"not recover the cross-column rows")

    ids_s, scores_s = HybridExecutor(t, idx).execute(q, plan)
    got_s = set(int(i) for i in np.asarray(ids_s) if i >= 0)
    assert len(got_s & oracle) == q.k
    assert_results_match(ids_s, scores_s, ids_b, scores_b)


def test_predict_delegates_to_plan_codes(rng):
    """predict() and plan_codes->plan_from_codes are one decode path: both
    must produce the same ExecutionPlan on random inputs."""
    in_dim, n_vec = 24, 2
    rew = MHQRewriter(in_dim, n_vec, RewriterConfig(seed=3))
    for i in range(8):
        x = rng.normal(size=(in_dim,)).astype(np.float32)
        via_predict = rew.predict(x)
        codes = np.asarray(rew.plan_codes(rew.params, jnp.asarray(x)))
        via_codes = rew.plan_from_codes(codes)
        assert via_predict == via_codes


# ---------------------------------------------------------------------------
# batched executor parity
# ---------------------------------------------------------------------------

def assert_results_match(ids_s, scores_s, ids_b, scores_b, *, atol=1e-4):
    """Per-query parity up to float reduction order: scores must agree to
    tolerance everywhere, and any position where the ids differ must be a
    float-tie (both candidates' scores equal within atol) — the batched
    path scores via GEMM, the sequential one via gathered matvec, so the
    last ulp may order near-exact ties differently."""
    ids_s, scores_s = np.asarray(ids_s), np.asarray(scores_s)
    ids_b, scores_b = np.asarray(ids_b), np.asarray(scores_b)
    np.testing.assert_allclose(scores_b, scores_s, atol=atol, rtol=1e-5)
    diff = ids_s != ids_b
    if np.any(diff):
        np.testing.assert_allclose(scores_b[diff], scores_s[diff], atol=atol,
                                   err_msg="ids differ on non-tied scores")


def test_bucket_helpers():
    assert [next_bucket(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    assert next_bucket(3, 16) == 16
    assert [pow2_at_most(n) for n in (1, 2, 3, 7, 8, 100)] == [1, 2, 2, 4, 8, 64]


@pytest.fixture(scope="module")
def exec_setup(tiny_table):
    t = tiny_table
    idx = [ivf.build(v, 16, seed=i, metric=t.schema.metric)
           for i, v in enumerate(t.vectors)]
    return t, HybridExecutor(t, idx), BatchedHybridExecutor(t, idx)


def test_batched_executor_parity_all_strategies(exec_setup):
    """Same workload through the sequential loop and the batched path ->
    identical ids and scores per query, across every strategy (incl. the
    iterative re-expansion path) and mixed group sizes."""
    t, seq, bx = exec_setup
    wl = queries.gen_workload(t, 10, n_vec_used=2, seed=3) + \
        queries.gen_workload(t, 5, n_vec_used=1, seed=4)
    grid = candidate_plans(2, weights=(0.9, 0.1)) + [default_plan(2)]
    plans = [grid[j % len(grid)] for j in range(len(wl))]
    batched = bx.execute_batch(wl, plans)
    for q, p, (ids_b, scores_b) in zip(wl, plans, batched):
        ids_s, scores_s = seq.execute(q, p)
        assert_results_match(ids_s, scores_s, ids_b, scores_b)


def test_batched_executor_filter_first_group(exec_setup):
    t, seq, bx = exec_setup
    wl = queries.gen_workload(t, 6, n_vec_used=2, seed=5)
    plan = ExecutionPlan("filter_first",
                         tuple(SubqueryParams() for _ in range(2)),
                         max_candidates=512)
    batched = bx.execute_batch(wl, [plan] * len(wl))
    for q, (ids_b, scores_b) in zip(wl, batched):
        ids_s, scores_s = seq.execute(q, plan)
        assert_results_match(ids_s, scores_s, ids_b, scores_b)


def test_filter_first_chunks_count_their_compaction(exec_setup):
    """Every real filter-first query is counted under the method its
    compaction took; the chunk's padding is not, and the dispatcher's
    path counts gain no keys."""
    t, seq, _ = exec_setup
    bx = BatchedHybridExecutor(t, seq.indexes)
    wl = queries.gen_workload(t, 3, n_vec_used=2, seed=5)  # padded to 4
    plan = ExecutionPlan("filter_first",
                         tuple(SubqueryParams() for _ in range(2)),
                         max_candidates=64)
    assert flat.compaction_method(t.n_rows, 64) == "search"
    batched = bx.execute_batch(wl, [plan] * len(wl))
    assert bx.counts == {"ff_rows_search": 3, "ff_rows_scatter": 0}
    assert set(bx.dispatcher.counts) <= {"dense", "candidate_local"}
    for q, (ids_b, scores_b) in zip(wl, batched):
        ids_s, scores_s = seq.execute(q, plan)
        assert_results_match(ids_s, scores_s, ids_b, scores_b)


def test_batched_executor_parity_mixed_clause_counts(exec_setup):
    """Satellite: batched vs sequential on a batch mixing conjunctive (C=1)
    and DNF (C∈{2,4}) predicates — groups split per clause bucket, every
    query's result must still match the sequential executor."""
    t, seq, bx = exec_setup
    wl = queries.gen_dnf_workload(t, 8, n_vec_used=2, seed=11,
                                  clause_counts=(2, 3, 4)) + \
        queries.gen_workload(t, 4, n_vec_used=2, seed=12)
    buckets = {clause_bucket(q.predicates) for q in wl}
    assert len(buckets) >= 2  # genuinely mixed complexity
    grid = candidate_plans(2, weights=(0.7, 0.3)) + [default_plan(2)]
    plans = [grid[j % len(grid)] for j in range(len(wl))]
    batched = bx.execute_batch(wl, plans)
    for q, p, (ids_b, scores_b) in zip(wl, plans, batched):
        ids_s, scores_s = seq.execute(q, p)
        assert_results_match(ids_s, scores_s, ids_b, scores_b)


# ---------------------------------------------------------------------------
# three-way parity: sequential vs batched vs cross-shard
# ---------------------------------------------------------------------------

def _assert_three_way(t, seq, bx, wl, *, shard_counts=(2, 5)):
    """filter_first with an uncapped gather is the budget at which all three
    paths (sequential, batched, cross-shard exact scan) compute the same
    mathematical result — so parity is well-defined for ANY predicate."""
    plans = [ExecutionPlan("filter_first",
                           tuple(SubqueryParams() for _ in range(q.n_vec)),
                           max_candidates=t.n_rows) for q in wl]
    batched = bx.execute_batch(wl, plans)
    sharded = {s: BatchedHybridExecutor(t, bx.indexes, bx.engine, n_shards=s)
               .execute_batch_sharded(wl) for s in shard_counts}
    for j, (q, p) in enumerate(zip(wl, plans)):
        ids_s, scores_s = seq.execute(q, p)
        ids_b, scores_b = batched[j]
        assert_results_match(ids_s, scores_s, ids_b, scores_b)
        for s in shard_counts:
            ids_x, scores_x = sharded[s][j]
            assert_results_match(ids_s, scores_s, ids_x, scores_x)


def _mixed_wl(t, seed):
    return queries.gen_dnf_workload(t, 5, n_vec_used=2, seed=seed,
                                    clause_counts=(2, 3, 4)) + \
        queries.gen_workload(t, 3, n_vec_used=2, seed=seed + 1)


def test_three_way_parity_seed_corpus(exec_setup):
    """Deterministic sweep (always runs, hypothesis or not): sequential vs
    execute_batch vs cross-shard execute_batch agree (float-tie tolerant)
    on mixed clause-bucket batches, for a divisible (2) and a padded (7)
    shard split of the 1500-row table."""
    t, seq, bx = exec_setup
    for seed in (101, 202):
        wl = _mixed_wl(t, seed)
        assert len({clause_bucket(q.predicates) for q in wl}) >= 2
        _assert_three_way(t, seq, bx, wl, shard_counts=(2, 7))


@pytest.mark.slow
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 50_000))
def test_three_way_parity_property(exec_setup, seed):
    """Hypothesis property sweep of the same three-way parity over random
    mixed clause-bucket workloads."""
    t, seq, bx = exec_setup
    _assert_three_way(t, seq, bx, _mixed_wl(t, seed), shard_counts=(4,))


def test_sharded_executor_mesh_wiring(exec_setup):
    """A bound 1-device mesh routes through the shard_map kernel and must
    reproduce the logical-shard reference bit-for-bit (the multi-device
    equivalence runs in tests/test_distributed.py's subprocess). The
    logical executor pins the dense path — the mesh side is always dense,
    and bit-parity is only defined against the same scoring path."""
    import jax
    from jax.sharding import Mesh

    from repro.serve.batch import DENSE, CostModel

    t, _, bx = exec_setup
    wl = _mixed_wl(t, 77)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    bx_mesh = BatchedHybridExecutor(t, bx.indexes, bx.engine, mesh=mesh)
    bx_log = BatchedHybridExecutor(t, bx.indexes, bx.engine, n_shards=1,
                                   cost_model=CostModel(force=DENSE))
    res_m = bx_mesh.execute_batch_sharded(wl)
    res_l = bx_log.execute_batch_sharded(wl)
    for (im, sm), (il, sl) in zip(res_m, res_l):
        np.testing.assert_array_equal(im, il)
        np.testing.assert_allclose(sm, sl, atol=1e-6)


def test_batched_executor_single_index_group(exec_setup):
    t, seq, bx = exec_setup
    wl = queries.gen_workload(t, 4, n_vec_used=2, seed=6)
    plan = ExecutionPlan(
        "single_index",
        tuple(SubqueryParams(k_mult=4, nprobe=8) for _ in range(2)),
        dominant=1)
    assert plan_columns(wl[0], plan) == (1,)
    batched = bx.execute_batch(wl, [plan] * len(wl))
    for q, (ids_b, scores_b) in zip(wl, batched):
        ids_s, scores_s = seq.execute(q, plan)
        assert_results_match(ids_s, scores_s, ids_b, scores_b)


# ---------------------------------------------------------------------------
# scoring dispatcher: cost-model routing, decision log, per-group crossover
# ---------------------------------------------------------------------------

def test_cost_model_choose():
    from repro.serve.batch import CANDIDATE_LOCAL, DENSE, CostModel

    cm = CostModel(crossover=1.0, overhead=0)
    assert cm.choose(batch=4, scan=100, n_rows=1000) == CANDIDATE_LOCAL
    assert cm.choose(batch=32, scan=100, n_rows=1000) == DENSE
    assert CostModel(crossover=4.0, overhead=0).choose(
        batch=32, scan=100, n_rows=1000) == CANDIDATE_LOCAL
    # the constant per-batch term adds to the candidate-local side
    assert CostModel(crossover=1.0, overhead=700).choose(
        batch=4, scan=100, n_rows=1000) == DENSE
    for force in (DENSE, CANDIDATE_LOCAL):
        assert CostModel(force=force).choose(
            batch=1, scan=1, n_rows=10**9) == force


def test_cost_model_small_batch_overhead_regression():
    """Satellite: the constant per-batch overhead term pins the dispatch
    decisions measured end-to-end on this container
    (``benchmarks/kernels_bench.py overhead_sweep`` + ``serving
    --crossover``): candidate-local serves the 500k suite at B=8 AND B=32
    (measured 1.47x / 4.39x — the stale 0.92x B=8 row did not reproduce),
    dense serves the 60k suite at both batch sizes, and near the crossover
    boundary a tiny batch now falls back to dense where the overhead-free
    model mispredicted candidate-local."""
    from repro.serve.batch import CANDIDATE_LOCAL, DENSE, CostModel

    cm = CostModel()  # the calibrated defaults
    assert cm.choose(batch=8, scan=2048, n_rows=500_000) == CANDIDATE_LOCAL
    assert cm.choose(batch=32, scan=2048, n_rows=500_000) == CANDIDATE_LOCAL
    assert cm.choose(batch=8, scan=2048, n_rows=60_000) == DENSE
    assert cm.choose(batch=32, scan=2048, n_rows=60_000) == DENSE
    # near-boundary tiny batch: the fixed per-batch cost flips it dense
    naive = CostModel(overhead=0)
    assert naive.choose(batch=1, scan=67_000,
                        n_rows=500_000) == CANDIDATE_LOCAL
    assert cm.choose(batch=1, scan=67_000, n_rows=500_000) == DENSE


def test_dispatcher_forced_paths_parity(exec_setup):
    """The two scoring paths forced via a fake cost model must produce the
    same results (float-tie tolerant) on the same workload, and every
    recorded decision must carry the forced path."""
    from repro.serve.batch import CANDIDATE_LOCAL, DENSE, CostModel

    t, seq, bx = exec_setup
    wl = queries.gen_workload(t, 8, n_vec_used=2, seed=91) + \
        queries.gen_dnf_workload(t, 4, n_vec_used=2, seed=92,
                                 clause_counts=(2, 4))
    grid = candidate_plans(2, weights=(0.8, 0.2)) + [default_plan(2)]
    plans = [grid[j % len(grid)] for j in range(len(wl))]
    results = {}
    for force in (DENSE, CANDIDATE_LOCAL):
        bxf = BatchedHybridExecutor(t, bx.indexes, bx.engine,
                                    cost_model=CostModel(force=force))
        results[force] = bxf.execute_batch(wl, plans)
        counts, decisions = bxf.dispatcher.take()
        assert set(counts) == {force}
        assert decisions and all(d["path"] == force for d in decisions)
    for (ids_d, s_d), (ids_l, s_l) in zip(results[DENSE],
                                          results[CANDIDATE_LOCAL]):
        assert_results_match(ids_d, s_d, ids_l, s_l)


def test_dispatcher_crossover_honored_per_group(exec_setup):
    """One batch, two groups with different candidate budgets: the small
    budget clears the crossover (candidate-local) while the full-table
    filter_first group does not (dense) — in the SAME execute_batch call.
    The threshold is per group, never batch-global."""
    from repro.serve.batch import CANDIDATE_LOCAL, DENSE, CostModel

    t, seq, bx = exec_setup
    wl = queries.gen_workload(t, 8, n_vec_used=2, seed=93)
    small = ExecutionPlan(
        "index_scan",
        tuple(SubqueryParams(k_mult=2, nprobe=8, max_scan=64,
                             iterative=False) for _ in range(2)))
    full = ExecutionPlan(
        "filter_first", tuple(SubqueryParams() for _ in range(2)),
        max_candidates=t.n_rows)
    plans = [small, small, small, small, full, full, full, full]
    cm = CostModel(crossover=1.0, overhead=0)
    # ix group budget is per active column ((64+64)/2): 4·64 <= 1500 ->
    # candidate-local; the full-table ff group: 4·1500 > 1500 -> dense
    assert cm.choose(batch=4, scan=64, n_rows=t.n_rows) == CANDIDATE_LOCAL
    assert cm.choose(batch=4, scan=t.n_rows, n_rows=t.n_rows) == DENSE
    bxc = BatchedHybridExecutor(t, bx.indexes, bx.engine, cost_model=cm)
    results = bxc.execute_batch(wl, plans)
    counts, decisions = bxc.dispatcher.take()
    by_group = {d["group"][0]: d["path"] for d in decisions}
    assert by_group == {"ix": CANDIDATE_LOCAL, "ff": DENSE}
    assert counts == {CANDIDATE_LOCAL: 1, DENSE: 1}
    # every decision re-derives from the cost model inputs it logged
    for d in decisions:
        assert d["path"] == cm.choose(batch=d["batch"], scan=d["scan"],
                                      n_rows=t.n_rows)
    # and both groups' results still match the sequential executor
    for q, p, (ids_b, scores_b) in zip(wl, plans, results):
        ids_s, scores_s = seq.execute(q, p)
        assert_results_match(ids_s, scores_s, ids_b, scores_b)


def test_dispatcher_sharded_chunks_route_and_match(exec_setup):
    """execute_batch_sharded routes through the dispatcher too: forcing
    each path must leave the decision log with that path and produce the
    same (exact) results."""
    from repro.serve.batch import CANDIDATE_LOCAL, DENSE, CostModel

    t, _, bx = exec_setup
    wl = _mixed_wl(t, 95)
    results = {}
    for force in (DENSE, CANDIDATE_LOCAL):
        bxf = BatchedHybridExecutor(t, bx.indexes, bx.engine, n_shards=3,
                                    cost_model=CostModel(force=force))
        results[force] = bxf.execute_batch_sharded(wl)
        counts, decisions = bxf.dispatcher.take()
        assert set(counts) == {force}
        assert all(d["group"][0] == "sharded" for d in decisions)
    for (ids_d, s_d), (ids_l, s_l) in zip(results[DENSE],
                                          results[CANDIDATE_LOCAL]):
        assert_results_match(ids_d, s_d, ids_l, s_l)


def test_serve_report_records_path_counts():
    """ServeReport surfaces the dispatcher's per-group path counts and
    describe() renders them; bind_cost_model forces the path end-to-end."""
    from repro.serve.batch import CANDIDATE_LOCAL, DENSE, CostModel

    table = datasets.make("part", rows=1200, seed=2)
    wl = queries.gen_workload(table, 8, n_vec_used=2, seed=21)
    bq = BoomHQ(table, BoomHQConfig(
        n_clusters=8, use_de=False,
        rewriter=RewriterConfig(steps=10, refine_columns=False)))
    try:
        for force in (CANDIDATE_LOCAL, DENSE):
            bq.bind_cost_model(CostModel(force=force))
            engine = ServingEngine(bq, batch_size=4)
            engine.warmup(wl)
            _, rep = engine.serve(wl)
            assert rep.path_counts and set(rep.path_counts) == {force}
            assert f"paths {force}" in rep.describe()
    finally:
        bq.bind_cost_model()


# ---------------------------------------------------------------------------
# end-to-end: batched optimizer + serving engine
# ---------------------------------------------------------------------------

def test_optimize_batch_matches_sequential(fitted):
    bq, test = fitted
    plans_seq = [bq.optimize(q) for q in test]
    plans_bat = bq.optimize_batch(test)
    assert plans_seq == plans_bat


def test_execute_batch_parity_and_recall(fitted):
    """Batched end-to-end serving returns the sequential path's exact ids
    and scores — hence zero recall regression by construction."""
    bq, test = fitted
    batched = bq.execute_batch(test)
    seq_recs, bat_recs = [], []
    for q, (ids_b, scores_b) in zip(test, batched):
        ids_s, scores_s = bq.execute(q)
        assert_results_match(ids_s, scores_s, ids_b, scores_b)
        gt, _ = flat.ground_truth(bq.table, list(q.query_vectors),
                                  list(q.weights), q.predicates, q.k)
        seq_recs.append(recall_at_k(ids_s, gt))
        bat_recs.append(recall_at_k(ids_b, gt))
    assert np.mean(bat_recs) >= np.mean(seq_recs) - 1e-3


def test_serving_engine_reports(fitted):
    bq, test = fitted
    gts = [np.asarray(flat.ground_truth(bq.table, list(q.query_vectors),
                                        list(q.weights), q.predicates,
                                        q.k)[0]) for q in test]
    engine = ServingEngine(bq, batch_size=4)
    engine.warmup(test)
    results, rep = engine.serve(test, gt_ids=gts)
    assert len(results) == len(test)
    assert rep.n_queries == len(test)
    assert rep.n_batches == (len(test) + 3) // 4
    assert rep.qps > 0
    assert rep.mean_recall is not None and 0.0 <= rep.mean_recall <= 1.0
    assert "QPS" in rep.describe()


def test_unfitted_execute_batch_uses_default_plans():
    table = datasets.make("part", rows=1200, seed=2)
    wl = queries.gen_workload(table, 3, n_vec_used=2, seed=7)
    bq = BoomHQ(table, BoomHQConfig(
        n_clusters=8, use_de=False,
        rewriter=RewriterConfig(steps=10, refine_columns=False)))
    plans = bq.optimize_batch(wl)
    assert all(p == default_plan(q.n_vec) for p, q in zip(plans, wl))
    results = bq.execute_batch(wl)
    for q, (ids, scores) in zip(wl, results):
        # parity with the sequential fallback (a query may legitimately
        # qualify fewer than k rows — e.g. an empty-selectivity predicate)
        ids_s, scores_s = bq.execute(q)
        assert_results_match(ids_s, scores_s, ids, scores)


@pytest.mark.parametrize("n_shards", [1, 2])
def test_underfill_escalation_counts(n_shards):
    """A query qualifying fewer than k rows under filter_first is retried
    once; its retry finds no row filter_first had not already returned.
    The single-device and the sharded path count it alike."""
    import dataclasses

    table = datasets.make("part", rows=900, seed=4)
    m = table.schema.n_scalar
    wl = queries.gen_workload(table, 2, n_vec_used=2, seed=7)
    size = float(np.asarray(table.scalars)[0, 2])  # unique per row
    short = dataclasses.replace(
        wl[0], predicates=Predicates.from_conditions(m, {2: (size, size)}))
    full = dataclasses.replace(wl[1], predicates=Predicates.none(m))
    bq = BoomHQ(table, BoomHQConfig(
        n_clusters=8, use_de=False, graph_degree=0,
        rewriter=RewriterConfig(steps=10, refine_columns=False)))
    if n_shards > 1:
        bq.bind_shards(n_shards)
    bq.optimize_batch = lambda qs, **kw: [
        ExecutionPlan("filter_first",
                      tuple(SubqueryParams() for _ in range(q.n_vec)))
        for q in qs]
    results = bq.execute_batch([short, full])
    assert int(np.sum(np.asarray(results[0][0]) >= 0)) == 1
    assert int(np.sum(np.asarray(results[1][0]) >= 0)) == full.k
    assert bq.counts == {"queries_executed": 2, "escalated": 1,
                         "escalation_passes": 1, "escalation_improved": 0}


def test_sharded_serving_engine_matches_ground_truth():
    """ServingEngine over a bind_shards-bound BoomHQ with the cost model
    pinned to the EXACT sharded scan: every served result is the exact
    filtered top-k, and bind_shards() restores single-shard serving. (The
    default cost model routes index groups three ways — per-shard IVF /
    exact scan / single-device — so exactness is only a contract of the
    dense-forced configuration; the learned routes are floored against the
    oracle in tests/test_oracle.py and tests/test_sharded_ivf.py.)"""
    from repro.serve.batch import DENSE, CostModel

    table = datasets.make("part", rows=1200, seed=2)
    wl = queries.gen_workload(table, 6, n_vec_used=2, seed=9)
    bq = BoomHQ(table, BoomHQConfig(
        n_clusters=8, use_de=False,
        rewriter=RewriterConfig(steps=10, refine_columns=False)))
    bq.bind_shards(3).bind_cost_model(CostModel(force=DENSE))
    assert bq._batched_executor().n_shards == 3
    engine = ServingEngine(bq, batch_size=4)
    results, rep = engine.serve(wl)
    assert rep.n_queries == len(wl) and rep.n_batches == 2
    for q, (ids, scores) in zip(wl, results):
        gt_ids, gt_s = flat.ground_truth(table, list(q.query_vectors),
                                         list(q.weights), q.predicates, q.k)
        assert_results_match(gt_ids, gt_s, ids, scores)
    bq.bind_shards().bind_cost_model()
    assert bq._batched_executor().n_shards == 1
