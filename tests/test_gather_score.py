"""Candidate-local gather+score kernel parity harness.

Three implementations of the same contract are pinned against each other:

  * the Pallas kernel (``use_kernel=True, interpret=True`` — the exact
    program a TPU backend would tile through Mosaic, executed by the
    interpreter on CPU);
  * the pure-jnp reference (``kernels.ref.gather_score_ref``, the off-TPU
    serving path);
  * an independent float64 NumPy oracle built here from ``tests/oracle.py``
    primitives (mask + similarity share no code with repro kernels).

Sweeps cover every clause bucket (C=1/2/4 plus the conjunctive shim), both
metrics (ip/l2), non-power-of-two candidate counts, duplicate and -1-padded
candidate rows, S < k underfill, and all-filtered-out groups. The vectordb
entry points that wrap the kernel (``ivf.search_local_batch``,
``flat.filter_first_local_batch``) are oracle-pinned at the bottom.
"""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

import jax.numpy as jnp

from oracle import eval_mask_np, similarity_np, tie_tolerance

from repro.kernels.gather_score import (
    NEG, GatherRows, gather_score_topk, merge_topk_unique,
)
from repro.vectordb.predicates import PredicateSet, Predicates, stack


# ---------------------------------------------------------------------------
# case construction
# ---------------------------------------------------------------------------

def _table(rng, n, dims, m):
    vectors = tuple(jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
                    for d in dims)
    scalars = jnp.asarray(rng.uniform(0, 10, (n, m)), jnp.float32)
    return vectors, scalars


def _random_pred(rng, m, c, *, sel=0.5, conjunctive_shim=False):
    """One random DNF predicate with ``c`` clauses (``c=1`` optionally as the
    conjunctive ``Predicates`` shim — the kernel must accept both)."""
    if conjunctive_shim:
        assert c == 1
        lo = rng.uniform(0, 10 * (1 - sel))
        return Predicates.from_conditions(m, {0: (lo, lo + 10 * sel)})
    clauses = []
    for _ in range(c):
        col = int(rng.integers(0, m))
        lo = rng.uniform(0, 10 * (1 - sel))
        clauses.append({col: (lo, lo + 10 * sel)})
    return PredicateSet.from_clauses(m, clauses, n_clauses=c)


def _candidates(rng, b, s, n, *, dup_frac=0.3, pad_frac=0.2):
    """(b, s) candidate matrix with duplicate rows and -1 padding mixed in."""
    cand = rng.integers(0, n, size=(b, s))
    n_dup = int(s * dup_frac)
    if n_dup and s > 1:
        for row in cand:
            src = rng.integers(0, s, size=n_dup)
            dst = rng.integers(0, s, size=n_dup)
            row[dst] = row[src]
    pad = rng.random(size=(b, s)) < pad_frac
    cand[pad] = -1
    return cand.astype(np.int32)


def _oracle_topk(cand, vectors, qs, weights, scalars, preds, k, metric):
    """Independent float64 oracle over the candidate subset.

    Per query: dedup valid candidate rows, score them exactly, apply the
    NumPy DNF mask, select top-k by (-score, id). Returns (ids (B, k),
    scores (B, k), n_qual (B,)) — ``n_qual`` counts qualifying SLOTS
    (duplicates included), matching the kernel's counter contract."""
    cand = np.asarray(cand)
    scal_np = np.asarray(scalars)
    b, _ = cand.shape
    out_ids = np.full((b, k), -1, np.int64)
    out_scores = np.full((b, k), NEG, np.float64)
    n_qual = np.zeros((b,), np.int64)
    for j in range(b):
        total = np.zeros((scal_np.shape[0],), np.float64)
        for i, v in enumerate(vectors):
            w = float(np.asarray(weights)[j, i])
            if w != 0.0:
                total += w * similarity_np(np.asarray(qs[i])[j],
                                           np.asarray(v), metric)
        mask = eval_mask_np(preds[j], scal_np) if preds is not None \
            else np.ones((scal_np.shape[0],), bool)
        slots = cand[j][cand[j] >= 0]
        n_qual[j] = int(np.sum(mask[slots]))
        rows = np.unique(slots)
        rows = rows[mask[rows]]
        order = rows[np.lexsort((rows, -total[rows]))][:k]
        out_ids[j, : len(order)] = order
        out_scores[j, : len(order)] = total[order]
    return out_ids, out_scores, n_qual


def _assert_vs_oracle(ids, scores, o_ids, o_scores, *, atol=1e-3):
    """Float32-vs-float64 tolerant comparison: scores must agree to
    tolerance; a differing id is only acceptable on an oracle score tie."""
    ids, scores = np.asarray(ids), np.asarray(scores)
    filled = o_ids >= 0
    assert np.array_equal(ids >= 0, filled)
    np.testing.assert_allclose(scores[filled], o_scores[filled],
                               atol=atol, rtol=1e-4)
    for j in range(ids.shape[0]):
        for p in np.flatnonzero(ids[j] != o_ids[j]):
            tol = tie_tolerance(float(o_scores[j, p]))
            assert abs(scores[j, p] - o_scores[j, p]) <= tol, (
                j, p, ids[j, p], o_ids[j, p], scores[j, p], o_scores[j, p])


def _run_all_paths(cand, vectors, qs, weights, scalars, pred_b, *, k, metric,
                   block_s=32):
    rows = GatherRows.build(vectors, scalars)
    kern = gather_score_topk(jnp.asarray(cand), rows, qs, weights, pred_b,
                             k=k, metric=metric, use_kernel=True,
                             interpret=True, block_s=block_s)
    ref = gather_score_topk(jnp.asarray(cand), rows, qs, weights, pred_b,
                            k=k, metric=metric, use_kernel=False)
    return kern, ref


def _check_case(rng, *, n, dims, m, b, s, c, k, metric, sel=0.5,
                conjunctive_shim=False, block_s=32):
    vectors, scalars = _table(rng, n, dims, m)
    preds = [_random_pred(rng, m, c, sel=sel,
                          conjunctive_shim=conjunctive_shim)
             for _ in range(b)]
    pred_b = stack(preds)
    qs = tuple(jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
               for d in dims)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (b, len(dims))), jnp.float32)
    cand = _candidates(rng, b, s, n)

    (ids_k, s_k, q_k), (ids_r, s_r, q_r) = _run_all_paths(
        cand, vectors, qs, weights, scalars, pred_b, k=k, metric=metric,
        block_s=block_s)

    # kernel vs reference: identical ids and counters, scores to tolerance
    np.testing.assert_array_equal(np.asarray(ids_k), np.asarray(ids_r))
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               atol=1e-3, rtol=1e-4)

    # both vs the independent float64 oracle
    o_ids, o_scores, o_qual = _oracle_topk(
        cand, vectors, qs, weights, scalars, preds, k, metric)
    np.testing.assert_array_equal(np.asarray(q_r), o_qual)
    _assert_vs_oracle(ids_r, s_r, o_ids, o_scores)
    _assert_vs_oracle(ids_k, s_k, o_ids, o_scores)


# ---------------------------------------------------------------------------
# deterministic corpus
# ---------------------------------------------------------------------------

CORPUS = [
    # (seed, n, dims, m, b, s, c, k, metric)
    (0, 200, (16,), 2, 3, 64, 1, 5, "dot"),
    (1, 200, (16, 8), 3, 2, 33, 1, 5, "l2"),       # non-pow2 S, 2 columns
    (2, 300, (8,), 2, 4, 100, 2, 10, "dot"),       # C=2, non-pow2 S
    (3, 300, (8, 24), 2, 2, 57, 2, 7, "l2"),
    (4, 150, (32,), 4, 3, 48, 4, 10, "dot"),       # C=4 bucket
    (5, 150, (8,), 3, 2, 96, 4, 3, "l2"),
    (6, 120, (8,), 2, 2, 3, 1, 5, "dot"),          # S < k underfill
    (7, 250, (16,), 2, 1, 129, 2, 10, "dot"),      # S % block_s == 1
]


@pytest.mark.parametrize("seed,n,dims,m,b,s,c,k,metric", CORPUS)
def test_kernel_parity_corpus(seed, n, dims, m, b, s, c, k, metric):
    _check_case(np.random.default_rng(seed), n=n, dims=dims, m=m, b=b, s=s,
                c=c, k=k, metric=metric)


INT8_CORPUS = [
    # (seed, n, dims, m, b, s, c, k, metric)
    (21, 200, (16,), 2, 3, 64, 2, 5, "dot"),
    (22, 220, (18, 8), 3, 2, 57, 1, 7, "l2"),  # d % 4 != 0, 2 columns
]


@pytest.mark.parametrize("seed,n,dims,m,b,s,c,k,metric", INT8_CORPUS)
def test_int8_kernel_parity(seed, n, dims, m, b, s, c, k, metric):
    """The quantized tier's packed row views (four int8 lanes per word,
    then the row's dequant scale): kernel vs reference over the same int8
    replicas — same counters, scores to tolerance, ids up to ties."""
    from repro.kernels.int8_scan import quantize_rows

    rng = np.random.default_rng(seed)
    vectors, scalars = _table(rng, n, dims, m)
    q8 = [quantize_rows(v) for v in vectors]
    rows = GatherRows.build(tuple(v for v, _ in q8), scalars,
                            tuple(sc for _, sc in q8))
    pred_b = stack([_random_pred(rng, m, c) for _ in range(b)])
    qs = tuple(jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
               for d in dims)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (b, len(dims))), jnp.float32)
    cand = jnp.asarray(_candidates(rng, b, s, n))
    ids_k, s_k, q_k = gather_score_topk(
        cand, rows, qs, w, pred_b, k=k, metric=metric, use_kernel=True,
        interpret=True, block_s=32)
    ids_r, s_r, q_r = gather_score_topk(
        cand, rows, qs, w, pred_b, k=k, metric=metric, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    _assert_vs_oracle(ids_k, s_k, np.asarray(ids_r), np.asarray(s_r))


def test_kernel_parity_conjunctive_shim():
    """The C=1 conjunctive ``Predicates`` shim must hit the same path as a
    one-clause ``PredicateSet``."""
    _check_case(np.random.default_rng(11), n=180, dims=(16,), m=2, b=3, s=40,
                c=1, k=5, metric="dot", conjunctive_shim=True)


def test_all_filtered_out_group():
    """A group whose predicate matches nothing: all ids -1, scores NEG,
    n_qualified 0 — on both the kernel and the reference."""
    rng = np.random.default_rng(21)
    vectors, scalars = _table(rng, 120, (16,), 2)
    pred_b = stack([PredicateSet.from_clauses(
        2, [{0: (100.0, 200.0)}, {1: (-50.0, -40.0)}]) for _ in range(2)])
    qs = (jnp.asarray(rng.normal(size=(2, 16)), jnp.float32),)
    w = jnp.ones((2, 1), jnp.float32)
    cand = _candidates(rng, 2, 64, 120, pad_frac=0.0)
    for use_kernel in (True, False):
        ids, scores, n_qual = gather_score_topk(
            jnp.asarray(cand), GatherRows.build(vectors, scalars), qs, w,
            pred_b, k=5,
            metric="dot", use_kernel=use_kernel, interpret=True, block_s=32)
        assert (np.asarray(ids) == -1).all()
        assert (np.asarray(scores) <= NEG / 2).all()
        assert (np.asarray(n_qual) == 0).all()


def test_duplicates_never_crowd_out_distinct_rows():
    """A candidate list dominated by copies of one row must still surface k
    DISTINCT qualifying rows: duplicates are knocked out by row id inside
    each block and deduplicated again at the merge."""
    rng = np.random.default_rng(31)
    vectors, scalars = _table(rng, 100, (8,), 1)
    total = np.asarray(vectors[0] @ rng.normal(size=(8,)))  # just for rows
    best = int(np.argmax(total))
    k = 5
    others = [r for r in range(20) if r != best][: 2 * k]
    cand = np.asarray([[best] * 40 + others + [-1] * 6], np.int32)
    qs = (jnp.asarray(rng.normal(size=(1, 8)), jnp.float32),)
    w = jnp.ones((1, 1), jnp.float32)
    pred_b = stack([Predicates.none(1)])
    for use_kernel in (True, False):
        ids, scores, n_qual = gather_score_topk(
            jnp.asarray(cand), GatherRows.build(vectors, scalars), qs, w,
            pred_b, k=k,
            metric="dot", use_kernel=use_kernel, interpret=True, block_s=16)
        got = np.asarray(ids)[0]
        assert (got >= 0).all()
        assert len(set(got.tolist())) == k  # k distinct rows
        assert int(np.asarray(n_qual)[0]) == 40 + len(others)


def test_pred_none_skips_masking():
    """pred=None (pre-qualified candidates, the rerank-union path) must
    score every valid slot."""
    rng = np.random.default_rng(41)
    vectors, scalars = _table(rng, 90, (8,), 2)
    qs = (jnp.asarray(rng.normal(size=(2, 8)), jnp.float32),)
    w = jnp.ones((2, 1), jnp.float32)
    cand = _candidates(rng, 2, 48, 90, pad_frac=0.25)
    o_ids, o_scores, _ = _oracle_topk(cand, vectors, qs, w, scalars, None,
                                      5, "dot")
    for use_kernel in (True, False):
        ids, scores, n_qual = gather_score_topk(
            jnp.asarray(cand), GatherRows.build(vectors, scalars), qs, w,
            None, k=5,
            metric="dot", use_kernel=use_kernel, interpret=True, block_s=16)
        np.testing.assert_array_equal(
            np.asarray(n_qual), np.sum(cand >= 0, axis=1))
        _assert_vs_oracle(ids, scores, o_ids, o_scores)


def test_merge_topk_unique_underfill_and_ties():
    """The cross-block merge: duplicates keep one slot, padding never
    surfaces, ties break by smaller row id."""
    ids = jnp.asarray([[7, 3, 7, -1, 3, 9]], jnp.int32)
    scores = jnp.asarray([[1.0, 2.0, 1.0, NEG, 2.0, 2.0]], jnp.float32)
    out_ids, out_scores = merge_topk_unique(ids, scores, 5)
    # 3 and 9 tie at 2.0 -> smaller id first; 7 at 1.0; then empty slots
    np.testing.assert_array_equal(np.asarray(out_ids)[0],
                                  [3, 9, 7, -1, -1])
    assert np.asarray(out_scores)[0, 3] <= NEG / 2


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 100_000), n=st.integers(40, 300),
       d=st.sampled_from([8, 16]), m=st.integers(1, 4),
       b=st.integers(1, 4), s=st.integers(1, 80),
       c=st.sampled_from([1, 2, 4]), k=st.sampled_from([1, 5, 10]),
       metric=st.sampled_from(["dot", "l2"]),
       sel=st.floats(0.05, 1.0))
def test_kernel_parity_property(seed, n, d, m, b, s, c, k, metric, sel):
    """Hypothesis sweep of the same three-way parity over random shapes,
    clause buckets, metrics and selectivities."""
    _check_case(np.random.default_rng(seed), n=n, dims=(d,), m=m, b=b, s=s,
                c=c, k=k, metric=metric, sel=sel, block_s=16)


@pytest.mark.slow
def test_kernel_parity_large_shapes():
    """Interpreter-mode kernel on realistic block/candidate widths (the
    shapes a TPU run would tile) — slow under the interpreter, so marked
    for the tier-1 job only."""
    rng = np.random.default_rng(51)
    _check_case(rng, n=4000, dims=(64, 32), m=4, b=8, s=1024, c=2, k=10,
                metric="dot", block_s=256)
    _check_case(rng, n=4000, dims=(32,), m=3, b=4, s=777, c=4, k=10,
                metric="l2", block_s=256)


# ---------------------------------------------------------------------------
# vectordb candidate-local entry points vs the oracle
# ---------------------------------------------------------------------------

def test_search_local_batch_matches_scored_search(tiny_table):
    """ivf.search_local_batch (fused gather+score) against the dense-scored
    per-query search on the same probes: same probe slots, so the result
    sets agree up to float ties."""
    import jax

    from repro.vectordb import ivf
    from repro.vectordb.table import similarity

    t = tiny_table
    rng = np.random.default_rng(61)
    idx = ivf.build(t.vectors[0], 16, seed=0, metric=t.schema.metric)
    b, k, nprobe, max_scan = 4, 10, 8, 512
    q_b = jnp.asarray(rng.normal(size=(b, t.vectors[0].shape[1])),
                      jnp.float32)
    preds = [_random_pred(rng, t.schema.n_scalar, c, sel=0.6)
             for c in (1, 2, 4, 1)]
    pred_b = stack(preds)
    ids_l, s_l, n_sc, n_q = ivf.search_local_batch(
        idx, t.gather_rows((0,)), pred_b, q_b,
        nprobe=nprobe, max_scan=max_scan, k=k)
    rs_b = jax.vmap(
        lambda q: similarity(q, t.vectors[0], t.schema.metric))(q_b)
    for j in range(b):
        ids_s, s_s, _, n_qs = ivf.search_scored(
            idx, rs_b[j], t.scalars, preds[j], q_b[j],
            nprobe=nprobe, max_scan=max_scan, k=k)
        assert int(n_q[j]) == int(n_qs)
        # same candidate slots -> same top-k SET up to float ties
        np.testing.assert_allclose(
            np.sort(np.asarray(s_l[j])), np.sort(np.asarray(s_s)),
            atol=1e-3, rtol=1e-4)


def test_filter_first_local_batch_matches_sequential(tiny_table):
    """flat.filter_first_local_batch vs the sequential filter_first on the
    same cap: identical counters, score parity, tie-tolerant ids."""
    from repro.vectordb import flat

    t = tiny_table
    rng = np.random.default_rng(71)
    b, k, cap = 3, 10, 256
    preds = [_random_pred(rng, t.schema.n_scalar, c, sel=0.4)
             for c in (1, 2, 4)]
    pred_b = stack(preds)
    qs = [tuple(jnp.asarray(rng.normal(size=(v.shape[1],)), jnp.float32)
                for v in t.vectors) for _ in range(b)]
    q_b = tuple(jnp.stack([qs[j][i] for j in range(b)])
                for i in range(t.schema.n_vec))
    w = rng.uniform(0.2, 1.0, (b, t.schema.n_vec)).astype(np.float32)
    ids_l, s_l, n_sc, n_q = flat.filter_first_local_batch(
        t.gather_rows(), pred_b, q_b, jnp.asarray(w),
        k=k, max_candidates=cap, n_vec=t.schema.n_vec,
        metric=t.schema.metric)
    for j in range(b):
        ids_s, s_s, n_sc_s, n_q_s = flat.filter_first(
            tuple(t.vectors), t.scalars, preds[j], qs[j],
            jnp.asarray(w[j]), t.schema.metric, k=k, max_candidates=cap,
            n_vec=t.schema.n_vec)
        assert int(n_q[j]) == int(n_q_s)
        assert int(n_sc[j]) == int(n_sc_s)
        np.testing.assert_allclose(np.asarray(s_l[j]), np.asarray(s_s),
                                   atol=1e-3, rtol=1e-4)
