"""Predicate-algebra API: builder -> DNF compilation -> evaluation parity
with a pure-NumPy oracle, clause-grid legalization, union selectivity
estimates, clause-folded soft encodings, and the engine-aware default plan."""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.core.executor import MILVUS, PGVECTOR
from repro.core.query import default_plan
from repro.vectordb import algebra, histogram, ivf
from repro.vectordb.algebra import col
from repro.vectordb.predicates import (
    CLAUSE_GRID, MAX_CLAUSES, PredicateSet, Predicates, active_any, as_set,
    clause_bucket, eval_mask, soft_encode, stack, take,
)


# ---------------------------------------------------------------------------
# pure-NumPy oracle over expression trees
# ---------------------------------------------------------------------------

def np_eval(expr, scal: np.ndarray) -> np.ndarray:
    """Reference evaluator: interprets the expression tree directly."""
    if isinstance(expr, algebra.Cond):
        x = scal[:, int(expr.col)]
        return (x >= np.float32(expr.lo)) & (x <= np.float32(expr.hi))
    if isinstance(expr, algebra.And):
        out = np.ones(scal.shape[0], bool)
        for p in expr.parts:
            out &= np_eval(p, scal)
        return out
    if isinstance(expr, algebra.Or):
        out = np.zeros(scal.shape[0], bool)
        for p in expr.parts:
            out |= np_eval(p, scal)
        return out
    assert isinstance(expr, algebra.Not)
    return ~np_eval(expr.part, scal)


def random_expr(rng, scal: np.ndarray, depth: int = 0):
    """Random expression tree over the data's value ranges."""
    m = scal.shape[1]
    r = rng.random()
    if depth >= 3 or r < 0.45:
        c = int(rng.integers(0, m))
        lo, hi = float(scal[:, c].min()), float(scal[:, c].max())
        a, b = sorted(rng.uniform(lo, hi, 2))
        kind = rng.integers(0, 6)
        if kind == 0:
            return col(c).between(a, b)
        if kind == 1:
            return col(c) <= b
        if kind == 2:
            return col(c) > a
        if kind == 3:
            return col(c) == float(rng.choice(scal[:, c]))
        if kind == 4:
            return col(c).below(b)
        vals = rng.choice(np.unique(scal[:, c]),
                          size=min(3, len(np.unique(scal[:, c]))),
                          replace=False)
        return col(c).isin([float(v) for v in vals])
    a = random_expr(rng, scal, depth + 1)
    b = random_expr(rng, scal, depth + 1)
    if r < 0.7:
        return a & b
    if r < 0.9:
        return a | b
    return ~a


@pytest.fixture(scope="module")
def scal4():
    rng = np.random.default_rng(7)
    return np.stack([
        rng.integers(0, 10, 3000).astype(np.float32),
        rng.integers(0, 50, 3000).astype(np.float32),
        rng.lognormal(1.0, 0.6, 3000).astype(np.float32),
        rng.uniform(1.0, 1000.0, 3000).astype(np.float32)], axis=1)


def _check_tree(expr, scal):
    try:
        ps = expr.compile(m=scal.shape[1])
    except ValueError:
        return None  # DNF wider than the clause grid — a legal refusal
    got = np.asarray(eval_mask(ps, jnp.asarray(scal)))
    want = np_eval(expr, scal)
    np.testing.assert_array_equal(got, want)
    assert ps.n_clauses in CLAUSE_GRID
    return ps


def test_random_trees_match_numpy_oracle(scal4):
    """Deterministic sweep (always runs, hypothesis or not)."""
    rng = np.random.default_rng(0)
    compiled = 0
    for _ in range(120):
        if _check_tree(random_expr(rng, scal4), scal4) is not None:
            compiled += 1
    assert compiled > 60  # the clause grid must not be refusing everything


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 100_000))
def test_property_eval_matches_numpy_oracle(seed):
    rng = np.random.default_rng(seed)
    scal = np.stack([
        rng.integers(0, 8, 400).astype(np.float32),
        rng.uniform(-5.0, 5.0, 400).astype(np.float32),
        rng.lognormal(0.5, 1.0, 400).astype(np.float32)], axis=1)
    _check_tree(random_expr(rng, scal), scal)


def test_strict_bounds_at_zero_exclude_zero():
    """NOT (x == 0), x < 0 and x > 0 exclude the zeros: the strict bounds
    step over float32 subnormals, which the device flushes to zero (a
    bound of ±1.4e-45 compared equal to 0.0). Seed 63601 of the property
    test above found it."""
    scal = np.asarray([[0.0], [-1.0], [2.0], [0.0]], np.float32)
    for expr, want in ((~(col(0) == 0.0), [False, True, True, False]),
                       (col(0) < 0.0, [False, True, False, False]),
                       (col(0) > 0.0, [False, False, True, False])):
        got = np.asarray(eval_mask(expr.compile(m=1), jnp.asarray(scal)))
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(np_eval(expr, scal), want)


# ---------------------------------------------------------------------------
# builder / compilation specifics
# ---------------------------------------------------------------------------

def test_builder_shapes_and_grid(scal4):
    ps = (col(3).between(10, 50) | (col(1) == 3)).compile(m=4)
    assert isinstance(ps, PredicateSet)
    assert ps.n_clauses == 2 and bool(ps.clause_valid.all())

    ps3 = col(1).isin([1, 2, 3]).compile(m=4)
    assert ps3.n_clauses == 4  # 3 clauses pad onto the (1, 2, 4) grid
    assert int(np.asarray(ps3.clause_valid).sum()) == 3
    assert clause_bucket(ps3) == 4

    with pytest.raises(ValueError):
        col(1).isin(range(MAX_CLAUSES + 1)).compile(m=4)


def test_compile_resolves_names(tiny_table):
    t = tiny_table
    ps = (col("price").between(10, 500) & (col("brand") == 2)).compile(t.schema)
    scal = np.asarray(t.scalars)
    want = (scal[:, 3] >= 10) & (scal[:, 3] <= 500) & (scal[:, 1] == 2)
    np.testing.assert_array_equal(np.asarray(eval_mask(ps, t.scalars)), want)
    with pytest.raises(KeyError):
        (col("no_such_column") == 1).compile(t.schema)
    with pytest.raises(TypeError):
        algebra.compile(col("price"), t.schema)


def test_unsatisfiable_compiles_to_empty_mask(scal4):
    ps = ((col(2) < 1.0) & (col(2) > 2.0)).compile(m=4)
    assert not np.asarray(eval_mask(ps, jnp.asarray(scal4))).any()


def test_negation_is_exact_complement(scal4):
    e = col(3).between(100.0, 500.0)
    m = np.asarray(eval_mask(e.compile(m=4), jnp.asarray(scal4)))
    mn = np.asarray(eval_mask((~e).compile(m=4), jnp.asarray(scal4)))
    assert np.array_equal(mn, ~m)


def test_predicates_compat_shim_is_c1(scal4):
    p = Predicates.from_conditions(4, {3: (100.0, 500.0)})
    ps = as_set(p)
    assert ps.n_clauses == 1 and bool(ps.clause_valid.all())
    np.testing.assert_array_equal(
        np.asarray(eval_mask(p, jnp.asarray(scal4))),
        np.asarray(eval_mask(ps, jnp.asarray(scal4))))
    assert np.array_equal(np.asarray(active_any(p)), np.asarray(p.active))


def test_stack_and_take_mixed_types(scal4):
    p1 = Predicates.from_conditions(4, {0: (3.0, 3.0)})
    ps = (col(3).between(10, 50) | (col(1) == 3)).compile(m=4)
    st_b = stack([p1, ps])
    assert isinstance(st_b, PredicateSet) and st_b.active.shape == (2, 2, 4)
    masks = np.asarray(jax.vmap(
        lambda p: eval_mask(p, jnp.asarray(scal4)))(st_b))
    np.testing.assert_array_equal(
        masks[0], np.asarray(eval_mask(p1, jnp.asarray(scal4))))
    np.testing.assert_array_equal(
        masks[1], np.asarray(eval_mask(ps, jnp.asarray(scal4))))
    sub = take(st_b, np.asarray([1]))
    assert sub.active.shape == (1, 2, 4)
    # all-conjunctive stacks stay on the cheap C=1 representation
    assert isinstance(stack([p1, p1]), Predicates)


# ---------------------------------------------------------------------------
# selectivity union estimates
# ---------------------------------------------------------------------------

def test_union_selectivity_inclusion_exclusion(scal4):
    h = histogram.build(jnp.asarray(scal4), 64)
    # overlapping ranges on one column: union < sum
    e = col(3).between(100, 500) | col(3).between(300, 700)
    est = float(histogram.estimate_selectivity(h, e.compile(m=4)))
    exact = float((((scal4[:, 3] >= 100) & (scal4[:, 3] <= 500))
                   | ((scal4[:, 3] >= 300) & (scal4[:, 3] <= 700))).mean())
    assert abs(est - exact) < 0.06


def test_union_selectivity_bonferroni_upper_bound(scal4):
    h = histogram.build(jnp.asarray(scal4), 64)
    e = col(1).isin([1, 2, 3])  # pads to C=4
    est = float(histogram.estimate_selectivity(h, e.compile(m=4)))
    exact = float(np.isin(scal4[:, 1], [1, 2, 3]).mean())
    assert est >= exact - 0.03  # upper bound (disjoint points: ~tight)
    assert est <= 1.0


# ---------------------------------------------------------------------------
# clause-folded soft encoding
# ---------------------------------------------------------------------------

def test_soft_encode_folds_clauses(scal4):
    edges = jnp.asarray(np.stack([
        np.linspace(scal4[:, i].min(), scal4[:, i].max() * 1.001, 9)
        for i in range(4)]))
    p1 = Predicates.from_conditions(4, {3: (100.0, 500.0)})
    np.testing.assert_allclose(
        np.asarray(soft_encode(as_set(p1), edges)),
        np.asarray(soft_encode(p1, edges)), atol=1e-6)  # C=1 == old rule
    ps = (col(3).between(100, 300) | col(3).between(600, 900)).compile(m=4)
    enc = np.asarray(soft_encode(ps, edges))
    assert enc.shape == (4, 8)
    np.testing.assert_allclose(enc.sum(axis=1), 1.0, atol=1e-5)
    # both lobes of the OR must carry mass
    bin_lo = np.asarray(edges[3])[:-1]
    lobe1 = enc[3][(bin_lo >= 50) & (bin_lo <= 350)].sum()
    lobe2 = enc[3][(bin_lo >= 550) & (bin_lo <= 950)].sum()
    assert lobe1 > 0.1 and lobe2 > 0.1


# ---------------------------------------------------------------------------
# DNF through the search substrate + engine-aware default plan
# ---------------------------------------------------------------------------

def test_ivf_search_respects_dnf(tiny_table):
    t = tiny_table
    idx = ivf.build(t.vectors[0], 16, metric=t.schema.metric)
    ps = ((col("category") == 3) | (col("category") == 5)).compile(t.schema)
    q = jnp.asarray(np.asarray(t.vectors[0][3]))
    ids, _, _, _ = ivf.search(idx, t.vectors[0], t.scalars, ps, q,
                              nprobe=16, max_scan=t.n_rows, k=10)
    scal = np.asarray(t.scalars)
    for i in np.asarray(ids):
        if i >= 0:
            assert scal[i, 0] in (3.0, 5.0)


def test_default_plan_respects_engine_caps():
    free = default_plan(2, PGVECTOR)
    assert free == default_plan(2)  # pgvector exposes everything
    clamped = default_plan(2, MILVUS)
    for s in clamped.subqueries:
        assert s.max_scan == MILVUS.default_max_scan
        assert not s.iterative
        assert s.nprobe <= MILVUS.nprobe_cap
