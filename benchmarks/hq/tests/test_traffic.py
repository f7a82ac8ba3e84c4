"""The traffic generator repeats by seed and keeps its strata."""
import dataclasses

import numpy as np
import pytest

from benchmarks.hq import reference, traffic


def _pool(cell, data, seed, n=40, sel_range=(0.0, 1.0)):
    tr = cell.traffic
    m = len(data.scalar_cols)

    def sel(dnfs):
        return reference.qualifying_counts(dnfs, data.scalars, m) \
            / data.n_rows

    return traffic.make_queries(
        data, sel, tr["pool"]["mix"], n, seed=seed, tag=2, k=tr["k"],
        recall_targets=tr["recall_targets"], sel_range=sel_range)


def _same(a, b):
    return (a.tree == b.tree and a.dnf == b.dnf and a.weights == b.weights
            and a.recall_target == b.recall_target
            and all(np.array_equal(x, y) for x, y in zip(a.vectors,
                                                         b.vectors)))


@pytest.mark.parametrize("which", ["part_small", "sift_small"])
def test_pool_repeats_by_seed(which, request):
    cell, data = request.getfixturevalue(which)
    a = _pool(cell, data, 2**31 + 17)
    b = _pool(cell, data, 2**31 + 17)
    c = _pool(cell, data, 5)
    assert all(_same(x, y) for x, y in zip(a, b))
    assert not all(_same(x, y) for x, y in zip(a, c))


@pytest.mark.parametrize("which", ["part_small", "sift_small"])
def test_pool_work_is_fixed_per_cell(which, request):
    """Every seed serves the cell's one set of predicates and recall
    targets, so the qualifying rows, and with them the work, are the same;
    the query vectors and the order are the seed's own."""
    cell, data = request.getfixturevalue(which)
    tr = dict(cell.traffic, pool=dict(cell.traffic["pool"], size=40))
    cell = dataclasses.replace(cell, traffic=tr)
    m = len(data.scalar_cols)

    def sel(dnfs):
        return reference.qualifying_counts(dnfs, data.scalars, m) \
            / data.n_rows

    a = traffic.make_pool(cell, data, sel, 2**31 + 17)
    b = traffic.make_pool(cell, data, sel, 2**31 + 17)
    c = traffic.make_pool(cell, data, sel, 5)
    assert len(a) == len(c) == 40
    assert all(_same(x, y) for x, y in zip(a, b))

    def work(pool):
        return sorted((repr(q.tree), q.recall_target, q.selectivity)
                      for q in pool)

    assert work(a) == work(c)
    by_tree = {repr(q.tree): q for q in c}
    assert not any(all(np.array_equal(x, y) for x, y in zip(
        q.vectors, by_tree[repr(q.tree)].vectors)) for q in a)


def test_data_repeats_by_seed(part_small):
    from benchmarks.hq import data

    cell, d1 = part_small
    d2 = data.make(cell.config["table"], 7)
    d3 = data.make(cell.config["table"], 8)
    assert np.array_equal(np.asarray(d1.vectors[0]), np.asarray(d2.vectors[0]))
    assert np.array_equal(d1.host_scalars, d2.host_scalars)
    assert not np.array_equal(d1.host_scalars, d3.host_scalars)


def test_pool_keeps_strata(part_small):
    """Selectivities spread over the ten bins far more evenly than the raw
    candidates do, and the mix shares hold."""
    cell, data = part_small
    pool = _pool(cell, data, 3, n=60)
    sels = np.asarray([q.selectivity for q in pool])
    hist = np.histogram(sels, bins=10, range=(0, 1))[0]
    assert (hist > 0).sum() >= 6
    assert hist.max() <= 60 // 2
    kinds = [q.kind for q in pool]
    assert kinds.count("conj") == kinds.count("dnf") == 30
    assert all(1 <= len(q.dnf) <= traffic.MAX_CLAUSES for q in pool)
    assert all(abs(sum(q.weights) - 1.0) < 1e-6 for q in pool)


def test_selectivity_range_is_kept(part_small):
    cell, data = part_small
    pool = _pool(cell, data, 4, n=10, sel_range=(0.0, 0.3))
    assert all(q.selectivity <= 0.3 for q in pool)


def test_dnf_expansion_matches_tree_semantics(part_small):
    """The expanded DNF of NOT / IN / AND / OR trees agrees with a direct
    evaluation of the tree on every row."""
    _, data = part_small
    scal = data.host_scalars

    def direct(t):
        op = t[0]
        if op == "range":
            x = scal[:, t[1]]
            return (x >= t[2]) & (x <= t[3])
        if op == "eq":
            return scal[:, t[1]] == t[2]
        if op == "in":
            return np.isin(scal[:, t[1]], np.asarray(t[2], np.float32))
        if op == "not":
            return ~direct(t[1])
        parts = [direct(s) for s in t[1]]
        return np.logical_or.reduce(parts) if op == "or" \
            else np.logical_and.reduce(parts)

    rng = np.random.default_rng(0)
    uniques = {c: np.unique(scal[:, c]) for c in (0, 1)}
    for _ in range(200):
        t = traffic.random_dnf(data, rng, int(rng.choice([2, 3, 4])),
                               uniques)
        got = reference.satisfies(traffic.dedupe(traffic.expand(t)), scal)
        assert np.array_equal(got, direct(t)), t


def test_schedule_same_gaps_every_seed():
    d1, i1 = traffic.schedule(100.0, 10.0, 30, 1)
    d2, i2 = traffic.schedule(100.0, 10.0, 30, 1)
    d3, i3 = traffic.schedule(100.0, 10.0, 30, 2**33)
    assert np.array_equal(d1, d2) and np.array_equal(i1, i2)
    assert not np.array_equal(d1, d3)
    g1, g3 = np.sort(np.diff(d1)), np.sort(np.diff(d3))
    assert len(d1) == len(d3) == 1000
    assert abs(g1.mean() - 0.01) < 1e-3 and abs(g3.mean() - 0.01) < 1e-3
    counts = np.bincount(i1, minlength=30)
    assert counts.max() - counts.min() <= 1
    assert d1.max() < 10.0
