"""Traffic: a pool of distinct hybrid queries, and the open-loop schedule.

The benchmark's own copy of the paper's query generator (BoomHQ §4 "Query
Generation"): predicates over a random subset of the scalar columns
(equality on categoricals, ranges on numerics) or DNF shapes (IN-lists, ORs
of ranges, IN-list AND range, NOT of a range, each optionally AND-ed with
one more range); the selectivity of each candidate measured exactly on the
table and flattened over sub-intervals by oversample-then-stratify; query
vectors uniform within each dimension's data range; and w1 ~ U[0, 1],
w2 = 1 - w1 for two-vector queries.

A predicate is kept two ways: as an expression tree of plain tuples, which
the harness hands to the program's builder algebra, and as the DNF this
module expands it to, which the reference evaluates. Thresholds are float32
values, so both sides compare the stored float32 scalars alike.

Tree nodes: ("range", col, lo, hi) closed, ("eq", col, v), ("in", col,
[v...]), ("not", node), ("or", [nodes]), ("and", [nodes]). A DNF clause is
{col: (lo, hi, lo_open, hi_open)}.
"""
from __future__ import annotations

import dataclasses
import itertools

import numpy as np

from benchmarks.hq.data import Data, seed_words

MAX_CLAUSES = 4  # the widest DNF a query may compile to


@dataclasses.dataclass(frozen=True)
class PoolQuery:
    tree: tuple
    dnf: tuple  # clauses, each a tuple of (col, lo, hi, lo_open, hi_open)
    vectors: tuple  # one (d_i,) float32 array per vector column
    weights: tuple
    k: int
    recall_target: float
    kind: str  # "conj" | "dnf"
    selectivity: float


def f32(v) -> float:
    return float(np.float32(v))


# ---------------------------------------------------------------------------
# predicate shapes
# ---------------------------------------------------------------------------

def _range(scal, c, rng):
    lo, hi = scal[:, c].min(), scal[:, c].max()
    a, b = sorted(rng.uniform(lo, hi, size=2))
    return ("range", int(c), f32(a), f32(b))


def random_conjunction(data: Data, rng) -> tuple:
    scal = data.host_scalars
    m = scal.shape[1]
    cols = rng.choice(m, size=rng.integers(1, m + 1), replace=False)
    parts = []
    for c in cols:
        if data.scalar_cols[c]["kind"] == "cat":
            parts.append(("eq", int(c), f32(rng.choice(scal[:, c]))))
            continue
        lo, hi = scal[:, c].min(), scal[:, c].max()
        a, b = (f32(x) for x in sorted(rng.uniform(lo, hi, size=2)))
        kind = rng.integers(0, 3)
        if kind == 0:
            parts.append(("range", int(c), a, b))
        elif kind == 1:
            parts.append(("range", int(c), -np.inf, b))
        else:
            parts.append(("range", int(c), a, np.inf))
    return ("and", parts)


def random_dnf(data: Data, rng, n_clauses: int, uniques: dict) -> tuple:
    scal = data.host_scalars
    m = scal.shape[1]
    cats = [i for i in range(m) if data.scalar_cols[i]["kind"] == "cat"]
    nums = [i for i in range(m) if data.scalar_cols[i]["kind"] == "num"]

    def in_list(size):
        c = int(rng.choice(cats))
        vals = uniques[c]
        pick = rng.choice(vals, size=min(size, len(vals)), replace=False)
        return ("in", c, [f32(v) for v in pick])

    shape = rng.integers(0, 4)
    if shape == 0 and cats:
        expr = in_list(n_clauses)
    elif shape == 1 and nums:
        expr = ("or", [_range(scal, int(rng.choice(nums)), rng)
                       for _ in range(n_clauses)])
    elif shape == 2 and cats and nums:
        expr = ("and", [in_list(n_clauses),
                        _range(scal, int(rng.choice(nums)), rng)])
    else:
        c = int(rng.choice(nums)) if nums else 0
        expr = ("not", _range(scal, c, rng))
        if n_clauses > 2 and nums:
            expr = ("or", [expr, _range(scal, int(rng.choice(nums)), rng)])
    if rng.random() < 0.5 and nums:
        expr = ("and", [expr, _range(scal, int(rng.choice(nums)), rng)])
    return expr


# ---------------------------------------------------------------------------
# DNF expansion (what the reference evaluates)
# ---------------------------------------------------------------------------

def _meet(a, b):
    """Intersection of two intervals (lo, hi, lo_open, hi_open), or None."""
    if a[0] > b[0]:
        lo, lo_open = a[0], a[2]
    elif b[0] > a[0]:
        lo, lo_open = b[0], b[2]
    else:
        lo, lo_open = a[0], a[2] or b[2]
    if a[1] < b[1]:
        hi, hi_open = a[1], a[3]
    elif b[1] < a[1]:
        hi, hi_open = b[1], b[3]
    else:
        hi, hi_open = a[1], a[3] or b[3]
    if lo > hi or (lo == hi and (lo_open or hi_open)):
        return None
    return (lo, hi, lo_open, hi_open)


def _and(x: list, y: list) -> list:
    out = []
    for cx, cy in itertools.product(x, y):
        clause = dict(cx)
        ok = True
        for c, iv in cy.items():
            iv = iv if c not in clause else _meet(clause[c], iv)
            if iv is None:
                ok = False
                break
            clause[c] = iv
        if ok:
            out.append(clause)
    return out


def expand(tree) -> list:
    """The tree as a list of DNF clauses {col: (lo, hi, lo_open, hi_open)}.
    An empty list is a predicate no row satisfies."""
    op = tree[0]
    if op == "range":
        return [{tree[1]: (tree[2], tree[3], False, False)}]
    if op == "eq":
        return [{tree[1]: (tree[2], tree[2], False, False)}]
    if op == "in":
        return [{tree[1]: (v, v, False, False)} for v in tree[2]]
    if op == "or":
        return [c for t in tree[1] for c in expand(t)]
    if op == "and":
        out = [{}]
        for t in tree[1]:
            out = _and(out, expand(t))
        return out
    if op == "not":
        inner = tree[1]
        if inner[0] == "eq":
            inner = ("range", inner[1], inner[2], inner[2])
        if inner[0] != "range":
            raise ValueError(f"NOT of {inner[0]!r} is not generated")
        _, c, lo, hi = inner
        out = []
        if lo > -np.inf:
            out.append({c: (-np.inf, lo, False, True)})
        if hi < np.inf:
            out.append({c: (hi, np.inf, True, False)})
        return out
    raise ValueError(f"unknown node {op!r}")


def dedupe(clauses: list) -> tuple:
    seen, out = set(), []
    for c in clauses:
        key = tuple(sorted(c.items()))
        if key not in seen:
            seen.add(key)
            out.append(tuple((col,) + iv for col, iv in key))
    return tuple(out)


# ---------------------------------------------------------------------------
# pool
# ---------------------------------------------------------------------------

def stratify(sels: np.ndarray, n: int, bins: int, lo: float, hi: float
             ) -> list:
    """Indices of ``n`` candidates whose selectivities spread evenly over
    ``bins`` sub-intervals of [lo, hi] (paper: regenerate when a
    sub-interval overfills), filled round-robin from the rest."""
    width = (hi - lo) / bins
    buckets = [[] for _ in range(bins)]
    for i, s in enumerate(sels):
        if lo <= s <= hi:
            b = min(int((s - lo) / width) if width > 0 else 0, bins - 1)
            buckets[b].append(i)
    cap = max(1, n // bins)
    chosen = [i for b in buckets for i in b[:cap]]
    rest = [i for b in buckets for i in b[cap:]]
    chosen += rest[: max(0, n - len(chosen))]
    return chosen[:n]


def vector_ranges(data: Data) -> list:
    return [(np.asarray(v.min(0)), np.asarray(v.max(0)))
            for v in data.vectors]


def draw_predicates(data: Data, rng, entry: dict, n: int, uniques: dict
                    ) -> list:
    """``n`` candidate predicates of one mix entry: (tree, dnf clauses)."""
    out = []
    while len(out) < n:
        if entry["kind"] == "conj":
            tree = random_conjunction(data, rng)
        else:
            tree = random_dnf(data, rng, int(rng.choice(entry["clauses"])),
                              uniques)
        dnf = dedupe(expand(tree))
        if 1 <= len(dnf) <= MAX_CLAUSES:
            out.append((tree, dnf))
    return out


def make_queries(data: Data, selectivity_fn, mix: list, n: int, *, seed: int,
                 tag: int, k: int, recall_targets, bins: int = 10,
                 oversample: int = 6, sel_range=(0.0, 1.0),
                 vector_seed: int | None = None) -> list:
    """``n`` queries split over the ``mix`` entries by their ``share``;
    ``selectivity_fn(dnfs) -> (P,)`` measures candidates on the table.

    ``seed`` draws the predicates and recall targets. With ``vector_seed``
    the query vectors, weights and the pool's order come from that seed
    instead, so every vector seed serves the same predicates: the same
    qualifying-row counts, the same work."""
    rng = np.random.default_rng(seed_words(seed, tag))
    vrng = (rng if vector_seed is None
            else np.random.default_rng(seed_words(vector_seed, tag, 1)))
    uniques = {c: np.unique(data.host_scalars[:, c])
               for c, sc in enumerate(data.scalar_cols)
               if sc["kind"] == "cat"}
    ranges = vector_ranges(data)
    n_vec = len(data.vectors)
    counts = [int(round(e["share"] * n)) for e in mix]
    counts[-1] = n - sum(counts[:-1])
    out = []
    for entry, cnt in zip(mix, counts):
        if cnt <= 0:
            continue
        cands = draw_predicates(data, rng, entry, cnt * oversample, uniques)
        sels = np.asarray(selectivity_fn([d for _, d in cands]))
        chosen = stratify(sels, cnt, bins, *sel_range)
        if len(chosen) < cnt:
            raise ValueError(f"only {len(chosen)} of {cnt} {entry['kind']} "
                             f"predicates fall in {sel_range}")
        used = int(entry.get("n_vec_used", n_vec))
        for i in chosen:
            vecs = tuple(vrng.uniform(lo, hi).astype(np.float32)
                         for lo, hi in ranges)
            if used == 1 or n_vec == 1:
                w = tuple(1.0 if j == 0 else 0.0 for j in range(n_vec))
            else:
                w1 = f32(vrng.uniform(0.0, 1.0))
                w = (w1, f32(1.0 - w1)) + (0.0,) * (n_vec - 2)
            out.append(PoolQuery(
                tree=cands[i][0], dnf=cands[i][1], vectors=vecs, weights=w,
                k=k, recall_target=float(rng.choice(recall_targets)),
                kind=entry["kind"], selectivity=float(sels[i])))
    order = vrng.permutation(len(out))
    return [out[i] for i in order]


def make_pool(cell, data: Data, selectivity_fn, seed: int) -> list:
    """The cell's query pool: its predicates and recall targets from the
    configuration's ``data_seed``, fixed for the cell as a benchmark's
    query set is; its query vectors and order from ``seed``."""
    tr, ps = cell.traffic, cell.traffic["pool"]
    return make_queries(
        data, selectivity_fn, ps["mix"], ps["size"],
        seed=cell.config["data_seed"], tag=2, k=tr["k"],
        recall_targets=tr["recall_targets"], bins=ps.get("bins", 10),
        oversample=ps.get("oversample", 6),
        sel_range=tuple(ps.get("selectivity", (0.0, 1.0))),
        vector_seed=seed)


# ---------------------------------------------------------------------------
# open-loop schedule
# ---------------------------------------------------------------------------

def schedule(rate: float, seconds: float, n_pool: int, seed: int, tag: int = 4
             ) -> tuple[np.ndarray, np.ndarray]:
    """Poisson arrivals at ``rate`` over ``seconds``: (due offsets in
    seconds, pool index of each request). Every seed gets the same set of
    gaps (the exponential's quantiles) and sends each pool query equally
    often, in an order of its own."""
    rng = np.random.default_rng(seed_words(seed, tag))
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    reps = -(-n // n_pool)
    idx = np.concatenate([rng.permutation(n_pool) for _ in range(reps)])[:n]
    keep = due < seconds
    return due[keep], idx[keep]
