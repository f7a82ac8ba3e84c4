"""The comparison that decides ``correct``.

Every answer that was due in the window is judged against the plain
reference, once the window has closed:

* ``failed``: requests that raised or never came back;
* ``bad_rows``: returned rows out of range, repeated within an answer,
  failing the query's predicate, or out of score order;
* ``score_err``: the largest gap between a returned score and the row's
  float64 reference score, over max(1, |reference|);
* ``exact_short``: rows owed (min(k, rows that satisfy the predicate))
  but missing from answers to queries the planner sent only to
  ``filter_first`` in the window. That plan scores every qualifying row up
  to a cap far above k, so such an answer that comes back short, or empty,
  was left out or cut, not approximated.

Each number has a limit in the traffic file (``limits``); ``correct`` is
every number within its limit. Two shares are printed beside them, with no
limit: ``missing``, the owed rows that no answer held, and ``empty``, the
answers with no row although rows qualify. Approximate plans come back
short, or empty, by shares that vary with the seed (PERF.md). Recall@k of
each answer (tie-aware: a returned row counts when it qualifies and
reaches the reference's k-th float64 score less 1e-4 + 1e-5 |k-th|) is
the ``recall`` metric, not a limit: approximate plans may miss rows, and
the bound on recall guards them.
"""
from __future__ import annotations

import numpy as np

from benchmarks.hq import reference

ORDER_RTOL = 1e-6  # score order is checked up to float32 rounding
NUMBERS = ("failed", "bad_rows", "score_err", "exact_short")  # limited


def tie_tolerance(kth: float) -> float:
    return 1e-4 + 1e-5 * abs(kth)


def judge(pool: list, answers: list, ref: tuple, vectors: list,
          host_scalars: np.ndarray, metric: str, *, n_failed: int = 0,
          exact_pool: frozenset = frozenset()) -> tuple[dict, list, dict]:
    """``answers``: [(pool index, ids, scores)] of the requests served;
    ``ref``: ``reference.scan_topk`` over the pool; ``exact_pool``: the pool
    indices planned only as ``filter_first``. -> (numbers, recall of each
    answer, {"missing", "empty"} shares)."""
    ref_ids, ref_sc, n_qual = ref
    n = host_scalars.shape[0]
    per_q: dict = {}
    for qi, ids, _ in answers:
        ids = np.asarray(ids).ravel()
        per_q.setdefault(qi, set()).update(
            int(i) for i in ids if 0 <= i < n)
    for qi in per_q:
        per_q[qi].update(int(i) for i, s in zip(ref_ids[qi], ref_sc[qi])
                         if np.isfinite(s))
    union = np.asarray(sorted(set().union(*per_q.values())) or [0], np.int64)
    rows = reference.host_rows(vectors, union)
    where = {int(r): j for j, r in enumerate(union)}
    s64, kth, budget = {}, {}, {}
    for qi, ids in per_q.items():
        ids = np.asarray(sorted(ids), np.int64)
        sub = [v[[where[int(i)] for i in ids]] for v in rows]
        sc = reference.scores64(sub, pool[qi], metric)
        s64[qi] = dict(zip(ids.tolist(), sc.tolist()))
        cand = [s64[qi][int(i)] for i, s in zip(ref_ids[qi], ref_sc[qi])
                if np.isfinite(s)]
        budget[qi] = min(pool[qi].k, int(n_qual[qi]))
        kth[qi] = sorted(cand, reverse=True)[budget[qi] - 1] \
            if budget[qi] else None
    bad = 0
    owed = short = 0
    n_empty = n_owing = exact_short = 0
    err = 0.0
    recalls = []
    for qi, ids, scores in answers:
        q = pool[qi]
        ids = np.asarray(ids).ravel().astype(np.int64)
        scores = np.asarray(scores, np.float64).ravel()
        valid = ids >= 0
        vid, vsc = ids[valid], scores[valid]
        inside = (vid < n)
        bad += int(np.sum(~inside))
        vid, vsc = vid[inside], vsc[inside]
        bad += len(vid) - len(set(vid.tolist()))
        qual = reference.satisfies(q.dnf, host_scalars[vid]) if len(vid) \
            else np.zeros(0, bool)
        bad += int(np.sum(~qual))
        if len(vsc) > 1:
            drop = vsc[1:] - vsc[:-1]
            bad += int(np.sum(drop > ORDER_RTOL * np.maximum(
                1.0, np.abs(vsc[:-1]))))
        exact = np.asarray([s64[qi][int(i)] for i in vid], np.float64)
        if len(exact):
            err = max(err, float(np.max(np.abs(vsc - exact)
                                        / np.maximum(1.0, np.abs(exact)))))
        owed += budget[qi]
        gap = max(0, budget[qi] - len(set(vid.tolist())))
        short += gap
        if qi in exact_pool:
            exact_short += gap
        if budget[qi]:
            n_owing += 1
            n_empty += int(len(vid) == 0)
        if budget[qi] == 0:
            recalls.append(1.0)
            continue
        tol = tie_tolerance(kth[qi])
        good = {int(i) for i, ok, e in zip(vid, qual, exact)
                if ok and e >= kth[qi] - tol}
        recalls.append(min(len(good), budget[qi]) / budget[qi])
    numbers = {"failed": n_failed, "bad_rows": bad, "score_err": err,
               "exact_short": exact_short}
    return numbers, recalls, {"missing": short / max(1, owed),
                              "empty": n_empty / max(1, n_owing)}


def verdict(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in numbers)


def describe(numbers: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} in a fixed order, for the result line."""
    return {k: {"value": numbers[k], "limit": limits[k]} for k in numbers}
