"""The plain reference: filtered top-k by brute force, with no import of
the program.

A predicate is the traffic module's DNF (clauses of closed or open
intervals over the float32 scalars), evaluated with plain comparisons. A
row's score is the weighted sum over vector columns of its similarity to
the query: the dot product, or for ``l2`` the expanded negative squared
distance 2 q.v - |v|^2 - |q|^2 (higher is closer). The device scans every
row in float32 at HIGHEST precision to find each query's candidates; the
host then scores the rows that matter in float64, and those float64 scores
are what answers are judged by.

``control=True`` scores the scan's matmuls in three bfloat16 passes (each
operand split into a bfloat16 head and tail, the tail-by-tail product
dropped): the ``high`` precision one step below what the configuration
states, which the comparison has to refuse.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 16
PRED_BLOCK = 32


def dnf_arrays(dnfs: list, m: int, c_max: int = 4) -> tuple:
    """Clauses as dense (P, C, M) lo/hi/lo_open/hi_open/active fields and
    (P, C) clause_valid."""
    p = len(dnfs)
    lo = np.full((p, c_max, m), -np.inf, np.float32)
    hi = np.full((p, c_max, m), np.inf, np.float32)
    lo_open = np.zeros((p, c_max, m), bool)
    hi_open = np.zeros((p, c_max, m), bool)
    active = np.zeros((p, c_max, m), bool)
    valid = np.zeros((p, c_max), bool)
    for i, clauses in enumerate(dnfs):
        for c, atoms in enumerate(clauses):
            valid[i, c] = True
            for col, a, b, ao, bo in atoms:
                lo[i, c, col], hi[i, c, col] = a, b
                lo_open[i, c, col], hi_open[i, c, col] = ao, bo
                active[i, c, col] = True
    return lo, hi, lo_open, hi_open, active, valid


def _mask(fields, scalars):
    """(P, n) bool: OR over valid clauses of AND over active columns."""
    lo, hi, lo_open, hi_open, active, valid = fields
    p, c_max, m = lo.shape
    out = jnp.zeros((p, scalars.shape[0]), bool)
    for c in range(c_max):
        ok = valid[:, c, None]
        for j in range(m):
            x = scalars[None, :, j]
            above = jnp.where(lo_open[:, c, j, None], x > lo[:, c, j, None],
                              x >= lo[:, c, j, None])
            below = jnp.where(hi_open[:, c, j, None], x < hi[:, c, j, None],
                              x <= hi[:, c, j, None])
            ok = ok & ((above & below) | ~active[:, c, j, None])
        out = out | ok
    return out


@jax.jit
def _count(fields, scalars):
    return jnp.sum(_mask(fields, scalars), axis=1)


def _blocks(arrays: tuple, size: int):
    """Split leading axes into blocks of ``size``, padding the last with
    copies of its first row (so one program serves every block)."""
    n = arrays[0].shape[0]
    for s in range(0, n, size):
        part = [a[s:s + size] for a in arrays]
        pad = size - part[0].shape[0]
        if pad:
            part = [np.concatenate([a, np.repeat(a[:1], pad, 0)]) for a in part]
        yield s, min(size, n - s), part


def qualifying_counts(dnfs: list, scalars, m: int) -> np.ndarray:
    """(P,) rows satisfying each predicate."""
    out = np.zeros(len(dnfs), np.int64)
    for s, n, part in _blocks(dnf_arrays(dnfs, m), PRED_BLOCK):
        out[s:s + n] = np.asarray(_count(tuple(part), scalars))[:n]
    return out


def _bf16(x):
    """x rounded to bfloat16, kept in float32 (``reduce_precision`` is not
    folded away as a float32 -> bfloat16 -> float32 round trip may be)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _bf16x3(a, b):
    """a @ b.T from bfloat16 heads and tails, tail x tail dropped: the
    products of bfloat16 values are exact in float32, so each pass is one
    bfloat16 MXU pass with float32 accumulation."""
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    mm = partial(jnp.matmul, precision=HIGHEST)
    return mm(ah, bh.T) + mm(ah, bl.T) + mm(al, bh.T)


@partial(jax.jit, static_argnames=("kk", "metric", "control"))
def _topk_block(vectors, scalars, qv, w, fields, *, kk, metric, control):
    n = scalars.shape[0]
    total = jnp.zeros((w.shape[0], n), jnp.float32)
    for i, v in enumerate(vectors):
        q = qv[i]
        dot = _bf16x3(q, v) if control else \
            jnp.matmul(q, v.T, precision=HIGHEST)
        if metric == "l2":
            dot = 2.0 * dot - jnp.sum(v * v, axis=1)[None] \
                - jnp.sum(q * q, axis=1)[:, None]
        total = total + w[:, i, None] * dot
    mask = _mask(fields, scalars)
    top_s, top_i = jax.lax.top_k(jnp.where(mask, total, -jnp.inf), kk)
    return top_i, top_s, jnp.sum(mask, axis=1)


def scan_topk(vectors: list, scalars, pool: list, kk: int, metric: str,
              *, control: bool = False) -> tuple:
    """Device scan of every row for every pool query. -> (ids (Q, kk),
    float32 scores (Q, kk), qualifying rows (Q,)); ids of rows that fail
    the predicate carry -inf scores."""
    m = int(scalars.shape[1])
    n_vec = len(vectors)
    fields = dnf_arrays([q.dnf for q in pool], m)
    qv = [np.stack([q.vectors[i] for q in pool]).astype(np.float32)
          for i in range(n_vec)]
    w = np.asarray([q.weights for q in pool], np.float32)
    q_n = len(pool)
    ids = np.zeros((q_n, kk), np.int64)
    sc = np.zeros((q_n, kk), np.float32)
    nq = np.zeros(q_n, np.int64)
    for s, n, part in _blocks(tuple(qv) + (w,) + fields, QUERY_BLOCK):
        i, sco, cnt = _topk_block(
            tuple(vectors), scalars, tuple(part[:n_vec]), part[n_vec],
            tuple(part[n_vec + 1:]), kk=kk, metric=metric, control=control)
        ids[s:s + n] = np.asarray(i)[:n]
        sc[s:s + n] = np.asarray(sco)[:n]
        nq[s:s + n] = np.asarray(cnt)[:n]
    return ids, sc, nq


def host_rows(vectors: list, ids: np.ndarray) -> list:
    """float64 host copies of rows ``ids`` of every vector column."""
    take = jnp.asarray(ids.astype(np.int32))
    return [np.asarray(jnp.take(v, take, axis=0)).astype(np.float64)
            for v in vectors]


def scores64(rows: list, q, metric: str) -> np.ndarray:
    """float64 weighted scores of gathered rows against pool query ``q``."""
    total = np.zeros(rows[0].shape[0], np.float64)
    for i, v in enumerate(rows):
        w = float(q.weights[i])
        if w == 0.0:
            continue
        qv = np.asarray(q.vectors[i], np.float64)
        dot = v @ qv
        if metric == "l2":
            dot = 2.0 * dot - np.sum(v * v, axis=1) - float(qv @ qv)
        total += w * dot
    return total


def satisfies(dnf: tuple, scal: np.ndarray) -> np.ndarray:
    """(r,) bool: do float32 scalar rows ``scal`` (r, M) satisfy ``dnf``."""
    out = np.zeros(scal.shape[0], bool)
    for clause in dnf:
        ok = np.ones(scal.shape[0], bool)
        for col, a, b, ao, bo in clause:
            x = scal[:, col]
            ok &= (x > a) if ao else (x >= a)
            ok &= (x < b) if bo else (x <= b)
        out |= ok
    return out
