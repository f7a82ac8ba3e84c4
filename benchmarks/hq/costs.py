"""Operations and bytes of the fused gather+score kernel, per call.

The kernel (``gather_score_blocks``) copies, for every candidate slot that
holds a row id, that row of each scored column's row view and of the
scalars' 128-lane view from HBM; padding slots move nothing. It then takes
one dot product per column (and, for ``l2``, the row's squared norm).
So a call needs

    bytes = valid candidates x (sum of view row bytes + 128 x 4)
    ops   = valid candidates x sum over columns of 2 d (4 d for l2)

and its least time is the larger of ops / peak and bytes / HBM bandwidth.
The valid candidates come from each group call's own outputs (``n_scored``)
or inputs (the rerank union's ids), read after the window.
"""
from __future__ import annotations

import dataclasses

import numpy as np

META_ROW_BYTES = 128 * 4
MAX_TOPK = 128  # the widest static k a kernel is launched with
RERANK_MULT = 4  # the int8 pass keeps this many x k rows for the rerank


@dataclasses.dataclass
class KernelCall:
    """One kernel launch within a group call: bytes and operations per
    valid slot, and how to count the valid slots from the call's device
    arrays once the window is over (on the host, so the window runs no
    program of the benchmark's)."""

    row_bytes: int
    row_ops: int
    arrays: tuple  # device arrays the count is read from
    count: object  # count(*host arrays) -> valid slots


def view_bytes(view) -> int:
    """Bytes of one row of an (n, 1, W) row view."""
    return int(view.shape[-1]) * int(np.dtype(view.dtype).itemsize)


def row_cost(rows, dims, metric: str) -> tuple[int, int]:
    """(bytes, ops) of one gathered row of ``GatherRows`` ``rows``."""
    per = 4 if metric == "l2" else 2
    return (sum(view_bytes(v) for v in rows.views) + META_ROW_BYTES,
            per * int(sum(dims)))


def calls_of(group: str, args: tuple, kwargs: dict, out) -> list:
    """The kernel launches of one group call, or [] for groups that do not
    run the gather kernel."""
    if group in ("ivf.search_local_batch", "ivf.search_local_batch_int8"):
        index = args[0]
        q_b = args[4] if group.endswith("int8") else args[3]
        dims = (int(q_b.shape[1]),)
        metric = index.metric
        rows = args[1]
        _, _, n_scored, n_qual = out
        if not group.endswith("int8"):
            b, o = row_cost(rows, dims, metric)
            return [KernelCall(b, o, (n_scored,), np.sum)]
        rows_i8 = args[2]
        k = int(kwargs["k"])
        kq = max(k, min(RERANK_MULT * k, MAX_TOPK))
        b8, o8 = row_cost(rows_i8, dims, metric)
        b32, o32 = row_cost(rows, dims, metric)
        return [KernelCall(b8, o8, (n_scored,), np.sum),
                KernelCall(b32, o32, (n_qual,),
                           lambda q: np.sum(np.minimum(q, kq)))]
    if group == "flat.filter_first_local_batch":
        rows, _, qv_b, _ = args[:4]
        n_vec = int(kwargs["n_vec"])
        sel = rows.select(range(n_vec))
        b, o = row_cost(sel, [int(q.shape[1]) for q in qv_b[:n_vec]],
                        kwargs.get("metric", "dot"))
        return [KernelCall(b, o, (out[2],), np.sum)]
    if group == "batch._gather_rerank_batch":
        rows_b, rows, q_b = args[:3]
        b, o = row_cost(rows, [int(q.shape[1]) for q in q_b],
                        kwargs.get("metric", "dot"))
        return [KernelCall(b, o, (rows_b,), lambda r: np.sum(r >= 0))]
    return []


def least_seconds(calls: list, peak: dict) -> tuple[float, float, float]:
    """(least seconds, bytes, ops) summed over kernel launches."""
    t = nbytes = nops = 0.0
    for c in calls:
        v = float(c.count(*(np.asarray(a) for a in c.arrays)))
        cb, co = v * c.row_bytes, v * c.row_ops
        nbytes += cb
        nops += co
        t += max(co / peak["bf16_flops"], cb / peak["hbm_bytes_per_s"])
    return t, nbytes, nops
