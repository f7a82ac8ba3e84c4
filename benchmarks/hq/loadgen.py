"""Open-loop load: independent callers sending at absolute due times.

Each request is due at ``t0 + due[i]`` and is sent then, whatever happened
before it; its latency runs from when it was due, so a stall is charged to
every request it delays. How late each send left (the generator's own
lateness) is kept too. A request that raised counts as failed; one that
has not come back a grace period after the window closed never came.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time

import numpy as np

OK = "ok"
FAILED = "failed"
NEVER = "never_came"


@dataclasses.dataclass
class Requests:
    due: np.ndarray  # absolute due instants (perf_counter seconds)
    sent: np.ndarray
    done: np.ndarray  # inf where the request failed or never came
    status: list
    results: list  # (ids, scores) of OK requests, else None
    pool_index: np.ndarray

    def latencies(self) -> np.ndarray:
        """Seconds from due to done; inf for requests not served."""
        return self.done - self.due


async def drive(submit, queries: list, due: np.ndarray, idx: np.ndarray, *,
                t0: float, window: float, on_close=None, grace: float = 60.0
                ) -> Requests:
    """Send ``queries[idx[i]]`` at ``t0 + due[i]`` through the coroutine
    ``submit(query) -> (status, result)``; call ``on_close()`` when the
    window ends, then wait up to ``grace`` seconds for what is in flight."""
    n = len(due)
    req = Requests(due=t0 + np.asarray(due, np.float64),
                   sent=np.full(n, np.nan), done=np.full(n, np.inf),
                   status=[NEVER] * n, results=[None] * n,
                   pool_index=np.asarray(idx))

    async def one(i):
        try:
            status, result = await submit(queries[idx[i]])
        except Exception:  # noqa: BLE001 — a raised request is a failure
            status, result = FAILED, None
        if status == OK:
            req.done[i] = time.perf_counter()
            req.results[i] = result
        req.status[i] = status

    tasks = []
    for i in range(n):
        delay = req.due[i] - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        req.sent[i] = time.perf_counter()
        tasks.append(asyncio.ensure_future(one(i)))
    close = t0 + window
    if close > time.perf_counter():
        await asyncio.sleep(close - time.perf_counter())
    if on_close is not None:
        on_close()
    if tasks:
        _, pending = await asyncio.wait(tasks, timeout=grace)
        for t in pending:
            t.cancel()
    return req


def quantile(values: np.ndarray, p: float) -> float:
    """numpy's linear quantile, where a value that touches an infinite
    sample (a request never served) is infinite."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return float("inf")
    pos = p * (len(v) - 1)
    lo, hi = v[int(np.floor(pos))], v[int(np.ceil(pos))]
    if not (np.isfinite(lo) and np.isfinite(hi)):
        return float("inf")
    return float(lo + (hi - lo) * (pos - np.floor(pos)))


def summary(req: Requests, t0: float, window: float) -> dict:
    """End-to-end numbers over the whole window: completions within it per
    second, and latency quantiles over every request due in it, failures
    and the never-come counting as infinitely late."""
    lat = np.sort(req.latencies())
    served = np.asarray([s == OK for s in req.status])
    in_window = served & (req.done <= t0 + window)
    late = req.sent - req.due

    def q(p):
        return quantile(lat, p) * 1e3

    return {
        "attempted": int(len(req.due)),
        "served": int(served.sum()),
        "failed": int((~served).sum()),
        "qps": float(in_window.sum() / window),
        "p50_ms": q(0.50),
        "p95_ms": q(0.95),
        "p99_ms": q(0.99),
        "late_ms": late * 1e3,
    }
