"""qps and the tails are taken over the whole window, failures as misses."""
import asyncio

import numpy as np

from benchmarks.hq import loadgen


def _requests(due, done, status):
    n = len(due)
    return loadgen.Requests(
        due=np.asarray(due, float), sent=np.asarray(due, float),
        done=np.asarray(done, float), status=list(status),
        results=[None] * n, pool_index=np.zeros(n, int))


def test_qps_counts_completions_inside_the_window():
    # 10 requests due in a 2 s window; 8 done inside it, one done after it,
    # one failed
    due = np.linspace(0.0, 1.8, 10)
    done = due + 0.1
    done[8] = 2.5
    done[9] = np.inf
    st = ["ok"] * 9 + ["failed"]
    s = loadgen.summary(_requests(due, done, st), 0.0, 2.0)
    assert s["attempted"] == 10 and s["served"] == 9 and s["failed"] == 1
    assert s["qps"] == 8 / 2.0


def test_tails_are_over_every_request_with_failures_infinite():
    n = 100
    due = np.arange(n) * 0.01
    lat = np.linspace(0.001, 0.100, n)  # 1..100 ms
    done = due + lat
    st = ["ok"] * n
    s = loadgen.summary(_requests(due, done, st), 0.0, 1.0)
    assert abs(s["p50_ms"] - np.quantile(lat, 0.5) * 1e3) < 1e-9
    assert abs(s["p95_ms"] - np.quantile(lat, 0.95) * 1e3) < 1e-9
    # six failures push the 95th percentile to infinity: they miss every
    # latency limit
    done[:6] = np.inf
    st = ["failed"] * 6 + ["ok"] * (n - 6)
    s = loadgen.summary(_requests(due, done, st), 0.0, 1.0)
    assert s["p95_ms"] == float("inf")
    assert np.isfinite(s["p50_ms"])


def test_drive_sends_on_schedule_and_times_from_due():
    async def go():
        async def submit(q):
            if q == "bad":
                raise RuntimeError("boom")
            await asyncio.sleep(0.05)
            return loadgen.OK, ("ids", "scores")

        queries = ["a", "bad", "c"]
        due = np.asarray([0.0, 0.02, 0.04])
        idx = np.asarray([0, 1, 2])
        import time

        t0 = time.perf_counter() + 0.01
        closed = []
        req = await loadgen.drive(submit, queries, due, idx, t0=t0,
                                  window=0.1, on_close=lambda: closed.append(1))
        return req, t0, closed

    req, t0, closed = asyncio.run(go())
    assert closed == [1]
    assert req.status == ["ok", "failed", "ok"]
    assert np.all(req.sent - req.due >= 0)
    lat = req.latencies()
    assert np.isinf(lat[1])
    assert 0.05 <= lat[0] < 0.5 and 0.05 <= lat[2] < 0.5
    assert np.allclose(req.due - t0, [0.0, 0.02, 0.04])


def test_never_came_counts_as_failed():
    async def go():
        async def submit(q):
            await asyncio.sleep(10)
            return loadgen.OK, None

        import time

        t0 = time.perf_counter()
        return await loadgen.drive(submit, ["x"], np.asarray([0.0]),
                                   np.asarray([0]), t0=t0, window=0.01,
                                   grace=0.05), t0

    req, t0 = asyncio.run(go())
    assert req.status == [loadgen.NEVER]
    s = loadgen.summary(req, t0, 0.01)
    assert s["failed"] == 1 and s["qps"] == 0.0
