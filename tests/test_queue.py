"""Deadline-aware batch formation under an injected fake clock, plus the
asyncio serving engine end-to-end (real clock, tiny table)."""
import asyncio

import numpy as np

from repro.bench import datasets, queries
from repro.core.boomhq import BoomHQ, BoomHQConfig
from repro.core.rewriter import RewriterConfig
import pytest

from repro.serve.queue import (
    FAILED, OK, TIMED_OUT, AsyncServingEngine, BatchFormer, serve_stream,
)
from repro.vectordb import flat


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self) -> float:
        return self.now

    def advance(self, dt: float) -> float:
        self.now += dt
        return self.now


def _former(**kw) -> tuple[BatchFormer, FakeClock]:
    clock = FakeClock()
    kw.setdefault("batch_size", 4)
    kw.setdefault("max_wait", 1.0)
    return BatchFormer(clock=clock, **kw), clock


def test_cut_on_full_preserves_fifo():
    f, clock = _former()
    reqs = [f.submit(f"q{i}") for i in range(5)]
    batch, expired = f.poll()
    assert expired == []
    assert [r.seq for r in batch] == [0, 1, 2, 3]  # FIFO, oldest first
    assert [r.query for r in batch] == ["q0", "q1", "q2", "q3"]
    # the 5th request is not yet aged — no second cut at the same instant
    batch2, _ = f.poll()
    assert batch2 is None and len(f) == 1
    assert reqs[4].status == "pending"


def test_cut_on_age():
    f, clock = _former(batch_size=8, max_wait=0.5)
    f.submit("a")
    clock.advance(0.2)
    f.submit("b")
    assert f.poll()[0] is None  # oldest age 0.2 < 0.5, queue not full
    clock.advance(0.31)  # oldest now 0.51 >= max_wait
    batch, _ = f.poll()
    assert [r.query for r in batch] == ["a", "b"]  # underfull but aged out


def test_expired_reported_and_never_executed():
    f, clock = _former(batch_size=2, max_wait=10.0)
    doomed = f.submit("doomed", timeout=0.5)
    clock.advance(1.0)
    ok = f.submit("ok")  # arrives after the deadline passed
    batch, expired = f.poll()
    assert expired == [doomed]
    assert doomed.status == TIMED_OUT and doomed.result is None
    assert doomed.done == clock.now and doomed.latency == 1.0
    # the expired request freed its slot: no cut-on-full, no stale entry
    assert batch is None and len(f) == 1
    clock.advance(10.0)
    batch, expired = f.poll()
    assert expired == [] and [r.seq for r in batch] == [ok.seq]


def test_expiry_wins_over_formation():
    """A request whose deadline has passed never enters a batch, even when
    the queue is full enough to cut at the same poll."""
    f, clock = _former(batch_size=2, max_wait=10.0)
    a = f.submit("a", timeout=0.1)
    f.submit("b")
    f.submit("c")
    clock.advance(0.2)
    batch, expired = f.poll()
    assert expired == [a]
    assert [r.query for r in batch] == ["b", "c"]


def test_deadline_exactly_at_poll_still_serves():
    """now == deadline is NOT expired (strict >): a budget of exactly the
    queue wait still executes."""
    f, clock = _former(batch_size=8, max_wait=0.5)
    r = f.submit("edge", timeout=0.5)
    clock.advance(0.5)
    batch, expired = f.poll()
    assert expired == [] and batch == [r]


def test_next_event_schedules_earliest_of_age_and_deadline():
    f, clock = _former(batch_size=8, max_wait=1.0)
    assert f.next_event() is None
    f.submit("a")  # cut-on-age instant: 1.0
    assert f.next_event() == 1.0
    f.submit("b", timeout=0.25)  # deadline 0.25 is sooner
    assert f.next_event() == 0.25
    clock.advance(2.0)
    f.poll()
    assert f.next_event() is None  # drained


def test_flush_forces_underfull_unaged_batch():
    f, clock = _former(batch_size=8, max_wait=100.0)
    f.submit("a")
    f.submit("b")
    assert f.poll()[0] is None
    batch, _ = f.poll(flush=True)
    assert [r.query for r in batch] == ["a", "b"]


# ---------------------------------------------------------------------------
# asyncio engine end-to-end
# ---------------------------------------------------------------------------

def _tiny_bq():
    table = datasets.make("part", rows=900, seed=4)
    bq = BoomHQ(table, BoomHQConfig(
        n_clusters=8, use_de=False,
        rewriter=RewriterConfig(steps=10, refine_columns=False)))
    return table, bq


def test_async_engine_serves_stream():
    from repro.serve.batch import DENSE, CostModel

    table, bq = _tiny_bq()
    # pin the exact sharded scan: this test asserts ground-truth scores,
    # and the default cost model would route this tiny table's index
    # groups through the (approximate) single-device learned path
    bq.bind_shards(3).bind_cost_model(CostModel(force=DENSE))
    wl = queries.gen_workload(table, 8, n_vec_used=2, seed=11)

    async def main():
        eng = AsyncServingEngine(bq, batch_size=3, max_wait=0.01)
        reqs = await serve_stream(eng, wl)
        return eng, reqs

    eng, reqs = asyncio.run(main())
    assert [r.query for r in reqs] == wl  # submission order preserved
    assert all(r.status == OK for r in reqs)
    for r in reqs:
        q = r.query
        gt_ids, gt_s = flat.ground_truth(table, list(q.query_vectors),
                                         list(q.weights), q.predicates, q.k)
        ids, scores = r.result
        np.testing.assert_allclose(np.asarray(scores), np.asarray(gt_s),
                                   atol=1e-4, rtol=1e-5)
    rep = eng.report()
    assert rep.n_queries == len(wl) and rep.n_timed_out == 0
    assert rep.qps > 0 and rep.p50_ms is not None and rep.p99_ms >= rep.p50_ms
    assert "p50" in rep.describe()


def test_async_engine_survives_execution_failure():
    """A raising execute_batch fails ITS requests (submit re-raises) but
    must not kill the drainer — later requests still get served."""
    table, bq = _tiny_bq()
    wl = queries.gen_workload(table, 2, n_vec_used=2, seed=13)
    state = {"calls": 0}

    class Flaky:
        def execute_batch(self, qs):
            state["calls"] += 1
            if state["calls"] == 1:
                raise RuntimeError("boom")
            return bq.execute_batch(qs)

    async def main():
        eng = AsyncServingEngine(Flaky(), batch_size=1, max_wait=0.0)
        async with eng:
            with pytest.raises(RuntimeError, match="boom"):
                await eng.submit(wl[0])
            ok = await eng.submit(wl[1])
        return eng, ok

    eng, ok = asyncio.run(main())
    assert ok.status == OK and ok.result is not None
    served = sorted(eng._served, key=lambda r: r.seq)
    assert [r.status for r in served] == [FAILED, OK]
    assert eng.report().n_timed_out == 0


def test_async_engine_stop_noflush_fails_inflight():
    """stop(flush=False) mid-execution must not strand the in-flight
    batch's submit() callers — they resolve with a cancellation instead of
    hanging forever."""
    import time as _time

    class Slow:
        def execute_batch(self, qs):
            _time.sleep(0.4)
            return [(np.asarray([0]), np.asarray([0.0]))] * len(qs)

    async def main():
        eng = AsyncServingEngine(Slow(), batch_size=1, max_wait=0.0)
        await eng.start()
        task = asyncio.ensure_future(eng.submit("q"))
        await asyncio.sleep(0.1)  # batch formed and executing in the worker
        # a second request that never forms a batch (the drainer is busy
        # and stop() won't flush) must also resolve, not hang
        eng.former.batch_size = 99
        queued = asyncio.ensure_future(eng.submit("q2"))
        await asyncio.sleep(0)
        await eng.stop(flush=False)
        with pytest.raises(asyncio.CancelledError):
            await asyncio.wait_for(task, timeout=2.0)
        with pytest.raises(asyncio.CancelledError):
            await asyncio.wait_for(queued, timeout=2.0)
        return eng

    eng = asyncio.run(main())
    assert sorted(r.status for r in eng._served) == [FAILED, FAILED]


def test_deadline_between_cut_and_dispatch_times_out():
    """Regression: deadline enforcement must NOT stop at cut time. A request
    whose deadline lands between poll() (batch formed) and _execute
    (dispatch) resolves timed_out and is dropped from the executed batch —
    it used to execute anyway and report OK."""
    clock = FakeClock()
    executed = []

    class Recorder:
        def execute_batch(self, qs):
            executed.extend(qs)
            return [(np.asarray([0]), np.asarray([0.0]))] * len(qs)

    async def main():
        eng = AsyncServingEngine(Recorder(), batch_size=2, max_wait=0.0,
                                 clock=clock)
        await eng.start()
        eng.former.submit("doomed", timeout=0.5)
        eng.former.submit("survivor", timeout=5.0)
        batch, expired = eng.former.poll()  # cut at t=0: nothing expired
        assert expired == [] and len(batch) == 2
        # the deadline passes AFTER the cut, BEFORE dispatch (e.g. the
        # batch sat behind an in-flight one)
        clock.advance(1.0)
        await eng._execute(batch)
        await eng.stop(flush=False)
        return eng, batch

    eng, (doomed, survivor) = asyncio.run(main())
    assert doomed.status == TIMED_OUT and doomed.result is None
    assert doomed.done == 1.0
    assert survivor.status == OK and survivor.result is not None
    assert executed == ["survivor"]  # the expired request never executed
    rep = eng.report()
    assert rep.n_timed_out == 1 and rep.n_queries >= 2


def test_dispatch_expiry_keeps_exact_deadline_serving():
    """now == deadline at dispatch still executes (same strict > rule as
    queue-side expiry), and an all-expired batch executes nothing."""
    clock = FakeClock()
    executed = []

    class Recorder:
        def execute_batch(self, qs):
            executed.extend(qs)
            return [(np.asarray([0]), np.asarray([0.0]))] * len(qs)

    async def main():
        eng = AsyncServingEngine(Recorder(), batch_size=2, max_wait=0.0,
                                 clock=clock)
        await eng.start()
        edge = eng.former.submit("edge", timeout=1.0)
        batch, _ = eng.former.poll(flush=True)
        clock.advance(1.0)  # exactly at the deadline
        await eng._execute(batch)
        dead = eng.former.submit("dead", timeout=0.1)
        batch, _ = eng.former.poll(flush=True)
        clock.advance(1.0)
        await eng._execute(batch)  # whole batch expired: no executor call
        await eng.stop(flush=False)
        return edge, dead

    edge, dead = asyncio.run(main())
    assert edge.status == OK and executed == ["edge"]
    assert dead.status == TIMED_OUT


def test_async_engine_timeout_disposition():
    _, bq = _tiny_bq()

    async def main():
        eng = AsyncServingEngine(bq, batch_size=64, max_wait=0.2)
        async with eng:
            r = await eng.submit("never-executed-query", timeout=0.0)
        return eng, r

    eng, r = asyncio.run(main())
    # a zero budget expires before any batch cuts — and is never executed,
    # which is also why a non-MHQ placeholder query cannot crash the engine
    assert r.status == TIMED_OUT and r.result is None
    rep = eng.report()
    assert rep.n_timed_out == 1 and rep.p50_ms is None


def test_compaction_failure_surfaces_at_drain():
    """A compaction that raises on the worker thread stops further
    compactions and re-raises from drain() (so an engine's stop() fails),
    instead of vanishing with its future."""
    from repro.serve.queue import CompactionScheduler

    class FailingTiered:
        n_calls = 0

        def needs_compaction(self):
            return True

        def compact(self):
            self.n_calls += 1
            raise RuntimeError("compaction broke")

    tiered = FailingTiered()
    sched = CompactionScheduler(tiered)
    assert sched.maybe_schedule()
    sched._inflight.exception()  # wait for the worker to finish
    assert not sched.maybe_schedule()  # a failed compaction is final
    with pytest.raises(RuntimeError, match="compaction broke"):
        sched.drain()
    assert tiered.n_calls == 1


def test_former_stamps_cut_and_engine_counts_reasons():
    """Every request of a cut batch carries the cut instant and the batch's
    number; the engine counts each cut by its reason (full, age, flush)
    and sums queue wait (cut - arrival) exactly, under a fake clock."""
    clock = FakeClock()

    class Recorder:
        def execute_batch(self, qs):
            return [(np.asarray([0]), np.asarray([0.0]))] * len(qs)

    async def main():
        eng = AsyncServingEngine(Recorder(), batch_size=2, max_wait=1.0,
                                 clock=clock)
        await eng.start()
        f = eng.former
        a, b = f.submit("a"), f.submit("b")
        clock.advance(0.25)
        full, _ = f.poll()  # two pending: cut on full at 0.25
        clock.advance(0.25)
        c = f.submit("c")  # arrives at 0.5
        assert f.poll(now=1.0) == (None, [])  # aged 0.5 of 1.0
        clock.advance(1.25)
        aged, _ = f.poll()  # cut on age at 1.75
        d = f.submit("d")
        clock.advance(0.5)
        flushed, _ = f.poll(flush=True)  # cut by flush at 2.25
        for batch in (full, aged, flushed):
            await eng._execute(batch)
        await eng.stop(flush=False)
        return eng, (a, b, c, d), (full, aged, flushed)

    eng, (a, b, c, d), (full, aged, flushed) = asyncio.run(main())
    assert full == [a, b] and aged == [c] and flushed == [d]
    assert [r.cut for r in (a, b, c, d)] == [0.25, 0.25, 1.75, 2.25]
    assert [r.batch for r in (a, b, c, d)] == [0, 0, 1, 2]
    assert eng.counts == {"requests_cut": 4,
                          "queue_wait_s": 0.25 + 0.25 + 1.25 + 0.5,
                          "batches_full": 1, "batches_age": 1,
                          "batches_flush": 1}
    assert eng.counts is eng.former.counts
    assert eng._n_batches == 3


@pytest.mark.parametrize("pending", [False, True])
def test_cancelled_drainer_leaves_no_span_open(monkeypatch, pending):
    """stop(flush=False) cancels the drainer inside its wait: the wait's
    span (for an arrival, or for a pending request's cut) is exited."""
    import repro.serve.queue as queue_mod

    opened, closed = [], []

    class Recorded:
        def __init__(self, name, batch=None):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            closed.append(self.name)

    monkeypatch.setattr(queue_mod, "span", Recorded)

    class Never:
        def execute_batch(self, qs):
            raise AssertionError("nothing is cut")

    async def main():
        eng = AsyncServingEngine(Never(), batch_size=8, max_wait=60.0)
        await eng.start()
        if pending:
            waiter = asyncio.ensure_future(eng.submit("q"))
        await asyncio.sleep(0.05)  # the drainer is waiting
        await eng.stop(flush=False)
        if pending:
            with pytest.raises(asyncio.CancelledError):
                await waiter

    asyncio.run(main())
    want = "hq.frontend.cut_wait" if pending else "hq.frontend.await_arrival"
    assert opened and opened[-1] == want
    assert sorted(opened) == sorted(closed)


def test_worker_call_carries_its_batch_number():
    """The worker thread's call of a cut batch sees that batch's number in
    ``spans.BATCH``, so its spans share it with the event loop's; outside
    a formed batch there is none."""
    from repro.common import spans

    clock = FakeClock()
    seen = []

    class Recorder:
        def execute_batch(self, qs):
            seen.append(spans.BATCH.get())
            return [(np.asarray([0]), np.asarray([0.0]))] * len(qs)

    async def main():
        eng = AsyncServingEngine(Recorder(), batch_size=1, max_wait=1.0,
                                 clock=clock)
        await eng.start()
        cut = []
        for q in ("a", "b", "c"):
            eng.former.submit(q)
            batch, _ = eng.former.poll()
            cut.append(batch[0].batch)
            await eng._execute(batch)
        await eng.stop(flush=False)
        return cut

    assert asyncio.run(main()) == seen == [0, 1, 2]
    assert spans.BATCH.get() is None
