"""Async deadline-aware MHQ serving: queue → batch formation → fan-out.

The synchronous ``ServingEngine`` chops a PRE-COLLECTED query list into
fixed batches — fine for benchmarks, wrong for live traffic, where requests
arrive one at a time and each carries a latency budget. This module adds the
missing front half of the serving pipeline:

  request queue  →  deadline-aware batch formation  →  batched execution
                                                        (shard fan-out + merge)

  * ``BatchFormer`` is the pure-synchronous policy core (injectable clock,
    so tests drive it under a fake clock): a batch CUTS when ``batch_size``
    requests are pending (cut-on-full) OR when the oldest pending request
    has aged past ``max_wait`` seconds (cut-on-age). Requests whose
    per-request deadline passes while still queued are expired — reported
    with a ``timed_out`` disposition and NEVER executed. FIFO arrival
    order is preserved within every formed batch.
  * ``AsyncServingEngine`` is the asyncio front-end: concurrent
    ``submit()`` callers share formed batches; one drainer task cuts
    batches and executes them through ``BoomHQ.execute_batch`` — which
    fans each batch out over the table shards when the instance is
    ``bind_shards``-bound — in a worker thread, so the event loop keeps
    accepting arrivals mid-execution.

Dispositions and latency percentiles land in the shared ``ServeReport``
(``n_timed_out``, ``p50_ms``/``p99_ms``). The front end counts its cuts,
by reason, and their queue wait in ``counts``; the drainer's waits
(``hq.frontend.cut_wait``, ``hq.frontend.await_arrival``) and the
resolution of a finished batch (``hq.frontend.resolve``) are host spans
(``repro.common.spans``).
"""
from __future__ import annotations

import asyncio
import concurrent.futures
import contextvars
import dataclasses
import functools
import time
from typing import Callable, Optional

import numpy as np

from repro.common.spans import BATCH, span
from repro.core.executor import recall_at_k
from repro.core.query import MHQ
from repro.serve.batch import ServeReport

PENDING = "pending"
OK = "ok"
TIMED_OUT = "timed_out"
FAILED = "failed"  # execution raised; the exception propagates to submit()

_DEFAULT = object()  # sentinel: "use the engine's default timeout"


@dataclasses.dataclass
class ServeRequest:
    """One enqueued query: arrival instant, optional ABSOLUTE deadline, and
    (once the engine resolves it) disposition + result."""

    query: MHQ
    seq: int
    arrival: float
    deadline: Optional[float] = None  # clock instant; None = no deadline
    status: str = PENDING  # PENDING | OK | TIMED_OUT | FAILED
    result: Optional[tuple] = None  # (ids, scores) when status == OK
    done: float = 0.0
    cut: Optional[float] = None  # clock instant its batch was cut
    batch: Optional[int] = None  # sequence number of that batch
    cache_hit: bool = False  # resolved by the semantic cache, zero scan cost
    # tiered serving: the immutable (epoch, hot, cold) snapshot stamped on
    # the whole batch at CUT time — every request in a batch shares one, so
    # an epoch swap between formation and execution can never mix states
    snapshot: Optional[object] = None

    @property
    def latency(self) -> float:
        """Queue wait + execution for OK; time-to-expiry for TIMED_OUT."""
        return self.done - self.arrival


class BatchFormer:
    """Deadline-aware batch formation over a FIFO request queue.

    Synchronous policy core with an injectable ``clock`` — the async engine
    drives it with wall time, tests with a fake clock. See the module
    docstring for the cut/expire policy.
    """

    def __init__(self, *, batch_size: int = 32, max_wait: float = 0.05,
                 clock: Callable[[], float] = time.monotonic,
                 snapshot_fn: Optional[Callable[[], object]] = None):
        assert batch_size >= 1 and max_wait >= 0.0
        self.batch_size = batch_size
        self.max_wait = max_wait
        self.clock = clock
        # tiered serving: called ONCE per cut; the returned snapshot is
        # stamped on every request of the formed batch (snapshot-at-cut)
        self.snapshot_fn = snapshot_fn
        self._pending: list[ServeRequest] = []
        self._seq = 0
        # requests cut, their summed queue wait (cut - arrival, seconds)
        # and batches by what cut them; AsyncServingEngine.counts
        self.counts = {"requests_cut": 0, "queue_wait_s": 0.0,
                       "batches_full": 0, "batches_age": 0,
                       "batches_flush": 0}

    @property
    def n_cut(self) -> int:
        """Batches cut so far: the next batch's sequence number."""
        c = self.counts
        return c["batches_full"] + c["batches_age"] + c["batches_flush"]

    def __len__(self) -> int:
        return len(self._pending)

    def admit(self, query: MHQ, *, timeout: Optional[float] = None,
              now: Optional[float] = None) -> ServeRequest:
        """Stamp (but do NOT enqueue) the next request — sequence number,
        arrival instant and absolute deadline. Front-ends that resolve a
        request without ever forming it into a batch (a semantic-cache hit)
        use this directly so cached requests still occupy their slot in the
        serve order."""
        now = self.clock() if now is None else now
        r = ServeRequest(
            query=query, seq=self._seq, arrival=now,
            deadline=None if timeout is None else now + timeout)
        self._seq += 1
        return r

    def submit(self, query: MHQ, *, timeout: Optional[float] = None,
               now: Optional[float] = None) -> ServeRequest:
        """Enqueue one request; ``timeout`` (seconds from now) sets its
        absolute deadline."""
        r = self.admit(query, timeout=timeout, now=now)
        self._pending.append(r)
        return r

    def expire(self, now: Optional[float] = None) -> list[ServeRequest]:
        """Remove (and mark ``timed_out``) every pending request whose
        deadline has passed — they will never be executed."""
        now = self.clock() if now is None else now
        dead = [r for r in self._pending
                if r.deadline is not None and now > r.deadline]
        if dead:
            gone = {r.seq for r in dead}
            self._pending = [r for r in self._pending if r.seq not in gone]
            for r in dead:
                r.status = TIMED_OUT
                r.done = now
        return dead

    def poll(self, now: Optional[float] = None, *, flush: bool = False
             ) -> tuple[Optional[list[ServeRequest]], list[ServeRequest]]:
        """-> (batch | None, expired).

        Expiry runs first (expired requests never enter a batch); then a
        batch of the OLDEST ≤ ``batch_size`` requests cuts when the queue
        is full, the oldest request aged past ``max_wait``, or ``flush``
        forces the remainder out. Each request of the batch is stamped
        with ``now`` (``cut``) and the batch's number, and ``counts`` take
        the cut, its reason and its queue wait."""
        now = self.clock() if now is None else now
        expired = self.expire(now)
        if not self._pending:
            return None, expired
        if len(self._pending) >= self.batch_size:
            why = "batches_full"
        elif now - self._pending[0].arrival >= self.max_wait:
            why = "batches_age"
        elif flush:
            why = "batches_flush"
        else:
            return None, expired
        batch = self._pending[: self.batch_size]
        self._pending = self._pending[self.batch_size:]
        snap = self.snapshot_fn() if self.snapshot_fn is not None else None
        for r in batch:
            r.cut, r.batch = now, self.n_cut
            if snap is not None:
                r.snapshot = snap  # snapshot-at-cut: one per batch
        self.counts[why] += 1
        self.counts["requests_cut"] += len(batch)
        self.counts["queue_wait_s"] += sum(r.cut - r.arrival for r in batch)
        return batch, expired

    def drain(self) -> list[ServeRequest]:
        """Remove and return every pending request (engine shutdown)."""
        out, self._pending = self._pending, []
        return out

    def next_event(self, now: Optional[float] = None) -> Optional[float]:
        """Earliest future instant a poll could act — the oldest request's
        cut-on-age instant or the soonest deadline — or None when idle."""
        if not self._pending:
            return None
        t = self._pending[0].arrival + self.max_wait
        for r in self._pending:
            if r.deadline is not None:
                t = min(t, r.deadline)
        return t


class CompactionScheduler:
    """Background hot→cold compaction — the same single-worker-thread
    pattern ``AsyncServingEngine`` executes batches with, on its OWN pool
    so a compaction can never delay a batch (and vice versa). At most one
    compaction runs at a time; ``maybe_schedule()`` is cheap and safe to
    call from any thread (the ingest path calls it on every insert that
    fills the hot segment, the drainer nudges it between batches)."""

    def __init__(self, tiered):
        self.tiered = tiered
        self._pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
        self._inflight: Optional[concurrent.futures.Future] = None
        self.n_scheduled = 0
        # the first compaction that raised; no compaction is scheduled after
        # it, and drain() re-raises it so the failure reaches the caller
        self.error: Optional[BaseException] = None

    def _reap(self) -> bool:
        """Collect a finished compaction. -> True when none is in flight."""
        f = self._inflight
        if f is None:
            return True
        if not f.done():
            return False
        self._inflight = None
        if self.error is None and f.exception() is not None:
            self.error = f.exception()
        return True

    def maybe_schedule(self) -> bool:
        """Submit one compaction if the hot segment needs it, none is
        already in flight and none has failed. Returns True when one was
        submitted."""
        if not self._reap() or self.error is not None:
            return False
        if not self.tiered.needs_compaction():
            return False
        self._inflight = self._pool.submit(self.tiered.compact)
        self.n_scheduled += 1
        return True

    def drain(self) -> None:
        """Wait out the in-flight compaction and stop the worker; re-raise
        the first compaction failure."""
        if self._inflight is not None:
            concurrent.futures.wait([self._inflight])
            self._reap()
        self._pool.shutdown(wait=True)
        if self.error is not None:
            raise self.error


class AsyncServingEngine:
    """Asyncio deployment front-end over a fitted ``BoomHQ``.

    ``submit()`` coroutines from any number of concurrent callers enqueue
    into one ``BatchFormer``; a single drainer task cuts batches
    (cut-on-full / cut-on-age) and executes each through
    ``BoomHQ.execute_batch`` — one fused optimizer dispatch + grouped
    (possibly cross-shard) execution per batch — inside a worker thread so
    new arrivals keep landing while a batch runs. Expired requests resolve
    with ``status == "timed_out"`` and are never executed.
    """

    def __init__(self, boomhq, *, batch_size: int = 32,
                 max_wait: float = 0.05,
                 default_timeout: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic,
                 semcache=None):
        self.bq = boomhq
        self.former = BatchFormer(batch_size=batch_size, max_wait=max_wait,
                                  clock=clock)
        # the front end's counters: cuts by reason, requests cut and their
        # summed queue wait (``BatchFormer.counts``)
        self.counts = self.former.counts
        # optional serve.semcache.SemanticCache consulted at submit time:
        # hits resolve immediately (zero scan cost), misses populate after
        # their batch executes, stamped with the batch snapshot's token
        self.semcache = semcache
        self.default_timeout = default_timeout
        self.clock = clock
        self._futures: dict[int, asyncio.Future] = {}
        self._event: Optional[asyncio.Event] = None
        self._task: Optional[asyncio.Task] = None
        self._served: list[ServeRequest] = []
        self._n_batches = 0
        self._t0: Optional[float] = None
        self._pool: Optional[concurrent.futures.ThreadPoolExecutor] = None
        self._compactor: Optional[CompactionScheduler] = None

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> "AsyncServingEngine":
        if self._task is None:
            self._event = asyncio.Event()
            # ONE worker thread: batches execute strictly in formation
            # order, and a late stop() flush can never race the drainer
            # into two concurrent execute_batch calls
            self._pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
            if getattr(self.bq, "tiered", None) is not None:
                # snapshot-at-cut: every batch executes against one
                # immutable (epoch, hot, cold) view, and compaction runs
                # on its own worker so serving never pauses for it
                self.former.snapshot_fn = self.bq.tiered.snapshot
                self._compactor = CompactionScheduler(self.bq.tiered)
                self.bq._compactor = self._compactor
            self._task = asyncio.get_running_loop().create_task(self._drain())
        return self

    async def stop(self, *, flush: bool = True) -> None:
        """Serve (or expire) everything still queued, then stop the drainer
        and tear down the worker thread."""
        if self._task is None:
            return
        while flush and (len(self.former) or not self._all_resolved()):
            self._event.set()
            await asyncio.sleep(1e-3)
            batch, expired = self.former.poll(flush=True)
            self._resolve_expired(expired)
            if batch:
                await self._execute(batch)
        self._task.cancel()
        try:
            await self._task
        except asyncio.CancelledError:
            pass
        self._task = None
        # flush=False: fail everything never formed into a batch (and any
        # straggler future) so no submit() caller is left hanging — the
        # in-flight batch's futures were already failed by _execute's
        # cancellation branch
        for r in self.former.drain():
            r.status = FAILED
            r.done = self.clock()
            self._finish(r, exc=asyncio.CancelledError("engine stopped"))
        for seq in list(self._futures):
            fut = self._futures.pop(seq)
            if not fut.done():
                fut.set_exception(asyncio.CancelledError("engine stopped"))
        # wait=False: do not block the event loop on a discarded batch
        self._pool.shutdown(wait=False, cancel_futures=True)
        self._pool = None
        if self._compactor is not None:
            # let the in-flight compaction land (it owns published state);
            # a compaction that raised re-raises here, out of stop()
            try:
                await asyncio.get_running_loop().run_in_executor(
                    None, self._compactor.drain)
            finally:
                if getattr(self.bq, "_compactor", None) is self._compactor:
                    self.bq._compactor = None
                self._compactor = None

    async def __aenter__(self) -> "AsyncServingEngine":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    def _all_resolved(self) -> bool:
        return not self._futures

    # -- request path ------------------------------------------------------

    def _cache_token(self) -> tuple:
        """CURRENT freshness token for semantic-cache admission:
        ``(epoch, n_rows)`` of the tiered snapshot (an epoch bump OR any
        hot-tier insert changes it), or ``(0, table.n_rows)`` untiered
        (eager inserts grow the table). One snapshot pointer read — never
        the mutable tiering fields (EP001)."""
        tiered = getattr(self.bq, "tiered", None)
        if tiered is not None:
            snap = tiered.snapshot()
            return (snap.epoch, snap.n_rows)
        return (0, self.bq.table.n_rows)

    async def submit(self, query: MHQ, *, timeout=_DEFAULT) -> ServeRequest:
        """Enqueue one query and await its disposition. Returns the resolved
        ``ServeRequest`` (``status`` is ``"ok"`` with ``result`` set, or
        ``"timed_out"`` with ``result`` None). With a semantic cache bound,
        a fresh-enough repeat resolves HERE — never queued, never executed,
        ``cache_hit`` set."""
        await self.start()
        tmo = self.default_timeout if timeout is _DEFAULT else timeout
        # fold the tenant namespace BEFORE the cache key is computed, so
        # the implicit conjunct is part of the predicate signature
        if getattr(query, "tenant_id", None) is not None and \
                hasattr(self.bq, "resolve_tenant"):
            query = self.bq.resolve_tenant(query)
        if self.semcache is not None:
            cached = self.semcache.lookup(query, self._cache_token())
            if cached is not None:
                r = self.former.admit(query, timeout=tmo)
                if self._t0 is None:
                    self._t0 = r.arrival
                r.status = OK
                r.result = cached
                r.cache_hit = True
                r.done = self.clock()
                self._served.append(r)
                return r
        r = self.former.submit(query, timeout=tmo)
        if self._t0 is None:
            self._t0 = r.arrival
        fut = asyncio.get_running_loop().create_future()
        self._futures[r.seq] = fut
        self._event.set()
        await fut
        return r

    async def _drain(self) -> None:
        while True:
            if self._compactor is not None:
                self._compactor.maybe_schedule()
            batch, expired = self.former.poll()
            self._resolve_expired(expired)
            if batch:
                await self._execute(batch)
                continue  # queue may already hold the next full batch
            nxt = self.former.next_event()
            # a pending request waits for its cut (age or deadline); with
            # none pending the drainer waits for an arrival. The span is
            # closed however the wait ends, a cancelling stop() included
            if nxt is None:
                waiting = span("hq.frontend.await_arrival")
            else:
                waiting = span("hq.frontend.cut_wait", self.former.n_cut)
            with waiting:
                try:
                    wait = None if nxt is None \
                        else max(1e-4, nxt - self.clock())
                    await asyncio.wait_for(self._event.wait(), wait)
                except asyncio.TimeoutError:
                    pass
            self._event.clear()

    async def _execute(self, batch: list[ServeRequest]) -> None:
        # deadline enforcement does NOT stop at cut time: a request whose
        # deadline passed while its batch sat behind an in-flight one must
        # resolve timed_out here, not execute and report OK (same strict
        # `now > deadline` rule as BatchFormer.expire)
        now = self.clock()
        late = [r for r in batch
                if r.deadline is not None and now > r.deadline]
        if late:
            for r in late:
                r.status = TIMED_OUT
                r.done = now
                self._finish(r)
            batch = [r for r in batch if r.status == PENDING]
            if not batch:
                return
        loop = asyncio.get_running_loop()
        queries = [r.query for r in batch]
        if batch[0].snapshot is not None:
            # the whole batch shares the snapshot stamped at cut time —
            # an epoch swap landing mid-flight cannot change what it sees
            run = functools.partial(
                self.bq.execute_batch, queries, snapshot=batch[0].snapshot)
        else:
            run = functools.partial(self.bq.execute_batch, queries)
        # the worker's spans carry the batch's number (common.spans)
        ctx = contextvars.copy_context()
        ctx.run(BATCH.set, batch[0].batch)
        exec_fut = loop.run_in_executor(self._pool, ctx.run, run)
        try:
            results = await asyncio.shield(exec_fut)
        except asyncio.CancelledError:
            # stop(flush=False) cancelled the drainer mid-batch: fail the
            # in-flight batch's futures so no submit() caller is stranded,
            # swallow the worker's eventual outcome, finish cancelling
            exec_fut.add_done_callback(
                lambda f: f.cancelled() or f.exception())
            now = self.clock()
            for r in batch:
                r.status = FAILED
                r.done = now
                self._finish(r, exc=asyncio.CancelledError("engine stopped"))
            raise
        except Exception as exc:  # noqa: BLE001 — a failed batch must fail
            # ITS requests (submit() re-raises), never kill the drainer:
            # a dead drainer would strand every later future forever
            now = self.clock()
            self._n_batches += 1
            for r in batch:
                r.status = FAILED
                r.done = now
                self._finish(r, exc=exc)
            return
        now = self.clock()
        self._n_batches += 1
        with span("hq.frontend.resolve", batch[0].batch):
            token = None
            if self.semcache is not None:
                snap = batch[0].snapshot
                # stamp entries with the token of the state the batch
                # actually executed under (its cut-time snapshot), not the
                # current one — an epoch swap mid-flight must leave these
                # entries born stale
                token = (snap.epoch, snap.n_rows) if snap is not None \
                    else (0, self.bq.table.n_rows)
            for r, res in zip(batch, results):
                r.status = OK
                r.result = res
                r.done = now
                if token is not None:
                    self.semcache.insert(r.query, token, res[0], res[1])
                self._finish(r)

    def _resolve_expired(self, expired: list[ServeRequest]) -> None:
        for r in expired:
            self._finish(r)

    def _finish(self, r: ServeRequest, *, exc: Optional[Exception] = None
                ) -> None:
        self._served.append(r)
        fut = self._futures.pop(r.seq, None)
        if fut is not None and not fut.done():
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.set_result(r)

    # -- accounting --------------------------------------------------------

    def report(self, *, gt_ids: Optional[dict] = None) -> ServeReport:
        """Aggregate dispositions/latency over everything served so far.
        ``gt_ids``: optional ``{seq: ground-truth id array}`` for recall
        accounting over the OK requests."""
        served = sorted(self._served, key=lambda r: r.seq)
        ok = [r for r in served if r.status == OK]
        lats = np.asarray([r.latency for r in ok], np.float64)
        t_end = max((r.done for r in served), default=0.0)
        seconds = max(t_end - (self._t0 or 0.0), 1e-9) if served else 0.0
        recalls = None
        if gt_ids is not None:
            recalls = [recall_at_k(r.result[0], gt_ids[r.seq])
                       for r in ok if r.seq in gt_ids]
        tiered = getattr(self.bq, "tiered", None)
        tenants: dict = {}
        for r in served:
            t = getattr(r.query, "tenant_id", None)
            d = tenants.setdefault(t, {
                "n_queries": 0, "n_ok": 0, "n_timed_out": 0,
                "n_cache_hits": 0, "recalls": []})
            d["n_queries"] += 1
            d["n_ok"] += r.status == OK
            d["n_timed_out"] += r.status == TIMED_OUT
            d["n_cache_hits"] += r.cache_hit
            if r.status == OK and gt_ids is not None and r.seq in gt_ids:
                d["recalls"].append(recall_at_k(r.result[0], gt_ids[r.seq]))
        for d in tenants.values():
            rs = d.pop("recalls")  # host floats from recall_at_k
            d["mean_recall"] = sum(rs) / len(rs) if rs else None
            d["qps"] = d["n_ok"] / seconds if served else 0.0
        return ServeReport(
            n_queries=len(served),
            n_batches=self._n_batches,
            seconds=seconds,
            qps=len(ok) / seconds if served else 0.0,
            mean_recall=float(np.mean(recalls)) if recalls else None,
            recalls=recalls,
            n_timed_out=sum(r.status == TIMED_OUT for r in served),
            p50_ms=float(np.percentile(lats, 50) * 1e3) if len(lats) else None,
            p99_ms=float(np.percentile(lats, 99) * 1e3) if len(lats) else None,
            n_inserted=0 if tiered is None else tiered.n_inserted,
            n_compactions=0 if tiered is None else tiered.n_compactions,
            epoch=0 if tiered is None else tiered.epoch,
            n_cache_hits=sum(r.cache_hit for r in served),
            tenants=tenants or None,
        )


async def serve_stream(engine: AsyncServingEngine, queries: list[MHQ], *,
                       arrival_gaps: Optional[list[float]] = None,
                       timeout=_DEFAULT) -> list[ServeRequest]:
    """Submit a query stream with the given inter-arrival gaps (seconds;
    None = all-at-once) and await every disposition. Returns the resolved
    requests in submission order — the open-loop driver benchmarks and
    examples use for Poisson traffic."""
    async with engine:
        tasks = []
        for i, q in enumerate(queries):
            if arrival_gaps is not None and i > 0:
                await asyncio.sleep(arrival_gaps[i - 1])
            tasks.append(asyncio.ensure_future(
                engine.submit(q, timeout=timeout)))
        return list(await asyncio.gather(*tasks))
