"""Tiered hot/cold table: LSM-style streaming ingest over the IVF tier.

The serving stack was build-once: ``ivf.extend`` regrouped buckets eagerly
and every insert rebuilt the executor, so live traffic had no path from an
insert to the probing tier. This module adds the two-tier table that closes
that gap (ROADMAP open item 1):

* a small writable **hot segment** — flat, bounded capacity, append-only.
  Queries always score it candidate-locally with the fused
  ``kernels.gather_score`` kernel and filter the predicate EXACTLY, so hot
  rows never cost recall; the segment is bounded, so the extra scan is
  O(capacity) per batch regardless of table size.
* sealed **cold state** — the existing ``Table`` + per-column IVF indexes
  (and, under ``bind_shards``, the ``ShardedIVF`` tier built from them),
  searched through the unchanged plan-driven probing paths.

Row ids are GLOBAL: cold rows keep ``[0, n_cold)`` and hot rows are numbered
``id_offset + local_slot`` where ``id_offset`` is the cold row count when
their hot generation opened. Compaction appends a generation's rows to the
cold table at exactly those positions, so ids are stable across the
hot→cold transition and the existing O(shards·k) dedup merge, the underfill
escalation and the recall contracts all survive unchanged.

Concurrency model — the **epoch-swap protocol**:

* All mutable state (``_hot``/``_sealing``/``_cold``/``_epoch``) lives
  behind one condition lock and is NEVER read by serving code. Queries call
  ``snapshot()`` once at batch-formation time and execute the whole batch
  against that immutable ``TieredSnapshot`` — boomlint rule EP001 enforces
  this repo-wide (docs/analysis.md).
* Inserts append in place into the active generation's buffers. Appends
  only ever touch rows at-or-beyond every published snapshot's ``count``,
  which the candidate mask excludes, so in-flight queries are isolated
  without copying.
* When the active generation fills it is **sealed** (frozen view published
  alongside a fresh empty generation) and **compaction** — normally on a
  background worker thread (``serve.queue.CompactionScheduler``, the
  ``AsyncServingEngine`` worker-pool pattern) — folds the sealed rows into
  the cold table/indexes via the incremental ``ivf.extend`` path, then
  publishes the new cold state by swapping the snapshot pointer and
  bumping the **epoch**. Serving never pauses: batches formed before the
  swap keep their old snapshot, batches formed after read the new one.
* Only the INGEST side ever blocks (backpressure): an insert that outruns
  both generations waits for the in-flight compaction, or runs one inline
  on the caller's thread.

The epoch also drives the accounting that keeps plans honest as data
drifts: compaction re-bins the sealed rows into the selectivity histograms
(planning sees them once they are cold; hot rows are exact-filtered so they
need no estimate) and ``rows_since_finetune`` tracks encoder staleness
until the owner's finetune callback clears it.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.vectordb import graph as graphmod
from repro.vectordb import histogram, ivf
from repro.vectordb.table import Table

DEFAULT_HOT_CAPACITY = 1024


# host->device materializations of hot views, for the per-insert transfer
# accounting (one "transfer" = one column buffer moved); see hot_view_transfers
_transfer_lock = threading.Lock()
_hot_view_transfers = 0


def hot_view_transfers() -> int:
    """Cumulative count of hot-view column buffers copied host->device.

    Publishing a view is free — the device copies are built lazily on the
    first reader — so the delta across an insert-only window (no query
    snapshots consumed) must be 0."""
    with _transfer_lock:
        return _hot_view_transfers


class HotView:
    """Immutable logical view of one hot generation at a published instant.

    Construction is a host-side token: it captures REFERENCES to the
    generation's full-capacity host buffers plus ``count``/``id_offset``.
    The device copies (static shapes keep the jit cache bounded), with the
    gather kernel's row views (``rows``), are built LAZILY — on the first
    ``rows``/``vectors``/``scalars`` read, i.e. the first query snapshot
    that actually scores this view — and cached per view, so
    insert-heavy windows with no interleaved reads publish versions at zero
    transfer cost (``hot_view_transfers`` counts the copies).

    Only rows ``< count`` are valid: the candidate mask in the hot top-k
    excludes the rest, and appends only ever write rows at-or-beyond every
    published view's ``count``, so a late materialization still reads
    exactly the rows the view logically froze."""

    __slots__ = ("np_vectors", "np_scalars", "count", "id_offset",
                 "_device", "_lock")

    def __init__(self, np_vectors: tuple, np_scalars: np.ndarray,
                 count: int, id_offset: int):
        self.np_vectors = tuple(np_vectors)  # per-column (capacity, d_i) f32
        self.np_scalars = np_scalars  # (capacity, M) f32
        self.count = count  # valid rows
        self.id_offset = id_offset  # global row id of local slot 0
        self._device = None
        self._lock = threading.Lock()

    @property
    def rows(self):
        """The view's device copy as ``GatherRows``."""
        if self._device is None:
            with self._lock:
                if self._device is None:
                    from repro.kernels.gather_score import GatherRows

                    global _hot_view_transfers
                    dev = GatherRows.build(
                        tuple(jnp.asarray(b) for b in self.np_vectors),
                        jnp.asarray(self.np_scalars))
                    with _transfer_lock:
                        _hot_view_transfers += len(self.np_vectors) + 1
                    self._device = dev
        return self._device

    @property
    def vectors(self) -> tuple:
        return self.rows.vectors

    @property
    def scalars(self) -> jax.Array:
        return self.rows.scalars

    @property
    def capacity(self) -> int:
        return int(self.np_scalars.shape[0])


@dataclasses.dataclass(frozen=True)
class ColdState:
    """One sealed cold epoch: table + per-column IVF + histograms, plus the
    optional per-column proximity graphs (the third-strategy tier — sealed
    exactly like the IVF state, extended on compaction, ``None`` when the
    deployment has no graph tier)."""

    table: Table
    indexes: tuple
    hists: histogram.Histograms
    graphs: tuple | None = None


@dataclasses.dataclass(frozen=True)
class TieredSnapshot:
    """The consistent ``(epoch, hot_view, cold_shards)`` unit every batch
    executes against. Immutable — a swap publishes a NEW snapshot; nothing
    a formed batch holds ever mutates."""

    epoch: int
    cold: ColdState
    hot_views: tuple  # 0..2 HotView (active [+ sealing during compaction])

    @property
    def n_hot(self) -> int:
        return sum(v.count for v in self.hot_views)

    @property
    def n_rows(self) -> int:
        """Logical row count (cold + every hot view)."""
        return self.cold.table.n_rows + self.n_hot


class _HotBuffer:
    """Mutable append-only host-side backing of one hot generation."""

    def __init__(self, schema, capacity: int, id_offset: int):
        self.vectors = [np.zeros((capacity, vc.dim), np.float32)
                        for vc in schema.vector_cols]
        self.scalars = np.zeros((capacity, schema.n_scalar), np.float32)
        self.count = 0
        self.id_offset = id_offset
        self.capacity = capacity

    def write(self, vecs: list, scal: np.ndarray, pos: int, take: int) -> None:
        lo = self.count
        for buf, v in zip(self.vectors, vecs):
            buf[lo: lo + take] = v[pos: pos + take]
        self.scalars[lo: lo + take] = scal[pos: pos + take]
        self.count += take

    def view(self) -> HotView:
        # a host-side token over the live buffers: rows >= count are stale
        # garbage (or rows appended after this publish) and masked out by
        # every consumer; device copies happen on first read (lazy)
        return HotView(
            np_vectors=tuple(self.vectors),
            np_scalars=self.scalars,
            count=self.count,
            id_offset=self.id_offset,
        )


class TieredTable:
    """Writable hot segment in front of sealed cold IVF state.

    Owns ALL mutable tiering state. Serving code must read through
    ``snapshot()`` (EP001); ingest goes through ``insert()``; compaction
    through ``compact()`` — safe from any thread.
    """

    def __init__(self, table: Table, indexes, hists, *,
                 hot_capacity: int = DEFAULT_HOT_CAPACITY,
                 rebuild_every: int = 0,
                 finetune_cb: Optional[Callable] = None,
                 graphs=None):
        assert hot_capacity >= 1
        self.schema = table.schema
        self.hot_capacity = hot_capacity
        # sealing step: every Nth compaction re-clusters the whole column
        # (full k-means rebuild) instead of the incremental centroid-assign
        # extend; 0 = incremental only
        self.rebuild_every = rebuild_every
        self.finetune_cb = finetune_cb
        self._cond = threading.Condition()
        self._cold = ColdState(
            table, tuple(indexes), hists,
            tuple(graphs) if graphs is not None else None)
        self._hot = _HotBuffer(table.schema, hot_capacity,
                               id_offset=table.n_rows)
        self._sealing: Optional[HotView] = None
        self._compacting = False
        self._epoch = 0
        self._compactions = 0
        self._inserted = 0
        self._rows_since_finetune = 0
        self._snap = self._build_snapshot()

    # -- the one sanctioned read path --------------------------------------

    def snapshot(self) -> TieredSnapshot:
        """The current published ``(epoch, hot_view, cold_shards)`` —
        ONE atomic pointer read. Take it once at batch formation and use it
        for the whole batch; never read the mutable fields (EP001)."""
        return self._snap

    # -- bookkeeping (host-side, locked) ------------------------------------

    def _build_snapshot(self) -> TieredSnapshot:
        views = []
        if self._sealing is not None and self._sealing.count > 0:
            views.append(self._sealing)
        if self._hot.count > 0:
            views.append(self._hot.view())
        return TieredSnapshot(epoch=self._epoch, cold=self._cold,
                              hot_views=tuple(views))

    def _publish_locked(self) -> None:
        self._snap = self._build_snapshot()

    def _seal_locked(self) -> None:
        """Freeze the (full) active generation and open a fresh one whose
        id space starts right behind it."""
        assert self._sealing is None
        self._sealing = self._hot.view()
        self._hot = _HotBuffer(
            self.schema, self.hot_capacity,
            id_offset=self._sealing.id_offset + self._sealing.count)
        self._publish_locked()

    # -- ingest -------------------------------------------------------------

    def insert(self, vectors: list, scalars) -> dict:
        """Append rows to the hot segment; global ids are assigned in
        arrival order. Never blocks serving — only the INGEST caller waits
        (or compacts inline) when both generations are full."""
        vecs = [np.asarray(v, np.float32) for v in vectors]
        scal = np.asarray(scalars, np.float32)
        m = int(scal.shape[0])
        pos = 0
        while pos < m:
            run_inline = False
            with self._cond:
                free = self._hot.capacity - self._hot.count
                if free > 0:
                    take = min(free, m - pos)
                    self._hot.write(vecs, scal, pos, take)
                    pos += take
                    self._publish_locked()
                    if pos == m:
                        break
                    continue
                # active generation full and rows remain: make room
                if self._compacting:
                    # backpressure: the in-flight compaction publishes soon
                    self._cond.wait(timeout=30.0)
                    continue
                if self._sealing is None:
                    self._seal_locked()
                    continue
                run_inline = True  # sealed segment pending, no worker
            if run_inline:
                self.compact()
        with self._cond:
            self._inserted += m
            self._rows_since_finetune += m
            return {"inserted": m, "hot_fill": self._hot.count,
                    "hot_capacity": self.hot_capacity,
                    "needs_compaction": self._needs_compaction_locked(),
                    "epoch": self._epoch}

    def _needs_compaction_locked(self) -> bool:
        return self._sealing is not None or \
            self._hot.count >= self.hot_capacity

    def needs_compaction(self) -> bool:
        with self._cond:
            return self._needs_compaction_locked() and not self._compacting

    # -- compaction ---------------------------------------------------------

    def compact(self) -> dict:
        """Fold the sealed hot generation into the cold state and publish
        under a new epoch. Heavy work (cluster assignment, bucket insert,
        histogram re-bin, optional encoder finetune) runs OUTSIDE the lock;
        in-flight batches keep their pre-swap snapshot throughout."""
        t0 = time.perf_counter()
        with self._cond:
            if self._compacting:
                return {"compacted": 0, "epoch": self._epoch}
            if self._sealing is None:
                if self._hot.count == 0:
                    return {"compacted": 0, "epoch": self._epoch}
                self._seal_locked()
            frozen = self._sealing
            cold = self._cold
            # the rebuild_every decision is a function of WHICH compaction
            # this is — capture the sequence number under the lock at seal
            # time (reading self._compactions in the unlocked section below
            # raced concurrent compactions and could skip or double-fire
            # the re-cluster)
            seq = self._compactions + 1
            self._compacting = True
        rebuild = self.rebuild_every > 0 and seq % self.rebuild_every == 0
        try:
            n = frozen.count
            first_new = cold.table.n_rows
            assert first_new == frozen.id_offset  # global ids stay stable
            new_vecs = [b[:n] for b in frozen.np_vectors]
            new_scal = frozen.np_scalars[:n]
            table = cold.table.append(new_vecs, new_scal)
            if rebuild:  # sealing step: full re-cluster of every column
                indexes = tuple(
                    ivf.build(v, idx.n_clusters, seed=i, metric=idx.metric)
                    for i, (idx, v) in enumerate(
                        zip(cold.indexes, table.vectors)))
            else:  # steady state: nearest-centroid incremental insert
                indexes = tuple(
                    ivf.extend(idx, jnp.asarray(v), first_new)
                    for idx, v in zip(cold.indexes, new_vecs))
            hists = histogram.update(cold.hists, jnp.asarray(new_scal))
            # the graph tier seals alongside the IVF state: new rows get
            # forward edges against the full post-append column plus
            # reverse-edge splices into their neighbors' free slots
            # (graph.extend keeps the incremental path even on rebuild
            # compactions — re-running the full kNN+prune build per sealing
            # step would dominate the compaction)
            graphs = None if cold.graphs is None else tuple(
                graphmod.extend(g, jnp.asarray(v), first_new)
                for g, v in zip(cold.graphs, table.vectors))
            new_cold = ColdState(table, indexes, hists, graphs)
            if self.finetune_cb is not None:
                self.finetune_cb(new_cold, first_new, n)
                with self._cond:
                    self._rows_since_finetune = max(
                        0, self._rows_since_finetune - n)
        except BaseException:
            with self._cond:  # leave the sealed segment intact for a retry
                self._compacting = False
                self._cond.notify_all()
            raise
        with self._cond:
            self._cold = new_cold
            self._sealing = None
            self._epoch += 1
            self._compactions += 1
            self._compacting = False
            self._publish_locked()
            self._cond.notify_all()
        return {"compacted": n, "epoch": self._epoch, "rebuild": rebuild,
                "seconds": time.perf_counter() - t0}

    # -- accounting ---------------------------------------------------------

    @property
    def epoch(self) -> int:
        with self._cond:
            return self._epoch

    @property
    def n_compactions(self) -> int:
        with self._cond:
            return self._compactions

    @property
    def n_inserted(self) -> int:
        with self._cond:
            return self._inserted

    def encoder_staleness(self) -> float:
        """Fraction of logical rows the data encoder has never seen —
        epoch-fed drift accounting for the owner's finetune policy."""
        with self._cond:
            snap = self._snap
            return self._rows_since_finetune / max(1, snap.n_rows)

    def logical_table(self) -> Table:
        """Materialize the concatenated logical table (cold ‖ hot views) —
        for oracles, ground truth and offline use, NOT the serving path."""
        snap = self.snapshot()
        t = snap.cold.table
        if not snap.hot_views:
            return t
        vecs = [np.asarray(v) for v in t.vectors]
        scal = np.asarray(t.scalars)
        for view in snap.hot_views:
            vecs = [np.concatenate([a, b[: view.count]])
                    for a, b in zip(vecs, view.np_vectors)]
            scal = np.concatenate([scal, view.np_scalars[: view.count]])
        return Table.from_numpy(t.schema, vecs, scal)


# ---------------------------------------------------------------------------
# hot-segment scoring + merge (the query-side half)
# ---------------------------------------------------------------------------

def _hot_topk(view_args, qs, weights, pred_b, *, k: int, metric: str):
    """Exact filtered top-k over one hot view for a padded query batch.

    Candidate slots are the full static capacity masked down to ``count``,
    scored with the fused candidate-local kernel — the hot segment is just
    one more candidate source. Local slot ids map to global ids via the
    view's offset."""
    from repro.kernels.gather_score import gather_score_topk

    rows, count, id_offset = view_args
    cap = rows.scalars.shape[0]
    b = weights.shape[0]
    slots = jnp.arange(cap, dtype=jnp.int32)
    cand = jnp.where(slots[None, :] < count, slots[None, :], -1)
    cand = jnp.broadcast_to(cand, (b, cap)).astype(jnp.int32)
    ids, scores, n_qual = gather_score_topk(
        cand, rows, qs, weights, pred_b, k=k, metric=metric)
    ids = jnp.where(ids >= 0, ids + id_offset, -1).astype(jnp.int32)
    return ids, scores, n_qual


@partial(jax.jit, static_argnames=("k", "metric"))
def merge_hot_batch(cold_ids, cold_scores, views, qs, weights, pred_b, *,
                    k: int, metric: str):
    """Fold every hot view's exact candidates into the cold results through
    the existing O(shards·k) dedup merge (``merge_topk_unique``): the hot
    segment rides the same contract as one more shard. Hot and cold id
    spaces are disjoint by construction, so dedup is a no-op and ties break
    by smaller global id exactly like the sharded merge.

    ``views``: tuple of (rows, count, id_offset) pytrees —
    count/id_offset ride as traced scalars so inserts never recompile;
    only the view COUNT (1 vs 2, during compaction) and the static shapes
    key the jit cache."""
    from repro.kernels.gather_score import merge_topk_unique

    all_ids, all_scores = [cold_ids], [cold_scores]
    for view_args in views:
        ids, scores, _ = _hot_topk(view_args, qs, weights, pred_b,
                                   k=k, metric=metric)
        all_ids.append(ids)
        all_scores.append(scores)
    return merge_topk_unique(jnp.concatenate(all_ids, axis=1),
                             jnp.concatenate(all_scores, axis=1), k)


def view_args(view: HotView):
    """HotView -> the traced pytree ``merge_hot_batch`` consumes."""
    return (view.rows, jnp.asarray(view.count, jnp.int32),
            jnp.asarray(view.id_offset, jnp.int32))
