"""IVF-Flat index: k-means clustering + probe-based search.

TPU adaptation of the paper's HNSW substrate (DESIGN.md §2): the navigable
graph becomes a cluster decomposition; ``ef_search`` becomes ``nprobe``;
``max_scan_tuples`` caps the gathered candidate count; ``iterative_scan``
becomes nprobe re-expansion when the filtered result underfills k.

Everything is static-shape jit-able: the probed clusters' rows are mapped to
a fixed ``max_scan`` slot array via a prefix-sum + searchsorted trick, so a
single fused gather/score/mask/top-k runs on device regardless of how many
rows each cluster holds.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.shapes import GATHER_BLOCK_S, NEG
from repro.vectordb.predicates import PredicateLike, eval_mask
from repro.vectordb.table import similarity


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class IVFIndex:
    centroids: jax.Array  # (C, d)
    sorted_rows: jax.Array  # (n,) i32 — row ids grouped by cluster
    offsets: jax.Array  # (C+1,) i32 — cluster c owns sorted_rows[offsets[c]:offsets[c+1]]
    metric: str

    def tree_flatten(self):
        return (self.centroids, self.sorted_rows, self.offsets), self.metric

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, metric=aux)

    @property
    def n_clusters(self) -> int:
        return int(self.centroids.shape[0])


# ---------------------------------------------------------------------------
# k-means
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_clusters", "iters"))
def _kmeans(vectors: jax.Array, key: jax.Array, n_clusters: int, iters: int = 12):
    n = vectors.shape[0]
    idx = jax.random.choice(key, n, (n_clusters,), replace=False)
    cent = vectors[idx]

    def step(cent, _):
        d = (
            jnp.sum(cent * cent, axis=1)[None, :]
            - 2.0 * (vectors @ cent.T)
        )  # (n, C) up to +||v||² const
        assign = jnp.argmin(d, axis=1)
        one = jax.nn.one_hot(assign, n_clusters, dtype=jnp.float32)
        counts = one.sum(0)
        sums = one.T @ vectors
        newc = sums / jnp.maximum(counts[:, None], 1.0)
        # dead centroids keep their old position
        newc = jnp.where(counts[:, None] > 0, newc, cent)
        return newc, None

    cent, _ = jax.lax.scan(step, cent, None, length=iters)
    d = jnp.sum(cent * cent, axis=1)[None, :] - 2.0 * (vectors @ cent.T)
    assign = jnp.argmin(d, axis=1)
    return cent, assign


def build(vectors: jax.Array, n_clusters: int, seed: int = 0, iters: int = 12,
          metric: str = "dot") -> IVFIndex:
    cent, assign = _kmeans(vectors, jax.random.PRNGKey(seed), n_clusters, iters)
    assign_np = np.asarray(assign)
    order = np.argsort(assign_np, kind="stable").astype(np.int32)
    counts = np.bincount(assign_np, minlength=n_clusters)
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return IVFIndex(
        centroids=cent,
        sorted_rows=jnp.asarray(order),
        offsets=jnp.asarray(offsets),
        metric=metric,
    )


# Below this fraction of the existing rows an insert takes the incremental
# splice (one O(n + m) np.insert, no sort) instead of the full regroup —
# the compaction path's steady state folds one bounded hot segment at a
# time, always far under this.
EXTEND_INCREMENTAL_FRACTION = 0.25


def _assign_to_centroids(index: IVFIndex, new_vectors: jax.Array) -> np.ndarray:
    d = (
        jnp.sum(index.centroids * index.centroids, axis=1)[None, :]
        - 2.0 * (new_vectors @ index.centroids.T)
    )
    return np.asarray(jnp.argmin(d, axis=1))


def _extend_regroup(index: IVFIndex, assign: np.ndarray,
                    rows: np.ndarray) -> IVFIndex:
    old_rows = np.asarray(index.sorted_rows)
    old_off = np.asarray(index.offsets)
    C = index.n_clusters
    # One vectorized regroup pass, O((n + inserts) log): a stable sort of
    # [old assignments ‖ new assignments] keeps each cluster's existing rows
    # in order and appends the new rows in insertion order behind them.
    old_assign = np.repeat(np.arange(C), np.diff(old_off))
    all_assign = np.concatenate([old_assign, assign])
    all_rows = np.concatenate([old_rows, rows]).astype(np.int32)
    order = np.argsort(all_assign, kind="stable")
    counts = np.bincount(all_assign, minlength=C)
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return IVFIndex(
        centroids=index.centroids,
        sorted_rows=jnp.asarray(all_rows[order]),
        offsets=jnp.asarray(offsets),
        metric=index.metric,
    )


def _extend_incremental(index: IVFIndex, assign: np.ndarray,
                        rows: np.ndarray) -> IVFIndex:
    """Splice the new rows into their clusters without re-sorting the whole
    layout: every new row lands at the END of its cluster's segment
    (``np.insert`` is stable at equal positions, so rows sharing a cluster
    keep insertion order) — byte-identical to the regroup semantics at
    O(n + m) instead of O((n + m) log (n + m))."""
    old_rows = np.asarray(index.sorted_rows)
    old_off = np.asarray(index.offsets)
    C = index.n_clusters
    pos = old_off[assign + 1]  # insert just before the next cluster's rows
    sorted_rows = np.insert(old_rows, pos, rows).astype(np.int32)
    counts = np.bincount(assign, minlength=C)
    offsets = (old_off + np.concatenate(
        [[0], np.cumsum(counts)])).astype(np.int32)
    return IVFIndex(
        centroids=index.centroids,
        sorted_rows=jnp.asarray(sorted_rows),
        offsets=jnp.asarray(offsets),
        metric=index.metric,
    )


def extend(index: IVFIndex, new_vectors: jax.Array, first_new_row: int) -> IVFIndex:
    """Insert rows into existing clusters (centroids unchanged) — the cheap
    maintenance path that matches the paper's buffer-then-integrate updates
    and the tiered compaction's hot→cold fold. Small inserts (the steady
    compaction case) take the incremental splice; large ones the vectorized
    regroup — both produce identical layouts. The full re-cluster
    (``build``) stays the sealing step (``TieredTable.rebuild_every``)."""
    assign = _assign_to_centroids(index, new_vectors)
    rows = np.arange(first_new_row, first_new_row + new_vectors.shape[0],
                     dtype=np.int32)
    n_old = int(index.sorted_rows.shape[0])
    if rows.shape[0] <= max(1, int(n_old * EXTEND_INCREMENTAL_FRACTION)):
        return _extend_incremental(index, assign, rows)
    return _extend_regroup(index, assign, rows)


# ---------------------------------------------------------------------------
# probing search
# ---------------------------------------------------------------------------

def _candidate_slots(index: IVFIndex, probe_clusters: jax.Array, max_scan: int):
    """Map ``max_scan`` static slots onto the rows of the probed clusters.

    Returns (row_ids (max_scan,), valid (max_scan,)).
    """
    starts = index.offsets[probe_clusters]
    sizes = index.offsets[probe_clusters + 1] - starts
    cum = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    total = cum[-1]
    slots = jnp.arange(max_scan, dtype=jnp.int32)
    which = jnp.clip(jnp.searchsorted(cum, slots, side="right") - 1, 0, sizes.shape[0] - 1)
    within = slots - cum[which]
    valid = slots < jnp.minimum(total, max_scan)
    gather_pos = jnp.clip(starts[which] + within, 0, index.sorted_rows.shape[0] - 1)
    return index.sorted_rows[gather_pos], valid


def probe_scan_budget(n_clusters: int, n_rows: int, *, nprobe: int,
                      probe_k: int) -> int:
    """Candidate budget of one neighborhood pre-probe: ``nprobe`` clusters
    at ~4× the mean cluster size, floored at ``4·probe_k`` and capped at
    the table. Shared by ``preprobe``/``preprobe_scored`` and the planner's
    scan-cost estimate (``BoomHQ._plan_local``), so the dense-vs-local
    planning decision can never drift from what the probe gathers."""
    return min(n_rows,
               max(probe_k * 4, (nprobe * 4 * n_rows) // max(1, n_clusters)))


def probe_slots(index: IVFIndex, q: jax.Array, *, nprobe: int, max_scan: int):
    """Probe the ``nprobe`` closest clusters and map their rows onto
    ``max_scan`` static candidate slots. -> (rows (max_scan,), valid
    (max_scan,)) — the shared slot selection of every search variant."""
    csim = similarity(q, index.centroids, index.metric)
    _, probe_clusters = jax.lax.top_k(csim, nprobe)
    return _candidate_slots(index, probe_clusters, max_scan)


@partial(jax.jit, static_argnames=("nprobe", "max_scan", "k"))
def search(
    index: IVFIndex,
    vectors: jax.Array,  # (n, d) the indexed column
    scalars: jax.Array,  # (n, M)
    pred: PredicateLike,
    q: jax.Array,  # (d,)
    *,
    nprobe: int,
    max_scan: int,
    k: int,
):
    """Index-first filtered search on one vector column.

    Returns (ids (k,), scores (k,), n_scored (), n_qualified ()). Unfilled
    result slots carry id -1 / score NEG.
    """
    rows, valid = probe_slots(index, q, nprobe=nprobe, max_scan=max_scan)
    cand_vecs = vectors[rows]
    cand_scal = scalars[rows]
    scores = similarity(q, cand_vecs, index.metric)
    qual = eval_mask(pred, cand_scal) & valid
    masked = jnp.where(qual, scores, NEG)
    top_scores, top_idx = jax.lax.top_k(masked, k)
    ids = jnp.where(top_scores > NEG / 2, rows[top_idx], -1)
    return ids, top_scores, jnp.sum(valid), jnp.sum(qual)


@partial(jax.jit, static_argnames=("nprobe", "max_scan", "k"))
def search_scored(
    index: IVFIndex,
    row_scores: jax.Array,  # (n,) this column's precomputed query similarities
    scalars: jax.Array,
    pred: PredicateLike,
    q: jax.Array,
    *,
    nprobe: int,
    max_scan: int,
    k: int,
):
    """``search`` with the row similarities precomputed.

    The batched serving path scores ALL rows for a whole query batch in one
    multithreaded GEMM, then runs this cheap slot-select + score-gather per
    query — gathering f32 scores instead of (max_scan, d) vectors. Results
    match ``search`` up to float reduction order (GEMM vs gathered matvec).
    Re-probing at a larger nprobe reuses the same ``row_scores``.
    """
    rows, valid = probe_slots(index, q, nprobe=nprobe, max_scan=max_scan)
    scores = row_scores[rows]
    qual = eval_mask(pred, scalars[rows]) & valid
    masked = jnp.where(qual, scores, NEG)
    top_scores, top_idx = jax.lax.top_k(masked, k)
    ids = jnp.where(top_scores > NEG / 2, rows[top_idx], -1)
    return ids, top_scores, jnp.sum(valid), jnp.sum(qual)


@partial(jax.jit, static_argnames=("nprobe", "probe_k"))
def preprobe(
    index: IVFIndex,
    vectors: jax.Array,
    scalars: jax.Array,
    pred: PredicateLike,
    q: jax.Array,
    *,
    nprobe: int = 1,
    probe_k: int = 32,
):
    """Paper §3.3 neighborhood pre-probing: a cheap *unfiltered* ANN probe,
    then the local satisfaction rate of the predicates among those neighbors.

    Returns (rate (), mean_top_score ()).
    """
    csim = similarity(q, index.centroids, index.metric)
    _, probe_clusters = jax.lax.top_k(csim, nprobe)
    n = vectors.shape[0]
    max_scan = probe_scan_budget(index.n_clusters, n, nprobe=nprobe,
                                 probe_k=probe_k)
    rows, valid = _candidate_slots(index, probe_clusters, max_scan)
    scores = jnp.where(valid, similarity(q, vectors[rows], index.metric), NEG)
    return _probe_stats(scores, rows, scalars, pred, probe_k)


def _probe_stats(scores, rows, scalars, pred, probe_k):
    top_scores, top_idx = jax.lax.top_k(scores, probe_k)
    neigh_rows = rows[top_idx]
    ok = eval_mask(pred, scalars[neigh_rows])
    found = top_scores > NEG / 2
    rate = jnp.sum(ok & found) / jnp.maximum(jnp.sum(found), 1)
    mean_s = jnp.sum(jnp.where(found, top_scores, 0.0)) / jnp.maximum(jnp.sum(found), 1)
    return rate, mean_s


@partial(jax.jit, static_argnames=("nprobe", "probe_k"))
def preprobe_scored(
    index: IVFIndex,
    row_scores: jax.Array,  # (n,) this column's precomputed similarities
    scalars: jax.Array,
    pred: PredicateLike,
    q: jax.Array,
    *,
    nprobe: int = 1,
    probe_k: int = 32,
):
    """``preprobe`` with the row similarities precomputed — the batched
    optimizer path scores every row for the whole batch in one GEMM (shared
    with the batched executor) and gathers f32 scores here instead of
    materializing (batch, max_scan, d) vector tensors under vmap."""
    csim = similarity(q, index.centroids, index.metric)
    _, probe_clusters = jax.lax.top_k(csim, nprobe)
    n = row_scores.shape[0]
    max_scan = probe_scan_budget(index.n_clusters, n, nprobe=nprobe,
                                 probe_k=probe_k)
    rows, valid = _candidate_slots(index, probe_clusters, max_scan)
    scores = jnp.where(valid, row_scores[rows], NEG)
    return _probe_stats(scores, rows, scalars, pred, probe_k)


# ---------------------------------------------------------------------------
# candidate-local batched search (no dense score matrix)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("nprobe", "max_scan", "k", "use_kernel",
                                   "interpret", "block_s"))
def search_local_batch(
    index: IVFIndex,
    rows,  # GatherRows of the indexed column
    pred_b: PredicateLike,  # stacked, leading axis B
    q_b: jax.Array,  # (B, d)
    *,
    nprobe: int,
    max_scan: int,
    k: int,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    block_s: int = GATHER_BLOCK_S,
):
    """Candidate-local batched variant of ``search_scored``: no dense (B, n)
    score matrix is ever built. Candidate slots are selected per query (the
    cheap part) and ONE fused gather+score+mask+top-k
    (``kernels.gather_score``) touches only those ``B·max_scan`` rows —
    the path the dispatcher picks once ``B·max_scan / n_rows`` drops below
    the crossover. Returns (ids (B, k), scores (B, k), n_scored (B,),
    n_qualified (B,)); ties break by smaller row id (``search`` breaks by
    candidate-slot order, so near-exact ties may order differently)."""
    from repro.kernels.gather_score import gather_score_topk

    rows_b, valid_b = jax.vmap(
        lambda q: probe_slots(index, q, nprobe=nprobe, max_scan=max_scan))(q_b)
    cand = jnp.where(valid_b, rows_b, -1).astype(jnp.int32)
    w = jnp.ones((q_b.shape[0], 1), jnp.float32)
    ids, scores, n_qual = gather_score_topk(
        cand, rows, (q_b,), w, pred_b, k=k,
        metric=index.metric, use_kernel=use_kernel, interpret=interpret,
        block_s=block_s)
    return ids, scores, jnp.sum(valid_b, axis=1), n_qual


@partial(jax.jit, static_argnames=("nprobe", "max_scan", "k", "rerank_mult",
                                   "use_kernel", "interpret", "block_s"))
def search_local_batch_int8(
    index: IVFIndex,
    rows,  # GatherRows of the exact fp32 column (the rerank tier)
    rows_i8,  # GatherRows of its int8 replica (the scoring tier); both
    # carry the exact fp32 scalars
    pred_b: PredicateLike,  # stacked, leading axis B
    q_b: jax.Array,  # (B, d)
    *,
    nprobe: int,
    max_scan: int,
    k: int,
    rerank_mult: int | None = None,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    block_s: int = GATHER_BLOCK_S,
):
    """Quantized-tier ``search_local_batch``: identical slot selection, but
    the probed candidates are scored from the int8 replica (predicate
    filtering stays on the exact fp32 scalars) and only the top-α·k
    quantized survivors are re-scored exactly in fp32
    (``kernels.gather_score.gather_score_topk_int8``). Returned scores are
    exact fp32; quantization can only perturb WHICH α·k candidates reach
    the rerank, never their final scores or the qualified counts that
    drive iterative re-expansion."""
    from repro.kernels.gather_score import gather_score_topk_int8

    rows_b, valid_b = jax.vmap(
        lambda q: probe_slots(index, q, nprobe=nprobe, max_scan=max_scan))(q_b)
    cand = jnp.where(valid_b, rows_b, -1).astype(jnp.int32)
    w = jnp.ones((q_b.shape[0], 1), jnp.float32)
    kwargs = {} if rerank_mult is None else {"rerank_mult": rerank_mult}
    ids, scores, n_qual = gather_score_topk_int8(
        cand, rows, rows_i8, (q_b,), w, pred_b, k=k, metric=index.metric,
        use_kernel=use_kernel, interpret=interpret, block_s=block_s,
        **kwargs)
    return ids, scores, jnp.sum(valid_b, axis=1), n_qual
