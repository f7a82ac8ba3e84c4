"""Flat (sequential-scan) search paths.

``filter_first``: evaluate the predicate over all rows, gather up to
``max_candidates`` qualifying rows, score only those — the TPU analogue of
'scalar-index assisted sequential scan'. Its costs: the mask and its
running count are O(n) streaming passes; the compaction to the
``max_candidates`` = S row ids is O(S·log n) by search or O(n) by scatter,
chosen from the static shape (``compaction_method``); scoring is O(S).

``masked_scan``: score every row with the predicate as a mask — the exact
oracle (ground truth) and the fallback when selectivity is high. On TPU the
inner loop is the fused Pallas ``masked_topk`` kernel (kernels/); the jnp
path here is its oracle and the CPU execution path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.shapes import GATHER_BLOCK_S, NEG
from repro.vectordb.predicates import PredicateLike, eval_mask
from repro.vectordb.table import Table


_LANES = 128  # the search's block width: the TPU's vector lanes


def compaction_method(n: int, size: int) -> str:
    """How ``compact_rows`` compacts a length-``n`` mask to ``size`` slots:
    ``"search"`` where ``size · ⌈log2 n⌉ < n``, else ``"scatter"``.

    ``jnp.nonzero``'s scatter-add makes n colliding updates, which the TPU
    serialises. The search makes none: O(n) dense passes for the running
    count, then ``size`` lookups. The rule compares the search's
    O(size · log n) work with the scatter's O(n): it takes the search
    where the slots are a small share of the rows (filter-first caps,
    e.g. 16,384 slots of sift_1m's 1M rows) and keeps the scatter where
    the cap is near the table size (the sharded path's
    ``max_candidates = n_rows``, tiny tables). Timed on one TPU v5e,
    vmapped over 4 masks of 1M rows: 1.65 ms by search against 36.8 ms
    by scatter at 16,384 slots, 9.2 against 36.8 at 131,072; so the
    rule's bound (about 50,000 slots at 1M rows) errs toward the scatter.
    """
    return "search" if size * max(1, (n - 1).bit_length()) < n else "scatter"


def _rows_by_scatter(mask: jax.Array, size: int, fill_value: int):
    return jnp.nonzero(mask, size=size, fill_value=fill_value)[0].astype(
        jnp.int32)


def _inclusive_count(mask: jax.Array) -> jax.Array:
    """``cumsum(mask)`` as int32, padded to whole 128-row blocks: each
    block's running count is one matmul of 0/1 values with a triangular
    0/1 matrix (exact: counts ≤ 128 in float32), plus the counts of the
    blocks before it."""
    n = mask.shape[0]
    nb = -(-n // _LANES)
    m = jnp.pad(mask, (0, nb * _LANES - n)).reshape(nb, _LANES)
    lane = jnp.arange(_LANES)
    tri = (lane[:, None] <= lane[None, :]).astype(jnp.bfloat16)
    within = jnp.dot(m.astype(jnp.bfloat16), tri,
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    before = jnp.cumsum(within[:, -1]) - within[:, -1]
    return (within + before[:, None]).reshape(-1)


def _count_at_most(e: jax.Array, j: jax.Array) -> jax.Array:
    """``#{i : e[i] <= j}`` for each j, ``e`` nondecreasing: per factor of
    128 in ``len(e)``, one (len(j), 128) row gather and compare, in place
    of a binary search's scalar gathers."""
    m = e.shape[0]
    if m <= _LANES:
        return jnp.sum(e <= j[:, None], axis=1, dtype=jnp.int32)
    mb = -(-m // _LANES)
    eb = jnp.pad(e, (0, mb * _LANES - m),
                 constant_values=jnp.iinfo(jnp.int32).max).reshape(mb, _LANES)
    whole = _count_at_most(eb[:, -1], j)  # blocks wholly <= j
    row = eb[jnp.minimum(whole, mb - 1)]
    inside = jnp.sum(row <= j[:, None], axis=1, dtype=jnp.int32)
    return whole * _LANES + jnp.where(whole < mb, inside, 0)


def _rows_by_search(mask: jax.Array, size: int, fill_value: int):
    """Slot j holds the first row whose running count of qualifying rows
    reaches j + 1, i.e. the number of rows whose count is at most j."""
    cs = _inclusive_count(mask)
    j = jnp.arange(size, dtype=jnp.int32)
    return jnp.where(j < cs[-1], _count_at_most(cs, j), fill_value)


COMPACTIONS = {"search": _rows_by_search, "scatter": _rows_by_scatter}


def compact_rows(mask: jax.Array, size: int, fill_value: int) -> jax.Array:
    """(n,) bool mask -> (size,) int32 ids of its first ``size`` True rows,
    ascending, padded with ``fill_value``: exactly
    ``jnp.nonzero(mask, size=size, fill_value=fill_value)[0]``, by the
    method ``compaction_method(n, size)`` picks. Traced into the caller's
    program; vmap it for a batch of masks."""
    method = compaction_method(mask.shape[0], size)
    return COMPACTIONS[method](mask, size, fill_value)


@partial(jax.jit, static_argnames=("k", "max_candidates", "n_vec", "metric"))
def filter_first(
    vectors: tuple,  # tuple of (n, d_i)
    scalars: jax.Array,
    pred: PredicateLike,
    query_vectors: tuple,  # tuple of (d_i,)
    weights: jax.Array,
    metric: str = "dot",
    *,
    k: int,
    max_candidates: int,
    n_vec: int,
):
    """Filter-first execution. Returns (ids, scores, n_scored, n_qualified)."""
    mask = eval_mask(pred, scalars)
    n = scalars.shape[0]
    rows = compact_rows(mask, max_candidates, n)
    valid = rows < n
    rows_c = jnp.clip(rows, 0, n - 1)
    from repro.vectordb.table import similarity

    total = jnp.zeros((max_candidates,), jnp.float32)
    for i in range(n_vec):
        total = total + weights[i] * similarity(query_vectors[i], vectors[i][rows_c], metric)
    masked = jnp.where(valid, total, NEG)
    top_scores, top_idx = jax.lax.top_k(masked, k)
    ids = jnp.where(top_scores > NEG / 2, rows_c[top_idx], -1)
    # n_scored is capped by the gather width; n_qualified is the true
    # qualifying-row count (underfill/escalation logic reads it).
    return ids, top_scores, jnp.sum(valid), jnp.sum(mask)


@partial(jax.jit, static_argnames=("k", "max_candidates"))
def filter_first_scored(
    row_scores: jax.Array,  # (n,) precomputed weighted scores for ONE query
    scalars: jax.Array,
    pred: PredicateLike,
    *,
    k: int,
    max_candidates: int,
):
    """``filter_first`` with the weighted row scores precomputed — the
    batched serving path computes Σ_i w_i·(V_i @ q_i) for a whole batch via
    per-column GEMMs and then runs this per query (matching ``filter_first``
    up to float reduction order)."""
    mask = eval_mask(pred, scalars)
    n = scalars.shape[0]
    rows = compact_rows(mask, max_candidates, n)
    valid = rows < n
    rows_c = jnp.clip(rows, 0, n - 1)
    masked = jnp.where(valid, row_scores[rows_c], NEG)
    top_scores, top_idx = jax.lax.top_k(masked, k)
    ids = jnp.where(top_scores > NEG / 2, rows_c[top_idx], -1)
    return ids, top_scores, jnp.sum(valid), jnp.sum(mask)


@partial(jax.jit, static_argnames=("k", "max_candidates", "n_vec", "metric",
                                   "use_kernel", "interpret", "block_s"))
def filter_first_local_batch(
    rows,  # GatherRows of the table's columns
    pred_b: PredicateLike,  # stacked, leading axis B
    query_vectors_b: tuple,  # tuple of (B, d_i)
    weights_b: jax.Array,  # (B, n_vec)
    *,
    k: int,
    max_candidates: int,
    n_vec: int,
    metric: str = "dot",
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    block_s: int = GATHER_BLOCK_S,
):
    """Candidate-local batched ``filter_first``: evaluate the predicate over
    all rows per query, then ONE fused gather+score+top-k
    (``kernels.gather_score``) over only the ≤ ``max_candidates`` qualifying
    rows — no dense (B, n) score matrix. Returns (ids (B, k), scores (B, k),
    n_scored (B,), n_qualified (B,)); the candidates are pre-qualified, so
    the fused kernel skips re-masking."""
    from repro.kernels.gather_score import gather_score_topk

    mask_b = jax.vmap(lambda p: eval_mask(p, rows.scalars))(pred_b)  # (B, n)
    cand = jax.vmap(lambda m: compact_rows(m, max_candidates, -1))(mask_b)
    ids, scores, _ = gather_score_topk(
        cand, rows.select(range(n_vec)), tuple(query_vectors_b[:n_vec]),
        weights_b, None, k=k, metric=metric, use_kernel=use_kernel,
        interpret=interpret, block_s=block_s)
    return ids, scores, jnp.sum(cand >= 0, axis=1), jnp.sum(mask_b, axis=1)


@partial(jax.jit, static_argnames=("k", "n_vec", "metric"))
def masked_scan(
    vectors: tuple,
    scalars: jax.Array,
    pred: PredicateLike,
    query_vectors: tuple,
    weights: jax.Array,
    metric: str = "dot",
    *,
    k: int,
    n_vec: int,
):
    """Exact filtered top-k over the full table (also the recall oracle)."""
    from repro.vectordb.table import similarity

    n = scalars.shape[0]
    total = jnp.zeros((n,), jnp.float32)
    for i in range(n_vec):
        total = total + weights[i] * similarity(query_vectors[i], vectors[i], metric)
    mask = eval_mask(pred, scalars)
    masked = jnp.where(mask, total, NEG)
    top_scores, top_idx = jax.lax.top_k(masked, k)
    ids = jnp.where(top_scores > NEG / 2, top_idx, -1)
    return ids, top_scores, jnp.asarray(n), jnp.sum(mask)


def ground_truth(table: Table, query_vectors, weights, pred: PredicateLike, k: int):
    ids, scores, _, _ = masked_scan(
        tuple(table.vectors),
        table.scalars,
        pred,
        tuple(query_vectors),
        jnp.asarray(weights),
        table.schema.metric,
        k=k,
        n_vec=table.schema.n_vec,
    )
    return ids, scores
