"""Flat (sequential-scan) search paths.

``filter_first``: evaluate the predicate over all rows, gather up to
``max_candidates`` qualifying rows, score only those — cost ∝ selectivity·n,
the TPU analogue of 'scalar-index assisted sequential scan'.

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
    rows = jnp.nonzero(mask, size=max_candidates, fill_value=n)[0]
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
    rows = jnp.nonzero(mask, size=max_candidates, fill_value=n)[0]
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
    rows_b = jax.vmap(
        lambda m: jnp.nonzero(m, size=max_candidates, fill_value=-1)[0]
    )(mask_b)
    cand = rows_b.astype(jnp.int32)
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
