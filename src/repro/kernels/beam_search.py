"""Predicate-aware graph beam search: fixed-trip-count routing + fused
candidate extraction (the third index strategy, ROADMAP open item 1).

IVF probing pays for selectivity twice on predicate-correlated data: the
clusters nearest the query are exactly the clusters the predicate empties,
so the probe budget scans rows the DNF mask then throws away. A proximity
graph routes AROUND the emptied region instead — each hop moves the
frontier along similarity gradients, and qualifying rows a few edges past
the non-qualifying shell are reachable at a scan budget no probe list can
match. This module is the search half of that trade (the graph itself is
built by ``vectordb.graph``); everything is static-shape and jit-able:

  * **fixed trip count** — exactly ``n_hops`` hops of exactly
    ``beam_width`` expansions of exactly ``degree`` neighbors, so one
    trace serves every query and the batched executor's jit cache is
    keyed only by the legalized plan knobs;
  * **visited set as a row bitmask** — a packed ``(ceil(n/32),)`` uint32
    word array; membership is a shift-and-mask gather, insertion is a
    scatter-add of one bit per first-seen row (batch-deduplicated first,
    so each (word, bit) pair is touched at most once per hop);
  * **predicate folded into ROUTING, not reachability** — the DNF mask
    never prunes edges (filtered-out rows still relay the walk through
    non-qualifying regions); instead the beam is split: half the frontier
    slots go to the best unexpanded candidates by raw similarity (the
    navigators), half to the best *qualifying* unexpanded candidates (the
    result magnets). Non-qualifying rows can route but can never crowd
    qualifying ones out of their half of the beam;
  * **predicate-qualifying entry seeds** — besides the graph's global
    entry points, each query's walk is seeded with
    ``GRAPH_SEED_FACTOR·beam_width`` qualifying rows under the query's
    own DNF mask (the filtered-ANN "teleport" that NPG-style native
    hybrid search uses for anti-correlated predicates): on the correlated
    hard stratum the global entries sit in regions the predicate empties,
    and without a foothold inside the qualifying region the result
    magnets have nothing to climb from. Seeds are chosen by hashed row id
    (deterministic pseudo-random spread), so a LARGE qualifying region is
    sampled everywhere instead of at its lowest row ids and the walk
    hill-climbs from the best of the sample. The seed mask is one vmapped
    scalar pass — O(n·M) compare work, the same pre-pass filter_first
    pays, NOT a vector-column scan — and seeds count toward ``n_scored``
    like every other visited row;
  * **one fused extraction** — every row the walk ever visited is
    accumulated into a static ``(entry + n_hops·beam_width·degree)``-slot
    candidate pool, and the result set is ONE ``gather_score_topk`` call
    (the PR 4 Pallas kernel) over that pool with the full DNF predicate:
    dedup, masking, weighted scoring and top-k selection all follow the
    kernel's exact contract, so filtered-out rows used for routing can
    never enter the result set.

Routing similarities are computed with plain-jnp gathers inside the loop
(XLA fuses the per-hop gather+matvec); the Pallas kernel handles the one
heavy candidate-pool scoring pass. ``use_kernel``/``interpret`` pass
through to it with the same defaults as ``gather_score_topk`` — tests pin
kernel-vs-reference parity of the WHOLE search with
``use_kernel=True, interpret=True``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.gather_score import gather_score_topk
from repro.kernels.shapes import GATHER_BLOCK_S, GRAPH_SEED_FACTOR, NEG
from repro.vectordb.predicates import PredicateLike, eval_mask
from repro.vectordb.table import similarity


def _mark_fresh(visited: jax.Array, ids: jax.Array, n_words: int):
    """Batch-insert ``ids`` (i32, -1 = padding, duplicates allowed) into the
    packed uint32 visited bitmask. Returns (visited', fresh) where ``fresh``
    flags the FIRST occurrence of each not-yet-visited row — exactly the
    slots whose bits were set. Within-batch duplicates are resolved by a
    sort pass first, so the scatter-add touches every (word, bit) pair at
    most once and the add is an exact bitwise OR."""
    order = jnp.argsort(ids)
    sorted_ids = ids[order]
    first_sorted = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]])
    first = jnp.zeros_like(first_sorted).at[order].set(first_sorted)
    idc = jnp.clip(ids, 0, n_words * 32 - 1)
    word = idc >> 5
    bit = (idc & 31).astype(jnp.uint32)
    seen = (visited[word] >> bit) & jnp.uint32(1)
    fresh = first & (ids >= 0) & (seen == 0)
    bitval = jnp.where(fresh, jnp.uint32(1) << bit, jnp.uint32(0))
    visited = visited.at[jnp.where(fresh, word, n_words)].add(
        bitval, mode="drop")
    return visited, fresh


def _beam_one(neighbors, vectors, scalars, entry, pred, q, *,
              beam_width: int, n_hops: int, metric: str):
    """Single-query routing walk. Returns (cand (S,), n_visited ()) with
    S = entry + n_hops·beam_width·degree; cand carries every first-visited
    row id, -1 in never-filled slots."""
    n, r = neighbors.shape
    e = entry.shape[0]
    expand = beam_width * r
    s_total = e + n_hops * expand
    p = e + expand  # frontier pool slots
    n_words = (n + 31) // 32
    w_qual = beam_width // 2
    w_raw = beam_width - w_qual

    def score_rows(ids, fresh):
        idc = jnp.clip(ids, 0, n - 1)
        sc = jnp.where(fresh, similarity(q, vectors[idc], metric), NEG)
        qual = eval_mask(pred, scalars[idc]) & fresh
        return sc, qual

    visited = jnp.zeros((n_words,), jnp.uint32)
    visited, fresh0 = _mark_fresh(visited, entry.astype(jnp.int32), n_words)
    seed_ids = jnp.where(fresh0, entry, -1).astype(jnp.int32)
    seed_sc, seed_qual = score_rows(seed_ids, fresh0)

    pool_ids = jnp.full((p,), -1, jnp.int32).at[:e].set(seed_ids)
    pool_sc = jnp.full((p,), NEG, jnp.float32).at[:e].set(seed_sc)
    pool_qual = jnp.zeros((p,), bool).at[:e].set(seed_qual)
    pool_exp = jnp.zeros((p,), bool)
    out = jnp.full((s_total,), -1, jnp.int32).at[:e].set(seed_ids)

    def hop(h, carry):
        pool_ids, pool_sc, pool_qual, pool_exp, visited, out = carry
        # split beam: w_raw navigator slots by raw similarity, w_qual
        # result-magnet slots by qualifying-only similarity — the
        # predicate shapes WHERE the walk lingers, never what it may
        # traverse
        selectable = (pool_ids >= 0) & ~pool_exp
        raw = jnp.where(selectable, pool_sc, NEG)
        _, i_raw = jax.lax.top_k(raw, w_raw)
        taken = jnp.zeros((p,), bool).at[i_raw].set(True)
        qual_sc = jnp.where(selectable & pool_qual & ~taken, pool_sc, NEG)
        _, i_qual = jax.lax.top_k(qual_sc, w_qual)
        fr_idx = jnp.concatenate([i_raw, i_qual])
        fr_ok = jnp.concatenate([raw[i_raw], qual_sc[i_qual]]) > NEG / 2
        # mark expanded only where the pick was real — top_k on an
        # all-NEG lane returns arbitrary indices
        pool_exp = pool_exp.at[jnp.where(fr_idx >= 0, fr_idx, p)].set(
            fr_ok, mode="drop") | pool_exp

        fr_ids = jnp.where(fr_ok, pool_ids[fr_idx], -1)
        nb = neighbors[jnp.clip(fr_ids, 0, n - 1)]  # (beam_width, r)
        nb = jnp.where(fr_ok[:, None], nb, -1).reshape(expand)
        visited2, fresh = _mark_fresh(visited, nb, n_words)
        new_ids = jnp.where(fresh, nb, -1).astype(jnp.int32)
        new_sc, new_qual = score_rows(new_ids, fresh)
        out = jax.lax.dynamic_update_slice(out, new_ids, (e + h * expand,))

        # frontier merge: best p slots by routing score survive; expanded
        # entries keep their flag (the bitmask blocks re-insertion, the
        # flag blocks re-expansion)
        all_ids = jnp.concatenate([pool_ids, new_ids])
        all_sc = jnp.concatenate([pool_sc, new_sc])
        all_qual = jnp.concatenate([pool_qual, new_qual])
        all_exp = jnp.concatenate([pool_exp, jnp.zeros((expand,), bool)])
        top_sc, sel = jax.lax.top_k(all_sc, p)
        return (all_ids[sel], top_sc, all_qual[sel], all_exp[sel],
                visited2, out)

    carry = (pool_ids, pool_sc, pool_qual, pool_exp, visited, out)
    *_, out = jax.lax.fori_loop(0, n_hops, hop, carry)
    return out, jnp.sum(out >= 0)


@partial(jax.jit, static_argnames=("beam_width", "n_hops", "metric"))
def beam_candidates_batch(neighbors, vectors, scalars, entry, pred_b, q_b, *,
                          beam_width: int, n_hops: int, metric: str = "dot"):
    """vmapped routing for a query batch. -> (cand (B, S) i32, -1 padded;
    n_visited (B,)) — the candidate matrix feeds ``gather_score_topk``
    directly (its contract allows -1 pads and duplicates, though the
    bitmask guarantees per-query uniqueness already). ``entry`` is either
    a shared (E,) row set or per-query (B, E) rows (how the qualifying
    seeds ride in); -1 entries are ignored."""
    walk = partial(_beam_one, neighbors, vectors, scalars,
                   beam_width=beam_width, n_hops=n_hops, metric=metric)
    if entry.ndim == 2:
        return jax.vmap(walk)(entry, pred_b, q_b)
    return jax.vmap(partial(walk, entry))(pred_b, q_b)


@partial(jax.jit, static_argnames=("k", "beam_width", "n_hops", "metric",
                                   "use_kernel", "interpret", "block_s"))
def beam_search_topk(
    neighbors: jax.Array,  # (n, r) i32 adjacency, -1 = free slot
    entry: jax.Array,  # (E,) i32 entry points
    rows,  # GatherRows of the indexed column
    pred_b: PredicateLike,  # stacked, leading axis B
    q_b: jax.Array,  # (B, d)
    *,
    k: int,
    beam_width: int,
    n_hops: int,
    metric: str = "dot",
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    block_s: int = GATHER_BLOCK_S,
):
    """Filtered top-k over the graph for a query batch.

    Routing walks the graph predicate-aware (module doc); the result set
    is ONE fused gather+score+mask+top-k over every visited row. Returns
    (ids (B, k), scores (B, k), n_scored (B,), n_qualified (B,)) —
    the same contract as ``ivf.search_local_batch``, so the executor's
    subquery plumbing (RRF union, rerank, iterative accounting) is
    strategy-agnostic. ``n_scored`` is the visited-row count: the scan
    budget the walk actually spent, comparable with IVF's probed-slot
    count in the cost model's crossover fit.

    Each query's entry set is the graph's global entry points plus
    ``SEED_FACTOR·beam_width`` predicate-qualifying seed rows (module
    doc) — found by one vmapped DNF-mask pass over the scalar columns, so
    an anti-correlated predicate still hands the result magnets a
    foothold inside the qualifying region. Seeds are one row per row-id
    segment, picked by hashed row id (a Knuth multiplicative key), not
    first-by-row-id: a deterministic pseudo-random SPREAD over the
    qualifying set, so a large qualifying region is sampled everywhere
    rather than at its lowest row ids — the walk then hill-climbs from
    the best of them. Empty segments pad with -1 and are ignored by the
    walk."""
    vectors, scalars = rows.vectors[0], rows.scalars
    n = scalars.shape[0]
    n_seeds = GRAPH_SEED_FACTOR * beam_width
    seg = -(-n // n_seeds)
    pad = seg * n_seeds - n
    mask_b = jax.vmap(lambda p: eval_mask(p, scalars))(pred_b)
    # one seed per row-id segment, the qualifying row with the largest
    # hashed id (Knuth multiplicative key): a deterministic uniform
    # sample of the qualifying set at O(n) compare work — no sort, no
    # top_k over the table
    key = (jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(2654435761)) >> 12
    key_seg = jnp.pad(key.astype(jnp.int32), (0, pad),
                      constant_values=-1).reshape(n_seeds, seg)
    def pick(m):
        kk = jnp.where(jnp.pad(m, (0, pad)).reshape(n_seeds, seg),
                       key_seg, -1)
        j = jnp.argmax(kk, axis=1)
        ok = jnp.take_along_axis(kk, j[:, None], 1)[:, 0] >= 0
        rows = j.astype(jnp.int32) + jnp.arange(n_seeds, dtype=jnp.int32) * seg
        return jnp.where(ok, rows, -1)
    seeds = jax.vmap(pick)(mask_b)
    entry_b = jnp.concatenate([
        jnp.broadcast_to(entry[None, :],
                         (q_b.shape[0], entry.shape[0])).astype(jnp.int32),
        seeds], axis=1)
    cand, n_visited = beam_candidates_batch(
        neighbors, vectors, scalars, entry_b, pred_b, q_b,
        beam_width=beam_width, n_hops=n_hops, metric=metric)
    w = jnp.ones((q_b.shape[0], 1), jnp.float32)
    ids, scores, n_qual = gather_score_topk(
        cand, rows, (q_b,), w, pred_b, k=k, metric=metric,
        use_kernel=use_kernel, interpret=interpret, block_s=block_s)
    return ids, scores, n_visited, n_qual
