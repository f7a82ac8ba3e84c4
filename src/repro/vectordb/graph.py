"""Fixed-degree proximity graph index (Vamana-style) + beam-search entry.

The third index strategy next to ``flat`` (filter_first) and ``ivf``
(index_scan): a degree-``R`` navigable graph built OFFLINE from the cold
table, searched by the fixed-trip-count predicate-aware beam search in
``kernels.beam_search``. Where IVF's probe list commits the whole scan
budget to the clusters nearest the query — exactly the region a
correlated predicate empties — the graph walk spends its budget hop by
hop, routing THROUGH non-qualifying rows toward the qualifying shell.

Build (numpy/offline, mirrors the DiskANN/Vamana recipe under this
repo's static-shape constraints):

  1. blocked exact kNN — each row's top-``4R`` candidates by one chunked
     GEMM per block (no index bootstrap; the cold table is sealed and
     bounded, and build runs in the compaction/seal path, off the serving
     hot loop);
  2. α-occlusion prune — candidates in similarity order; a candidate is
     dropped when it is (α-adjustedly) closer to an already-kept neighbor
     than to the node, which diversifies edges across directions instead
     of wasting degree on one tight cluster;
  3. reverse-edge fill — each kept edge (i→j) is mirrored into j's free
     slots (vectorized grouped scatter), making the graph navigable from
     sparse regions.

The degree sits on ``DEGREE_GRID`` so adjacency shapes — and therefore
the beam-search jit cache — stay bounded exactly like every other
legalized knob. ``extend`` appends rows for the compaction path (blocked
top-``R`` connect + reverse fill, no re-prune) — the cheap maintenance
step matching ``ivf.extend``; the sealing rebuild is ``build``, matching
``TieredTable.rebuild_every``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.shapes import GRAPH_ENTRY_POINTS, NEG
from repro.vectordb.predicates import PredicateLike, stack

# Legalized out-degrees, the graph analogue of NPROBE_GRID: every
# adjacency launched at serving time has one of these static widths.
DEGREE_GRID = (8, 16, 32)
DEFAULT_DEGREE = 16
# α > 1 keeps a candidate unless it is α-times closer to a kept neighbor
# than to the node — the Vamana densification that keeps long-range edges.
DEFAULT_ALPHA = 1.2
# candidate pool width for the prune, as a multiple of the degree
BUILD_CANDIDATE_MULT = 4
_KNN_CHUNK = 1024
_PRUNE_CHUNK = 512


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GraphIndex:
    neighbors: jax.Array  # (n, R) i32 adjacency, -1 = free slot
    entry_points: jax.Array  # (E,) i32 — medoid + strided seeds
    metric: str

    def tree_flatten(self):
        return (self.neighbors, self.entry_points), self.metric

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, metric=aux)

    @property
    def degree(self) -> int:
        return int(self.neighbors.shape[1])

    @property
    def n_rows(self) -> int:
        return int(self.neighbors.shape[0])


def legal_degree(degree: int) -> int:
    """Smallest grid degree >= the request (largest grid entry if none)."""
    for d in DEGREE_GRID:
        if d >= degree:
            return d
    return DEGREE_GRID[-1]


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("c", "metric"))
def _chunk_topk(vectors, chunk, row0, *, c: int, metric: str):
    """Exact top-c neighbors of ``chunk`` rows (table rows row0..) against
    the whole column, self-similarity masked out."""
    g = chunk @ vectors.T
    if metric == "l2":
        sims = (2.0 * g
                - jnp.sum(vectors * vectors, axis=1)[None, :]
                - jnp.sum(chunk * chunk, axis=1)[:, None])
    else:
        sims = g
    b = chunk.shape[0]
    sims = sims.at[jnp.arange(b), row0 + jnp.arange(b)].set(NEG)
    top_s, top_i = jax.lax.top_k(sims, c)
    return jnp.where(top_s > NEG / 2, top_i, -1).astype(jnp.int32), top_s


@partial(jax.jit, static_argnames=("r", "metric"))
def _prune_chunk(cand_ids, cand_sims, cand_vecs, alpha, *, r: int,
                 metric: str):
    """α-occlusion prune of (B, C) similarity-ordered candidate lists down
    to degree r. Candidate t is occluded when some already-kept l has
    sim(t, l) beating the α-adjusted sim(node, t): for l2 (sims = -dist²)
    that is dist(t,l)·α < dist(node,t); for dot the α margin scales the
    node similarity directly."""
    g = jnp.einsum("bcd,bed->bce", cand_vecs, cand_vecs)
    if metric == "l2":
        nrm = jnp.sum(cand_vecs * cand_vecs, axis=-1)  # (B, C)
        pair = 2.0 * g - nrm[:, :, None] - nrm[:, None, :]
        thresh = cand_sims / (alpha * alpha)
    else:
        pair = g
        thresh = jnp.where(cand_sims >= 0.0, cand_sims * alpha,
                           cand_sims / alpha)
    c = cand_ids.shape[1]

    def one(ids, pr, th):
        def step(t, carry):
            sel, cnt = carry
            occ = jnp.any(sel & (pr[t] > th[t]))
            take = (ids[t] >= 0) & ~occ & (cnt < r)
            return sel.at[t].set(take), cnt + take.astype(jnp.int32)

        sel, _ = jax.lax.fori_loop(
            0, c, step, (jnp.zeros((c,), bool), jnp.asarray(0, jnp.int32)))
        pos = jnp.cumsum(sel.astype(jnp.int32)) - 1
        return jnp.full((r,), -1, jnp.int32).at[
            jnp.where(sel, pos, r)].set(
            jnp.where(sel, ids, -1), mode="drop")

    return jax.vmap(one)(cand_ids, pair, thresh)


def _reverse_fill(neigh: np.ndarray, src_rows: np.ndarray | None = None):
    """Mirror forward edges (i→j) into j's free adjacency slots, in place.

    One vectorized grouped scatter: edges sort by destination, each
    destination accepts reverse edges up to its free degree in source
    order. ``src_rows`` restricts the mirrored edges to those sources
    (the extend path mirrors only the new rows' edges). A mirrored edge
    may duplicate an existing forward edge — harmless, the search-side
    visited bitmask drops the second occurrence."""
    n, r = neigh.shape
    deg = (neigh >= 0).sum(1)
    if src_rows is None:
        src = np.repeat(np.arange(n, dtype=np.int32), r)
        dst = neigh.reshape(-1)
    else:
        src = np.repeat(np.asarray(src_rows, np.int32), r)
        dst = neigh[src_rows].reshape(-1)
    ok = dst >= 0
    src, dst = src[ok], dst[ok]
    order = np.argsort(dst, kind="stable")
    dsts, srcs = dst[order], src[order]
    rank = np.arange(dsts.size) - np.searchsorted(dsts, dsts, side="left")
    keep = rank < (r - deg[dsts])
    neigh[dsts[keep], deg[dsts[keep]] + rank[keep]] = srcs[keep]


def _entry_points(vectors: jax.Array, metric: str,
                  n_entry: int = GRAPH_ENTRY_POINTS) -> np.ndarray:
    """Medoid (closest row to the column mean) + strided seeds: the medoid
    anchors the dense core, the strided rows cover disconnected or sparse
    regions the prune may have isolated."""
    n = int(vectors.shape[0])
    mu = jnp.mean(vectors, axis=0)
    g = vectors @ mu
    if metric == "l2":
        g = 2.0 * g - jnp.sum(vectors * vectors, axis=1) - jnp.sum(mu * mu)
    pts = ((np.arange(n_entry, dtype=np.int64) * n) // n_entry).astype(
        np.int32)
    pts[0] = int(jnp.argmax(g))
    return pts


def build(vectors: jax.Array, degree: int = DEFAULT_DEGREE, *,
          alpha: float = DEFAULT_ALPHA, metric: str = "dot") -> GraphIndex:
    """Offline graph build from a sealed column (module doc). ``degree``
    legalizes onto ``DEGREE_GRID``."""
    r = legal_degree(degree)
    n = int(vectors.shape[0])
    c = min(BUILD_CANDIDATE_MULT * r, max(1, n - 1))
    # prune forward edges to HALF degree, reserving the rest for reverse
    # fill: under dot the α-occlusion rule rarely triggers, so a full-
    # degree prune leaves zero free slots, the reverse fill becomes a
    # no-op, and the purely-forward kNN digraph collapses into per-row
    # islands nothing can route into
    r_fwd = max(1, r // 2)
    neigh = np.full((n, r), -1, np.int32)
    alpha_j = jnp.asarray(alpha, jnp.float32)
    for lo in range(0, n, _KNN_CHUNK):
        hi = min(lo + _KNN_CHUNK, n)
        ids, sims = _chunk_topk(vectors, vectors[lo:hi], lo, c=c,
                                metric=metric)
        for plo in range(0, hi - lo, _PRUNE_CHUNK):
            phi = min(plo + _PRUNE_CHUNK, hi - lo)
            cand_vecs = vectors[jnp.clip(ids[plo:phi], 0, n - 1)]
            neigh[lo + plo:lo + phi, :r_fwd] = np.asarray(_prune_chunk(
                ids[plo:phi], sims[plo:phi], cand_vecs, alpha_j,
                r=r_fwd, metric=metric))
    _reverse_fill(neigh)
    entries = _entry_points(vectors, metric)
    _repair_reachability(neigh, np.asarray(vectors), entries, metric)
    return GraphIndex(neighbors=jnp.asarray(neigh),
                      entry_points=jnp.asarray(entries),
                      metric=metric)


def _repair_reachability(neigh: np.ndarray, vec: np.ndarray,
                         entries: np.ndarray, metric: str,
                         links_per_round: int = 32,
                         max_rounds: int = 64) -> None:
    """Make every row reachable from the entry points, in place.

    The build's candidate pool is pure kNN, so on well-separated data the
    pruned graph fragments into cluster islands and the walk can never
    leave the components the entries land in (true Vamana avoids this via
    search-seeded candidate pools, which carry long-range edges). Repair:
    directed BFS from the entries, then for the nearest unreached rows
    splice one edge reachable→unreached (evicting the donor's weakest
    slot), re-flood, repeat. Each spliced edge floods the target's whole
    local component on the next BFS, so rounds ~ #islands, not #rows."""
    n, r = neigh.shape
    seed = np.zeros(n, bool)
    seed[np.asarray(entries)] = True

    def flood():
        reach = seed.copy()
        frontier = np.where(reach)[0]
        while frontier.size:
            nxt = neigh[frontier].reshape(-1)
            nxt = np.unique(nxt[nxt >= 0])
            nxt = nxt[~reach[nxt]]
            reach[nxt] = True
            frontier = nxt
        return reach

    forced = np.zeros((n, r), bool)  # spliced edges are never evicted
    indeg = np.bincount(neigh[neigh >= 0], minlength=n)
    stall = 0
    prev_un = n + 1
    for _ in range(max_rounds):
        # full re-flood every round: an eviction can disconnect rows
        # counted reachable in an earlier round, so an incrementally-grown
        # reach mask would drift optimistic
        reach = flood()
        un = np.where(~reach)[0]
        if un.size == 0:
            return
        stall = stall + 1 if un.size >= prev_un else 0
        if stall >= 3:
            return
        prev_un = un.size
        rs = np.where(reach)[0]
        # nearest reachable donor for each unreached row (blocked GEMM)
        sims = vec[un] @ vec[rs].T
        if metric == "l2":
            sims = (2.0 * sims
                    - (vec[rs] * vec[rs]).sum(1)[None, :]
                    - (vec[un] * vec[un]).sum(1)[:, None])
        best_sim = sims.max(1)
        take = np.argsort(-best_sim)[:max(links_per_round, n // 256)]
        for t in take:
            u = int(un[t])
            # donors in similarity order — fall past any donor whose every
            # slot already holds a forced splice
            for d in np.argsort(-sims[t])[:64]:
                v = int(rs[d])
                free = np.where(neigh[v] < 0)[0]
                if free.size:
                    slot = int(free[0])
                else:
                    # evict the edge whose target is most redundantly
                    # referenced elsewhere — evicting the geometrically
                    # weakest edge instead tends to cut long-range bridges
                    # and disconnect more rows than the splice recovers
                    cand = np.where(~forced[v])[0]
                    if cand.size == 0:
                        continue
                    slot = int(cand[int(np.argmax(indeg[neigh[v, cand]]))])
                    indeg[neigh[v, slot]] -= 1
                neigh[v, slot] = u
                forced[v, slot] = True
                indeg[u] += 1
                break


def extend(index: GraphIndex, vectors: jax.Array,
           first_new_row: int) -> GraphIndex:
    """Append rows ``vectors[first_new_row:]`` (``vectors`` is the FULL
    post-append column) — the cheap compaction-path maintenance step.
    New rows get exact top-R forward edges into the whole grown column
    (no re-prune: the sealed prefix's diversity is preserved, and the
    sealing rebuild re-prunes everything) and mirror into existing rows'
    free slots, which keeps them reachable from the old graph."""
    n = int(vectors.shape[0])
    r = index.degree
    assert first_new_row == index.n_rows, (first_new_row, index.n_rows)
    c = min(r, max(1, n - 1))
    lists = []
    for lo in range(first_new_row, n, _KNN_CHUNK):
        hi = min(lo + _KNN_CHUNK, n)
        ids, _ = _chunk_topk(vectors, vectors[lo:hi], lo, c=c, metric=index.metric)
        lists.append(np.asarray(ids))
    new = np.full((n - first_new_row, r), -1, np.int32)
    if lists:
        got = np.concatenate(lists)
        new[:, :got.shape[1]] = got
    neigh = np.concatenate([np.asarray(index.neighbors), new])
    new_ids = np.arange(first_new_row, n, dtype=np.int32)
    _reverse_fill(neigh, new_ids)
    # _reverse_fill only consumes FREE slots and a sealed graph's slots
    # are mostly saturated by its own build-time fill, so appended rows
    # can end up referenced by nobody — the repair pass splices them (and
    # anything else the eviction churn disconnects) back in
    _repair_reachability(neigh, np.asarray(vectors),
                         np.asarray(index.entry_points), index.metric)
    return GraphIndex(neighbors=jnp.asarray(neigh),
                      entry_points=index.entry_points, metric=index.metric)


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

def search_local_batch(
    index: GraphIndex,
    rows,  # GatherRows of the indexed column
    pred_b: PredicateLike,  # stacked, leading axis B
    q_b: jax.Array,  # (B, d)
    *,
    beam_width: int,
    n_hops: int,
    k: int,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
):
    """Candidate-local batched graph search — the same contract as
    ``ivf.search_local_batch``: (ids (B, k), scores (B, k), n_scored (B,),
    n_qualified (B,)), ties by smaller row id, -1/NEG empty slots.
    ``n_scored`` counts visited rows (the walk's actual scan budget)."""
    from repro.kernels.beam_search import beam_search_topk

    return beam_search_topk(
        index.neighbors, index.entry_points, rows, pred_b, q_b,
        k=k, beam_width=beam_width, n_hops=n_hops, metric=index.metric,
        use_kernel=use_kernel, interpret=interpret)


def search(
    index: GraphIndex,
    rows,  # GatherRows of the indexed column
    pred: PredicateLike,
    q: jax.Array,  # (d,)
    *,
    beam_width: int,
    n_hops: int,
    k: int,
):
    """Single-query convenience wrapper mirroring ``ivf.search``:
    (ids (k,), scores (k,), n_scored (), n_qualified ())."""
    ids, scores, n_scored, n_qual = search_local_batch(
        index, rows, stack([pred]), q[None], k=k,
        beam_width=beam_width, n_hops=n_hops)
    return ids[0], scores[0], n_scored[0], n_qual[0]
