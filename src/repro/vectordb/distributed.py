"""Mesh-sharded MHQ search (beyond-paper: the technique as a distributed,
first-class feature — DESIGN.md §2 'Distribution').

DB rows are sharded over the mesh's data axes; each device scores its local
shard and keeps a local top-k; the global top-k merges the per-device
candidates with one all-gather of O(devices · k) elements — independent of
DB size, so the collective term stays negligible (see EXPERIMENTS.md
§Roofline boomhq rows).

Two families of sharded search live here:

  * the EXACT scans (``sharded_masked_scan*``, ``sharded_batch_topk``) mask
    + local-top-k precomputed dense scores per shard — optimal while the
    dense GEMM is cheap relative to the table;
  * the PLAN-DRIVEN path (``ShardedIVF`` + ``sharded_ivf_topk``): each
    shard holds its slice's own IVF index and probes it with the learned
    plan's legalized knobs (nprobe / max_scan / k_i split across shards),
    reranking the candidate union with the fused candidate-local
    gather+score kernel INSIDE the shard — so the learned knobs stay
    operative at the scale tier where the dense GEMM becomes the wall.

Implemented with ``shard_map`` so the collective schedule is explicit; a
logical single-device variant keeps identical merge semantics for tests
and mesh-less serving.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.vectordb.predicates import eval_mask
from repro.vectordb.table import similarity

NEG = -1e30


def place_rows(x: jax.Array, mesh: Mesh, data_axes=("data",)) -> jax.Array:
    """Put ``x`` on the mesh with its leading (row or shard) axis split over
    ``data_axes`` and every other axis whole — the layout every shard_map
    here reads, so a call never has to move table data."""
    axes = data_axes if isinstance(data_axes, tuple) else (data_axes,)
    spec = P(axes, *([None] * (x.ndim - 1)))
    return jax.device_put(x, NamedSharding(mesh, spec))


def place_table(table, mesh: Mesh, data_axes=("data",)):
    """A copy of ``table`` whose columns, scalars, int8 replicas and gather
    row views (built here if missing) are row-sharded over the mesh by
    ``place_rows``: contiguous row blocks, block i on device i, as
    ``shard_map`` slices them."""
    table.gather_rows()
    table.gather_rows(int8=True)
    return table.with_arrays(lambda x: place_rows(x, mesh, data_axes))


def sharded_masked_scan(mesh: Mesh, data_axes=("data",), *, k: int, n_vec: int,
                        metric: str = "dot"):
    """Build a jit'd sharded filtered top-k: rows sharded over ``data_axes``.

    Returned fn signature:
      fn(vectors: tuple[(n, d_i)], scalars (n, M), pred, qs tuple[(d_i,)], w (N,))
        -> (ids (k,), scores (k,))
    Row ids are global.
    """
    axes = data_axes if isinstance(data_axes, tuple) else (data_axes,)

    def local(vectors, scalars, pred, qs, w, row0):
        n_local = scalars.shape[0]
        total = jnp.zeros((n_local,), jnp.float32)
        for i in range(n_vec):
            total = total + w[i] * similarity(qs[i], vectors[i], metric)
        mask = eval_mask(pred, scalars)
        masked = jnp.where(mask, total, NEG)
        kk = min(k, n_local)
        s, idx = jax.lax.top_k(masked, kk)
        gids = row0 + idx  # globalize
        # gather candidates from every shard, then merge
        s_all = jax.lax.all_gather(s, axes, tiled=True)
        g_all = jax.lax.all_gather(gids, axes, tiled=True)
        ms, mi = jax.lax.top_k(s_all, k)
        out_ids = jnp.where(ms > NEG / 2, g_all[mi], -1)
        return out_ids, ms

    vec_specs = tuple(P(axes, None) for _ in range(n_vec))
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(vec_specs, P(axes, None), P(), tuple(P() for _ in range(n_vec)), P(), P(axes)),
        out_specs=(P(), P()),
        check_vma=False,
    )

    def run(vectors, scalars, pred, qs, w):
        n = scalars.shape[0]
        n_dev = 1
        for a in axes:
            n_dev *= mesh.shape[a]
        assert n % n_dev == 0, (n, n_dev)
        row0 = jnp.arange(n_dev, dtype=jnp.int32) * (n // n_dev)
        return fn(tuple(vectors), scalars, pred, tuple(qs), w, row0)

    return jax.jit(run)


def sharded_masked_scan_batched(mesh: Mesh, data_axes=("data",), *, k: int,
                                n_vec: int, metric: str = "dot",
                                int8: bool = False):
    """Beyond-paper optimized distributed scan: QUERY BATCHING (one pass over
    the DB shard serves Q queries — turns the memory-bound matvec into an
    MXU matmul) and optional INT8 DB storage (per-row absmax scales; 4× less
    HBM traffic on the scan — the Pallas int8_scan kernel's layout).

    Returned fn:
      fn(vectors, [scales,] scalars, preds (stacked Q), qs tuple[(Q, d_i)],
         w (Q, N)) -> (ids (Q, k), scores (Q, k))
    """
    axes = data_axes if isinstance(data_axes, tuple) else (data_axes,)

    def local(vectors, scales, scalars, preds, qs, w, row0):
        n_local = scalars.shape[0]
        q_batch = qs[0].shape[0]
        total = jnp.zeros((q_batch, n_local), jnp.float32)
        for i in range(n_vec):
            v = vectors[i]
            if int8:
                # true int8 path: quantize the queries too and run the dot
                # on the MXU's int8×int8→int32 — the DB is read as int8
                qsc = jnp.maximum(jnp.max(jnp.abs(qs[i]), axis=-1), 1e-12) / 127.0
                q8 = jnp.clip(jnp.round(qs[i] / qsc[:, None]), -127, 127
                              ).astype(jnp.int8)
                acc = jnp.einsum("nd,qd->qn", v, q8,
                                 preferred_element_type=jnp.int32)
                s = acc.astype(jnp.float32) * scales[i][None, :] * qsc[:, None]
            else:
                s = jnp.einsum("nd,qd->qn", v, qs[i])
                if metric == "l2":
                    s = 2.0 * s - jnp.sum(v * v, axis=-1)[None] \
                        - jnp.sum(qs[i] * qs[i], axis=-1)[:, None]
            total = total + w[:, i][:, None] * s
        # per-query DNF predicate masks: preds fields stacked over Q, the
        # shared OR-over-clauses evaluator vmapped over the query axis
        mask = jax.vmap(lambda p: eval_mask(p, scalars))(preds)  # (Q, n_local)
        masked = jnp.where(mask, total, NEG)
        kk = min(k, n_local)
        s_loc, idx = jax.lax.top_k(masked, kk)  # (Q, kk)
        gids = row0 + idx
        s_all = jax.lax.all_gather(s_loc, axes, axis=1, tiled=True)
        g_all = jax.lax.all_gather(gids, axes, axis=1, tiled=True)
        ms, mi = jax.lax.top_k(s_all, k)
        out_ids = jnp.where(ms > NEG / 2, jnp.take_along_axis(g_all, mi, 1), -1)
        return out_ids, ms

    vec_specs = tuple(P(axes, None) for _ in range(n_vec))
    scale_specs = tuple(P(axes) for _ in range(n_vec)) if int8 else P()
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(vec_specs, scale_specs, P(axes, None), P(),
                  tuple(P() for _ in range(n_vec)), P(), P(axes)),
        out_specs=(P(), P()),
        check_vma=False,
    )

    def run(vectors, scales, scalars, preds, qs, w):
        n = scalars.shape[0]
        n_dev = 1
        for a in axes:
            n_dev *= mesh.shape[a]
        assert n % n_dev == 0, (n, n_dev)
        row0 = jnp.arange(n_dev, dtype=jnp.int32) * (n // n_dev)
        scales = tuple(scales) if int8 else jnp.zeros(())
        return fn(tuple(vectors), scales, scalars, preds, tuple(qs), w, row0)

    return jax.jit(run)


# ---------------------------------------------------------------------------
# cross-shard batched serving entry point (serve/batch.py fans out here)
# ---------------------------------------------------------------------------
#
# The batched serving layer already computes dense per-column score matrices
# for the whole batch (serve.batch.compute_batch_scores); the cross-shard
# path must not re-score. Both functions below therefore take the WEIGHTED
# (Q, n) score matrix as input and only do per-shard mask + local top-k +
# one O(shards · k) merge:
#
#   * ``sharded_batch_topk`` builds the shard_map version: rows (score
#     columns + scalar rows) are sharded over the mesh's data axes, each
#     device reads only its local (Q, n_local) block of the dense matrix,
#     and the merge is one all-gather of O(shards · k) candidates.
#   * ``sharded_topk_ref`` is the single-device logical-shard reference
#     with IDENTICAL merge semantics (same local top-k widths, same shard
#     concatenation order, same tie-breaking) — the executor uses it when
#     no multi-device mesh is bound, and tests use it as the shard_map
#     oracle.


def _merge_shard_candidates(s_all, g_all, *, k):
    """Top-k over the concatenated per-shard candidates (Q, S·kk); output
    padded to width k with id -1 / score NEG when fewer candidates exist."""
    kf = min(k, s_all.shape[1])
    ms, mi = jax.lax.top_k(s_all, kf)
    ids = jnp.where(ms > NEG / 2, jnp.take_along_axis(g_all, mi, 1), -1)
    if kf < k:
        pad = ((0, 0), (0, k - kf))
        ids = jnp.pad(ids, pad, constant_values=-1)
        ms = jnp.pad(ms, pad, constant_values=NEG)
    return ids, ms


@partial(jax.jit, static_argnames=("k", "n_shards"))
def sharded_topk_ref(w_scores, mask, *, k, n_shards):
    """Logical-shard filtered top-k over precomputed weighted scores.

    ``w_scores``/``mask``: (Q, n). Rows split into ``n_shards`` contiguous
    shards (right-padded with non-qualifying rows when n % n_shards != 0);
    each shard keeps a local top-min(k, shard_len), then one merge over the
    (Q, shards·kk) candidates. Runs on a single device — the semantics (and
    tie-breaking) match ``sharded_batch_topk`` exactly.
    """
    q, n = w_scores.shape
    per = -(-n // n_shards)  # ceil-div shard length
    masked = jnp.where(mask, w_scores, NEG)
    masked = jnp.pad(masked, ((0, 0), (0, per * n_shards - n)),
                     constant_values=NEG)
    local = masked.reshape(q, n_shards, per)
    kk = min(k, per)
    s_loc, idx = jax.lax.top_k(local, kk)  # (Q, S, kk)
    gids = jnp.arange(n_shards, dtype=jnp.int32)[None, :, None] * per + idx
    return _merge_shard_candidates(s_loc.reshape(q, n_shards * kk),
                                   gids.reshape(q, n_shards * kk), k=k)


def sharded_batch_topk(mesh: Mesh, data_axes=("data",), *, k: int):
    """Build the jit'd cross-shard batched filtered top-k.

    Returned fn signature:
      fn(w_scores (Q, n), scalars (n, M), preds (stacked over Q))
        -> (ids (Q, k), scores (Q, k))

    ``w_scores`` is the whole-batch weighted score matrix assembled from the
    serving layer's per-column GEMMs; the shard_map in_spec slices its row
    axis so each device reads only its local (Q, n_local) block — the scan
    reuses the dense matrices instead of re-scoring, and the collective is
    one all-gather of O(shards · k) candidates per query.
    """
    axes = data_axes if isinstance(data_axes, tuple) else (data_axes,)

    def local(w_scores, scalars, preds, row0):
        n_local = scalars.shape[0]
        mask = jax.vmap(lambda p: eval_mask(p, scalars))(preds)  # (Q, n_local)
        masked = jnp.where(mask, w_scores, NEG)
        kk = min(k, n_local)
        s_loc, idx = jax.lax.top_k(masked, kk)  # (Q, kk)
        gids = row0 + idx  # globalize
        s_all = jax.lax.all_gather(s_loc, axes, axis=1, tiled=True)
        g_all = jax.lax.all_gather(gids, axes, axis=1, tiled=True)
        return _merge_shard_candidates(s_all, g_all, k=k)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, axes), P(axes, None), P(), P(axes)),
        out_specs=(P(), P()),
        check_vma=False,
    )

    def run(w_scores, scalars, preds):
        n = scalars.shape[0]
        n_dev = 1
        for a in axes:
            n_dev *= mesh.shape[a]
        assert n % n_dev == 0, (n, n_dev)
        row0 = jnp.arange(n_dev, dtype=jnp.int32) * (n // n_dev)
        return fn(w_scores, scalars, preds, row0)

    return jax.jit(run)


# ---------------------------------------------------------------------------
# per-shard IVF indexing + plan-driven probing (the learned knobs at scale)
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedIVF:
    """Per-shard IVF indexes of ONE vector column, stacked on a leading
    shard axis so a single structure serves both execution modes: under
    ``shard_map`` axis 0 shards across the mesh's data axes (each device
    reads only its own shard's index), and the logical single-device path
    loops over it with identical semantics.

    Rows are the table's contiguous ``shard_len``-sized slices.
    ``sorted_rows`` holds LOCAL row ids (0 .. shard_rows-1); callers
    globalize with ``shard * shard_len``. The last shard of a non-divisible
    table is short: its ``sorted_rows`` tail is zero-padded, and because
    ``offsets`` only ever counts the shard's real rows, padded slots can
    never be selected as probe candidates.
    """

    centroids: jax.Array    # (S, C, d)
    sorted_rows: jax.Array  # (S, shard_len) i32 local row ids, zero-padded
    offsets: jax.Array      # (S, C+1) i32

    metric: str

    def tree_flatten(self):
        return (self.centroids, self.sorted_rows, self.offsets), self.metric

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, metric=aux)

    @property
    def n_shards(self) -> int:
        return int(self.centroids.shape[0])

    @property
    def n_clusters(self) -> int:
        return int(self.centroids.shape[1])

    @property
    def shard_len(self) -> int:
        return int(self.sorted_rows.shape[1])

    def placed(self, mesh: Mesh, data_axes=("data",)) -> "ShardedIVF":
        """This index with shard s's arrays on the mesh's device s."""
        return ShardedIVF(*(place_rows(x, mesh, data_axes) for x in
                            (self.centroids, self.sorted_rows, self.offsets)),
                          metric=self.metric)

    def local_index(self, s: int):
        """Shard ``s``'s index as a plain ``ivf.IVFIndex`` (tests, probes)."""
        from repro.vectordb import ivf as _ivf

        return _ivf.IVFIndex(self.centroids[s], self.sorted_rows[s],
                             self.offsets[s], self.metric)


def build_sharded_ivf(vectors: jax.Array, n_shards: int, *,
                      n_clusters: int, seed: int = 0, metric: str = "dot",
                      base_index=None) -> ShardedIVF:
    """Build one per-shard IVF index per contiguous table slice.

    ``n_clusters`` is the PER-SHARD cluster count; every shard gets the
    same count (clamped to the shortest shard) so the stacked arrays stay
    static-shape. With ``n_shards == 1`` and a ``base_index`` the existing
    single-device index is reused verbatim — the degenerate configuration
    is then bit-for-bit the single-device candidate-local path."""
    from repro.vectordb import ivf as _ivf

    n = int(vectors.shape[0])
    s = max(1, int(n_shards))
    if s == 1 and base_index is not None:
        return ShardedIVF(base_index.centroids[None],
                          base_index.sorted_rows[None],
                          base_index.offsets[None], base_index.metric)
    shard_len = -(-n // s)
    n_last = n - (s - 1) * shard_len
    c = max(1, min(int(n_clusters), n_last))
    cents, rows, offs = [], [], []
    for i in range(s):
        v = vectors[i * shard_len: min((i + 1) * shard_len, n)]
        idx = _ivf.build(v, c, seed=seed + 7919 * i, metric=metric)
        r = idx.sorted_rows
        if int(r.shape[0]) < shard_len:
            r = jnp.pad(r, (0, shard_len - int(r.shape[0])))
        cents.append(idx.centroids)
        rows.append(r)
        offs.append(idx.offsets)
    return ShardedIVF(jnp.stack(cents), jnp.stack(rows), jnp.stack(offs),
                      metric)


def sharded_ivf_topk(n_shards: int, mesh: Mesh | None = None,
                     data_axes=("data",), *, subs: tuple, k: int,
                     metric: str, pad_total: int):
    """Build the jit'd plan-driven per-shard probing search.

    ``subs``: one entry per probed column, carrying the SHARD-LEGALIZED
    static plan params ``(pos, k_i, ks, nprobe, max_scan, iterative)`` —
    ``pos`` indexes the column tuples passed at call time (the chunk's
    weighted columns), ``ks`` the bucketed local top-k width,
    ``nprobe``/``max_scan`` the per-shard probing budget
    (``executor.legalize_for_shard``) and ``iterative`` the plan's
    re-expansion flag. Each shard probes its own IVF index
    (``ivf.search_local_batch``), reranks the per-shard candidate union by
    the full weighted score with the fused candidate-local gather+score
    kernel — the PR 4 path, now running INSIDE each shard — and keeps a
    local top-k; the global result is one O(shards · k) candidate merge.

    Returned fn signature:
      fn(cent_t, rows_t, offs_t  — per-probed-column ``ShardedIVF`` arrays,
         rows (``GatherRows`` of the chunk's columns), pred_b (stacked
         over B), qv_t tuple[(B, d_i)], w_b (B, n_cols))
        -> (ids (B, k), scores (B, k), fill (B, S), boundary (B, S),
            starved (B, S))

    ``fill[:, s]`` is how many candidates shard ``s`` contributed per query
    and ``boundary[:, s]`` is shard ``s``'s weakest VALID kept local score
    (its k-th when the local top-k filled; NEG when it kept nothing) — the
    executor's per-shard escalation reads both: a shard whose boundary
    reaches the merged k-th score had its ENTIRE contribution land at or
    above the global cutoff, so its probing budget (local truncation or a
    starved probe), not the data, was the binding constraint and rows it
    never surfaced may belong in the global top-k — the loss mode the old
    merged-underfill trigger could never see. ``starved[:, s]`` marks a
    shard whose probe of an ``iterative`` subquery qualified fewer than
    its k_i rows without probing every cluster: where the single-device
    path re-expands nprobe (iterative_scan), the shard needs the same
    escalation. Without a mesh the shard axis is looped on one device over
    the whole table's rows (a short last shard's padded index slots are
    unreachable by construction); with a mesh the identical body runs
    under ``shard_map`` on each device's own row block and the merge is
    one all-gather, in the same shard order.
    """
    from repro.core.executor import rrf_extras
    from repro.kernels.gather_score import gather_score_topk
    from repro.vectordb import ivf as _ivf

    s = max(1, int(n_shards))
    axes = data_axes if isinstance(data_axes, tuple) else (data_axes,)

    def body(cent_t, rows_t, offs_t, base, shift, rows, pred_b, qv_t, w_b):
        """One shard: probe each planned column, rerank the union, local
        top-k. Candidate ids are shard-local plus ``base``, the shard's
        first row in ``rows`` (0 under shard_map, where ``rows`` is the
        device's own block; the shard's offset on one device, where it is
        the whole table); ``shift`` makes them global."""
        wide, cands = [], []
        starved = jnp.zeros((w_b.shape[0],), bool)
        for j, (pos, k_i, ks, np_s, ms_s, it) in enumerate(subs):
            idx = _ivf.IVFIndex(cent_t[j], rows_t[j] + base, offs_t[j],
                                metric)
            ids_j, _, _, nq_j = _ivf.search_local_batch(
                idx, rows.select((pos,)), pred_b, qv_t[pos],
                nprobe=np_s, max_scan=ms_s, k=ks)
            if it and np_s < idx.n_clusters:
                starved = starved | (nq_j < k_i)
            wide.append(ids_j)
            cands.append(ids_j[:, :k_i])
        rows_b = jnp.concatenate(cands, axis=1)
        # multi-column unions fill the pad slots with RRF-fused extras from
        # the wide probe tails — the SAME composition the single-device
        # executors build (`_union_candidates`), so S=1 stays bit-for-bit
        # and every shard recovers rows ranking below top-k_i in all of its
        # per-column lists at zero extra probing cost
        if len(subs) > 1 and pad_total > rows_b.shape[1]:
            extras = rrf_extras(
                tuple(wide), kis=tuple(s[1] for s in subs),
                n_extra=pad_total - rows_b.shape[1])
            rows_b = jnp.concatenate([rows_b, extras], axis=1)
        elif pad_total > rows_b.shape[1]:
            rows_b = jnp.pad(rows_b,
                             ((0, 0), (0, pad_total - rows_b.shape[1])),
                             constant_values=-1)
        ids_l, scores_l, _ = gather_score_topk(
            rows_b.astype(jnp.int32), rows, qv_t, w_b, None, k=k,
            metric=metric)
        fill = jnp.sum(ids_l >= 0, axis=1).astype(jnp.int32)
        ids_g = jnp.where(ids_l >= 0, ids_l + shift, -1)
        # weakest VALID kept local score (scores are sorted descending, so
        # that is slot fill-1, the k-th when the shard kept a full top-k):
        # the boundary the escalation trigger compares against the merged
        # k-th. NEG when the shard contributed nothing.
        last = jnp.maximum(fill - 1, 0)[:, None]
        boundary = jnp.where(
            fill > 0, jnp.take_along_axis(scores_l, last, axis=1)[:, 0],
            jnp.float32(NEG))
        return ids_g, scores_l, fill, boundary, starved

    zero = jnp.int32(0)
    if mesh is None:
        def run(cent_t, rows_t, offs_t, rows, pred_b, qv_t, w_b):
            n = rows.scalars.shape[0]
            shard_len = -(-n // s)
            if s == 1:
                # degenerate configuration: EXACTLY the single-device
                # candidate-local chunk (no loop, identity merge)
                ids, sc, fill, bnd, stv = body(
                    tuple(c[0] for c in cent_t), tuple(r[0] for r in rows_t),
                    tuple(o[0] for o in offs_t), zero, zero, rows,
                    pred_b, qv_t, w_b)
                return ids, sc, fill[:, None], bnd[:, None], stv[:, None]
            row0 = jnp.arange(s, dtype=jnp.int32) * shard_len
            # a loop over the shard axis, not vmap: the gather kernel reads
            # its table from HBM (memory space ANY), which Mosaic cannot
            # batch. Every shard reads the same whole-table rows, offset
            # by its first row, so no shard's rows are sliced or copied.
            ids, sc, fill, bnd, stv = jax.lax.map(
                lambda xs: body(*xs, zero, rows, pred_b, qv_t, w_b),
                (cent_t, rows_t, offs_t, row0))
            b = sc.shape[1]
            # (S, B, k) -> (B, S·k) in shard order — the all_gather layout
            s_all = jnp.swapaxes(sc, 0, 1).reshape(b, s * k)
            g_all = jnp.swapaxes(ids, 0, 1).reshape(b, s * k)
            mi, ms = _merge_shard_candidates(s_all, g_all, k=k)
            return (mi, ms, jnp.swapaxes(fill, 0, 1),
                    jnp.swapaxes(bnd, 0, 1), jnp.swapaxes(stv, 0, 1))

        return jax.jit(run)

    sub_specs3 = tuple(P(axes, None, None) for _ in subs)
    sub_specs2 = tuple(P(axes, None) for _ in subs)

    def local(cent_t, rows_t, offs_t, rows, pred_b, qv_t, w_b, row0):
        ids_g, sc, fill, bnd, stv = body(
            tuple(c[0] for c in cent_t), tuple(r[0] for r in rows_t),
            tuple(o[0] for o in offs_t), zero, row0[0], rows,
            pred_b, qv_t, w_b)
        s_all = jax.lax.all_gather(sc, axes, axis=1, tiled=True)
        g_all = jax.lax.all_gather(ids_g, axes, axis=1, tiled=True)
        mi, ms = _merge_shard_candidates(s_all, g_all, k=k)
        return mi, ms, fill[None, :], bnd[None, :], stv[None, :]

    # every array of ``rows`` splits on its leading (row) axis
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(sub_specs3, sub_specs2, sub_specs2, P(axes), P(), P(),
                  P(), P(axes)),
        out_specs=(P(), P(), P(axes, None), P(axes, None), P(axes, None)),
        check_vma=False)

    def run(cent_t, rows_t, offs_t, rows, pred_b, qv_t, w_b):
        n = rows.scalars.shape[0]
        assert n % s == 0, (n, s)
        row0 = jnp.arange(s, dtype=jnp.int32) * (n // s)
        mi, ms, fill, bnd, stv = fn(cent_t, rows_t, offs_t, rows, pred_b,
                                    qv_t, w_b, row0)
        return (mi, ms, jnp.swapaxes(fill, 0, 1), jnp.swapaxes(bnd, 0, 1),
                jnp.swapaxes(stv, 0, 1))

    return jax.jit(run)
