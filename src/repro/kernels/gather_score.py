"""Fused candidate-local gather+score kernel: the executor hot path past the
dense-GEMM crossover.

The batched executor scores DENSELY — one GEMM over all rows per vector
column per batch — which is optimal while ``B·max_scan / n_rows`` is large
but becomes the wall past ~10⁵-row shards: the GEMM touches every row even
though the learned plans only ever look at ``max_scan`` candidates per
query. This kernel closes that gap. Given a ``(B, S)`` candidate-row matrix
(padded with -1), each grid step (query b, candidate block j):

  * copies the block's candidate ids into SMEM, then gathers each
    candidate's row of every column's ``(n, 1, W)`` row view plus the
    scalars' row view (``GatherRows``, built once per table version by its
    owner) into VMEM scratch with one HBM→VMEM async copy per row and view
    — the table stays in HBM, so its size is bounded by HBM, not the
    ~16 MB VMEM, and a call moves only its candidates' rows; the rows are
    arbitrary, so there is no contiguous BlockSpec for them;
  * scores each column with f32 VPU products and lane reductions (no
    reduced-precision MXU pass) and combines with the query's column
    weights (l2 keeps the -||v||² and -||q||² terms so score VALUES match
    ``table.similarity``, not just the ranking);
  * evaluates the DNF predicate on the gathered scalars (OR over valid
    clauses of AND over active columns) and masks;
  * selects the block-local top-k by k rounds of max+knockout, where the
    knockout removes every slot carrying the winning ROW ID — duplicate
    candidates (the rerank union) can never crowd distinct rows out of a
    block's k slots — into one lane-dense output row.

Per-block candidates merge in the caller (``merge_topk_unique``) by the same
max + knockout-by-id rounds, so ties break by smaller row id — the rule the
pure-jnp reference (``ref.gather_score_ref``) and the NumPy test oracle use,
which keeps kernel-vs-reference id parity exact on tie-free data.

Off-TPU the public entry ``gather_score_topk`` runs the reference path by
default (the interpreter would execute the Pallas kernel in Python, row by
row); on a TPU backend the same call tiles through Mosaic. ``use_kernel``
forces either path (tests pin kernel-vs-reference parity with
``use_kernel=True, interpret=True``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.shapes import GATHER_BLOCK_S, ID_SENTINEL, NEG, round128


def _pred_fields(pred):
    """Dense (B, C, M) lo/hi/active + (B, C) clause_valid f32 fields from a
    batched PredicateLike (the conjunctive shim lifts to one valid clause)."""
    from repro.vectordb.predicates import as_set

    ps = as_set(pred)
    return (ps.lo.astype(jnp.float32), ps.hi.astype(jnp.float32),
            ps.active.astype(jnp.float32), ps.clause_valid.astype(jnp.float32))


def merge_topk_unique(ids, scores, k: int):
    """(B, P) candidate pools -> (B, k) top-k with duplicate row ids
    suppressed and ties broken by smaller row id.

    Padded slots carry id -1 / score NEG. Duplicate ids score identically
    (same row, same per-row dot). k rounds of max + knockout-by-id: each
    round is one pass over the pool, with no sort — XLA's TPU sort of a
    pool tens of thousands wide takes tens of seconds to compile, and a
    serving path compiles one such merge per plan shape."""
    ids = ids.astype(jnp.int32)
    lane = jnp.arange(k, dtype=jnp.int32)[None, :]

    def select(r, carry):
        s, out_i, out_s = carry
        m = jnp.max(s, axis=1, keepdims=True)
        first = jnp.min(jnp.where((s >= m) & (s > NEG / 2), ids,
                                  jnp.int32(ID_SENTINEL)),
                        axis=1, keepdims=True)
        hit = m > NEG / 2
        out_i = jnp.where(lane == r, jnp.where(hit, first, -1), out_i)
        out_s = jnp.where(lane == r, jnp.where(hit, m, NEG), out_s)
        return jnp.where(ids == first, NEG, s), out_i, out_s

    b = ids.shape[0]
    _, out_i, out_s = jax.lax.fori_loop(
        0, k, select,
        (jnp.where(ids >= 0, scores.astype(jnp.float32), NEG),
         jnp.full((b, k), -1, jnp.int32), jnp.full((b, k), NEG, jnp.float32)))
    return out_i, out_s


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _kernel(cand_hbm, w_ref, lo_ref, hi_ref, act_ref, cval_ref, q_ref,
            *refs, k: int, k_pad: int, block_s: int, nb: int, dims: tuple,
            n_clauses: int, metric: str, apply_pred: bool, int8: bool):
    """One (query b, candidate block j) grid step. Every per-candidate
    quantity is a (block_s, 1) column; each output is one lane-dense
    (1, k_pad) row, so no scalar is ever stored into a vector ref.

    ``refs``: the per-column row views and the meta view (HBM), the three
    outputs, then scratch: SMEM ids, the id column, one row tile per view
    and the DMA semaphores."""
    n_vec = len(dims)
    view_hbm = refs[:n_vec + 1]  # columns..., meta
    out_s_ref, out_i_ref, out_q_ref = refs[n_vec + 1:n_vec + 4]
    ids_smem, cid_tile = refs[n_vec + 4:n_vec + 6]
    tiles = refs[n_vec + 6:2 * n_vec + 7]
    sem = refs[2 * n_vec + 7]
    # the block's candidate ids land in SMEM, so every row copy below
    # takes a scalar address
    blk = pl.program_id(0) * nb + pl.program_id(1)
    ids_copy = pltpu.make_async_copy(cand_hbm.at[blk], ids_smem, sem.at[0])
    ids_copy.start()
    ids_copy.wait()

    def row_copies(t, r):
        return [pltpu.make_async_copy(v.at[pl.ds(r, 1)],
                                      tile.at[pl.ds(t, 1)], sem.at[1 + c])
                for c, (v, tile) in enumerate(zip(view_hbm, tiles))]

    # arbitrary-row gather: one HBM->VMEM copy per valid candidate row and
    # view, all started before any is waited on. The table never enters
    # VMEM, so its size is bounded by HBM; padding slots (-1) move nothing.
    def start(t, _):
        r = ids_smem[0, t]

        @pl.when(r >= 0)
        def _():
            for cp in row_copies(t, r):
                cp.start()
        return 0

    def wait(t, _):
        r = ids_smem[0, t]

        @pl.when(r >= 0)
        def _():
            for cp in row_copies(t, r):
                cp.wait()
        cid_tile[pl.ds(t, 1), :] = jnp.full((1, 128), r, jnp.int32)
        return 0

    jax.lax.fori_loop(0, block_s, start, 0)
    jax.lax.fori_loop(0, block_s, wait, 0)

    cid = cid_tile[:, 0:1]  # (BS, 1) i32, -1 = padding
    valid = cid >= 0
    meta = tiles[n_vec][...].reshape(block_s, 128)  # [scalars | 0-pad]
    w = w_ref[0]  # (1, n_vec)
    total = jnp.zeros((block_s, 1), jnp.float32)
    off = 0
    for i, d in enumerate(dims):
        seg = tiles[i][...]
        width = seg.shape[-1]
        seg = seg.reshape(block_s, width)
        q = q_ref[0, :, off:off + width]  # (1, W) f32 or (4, W) for int8
        off += width
        # VPU multiplies + lane reductions: exact f32 products (no
        # reduced-precision MXU passes), so score VALUES match
        # ``table.similarity``; zero lane padding adds nothing
        if int8:
            # quantized tier: the row holds d/4 words of 4 packed int8
            # lanes (byte p of word m is element 4m+p; q is regrouped the
            # same way) — a quarter of the fp32 bytes per gathered row —
            # then the row's dequant scale as f32 bits in word d/4
            d4 = -(-d // 4)
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
            sc = jnp.sum(jnp.where(lane == d4, jax.lax.bitcast_convert_type(
                seg, jnp.float32), 0.0), axis=1, keepdims=True)
            seg = jnp.where(lane < d4, seg, 0)
            s = jnp.zeros((block_s, 1), jnp.float32)
            nn = jnp.zeros((block_s, 1), jnp.float32)
            for p in range(4):
                e = ((seg << (24 - 8 * p)) >> 24).astype(jnp.float32)
                s = s + jnp.sum(e * q[p:p + 1], axis=1, keepdims=True)
                nn = nn + jnp.sum(e * e, axis=1, keepdims=True)
            # score(v·s) = s·score(v); l2 norms rescale by s²
            s = s * sc
            nn = nn * sc * sc
        else:
            s = jnp.sum(seg * q, axis=1, keepdims=True)
            nn = jnp.sum(seg * seg, axis=1, keepdims=True)
        if metric == "l2":
            s = 2.0 * s - nn - jnp.sum(q * q)
        total = total + w[:, i:i + 1] * jnp.where(valid, s, 0.0)

    ok = valid
    if apply_pred:
        # lo/hi/active are padded to the meta view's 128 lanes with
        # inactive columns, so lanes past the scalars always pass
        any_clause = jnp.zeros((block_s, 1), bool)
        for c in range(n_clauses):
            sat = (((meta >= lo_ref[0, c:c + 1, :])
                    & (meta <= hi_ref[0, c:c + 1, :]))
                   | (act_ref[0, c:c + 1, :] < 0.5))  # (BS, 128)
            clause = jnp.min(sat.astype(jnp.int32), axis=1, keepdims=True)
            any_clause = any_clause | (
                (clause > 0) & (cval_ref[0, :, c:c + 1] > 0.5))
        ok = ok & any_clause
    out_q_ref[0] = jnp.broadcast_to(
        jnp.sum(ok.astype(jnp.int32), axis=0, keepdims=True), (1, 128))

    out_s_ref[0], out_i_ref[0] = block_topk(
        jnp.where(ok, total, NEG), cid, k=k, k_pad=k_pad)


def block_topk(s, ids, *, k: int, k_pad: int):
    """Top-k of a (R, 1) score column by k rounds of max + knockout, where
    the knockout removes every slot carrying the winning id (duplicates
    never take two slots) and ties go to the smaller id. -> lane-dense
    (1, k_pad) score and id rows; unfilled slots hold NEG / -1. Vector ops
    only, so the rounds lower on Mosaic as well as in the interpreter."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, k_pad), 1)

    def select(r, carry):
        s, row_s, row_i = carry
        m = jnp.max(s, axis=0, keepdims=True)  # (1, 1)
        is_max = (s >= m) & (s > NEG / 2)
        first = jnp.min(jnp.where(is_max, ids, jnp.int32(ID_SENTINEL)),
                        axis=0, keepdims=True)
        row_s = jnp.where(lane == r, m, row_s)
        row_i = jnp.where(lane == r, jnp.where(m > NEG / 2, first, -1),
                          row_i)
        return jnp.where(ids == first, NEG, s), row_s, row_i

    _, row_s, row_i = jax.lax.fori_loop(
        0, k, select, (s, jnp.full((1, k_pad), NEG, jnp.float32),
                       jnp.full((1, k_pad), -1, jnp.int32)))
    return row_s, row_i


@jax.jit
def column_view(v):
    """fp32 row view of one (n, d) column: (n, 1, d rounded up to 128
    lanes), zero-padded."""
    n, d = v.shape
    return jnp.pad(v.astype(jnp.float32),
                   ((0, 0), (0, round128(d) - d)))[:, None, :]


@jax.jit
def int8_view(v, scale):
    """int8 row view of one (n, d) replica: four int8 lanes per i32 word
    (byte p of word m is element 4m+p), then the row's dequant scale as
    f32 bits in word d/4, zero-padded to 128 lanes."""
    n, d = v.shape
    d4 = -(-d // 4)
    b = jnp.pad(v, ((0, 0), (0, 4 * d4 - d)))
    b = (b.astype(jnp.int32) & 0xFF).reshape(n, d4, 4)
    words = b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) \
        | (b[..., 3] << 24)
    scale = jax.lax.bitcast_convert_type(scale.astype(jnp.float32),
                                         jnp.int32)[:, None]
    row = jnp.concatenate([words, scale], axis=1)
    return jnp.pad(row, ((0, 0), (0, round128(d4 + 1) - d4 - 1)))[:, None, :]


@jax.jit
def meta_view(scalars):
    """(n, 1, 128) row view of the (n, M) scalars, zero-padded."""
    m = scalars.shape[1]
    assert m <= 128, m
    return jnp.pad(scalars.astype(jnp.float32),
                   ((0, 0), (0, 128 - m)))[:, None, :]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GatherRows:
    """A table's columns as the candidate-local scorers read them: the
    plain arrays (routing, predicate masks and the reference path) and the
    kernel's row views. Mosaic copies rows only along a leading axis and
    in whole 128-lane tiles, so each column is kept as an (n, 1, W) view
    whose row is one DMA, and the scalars as an (n, 1, 128) view.

    Views cost one copy of the table per version: the owner (``Table``,
    a hot view, a mesh placement) builds them once and every call reads
    only its candidates' rows. ``build`` makes them from plain arrays, for
    callers that hold no owner (tests, benchmarks).

    fp32 rows carry f32 columns; int8 rows (``scales`` given) carry the
    int8 replicas and per-row dequant scales, their views packed words
    (``int8_view``)."""

    vectors: tuple  # (n, d_i) per column: f32, or the int8 replicas
    scalars: jax.Array  # (n, M) f32
    scales: tuple | None  # (n,) f32 per-row dequant scales (int8 only)
    views: tuple  # (n, 1, W_i) per column
    meta: jax.Array  # (n, 1, 128) f32 scalars

    @staticmethod
    def build(vectors, scalars, scales=None, meta=None) -> "GatherRows":
        vectors = tuple(vectors)
        if scales is None:
            views = tuple(column_view(v) for v in vectors)
        else:
            scales = tuple(scales)
            views = tuple(int8_view(v, s) for v, s in zip(vectors, scales))
        return GatherRows(vectors, scalars, scales, views,
                          meta_view(scalars) if meta is None else meta)

    def select(self, cols) -> "GatherRows":
        """The same rows restricted to columns ``cols``."""
        pick = lambda xs: tuple(xs[c] for c in cols)
        return GatherRows(pick(self.vectors), self.scalars,
                          None if self.scales is None else pick(self.scales),
                          pick(self.views), self.meta)

    @property
    def int8(self) -> bool:
        return self.scales is not None


def _query_view(qs, widths, int8: bool):
    """(B, R, sum of view widths) queries laid out like the row views:
    R = 1 row of zero-padded f32 lanes, or for int8 R = 4 rows where row p
    holds the elements 4m+p the packed byte p multiplies."""
    parts = []
    for q, width in zip(qs, widths):
        q = q.astype(jnp.float32)
        b, d = q.shape
        if int8:
            q = jnp.pad(q, ((0, 0), (0, (-d) % 4)))
            q = jnp.swapaxes(q.reshape(b, -1, 4), 1, 2)  # (B, 4, d/4)
        else:
            q = q[:, None, :]
        parts.append(jnp.pad(q, ((0, 0), (0, 0), (0, width - q.shape[2]))))
    return jnp.concatenate(parts, axis=2)


@functools.partial(jax.jit, static_argnames=("k", "block_s", "metric",
                                             "apply_pred", "interpret"))
def gather_score_blocks(cand, rows: GatherRows, qs, weights, lo, hi, active,
                        clause_valid, *, k: int, block_s: int,
                        metric: str = "dot", apply_pred: bool = True,
                        interpret: bool = True):
    """-> (block_scores (B, nb, k), block_ids (B, nb, k), block_qual (B, nb)).

    ``cand`` (B, S) i32 candidate rows (-1 = padding), S a multiple of
    ``block_s``; block ids are ROW ids (block-locally deduplicated).

    Int8 ``rows`` (``rows.scales`` given) hold the int8 replicas: rows
    gather as packed int8 words and dequantize per row in VMEM — the
    quantized scoring tier.

    Layout for Mosaic: the table enters as ``rows``' (n, 1, W) row views,
    read in place from HBM; candidate ids as lane-padded (B·nb, 1, block_s)
    rows copied into SMEM; per-query operands as (B, R, X) arrays whose
    blocks span their trailing dims; and each output block is a lane-dense
    (1, k_pad) row (k_pad = k rounded up to 128), sliced back to k here."""
    b, s_tot = cand.shape
    assert s_tot % block_s == 0, (s_tot, block_s)
    nb = s_tot // block_s
    m = rows.scalars.shape[1]
    widths = tuple(v.shape[-1] for v in rows.views)
    dims = tuple(q.shape[1] for q in qs)
    q3 = _query_view(qs, widths, rows.int8)
    k_pad = round128(k)
    kern = functools.partial(
        _kernel, k=k, k_pad=k_pad, block_s=block_s, nb=nb, dims=dims,
        n_clauses=lo.shape[1], metric=metric, apply_pred=apply_pred,
        int8=rows.int8)
    # predicate fields padded to the meta view: extra lanes inactive
    pad = ((0, 0), (0, 0), (0, 128 - m))
    lo3 = jnp.pad(lo.astype(jnp.float32), pad)
    hi3 = jnp.pad(hi.astype(jnp.float32), pad)
    act3 = jnp.pad(active.astype(jnp.float32), pad)
    w3 = weights.astype(jnp.float32)[:, None, :]
    # one id row per block, lane-padded: ids copy in whole 128-lane tiles
    ids3 = jnp.pad(cand.astype(jnp.int32).reshape(b * nb, 1, block_s),
                   ((0, 0), (0, 0), (0, round128(block_s) - block_s)),
                   constant_values=-1)
    cval3 = clause_valid.astype(jnp.float32)[:, None, :]

    def per_query(x):
        return pl.BlockSpec((1,) + x.shape[1:],
                            lambda b_, j: (b_,) + (0,) * (x.ndim - 1))

    hbm = pl.BlockSpec(memory_space=pl.ANY)

    def out_block(width):
        return pl.BlockSpec((1, 1, width), lambda b_, j: (b_ * nb + j, 0, 0))

    views = tuple(rows.views) + (rows.meta,)
    out_s, out_i, out_q = pl.pallas_call(
        kern,
        grid=(b, nb),
        in_specs=[hbm, per_query(w3), per_query(lo3), per_query(hi3),
                  per_query(act3), per_query(cval3), per_query(q3)]
        + [hbm] * len(views),
        out_specs=[out_block(k_pad), out_block(k_pad), out_block(128)],
        out_shape=[
            jax.ShapeDtypeStruct((b * nb, 1, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((b * nb, 1, k_pad), jnp.int32),
            jax.ShapeDtypeStruct((b * nb, 1, 128), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((1, ids3.shape[-1]), jnp.int32),  # block's cand ids
            pltpu.VMEM((block_s, 128), jnp.int32),  # the same ids as a column
        ] + [pltpu.VMEM((block_s, 1, v.shape[-1]), v.dtype)  # gathered rows
             for v in views]
        + [pltpu.SemaphoreType.DMA((1 + len(views),))],
        interpret=interpret,
    )(ids3, w3, lo3, hi3, act3, cval3, q3, *views)
    return (out_s[:, 0, :k].reshape(b, nb, k),
            out_i[:, 0, :k].reshape(b, nb, k),
            out_q[:, 0, 0].reshape(b, nb))


# ---------------------------------------------------------------------------
# public entry — kernel on TPU, pure-jnp reference elsewhere
# ---------------------------------------------------------------------------

def _default_use_kernel() -> bool:
    return jax.default_backend() == "tpu"


def gather_score_topk(cand, rows: GatherRows, qs, weights, pred=None, *,
                      k: int, metric: str = "dot",
                      block_s: int = GATHER_BLOCK_S,
                      use_kernel: bool | None = None,
                      interpret: bool | None = None):
    """Fused candidate-local filtered top-k for a query batch.

    cand:    (B, S) i32 candidate row ids, -1 = padded/empty slot (duplicates
             allowed — they are deduplicated before selection).
    rows:    the scored columns and the scalars (``GatherRows``); int8 rows
             run the quantized tier (4× fewer gathered HBM bytes; the DNF
             mask still evaluates on the exact fp32 scalars).
    qs:      tuple of (B, d_i) queries, one per column of ``rows``;
    weights: (B, n_vec) per-column weights.
    pred:    batched PredicateLike (leading axis B) or None to skip masking
             (candidates already qualified, e.g. the rerank union).

    -> (ids (B, k), scores (B, k), n_qualified (B,)). Empty slots carry
    id -1 / score NEG; ties break by smaller row id. Traceable — callers
    jit it into their own graphs."""
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    b, s_tot = cand.shape
    apply_pred = pred is not None
    if apply_pred:
        lo, hi, act, cval = _pred_fields(pred)
    else:
        m = rows.scalars.shape[1]
        lo = jnp.full((b, 1, m), -jnp.inf, jnp.float32)
        hi = jnp.full((b, 1, m), jnp.inf, jnp.float32)
        act = jnp.zeros((b, 1, m), jnp.float32)
        cval = jnp.ones((b, 1), jnp.float32)

    if not use_kernel:
        from repro.kernels.ref import gather_score_ref

        return gather_score_ref(cand, rows.vectors, qs, weights, rows.scalars,
                                lo, hi, act, cval, k=k, metric=metric,
                                apply_pred=apply_pred, scales=rows.scales)

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bs = min(block_s, _next_pow2(max(s_tot, k, 8)))
    pad = (-s_tot) % bs
    if s_tot + pad < k:  # the merge pool (nb·k) must hold at least k slots
        pad += ((k - (s_tot + pad)) + bs - 1) // bs * bs
    if pad:
        cand = jnp.pad(cand, ((0, 0), (0, pad)), constant_values=-1)
    out_s, out_i, out_q = gather_score_blocks(
        cand, rows, tuple(qs), weights, lo, hi, act, cval, k=k, block_s=bs,
        metric=metric, apply_pred=apply_pred, interpret=interpret)
    nb = cand.shape[1] // bs
    ids, scores = merge_topk_unique(
        out_i.reshape(b, nb * k), out_s.reshape(b, nb * k), k)
    return ids, scores, jnp.sum(out_q, axis=1)


# α of the two-stage quantized scan: the int8 pass keeps α·k candidates for
# the exact fp32 rerank. Measured on the quantization-loss suite: α=4 holds
# the int8-tier recall within 0.01 of fp32 candidate-local on every clause
# bucket; the rerank pool is capped at MAX_TOPK (the largest static k).
RERANK_MULT = 4


def gather_score_topk_int8(cand, rows: GatherRows, rows_i8: GatherRows, qs,
                           weights, pred=None, *, k: int,
                           metric: str = "dot",
                           rerank_mult: int = RERANK_MULT,
                           block_s: int = GATHER_BLOCK_S,
                           use_kernel: bool | None = None,
                           interpret: bool | None = None):
    """Two-stage quantized candidate-local top-k: int8 gather→score→DNF-mask
    over ``rows_i8`` keeps the top ``rerank_mult·k`` candidates (predicates
    evaluate on the EXACT scalars, so filtering is bit-identical to fp32),
    then the fp32 kernel reranks exactly those rows of ``rows`` — returned
    scores are exact fp32 and the quantization can only affect which
    near-boundary rows reach the rerank pool.

    Same contract as ``gather_score_topk``; ``n_qualified`` counts the
    original candidate list's qualifying slots (stage-1 semantics)."""
    from repro.kernels.shapes import MAX_TOPK

    kq = max(k, min(rerank_mult * k, MAX_TOPK))
    ids_q, _, n_qual = gather_score_topk(
        cand, rows_i8, qs, weights, pred, k=kq, metric=metric,
        block_s=block_s, use_kernel=use_kernel, interpret=interpret)
    # survivors are already predicate-qualified and deduplicated (-1 pads)
    ids, scores, _ = gather_score_topk(
        ids_q, rows, qs, weights, None, k=k, metric=metric,
        block_s=block_s, use_kernel=use_kernel, interpret=interpret)
    return ids, scores, n_qual


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p
