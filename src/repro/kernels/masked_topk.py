"""Fused filtered-scan kernel: score ⊙ predicate-mask → per-block top-k.

The paper's hot loop (§3.4 execution) is "score rows, drop rows failing
Q_S, keep the best k". On TPU we tile the DB into (block_rows × dim) VMEM
blocks; each grid step runs one MXU matvec (scores), evaluates the
conjunctive range predicate on the block's scalars, masks, and selects the
block-local top-K by K rounds of max+knockout (K is static and small, so
this stays fully vectorized — no sort, which Mosaic lowers poorly).
Per-block candidates go back to HBM; the cross-block merge is a single
O(nb·K) ``lax.top_k`` in the caller (ops.py).

Grid is 1-D over row blocks; the query and predicate vectors stay resident
(their index_map pins block (0, …)). VMEM per step ≈ block_rows·(dim + M)·4B
— block_rows=1024, dim=768, M=8 ⇒ ~3.2 MB, comfortably inside 16 MB VMEM,
with dims aligned to the 128-lane MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gather_score import block_topk
from repro.kernels.shapes import NEG, SCAN_BLOCK_ROWS, round128


def _kernel(q_ref, vec_ref, scal_ref, lo_ref, hi_ref, act_ref, nrows_ref,
            out_s_ref, out_i_ref, *, k: int, block_rows: int, metric: str):
    i = pl.program_id(0)
    v = vec_ref[...]  # (BN, D)
    q = q_ref[...]  # (1, D)
    scores = jnp.dot(v, q.T, preferred_element_type=jnp.float32)  # (BN, 1)
    if metric == "l2":  # -||v - q||² up to the constant ||q||²
        scores = 2.0 * scores - jnp.sum(v * v, axis=1, keepdims=True)
    sc = scal_ref[...]  # (BN, M)
    ok = (sc >= lo_ref[...]) & (sc <= hi_ref[...]) | (act_ref[...] < 0.5)
    ok = jnp.all(ok, axis=1, keepdims=True)  # (BN, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (block_rows, 1), 0)
    gid = i * block_rows + row
    valid = gid < nrows_ref[0, 0]
    s = jnp.where(ok & valid, scores, NEG)  # (BN, 1)
    # k rounds of (max, knockout), tie-break by smallest row id — static k
    # keeps everything vectorized
    out_s_ref[0], out_i_ref[0] = block_topk(s, gid, k=k,
                                            k_pad=out_s_ref.shape[-1])


@functools.partial(jax.jit, static_argnames=("k", "block_rows", "metric",
                                             "interpret"))
def masked_topk_blocks(q, vectors, scalars, lo, hi, active, n_rows, *,
                       k: int, block_rows: int = SCAN_BLOCK_ROWS,
                       metric: str = "dot", interpret: bool = True):
    """-> (block_scores (nb, k), block_ids (nb, k)). Inputs must be padded to
    a multiple of block_rows (ops.py handles padding + the final merge)."""
    n, d = vectors.shape
    m = scalars.shape[1]
    assert n % block_rows == 0, (n, block_rows)
    nb = n // block_rows
    k_pad = round128(k)  # lane-dense (1, k_pad) output rows
    kern = functools.partial(_kernel, k=k, block_rows=block_rows, metric=metric)
    out_s, out_i = pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, d), lambda i: (0, 0)),  # q — resident
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),  # vectors tile
            pl.BlockSpec((block_rows, m), lambda i: (i, 0)),  # scalars tile
            pl.BlockSpec((1, m), lambda i: (0, 0)),  # lo
            pl.BlockSpec((1, m), lambda i: (0, 0)),  # hi
            pl.BlockSpec((1, m), lambda i: (0, 0)),  # active
            pl.BlockSpec((1, 1), lambda i: (0, 0)),  # n_rows
        ],
        out_specs=[
            pl.BlockSpec((1, 1, k_pad), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, k_pad), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, k_pad), jnp.int32),
        ],
        interpret=interpret,
    )(q[None, :], vectors, scalars, lo[None, :], hi[None, :],
      active[None, :].astype(jnp.float32),
      jnp.asarray(n_rows, jnp.int32).reshape(1, 1))
    return out_s[:, 0, :k], out_i[:, 0, :k]
