"""Columnar vector+scalar table — the storage substrate for MHQ.

A table holds N vector columns and M scalar columns (paper Fig. 1). All
scalar columns are stored as a dense ``(n, M)`` float32 matrix; categorical
columns carry integer category codes (their cardinality lives in the schema),
so every predicate is expressible as a closed range ``[lo, hi]`` (equality is
``[c, c]``). This keeps predicate evaluation a single fused compare-reduce on
TPU, and the encoder re-expands categoricals to one-hot from the codes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ScalarCol:
    name: str
    kind: str  # "num" | "cat"
    n_categories: int = 0  # for "cat"


@dataclasses.dataclass(frozen=True)
class VectorCol:
    name: str
    dim: int


@dataclasses.dataclass(frozen=True)
class TableSchema:
    vector_cols: tuple[VectorCol, ...]
    scalar_cols: tuple[ScalarCol, ...]
    metric: str = "dot"  # "dot" (higher=closer) | "l2" (lower=closer)

    @property
    def n_vec(self) -> int:
        return len(self.vector_cols)

    @property
    def n_scalar(self) -> int:
        return len(self.scalar_cols)

    def vec_index(self, name: str) -> int:
        return [v.name for v in self.vector_cols].index(name)


@dataclasses.dataclass
class Table:
    schema: TableSchema
    vectors: list[jax.Array]  # one (n, d_i) per vector column
    scalars: jax.Array  # (n, M) float32
    # per-column symmetric int8 replica (the quantized scoring tier):
    # vectors_i8[i] is (n, d_i) int8, scales[i] the (n,) f32 per-row absmax
    # scale (zero-point is 0 by symmetry). Built lazily per column and
    # maintained through append, so TieredTable compaction inherits it.
    vectors_i8: Optional[list] = None
    scales: Optional[list] = None
    # the gather kernel's row views (``gather_rows``), built on first use
    # per column and precision and kept with this table version
    _views: dict = dataclasses.field(default_factory=dict, repr=False,
                                     compare=False)

    @property
    def n_rows(self) -> int:
        return int(self.scalars.shape[0])

    def quantized(self, i: int) -> tuple[jax.Array, jax.Array]:
        """The column's int8 replica, built on first use and cached.
        -> ((n, d_i) int8, (n,) f32 per-row scales)."""
        if self.vectors_i8 is None:
            self.vectors_i8 = [None] * self.schema.n_vec
            self.scales = [None] * self.schema.n_vec
        if self.vectors_i8[i] is None:
            from repro.kernels.int8_scan import quantize_rows

            self.vectors_i8[i], self.scales[i] = quantize_rows(self.vectors[i])
        return self.vectors_i8[i], self.scales[i]

    def gather_rows(self, cols=None, *, int8: bool = False):
        """Columns ``cols`` (default: all) as the candidate-local scorers
        read them (``kernels.gather_score.GatherRows``): fp32, or with
        ``int8`` the int8 replicas. The row views cost one copy of each
        column; they are built on first use and cached with this table
        version, like the replica, so a call gathers only its candidates'
        rows."""
        from repro.kernels import gather_score as gs

        cols = tuple(range(self.schema.n_vec)) if cols is None else tuple(cols)
        if "meta" not in self._views:
            self._views["meta"] = gs.meta_view(self.scalars)
        for i in cols:
            if (i, int8) not in self._views:
                self._views[(i, int8)] = gs.int8_view(*self.quantized(i)) \
                    if int8 else gs.column_view(self.vectors[i])
        views = tuple(self._views[(i, int8)] for i in cols)
        if int8:
            return gs.GatherRows(
                tuple(self.vectors_i8[i] for i in cols), self.scalars,
                tuple(self.scales[i] for i in cols), views,
                self._views["meta"])
        return gs.GatherRows(tuple(self.vectors[i] for i in cols),
                             self.scalars, None, views, self._views["meta"])

    def with_arrays(self, fn) -> "Table":
        """This table version with ``fn`` applied to every array it holds —
        columns, scalars, int8 replicas and built row views (placement)."""
        def each(xs):
            return None if xs is None else [
                None if x is None else fn(x) for x in xs]

        new = Table(self.schema, each(self.vectors), fn(self.scalars),
                    each(self.vectors_i8), each(self.scales))
        new._views.update((key, fn(v)) for key, v in self._views.items())
        return new

    @staticmethod
    def from_numpy(schema: TableSchema, vectors: list[np.ndarray], scalars: np.ndarray) -> "Table":
        assert len(vectors) == schema.n_vec
        n = scalars.shape[0]
        for v, col in zip(vectors, schema.vector_cols):
            assert v.shape == (n, col.dim), (v.shape, col)
        assert scalars.shape == (n, schema.n_scalar)
        return Table(
            schema=schema,
            vectors=[jnp.asarray(v, jnp.float32) for v in vectors],
            scalars=jnp.asarray(scalars, jnp.float32),
        )

    def append(self, vectors: list[np.ndarray], scalars: np.ndarray) -> "Table":
        """Immutable append (used by the data-update experiments).

        The scale is per ROW, so an append never re-quantizes old rows: any
        already-built int8 replica carries over as (old replica ‖ quantized
        new rows) — compaction keeps the quantized tier warm for free."""
        new = Table(
            schema=self.schema,
            vectors=[jnp.concatenate([a, jnp.asarray(b, jnp.float32)]) for a, b in zip(self.vectors, vectors)],
            scalars=jnp.concatenate([self.scalars, jnp.asarray(scalars, jnp.float32)]),
        )
        if self.vectors_i8 is not None and any(
                q is not None for q in self.vectors_i8):
            from repro.kernels.int8_scan import quantize_rows

            new.vectors_i8 = [None] * self.schema.n_vec
            new.scales = [None] * self.schema.n_vec
            for i, nv in enumerate(vectors):
                if self.vectors_i8[i] is None:
                    continue
                qn, sn = quantize_rows(jnp.asarray(nv, jnp.float32))
                new.vectors_i8[i] = jnp.concatenate([self.vectors_i8[i], qn])
                new.scales[i] = jnp.concatenate([self.scales[i], sn])
        return new


def similarity(q: jax.Array, vecs: jax.Array, metric: str) -> jax.Array:
    """Score rows of ``vecs`` (n, d) against ``q`` (d,). Higher = better.

    Scores are returned as results, so the matmul runs at HIGHEST
    precision: on a TPU an f32 matmul otherwise takes one reduced-precision
    bf16 pass, which missed the oracle's 1e-4 tolerance on part's unit-norm
    768-d rows (2.6e-4 off on a TPU v5e)."""
    dot = jnp.matmul(vecs, q, precision=jax.lax.Precision.HIGHEST)
    if metric == "dot":
        return dot
    if metric == "l2":
        # -||v - q||^2 expanded — keeps it a single matmul + row norms
        return 2.0 * dot - jnp.sum(vecs * vecs, axis=-1) - jnp.sum(q * q)
    raise ValueError(f"unknown metric {metric!r}")


def weighted_score(
    table: Table, query_vectors: list[jax.Array], weights: jax.Array, rows: Optional[jax.Array] = None
) -> jax.Array:
    """Composite score Σ_i w_i · sim(q_i, o.v_i) (paper §1 definition)."""
    total = None
    for i, q in enumerate(query_vectors):
        vecs = table.vectors[i] if rows is None else table.vectors[i][rows]
        s = weights[i] * similarity(q, vecs, table.schema.metric)
        total = s if total is None else total + s
    return total
