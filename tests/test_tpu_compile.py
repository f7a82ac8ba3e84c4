"""Compile the serving path's Pallas kernels for a described TPU v5e.

No chip is attached: ``get_topology_desc`` describes a v5e:2x2 host, and
each kernel is lowered and compiled by the TPU compiler against one of its
devices at the widths the deployments use; the mesh-sharded serving
programs compile against all four. That catches what interpret
mode cannot — block shapes off the (8, 128) tiling, row copies Mosaic
refuses, scalar stores into vector refs, VMEM overuse — at no chip time.
Each compiled program must contain the Mosaic kernel (``tpu_custom_call``).

The kernel switches are passed explicitly (``interpret=False``,
``use_kernel=True``): ``jax.default_backend()`` reads ``cpu`` here, so the
defaults would pick the reference path. The topology is described inside a
module-scoped fixture — never at import — because only one process at a
time may load the TPU library; every pytest-xdist worker then collects the
same tests, and only the worker that runs them loads it.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.shapes import GATHER_BLOCK_S, MAX_TOPK

N_ROWS = 200_000  # the part deployment's row count
N_SCALARS = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for an absent chip is written to the persistent cache but
    # cannot be read back: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices[:4]), ("data",))


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pred(sharding, b, c):
    from repro.vectordb.predicates import PredicateSet

    return PredicateSet(
        active=_spec(sharding, (b, c, N_SCALARS), jnp.bool_),
        lo=_spec(sharding, (b, c, N_SCALARS)),
        hi=_spec(sharding, (b, c, N_SCALARS)),
        clause_valid=_spec(sharding, (b, c), jnp.bool_))


def _rows(sharding, dims, *, int8=False, n=N_ROWS):
    """``GatherRows`` shapes of ``n`` rows: columns of widths ``dims`` and
    their row views, as ``Table.gather_rows`` lays them out."""
    from repro.kernels.gather_score import GatherRows
    from repro.kernels.shapes import round128

    vdt = jnp.int8 if int8 else jnp.float32
    widths = [round128(-(-d // 4) + 1) if int8 else round128(d)
              for d in dims]
    return GatherRows(
        vectors=tuple(_spec(sharding, (n, d), vdt) for d in dims),
        scalars=_spec(sharding, (n, N_SCALARS)),
        scales=tuple(_spec(sharding, (n,)) for _ in dims) if int8 else None,
        views=tuple(_spec(sharding, (n, 1, w),
                          jnp.int32 if int8 else jnp.float32)
                    for w in widths),
        meta=_spec(sharding, (n, 1, 128)))


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dim,n_cols,int8,k", [
    (96, 1, False, 10),
    (96, 2, True, MAX_TOPK),
    (128, 2, False, MAX_TOPK),
    (128, 1, True, 10),
    (768, 2, False, 10),
    (768, 2, True, MAX_TOPK),
])
def test_gather_score_blocks_compiles(one_chip, dim, n_cols, int8, k):
    """The candidate-local gather+score kernel, fp32 and int8, at the
    deployments' widths (part's two 768-d columns among them) with full
    ``GATHER_BLOCK_S`` tiles."""
    from repro.kernels.gather_score import gather_score_blocks

    b, c = 8, 2
    args = (
        _spec(one_chip, (b, 2 * GATHER_BLOCK_S), jnp.int32),
        _rows(one_chip, (dim,) * n_cols, int8=int8),
        tuple(_spec(one_chip, (b, dim)) for _ in range(n_cols)),
        _spec(one_chip, (b, n_cols)),
        _spec(one_chip, (b, c, N_SCALARS)),
        _spec(one_chip, (b, c, N_SCALARS)),
        _spec(one_chip, (b, c, N_SCALARS)),
        _spec(one_chip, (b, c)),
    )
    _assert_kernel(lambda *a: gather_score_blocks(
        *a, k=k, block_s=GATHER_BLOCK_S, metric="l2" if int8 else "dot",
        interpret=False), *args)


def test_beam_search_compiles(one_chip):
    """The graph walk at its widest legalized corner (BEAM_GRID × HOP_GRID
    maxima) over a 200k-row 768-d column: the XLA routing loop plus the
    Pallas extraction over the whole visited pool."""
    from repro.core.query import BEAM_GRID, HOP_GRID
    from repro.kernels.beam_search import beam_search_topk
    from repro.kernels.shapes import GRAPH_ENTRY_POINTS

    b, degree = 8, 16
    args = (
        _spec(one_chip, (N_ROWS, degree), jnp.int32),
        _spec(one_chip, (GRAPH_ENTRY_POINTS,), jnp.int32),
        _rows(one_chip, (768,)),
        _pred(one_chip, b, 2),
        _spec(one_chip, (b, 768)),
    )
    _assert_kernel(lambda *a: beam_search_topk(
        *a, k=10, beam_width=max(BEAM_GRID), n_hops=max(HOP_GRID),
        use_kernel=True, interpret=False), *args)


@pytest.mark.parametrize("int8", [False, True])
def test_full_scan_kernels_compile(one_chip, int8):
    """The full-scan kernels (``masked_topk_blocks``, ``int8_topk_blocks``)
    at 768-d over a SCAN_BLOCK_ROWS-padded table."""
    from repro.kernels.int8_scan import int8_topk_blocks
    from repro.kernels.masked_topk import masked_topk_blocks
    from repro.kernels.shapes import SCAN_BLOCK_ROWS

    n = -(-N_ROWS // SCAN_BLOCK_ROWS) * SCAN_BLOCK_ROWS
    m, d = N_SCALARS, 768
    tail = (_spec(one_chip, (n, m)), _spec(one_chip, (m,)),
            _spec(one_chip, (m,)), _spec(one_chip, (m,), jnp.bool_),
            _spec(one_chip, (), jnp.int32))
    if int8:
        _assert_kernel(lambda *a: int8_topk_blocks(
            *a, k=MAX_TOPK, interpret=False), _spec(one_chip, (d,)),
            _spec(one_chip, (n, d), jnp.int8), _spec(one_chip, (n,)), *tail)
    else:
        _assert_kernel(lambda *a: masked_topk_blocks(
            *a, k=10, metric="l2", interpret=False), _spec(one_chip, (d,)),
            _spec(one_chip, (n, d)), *tail)


def _sharded_program(mesh, name):
    """(fn, args) of one mesh-sharded serving program over part's 200k
    rows, placed as ``bind_shards(4, mesh=...)`` places the table: rows
    split over the mesh's data axis, queries and predicates replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.executor import rrf_union_total
    from repro.serve.batch import _dense_scores
    from repro.vectordb.distributed import (
        sharded_batch_topk, sharded_ivf_topk,
    )

    b, c, d = 8, 2, 768
    rows = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())
    if name == "dense_scores":
        return (lambda v, q: _dense_scores(v, q, metric="dot"),
                (_spec(rows, (N_ROWS, d)), _spec(repl, (b, d))))
    if name == "sharded_batch_topk":
        return (sharded_batch_topk(mesh, k=10),
                (_spec(NamedSharding(mesh, P(None, "data")), (b, N_ROWS)),
                 _spec(rows, (N_ROWS, N_SCALARS)), _pred(repl, b, c)))
    n_shards, n_clusters, shard_len = 4, 16, N_ROWS // 4
    subs = ((0, 20, 32, 4, 4096, True), (1, 20, 32, 4, 4096, True))
    fn = sharded_ivf_topk(n_shards, mesh, subs=subs, k=10, metric="dot",
                          pad_total=rrf_union_total(40))
    per_col = lambda shape, dt=jnp.float32: tuple(
        _spec(rows, (n_shards,) + shape, dt) for _ in subs)
    return fn, (per_col((n_clusters, d)), per_col((shard_len,), jnp.int32),
                per_col((n_clusters + 1,), jnp.int32),
                _rows(rows, (d, d)), _pred(repl, b, c),
                tuple(_spec(repl, (b, d)) for _ in subs),
                _spec(repl, (b, 2)))


@pytest.mark.parametrize("name", ["dense_scores", "sharded_batch_topk",
                                  "sharded_ivf_topk"])
def test_mesh_sharded_programs_compile(four_chips, name, monkeypatch):
    """The ``--chips 4`` serving programs over a 4-device v5e mesh: the
    dense route's row-sharded scores and shard_map merge, and the per-shard
    probing route, whose gather kernel runs on each device's own row block
    under shard_map. The merges must gather across the mesh. The programs
    pick the kernel from the backend, so the backend reads as the chip's."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args = _sharded_program(four_chips, name)
    text = jax.jit(fn).lower(*args).compile().as_text()
    if name != "dense_scores":
        assert "all-gather" in text
    if name == "sharded_ivf_topk":
        assert "tpu_custom_call" in text
