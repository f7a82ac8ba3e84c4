"""Single source of truth for kernel tile/block shapes and sentinels.

Every Pallas kernel in this package *and* the boomlint PL001 VMEM
estimator (``repro.analysis``) read these constants, so the static
analyzer can never disagree with what the kernels actually launch. If a
tile shape changes here, the estimator budget check moves with it; if a
kernel grows a new scratch buffer, add it to the matching ``*_tile_bytes``
function in the same commit.

The byte estimators model resident VMEM per grid step: the row/candidate
tile plus the operands whose index_map pins them to block 0 (query,
predicate bounds). They deliberately ignore compiler-managed double
buffering — the budget (``DEFAULT_VMEM_BUDGET``) leaves headroom for it.
"""
from __future__ import annotations

# Score sentinel for masked-out / padded rows and the id sentinel used by
# the k-round knockout select (any value > max row count works; 2**30
# keeps int32 arithmetic safe).
NEG = -1e30
ID_SENTINEL = 2**30

# Row tile for the full-scan kernels (masked_topk, int8_scan). 1024 rows ×
# 768 dims × 4 B ≈ 3.2 MB resident — comfortable inside 16 MB VMEM with
# dims aligned to the 128-lane MXU.
SCAN_BLOCK_ROWS = 1024

# Candidate tile for the gather+score kernel (gather_score). 256 gathered
# rows per step bounds the per-column scratch to block_s·d·4 B.
GATHER_BLOCK_S = 256

# Declared support envelope — the largest shapes the serving kernels are
# expected to launch with. The PL001 trace-level check evaluates the
# estimators at this envelope against the budget.
MAX_COL_DIM = 768  # widest single vector column
MAX_VEC_COLS = 4  # most vector columns per table
MAX_SCALARS = 16  # most scalar predicate columns
MAX_TOPK = 128  # largest static k a kernel is launched with

# Graph-index beam search envelope (kernels/beam_search.py): the largest
# legalized knobs a plan may launch with. The per-hop expansion working
# set is beam_width·degree gathered rows; the visited-candidate pool the
# final gather+score extraction runs over is
# (GRAPH_ENTRY_POINTS + GRAPH_SEED_FACTOR·beam_width) +
# n_hops·beam_width·degree slots — the walk is seeded with the global
# entries PLUS GRAPH_SEED_FACTOR·beam_width predicate-qualifying rows per
# query (hashed-id spread over the qualifying set).
MAX_BEAM_WIDTH = 16  # widest legalized beam (BEAM_GRID max)
MAX_BEAM_HOPS = 8  # most legalized hops (HOP_GRID max)
MAX_GRAPH_DEGREE = 32  # largest graph out-degree (DEGREE_GRID max)
GRAPH_ENTRY_POINTS = 8  # static entry-point count (medoid + strided)
GRAPH_SEED_FACTOR = 4  # qualifying seed rows per beam slot

# Conservative per-step budget: 16 MB physical VMEM minus headroom for
# Mosaic double buffering and spills.
DEFAULT_VMEM_BUDGET = 12 * 2**20

_F32 = 4


def scan_tile_bytes(dim: int, n_scalars: int, *, k: int = MAX_TOPK,
                    block_rows: int = SCAN_BLOCK_ROWS) -> int:
    """Resident bytes per grid step of ``masked_topk_blocks``:
    (block_rows, dim) f32 vector tile + (block_rows, n_scalars) f32 scalar
    tile + pinned query/lo/hi/active rows + (1, k) output pools."""
    tile = block_rows * (dim + n_scalars) * _F32
    pinned = (dim + 3 * n_scalars + 1) * _F32
    out = 2 * k * _F32
    return tile + pinned + out


def int8_scan_tile_bytes(dim: int, n_scalars: int, *, k: int = MAX_TOPK,
                         block_rows: int = SCAN_BLOCK_ROWS) -> int:
    """Like ``scan_tile_bytes`` but the vector tile is int8 with a per-row
    f32 dequant scale column."""
    tile = block_rows * (dim + (1 + n_scalars) * _F32)
    pinned = (dim + 3 * n_scalars + 1) * _F32
    out = 2 * k * _F32
    return tile + pinned + out


def round128(x: int) -> int:
    """``x`` rounded up to whole 128-lane vregs."""
    return -(-x // 128) * 128


def gather_tile_bytes(dims: tuple, n_scalars: int, n_clauses: int, *,
                      k: int = MAX_TOPK,
                      block_s: int = GATHER_BLOCK_S) -> int:
    """Resident bytes per grid step of ``gather_score_blocks``: the
    (block_s, 1, W_i) scratch of gathered rows of each column's row view
    (padded to 128 lanes) and of the 128-lane scalars view, the
    (block_s, 128) id column + pinned per-query operands and output rows.
    The scalars ride in their 128-lane view, so ``n_scalars`` adds nothing
    while it fits."""
    w = sum(round128(d) for d in dims) + 128
    scratch = block_s * (w + 128) * _F32
    pinned = (w + 3 * n_clauses * 128 + n_clauses) * _F32
    out = 2 * round128(k) * _F32
    return scratch + pinned + out


def beam_tile_bytes(dim: int, n_scalars: int, n_clauses: int = 4, *,
                    k: int = MAX_TOPK,
                    beam_width: int = MAX_BEAM_WIDTH,
                    n_hops: int = MAX_BEAM_HOPS,
                    degree: int = MAX_GRAPH_DEGREE,
                    block_s: int = GATHER_BLOCK_S) -> int:
    """Resident bytes per query of the graph beam search
    (``kernels.beam_search``): the max of the XLA routing loop's per-hop
    working set and the final Pallas extraction's per-grid-step tile.

    Per hop the routing loop gathers ``beam_width·degree`` neighbor rows
    ((expand, dim) f32 vectors + (expand, n_scalars) f32 scalars + id /
    score / qual lanes) and merges them into the
    (entry + expand)-slot frontier pool (ids, scores, qual, expanded).
    The visited bitmask is table-sized HBM state (n/32 B), never tiled
    into VMEM, so it is deliberately outside this estimate. Result
    extraction is one ``gather_score`` launch over the accumulated
    visited-candidate pool, so its tile is exactly
    ``gather_tile_bytes((dim,), ...)``."""
    expand = beam_width * degree
    hop = expand * (dim + n_scalars + 3) * _F32
    pool = (GRAPH_ENTRY_POINTS + GRAPH_SEED_FACTOR * beam_width
            + expand) * 4 * _F32
    extract = gather_tile_bytes((dim,), n_scalars, n_clauses,
                                k=k, block_s=block_s)
    return max(hop + pool, extract)


def int8_gather_tile_bytes(dims: tuple, n_scalars: int, n_clauses: int, *,
                           k: int = MAX_TOPK,
                           block_s: int = GATHER_BLOCK_S) -> int:
    """``gather_tile_bytes`` for the quantized tier: each column's row is
    d/4 i32 words (four packed int8 lanes) and the row's dequant scale,
    padded to 128 lanes."""
    return gather_tile_bytes(tuple(-(-d // 4) + 1 for d in dims), n_scalars,
                             n_clauses, k=k, block_s=block_s)
