"""Level-2 checks: trace the real serving kernels and inspect jaxpr/HLO.

AST rules see what the source *says*; this pass checks what the compiler
actually *builds*. A tiny synthetic table (256 rows) is pushed through the
serving kernels — the candidate-local gather+score path, the batched
filter-first and IVF probes, and both sharded top-k merges — and each
jaxpr/HLO is walked for:

* **CM001** — host callbacks (``pure_callback``/``io_callback``/
  ``debug_callback``: a device->host round-trip per call), collectives
  beyond the O(shards·k) merge contract (at most ``max_all_gathers``
  all-gathers per kernel, nothing else), and host-transfer instructions in
  the compiled HLO (``launch.hlo_analysis.host_transfers``).
* **PL001** — the Pallas VMEM envelope: the tile estimators in
  ``kernels/shapes.py`` (the same constants the kernels launch with),
  evaluated at the declared support envelope, must fit the budget.

Shapes here are deliberately minuscule — the checks are structural
(primitive counts), not performance measurements.
"""
from __future__ import annotations

from repro.analysis.config import LintConfig
from repro.analysis.findings import Finding

CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                  "callback"}
COLLECTIVE_PRIMS = {"all_gather", "all_gather_invariant", "psum", "pmax",
                    "pmin", "all_to_all", "ppermute", "reduce_scatter",
                    "psum_scatter", "pgather"}


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            yield from _iter_sub(v)


def _iter_sub(v):
    if hasattr(v, "eqns"):  # Jaxpr
        yield from _iter_eqns(v)
    elif hasattr(v, "jaxpr") and hasattr(getattr(v, "jaxpr"), "eqns"):
        yield from _iter_eqns(v.jaxpr)  # ClosedJaxpr
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from _iter_sub(item)


def prim_counts(jaxpr) -> dict:
    counts: dict = {}
    for eqn in _iter_eqns(jaxpr):
        name = eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
    return counts


def _check_jaxpr(findings, label, path, counts, cfg: LintConfig,
                 *, allow_gathers: int | None = None):
    gathers = allow_gathers if allow_gathers is not None \
        else cfg.max_all_gathers
    for prim in sorted(set(counts) & CALLBACK_PRIMS):
        findings.append(Finding(
            "CM001", path, 1,
            f"{label}: jaxpr contains host callback `{prim}` "
            f"(×{counts[prim]}) — a device->host round-trip inside the "
            f"kernel", context=f"trace:{label}:callback:{prim}"))
    n_ag = counts.get("all_gather", 0) + counts.get("all_gather_invariant", 0)
    if n_ag > gathers:
        findings.append(Finding(
            "CM001", path, 1,
            f"{label}: {n_ag} all-gathers in the traced kernel — the merge "
            f"contract is at most {gathers} (scores + ids, O(shards·k))",
            context=f"trace:{label}:all_gather"))
    others = sorted((set(counts) & COLLECTIVE_PRIMS)
                    - {"all_gather", "all_gather_invariant"})
    for prim in others:
        findings.append(Finding(
            "CM001", path, 1,
            f"{label}: unexpected collective `{prim}` (×{counts[prim]}) — "
            f"serving kernels communicate only through the O(shards·k) "
            f"candidate merge", context=f"trace:{label}:{prim}"))


def check_vmem_envelope(cfg: LintConfig) -> list:
    """PL001 at the declared kernel envelope (kernels/shapes.py)."""
    from repro.kernels import shapes

    budget = cfg.budget()
    findings: list = []
    envelope = [
        ("masked_topk", "src/repro/kernels/masked_topk.py",
         shapes.scan_tile_bytes(shapes.MAX_COL_DIM, shapes.MAX_SCALARS)),
        ("int8_scan", "src/repro/kernels/int8_scan.py",
         shapes.int8_scan_tile_bytes(shapes.MAX_COL_DIM,
                                     shapes.MAX_SCALARS)),
        ("gather_score", "src/repro/kernels/gather_score.py",
         shapes.gather_tile_bytes(
             (shapes.MAX_COL_DIM,) * shapes.MAX_VEC_COLS,
             shapes.MAX_SCALARS, 4)),
        ("int8_gather_score", "src/repro/kernels/gather_score.py",
         shapes.int8_gather_tile_bytes(
             (shapes.MAX_COL_DIM,) * shapes.MAX_VEC_COLS,
             shapes.MAX_SCALARS, 4)),
        ("beam_search", "src/repro/kernels/beam_search.py",
         shapes.beam_tile_bytes(shapes.MAX_COL_DIM, shapes.MAX_SCALARS, 4)),
    ]
    for label, path, est in envelope:
        if est > budget:
            findings.append(Finding(
                "PL001", path, 1,
                f"{label}: VMEM estimate at the declared envelope is "
                f"{est / 2**20:.1f} MiB > budget {budget / 2**20:.0f} MiB "
                f"— shrink the tile constants in kernels/shapes.py or "
                f"raise the budget deliberately",
                context=f"trace:vmem:{label}"))
    return findings


def _fixture():
    import jax.numpy as jnp
    import numpy as np

    from repro.vectordb.predicates import Predicates, stack

    rng = np.random.default_rng(0)
    n, d, m, b = 256, 16, 4, 4
    vectors = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    scalars = jnp.asarray(rng.uniform(size=(n, m)), jnp.float32)
    q_b = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    pred_b = stack([Predicates.from_conditions(m, {0: (0.2, 0.9)})
                    for _ in range(b)])
    w_b = jnp.ones((b, 1), jnp.float32)
    return vectors, scalars, q_b, pred_b, w_b


def run_trace_checks(cfg: LintConfig) -> list:
    findings = check_vmem_envelope(cfg)
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh
    except Exception:  # pragma: no cover - jax-less checkout
        return findings

    from repro.kernels.gather_score import GatherRows, gather_score_topk
    from repro.launch import hlo_analysis
    from repro.vectordb import flat, ivf
    from repro.vectordb.distributed import (
        build_sharded_ivf, sharded_batch_topk, sharded_ivf_topk,
    )

    vectors, scalars, q_b, pred_b, w_b = _fixture()
    rows = GatherRows.build((vectors,), scalars)
    k = 8

    # gather_score: reference path (the off-TPU executor scoring path) and
    # the Pallas kernel body (interpret mode traces the same kernel jaxpr)
    cand = jnp.tile(jnp.arange(64, dtype=jnp.int32)[None], (q_b.shape[0], 1))
    for label, use_kernel in (("gather_score_ref", False),
                              ("gather_score_kernel", True)):
        jaxpr = jax.make_jaxpr(
            lambda c, r, q, w, p: gather_score_topk(
                c, r, (q,), w, p, k=k, use_kernel=use_kernel,
                interpret=True))(cand, rows, q_b, w_b, pred_b)
        _check_jaxpr(findings, label, "src/repro/kernels/gather_score.py",
                     prim_counts(jaxpr.jaxpr), cfg, allow_gathers=0)

    # batched filter-first (candidate-local, no dense matrix)
    jaxpr = jax.make_jaxpr(
        lambda r, p, q, w: flat.filter_first_local_batch(
            r, p, (q,), w, k=k, max_candidates=64, n_vec=1))(
        rows, pred_b, q_b, w_b)
    _check_jaxpr(findings, "filter_first_local_batch",
                 "src/repro/vectordb/flat.py", prim_counts(jaxpr.jaxpr),
                 cfg, allow_gathers=0)

    # plan-driven IVF probing (single-index batched path)
    index = ivf.build(vectors, 8, seed=0)
    jaxpr = jax.make_jaxpr(
        lambda r, p, q: ivf.search_local_batch(
            index, r, p, q, nprobe=2, max_scan=64, k=k))(
        rows, pred_b, q_b)
    _check_jaxpr(findings, "search_local_batch",
                 "src/repro/vectordb/ivf.py", prim_counts(jaxpr.jaxpr),
                 cfg, allow_gathers=0)

    # sharded exact merge under shard_map: the all-gather budget is the
    # whole point — 2 gathers (scores + ids) of O(shards·k), nothing else
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    fn = sharded_batch_topk(mesh, ("data",), k=k)
    w_scores = jnp.einsum("nd,qd->qn", vectors, q_b)
    jaxpr = jax.make_jaxpr(fn)(w_scores, scalars, pred_b)
    counts = prim_counts(jaxpr.jaxpr)
    _check_jaxpr(findings, "sharded_batch_topk",
                 "src/repro/vectordb/distributed.py", counts, cfg)
    if counts.get("all_gather", 0) == 0:  # the merge must actually exist
        findings.append(Finding(
            "CM001", "src/repro/vectordb/distributed.py", 1,
            "sharded_batch_topk: expected the O(shards·k) candidate merge "
            "(2 all-gathers) in the shard_map body, found none — the merge "
            "contract changed", context="trace:sharded_batch_topk:missing"))

    # compiled HLO of the same kernel: no device->host transfers allowed
    hlo = jax.jit(fn).lower(w_scores, scalars, pred_b).compile().as_text()
    report = hlo_analysis.comm_report(hlo,
                                      max_all_gathers=cfg.max_all_gathers)
    if report["host"]["count"] > 0:
        findings.append(Finding(
            "CM001", "src/repro/vectordb/distributed.py", 1,
            f"sharded_batch_topk: compiled HLO contains "
            f"{report['host']['count']} device<->host transfer(s): "
            f"{report['host']['ops']}",
            context="trace:sharded_batch_topk:host_transfer"))

    # plan-driven per-shard IVF probing, logical-shard path (vmap): must be
    # collective- and callback-free
    sivf = build_sharded_ivf(vectors, 2, n_clusters=8)
    sfn = sharded_ivf_topk(2, None, subs=((0, 8, 16, 2, 64, True),), k=k,
                           metric="dot", pad_total=64)
    jaxpr = jax.make_jaxpr(
        lambda c, r, o, g, p, q, w: sfn((c,), (r,), (o,), g, p, (q,), w))(
        sivf.centroids, sivf.sorted_rows, sivf.offsets, rows, pred_b, q_b,
        w_b)
    _check_jaxpr(findings, "sharded_ivf_topk",
                 "src/repro/vectordb/distributed.py",
                 prim_counts(jaxpr.jaxpr), cfg, allow_gathers=0)
    return findings
