"""Kernel micro-benchmarks: fused kernels vs oracles, dense-vs-local crossover.

On this CPU container the Pallas kernels execute in interpret mode, so the
meaningful numbers are (a) correctness parity with the oracle, (b) the
HBM-byte model (the int8 scan reads 4× fewer DB bytes per query), and
(c) the dense-vs-candidate-local CROSSOVER sweep: one (B, n) GEMM + masked
top-k over ALL rows versus the fused gather+score over only each query's
``scan`` candidate rows (``kernels.gather_score``, executing its off-TPU
reference path — the same code the serving dispatcher runs). The sweep
calibrates ``serve.batch.CostModel.crossover``: candidate-local wins while
``B·scan / n_rows`` stays below the reported measured ratio.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.gather_score import (
    GatherRows, gather_score_topk, gather_score_topk_int8,
)

NEG = -1e30


def _timeit(f, reps=3):
    jax.block_until_ready(f())  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(f())
    return (time.perf_counter() - t0) / reps * 1e3


# sweep points as work ratios B·scan/n — scan widths scale with the table
# so the sweep stays cheap on small benchmark runs and spans the same
# decision space on large ones
SWEEP_RATIOS = (0.07, 0.27, 1.1, 4.4, 17.5)


def crossover_sweep(n: int = 60_000, d: int = 128, b: int = 32, m: int = 3,
                    k: int = 10, scans=None,
                    precision: str = "fp32") -> list[dict]:
    """Dense batched scoring vs candidate-local fused gather+score.

    Dense cost is scan-independent (every row is scored); candidate-local
    scales with ``b·scan``. Each row reports both times, the work ratio
    ``b·scan/n`` and the speedup — the largest ratio with speedup > 1 is
    the measured crossover the ``CostModel`` default should sit under.

    ``precision="int8"`` runs the quantized tier as the candidate-local
    side (int8 gather→score→mask then exact fp32 rerank of the top-α·k) —
    the sweep that calibrates ``CostModel.crossover_int8``. The dense
    baseline stays fp32: there is no dense int8 path."""
    if scans is None:
        scans = tuple(max(64, int(r * n / b)) for r in SWEEP_RATIOS)
    from repro.vectordb.predicates import Predicates, stack

    rng = np.random.default_rng(0)
    vecs = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    scal = jnp.asarray(rng.uniform(0, 10, (n, m)), jnp.float32)
    q_b = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    w_b = jnp.ones((b, 1), jnp.float32)
    pred_b = stack([Predicates.from_conditions(m, {0: (2.0, 8.0)})
                    for _ in range(b)])

    @jax.jit
    def dense(qb, lo, hi):
        ws = qb @ vecs.T  # (b, n) — one GEMM over ALL rows
        ok = jnp.all((scal >= lo) & (scal <= hi)
                     | ~jnp.asarray([True] + [False] * (m - 1)), axis=1)
        masked = jnp.where(ok[None, :], ws, NEG)
        return jax.lax.top_k(masked, k)

    lo = jnp.asarray([2.0] + [-np.inf] * (m - 1), jnp.float32)
    hi = jnp.asarray([8.0] + [np.inf] * (m - 1), jnp.float32)
    ms_dense = _timeit(lambda: dense(q_b, lo, hi))

    table_rows = GatherRows.build((vecs,), scal)
    if precision == "int8":
        v8, sc8 = ops.quantize_rows(vecs)
        rows_i8 = GatherRows.build((v8,), scal, (sc8,), meta=table_rows.meta)

        @jax.jit
        def local_fn(c):
            return gather_score_topk_int8(
                c, table_rows, rows_i8, (q_b,), w_b, pred_b,
                k=k, metric="dot", use_kernel=False)
    else:
        @jax.jit
        def local_fn(c):
            # jitted like the serving paths (gather_score_topk is traceable
            # and always called inside the executor's jitted graphs)
            return gather_score_topk(c, table_rows, (q_b,), w_b, pred_b,
                                     k=k, metric="dot", use_kernel=False)

    rows = []
    for scan in scans:
        cand = jnp.asarray(rng.integers(0, n, size=(b, scan)), jnp.int32)
        ms_local = _timeit(lambda c=cand: local_fn(c))
        ratio = b * scan / n
        rows.append({
            "n_rows": n, "batch": b, "scan": scan, "precision": precision,
            "work_ratio": round(ratio, 3),
            "dense_ms": round(ms_dense, 2),
            "local_ms": round(ms_local, 2),
            "speedup": round(ms_dense / ms_local, 2),
        })
        print(f"  crossover[{precision}] n={n} B={b} scan={scan}: dense "
              f"{ms_dense:.1f}ms vs local {ms_local:.1f}ms -> "
              f"{rows[-1]['speedup']}x (B·scan/n = {ratio:.2f})")
    return rows


def measured_overhead_rows(rows: list[dict], *, scan: int, n_rows: int,
                           crossover: float = 0.136) -> float:
    """``CostModel.overhead`` from an affine fit of the candidate-local
    per-batch times: ``t(B) = OH_ms + slope·B`` (slope = per-gathered-row
    cost × scan). Dividing the fixed intercept by the per-row cost converts
    it to the gathered-row units the decision inequality
    ``B·scan + overhead <= crossover·n`` uses. The fit is then clamped so
    every MEASURED winner keeps winning under the final constants — near
    the boundary the decisions, not the noisy intercept, are the ground
    truth."""
    bs = np.asarray([r["batch"] for r in rows], np.float64)
    ts = np.asarray([r["local_ms"] for r in rows], np.float64)
    slope, oh_ms = np.polyfit(bs, ts, 1)
    per_row_ms = max(slope, 1e-9) / scan
    oh = float(max(0.0, oh_ms) / per_row_ms)
    wins = [crossover * n_rows - r["batch"] * r["scan"]
            for r in rows if r["local_wins"]]
    if wins:
        oh = min(oh, max(0.0, min(wins)))
    return round(oh)


def overhead_sweep(n: int = 500_000, k: int = 10, scan: int = 2048,
                   nprobe: int = 16, k_mult: int = 4,
                   batches=(4, 8, 16, 32), dataset: str = "sift",
                   seed: int = 0, precision: str = "fp32",
                   crossover: float = 0.136) -> dict:
    """Calibrate the candidate-local path's FIXED per-batch overhead
    END-TO-END: drive the real batched executor (fixed legalized plan,
    each scoring path forced) across batch sizes.

    The fixed costs the model must capture — per-query probe slot
    selection, group dispatch, iterative re-expansion host syncs — live in
    the serving path, NOT in the fused kernel alone, so the calibration
    times whole executor batches per batch size, fits the affine
    ``t(B) = OH + slope·B`` and converts the intercept to gathered-row
    units:

        candidate-local wins  iff  B·scan + overhead <= crossover·n

    This is the term that closes the ROADMAP's small-batch mispredict:
    without it ``B·scan`` shrinks with the batch while the fixed cost does
    not, so the model sent every near-boundary tiny batch candidate-local.
    The dense column is measured alongside as the ground truth the
    calibrated decisions are checked against."""
    from repro.bench import datasets, queries
    from repro.core.query import ExecutionPlan, SubqueryParams
    from repro.serve.batch import (
        BatchedHybridExecutor, CANDIDATE_LOCAL, DENSE, CostModel,
    )
    from repro.vectordb import ivf as _ivf

    table = datasets.make(dataset, rows=n, seed=seed)
    n_vec = table.schema.n_vec
    nc = max(64, min(512, table.n_rows // 2000))
    idx = [_ivf.build(v, nc, seed=i, metric=table.schema.metric)
           for i, v in enumerate(table.vectors)]
    plan = ExecutionPlan("index_scan", tuple(
        SubqueryParams(k_mult=k_mult, nprobe=nprobe, max_scan=scan,
                       iterative=True) for _ in range(n_vec)),
        precision=precision)
    rows = []
    for b in batches:
        wl = queries.gen_workload(table, b, n_vec_used=min(2, n_vec),
                                  seed=seed + 100)
        plans = [plan] * len(wl)
        row = {"batch": b, "scan": scan}
        for label, force in (("dense", DENSE), ("local", CANDIDATE_LOCAL)):
            bx = BatchedHybridExecutor(table, idx,
                                       cost_model=CostModel(force=force))
            bx.execute_batch(wl, plans)  # warm the jit caches
            reps = 3
            t0 = time.perf_counter()
            for _ in range(reps):
                bx.execute_batch(wl, plans)
            row[f"{label}_ms"] = round(
                (time.perf_counter() - t0) / reps * 1e3, 1)
        row["local_wins"] = row["local_ms"] < row["dense_ms"]
        rows.append(row)
        print(f"  overhead sweep[{precision}] B={b} scan={scan}: dense "
              f"{row['dense_ms']}ms vs local {row['local_ms']}ms -> "
              f"{'local' if row['local_wins'] else 'dense'}")
    oh = measured_overhead_rows(rows, scan=scan, n_rows=table.n_rows,
                                crossover=crossover)
    print(f"  calibrated CostModel.overhead[{precision}] ≈ {oh:.0f} "
          f"gathered rows")
    return {"n_rows": table.n_rows, "precision": precision, "table": rows,
            "overhead_rows": oh}


def measured_crossover(rows: list[dict]) -> float:
    """Largest measured work ratio at which candidate-local still wins
    (log-interpolated between the last winning and first losing sweep
    point) — the value ``serve.batch.CostModel.crossover`` should sit at."""
    wins = [r for r in rows if r["speedup"] >= 1.0]
    if not wins:
        return 0.0
    hi = max(r["work_ratio"] for r in wins)
    # losses BELOW hi are small-batch overhead artifacts, not the crossover
    losses_above = [r["work_ratio"] for r in rows
                    if r["speedup"] < 1.0 and r["work_ratio"] > hi]
    if not losses_above:
        return hi
    return round(float(np.sqrt(hi * min(losses_above))), 3)


def run(n: int = 20_000, d: int = 128, m: int = 3, k: int = 10, **_) -> dict:
    rng = np.random.default_rng(0)
    vecs = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    scal = jnp.asarray(rng.uniform(0, 10, (n, m)), jnp.float32)
    lo = jnp.asarray([3.0] + [-np.inf] * (m - 1), jnp.float32)
    hi = jnp.asarray([7.0] + [np.inf] * (m - 1), jnp.float32)
    act = jnp.asarray([True] + [False] * (m - 1))
    q = jnp.asarray(rng.normal(size=(d,)), jnp.float32)

    s_k, i_k, v_k = ops.masked_topk(q, vecs, scal, lo, hi, act, k=k)
    s_r, i_r = ref.masked_topk_ref(q, vecs, scal, lo, hi, act, n, k=k)
    parity = bool(np.allclose(np.asarray(s_k), np.asarray(s_r), atol=1e-4)
                  and np.array_equal(np.asarray(v_k), np.asarray(i_r) >= 0))

    qv, sc = ops.quantize_rows(vecs)
    s_q, i_q, _ = ops.int8_masked_topk(q, qv, sc, scal, lo, hi, act, k=k)
    rec = len(set(map(int, np.asarray(i_q))) & set(map(int, np.asarray(i_r)))) / k

    ms_ref = _timeit(lambda: ref.masked_topk_ref(q, vecs, scal, lo, hi, act,
                                                 n, k=k))
    fp32_bytes = n * d * 4
    int8_bytes = n * d * 1 + n * 4
    out = {
        "figure": "kernels_bench",
        "oracle_parity": parity,
        "int8_recall_vs_fp32": rec,
        "ref_scan_ms_cpu": round(ms_ref, 2),
        "db_bytes_fp32": fp32_bytes,
        "db_bytes_int8": int8_bytes,
        "hbm_reduction": round(fp32_bytes / int8_bytes, 2),
    }
    print(f"  kernels: parity={parity} int8_recall={rec:.2f} "
          f"HBM bytes/query {fp32_bytes/2**20:.1f}MiB -> "
          f"{int8_bytes/2**20:.1f}MiB ({out['hbm_reduction']}x)")
    out["crossover"] = crossover_sweep(n=n, d=d, m=m, k=k)
    out["measured_crossover"] = measured_crossover(out["crossover"])
    print(f"  measured crossover B·scan/n = {out['measured_crossover']}")
    return out


def calibrate_quantized(n_cross: int = 60_000, n_over: int = 500_000,
                        out: str = "benchmarks/results/quantized_crossover.json"
                        ) -> dict:
    """Per-precision CostModel calibration (``crossover`` /
    ``crossover_int8``, ``overhead`` / ``overhead_int8``): the 60k-row
    kernel crossover sweep and the 500k-row end-to-end overhead boundary,
    both precisions, written to ``benchmarks/results/``."""
    import json

    res = {"figure": "quantized_cost_model_calibration"}
    for prec in ("fp32", "int8"):
        sweep = crossover_sweep(n=n_cross, precision=prec)
        over = overhead_sweep(n=n_over, precision=prec,
                              crossover=measured_crossover(sweep))
        res[prec] = {
            "crossover_sweep": sweep,
            "measured_crossover": measured_crossover(sweep),
            "overhead_sweep": over,
            "measured_overhead_rows": over["overhead_rows"],
        }
        print(f"  [{prec}] measured crossover B·scan/n = "
              f"{res[prec]['measured_crossover']}, overhead ≈ "
              f"{over['overhead_rows']} gathered rows")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"  wrote {out}")
    return res


if __name__ == "__main__":
    # standalone run = the calibration figures: the 60k-row crossover sweep
    # plus the 500k-row end-to-end per-batch overhead boundary the
    # CostModel defaults are measured on, at BOTH precisions (the int8
    # rows calibrate crossover_int8/overhead_int8) — written to
    # benchmarks/results/quantized_crossover.json. (benchmarks.run keeps
    # its smaller n and skips the overhead sweep — it needs the big table
    # to be meaningful.)
    calibrate_quantized()
