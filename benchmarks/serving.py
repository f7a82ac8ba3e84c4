"""MHQ serving throughput: batched vs sequential, and async over shards.

Two measurements on one fitted suite:

  * ``run_sync_compare`` — the original figure: the sequential per-query
    loop vs ``ServingEngine`` -> ``BoomHQ.execute_batch`` (one fused
    optimizer dispatch + grouped vmapped execution per batch). Per-query
    results match up to float reduction order, so the recall columns must
    match and the QPS column is pure dispatch/batching win.
  * ``run_async_shards`` — the live-traffic figure: Poisson (open-loop)
    arrivals into the deadline-aware ``AsyncServingEngine``, served over
    1 / 2 / 4 table shards. The single-shard row is the plan-driven batched
    path; multi-shard rows fan every formed batch out across the shards
    (per-shard mask + local top-k on the dense score matrices, one
    O(shards·k) merge). Reports QPS, p50/p99 latency, timed-out count
    (zero at the default deadline) and oracle recall per shard count.

  PYTHONPATH=src python -m benchmarks.serving            # FAST suite
  PYTHONPATH=src python -m benchmarks.serving --smoke    # tiny, seconds

Multi-shard rows use logical shards (identical semantics on one device)
unless ``--mesh`` asks for a device mesh, which then needs that many
devices; on the CPU backend ``--mesh`` forces 4 host devices.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import time

from repro.common.compile_cache import place_compile_cache

DEFAULT_SHARDS = (1, 2, 4)
DEFAULT_DEADLINE = 5.0  # seconds — generous; the report must show 0 timeouts
DEFAULT_RATE = 100.0  # Poisson arrivals per second


def _smoke_sizes():
    from benchmarks import common

    return dict(common.FAST, rows=4000, n_train=16, n_test=8, frozen_steps=25,
                ae_steps=40, rw_steps=100, n_clusters=16)


def _stream_and_gts(suite, n_stream: int, seed: int):
    import numpy as np

    from benchmarks import common
    from repro.bench import queries

    stream = queries.gen_workload(suite.table, n_stream, n_vec_used=2,
                                  seed=seed + 100)
    gts = [np.asarray(common.flat.ground_truth(
        suite.table, list(q.query_vectors), list(q.weights), q.predicates,
        q.k)[0]) for q in stream]
    return stream, gts


def run_sync_compare(suite, stream, gts, *, batch_size: int = 32) -> dict:
    """Sequential per-query loop vs the batched ServingEngine."""
    import numpy as np

    from repro.core.executor import recall_at_k
    from repro.serve.batch import ServingEngine

    bq = suite.bq
    engine = ServingEngine(bq, batch_size=batch_size)
    # steady-state measurement: ONE untimed pass per path populates every
    # jit specialization (a long-running service reuses a bounded kernel
    # cache; cold-compile cost is amortized away in both columns)
    engine.serve(stream)
    for q in stream:
        bq.execute(q)

    seq_recs = []
    t0 = time.perf_counter()
    for q, gt in zip(stream, gts):
        ids, _ = bq.execute(q)
        seq_recs.append(recall_at_k(ids, gt))
    seq_s = time.perf_counter() - t0
    seq_qps = len(stream) / seq_s

    _, rep = engine.serve(stream, gt_ids=gts)
    speedup = rep.qps / seq_qps
    print(f"  serving sync: sequential {seq_qps:.1f} QPS "
          f"(recall {np.mean(seq_recs):.3f}) vs batched {rep.qps:.1f} QPS "
          f"(recall {rep.mean_recall:.3f}) -> {speedup:.2f}x")
    return {
        "sequential_qps": round(seq_qps, 1),
        "sequential_recall": round(float(np.mean(seq_recs)), 3),
        "batched_qps": round(rep.qps, 1),
        "batched_recall": round(rep.mean_recall, 3),
        "batched_speedup": round(speedup, 2),
    }


def _data_mesh(shards: int):
    """A 1-D ("data",) mesh over the first ``shards`` devices; too few
    devices is an error, never a silent fall back to logical shards."""
    import numpy as np

    import jax
    from jax.sharding import Mesh

    if jax.device_count() < shards:
        raise RuntimeError(f"a {shards}-shard mesh needs {shards} devices, "
                           f"found {jax.device_count()}")
    return Mesh(np.array(jax.devices()[:shards]), ("data",))


def run_async_shards(suite, stream, gts, *, batch_size: int = 32,
                     use_mesh: bool = False,
                     shards=DEFAULT_SHARDS, rate: float = DEFAULT_RATE,
                     max_wait: float = 0.01,
                     deadline: float = DEFAULT_DEADLINE, seed: int = 0
                     ) -> list[dict]:
    """Poisson open-loop arrivals into AsyncServingEngine per shard count.
    ``use_mesh`` serves every multi-shard row over a device mesh (an error
    when there are too few devices); otherwise shards are logical."""
    import numpy as np

    from repro.serve.batch import warm_bucket_ladder
    from repro.serve.queue import AsyncServingEngine, serve_stream

    bq = suite.bq
    rng = np.random.default_rng(seed + 7)
    gaps = rng.exponential(1.0 / rate, len(stream) - 1).tolist()
    rows = []
    try:
        for s in shards:
            mesh = None
            if s > 1 and use_mesh:
                mesh = _data_mesh(s)
                bq.bind_shards(s, mesh=mesh)
            elif s > 1:
                bq.bind_shards(s)  # logical shards, same semantics
            else:
                bq.bind_shards()  # plan-driven single-shard baseline
            warm_bucket_ladder(bq.execute_batch, stream, batch_size)
            engine = AsyncServingEngine(bq, batch_size=batch_size,
                                        max_wait=max_wait,
                                        default_timeout=deadline)
            reqs = asyncio.run(serve_stream(engine, stream,
                                            arrival_gaps=gaps))
            rep = engine.report(
                gt_ids={r.seq: gts[i] for i, r in enumerate(reqs)})
            row = {
                "shards": s,
                "mesh": mesh is not None,
                "qps": round(rep.qps, 1),
                "p50_ms": round(rep.p50_ms, 2),
                "p99_ms": round(rep.p99_ms, 2),
                "timed_out": rep.n_timed_out,
                "recall": round(rep.mean_recall, 3),
            }
            rows.append(row)
            print(f"  serving async shards={s}{' (mesh)' if row['mesh'] else ''}: "
                  f"{row['qps']} QPS, p50 {row['p50_ms']}ms, "
                  f"p99 {row['p99_ms']}ms, {row['timed_out']} timed out, "
                  f"recall {row['recall']}")
    finally:
        bq.bind_shards()  # leave the suite single-shard
    return rows


def run_sharded(dataset: str = "sift", rows: int = 500_000, shards: int = 4,
                *, batch_size: int = 32, n_stream: int = 64,
                max_scan: int = 2048, nprobe: int = 16, k_mult: int = 4,
                k: int = 10, seed: int = 0, use_mesh: bool = False) -> dict:
    """Sharded-IVF acceptance sweep: the plan's knobs operative at shard
    scale.

    Apples-to-apples at the plan tier where learned plans put large tables
    (index_scan at the smallest ``MAX_SCAN_GRID`` budget — the same regime
    ``run_crossover`` measures): the SAME legalized plan drives

      * ``1shard`` — the single-device batched executor, i.e. the existing
        single-device results (the 1-shard sharded configuration is
        bit-for-bit this path — tests/test_sharded_ivf.py);
      * ``{S}shard-dense-exact`` — the exact per-shard scan over the dense
        score matrices (the PR 3 fan-out; plans ignored, recall 1.0 by
        construction);
      * ``{S}shard-ivf`` — plan-driven per-shard IVF probing: each shard
        probes its own index with the shard-legalized knobs, reranks
        candidate-locally inside the shard, one O(shards·k) merge, and a
        query whose merged result underfills k takes the exact retry over
        only its underfilled shard-subset (the recall contract).

    The stratified stream deliberately includes the paper's HARD stratum —
    correlated predicates that empty out the probed neighborhoods (this
    repo's v→s scalars are derived from vector geometry), where the exact
    scan is genuinely optimal and the probing path must pay the escalation
    tax to keep its recall contract. The sweep therefore reports the full
    stream AND the probe-served tier (the queries whose probes filled k —
    the tier a fitted optimizer routes here): acceptance is that the
    probing path beats the exact sharded dense scan in QPS on that tier at
    an oracle recall no lower than the single-shard plan-driven path, with
    the full-stream recall also no lower (escalation only adds rows).

    QPS rows use LOGICAL shards by default: this is a single-host
    container, and a forced host-platform mesh splits one physical CPU
    into fake devices — shard_map partitioning overhead without real
    parallelism (measured: it halves every sharded row). The shard_map
    execution path is bit-parity-verified against the logical reference
    in tests/test_sharded_ivf.py and tests/test_distributed.py;
    ``use_mesh=True`` (CLI ``--mesh``) forces the mesh anyway."""
    import numpy as np

    from repro.bench import datasets, queries
    from repro.core.executor import recall_at_k
    from repro.core.query import ExecutionPlan, SubqueryParams
    from repro.serve.batch import (
        SHARDED_LOCAL, BatchedHybridExecutor, CostModel,
    )
    from repro.vectordb import flat, ivf

    table = datasets.make(dataset, rows=rows, seed=seed)
    n_vec = table.schema.n_vec
    nc = max(64, min(512, table.n_rows // 2000))
    t0 = time.time()
    idx = [ivf.build(v, nc, seed=i, metric=table.schema.metric)
           for i, v in enumerate(table.vectors)]
    print(f"  sharded suite built in {time.time() - t0:.0f}s "
          f"({table.n_rows} rows, {nc} clusters)")
    stream = queries.gen_workload(table, n_stream,
                                  n_vec_used=min(2, n_vec), seed=seed + 100)
    gts = [np.asarray(flat.ground_truth(
        table, list(q.query_vectors), list(q.weights), q.predicates,
        q.k)[0]) for q in stream]
    plan = ExecutionPlan("index_scan", tuple(
        SubqueryParams(k_mult=k_mult, nprobe=nprobe, max_scan=max_scan,
                       iterative=True) for _ in range(n_vec)))

    mesh = _data_mesh(shards) if use_mesh else None

    def make_bx(s, cm=None):
        kw = {} if s <= 1 else (
            {"mesh": mesh} if mesh is not None else {"n_shards": s})
        return BatchedHybridExecutor(table, idx, cost_model=cm, **kw)

    def serve(bx, mode, qs, q_gts, esc_out=None):
        plans = [plan] * len(qs)

        def call(sub, ps):
            if mode == "execute_batch":
                return bx.execute_batch(sub, ps)
            if mode == "sharded_no_plans":
                return bx.execute_batch_sharded(sub)
            return bx.execute_batch_sharded(sub, ps)

        call(qs[:batch_size], plans[:batch_size])  # warm the jit caches
        t0 = time.perf_counter()
        results = []
        for i in range(0, len(qs), batch_size):
            bx.escalated.clear()  # batch-relative indices
            results.extend(call(qs[i: i + batch_size],
                                plans[i: i + batch_size]))
            if esc_out is not None:
                esc_out.update(i + j for j in bx.escalated)
        dt = time.perf_counter() - t0
        recs = [recall_at_k(ids, gt)
                for (ids, _), gt in zip(results, q_gts)]
        return {"qps": round(len(qs) / dt, 1),
                "recall": round(float(np.mean(recs)), 3)}

    bx1 = make_bx(1)
    bxd = make_bx(shards)
    bxi = make_bx(shards, CostModel(force=SHARDED_LOCAL))
    rows_out = []
    esc = set()  # filled by the sharded-ivf timed pass itself
    for label, bx, mode in (
            ("1shard", bx1, "execute_batch"),
            (f"{shards}shard-dense-exact", bxd, "sharded_no_plans"),
            (f"{shards}shard-ivf", bxi, "sharded_plans")):
        row = {"config": label, "stream": "full",
               "mesh": bx is not bx1 and mesh is not None,
               **serve(bx, mode, stream, gts,
                       esc_out=esc if bx is bxi else None)}
        rows_out.append(row)
        print(f"  sharded {label}{' (mesh)' if row['mesh'] else ''}: "
              f"{row['qps']} QPS, recall {row['recall']}")
    # escalation segmentation: the probe-served tier re-measured alone
    served = [j for j in range(len(stream)) if j not in esc]
    out_tier = {}
    if served:
        sub = [stream[j] for j in served]
        sub_gts = [gts[j] for j in served]
        for label, bx, mode in (
                ("1shard", bx1, "execute_batch"),
                (f"{shards}shard-dense-exact", bxd, "sharded_no_plans"),
                (f"{shards}shard-ivf", bxi, "sharded_plans")):
            row = {"config": label, "stream": "probe-served",
                   "mesh": bx is not bx1 and mesh is not None,
                   **serve(bx, mode, sub, sub_gts)}
            rows_out.append(row)
            print(f"  probe-served tier {label}: {row['qps']} QPS, "
                  f"recall {row['recall']}")
        by_tier = {r["config"]: r for r in rows_out
                   if r["stream"] == "probe-served"}
        out_tier = {
            "probe_served_queries": len(served),
            "ivf_vs_dense_speedup_probe_served": round(
                by_tier[f"{shards}shard-ivf"]["qps"]
                / by_tier[f"{shards}shard-dense-exact"]["qps"], 2),
            "tier_recall_delta_vs_single": round(
                by_tier[f"{shards}shard-ivf"]["recall"]
                - by_tier["1shard"]["recall"], 4),
        }
    by = {r["config"]: r for r in rows_out if r["stream"] == "full"}
    out = {
        "figure": "serving_sharded_ivf",
        "dataset": dataset, "rows": table.n_rows, "shards": shards,
        "batch_size": batch_size, "n_stream": n_stream,
        "plan": {"strategy": "index_scan", "k_mult": k_mult,
                 "nprobe": nprobe, "max_scan": max_scan},
        "table": rows_out,
        "escalated_queries": len(esc),
        "recall_delta_vs_single": round(
            by[f"{shards}shard-ivf"]["recall"] - by["1shard"]["recall"], 4),
        **out_tier,
    }
    print(f"  acceptance: full-stream recall delta vs 1shard "
          f"{out['recall_delta_vs_single']:+.3f} "
          f"({len(esc)}/{len(stream)} escalated); probe-served tier "
          f"speedup vs exact dense "
          f"{out.get('ivf_vs_dense_speedup_probe_served', 'n/a')}x at "
          f"recall delta {out.get('tier_recall_delta_vs_single', 'n/a')}")
    return out


# dense-vs-candidate-local acceptance sweep: (dataset, rows, batch sizes).
# part = 2×768-dim columns (the multi-vector MHQ shape); sift = 1×128-dim at
# half a million rows (the scale where the dense GEMM becomes the wall).
CROSSOVER_TABLES = (("part", 60_000, (8, 32)), ("sift", 500_000, (8, 32)))


def run_crossover(tables=CROSSOVER_TABLES, *, n_stream: int = 64,
                  max_scan: int = 2048, nprobe: int = 16, k_mult: int = 4,
                  seed: int = 0) -> list[dict]:
    """Dense vs candidate-local batched executor QPS at a fixed plan.

    Both paths run the SAME legalized plan (index_scan, the smallest
    ``MAX_SCAN_GRID`` budget — the regime learned plans put large tables
    in), so they probe identical candidate slots and their oracle recall
    must agree to float ties; the QPS difference is purely the scoring
    path. The executor is driven directly (fixed plans, no optimizer) so
    the table isolates scoring; ``auto_path`` reports what the calibrated
    ``CostModel`` would pick for each group."""
    import numpy as np

    from repro.bench import datasets, queries
    from repro.core.executor import recall_at_k
    from repro.core.query import ExecutionPlan, SubqueryParams
    from repro.serve.batch import (
        BatchedHybridExecutor, CANDIDATE_LOCAL, DENSE, CostModel, next_bucket,
    )
    from repro.vectordb import flat, ivf

    rows_out = []
    for dataset, rows, batch_sizes in tables:
        table = datasets.make(dataset, rows=rows, seed=seed)
        n_vec = table.schema.n_vec
        nc = max(64, min(512, table.n_rows // 2000))
        idx = [ivf.build(v, nc, seed=i, metric=table.schema.metric)
               for i, v in enumerate(table.vectors)]
        stream = queries.gen_workload(table, n_stream,
                                      n_vec_used=min(2, n_vec),
                                      seed=seed + 100)
        gts = [np.asarray(flat.ground_truth(
            table, list(q.query_vectors), list(q.weights), q.predicates,
            q.k)[0]) for q in stream]
        plan = ExecutionPlan("index_scan", tuple(
            SubqueryParams(k_mult=k_mult, nprobe=nprobe, max_scan=max_scan,
                           iterative=True) for _ in range(n_vec)))
        plans = [plan] * len(stream)
        for bs in batch_sizes:
            row = {"dataset": dataset, "rows": table.n_rows, "batch": bs,
                   "max_scan": max_scan}
            scan_budget = max_scan * len([w for w in stream[0].weights
                                          if w > 0])
            row["auto_path"] = CostModel().choose(
                batch=next_bucket(bs), scan=scan_budget, n_rows=table.n_rows)
            for label, force in (("dense", DENSE),
                                 ("local", CANDIDATE_LOCAL)):
                bx = BatchedHybridExecutor(
                    table, idx, cost_model=CostModel(force=force))
                bx.execute_batch(stream[:bs], plans[:bs])  # warm jit
                t0 = time.perf_counter()
                results = []
                for s in range(0, len(stream), bs):
                    results.extend(
                        bx.execute_batch(stream[s: s + bs],
                                         plans[s: s + bs]))
                dt = time.perf_counter() - t0
                row[f"{label}_qps"] = round(len(stream) / dt, 1)
                row[f"{label}_recall"] = round(float(np.mean(
                    [recall_at_k(ids, gt)
                     for (ids, _), gt in zip(results, gts)])), 3)
            row["speedup"] = round(row["local_qps"] / row["dense_qps"], 2)
            row["recall_delta"] = round(
                abs(row["local_recall"] - row["dense_recall"]), 4)
            rows_out.append(row)
            print(f"  crossover {dataset} rows={row['rows']} B={bs}: "
                  f"dense {row['dense_qps']} QPS (recall "
                  f"{row['dense_recall']}) vs candidate-local "
                  f"{row['local_qps']} QPS (recall {row['local_recall']}) "
                  f"-> {row['speedup']}x, auto={row['auto_path']}")
    return rows_out


def run_quantized(tables=CROSSOVER_TABLES, *, n_stream: int = 64,
                  max_scan: int = 2048, nprobe: int = 16, k_mult: int = 4,
                  seed: int = 0) -> list[dict]:
    """int8-then-rerank vs fp32 candidate-local QPS at a fixed plan.

    Both paths are the SAME candidate-local executor on the SAME legalized
    plan — identical probed slots, identical predicate filtering on exact
    scalars — differing ONLY in ``ExecutionPlan.precision``: fp32 scores
    the gathered candidates exactly; int8 scores them from the quantized
    replica and exact-reranks the top-α·k (docs/quantized_tier.md). The
    acceptance claim is the int8 column's QPS win at an oracle recall
    delta within 0.01: quantization only perturbs WHICH near-boundary
    candidates reach the exact rerank, never the returned scores.
    ``auto_path`` columns report what the calibrated per-precision
    ``CostModel`` crossover picks for each configuration."""
    import numpy as np

    from repro.bench import datasets, queries
    from repro.core.executor import recall_at_k
    from repro.core.query import ExecutionPlan, SubqueryParams
    from repro.serve.batch import (
        BatchedHybridExecutor, CANDIDATE_LOCAL, CostModel, next_bucket,
    )
    from repro.vectordb import flat, ivf

    rows_out = []
    for dataset, rows, batch_sizes in tables:
        table = datasets.make(dataset, rows=rows, seed=seed)
        n_vec = table.schema.n_vec
        nc = max(64, min(512, table.n_rows // 2000))
        idx = [ivf.build(v, nc, seed=i, metric=table.schema.metric)
               for i, v in enumerate(table.vectors)]
        stream = queries.gen_workload(table, n_stream,
                                      n_vec_used=min(2, n_vec),
                                      seed=seed + 100)
        gts = [np.asarray(flat.ground_truth(
            table, list(q.query_vectors), list(q.weights), q.predicates,
            q.k)[0]) for q in stream]
        for bs in batch_sizes:
            row = {"dataset": dataset, "rows": table.n_rows, "batch": bs,
                   "max_scan": max_scan}
            scan_budget = max_scan * len([w for w in stream[0].weights
                                          if w > 0])
            for prec in ("fp32", "int8"):
                plan = ExecutionPlan("index_scan", tuple(
                    SubqueryParams(k_mult=k_mult, nprobe=nprobe,
                                   max_scan=max_scan, iterative=True)
                    for _ in range(n_vec)), precision=prec)
                plans = [plan] * len(stream)
                row[f"auto_path_{prec}"] = CostModel().choose(
                    batch=next_bucket(bs), scan=scan_budget,
                    n_rows=table.n_rows, precision=prec)
                bx = BatchedHybridExecutor(
                    table, idx,
                    cost_model=CostModel(force=CANDIDATE_LOCAL))
                bx.execute_batch(stream[:bs], plans[:bs])  # warm jit
                t0 = time.perf_counter()
                results = []
                for s in range(0, len(stream), bs):
                    results.extend(
                        bx.execute_batch(stream[s: s + bs],
                                         plans[s: s + bs]))
                dt = time.perf_counter() - t0
                row[f"{prec}_qps"] = round(len(stream) / dt, 1)
                row[f"{prec}_recall"] = round(float(np.mean(
                    [recall_at_k(ids, gt)
                     for (ids, _), gt in zip(results, gts)])), 3)
            row["int8_speedup"] = round(
                row["int8_qps"] / row["fp32_qps"], 2)
            row["recall_delta"] = round(
                row["fp32_recall"] - row["int8_recall"], 4)
            rows_out.append(row)
            print(f"  quantized {dataset} rows={row['rows']} B={bs}: "
                  f"fp32-local {row['fp32_qps']} QPS (recall "
                  f"{row['fp32_recall']}) vs int8-then-rerank "
                  f"{row['int8_qps']} QPS (recall {row['int8_recall']}) "
                  f"-> {row['int8_speedup']}x, recall delta "
                  f"{row['recall_delta']:+.4f}, auto int8="
                  f"{row['auto_path_int8']}")
    return rows_out


def run_semcache(*, rows: int = 4000, n_unique: int = 16, n_trace: int = 80,
                 tenants: int = 3, k: int = 10, n_insert: int = 48,
                 eps_fuzzy: float = 1e-3, seed: int = 0) -> dict:
    """Semantic-cache acceptance sweep (docs/semantic_cache.md).

    One fitted suite over 'part' with a categorical tenant column and
    namespaces bound, then a repeated-query trace (every unique query once,
    then random repeats) served sequentially through ``AsyncServingEngine``
    twice — without and with a ``SemanticCache(eps=0)``. The acceptance
    claims the JSON must carry:

      * ``speedup`` >= 2x: repeats resolve at submit time, zero scan cost;
      * ``miss_recall_delta`` == 0.0: misses run the identical execution
        path, so their oracle recall matches the uncached run exactly;
      * ``replay_parity_mismatches`` == 0: every hit returns the SAME
        ``(ids, scores)`` bits the uncached run computed for that position;
      * ``epoch_swap.stale_hits`` == 0: after insert+compact bumps the
        ``(epoch, n_rows)`` token, no pre-swap entry is ever served
        (``stale_drops`` > 0 shows the flush actually happened);
      * per-tenant accounting from ``ServeReport.tenants``.

    A fuzzy pass (``eps=eps_fuzzy``, repeats perturbed within eps) shows
    the semantic — not just exact — hit predicate."""
    import dataclasses

    import numpy as np

    from repro.bench import datasets, queries
    from repro.core.boomhq import BoomHQ, BoomHQConfig
    from repro.core.executor import recall_at_k
    from repro.core.rewriter import RewriterConfig
    from repro.serve.queue import AsyncServingEngine
    from repro.serve.semcache import SemanticCache
    from repro.vectordb import flat
    from repro.vectordb.table import ScalarCol, Table

    rng = np.random.default_rng(seed + 11)
    base = datasets.make("part", rows=rows, seed=seed)
    tcol = rng.integers(0, tenants, base.n_rows).astype(np.float32)
    schema = dataclasses.replace(
        base.schema,
        scalar_cols=tuple(base.schema.scalar_cols)
        + (ScalarCol("tenant", "cat", tenants),))
    table = Table.from_numpy(
        schema, [np.asarray(v) for v in base.vectors],
        np.concatenate([np.asarray(base.scalars), tcol[:, None]], axis=1))
    t0 = time.time()
    bq = BoomHQ(table, BoomHQConfig(
        n_clusters=16, use_de=False,
        rewriter=RewriterConfig(steps=20, refine_columns=False)))
    bq.fit(queries.gen_workload(table, 12, n_vec_used=2, k=k, seed=seed))
    bq.bind_tenants("tenant")
    print(f"  semcache suite fitted in {time.time() - t0:.0f}s "
          f"({table.n_rows} rows, {tenants} tenants)")

    pool = [dataclasses.replace(q, tenant_id=i % tenants)
            for i, q in enumerate(queries.gen_workload(
                table, n_unique, n_vec_used=2, k=k, seed=seed + 100))]
    # oracle GT over the tenant-FOLDED predicate (what the engine serves)
    gts = [np.asarray(flat.ground_truth(
        table, list(q.query_vectors), list(q.weights),
        bq.resolve_tenant(q).predicates, q.k)[0]) for q in pool]
    # every unique query once, then random repeats — repeats always arrive
    # after their original completed (sequential awaits), so they CAN hit
    trace = list(range(n_unique)) + list(
        rng.integers(0, n_unique, n_trace - n_unique))

    async def serve_seq(eng, qs):
        async with eng:
            t0 = time.perf_counter()
            reqs = [await eng.submit(q) for q in qs]
            dt = time.perf_counter() - t0
        return reqs, dt

    def engine(cache=None):
        return AsyncServingEngine(bq, batch_size=8, max_wait=0.002,
                                  semcache=cache)

    # warm pass populates the jit specializations both timed passes reuse
    asyncio.run(serve_seq(engine(), pool))

    reqs_base, dt_base = asyncio.run(
        serve_seq(engine(), [pool[i] for i in trace]))
    cache = SemanticCache(eps=0.0)
    eng_c = engine(cache)
    reqs_c, dt_c = asyncio.run(
        serve_seq(eng_c, [pool[i] for i in trace]))

    hits = [r.cache_hit for r in reqs_c]
    base_recs = [recall_at_k(np.asarray(r.result[0]), gts[trace[i]])
                 for i, r in enumerate(reqs_base)]
    miss_deltas, parity_bad = [], 0
    for i, r in enumerate(reqs_c):
        rec = recall_at_k(np.asarray(r.result[0]), gts[trace[i]])
        if r.cache_hit:
            b = reqs_base[i].result
            if not (np.array_equal(np.asarray(r.result[0]),
                                   np.asarray(b[0])[: pool[trace[i]].k])
                    and np.array_equal(np.asarray(r.result[1]),
                                       np.asarray(b[1])[: pool[trace[i]].k])):
                parity_bad += 1
        else:
            miss_deltas.append(rec - base_recs[i])
    rep = eng_c.report(gt_ids={r.seq: gts[trace[i]]
                               for i, r in enumerate(reqs_c)})

    # semantic (within-eps) repeats: perturb every repeat inside eps_fuzzy
    fuzz = []
    for j, i in enumerate(trace):
        q = pool[i]
        if j < n_unique:
            fuzz.append(q)
            continue
        delta = eps_fuzzy / 4.0
        fuzz.append(dataclasses.replace(q, query_vectors=tuple(
            np.asarray(v, np.float32)
            + (delta / np.sqrt(v.shape[-1])).astype(np.float32)
            for v in q.query_vectors)))
    reqs_f, _ = asyncio.run(
        serve_seq(engine(SemanticCache(eps=eps_fuzzy)), fuzz))
    fuzz_hits = sum(r.cache_hit for r in reqs_f)
    fuzz_rec = float(np.mean([
        recall_at_k(np.asarray(r.result[0]), gts[trace[j]])
        for j, r in enumerate(reqs_f)]))

    # epoch-swap oracle: populate -> insert+compact -> re-serve. Token bump
    # must flush every pre-swap entry; zero stale results served.
    bq.bind_tiered(hot_capacity=max(n_insert, 8))
    try:
        swap_cache = SemanticCache(eps=0.0)
        eng_s = engine(swap_cache)

        async def swap_phase():
            async with eng_s:
                first = [await eng_s.submit(q) for q in pool]
                warm = [await eng_s.submit(q) for q in pool]
                extra = datasets.make("part", rows=n_insert, seed=seed + 31)
                scal = np.concatenate(
                    [np.asarray(extra.scalars),
                     rng.integers(0, tenants, n_insert)
                        .astype(np.float32)[:, None]], axis=1)
                bq.tiered.insert([np.asarray(v) for v in extra.vectors],
                                 scal)
                bq.tiered.compact()  # epoch e -> e+1
                after = [await eng_s.submit(q) for q in pool]
                return first, warm, after

        _, warm, after = asyncio.run(swap_phase())
        stale_hits = 0
        for r in after:
            if r.cache_hit:
                ids, _ = bq.execute(r.query)
                if not np.array_equal(np.asarray(r.result[0]),
                                      np.asarray(ids)[: r.query.k]):
                    stale_hits += 1
        swap = {
            "pre_swap_hits": sum(r.cache_hit for r in warm),
            "post_swap_hits": sum(r.cache_hit for r in after),
            "stale_drops": swap_cache.stats()["stale_drops"],
            "stale_hits": stale_hits,
            "epoch": bq.tiered.epoch,
        }
    finally:
        bq.unbind_tiered()

    out = {
        "figure": "serving_semantic_cache",
        "rows": table.n_rows, "tenants": tenants,
        "n_unique": n_unique, "n_trace": n_trace, "k": k,
        "qps_nocache": round(len(trace) / dt_base, 1),
        "qps_cache": round(len(trace) / dt_c, 1),
        "speedup": round(dt_base / dt_c, 2),
        "hit_rate": round(sum(hits) / len(hits), 3),
        "n_cache_hits": rep.n_cache_hits,
        "mean_recall_cached_run": round(rep.mean_recall, 3),
        "mean_recall_uncached_run": round(float(np.mean(base_recs)), 3),
        "miss_recall_delta": round(
            float(np.mean(miss_deltas)) if miss_deltas else 0.0, 4),
        "replay_parity_mismatches": parity_bad,
        "fuzzy_eps": eps_fuzzy,
        "fuzzy_hit_rate": round(fuzz_hits / len(reqs_f), 3),
        "fuzzy_mean_recall": round(fuzz_rec, 3),
        "epoch_swap": swap,
        "per_tenant": rep.tenants,
    }
    print(f"  semcache: {out['qps_nocache']} QPS uncached vs "
          f"{out['qps_cache']} QPS cached -> {out['speedup']}x at hit rate "
          f"{out['hit_rate']}; miss recall delta {out['miss_recall_delta']}, "
          f"{parity_bad} parity mismatches; epoch swap: "
          f"{swap['post_swap_hits']} post-swap hits, "
          f"{swap['stale_drops']} stale drops, {swap['stale_hits']} stale "
          f"served; fuzzy(eps={eps_fuzzy}) hit rate {out['fuzzy_hit_rate']} "
          f"recall {out['fuzzy_mean_recall']}")
    return out


def run_graph(*, rows: int = 100_000, n_hard: int = 48, batch_size: int = 16,
              degree: int = 16, metric: str = "l2", k: int = 10,
              seed: int = 0) -> dict:
    """Graph-strategy acceptance on the correlated hard stratum
    (docs/graph_index.md).

    The stratum is built on the sift v→s table, whose ``cluster_id``
    scalar IS the k-means cluster of the vector: an equality predicate
    selects one geometric region, and placing the query near a row of a
    DIFFERENT cluster makes every IVF probe land on disqualified rows —
    the regime PR 5 showed escalating to the exact-scan fallback. Four
    measured rows:

      * ``graph`` — the new third strategy (beam 16 × 8 hops), recall +
        QPS + mean visited rows (its scan budget);
      * ``ivf_probe`` — IVF at a scan budget ≥ the graph's (nprobe
        rounded up, ``max_scan`` at the grid floor, 4×+ the graph's
        visited count): recall collapses, which is WHY this stratum
        escalates;
      * ``exact_full`` — the exact-scan fallback as the serving pipeline
        dispatches it (dense GEMM over all rows, recall 1.0 by
        construction);
      * ``exact_matched`` — the same fallback budgeted down to the
        graph's oracle recall (smallest ``max_candidates`` whose measured
        recall ≥ the graph's, timed on BOTH scoring paths and reported at
        the better of the two) — the matched-recall baseline the
        acceptance compares against.

    The acceptance claims: ``graph`` QPS > both exact rows' QPS at oracle
    recall ≥ the matched row's, and ``ivf_probe`` recall far below both.

    Two further sections feed the planner: (1) ``cost_model`` fits the
    ``CostModel.graph_row_cost`` / ``overhead_graph`` constants from the
    measured timings — the row unit is anchored on the dense exact scan
    (``crossover · n_rows`` units ↔ its measured per-batch wall time), so
    the graph-vs-exact crossover the constants encode reproduces the
    wall-clock ordering; (2) ``mixed_batch`` scans the fitted three-way
    cost surface (``choose_strategy``) over legal knob/batch shapes for a
    regime where each strategy wins, then executes ONE
    ``execute_batch`` over a stream carrying all three plan strategies
    and reports the per-group scoring-path decisions."""
    import numpy as np

    import jax.numpy as jnp

    from repro.bench import datasets
    from repro.core.executor import HybridExecutor, recall_at_k
    from repro.core.query import (
        BEAM_GRID, HOP_GRID, MAX_SCAN_GRID, MHQ, ExecutionPlan,
        SubqueryParams,
    )
    from repro.serve.batch import (
        CANDIDATE_LOCAL, BatchedHybridExecutor, CostModel,
    )
    from repro.vectordb import flat, graph, ivf
    from repro.vectordb.predicates import Predicates

    table = datasets.make("sift", rows=rows, seed=seed, metric=metric)
    n = table.n_rows
    nc = max(32, min(256, n // 2000))
    # the offline build is O(n^2) (~20 min at 100k on CPU): cache the
    # adjacency keyed by everything that determines it
    cache = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".graph_cache",
                         f"sift_{rows}_{degree}_{metric}_{seed}.npz")
    t0 = time.time()
    if os.path.exists(cache):
        z = np.load(cache)
        g = graph.GraphIndex(
            neighbors=jnp.asarray(z["neighbors"]),
            entry_points=jnp.asarray(z["entry_points"]), metric=metric)
        build_s = float(z["build_s"])
    else:
        g = graph.build(table.vectors[0], degree, metric=metric)
        build_s = time.time() - t0
        os.makedirs(os.path.dirname(cache), exist_ok=True)
        np.savez(cache, neighbors=np.asarray(g.neighbors),
                 entry_points=np.asarray(g.entry_points), build_s=build_s)
    iv = ivf.build(table.vectors[0], n_clusters=nc, metric=metric)
    print(f"  graph suite built in {time.time() - t0:.0f}s "
          f"({n} rows, degree {degree}, {nc} IVF clusters)")

    # -- the correlated hard stratum ------------------------------------
    clu = np.asarray(table.scalars)[:, 0].astype(int)
    counts = np.bincount(clu)
    good = [c for c in range(counts.shape[0]) if counts[c] >= 2 * k]
    rng = np.random.default_rng(seed + 5)
    vecs = np.asarray(table.vectors[0])
    hard = []
    for _ in range(n_hard):
        c = int(rng.choice(good))
        r = int(rng.choice(np.where(clu != c)[0]))
        qv = (vecs[r] + rng.normal(0, 0.02, vecs.shape[1])).astype(np.float32)
        pred = Predicates.from_conditions(
            table.scalars.shape[1], {0: (float(c), float(c))})
        hard.append(MHQ(query_vectors=(jnp.asarray(qv),), weights=(1.0,),
                        predicates=pred, k=k))
    gts = [np.asarray(flat.ground_truth(
        table, list(q.query_vectors), list(q.weights), q.predicates,
        q.k)[0]) for q in hard]

    hx = HybridExecutor(table, [iv], graphs=[g])
    subs = (SubqueryParams(k_mult=8, nprobe=8, max_scan=MAX_SCAN_GRID[0],
                           iterative=False),)

    def timed(bx, plan, qs=hard, q_gts=gts, bs=batch_size):
        plans = [plan] * len(qs)
        bx.execute_batch(qs[:bs], plans[:bs])  # warm jit
        t0 = time.perf_counter()
        res = []
        for s in range(0, len(qs), bs):
            res.extend(bx.execute_batch(qs[s: s + bs], plans[s: s + bs]))
        dt = time.perf_counter() - t0
        rec = float(np.mean([recall_at_k(ids, gt)
                             for (ids, _), gt in zip(res, q_gts)]))
        return round(rec, 3), round(len(qs) / dt, 1), dt / (len(qs) / bs)

    def visited(bw, nh, m=16):
        nv = []
        for q in hard[:m]:
            _, _, nvis, _ = graph.search(
                g, table.gather_rows((0,)), q.predicates,
                q.query_vectors[0], beam_width=bw, n_hops=nh, k=k)
            nv.append(int(nvis))
        return int(np.mean(nv))

    bx = BatchedHybridExecutor(table, [iv], graphs=[g])
    bxl = BatchedHybridExecutor(table, [iv], graphs=[g],
                                cost_model=CostModel(force=CANDIDATE_LOCAL))
    rows_out = []

    plan_g = hx.legalize(ExecutionPlan("graph", subs, beam_width=16,
                                       n_hops=8))
    v_big = visited(16, 8)
    g_rec, g_qps, t_g_big = timed(bx, plan_g)
    rows_out.append({"config": "graph", "recall": g_rec, "qps": g_qps,
                     "scan_rows": v_big,
                     "beam_width": 16, "n_hops": 8})
    print(f"  graph bw16 h8: recall {g_rec} at {g_qps} QPS "
          f"(visits ~{v_big} rows)")

    npb = max(2, -(-v_big // (n // nc)))
    plan_i = hx.legalize(ExecutionPlan("index_scan", (
        SubqueryParams(k_mult=8, nprobe=npb, max_scan=MAX_SCAN_GRID[0],
                       iterative=False),)))
    i_rec, i_qps, t_ix = timed(bxl, plan_i)
    rows_out.append({"config": "ivf_probe", "recall": i_rec, "qps": i_qps,
                     "scan_rows": MAX_SCAN_GRID[0], "nprobe": npb})
    print(f"  ivf nprobe={npb} max_scan={MAX_SCAN_GRID[0]}: recall {i_rec} "
          f"at {i_qps} QPS (budget {MAX_SCAN_GRID[0] / max(v_big, 1):.1f}x "
          f"the graph's)")

    plan_e = hx.legalize(ExecutionPlan("filter_first", subs))
    e_rec, e_qps, t_dense = timed(bx, plan_e)
    rows_out.append({"config": "exact_full", "recall": e_rec, "qps": e_qps,
                     "scan_rows": n})
    print(f"  exact full scan: recall {e_rec} at {e_qps} QPS")

    # smallest exact-scan budget whose recall matches the graph's; timed
    # on both scoring paths, reported at the better (generous baseline)
    matched = None
    for mc in (256, 512, 1024, 2048, 4096, 8192):
        pm = hx.legalize(ExecutionPlan("filter_first", subs,
                                       max_candidates=mc))
        m_rec, m_qps_l, _ = timed(bxl, pm)
        if m_rec >= g_rec:
            _, m_qps_d, _ = timed(bx, pm)
            matched = {"config": "exact_matched", "recall": m_rec,
                       "qps": max(m_qps_l, m_qps_d),
                       "scan_rows": mc,
                       "qps_local": m_qps_l, "qps_dense": m_qps_d}
            break
    if matched is None:  # graph recall above every truncated budget
        matched = {"config": "exact_matched", "recall": e_rec, "qps": e_qps,
                   "scan_rows": n}
    rows_out.append(matched)
    print(f"  exact matched-recall (mc={matched['scan_rows']}): recall "
          f"{matched['recall']} at {matched['qps']} QPS")

    # -- fit the CostModel graph constants ------------------------------
    # unit anchor: the dense exact scan's measured per-batch time is
    # crossover·n_rows units by definition of the strategy crossover, so
    # the fitted (graph_row_cost, overhead_graph) reproduce the measured
    # graph-vs-exact wall-clock ordering at serving shapes.
    cm0 = CostModel()
    unit_s = t_dense / (cm0.crossover * n)
    plan_g2 = hx.legalize(ExecutionPlan("graph", subs, beam_width=4,
                                        n_hops=2))
    v_small = visited(4, 2)
    _, _, t_g_small = timed(bx, plan_g2)
    u_big, u_small = t_g_big / unit_s, t_g_small / unit_s
    c_fit = max(0.05, (u_big - u_small)
                / max(1, batch_size * (v_big - v_small)))
    oh_fit = max(0.0, u_big - batch_size * v_big * c_fit)
    c_fit, oh_fit = round(c_fit, 3), round(oh_fit, 1)
    cost = {"graph_row_cost": c_fit, "overhead_graph": oh_fit,
            "unit_us": round(unit_s * 1e6, 3),
            "visited": {"bw16_h8": v_big, "bw4_h2": v_small},
            "batch_s": {"graph_bw16_h8": round(t_g_big, 4),
                        "graph_bw4_h2": round(t_g_small, 4),
                        "exact_dense": round(t_dense, 4),
                        "ivf_local": round(t_ix, 4)}}
    print(f"  cost fit: graph_row_cost {c_fit}, overhead_graph {oh_fit} "
          f"(dense-anchored unit {cost['unit_us']}us)")

    # -- three-way dispatch in one mixed batch --------------------------
    cm = CostModel(graph_row_cost=c_fit, overhead_graph=oh_fit)
    regimes = {}
    for b in (1, 2, 4, 8, 16, 32, 64, 128):
        for bw in BEAM_GRID:
            for nh in HOP_GRID:
                gs = max(1, int(v_big * (bw * nh) / (16 * 8)))
                for ms in MAX_SCAN_GRID:
                    s = cm.choose_strategy(batch=b, graph_scan=gs,
                                           probe_scan=min(ms, n), n_rows=n)
                    regimes.setdefault(s, {
                        "batch": b, "beam_width": bw, "n_hops": nh,
                        "graph_scan": gs, "probe_scan": min(ms, n)})
    print(f"  three-way regimes found: {sorted(regimes)}")

    mixed_plans = {
        "graph": plan_g,
        "index_scan": plan_i,
        "exact": plan_e,
    }
    stream, plans = [], []
    rng2 = np.random.default_rng(seed + 9)
    for i, q in enumerate(hard[:3 * (len(hard) // 3)]):
        strat = ("graph", "index_scan", "exact")[i % 3]
        stream.append(q)
        plans.append(mixed_plans[strat])
    order = rng2.permutation(len(stream))
    stream = [stream[i] for i in order]
    plans = [plans[i] for i in order]
    bx.dispatcher.take()  # drop warm-up decisions
    res = bx.execute_batch(stream, plans)
    counts, decisions = bx.dispatcher.take()
    keys = sorted({bx._group_key(q, hx.legalize(p))[0]
                   for q, p in zip(stream, plans)})
    mixed = {"batch": len(stream),
             "strategies": sorted({p.strategy for p in plans}),
             "group_kinds": keys,
             "scoring_paths": counts,
             "regimes": regimes,
             "all_three_in_one_batch": keys == ["ff", "gr", "ix"],
             "results": len(res)}
    print(f"  mixed batch of {len(stream)}: groups {keys}, scoring paths "
          f"{counts}")

    out = {
        "figure": "graph_index_hard_stratum",
        "dataset": "sift", "rows": n, "metric": metric, "degree": degree,
        "n_hard": n_hard, "batch_size": batch_size, "k": k,
        "build_s": round(build_s, 1),
        "table": rows_out,
        "cost_model": cost,
        "mixed_batch": mixed,
        "graph_vs_exact_full_speedup": round(g_qps / e_qps, 2),
        "graph_vs_exact_matched_speedup": round(
            g_qps / matched["qps"], 2),
        "graph_recall_minus_matched": round(g_rec - matched["recall"], 4),
    }
    print(f"  acceptance: graph {out['graph_vs_exact_full_speedup']}x vs "
          f"full exact, {out['graph_vs_exact_matched_speedup']}x vs "
          f"matched-recall exact (recall delta "
          f"{out['graph_recall_minus_matched']:+.3f}); ivf recall {i_rec} "
          f"vs graph {g_rec}")
    return out


def run(sizes=None, dataset: str = "part", *, n_stream: int = 64,
        batch_size: int = 32, seed: int = 0, shards=DEFAULT_SHARDS,
        rate: float = DEFAULT_RATE, deadline: float = DEFAULT_DEADLINE,
        use_mesh: bool = False) -> dict:
    from benchmarks import common

    sizes = common.FAST if sizes is None else sizes
    suite = common.build_suite(dataset, n_vec_used=2, seed=seed, sizes=sizes)
    stream, gts = _stream_and_gts(suite, n_stream, seed)
    out = {
        "figure": "serving_batched_and_async_sharded",
        "dataset": dataset, "rows": suite.table.n_rows,
        "n_stream": n_stream, "batch_size": batch_size,
        "poisson_rate": rate, "deadline_s": deadline,
    }
    out.update(run_sync_compare(suite, stream, gts, batch_size=batch_size))
    out["async_shards"] = run_async_shards(
        suite, stream, gts, batch_size=batch_size, shards=shards, rate=rate,
        deadline=deadline, seed=seed, use_mesh=use_mesh)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default=None,
                    help="default: part (suite) / sift (--sharded)")
    ap.add_argument("--n-stream", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--rate", type=float, default=DEFAULT_RATE,
                    help="Poisson arrival rate (requests/s)")
    ap.add_argument("--deadline", type=float, default=DEFAULT_DEADLINE,
                    help="per-request deadline (s)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny table for a seconds-long sanity run")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--crossover", action="store_true",
                    help="dense vs candidate-local acceptance sweep "
                         "(60k and 500k-row tables) instead of the suite")
    ap.add_argument("--quantized", action="store_true",
                    help="int8-then-rerank vs fp32 candidate-local "
                         "acceptance sweep (60k and 500k-row tables) "
                         "instead of the suite")
    ap.add_argument("--semcache", action="store_true",
                    help="semantic-cache acceptance sweep (repeated-query "
                         "trace, epoch-swap staleness oracle, per-tenant "
                         "accounting) instead of the suite")
    ap.add_argument("--sharded", action="store_true",
                    help="sharded-IVF acceptance sweep (500k rows, 4 "
                         "shards: learned per-shard probing vs exact "
                         "sharded scan vs single-device) instead of the "
                         "suite")
    ap.add_argument("--graph", action="store_true",
                    help="graph-strategy acceptance on the correlated "
                         "hard stratum (graph vs IVF-probe vs exact-scan "
                         "fallback, CostModel constant fit, three-way "
                         "mixed-batch dispatch) instead of the suite")
    ap.add_argument("--rows", type=int, default=500_000,
                    help="table rows for --sharded / --graph (--graph "
                         "caps at 100k: the offline build is O(n^2))")
    ap.add_argument("--shards", type=int, default=4,
                    help="shard count for --sharded")
    ap.add_argument("--mesh", action="store_true",
                    help="serve the multi-shard rows over a device mesh; "
                         "on the CPU backend (JAX_PLATFORMS=cpu) host "
                         "devices are forced for it (default: logical "
                         "shards — a fake mesh on one physical CPU measures "
                         "the partitioner, not the algorithm)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    place_compile_cache()

    if args.crossover:
        res = {"figure": "serving_scoring_crossover",
               "table": run_crossover(n_stream=args.n_stream)}
        if args.out:
            with open(args.out, "w") as f:
                json.dump(res, f, indent=2)
        return

    if args.graph:
        res = run_graph(rows=min(args.rows, 100_000))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(res, f, indent=2)
        return

    if args.semcache:
        res = run_semcache()
        if args.out:
            with open(args.out, "w") as f:
                json.dump(res, f, indent=2)
        return

    if args.quantized:
        res = {"figure": "serving_quantized_tier",
               "table": run_quantized(n_stream=args.n_stream)}
        if args.out:
            with open(args.out, "w") as f:
                json.dump(res, f, indent=2)
        return

    # on the CPU backend, --mesh forces a 4-device host platform BEFORE the
    # backend initializes so the 2/4-shard rows run under shard_map on a
    # mesh (imports below are lazy for exactly this reason). An accelerator
    # backend brings its own devices and is never given host devices.
    if args.mesh and os.environ.get("JAX_PLATFORMS", "").split(",")[0] \
            == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{max(max(DEFAULT_SHARDS), args.shards)}").strip()

    if args.sharded:
        res = run_sharded(args.dataset or "sift", rows=args.rows,
                          shards=args.shards, batch_size=args.batch_size,
                          n_stream=args.n_stream, use_mesh=args.mesh)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(res, f, indent=2)
        return

    from benchmarks import common

    sizes = _smoke_sizes() if args.smoke \
        else (common.FULL if args.full else common.FAST)
    res = run(sizes, args.dataset or "part", n_stream=args.n_stream,
              batch_size=args.batch_size, rate=args.rate,
              deadline=args.deadline, use_mesh=args.mesh)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=2)


if __name__ == "__main__":
    main()
