"""End-to-end driver: a hybrid-query SERVICE with batched + async requests.

Simulates the deployment the paper targets: a fitted BoomHQ instance serving
a stream of mixed MHQ requests (different weights, predicates, k and recall
targets) through the batched ``ServingEngine`` — one fused optimizer
dispatch + grouped vmapped execution per batch instead of a host sync per
query — with running QPS/recall accounting and a mid-stream data insert
(the paper's update scenario). The first batch is also served through the
old per-query loop so the dispatch win is visible.

Later stages switch to LIVE traffic: the table is sharded
(``bind_shards``) and a Poisson request stream flows through the async
deadline-aware engine — requests queue, batches cut when full or when the
oldest request ages out, each batch fans out across the shards, and every
request resolves with an ok/timed-out disposition plus its latency.

The final stage is STREAMING INGEST (``bind_tiered``,
docs/tiered_ingest.md): inserts land in a bounded writable hot segment in
front of the sealed cold IVF state, queries merge both tiers under one
epoch-swapped snapshot, and a background compaction folds hot rows cold
mid-stream with zero serving pauses.

  PYTHONPATH=src python examples/hybrid_serving.py
"""
import asyncio
import time

import numpy as np

from repro.bench import datasets, queries
from repro.common.compile_cache import place_compile_cache
from repro.core.boomhq import BoomHQ, BoomHQConfig
from repro.core.data_encoder import DataEncoderConfig
from repro.core.executor import recall_at_k
from repro.core.rewriter import RewriterConfig
from repro.serve.batch import ServingEngine, warm_bucket_ladder
from repro.serve.queue import AsyncServingEngine, serve_stream
from repro.vectordb import flat


def ground_truths(table, reqs):
    return [np.asarray(flat.ground_truth(table, list(q.query_vectors),
                                         list(q.weights), q.predicates,
                                         q.k)[0]) for q in reqs]


def main():
    place_compile_cache()
    table = datasets.make("aka_title", rows=6000, seed=0)
    train = queries.gen_workload(table, 40, n_vec_used=2, seed=1)
    bq = BoomHQ(table, BoomHQConfig(
        n_clusters=32,
        encoder=DataEncoderConfig(frozen_steps=40, ae_steps=80, sample=2048),
        rewriter=RewriterConfig(steps=250)))
    bq.fit(train)
    engine = ServingEngine(bq, batch_size=24)
    print("service ready")

    stream = queries.gen_workload(table, 48, n_vec_used=2, seed=2)
    engine.warmup(stream)

    # sequential reference on the first batch (the pre-batching hot path);
    # warm its jit specializations untimed so both columns are steady-state
    reqs = stream[:24]
    gts = ground_truths(bq.table, reqs)
    for q in reqs:
        bq.execute(q)
    recs, t0 = [], time.perf_counter()
    for q, gt in zip(reqs, gts):
        ids, _ = bq.execute(q)
        recs.append(recall_at_k(ids, gt))
    dt = time.perf_counter() - t0
    print(f"  [sequential] {len(reqs)} requests in {dt:.2f}s "
          f"({len(reqs)/dt:.1f} QPS), mean recall {np.mean(recs):.3f}")

    _, rep = engine.serve(reqs, gt_ids=gts)
    print(f"  [batch-1]    {rep.describe()}")

    # live data insert (buffered update + incremental encoder fine-tune)
    rng = np.random.default_rng(3)
    n_new = 600
    vecs = [np.asarray(v[:n_new]) + 0.05 * rng.normal(
        size=(n_new, v.shape[1])).astype(np.float32) for v in table.vectors]
    scal = np.asarray(table.scalars[:n_new])
    bq.insert(vecs, scal, finetune=True)
    print(f"inserted {n_new} rows -> {bq.table.n_rows} total")

    reqs2 = stream[24:]
    gts2 = ground_truths(bq.table, reqs2)
    _, rep2 = engine.serve(reqs2, gt_ids=gts2)
    print(f"  [batch-2 (post-insert)] {rep2.describe()}")

    # -- the scoring-dispatch knob ----------------------------------------
    # Each execution group picks its scoring path per batch: DENSE (one
    # GEMM over all rows per vector column) or CANDIDATE_LOCAL (fused
    # gather+score over only the plan's candidate budget). The default
    # CostModel routes a group candidate-local when
    # batch·scan <= crossover·n_rows (crossover calibrated by
    # `python -m benchmarks.serving --crossover`); `bind_cost_model`
    # overrides it — move the threshold, or pin every group to one path.
    # ServeReport.path_counts / describe() show what served the traffic.
    from repro.serve.batch import CANDIDATE_LOCAL, DENSE, CostModel
    bq.bind_cost_model(CostModel(force=CANDIDATE_LOCAL))
    _, rep_local = engine.serve(reqs2, gt_ids=gts2)
    print(f"  [candidate-local forced] {rep_local.describe()}")
    bq.bind_cost_model()  # restore the calibrated crossover

    # -- live traffic: async deadline-aware serving over a sharded table --
    # Deadline-critical serving pins the EXACT sharded scan: one kernel
    # shape per (clause bucket, k) keeps mid-stream jit compiles out of
    # the latency budget. (The default cost model would plan each batch
    # and route per group — richer, but its plan-keyed group shapes can
    # cold-compile mid-stream; the learned sharded route is demonstrated
    # on the batch engine below, where no deadline is at stake.)
    n_shards = 3  # 6600 post-insert rows -> three 2200-row shards
    assert bq.table.n_rows % n_shards == 0
    bq.bind_shards(n_shards).bind_cost_model(CostModel(force=DENSE))
    live = queries.gen_workload(bq.table, 36, n_vec_used=2, seed=5)
    warm_bucket_ladder(bq.execute_batch, live, batch_size=12)
    rng = np.random.default_rng(6)
    gaps = rng.exponential(1.0 / 150.0, len(live) - 1).tolist()  # Poisson
    aeng = AsyncServingEngine(bq, batch_size=12, max_wait=0.02,
                              default_timeout=2.0)
    reqs = asyncio.run(serve_stream(aeng, live, arrival_gaps=gaps))
    gts = {r.seq: g for r, g in zip(reqs, ground_truths(bq.table, live))}
    rep3 = aeng.report(gt_ids=gts)
    print(f"  [async, {n_shards} shards] {rep3.describe()}")
    assert rep3.n_timed_out == 0, "deadline budget was generous"

    # -- the sharded-IVF LEARNED path -------------------------------------
    # With shards bound, index-strategy groups are cost-model routed three
    # ways: plan-driven per-shard IVF probing (each shard probes its OWN
    # index with the learned plan's shard-legalized nprobe/max_scan and
    # reranks candidate-locally inside the shard — the learned knobs stay
    # operative at the scale where the dense GEMM becomes the wall), the
    # exact per-shard dense scan, or single-device when shards are too
    # small to amortize the O(shards·k) merge. This table IS that small,
    # so the default model routes single-device; forcing SHARDED_LOCAL
    # demonstrates the probing fan-out (per-shard underfill escalation
    # keeps the recall contract). ServeReport.path_counts shows the route.
    from repro.serve.batch import SHARDED_LOCAL
    gt_live = ground_truths(bq.table, live)
    bq.bind_cost_model(CostModel(force=SHARDED_LOCAL))
    seng = ServingEngine(bq, batch_size=12)
    seng.warmup(live)
    _, rep4 = seng.serve(live, gt_ids=gt_live)
    print(f"  [sharded-IVF learned, {n_shards} shards] {rep4.describe()}")
    assert rep4.path_counts and "sharded_local" in rep4.path_counts
    bq.bind_cost_model()  # restore the calibrated three-way routing

    # -- streaming ingest: the tiered hot/cold table ----------------------
    # The inserts above were the legacy EAGER path: every insert regrouped
    # the indexes and rebuilt the executor before returning. bind_tiered
    # switches to the LSM-style tiered table (docs/tiered_ingest.md):
    # inserts append to a bounded writable hot segment — visible to the
    # very next batch, scored exactly, candidate-locally — and a full
    # segment is folded into the cold IVF state by a BACKGROUND compaction
    # that publishes via an epoch-swapped snapshot. Serving never pauses:
    # every batch executes against the immutable snapshot stamped on it at
    # cut time, so an epoch swap mid-flight cannot mix row-id spaces.
    bq.bind_shards(1).bind_cost_model()
    bq.bind_tiered(hot_capacity=512)
    rng = np.random.default_rng(9)
    n_live = 700  # > hot capacity: forces a mid-stream background compaction
    lvecs = [np.asarray(v[:n_live]) + 0.05 * rng.normal(
        size=(n_live, v.shape[1])).astype(np.float32)
        for v in bq.table.vectors]
    lscal = np.asarray(bq.table.scalars[:n_live])

    async def ingest_while_serving():
        eng = AsyncServingEngine(bq, batch_size=12, max_wait=0.02)
        async with eng:
            tasks = [asyncio.ensure_future(eng.submit(q)) for q in live]
            # mid-stream: fills the hot segment; the engine's
            # CompactionScheduler folds it cold on its own worker thread
            await asyncio.get_running_loop().run_in_executor(
                None, bq.insert, lvecs, lscal)
            await asyncio.gather(*tasks)
        return eng

    eng5 = asyncio.run(ingest_while_serving())
    rep5 = eng5.report()
    print(f"  [tiered streaming ingest] {rep5.describe()}")
    assert rep5.n_compactions >= 1 and rep5.n_timed_out == 0
    snap = bq.tiered.snapshot()
    print(f"  epoch {snap.epoch}: {snap.cold.table.n_rows} cold + "
          f"{snap.n_hot} hot rows, "
          f"encoder staleness {bq.tiered.encoder_staleness():.3f}")
    bq.unbind_tiered()  # folds any remaining hot rows, back to build-once


if __name__ == "__main__":
    main()
