"""Quickstart: build a hybrid table, fit BoomHQ, run optimized MHQs —
including DNF predicates written with the builder algebra.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.bench import datasets, queries
from repro.common.compile_cache import place_compile_cache
from repro.core.boomhq import BoomHQ, BoomHQConfig
from repro.core.data_encoder import DataEncoderConfig
from repro.core.executor import recall_at_k
from repro.core.query import MHQ
from repro.core.rewriter import RewriterConfig
from repro.vectordb import flat
from repro.vectordb.algebra import col


def main():
    place_compile_cache()
    # 1. a table with two vector columns + four scalar columns (TPC-H Part
    #    shape, §4 benchmark construction)
    table = datasets.make("part", rows=4000, seed=0)
    print(f"table: {table.n_rows} rows, {table.schema.n_vec} vector cols, "
          f"{table.schema.n_scalar} scalar cols")

    # 2. a stratified MHQ workload (weighted two-vector queries) — half
    #    conjunctive, half DNF (OR-of-ranges / IN-lists via the builder)
    workload = queries.gen_workload(table, 24, n_vec_used=2, seed=1) + \
        queries.gen_dnf_workload(table, 16, n_vec_used=2, seed=2)

    # 3. fit the learned optimizer (data encoder + self-supervised rewriter)
    bq = BoomHQ(table, BoomHQConfig(
        n_clusters=32,
        encoder=DataEncoderConfig(frozen_steps=40, ae_steps=80, sample=1024),
        rewriter=RewriterConfig(steps=200)))
    metrics = bq.fit(workload[:30])
    print(f"fit done: strategy_acc={metrics['strategy_acc']:.2f} "
          f"({metrics['fit_seconds']:.0f}s)")

    # 4. optimized execution on unseen queries
    for q in workload[30:36]:
        plan = bq.optimize(q)
        ids, scores = bq.execute(q)
        gt, _ = flat.ground_truth(table, list(q.query_vectors),
                                  list(q.weights), q.predicates, q.k)
        print(f"  w={tuple(round(w, 2) for w in q.weights)} "
              f"plan={plan.strategy:12s} recall={recall_at_k(ids, gt):.2f} "
              f"top-id={int(np.asarray(ids)[0])}")

    # 5. hand-written DNF predicate through the builder algebra: mid-range
    #    prices OR a specific brand excluding the smallest sizes. compile()
    #    resolves names against the schema and legalizes the clause count
    #    onto the (1, 2, 4) grid.
    expr = col("price").between(100, 400) | \
        (col("brand") == 3) & ~col("size").below(2.0)
    pred = expr.compile(table.schema)
    q0 = workload[30]
    q = MHQ(query_vectors=q0.query_vectors, weights=q0.weights,
            predicates=pred, k=10)
    ids, _ = bq.execute(q)
    gt, _ = flat.ground_truth(table, list(q.query_vectors), list(q.weights),
                              pred, q.k)
    print(f"  DNF (C={pred.n_clauses}) plan={bq.optimize(q).strategy:12s} "
          f"recall={recall_at_k(ids, gt):.2f}")


if __name__ == "__main__":
    main()
