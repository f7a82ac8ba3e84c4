"""BoomHQ façade: the full learned optimizer wired end-to-end (paper Fig. 2).

  fit():      build per-column IVF indexes + histograms, train the
              correlation-aware data encoder, generate self-supervised plan
              labels over the training workload, train the rewriter heads.
  optimize(): query encoder -> X_in -> predicted ExecutionPlan.
  execute():  optimize + run on the bound engine personality.
  insert():   buffer-style data updates — extend indexes/histograms and
              incrementally fine-tune the data encoder (paper §3.2, §5.3).

Ablation switches (use_de / use_stats / use_gse / use_lnp) zero out the
corresponding X_in feature groups — BoomHQ w.o. DE / QE-Stats / QE-GSE /
QE-LNP in the paper's §5.5 naming.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.common.spans import span
from repro.core.data_encoder import DataEncoder, DataEncoderConfig
from repro.core.executor import EngineCaps, HybridExecutor, PGVECTOR
from repro.core.query import ExecutionPlan, MHQ, SubqueryParams, default_plan
from repro.core.query_encoder import QueryEncoder
from repro.core.rewriter import MHQRewriter, RewriterConfig, generate_label
from repro.vectordb import flat, graph, histogram, ivf
from repro.vectordb.table import Table


def _n_valid(ids) -> int:
    return int(np.sum(np.asarray(ids) >= 0))


@dataclasses.dataclass(frozen=True)
class BoomHQConfig:
    n_clusters: int = 64
    hist_bins: int = 64
    # per-column proximity graphs (the third "graph" strategy —
    # vectordb.graph): fixed out-degree of the sealed Vamana-style graph;
    # 0 disables the tier (plans legalize graph -> index_scan)
    graph_degree: int = 16
    encoder: DataEncoderConfig = dataclasses.field(default_factory=DataEncoderConfig)
    rewriter: RewriterConfig = dataclasses.field(default_factory=RewriterConfig)
    # ablations (§5.5)
    use_de: bool = True
    use_stats: bool = True
    use_gse: bool = True
    use_lnp: bool = True


class BoomHQ:
    def __init__(self, table: Table, cfg: BoomHQConfig = BoomHQConfig(),
                 engine: EngineCaps = PGVECTOR):
        self.table = table
        self.cfg = cfg
        self.engine = engine
        self.indexes = [
            ivf.build(v, min(cfg.n_clusters, max(2, table.n_rows // 8)),
                      seed=i, metric=table.schema.metric)
            for i, v in enumerate(table.vectors)
        ]
        self.hists = histogram.build(table.scalars, cfg.hist_bins)
        self.graphs = None
        if cfg.graph_degree:
            self.graphs = tuple(
                graph.build(v, cfg.graph_degree, metric=table.schema.metric)
                for v in table.vectors)
        self.executor = HybridExecutor(table, self.indexes, engine,
                                       graphs=self.graphs)
        self.data_encoder: Optional[DataEncoder] = None
        if cfg.use_de:
            self.data_encoder = DataEncoder(
                [v.shape[1] for v in table.vectors], table.schema.n_scalar,
                cfg.encoder)
        self.qenc: Optional[QueryEncoder] = None
        self.rewriter: Optional[MHQRewriter] = None
        self._fitted = False
        self.n_shards = 1  # cross-shard serving config (bind_shards)
        self.shard_mesh = None
        self._placed = None  # (source table, its mesh-placed copy)
        self.cost_model = None  # scoring-dispatch override (bind_cost_model)
        self.tiered = None  # streaming-ingest config (bind_tiered)
        self.tenant_col = None  # namespace column index (bind_tenants)
        self._compactor = None  # background scheduler (serve attaches one)
        self._tiered_finetune = True
        # recent served queries, retained so compaction can pre-warm the
        # post-swap jit shapes with REAL traffic before the epoch publish
        self._recent: deque = deque(maxlen=64)
        self._last_batch = 1
        # batched execution: queries run, and underfill escalation (queries
        # retried, retry passes, retries that returned more valid rows)
        self.counts = {"queries_executed": 0, "escalated": 0,
                       "escalation_passes": 0, "escalation_improved": 0}

    # -- offline -------------------------------------------------------------

    def fit(self, workload: list[MHQ], *, verbose: bool = False) -> dict:
        metrics = {}
        t0 = time.perf_counter()
        if self.data_encoder is not None:
            metrics.update(self.data_encoder.fit(self.table))
        self.qenc = QueryEncoder(self.table, self.indexes, self.hists,
                                 self.data_encoder)
        feats, labels = [], []
        for qi, q in enumerate(workload):
            gt_ids, _ = flat.ground_truth(
                self.table, list(q.query_vectors), list(q.weights),
                q.predicates, q.k)
            x = self._features(q)
            lab = generate_label(self.executor, q, gt_ids,
                                 refine_columns=self.cfg.rewriter.refine_columns)
            feats.append(x)
            labels.append(lab)
            if verbose and (qi + 1) % 50 == 0:
                print(f"  labeled {qi + 1}/{len(workload)} queries")
        X = np.stack(feats)
        n_vec = workload[0].n_vec
        self.rewriter = MHQRewriter(X.shape[1], n_vec, self.cfg.rewriter)
        metrics.update(self.rewriter.fit(X, labels))
        metrics["fit_seconds"] = time.perf_counter() - t0
        self._fitted = True
        return metrics

    def _features(self, q: MHQ) -> np.ndarray:
        """X_in for one query, via a single fused jitted pipeline (the
        unfused per-feature path in QueryEncoder.encode is kept for tests
        and ablations of individual probes)."""
        if getattr(self, "_fused_x", None) is None:
            self._fused_x = self._build_fused_features()
        de = self.data_encoder
        de_args = (de.params, de.edges) if (self.cfg.use_de and de is not None) \
            else (None, None)
        x = self._fused_x(
            de_args, self.qenc._edges, self.hists,
            tuple(self.indexes), tuple(self.table.vectors), self.table.scalars,
            tuple(q.query_vectors), q.predicates,
            jnp.asarray(q.weights, jnp.float32),
            jnp.asarray(float(np.log(q.k)), jnp.float32),
            jnp.asarray(q.recall_target, jnp.float32))
        return np.asarray(x)

    def _build_fused_features(self, scored: bool = False):
        """One jitted function assembling X_in exactly like
        QueryFeatures.x_in(): [ε_recon; rates; probe_scores; σ, log1p(1/σ);
        weights; log k, E_rec; S_enc].

        ``scored=True`` builds the batched variant: it takes one extra
        ``row_scores`` arg (a per-column tuple of (n,) similarities,
        precomputed by a whole-batch GEMM) and pre-probes by gathering f32
        scores instead of vectors — the vmapped vector gather is the
        dominant batched-optimizer cost on CPU."""
        from functools import partial

        from repro.core.query_encoder import S_ENC_BINS  # noqa: F401
        from repro.vectordb import ivf as _ivf
        from repro.vectordb.predicates import active_any as _active_any
        from repro.vectordb.predicates import soft_encode as _soft

        cfg = self.cfg
        use_de = cfg.use_de and self.data_encoder is not None
        de = self.data_encoder
        probe_k, probe_np = self.qenc.probe_k, self.qenc.probe_nprobe
        n_vec = self.table.schema.n_vec

        @partial(jax.jit, static_argnums=())
        def fused(de_args, senc_edges, hists, indexes, vectors, scalars,
                  qs, pred, weights, logk, rec, row_scores=()):
            de_params, de_edges = de_args
            if use_de:
                es = _soft(pred, de_edges).reshape(-1)
                recon = []
                for i in range(n_vec):
                    ev = de._evec(de_params, i, qs[i])
                    e = jnp.concatenate([ev, es], axis=-1)
                    recon.append(jnp.mean(jnp.square(de._ae(de_params, e) - e)))
                recon = jnp.stack(recon)
            else:
                recon = jnp.zeros((n_vec,), jnp.float32)
            if cfg.use_lnp:
                rates, scores = [], []
                for i in range(n_vec):
                    if scored:
                        r, s = _ivf.preprobe_scored(
                            indexes[i], row_scores[i], scalars, pred, qs[i],
                            nprobe=probe_np, probe_k=probe_k)
                    else:
                        r, s = _ivf.preprobe(
                            indexes[i], vectors[i], scalars, pred, qs[i],
                            nprobe=probe_np, probe_k=probe_k)
                    rates.append(r)
                    scores.append(s)
                rates, scores = jnp.stack(rates), jnp.stack(scores)
            else:
                rates = jnp.full((n_vec,), 0.5)
                scores = jnp.zeros((n_vec,))
            if cfg.use_gse:
                from repro.vectordb import histogram as _h
                sel = _h.estimate_selectivity(hists, pred)
            else:
                sel = jnp.asarray(0.5)
            enc = _soft(pred, senc_edges)
            s_enc = jnp.concatenate(
                [enc, _active_any(pred).astype(jnp.float32)[:, None]],
                axis=1).reshape(-1)
            if not cfg.use_stats:
                weights = jnp.full((n_vec,), 1.0 / n_vec)
                logk = jnp.asarray(np.log(10.0), jnp.float32)
                rec = jnp.asarray(0.9, jnp.float32)
            return jnp.concatenate([
                recon, rates, scores,
                jnp.stack([sel, jnp.log1p(1.0 / jnp.maximum(sel, 1e-6))]),
                weights, jnp.stack([logk, rec]), s_enc,
            ]).astype(jnp.float32)

        return fused

    # -- online ----------------------------------------------------------------

    SINGLE_INDEX_MIN_SKEW = 0.85  # paper: single-index only for skewed weights

    def optimize(self, q: MHQ) -> ExecutionPlan:
        """ONE fused jit call (features + heads + argmax) and ONE host sync
        per query — the optimizer's serving overhead is dispatch-dominated
        on small tables, so everything lives in a single graph."""
        if not self._fitted:
            return default_plan(q.n_vec, self.engine)
        if getattr(self, "_plan_jit", None) is None:
            self._build_plan_jit()
        de = self.data_encoder
        de_args = (de.params, de.edges) if (self.cfg.use_de and de is not None) \
            else (None, None)
        codes = np.asarray(self._plan_jit(
            self.rewriter.params, de_args, self.qenc._edges, self.hists,
            tuple(self.indexes), tuple(self.table.vectors), self.table.scalars,
            tuple(q.query_vectors), q.predicates,
            jnp.asarray(q.weights, jnp.float32),
            jnp.asarray(float(np.log(q.k)), jnp.float32),
            jnp.asarray(q.recall_target, jnp.float32)))
        return self._apply_skew_guard(self.rewriter.plan_from_codes(codes), q)

    def _apply_skew_guard(self, plan: ExecutionPlan, q: MHQ) -> ExecutionPlan:
        if plan.strategy == "single_index":
            wmax = float(np.max(q.weights))
            if wmax >= self.SINGLE_INDEX_MIN_SKEW:
                plan = dataclasses.replace(plan, dominant=int(np.argmax(q.weights)))
            else:  # guard: not skewed enough — fall back to per-column scans
                plan = dataclasses.replace(plan, strategy="index_scan")
        return plan

    def _plan_local(self, b: int, cold=None) -> bool:
        """Should batch planning skip the dense score GEMMs?

        The batched optimizer's only dense-score consumer is the pre-probe
        feature; its candidate budget is the probe scan (``probe_k·4`` or
        ``nprobe·4·n/C`` rows per query per column). The same cost model
        that dispatches execution groups weighs that budget against the
        table: when candidate-local wins, planning runs the unscored
        pre-probe (vector gathers on the small probe tiles) and the GEMMs
        are never built unless an execution group later asks for them."""
        from repro.serve.batch import CANDIDATE_LOCAL, CostModel, next_bucket
        cm = self.cost_model if self.cost_model is not None else CostModel()
        t = self.table if cold is None else cold.table
        idxs = self.indexes if cold is None else cold.indexes
        n = t.n_rows
        scan = 0
        for idx in idxs:
            if self.qenc is not None:
                scan += ivf.probe_scan_budget(
                    idx.n_clusters, n, nprobe=self.qenc.probe_nprobe,
                    probe_k=self.qenc.probe_k)
            else:
                scan += min(n, self.engine.default_max_scan)
        return cm.choose(batch=next_bucket(max(1, b)), scan=max(1, scan),
                         n_rows=n * max(1, len(idxs))) \
            == CANDIDATE_LOCAL

    def optimize_batch(self, qs: list[MHQ], *,
                       scores_b: Optional[tuple] = None,
                       dense: Optional[bool] = None,
                       cold=None) -> list[ExecutionPlan]:
        """Plan a whole batch with ONE fused jit call and ONE host sync:
        the per-query feature + head pipeline vmapped over the query axis
        (batch padded to a power-of-two bucket so the jit cache stays
        bounded). ``scores_b`` — per-column (B_bucket, n) dense similarity
        matrices from ``compute_batch_scores`` — feeds the pre-probe
        features; pass the same tuple to the batched executor so the GEMMs
        run once per batch. ``dense=None`` auto-picks: when the scoring
        cost model says the table is past the dense crossover (and no
        matrices were passed in), planning runs the UNSCORED pre-probe
        pipeline instead and no (B, n) matrix is ever built.

        ``cold`` — an optional epoch's ``tiered.ColdState``: planning reads
        THAT epoch's table/indexes/histograms (the snapshot a formed batch
        carries) instead of the façade's fields, so plans stay consistent
        with the data the batch will actually execute against."""
        if not qs:
            return []
        if not self._fitted:
            return [default_plan(q.n_vec, self.engine) for q in qs]
        t = self.table if cold is None else cold.table
        idxs = self.indexes if cold is None else list(cold.indexes)
        hs = self.hists if cold is None else cold.hists
        if dense is None:
            dense = scores_b is not None or not self._plan_local(
                len(qs), cold)
        if dense:
            if getattr(self, "_plan_batch_jit", None) is None:
                self._build_plan_batch_jit()
            from repro.serve.batch import compute_batch_scores
            if scores_b is None:
                scores_b = compute_batch_scores(t, qs)
        elif getattr(self, "_plan_batch_local_jit", None) is None:
            self._build_plan_batch_jit(scored=False)
        from repro.serve.batch import next_bucket
        from repro.vectordb import predicates
        b = len(qs)
        with span("hq.planner.prepare"):
            qpad = list(qs) + [qs[0]] * (next_bucket(b) - b)
            de = self.data_encoder
            de_args = (de.params, de.edges) \
                if (self.cfg.use_de and de is not None) else (None, None)
            pred_b = predicates.stack([q.predicates for q in qpad])
            qv_b = tuple(jnp.stack([q.query_vectors[i] for q in qpad])
                         for i in range(t.schema.n_vec))
            args = (
                self.rewriter.params, de_args, self.qenc._edges, hs,
                tuple(idxs), tuple(t.vectors), t.scalars,
                qv_b, pred_b,
                jnp.asarray([q.weights for q in qpad], jnp.float32),
                jnp.asarray([float(np.log(q.k)) for q in qpad], jnp.float32),
                jnp.asarray([q.recall_target for q in qpad], jnp.float32))
        with span("hq.planner.sync"):  # the plan program and its one sync
            codes = np.asarray(
                self._plan_batch_jit(*args, scores_b) if dense
                else self._plan_batch_local_jit(*args))
        with span("hq.planner.decode"):
            return [self._apply_skew_guard(self.rewriter.plan_from_codes(c),
                                           q)
                    for q, c in zip(qs, codes[:b])]

    def _build_plan_jit(self):
        fused = self._fused_x if getattr(self, "_fused_x", None) is not None \
            else self._build_fused_features()
        self._fused_x = fused
        rew = self.rewriter

        @jax.jit
        def plan_jit(rw_params, de_args, senc_edges, hists, indexes, vectors,
                     scalars, qs, pred, weights, logk, rec):
            x = fused(de_args, senc_edges, hists, indexes, vectors, scalars,
                      qs, pred, weights, logk, rec)  # nested jit inlines
            return rew.plan_codes(rw_params, x)

        self._plan_jit = plan_jit

    def _build_plan_batch_jit(self, scored: bool = True):
        fused = self._build_fused_features(scored=scored)
        rew = self.rewriter

        if scored:
            def one(rw_params, de_args, senc_edges, hists, indexes, vectors,
                    scalars, qs, pred, weights, logk, rec, row_scores):
                x = fused(de_args, senc_edges, hists, indexes, vectors,
                          scalars, qs, pred, weights, logk, rec, row_scores)
                return rew.plan_codes(rw_params, x)

            self._plan_batch_jit = jax.jit(jax.vmap(
                one,
                in_axes=(None, None, None, None, None, None, None,
                         0, 0, 0, 0, 0, 0)))
        else:
            def one(rw_params, de_args, senc_edges, hists, indexes, vectors,
                    scalars, qs, pred, weights, logk, rec):
                x = fused(de_args, senc_edges, hists, indexes, vectors,
                          scalars, qs, pred, weights, logk, rec)
                return rew.plan_codes(rw_params, x)

            self._plan_batch_local_jit = jax.jit(jax.vmap(
                one,
                in_axes=(None, None, None, None, None, None, None,
                         0, 0, 0, 0, 0)))

    def execute(self, q: MHQ):
        q = self.resolve_tenant(q)
        if self.tiered is not None:
            # tiered serving is snapshot-based and batch-shaped; a single
            # query rides a one-element batch against one snapshot
            return self.execute_batch([q])[0]
        ids, scores = self.executor.execute(q, self.optimize(q))
        # underfill safeguard: if the plan found fewer than k qualifying rows
        # (severe mis-prediction), escalate once to the robust default plan.
        # One transfer per result decides it (HS001: ids used to round-trip
        # the device twice more in the comparison below).
        nv = _n_valid(ids)
        if nv < q.k:
            ids2, scores2 = self.executor.execute(
                q, default_plan(q.n_vec, self.engine))
            if _n_valid(ids2) > nv:
                return ids2, scores2
        return ids, scores

    def bind_shards(self, n_shards: int = 1, *, mesh=None,
                    shard_axes=("data",)) -> "BoomHQ":
        """Serve over a SHARDED table: subsequent ``execute_batch`` calls
        plan the batch with the learned optimizer and fan each execution
        group out over contiguous table shards
        (``serve.batch.BatchedHybridExecutor.execute_batch_sharded``).
        Index-strategy groups are cost-model routed three ways: plan-driven
        per-shard IVF probing (each shard probes its own ``ShardedIVF``
        with the group's shard-legalized knobs and reranks candidate-
        locally inside the shard — the learned nprobe/max_scan finally
        operative at shard scale), the exact per-shard dense scan, or the
        plain single-device path when shards are too small to amortize the
        fan-out; filter_first groups keep the exact sharded scan. With a
        ``mesh`` the fan-out runs under shard_map over its data axes, and
        the table (columns, scalars, int8 replicas) is placed on the mesh
        here, once, row-sharded over those axes; ``n_shards`` must then be
        1 or the mesh's shard count. Without a mesh, logical shards on the
        local device keep identical semantics. ``bind_shards()``
        (defaults) restores single-shard serving."""
        axes = shard_axes if isinstance(shard_axes, tuple) else (shard_axes,)
        if mesh is not None:
            n_mesh = int(np.prod([mesh.shape[a] for a in axes]))
            if int(n_shards) not in (1, n_mesh):
                raise ValueError(f"{n_shards} shards over a {n_mesh}-way "
                                 f"mesh")
            n_shards = n_mesh
        self.n_shards = max(1, int(n_shards))
        self.shard_mesh = mesh
        self.shard_axes = axes
        self._placed = None
        self._batched = None  # rebind the executor with the new shard config
        if mesh is not None:
            self._serving_table()
        return self

    def _serving_table(self, cold=None):
        """The table batches execute on: a snapshot's cold epoch, else the
        façade's table — placed on the bound mesh, and placed again
        whenever an insert replaced it."""
        if cold is not None:
            return cold.table
        if self.shard_mesh is None:
            return self.table
        if self._placed is None or self._placed[0] is not self.table:
            from repro.vectordb.distributed import place_table
            self._placed = (self.table, place_table(
                self.table, self.shard_mesh, self.shard_axes))
        return self._placed[1]

    def bind_tiered(self, hot_capacity: int = 1024, *,
                    rebuild_every: int = 0,
                    finetune: bool = True) -> "BoomHQ":
        """Serve over a TIERED hot/cold table: subsequent ``insert`` calls
        append to a bounded writable hot segment (scored exactly,
        candidate-locally, as one extra merge source on every query) and
        background compaction folds full segments into the cold IVF state
        under an epoch-swapped snapshot — streaming ingest with zero
        serving pauses (``vectordb.tiered``, docs/tiered_ingest.md).
        Composes with ``bind_shards``/``bind_cost_model``: the cold tier
        keeps the existing plan-driven (possibly sharded) probing paths.
        ``rebuild_every=N`` makes every Nth compaction a full re-cluster
        (the sealing step); ``finetune`` keeps the data encoder updating on
        compacted rows. ``unbind_tiered()`` restores build-once serving."""
        from repro.vectordb.tiered import TieredTable
        self._tiered_finetune = finetune
        self.tiered = TieredTable(
            self.table, self.indexes, self.hists,
            hot_capacity=hot_capacity, rebuild_every=rebuild_every,
            finetune_cb=self._on_compaction, graphs=self.graphs)
        return self

    def unbind_tiered(self) -> "BoomHQ":
        """Back to build-once serving. The façade's table/index fields were
        kept in sync at every compaction, so the latest cold epoch stays
        the serving state; un-compacted hot rows (if any) are folded in
        through the legacy eager insert."""
        t = self.tiered
        self.tiered = None
        self._compactor = None
        if t is not None:
            snap = t.snapshot()
            for view in snap.hot_views:
                if view.count:
                    self.insert(
                        [v[: view.count] for v in view.np_vectors],
                        view.np_scalars[: view.count],
                        finetune=self._tiered_finetune)
        return self

    def _on_compaction(self, cold, first_new: int, n_new: int) -> None:
        """Compaction-thread callback (runs BEFORE the epoch publish):
        finetune the data encoder on the newly cold rows, refresh the query
        encoder, keep the façade's offline fields tracking the latest
        epoch, and PRE-WARM the post-swap jit shapes. Serving never reads
        these mutable fields (EP001) — batches in flight keep their
        snapshot."""
        if self.data_encoder is not None and self._tiered_finetune:
            self.data_encoder.update(
                cold.table, np.arange(first_new, first_new + n_new))
        if self.qenc is not None:
            self.qenc = QueryEncoder(cold.table, list(cold.indexes),
                                     cold.hists, self.data_encoder)
        self.table = cold.table
        self.indexes = list(cold.indexes)
        self.hists = cold.hists
        self.graphs = cold.graphs
        self.executor = HybridExecutor(cold.table, list(cold.indexes),
                                       self.engine, graphs=cold.graphs)
        self._prewarm_cold(cold)

    def _prewarm_cold(self, cold) -> None:
        """Compile the post-swap serving shapes BEFORE the epoch publish.

        Compaction grows the cold table, and the new row count is a new
        static shape for every serving jit (dense GEMMs, probe kernels,
        the fused batched optimizer) — the first post-swap batch used to
        pay the whole compile ladder inside its measured latency
        (benchmarks/results/data_updates.json: p99 ≈ 3× p50 with exactly
        one compaction in the window). Re-running a window of retained
        recent queries against the new cold state on THIS (compaction)
        thread populates the jit caches through the same code path serving
        will take, so the epoch bump lands on a warm engine; the built
        executor is published for the first post-swap batch to reuse."""
        qs = list(self._recent)[-max(1, self._last_batch):]
        if not qs:
            return
        from repro.serve.batch import warm_bucket_ladder
        from repro.vectordb.tiered import TieredSnapshot
        # a synthetic pre-publish snapshot of the new cold state (no hot
        # views: compaction just drained them). Warming goes through the
        # REAL serving entry so every branch the first post-swap batch can
        # take — planning, grouped execution, underfill escalation — is
        # compiled by the same code path that will serve it. The snapshot
        # also suppresses _recent re-recording (sub-batch guard).
        snap = TieredSnapshot(epoch=-1, cold=cold, hot_views=())
        warm_bucket_ladder(
            lambda batch: self.execute_batch(batch, snapshot=snap),
            qs, len(qs))

    def bind_tenants(self, column: int | str = "tenant") -> "BoomHQ":
        """Serve MULTI-TENANT: queries carrying ``MHQ.tenant_id`` are scoped
        to rows whose ``column`` equals that id. The namespace compiles to
        an implicit ``tenant == id`` conjunct folded into every DNF clause
        of the query's predicate (``predicates.fold_conjunct``) — the clause
        bucket, C-grid legalization and every kernel stay untouched.
        ``unbind_tenants()`` restores shared serving."""
        if isinstance(column, str):
            names = {sc.name: i for i, sc in
                     enumerate(self.table.schema.scalar_cols)}
            if column not in names:
                raise KeyError(f"unknown scalar column {column!r}")
            self.tenant_col = names[column]
        else:
            if not 0 <= int(column) < self.table.schema.n_scalar:
                raise IndexError(f"scalar column {column} out of range")
            self.tenant_col = int(column)
        return self

    def unbind_tenants(self) -> "BoomHQ":
        self.tenant_col = None
        return self

    def resolve_tenant(self, q: MHQ) -> MHQ:
        """Fold the query's tenant namespace into its predicate. No-op for
        untenanted queries or unbound engines; idempotent, so front-ends
        (the serving engine folds before its cache lookup) and the execute
        paths may both resolve."""
        if q.tenant_id is None or self.tenant_col is None:
            return q
        from repro.vectordb.predicates import fold_conjunct
        t = float(int(q.tenant_id))
        return dataclasses.replace(
            q, predicates=fold_conjunct(q.predicates, self.tenant_col, t, t))

    def bind_cost_model(self, cost_model=None) -> "BoomHQ":
        """Override the scoring dispatcher's cost model (a
        ``serve.batch.CostModel`` — crossover ratio and/or a forced path)
        for subsequent batched execution. ``bind_cost_model()`` restores the
        calibrated default."""
        self.cost_model = cost_model
        self._batched = None  # rebind the executor with the new model
        return self

    @property
    def _sharded(self) -> bool:
        return self.n_shards > 1 or self.shard_mesh is not None

    def execute_batch(self, queries: list[MHQ], *, snapshot=None) -> list:
        """Batched analogue of execute(): one fused optimizer dispatch for
        the whole batch, grouped vmapped execution, then one batched
        underfill-escalation pass. Returns [(ids, scores)] per query.

        Over a sharded table (``bind_shards``) execution instead fans the
        learned plans out across the shards: each index-strategy group is
        cost-model routed to per-shard IVF probing (the plans' knobs drive
        each shard's own index), the exact per-shard dense scan, or the
        single-device path, with per-shard underfill escalation inside the
        probing route and the global cross-check of
        ``_execute_batch_sharded`` on top.

        Over a TIERED table (``bind_tiered``) the whole batch executes
        against ONE immutable ``(epoch, hot_view, cold_shards)`` snapshot —
        ``snapshot`` when the batch former stamped one at cut time, else
        taken here — so an epoch swap mid-batch can never mix states: the
        cold side runs the unchanged plan-driven paths against the
        snapshot's epoch and the hot segment merges in as one extra exact
        candidate source (``_merge_hot``)."""
        if not queries:
            return []
        from repro.serve.batch import (
            MAX_BATCH_KERNEL, SLOT_BUDGET, compute_batch_scores, pow2_at_most,
        )
        queries = [self.resolve_tenant(q) for q in queries]
        if snapshot is None:  # outer call, not a size-limit sub-batch
            self._recent.extend(queries)
            self._last_batch = len(queries)
        snap = None
        if self.tiered is not None:
            snap = snapshot if snapshot is not None else \
                self.tiered.snapshot()
        cold = snap.cold if snap is not None else None
        t = self.table if cold is None else cold.table
        # bound the dense-score working set (batch · n_rows per column) the
        # same way the executor chunks do — large tables get sub-batches
        limit = pow2_at_most(max(1, min(
            MAX_BATCH_KERNEL, SLOT_BUDGET // max(t.n_rows, 1))))
        if len(queries) > limit:
            out = []
            for s in range(0, len(queries), limit):
                out.extend(self.execute_batch(queries[s: s + limit],
                                              snapshot=snap))
            return out
        # past the dense crossover the (B, n) similarity matrices are never
        # built: planning runs the unscored pre-probe pipeline and execution
        # groups gather only their candidate budgets (per-group dispatch can
        # still fall back to a per-chunk GEMM when a group wants dense)
        plan_local = self._plan_local(len(queries), cold)
        scores_b = None
        if not plan_local:
            with span("hq.exec.score"):
                scores_b = compute_batch_scores(t, queries)
        bx = self._batched_executor(cold)
        self.counts["queries_executed"] += len(queries)
        if self._sharded:
            results = self._execute_batch_sharded(queries, bx, scores_b,
                                                  cold=cold)
        else:
            plans = self.optimize_batch(queries, scores_b=scores_b,
                                        dense=not plan_local, cold=cold)
            with span("hq.exec.groups"):
                results = bx.execute_batch(queries, plans, scores_b=scores_b)
            results = self._escalate(
                queries, results, bx, scores_b,
                lambda q: default_plan(q.n_vec, self.engine))
        if snap is not None and snap.hot_views:
            with span("hq.exec.merge_hot"):
                results = self._merge_hot(results, queries, snap)
        return results

    def _escalate(self, queries: list[MHQ], results: list, bx, scores_b,
                  retry_plan) -> list:
        """Underfill escalation: every query that came back with fewer
        than k valid rows runs again, as one grouped pass, under
        ``retry_plan(query)``; the better-filled result wins. Counted in
        ``counts``."""
        with span("hq.exec.underfill"):  # one host sync per query
            under = [j for j, (ids, _) in enumerate(results)
                     if _n_valid(ids) < queries[j].k]
        if not under:
            return results
        self.counts["escalated"] += len(under)
        self.counts["escalation_passes"] += 1
        with span("hq.exec.escalate"):
            sub = np.asarray(under)
            retry = bx.execute_batch(
                [queries[j] for j in under],
                [retry_plan(queries[j]) for j in under],
                scores_b=tuple(s[sub] for s in scores_b)
                if scores_b is not None else None)
            for j, (ids2, s2) in zip(under, retry):
                if _n_valid(ids2) > _n_valid(results[j][0]):
                    results[j] = (ids2, s2)
                    self.counts["escalation_improved"] += 1
        return results

    def _merge_hot(self, results, queries: list[MHQ], snap) -> list:
        """Fold the snapshot's hot views into the cold results: ONE fused
        exact gather-score over each bounded hot view plus ONE pass of the
        existing O(shards·k) dedup merge (``merge_topk_unique``) — the hot
        segment is just one more candidate source, with globally disjoint
        row ids, so escalation and recall contracts survive unchanged. An
        empty hot segment never reaches here (bit-for-bit cold parity)."""
        from repro.kernels.shapes import NEG
        from repro.serve.batch import K_BUCKET_FLOOR, next_bucket
        from repro.vectordb import predicates, tiered
        b = len(queries)
        k_pad = next_bucket(max(K_BUCKET_FLOOR,
                                max(q.k for q in queries)))
        b_pad = next_bucket(b)
        qpad = list(queries) + [queries[0]] * (b_pad - b)
        n_vec = snap.cold.table.schema.n_vec
        pred_b = predicates.stack([q.predicates for q in qpad])
        qv_b = tuple(jnp.stack([q.query_vectors[i] for q in qpad])
                     for i in range(n_vec))
        w_b = jnp.asarray([q.weights for q in qpad], jnp.float32)
        ids_np = [np.asarray(r[0], np.int32).ravel() for r in results]
        sc_np = [np.asarray(r[1], np.float32).ravel() for r in results]
        cold_ids = np.full((b_pad, k_pad), -1, np.int32)
        cold_scores = np.full((b_pad, k_pad), np.float32(NEG), np.float32)
        for j in range(b):
            kk = min(ids_np[j].shape[0], k_pad)
            cold_ids[j, :kk] = ids_np[j][:kk]
            cold_scores[j, :kk] = sc_np[j][:kk]
        views = tuple(tiered.view_args(v) for v in snap.hot_views)
        m_ids, m_scores = tiered.merge_hot_batch(
            jnp.asarray(cold_ids), jnp.asarray(cold_scores), views,
            qv_b, w_b, pred_b, k=k_pad, metric=snap.cold.table.schema.metric)
        m_ids = np.asarray(m_ids)
        m_scores = np.asarray(m_scores)
        return [(m_ids[j, : q.k], m_scores[j, : q.k])
                for j, q in enumerate(queries)]

    def _execute_batch_sharded(self, queries: list[MHQ], bx,
                               scores_b: tuple, cold=None) -> list:
        """Plan-driven cross-shard execution + underfill escalation.

        The batch is planned by the learned optimizer exactly like the
        single-shard path, then fanned out: the executor routes every
        index-strategy group through the cost model (per-shard IVF probing
        / exact per-shard dense scan / single-device), with PER-SHARD
        underfill escalation inside the probing path (exact retry only on
        the underfilled shard-subset). This global cross-check remains on
        top: any query still returning fewer than k valid ids re-runs
        through the single-shard exact filter-first (one extra grouped pass
        over only that subset) and the better-filled result wins — the
        same recall contract the single-shard learned path keeps."""
        t = self.table if cold is None else cold.table
        plans = self.optimize_batch(queries, scores_b=scores_b, cold=cold)
        with span("hq.exec.groups"):
            results = bx.execute_batch_sharded(queries, plans,
                                               scores_b=scores_b)
        return self._escalate(
            queries, results, bx, scores_b,
            lambda q: ExecutionPlan(
                "filter_first", tuple(SubqueryParams() for _ in range(q.n_vec)),
                max_candidates=t.n_rows))

    def _batched_executor(self, cold=None):
        """Executor bound to the serving state — the façade's fields, or a
        snapshot's cold epoch when one is passed. Single-slot cache keyed
        on table identity: batches execute in formation order, so an epoch
        swap rebuilds once at the first post-swap batch and never
        thrashes."""
        from repro.serve.batch import BatchedHybridExecutor
        t = self._serving_table(cold)
        idxs = self.indexes if cold is None else list(cold.indexes)
        hs = self.hists if cold is None else cold.hists
        grs = self.graphs if cold is None else cold.graphs
        if getattr(self, "_batched", None) is None \
                or self._batched.table is not t:
            self._batched = BatchedHybridExecutor(
                t, idxs, self.engine,
                n_shards=self.n_shards, mesh=self.shard_mesh,
                shard_axes=getattr(self, "shard_axes", ("data",)),
                cost_model=self.cost_model, hists=hs, graphs=grs)
        return self._batched

    def execute_timed(self, q: MHQ, *, repeats: int = 1):
        """(ids, scores, seconds) — optimizer overhead INCLUDED (the paper
        counts pre-probing and inference in the measured latency)."""
        ids, scores = self.execute(q)  # warm (jit caches)
        jnp.asarray(scores).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(repeats):
            ids, scores = self.execute(q)
            jnp.asarray(scores).block_until_ready()
        dt = (time.perf_counter() - t0) / repeats
        return np.asarray(ids), np.asarray(scores), dt

    # -- updates (paper §3.2 incremental, §5.3) ---------------------------------

    def insert(self, vectors: list[np.ndarray], scalars: np.ndarray,
               *, finetune: bool = True) -> dict:
        """Data updates. Tiered (``bind_tiered``): rows append to the hot
        segment — visible to the next formed batch, exact-scored, never a
        serving pause — and compaction (background when a scheduler is
        attached, else deferred to the next ``compact()``) folds them cold,
        finetuning the encoder per ``finetune``. Untiered: the legacy eager
        path — extend indexes/histograms and rebuild the executor now."""
        if self.tiered is not None:
            self._tiered_finetune = finetune
            stats = self.tiered.insert(vectors, scalars)
            if stats["needs_compaction"] and self._compactor is not None:
                self._compactor.maybe_schedule()
            return stats
        first_new = self.table.n_rows
        self.table = self.table.append(vectors, scalars)
        self.indexes = [
            ivf.extend(idx, jnp.asarray(v, jnp.float32), first_new)
            for idx, v in zip(self.indexes, vectors)
        ]
        self.hists = histogram.update(self.hists, jnp.asarray(scalars, jnp.float32))
        if self.graphs is not None:
            # graph.extend reads the FULL post-append column (the graph
            # stores no vectors), so this must follow the table append
            self.graphs = tuple(
                graph.extend(g, v, first_new)
                for g, v in zip(self.graphs, self.table.vectors))
        self.executor = HybridExecutor(self.table, self.indexes, self.engine,
                                       graphs=self.graphs)
        self._batched = None  # rebind the batched executor to the new table
        out = {}
        if self.data_encoder is not None and finetune:
            new_rows = np.arange(first_new, self.table.n_rows)
            out = self.data_encoder.update(self.table, new_rows)
        if self.qenc is not None:
            self.qenc = QueryEncoder(self.table, self.indexes, self.hists,
                                     self.data_encoder)
        return out
