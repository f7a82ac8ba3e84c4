"""MHQ Rewriter: predicted execution strategies and parameters (paper §3.4).

Phase 1 — strategy head: X_in -> {filter_first, index_scan, single_index}.
Phase 2 — parameter heads: per vector column, classification over the
  nprobe / max_scan / k_mult grids + a Bernoulli head for iterative_scan.

Self-supervised training exactly as the paper prescribes: execute each
workload query under a grid of candidate configurations, measure (latency,
recall), and label with the cheapest configuration that meets the query's
recall target. A per-column greedy trim pass differentiates k_i/nprobe_i
across columns (the weight-adaptive behaviour of Fig. 5).
"""
from __future__ import annotations

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import nn
from repro.core.executor import HybridExecutor, recall_at_k
from repro.core.query import (
    BEAM_GRID, ExecutionPlan, HOP_GRID, KMULT_GRID, MAX_SCAN_GRID, MHQ,
    NPROBE_GRID, PRECISION_GRID, STRATEGIES, SubqueryParams,
)
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_step

N_NP, N_MS, N_KM = len(NPROBE_GRID), len(MAX_SCAN_GRID), len(KMULT_GRID)
N_BEAM, N_HOP = len(BEAM_GRID), len(HOP_GRID)
PER_COL = N_NP + N_MS + N_KM + 1


@dataclasses.dataclass(frozen=True)
class RewriterConfig:
    hidden: int = 96
    lr: float = 2e-3
    steps: int = 800
    batch: int = 64
    seed: int = 0
    refine_columns: bool = True  # per-column greedy trim of the best plan


@dataclasses.dataclass
class PlanLabel:
    strategy: int
    nprobe_idx: np.ndarray  # (N,)
    max_scan_idx: np.ndarray  # (N,)
    k_mult_idx: np.ndarray  # (N,)
    iterative: np.ndarray  # (N,) {0,1}
    latency: float
    recall: float
    precision: int = 0  # PRECISION_GRID index of the candidate-tier dtype
    beam_idx: int = 1  # BEAM_GRID index (graph strategy only)
    hop_idx: int = 1  # HOP_GRID index (graph strategy only)


class MHQRewriter:
    def __init__(self, in_dim: int, n_vec: int, cfg: RewriterConfig):
        self.cfg = cfg
        self.n_vec = n_vec
        self.in_dim = in_dim
        k = jax.random.PRNGKey(cfg.seed)
        k1, k2, k3, k4, k5 = jax.random.split(k, 5)
        h = cfg.hidden
        self.params = {
            "trunk": nn.mlp_init(k1, [in_dim, h, h]),
            "strategy": nn.mlp_init(k2, [h, len(STRATEGIES)]),
            "per_col": nn.mlp_init(k3, [h, n_vec * PER_COL]),
            "precision": nn.mlp_init(k4, [h, len(PRECISION_GRID)]),
            # graph-strategy knobs: beam-width and hop-count grids, one
            # shared head (the walk is per-query, not per-column)
            "graph": nn.mlp_init(k5, [h, N_BEAM + N_HOP]),
        }

    # -- forward -------------------------------------------------------------

    def _heads(self, params, x):
        z = nn.mlp_apply(params["trunk"], x, final_activation=True)
        strat = nn.mlp_apply(params["strategy"], z)
        per_col = nn.mlp_apply(params["per_col"], z)
        per_col = per_col.reshape(*per_col.shape[:-1], self.n_vec, PER_COL)
        prec = nn.mlp_apply(params["precision"], z)
        gr = nn.mlp_apply(params["graph"], z)
        return strat, per_col, prec, gr

    def plan_codes(self, params, x):
        """Jit-friendly head evaluation: -> int32 codes
        [strategy, np_idx×N, ms_idx×N, km_idx×N, iter×N, precision,
        beam_idx, hop_idx]."""
        strat, per_col, prec, gr = self._heads(params, x)
        s_idx = jnp.argmax(strat)[None]
        np_i = jnp.argmax(per_col[..., :N_NP], axis=-1)
        ms_i = jnp.argmax(per_col[..., N_NP:N_NP + N_MS], axis=-1)
        km_i = jnp.argmax(per_col[..., N_NP + N_MS:N_NP + N_MS + N_KM], axis=-1)
        it = (per_col[..., -1] > 0.0).astype(jnp.int32)
        p_idx = jnp.argmax(prec)[None]
        b_idx = jnp.argmax(gr[..., :N_BEAM])[None]
        h_idx = jnp.argmax(gr[..., N_BEAM:])[None]
        return jnp.concatenate(
            [s_idx, np_i, ms_i, km_i, it, p_idx, b_idx, h_idx]
        ).astype(jnp.int32)

    def plan_from_codes(self, codes: np.ndarray) -> ExecutionPlan:
        n = self.n_vec
        s_idx = int(codes[0])
        np_i, ms_i, km_i = (codes[1:1 + n], codes[1 + n:1 + 2 * n],
                            codes[1 + 2 * n:1 + 3 * n])
        it = codes[1 + 3 * n:1 + 4 * n]
        # precision + graph knobs ride as trailing codes; decode stays
        # compatible with shorter code vectors (older checkpoints/tests)
        prec = PRECISION_GRID[int(codes[1 + 4 * n])] \
            if codes.shape[0] > 1 + 4 * n else "fp32"
        beam = BEAM_GRID[int(codes[2 + 4 * n])] \
            if codes.shape[0] > 2 + 4 * n else ExecutionPlan.beam_width
        hops = HOP_GRID[int(codes[3 + 4 * n])] \
            if codes.shape[0] > 3 + 4 * n else ExecutionPlan.n_hops
        subs = tuple(
            SubqueryParams(k_mult=KMULT_GRID[km_i[i]], nprobe=NPROBE_GRID[np_i[i]],
                           max_scan=MAX_SCAN_GRID[ms_i[i]], iterative=bool(it[i]))
            for i in range(n))
        return ExecutionPlan(strategy=STRATEGIES[s_idx], subqueries=subs,
                             precision=prec, beam_width=beam, n_hops=hops)

    def predict(self, x: np.ndarray, *, k: int = 10) -> ExecutionPlan:
        """Single-query convenience wrapper over the canonical decode path
        (plan_codes -> plan_from_codes), so the two can never drift.

        Dominant column for single_index: the largest-weight feature is
        embedded in x; the caller picks it at plan-build time."""
        if not hasattr(self, "_codes_jit") or self._codes_jit is None:
            self._codes_jit = jax.jit(self.plan_codes)
        codes = np.asarray(self._codes_jit(self.params, jnp.asarray(x)))
        return self.plan_from_codes(codes)

    # -- training --------------------------------------------------------------

    def fit(self, X: np.ndarray, labels: list[PlanLabel]) -> dict:
        cfg = self.cfg
        n = X.shape[0]
        y_strat = jnp.asarray([l.strategy for l in labels])
        y_np = jnp.asarray(np.stack([l.nprobe_idx for l in labels]))
        y_ms = jnp.asarray(np.stack([l.max_scan_idx for l in labels]))
        y_km = jnp.asarray(np.stack([l.k_mult_idx for l in labels]))
        y_it = jnp.asarray(np.stack([l.iterative for l in labels]), jnp.float32)
        y_prec = jnp.asarray([l.precision for l in labels])
        y_beam = jnp.asarray([l.beam_idx for l in labels])
        y_hop = jnp.asarray([l.hop_idx for l in labels])
        # parameter losses only matter for index-scan-family labels
        par_mask = jnp.asarray([1.0 if l.strategy != 0 else 0.0 for l in labels])
        gr_idx = STRATEGIES.index("graph")
        gr_mask = jnp.asarray(
            [1.0 if l.strategy == gr_idx else 0.0 for l in labels])
        Xj = jnp.asarray(X)

        def loss_fn(params, idx):
            x = Xj[idx]
            strat, per_col, prec, gr = self._heads(params, x)
            ls = -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(strat), y_strat[idx][:, None], 1))
            # precision head: like the strategy head but masked to the
            # index family (filter_first is always fp32 post-legalization)
            lprec = -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(prec), y_prec[idx][:, None], 1)[..., 0]
                * par_mask[idx])
            # graph knob heads: only graph-strategy labels carry a
            # meaningful beam/hop choice
            lgr = -jnp.mean(
                (jnp.take_along_axis(
                    jax.nn.log_softmax(gr[..., :N_BEAM]),
                    y_beam[idx][:, None], 1)[..., 0]
                 + jnp.take_along_axis(
                    jax.nn.log_softmax(gr[..., N_BEAM:]),
                    y_hop[idx][:, None], 1)[..., 0]) * gr_mask[idx])
            ls = ls + lprec + lgr

            def head_ce(sl, y):
                logp = jax.nn.log_softmax(per_col[..., sl], axis=-1)
                ce = -jnp.take_along_axis(logp, y[idx][..., None], -1)[..., 0]
                return jnp.mean(ce * par_mask[idx][:, None])

            lp = head_ce(slice(0, N_NP), y_np)
            lp += head_ce(slice(N_NP, N_NP + N_MS), y_ms)
            lp += head_ce(slice(N_NP + N_MS, N_NP + N_MS + N_KM), y_km)
            logit_it = per_col[..., -1]
            bce = jnp.mean(
                (jax.nn.softplus(logit_it) - y_it[idx] * logit_it)
                * par_mask[idx][:, None])
            return ls + lp + bce

        opt_cfg = AdamWConfig(lr=cfg.lr, weight_decay=1e-4, grad_clip_norm=1.0)
        st = adamw_init(self.params, opt_cfg)
        train = adamw_step(loss_fn, opt_cfg)
        rng = np.random.default_rng(cfg.seed)
        l = jnp.zeros(())
        for step in range(cfg.steps):
            idx = jnp.asarray(rng.integers(0, n, min(cfg.batch, n)))
            self.params, st, l = train(self.params, st, idx)
        # training accuracy
        strat, _, _, _ = self._heads(self.params, Xj)
        acc = float(jnp.mean(jnp.argmax(strat, -1) == y_strat))
        return {"rewriter_loss": float(l), "strategy_acc": acc}


# ---------------------------------------------------------------------------
# self-supervised label generation (grid execution)
# ---------------------------------------------------------------------------

def candidate_plans(n_vec: int, weights=None, *,
                    graphs: bool = False) -> list[ExecutionPlan]:
    """The exploration grid (coarse; per-column trim refines it afterwards).

    ``graphs``: offer graph-strategy configurations — only meaningful when
    the labeling executor has a graph tier bound (otherwise legalization
    rewrites them to index_scan and the label would be mis-attributed)."""
    plans = [ExecutionPlan("filter_first",
                           tuple(SubqueryParams() for _ in range(n_vec)))]
    for npb, km, ms in itertools.product((2, 8, 32), (2, 8), (8192, 131072)):
        subs = tuple(SubqueryParams(k_mult=km, nprobe=npb, max_scan=ms,
                                    iterative=True) for _ in range(n_vec))
        plans.append(ExecutionPlan("index_scan", subs))
    if graphs:
        # the beam/hop product spans cheap walks (short, narrow — the
        # selective-predicate sweet spot) through deep wide walks that
        # rival exhaustive probing on recall
        for bw, nh, km in ((4, 2, 2), (8, 4, 2), (8, 4, 8), (16, 8, 8)):
            subs = tuple(SubqueryParams(k_mult=km, iterative=False)
                         for _ in range(n_vec))
            plans.append(ExecutionPlan("graph", subs, beam_width=bw,
                                       n_hops=nh))
    # quantized-tier twins of the deep-scan configs: int8 candidate scoring
    # + exact fp32 rerank only pays off where the scan budget is large, so
    # the exploration grid offers it exactly there — label generation then
    # measures whether the two-stage path is actually cheaper at target
    for npb, km in itertools.product((8, 32), (2, 8)):
        subs = tuple(SubqueryParams(k_mult=km, nprobe=npb, max_scan=131072,
                                    iterative=True) for _ in range(n_vec))
        plans.append(ExecutionPlan("index_scan", subs, precision="int8"))
    if n_vec > 1 and weights is not None:
        dom = int(np.argmax(weights))
        for npb in (8, 32):
            subs = tuple(SubqueryParams(k_mult=8, nprobe=npb, max_scan=32768,
                                        iterative=True) for _ in range(n_vec))
            plans.append(ExecutionPlan("single_index", subs, dominant=dom))
    return plans


def _grid_index(grid, value) -> int:
    return min(range(len(grid)), key=lambda i: abs(grid[i] - value))


def plan_to_label(plan: ExecutionPlan, latency: float, recall: float) -> PlanLabel:
    return PlanLabel(
        strategy=STRATEGIES.index(plan.strategy),
        nprobe_idx=np.asarray([_grid_index(NPROBE_GRID, s.nprobe)
                               for s in plan.subqueries]),
        max_scan_idx=np.asarray([_grid_index(MAX_SCAN_GRID, s.max_scan)
                                 for s in plan.subqueries]),
        k_mult_idx=np.asarray([_grid_index(KMULT_GRID, s.k_mult)
                               for s in plan.subqueries]),
        iterative=np.asarray([1.0 if s.iterative else 0.0
                              for s in plan.subqueries], np.float32),
        latency=latency, recall=recall,
        precision=PRECISION_GRID.index(plan.precision),
        beam_idx=_grid_index(BEAM_GRID, plan.beam_width),
        hop_idx=_grid_index(HOP_GRID, plan.n_hops))


LABEL_RECALL_MARGIN = 0.05  # train to a margin above E_rec: the learned
# heads generalize imperfectly, so labels aim slightly high to keep the
# SERVED recall at/above the user threshold


def generate_label(executor: HybridExecutor, q: MHQ, gt_ids,
                   *, refine_columns: bool = True) -> PlanLabel:
    """Execute the candidate grid; label = cheapest plan meeting the target
    (+ margin). If nothing meets it, fall back to the highest-recall plan
    (the engine cannot do better within its own search space)."""
    target = min(1.0, q.recall_target + LABEL_RECALL_MARGIN)
    best, best_any = None, None
    has_graphs = getattr(executor, "graphs", None) is not None
    for plan in candidate_plans(q.n_vec, q.weights, graphs=has_graphs):
        ids, _, dt = executor.execute_timed(q, plan)
        rec = recall_at_k(ids, gt_ids)
        entry = (dt, rec, plan)
        if best_any is None or rec > best_any[1] + 1e-9 or \
                (abs(rec - best_any[1]) < 1e-9 and dt < best_any[0]):
            best_any = entry
        if rec >= target and (best is None or dt < best[0]):
            best = entry
    if best is None:
        best = best_any
    dt, rec, plan = best

    # per-column greedy trim: shrink k_mult / nprobe of each column while the
    # recall target still holds — differentiates columns by weight (Fig. 5)
    if refine_columns and plan.strategy != "filter_first" and q.n_vec > 1:
        # graph walks ignore nprobe — trimming it would loop to the grid
        # floor on no-op re-executions
        attrs = (("k_mult", KMULT_GRID),) if plan.strategy == "graph" else \
            (("k_mult", KMULT_GRID), ("nprobe", NPROBE_GRID))
        subs = list(plan.subqueries)
        for i in range(q.n_vec):
            for attr, grid in attrs:
                while True:
                    cur = getattr(subs[i], attr)
                    gi = _grid_index(grid, cur)
                    if gi == 0:
                        break
                    trial = dataclasses.replace(subs[i], **{attr: grid[gi - 1]})
                    trial_plan = dataclasses.replace(
                        plan, subqueries=tuple(subs[:i] + [trial] + subs[i + 1:]))
                    ids, _, dt_t = executor.execute_timed(q, trial_plan)
                    if recall_at_k(ids, gt_ids) >= target:
                        subs[i] = trial
                        plan, dt, rec = trial_plan, dt_t, recall_at_k(ids, gt_ids)
                    else:
                        break
        plan = dataclasses.replace(plan, subqueries=tuple(subs))

    return plan_to_label(plan, dt, rec)
