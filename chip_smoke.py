"""Chip smoke test: BoomHQ's hybrid-query serving path, end to end, on a TPU.

    python chip_smoke.py             # one chip: every scoring path
    python chip_smoke.py --chips 4   # the 4-way mesh-sharded phase only

Deployment: TPC-H ``part`` at its paper scale (bench/datasets.py SPECS:
200,000 rows, two 768-d vector columns, four scalar columns), generated from
``--seed``, resident on the chip with its int8 replica. The one-chip run
takes the path ``examples/hybrid_serving.py`` takes: ``datasets.make`` ->
``BoomHQ(...).fit`` (default config, graph tier on) -> ``warm_bucket_ladder``
-> ``AsyncServingEngine``/``serve_stream`` -> ``BoomHQ.execute_batch``. It
serves the same conjunctive + DNF requests once per scoring path — dense,
candidate-local fp32, candidate-local int8 and graph, each forced through
``bind_cost_model`` and fixed plans — and then under the learned plans.

Every served request is checked against the NumPy oracle (tests/oracle.py):
it resolves ok, every returned id satisfies its predicate, and every score
equals the row's exact score within ``tie_tolerance``. The exact path must
reach recall 1.0 up to ties, the generous-budget candidate-local paths a
mean of 0.95; graph and learned recall are printed. Each path's line names
the backend, the device kind, whether its jitted groups' compiled text holds
the Mosaic kernel (``tpu_custom_call``), compile seconds and requests served.

``--chips 4`` binds the same table to a 4-device mesh
(``bind_shards(4, mesh=...)``), prints where each column's rows live,
serves 8 conjunctive and 8 DNF requests (no bucket-ladder warm-up), and
compares the served ids and scores with logical shards on one device and
with the sharded oracle.

The script exits non-zero, without the last line, when JAX finds no TPU,
when any phase raises, any request fails or any check misses. Its last
stdout line is one JSON object: {"ok": true, "device": {...}}.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import functools
import json
import pathlib
import sys
import time
import traceback

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
# runs from a bare checkout: the package and the test oracle by path
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]

PART_ROWS = 200_000  # bench/datasets.py SPECS["part"].paper_rows
TRAIN_QUERIES = 8  # the fit's training workload
N_CONJ, N_DNF = 16, 16  # served requests per set: conjunctive, DNF
N_SHARD = 8  # --chips 4: conjunctive and DNF requests each
BATCH_SIZE = 8
EXACT_FLOOR = 1.0  # exact paths: every request, up to float ties
LOCAL_FLOOR = 0.95  # generous-budget candidate-local mean (test_oracle.py)


class SmokeFailure(AssertionError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# observation: compile seconds, and the compiled text of each jitted group
# ---------------------------------------------------------------------------

class CompileClock:
    """Sums the backend compile seconds JAX reports, and the compile seconds
    its persistent cache saved (cache hits), since the last lap."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    SAVED = "/jax/compilation_cache/compile_time_saved_sec"

    def __init__(self):
        import jax

        self.seconds = self.saved = 0.0
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.COMPILE:
            self.seconds += duration
            self.n += 1
        elif event == self.SAVED:
            self.saved += duration

    def lap(self) -> str:
        out = f"compile {self.seconds:.1f}s over {self.n} programs"
        if self.saved:
            out += f", {self.saved:.1f}s more saved by cache hits"
        self.seconds = self.saved = 0.0
        self.n = 0
        return out


# (module, jitted group function, whether it scores through the Pallas
# gather+score kernel)
GROUP_FNS = (
    ("repro.serve.batch", "_filter_first_batch", False),
    ("repro.serve.batch", "_search_batch", False),
    ("repro.serve.batch", "_rerank_batch", False),
    ("repro.serve.batch", "_gather_rerank_batch", True),
    ("repro.vectordb.ivf", "search_local_batch", True),
    ("repro.vectordb.ivf", "search_local_batch_int8", True),
    ("repro.vectordb.graph", "search_local_batch", True),
    ("repro.vectordb.flat", "filter_first_local_batch", True),
)


class GroupRecorder:
    """Keeps the last call of each execution-group function so a path's
    groups can be compiled again afterwards and their compiled text read."""

    def __init__(self):
        import importlib

        import jax

        self.calls: dict = {}
        self._undo = []
        for mod_name, fn_name, uses_kernel in GROUP_FNS:
            mod = importlib.import_module(mod_name)
            fn = getattr(mod, fn_name)
            key = f"{mod_name.rsplit('.', 1)[1]}.{fn_name}"

            def wrapped(*args, _fn=fn, _key=key, _k=uses_kernel, **kwargs):
                if not any(isinstance(x, jax.core.Tracer)
                           for x in jax.tree.leaves(args)):
                    self.calls[_key] = (_fn, _k, args, kwargs)
                return _fn(*args, **kwargs)

            setattr(mod, fn_name, wrapped)
            self._undo.append((mod, fn_name, fn))

    def take(self) -> dict:
        """-> {group: (uses_kernel, tpu_custom_call in its compiled text)}
        for every group called since the last take."""
        import jax

        out = {}
        for key, (fn, uses_kernel, args, kwargs) in self.calls.items():
            # a jitted group lowers as dispatched (a persistent-cache hit);
            # a plain function is jitted around its static keywords
            low = fn.lower(*args, **kwargs) if hasattr(fn, "lower") else \
                jax.jit(functools.partial(fn, **kwargs)).lower(*args)
            text = low.compile().as_text()
            out[key] = (uses_kernel, "tpu_custom_call" in text)
        self.calls = {}
        return out

    def close(self) -> None:
        for mod, name, fn in self._undo:
            setattr(mod, name, fn)


# ---------------------------------------------------------------------------
# the oracle
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class HostTable:
    """float64 host copy of a table for the NumPy oracle."""

    vectors: list
    scalars: np.ndarray
    schema: object


def host_table(table) -> HostTable:
    return HostTable([np.asarray(v, np.float64) for v in table.vectors],
                     np.asarray(table.scalars), table.schema)


def oracle_masks(ht: HostTable, reqs) -> list:
    """Per request: every row's exact weighted score, NEG where the row
    fails the predicate."""
    from oracle import brute_force_topk

    return [brute_force_topk(ht, [np.asarray(v) for v in q.query_vectors],
                             list(q.weights), q.predicates, q.k)[2]
            for q in reqs]


def check_results(reqs, results, masked) -> list:
    """Every id qualifies and carries its oracle score; -> per-request
    tie-aware recall."""
    from oracle import NEG, tie_aware_recall, tie_tolerance

    recalls = []
    for j, (q, (ids, scores), m) in enumerate(zip(reqs, results, masked)):
        ids = np.asarray(ids).ravel()
        scores = np.asarray(scores, np.float64).ravel()
        got = ids >= 0
        if np.any(m[ids[got]] <= NEG / 2):
            raise SmokeFailure(f"request {j}: returned rows that fail its "
                               f"predicate: {ids[got][m[ids[got]] <= NEG / 2]}")
        exact = m[ids[got]]
        err = np.abs(scores[got] - exact)
        tol = np.asarray([tie_tolerance(float(e)) for e in exact])
        if np.any(err > tol):
            i = int(np.argmax(err - tol))
            raise SmokeFailure(
                f"request {j}: row {int(ids[got][i])} scored "
                f"{scores[got][i]!r}, oracle {exact[i]!r} (tolerance "
                f"{tol[i]:.3g})")
        recalls.append(tie_aware_recall(ids, m, q.k))
    return recalls


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def make_requests(table, n_conj: int, n_dnf: int, seed: int, *,
                  n_vec_used: int = 2) -> list:
    """Conjunctive + DNF requests (2-4 clauses) weighting ``n_vec_used``
    columns."""
    from repro.bench import queries

    return queries.gen_workload(table, n_conj, n_vec_used=n_vec_used,
                                seed=seed + 2 * n_vec_used) \
        + queries.gen_dnf_workload(table, n_dnf, n_vec_used=n_vec_used,
                                   seed=seed + 2 * n_vec_used + 1,
                                   clause_counts=(2, 3, 4))


def build(rows: int, seed: int, n_train: int, cfg=None, clock=None):
    """datasets.make -> BoomHQ(...) -> fit. -> (bq, seconds), logging each
    step's seconds (and compile seconds, given a ``CompileClock``)."""
    from repro.bench import datasets, queries
    from repro.core.boomhq import BoomHQ, BoomHQConfig

    t_all = t0 = time.perf_counter()

    def step(name):
        nonlocal t0
        t, t0 = t0, time.perf_counter()
        log(f"  {name}: {t0 - t:.1f}s"
            + (f" ({clock.lap()})" if clock is not None else ""))

    table = datasets.make("part", rows=rows, seed=seed)
    train = queries.gen_workload(table, n_train, n_vec_used=2, seed=seed + 1)
    step("table")
    bq = BoomHQ(table, cfg if cfg is not None else BoomHQConfig())
    step("IVF indexes, histograms, graphs")
    bq.fit(train)
    step(f"fit on {n_train} training queries")
    return bq, time.perf_counter() - t_all


def serve(bq, reqs, batch_size: int, *, warm: bool = True) -> list:
    """warm_bucket_ladder (unless not ``warm``), then the requests through
    AsyncServingEngine / serve_stream. -> [(ids, scores)] in request order;
    raises unless every request resolved ok."""
    from repro.serve.batch import warm_bucket_ladder
    from repro.serve.queue import OK, AsyncServingEngine, serve_stream

    if warm:
        warm_bucket_ladder(bq.execute_batch, reqs, batch_size)
    engine = AsyncServingEngine(bq, batch_size=batch_size, max_wait=0.01)
    served = asyncio.run(serve_stream(engine, reqs))
    bad = [(r.seq, r.status) for r in served if r.status != OK]
    if bad:
        raise SmokeFailure(f"requests not served ok: {bad}")
    return [r.result for r in served]


@contextlib.contextmanager
def forced(bq, force=None, plan=None):
    """Pin the scoring path (``CostModel(force=...)``) and, optionally,
    every request's plan, as the tests do; restore the learned defaults."""
    from repro.serve.batch import CostModel

    bq.bind_cost_model(CostModel(force=force) if force else None)
    if plan is not None:
        bq.optimize_batch = lambda qs, **kw: [plan] * len(qs)
    try:
        yield
    finally:
        bq.__dict__.pop("optimize_batch", None)
        bq.bind_cost_model()


def scoring_paths(bq):
    """(name, request set, forced path, forced plan, recall floor, floor on
    every request) for the one-chip run. Request sets: "mixed" weights both
    columns; "single" one column.

    The candidate-local IVF paths run at the generous budget of
    tests/test_oracle.py (every cluster probed, scan uncapped) on the
    single-column set: there the probe is exhaustive and the 0.95 floor
    applies. Over two weighted columns a plan's candidates are each
    column's top-k_i, and a weighted top-10 row can rank below the
    largest k_i (80) in both columns, whatever the budget."""
    from repro.core.query import ExecutionPlan, SubqueryParams
    from repro.serve.batch import CANDIDATE_LOCAL, DENSE

    n_vec, n = bq.table.schema.n_vec, bq.table.n_rows
    generous = ExecutionPlan("index_scan", tuple(
        SubqueryParams(k_mult=8, nprobe=bq.indexes[i].n_clusters,
                       max_scan=n) for i in range(n_vec)))
    return (
        ("dense", "mixed", DENSE, ExecutionPlan(
            "filter_first", tuple(SubqueryParams() for _ in range(n_vec)),
            max_candidates=n), EXACT_FLOOR, True),
        ("candidate_local_fp32", "single", CANDIDATE_LOCAL, generous,
         LOCAL_FLOOR, False),
        ("candidate_local_int8", "single", CANDIDATE_LOCAL,
         dataclasses.replace(generous, precision="int8"), LOCAL_FLOOR, False),
        ("graph", "mixed", None, ExecutionPlan(
            "graph", tuple(SubqueryParams(k_mult=8, iterative=False)
                           for _ in range(n_vec)),
            beam_width=16, n_hops=8), None, False),
        ("learned", "mixed", None, None, None, False),
    )


def run_path(bq, reqs, masked, *, name, force, plan, floor, every,
             batch_size, clock, recorder):
    """Serve ``reqs`` down one scoring path and check them. -> stats."""
    t0 = time.perf_counter()
    clock.lap()
    with forced(bq, force, plan):
        results = serve(bq, reqs, batch_size)
    wall = time.perf_counter() - t0
    compile_s = clock.lap()
    groups = recorder.take() if recorder is not None else {}
    recalls = check_results(reqs, results, masked)
    stats = {"path": name, "served": len(results),
             "mean_recall": float(np.mean(recalls)),
             "min_recall": float(np.min(recalls)),
             "compile": compile_s, "wall_s": wall, "groups": groups}
    if floor is not None:
        got = stats["min_recall"] if every else stats["mean_recall"]
        if got < floor - 1e-9:
            raise SmokeFailure(f"{name}: recall {got} below floor {floor} "
                               f"({'every request' if every else 'mean'})")
    return stats


def require_kernel(stats) -> None:
    """Paths that score through the gather kernel must show it compiled."""
    used = {g: has for g, (uses, has) in stats["groups"].items() if uses}
    if stats["path"] in ("candidate_local_fp32", "candidate_local_int8",
                         "graph") and not used:
        raise SmokeFailure(f"{stats['path']}: no gather-kernel group ran")
    missing = [g for g, has in used.items() if not has]
    if missing:
        raise SmokeFailure(f"{stats['path']}: no tpu_custom_call in the "
                           f"compiled text of {missing}")


def describe_devices(arr) -> str:
    """'dev:rows' for each device holding a piece of ``arr``."""
    return ", ".join(
        f"{s.device.id}:{s.index[0].start or 0}-"
        f"{s.index[0].stop if s.index[0].stop is not None else arr.shape[0]}"
        for s in sorted(arr.addressable_shards, key=lambda s: s.device.id))


def shard_phase(bq, reqs, masked, ht, *, n_chips, batch_size):
    """Serve ``reqs`` over a ``n_chips``-device mesh and over logical
    shards, for the exact sharded scan and the per-shard probing route;
    compare ids/scores between the two and, for the exact scan, with the
    sharded oracle. -> stats per route. No bucket-ladder warm-up: it would
    compile every batch bucket of both routes, on and off the mesh, and
    only the served buckets are compared."""
    import jax
    from jax.sharding import Mesh

    from oracle import sharded_brute_force_topk, tie_tolerance
    from repro.serve.batch import DENSE, SHARDED_LOCAL

    if len(jax.devices()) < n_chips:
        raise SmokeFailure(f"--chips {n_chips} needs {n_chips} devices, "
                           f"found {len(jax.devices())}")
    mesh = Mesh(np.array(jax.devices()[:n_chips]), ("data",))
    out = []
    for route in (DENSE, SHARDED_LOCAL):
        with forced(bq, route):
            t0 = time.perf_counter()
            bq.bind_shards(n_chips, mesh=mesh)
            placed = bq._serving_table()
            for i, v in enumerate(placed.vectors):
                log(f"  column {i} {tuple(v.shape)}: rows on devices "
                    f"[{describe_devices(v)}]")
            log(f"  scalars: [{describe_devices(placed.scalars)}]; int8 "
                f"replica 0: [{describe_devices(placed.vectors_i8[0])}]")
            on_mesh = serve(bq, reqs, batch_size, warm=False)
            mesh_s = time.perf_counter() - t0
            bq.bind_shards(n_chips)
            logical = serve(bq, reqs, batch_size, warm=False)
            bq.bind_shards()
        recalls = check_results(reqs, on_mesh, masked)
        check_results(reqs, logical, masked)
        n_same = 0
        for j, ((mi, ms), (li, ls)) in enumerate(zip(on_mesh, logical)):
            mi, li = np.asarray(mi), np.asarray(li)
            ms, ls = np.asarray(ms, np.float64), np.asarray(ls, np.float64)
            tol = np.asarray([tie_tolerance(float(x)) for x in ls])
            if np.any(np.abs(ms - ls) > tol):
                raise SmokeFailure(f"{route} request {j}: mesh scores {ms} "
                                   f"!= logical-shard scores {ls}")
            n_same += int(np.array_equal(mi, li))
            if route == DENSE:
                q = reqs[j]
                _, o_scores, _ = sharded_brute_force_topk(
                    ht, [np.asarray(v) for v in q.query_vectors],
                    list(q.weights), q.predicates, q.k, n_shards=n_chips)
                o_scores = o_scores[: ms.shape[0]]
                tol = np.asarray([tie_tolerance(float(x)) for x in o_scores])
                if np.any(np.abs(ms - o_scores) > tol):
                    raise SmokeFailure(f"request {j}: mesh scores {ms} != "
                                       f"sharded oracle {o_scores}")
        out.append({"route": route, "served": len(on_mesh),
                    "ids_identical": n_same,
                    "mean_recall": float(np.mean(recalls)),
                    "mesh_wall_s": mesh_s})
    return out


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.common.compile_cache import place_compile_cache

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        log(f"FAIL: JAX found no TPU (platform {dev.platform!r}); this "
            f"smoke runs only on the chip")
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; compile "
        f"cache {place_compile_cache()}")
    failures = []
    try:
        clock = CompileClock()
        if args.chips == 4:
            from repro.bench import datasets
            from repro.core.boomhq import BoomHQ, BoomHQConfig

            t0 = time.perf_counter()
            table = datasets.make("part", rows=PART_ROWS, seed=args.seed)
            # no fit: the exact sharded scan ignores plans, and the probing
            # route runs the default plans; the graph tier is single-device
            bq = BoomHQ(table, BoomHQConfig(graph_degree=0))
            setup = time.perf_counter() - t0
        else:
            bq, setup = build(PART_ROWS, args.seed, TRAIN_QUERIES,
                              clock=clock)
            table = bq.table
        t0 = time.perf_counter()
        ht = host_table(table)
        if args.chips == 4:
            sets = {"mixed": make_requests(table, N_SHARD, N_SHARD,
                                           args.seed)}
        else:
            sets = {"mixed": make_requests(table, N_CONJ, N_DNF, args.seed),
                    "single": make_requests(table, N_CONJ, N_DNF, args.seed,
                                            n_vec_used=1)}
        masks = {k: oracle_masks(ht, v) for k, v in sets.items()}
        log(f"setup: {table.n_rows} rows x {len(table.vectors)} columns, "
            f"{setup:.1f}s ({clock.lap()}); oracle for "
            f"{sum(map(len, sets.values()))} requests "
            f"{time.perf_counter() - t0:.1f}s")
        if args.chips == 4:
            for s in shard_phase(bq, sets["mixed"], masks["mixed"], ht,
                                 n_chips=4, batch_size=BATCH_SIZE):
                log(f"sharded {s['route']}: backend {dev.platform} "
                    f"{dev.device_kind} x4, served {s['served']} ok, "
                    f"{s['ids_identical']} with ids identical to logical "
                    f"shards, mean recall {s['mean_recall']:.4f}, mesh "
                    f"wall {s['mesh_wall_s']:.1f}s, {clock.lap()}")
        else:
            recorder = GroupRecorder()
            for name, rset, force, plan, floor, every in scoring_paths(bq):
                try:
                    s = run_path(bq, sets[rset], masks[rset], name=name,
                                 force=force, plan=plan, floor=floor,
                                 every=every, batch_size=BATCH_SIZE,
                                 clock=clock, recorder=recorder)
                    require_kernel(s)
                except Exception as e:  # noqa: BLE001 — report every path
                    traceback.print_exc()
                    failures.append(f"{name}: {e!r}"[:500])
                    log(f"path {name}: FAILED {e!r}"[:2000])
                    continue
                groups = " ".join(f"{g}={has}" for g, (_, has)
                                  in sorted(s["groups"].items()))
                log(f"path {name}: backend {dev.platform} {dev.device_kind}"
                    f", {rset} requests served {s['served']} ok, mean "
                    f"recall {s['mean_recall']:.4f} (min "
                    f"{s['min_recall']:.4f}), wall {s['wall_s']:.1f}s, "
                    f"{s['compile']}, tpu_custom_call: "
                    f"{groups or 'no groups'}")
            recorder.close()
    except Exception as e:  # noqa: BLE001 — any phase failing fails the run
        traceback.print_exc()
        failures.append(repr(e)[:500])
    if failures:
        log("FAIL: " + "; ".join(failures))
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
