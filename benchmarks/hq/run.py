"""One run of one benchmark cell on the chip.

    python3 -m benchmarks.hq.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

In order: refuse a machine where JAX finds no TPU (or fewer chips than the
cell asks for); make the cell's table and its pool's predicates from its
configuration's ``data_seed``, and the pool's query vectors from
``--seed``; build the indexes and fit BoomHQ; warm the shapes the cell's
traffic uses; drive an open loop of independent callers through ``AsyncServingEngine.submit``
-> ``BoomHQ.execute_batch`` for ``--seconds``; judge every answer against
the plain reference; print one JSON line. With ``--trace 1`` the window is
traced and the line carries the cell's per-layer metrics instead of its
end-to-end ones.

Everything about a cell comes from files found by name (``spec.py``):
BENCHMARK.json, ``configs/<config>.json`` and ``workloads/<traffic>.json``.
Progress goes to standard error; set-up by phase, the plan mix and the
window summary are earlier lines of standard output; the numbers compared
with their limits are the last lines of standard error.
"""
from __future__ import annotations

import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _process_start() -> float:
    """perf_counter instant at which this process started."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return now - max(0.0, age)
    except (OSError, ValueError, IndexError):
        return now


T_PROCESS = _process_start()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402
from collections import Counter  # noqa: E402

import numpy as np  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def say(msg: str) -> None:
    print(msg, flush=True)


class NoChip(RuntimeError):
    pass


# warm-up: rounds of drawn batches of every size, and seconds of replay
# through the serving front end at the cell's rate
EXERCISE_ROUNDS = 6
REPLAY_SECONDS = 2.0


# ---------------------------------------------------------------------------
# the program's side: tables, queries, the fitted system
# ---------------------------------------------------------------------------

def program_table(data):
    from repro.vectordb.table import ScalarCol, Table, TableSchema, VectorCol

    schema = TableSchema(
        vector_cols=tuple(VectorCol(v["name"], v["dim"])
                          for v in data.vector_cols),
        scalar_cols=tuple(ScalarCol(s["name"], s["kind"], s["n_categories"])
                          for s in data.scalar_cols),
        metric=data.metric)
    return Table(schema, list(data.vectors), data.scalars)


def _expr(tree):
    from repro.vectordb.algebra import col

    op = tree[0]
    if op == "range":
        return col(tree[1]).between(tree[2], tree[3])
    if op == "eq":
        return col(tree[1]) == tree[2]
    if op == "in":
        return col(tree[1]).isin(tree[2])
    if op == "not":
        return ~_expr(tree[1])
    parts = [_expr(t) for t in tree[1]]
    out = parts[0]
    for p in parts[1:]:
        out = (out | p) if op == "or" else (out & p)
    return out


def program_query(q, schema):
    """A pool query as the program's MHQ: conjunctions as ``Predicates``
    (as the repo's conjunctive generator makes them), DNFs through the
    builder algebra."""
    import jax.numpy as jnp

    from repro.core.query import MHQ
    from repro.vectordb.predicates import Predicates

    m = schema.n_scalar
    if q.kind == "conj":
        conds = {}
        for part in q.tree[1]:
            lo, hi = (part[2], part[2]) if part[0] == "eq" \
                else (part[2], part[3])
            conds[part[1]] = (lo, hi)
        pred = Predicates.from_conditions(m, conds)
    else:
        pred = _expr(q.tree).compile(schema)
    return MHQ(query_vectors=tuple(jnp.asarray(v) for v in q.vectors),
               weights=tuple(q.weights), predicates=pred, k=q.k,
               recall_target=q.recall_target)


def boomhq_config(cfg: dict):
    from repro.core.boomhq import BoomHQConfig
    from repro.core.data_encoder import DataEncoderConfig
    from repro.core.rewriter import RewriterConfig

    b = dict(cfg["boomhq"])
    enc = DataEncoderConfig(**b.pop("encoder", {}))
    rew = RewriterConfig(**b.pop("rewriter", {}))
    return BoomHQConfig(encoder=enc, rewriter=rew, **b)


class PhaseClock:
    """Seconds spent inside wrapped program calls, by phase."""

    def __init__(self):
        self.seconds: Counter = Counter()
        self._undo = []

    def wrap(self, owner, name: str, phase: str):
        fn = getattr(owner, name)
        clock = self

        def wrapped(*args, **kwargs):
            if phase not in clock.seconds:
                log(f"set-up: {phase}")
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                clock.seconds[phase] += time.perf_counter() - t0

        setattr(owner, name, wrapped)
        self._undo.append((owner, name, fn))

    def close(self):
        for owner, name, fn in reversed(self._undo):
            setattr(owner, name, fn)


def build_system(cfg: dict, table, train: list, setup: dict):
    """BoomHQ over ``table``, fitted on ``train``; build and fit seconds by
    phase go into ``setup``."""
    import jax

    from repro.core import boomhq as bmod
    from repro.core.data_encoder import DataEncoder
    from repro.core.executor import ENGINES
    from repro.core.rewriter import MHQRewriter
    from repro.vectordb import flat, graph, histogram, ivf

    clock = PhaseClock()
    clock.wrap(ivf, "build", "ivf")
    clock.wrap(graph, "build", "graph")
    clock.wrap(histogram, "build", "histograms")
    clock.wrap(DataEncoder, "fit", "fit.encoder")
    clock.wrap(flat, "ground_truth", "fit.labels")
    clock.wrap(bmod, "generate_label", "fit.labels")
    clock.wrap(MHQRewriter, "fit", "fit.rewriter")
    try:
        t0 = time.perf_counter()
        bq = bmod.BoomHQ(table, boomhq_config(cfg),
                         engine=ENGINES[cfg["engine"]])
        jax.block_until_ready([i.centroids for i in bq.indexes])
        t1 = time.perf_counter()
        bq.fit(train)
        t2 = time.perf_counter()
    finally:
        clock.close()
    for k in ("ivf", "graph", "histograms"):
        setup[k] = clock.seconds[k]
    setup["indexes.other"] = (t1 - t0) - sum(setup[k] for k in
                                             ("ivf", "graph", "histograms"))
    for k in ("fit.encoder", "fit.labels", "fit.rewriter"):
        setup[k] = clock.seconds[k]
    setup["fit.other"] = (t2 - t1) - sum(
        setup[k] for k in ("fit.encoder", "fit.labels", "fit.rewriter"))
    return bq


# ---------------------------------------------------------------------------
# warm-up: the shapes of this cell's traffic, and no others
# ---------------------------------------------------------------------------

def buckets(batch_size: int, n_rows: int) -> list:
    """Batch buckets ``execute_batch`` can see: powers of two up to the
    batch size, capped at the size the program splits large tables into."""
    from repro.serve.batch import (
        MAX_BATCH_KERNEL, SLOT_BUDGET, next_bucket, pow2_at_most,
    )

    limit = pow2_at_most(max(1, min(MAX_BATCH_KERNEL,
                                    SLOT_BUDGET // max(n_rows, 1))))
    top = min(next_bucket(batch_size), limit)
    out, b = [], 1
    while b <= top:
        out.append(b)
        b <<= 1
    return out


def plan_keys(bq, queries: list, b: int) -> list:
    """Each query's execution-group key when planned in batches of ``b``."""
    bx = bq._batched_executor()
    keys = []
    for s in range(0, len(queries), b):
        part = queries[s:s + b]
        if len(part) < b:
            part = part + queries[: b - len(part)]
        plans = bq.optimize_batch(part)
        keys.extend(bx._group_key(q, bx.legalize(p))
                    for q, p in zip(part, plans))
    return keys[: len(queries)]


def warm_up(bq, queries: list, batch_size: int) -> dict:
    """Run, for every batch bucket ``b`` the traffic can form and every
    execution group its queries fall into at that bucket, batches of ``b``
    in which that group holds each power-of-two size up to ``b``: the plan
    programs, the group programs and the escalations of this traffic.
    -> {"batches": n, "keys": distinct group keys}."""
    n_rows = bq.table.n_rows
    n_batches = 0
    all_keys = set()
    for b in buckets(batch_size, n_rows):
        keys = plan_keys(bq, queries, b)
        groups: dict = {}
        for q, k in zip(queries, keys):
            groups.setdefault(k, []).append(q)
        all_keys.update(groups)
        filler = [q for q in queries]
        fi = 0
        for key, members in groups.items():
            others = [q for q, k in zip(queries, keys) if k != key] or filler
            s = 1
            while s <= b:
                batch = [members[i % len(members)] for i in range(s)]
                for _ in range(b - s):
                    batch.append(others[fi % len(others)])
                    fi += 1
                bq.execute_batch(batch)
                n_batches += 1
                s <<= 1
    return {"batches": n_batches, "keys": len(all_keys)}


def escalations(bq, queries: list, short: list, batch_size: int) -> int:
    """Batches of each size ``m`` the program executes as one, holding
    ``s`` = 1..m queries that qualify fewer than k rows. Every plan comes
    back short on those, so each batch escalates a subset of exactly ``s``
    queries through every round of the default plan's re-expansion: the
    programs, and the eager ops at unbucketed sizes, that a burst of such
    queries meets in the window. ``short``: indices into ``queries``.
    -> batches run."""
    if not short:
        return 0
    rest = sorted(set(range(len(queries))) - set(short)) or short
    top = min(batch_size, buckets(batch_size, bq.table.n_rows)[-1])
    n = 0
    for m in range(1, top + 1):
        for s in range(1, m + 1):
            pick = [short[(n + i) % len(short)] for i in range(s)] + \
                [rest[(n + i) % len(rest)] for i in range(m - s)]
            bq.execute_batch([queries[i] for i in pick])
            n += 1
    return n


def exercise(bq, queries: list, batch_size: int, seed: int) -> int:
    """``EXERCISE_ROUNDS`` batches of each size the program executes as one
    (a formed batch up to ``batch_size``, or the sub-batches it is split
    into at large tables), drawn from the pool by the seed. Underfill
    escalation and iterative re-expansion run on the subsets of a batch
    that come back short, at sizes no systematic pass can aim at; these
    batches meet them before the window does. -> batches run."""
    from benchmarks.hq.data import seed_words

    rng = np.random.default_rng(seed_words(seed, 6))
    top = min(batch_size, buckets(batch_size, bq.table.n_rows)[-1])
    n = 0
    for _ in range(EXERCISE_ROUNDS):
        for m in range(1, top + 1):
            pick = rng.choice(len(queries), size=m, replace=False)
            bq.execute_batch([queries[i] for i in pick])
            n += 1
    return n


def plan_mix(bq, queries: list, batch_size: int) -> dict:
    """Counts by strategy and precision of the pool's plans at the largest
    bucket, and a hash of the plan codes."""
    b = buckets(batch_size, bq.table.n_rows)[-1]
    plans = []
    for s in range(0, len(queries), b):
        plans.extend(bq.optimize_batch(queries[s:s + b]))
    mix = Counter(f"{p.strategy}@{p.precision}" for p in plans)
    digest = hashlib.sha256(
        "\n".join(p.describe() for p in plans).encode()).hexdigest()[:16]
    return {"counts": dict(sorted(mix.items())), "hash": digest}


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

async def serve_window(bq, queries: list, due, idx, *, seconds: float,
                       batch_size: int, max_wait: float, probes, trace_dir,
                       plans=None):
    """Drive the open loop; -> (requests, window counters, t0)."""
    import jax

    from benchmarks.hq import loadgen
    from repro.serve.queue import OK, AsyncServingEngine

    engine = AsyncServingEngine(bq, batch_size=batch_size, max_wait=max_wait)
    await engine.start()

    async def submit(q):
        r = await engine.submit(q, timeout=None)
        return (loadgen.OK if r.status == OK else r.status), r.result

    def snapshot():
        disp = bq._batched.dispatcher.counts if bq._batched else {}
        return {"batches": engine._n_batches, "served": len(engine._served),
                "dispatch": dict(disp), "t": time.perf_counter()}

    if trace_dir is not None:
        # host spans are the benchmark's own annotations: no tracing of
        # every Python call, which would slow the host-bound path it reads
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    window_span = jax.profiler.TraceAnnotation("hq.window")
    counters = {}

    def on_close():
        end = snapshot()
        if plans is not None:
            plans.active = False
        if probes is not None:
            probes.active = False
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        counters["end"] = end

    counters["start"] = snapshot()
    t0 = time.perf_counter() + 0.01
    if plans is not None:
        plans.active = True
    if probes is not None:
        window_span.__enter__()
        probes.active = True
    req = await loadgen.drive(submit, queries, due, idx, t0=t0,
                              window=seconds, on_close=on_close)
    await engine.stop()
    return req, counters, t0


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except Exception:  # noqa: BLE001 — a backend without the statistic
            pass
    return max(peaks) if peaks else 0


def finite(x: float) -> float:
    """A JSON-safe number: a latency that never ended reads as 1e12 ms."""
    return x if math.isfinite(x) else 1e12


class Prepared:
    """Set-up's product: the data, the pool, the fitted system."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def prepare(cell, seed: int) -> Prepared:
    """Data, query pool, indexes, fit and warm-up; set-up by phase."""
    import jax

    from benchmarks.hq import data as hqdata
    from benchmarks.hq import probes as hqprobes
    from benchmarks.hq import reference, traffic

    compiles = hqprobes.CompileCounter()
    cfg, tr = cell.config, cell.traffic
    setup: dict = {}

    t = time.perf_counter()
    # one deployment per cell: its table, the fit's training queries and
    # the pool's predicates come from the configuration's own seed, as a
    # public dataset and its query set are fixed; --seed draws the query
    # vectors and the order of the arrivals
    data = hqdata.make(cfg["table"], cfg["data_seed"])
    setup["data"] = time.perf_counter() - t
    log(f"data: {data.n_rows} rows, {setup['data']:.1f}s")

    t = time.perf_counter()
    m = len(data.scalar_cols)

    def selectivity(dnfs):
        return reference.qualifying_counts(dnfs, data.scalars, m) \
            / data.n_rows

    ps = tr["pool"]
    pool = traffic.make_pool(cell, data, selectivity, seed)
    # the fit's training queries follow the traffic the cell serves
    fs = cfg["fit"]
    train = traffic.make_queries(
        data, selectivity, ps["mix"], fs["train_queries"],
        seed=cfg["data_seed"], tag=3, k=tr["k"],
        recall_targets=(fs["recall"],), bins=ps.get("bins", 10),
        oversample=ps.get("oversample", 6),
        sel_range=tuple(ps.get("selectivity", (0.0, 1.0))))
    table = program_table(data)
    queries = [program_query(q, table.schema) for q in pool]
    train_q = [program_query(q, table.schema) for q in train]
    setup["queries"] = time.perf_counter() - t

    bq = build_system(cfg, table, train_q, setup)
    mix = plan_mix(bq, queries, tr["batch_size"])
    say("plan mix: " + json.dumps(mix))

    t = time.perf_counter()
    warm = warm_up(bq, queries, tr["batch_size"])
    short = [i for i, q in enumerate(pool)
             if round(q.selectivity * data.n_rows) < q.k]
    warm["escalated"] = escalations(bq, queries, short, tr["batch_size"])
    warm["exercised"] = exercise(bq, queries, tr["batch_size"], seed)
    # a short replay through the serving front end at the cell's rate
    due, idx = traffic.schedule(tr["arrivals"]["rate_per_s"],
                                REPLAY_SECONDS, len(pool), seed, tag=5)
    asyncio.run(serve_window(bq, queries, due, idx, seconds=float(due[-1]),
                             batch_size=tr["batch_size"],
                             max_wait=tr["max_wait_ms"] / 1e3, probes=None,
                             trace_dir=None))
    setup["warm_up"] = time.perf_counter() - t
    setup_s = time.perf_counter() - T_PROCESS
    say("setup by phase (s): " + json.dumps(
        {k: round(v, 3) for k, v in setup.items()})
        + f"; total {setup_s:.3f}; warm-up {warm['batches']} batches over "
        f"{warm['keys']} group keys, {warm['escalated']} escalating and "
        f"{warm['exercised']} drawn; compile {compiles.compile_s:.1f}s over "
        f"{len(compiles.compiled)} programs, {len(compiles.lowered)} lowered")
    return Prepared(data=data, pool=pool, table=table, bq=bq,
                    queries=queries, setup=setup, setup_s=setup_s, mix=mix,
                    compiles=compiles, cell=cell)


def measure(p: Prepared, seed: int, seconds: float, rate: float, *,
            trace: bool = False, keep_trace: str | None = None) -> dict:
    """One open-loop window at ``rate``; -> what was measured."""
    from benchmarks.hq import loadgen
    from benchmarks.hq import probes as hqprobes
    from benchmarks.hq import trace as hqtrace
    from benchmarks.hq import traffic

    tr = p.cell.traffic
    due, idx = traffic.schedule(rate, seconds, len(p.pool), seed)
    probes = hqprobes.Probes() if trace else None
    plans = hqprobes.PlanLog(p.bq)
    trace_dir = tempfile.mkdtemp(prefix="hq_trace_") if trace else None
    rec = None
    try:
        req, counters, t0 = asyncio.run(serve_window(
            p.bq, p.queries, due, idx, seconds=seconds,
            batch_size=tr["batch_size"], max_wait=tr["max_wait_ms"] / 1e3,
            probes=probes, plans=plans, trace_dir=trace_dir))
        if trace:
            path = hqtrace.newest_xplane(trace_dir)
            rec = hqtrace.extract(path)
            if keep_trace:
                os.makedirs(keep_trace, exist_ok=True)
                hqtrace.save(rec, os.path.join(keep_trace, "record.json"))
                with open(os.path.join(keep_trace, "layout.json"), "w") as f:
                    json.dump(hqtrace.layout(path), f)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if probes is not None:
            probes.close()
        plans.close()
    lowered, compiled = p.compiles.between(t0, t0 + seconds)
    summ = loadgen.summary(req, t0, seconds)
    say(f"window at {rate:g}/s: {summ['attempted']} requests due, "
        f"{summ['served']} served, qps {summ['qps']:.3f}, p50 "
        f"{summ['p50_ms']:.3f} ms, p95 {summ['p95_ms']:.3f} ms, p99 "
        f"{summ['p99_ms']:.3f} ms, send lateness p95 "
        f"{float(np.quantile(summ['late_ms'], 0.95)):.3f} ms; "
        f"{len(lowered)} programs lowered and {len(compiled)} compiled in "
        f"the window {sorted(Counter(lowered).items())}")
    return {"req": req, "summary": summ, "counters": counters, "t0": t0,
            "trace": rec, "lowered": lowered, "probes": probes,
            "filter_first": plans.only(p.queries, "filter_first")}


def judge(p: Prepared, m: dict) -> tuple[dict, list]:
    """Every answer of the window against the plain reference."""
    from benchmarks.hq import check, reference

    t = time.perf_counter()
    tr = p.cell.traffic
    ref = reference.scan_topk(p.data.vectors, p.data.scalars, p.pool,
                              2 * tr["k"], p.data.metric)
    req = m["req"]
    answers = [(int(req.pool_index[i]), r[0], r[1])
               for i, r in enumerate(req.results) if r is not None]
    numbers, recalls, shares = check.judge(
        p.pool, answers, ref, p.data.vectors, p.data.host_scalars,
        p.data.metric, n_failed=m["summary"]["failed"],
        exact_pool=m["filter_first"])
    log(f"reference and comparison {time.perf_counter() - t:.1f}s")
    say(f"recall {float(np.mean(recalls)) if recalls else 0.0!r} over "
        f"{len(recalls)} answers; {len(m['filter_first'])} pool queries "
        f"planned only as filter_first; owed rows missing "
        f"{shares['missing']!r}; empty answers {shares['empty']!r}")
    return numbers, recalls


def layer_record(m: dict, device: dict) -> dict:
    """What the per-layer readers read."""
    from benchmarks.hq import costs, peaks

    d, s = m["counters"]["end"], m["counters"]["start"]
    probes = m["probes"]
    least, nbytes, nops = costs.least_seconds(probes.kernel_calls,
                                              peaks.peaks(device["kind"]))
    return {
        "trace": m["trace"],
        "late_ms": list(map(float, m["summary"]["late_ms"])),
        "p95_ms": finite(m["summary"]["p95_ms"]),
        "requests": d["served"] - s["served"],
        "batches": d["batches"] - s["batches"],
        "dispatch": {k: d["dispatch"].get(k, 0) - s["dispatch"].get(k, 0)
                     for k in d["dispatch"]},
        "spans": probes.spans,
        "kernel": {"least_s": least, "bytes": nbytes, "ops": nops},
        "lowered_in_window": len(m["lowered"]),
    }


def run_cell(cell, seed: int, seconds: float, trace: bool,
             device: dict) -> dict:
    """Everything after the device check; -> the result line's object."""
    from benchmarks.hq import check, spec
    from benchmarks.hq import trace as hqtrace

    p = prepare(cell, seed)
    m = measure(p, seed, seconds, cell.traffic["arrivals"]["rate_per_s"],
                trace=trace)
    mem = memory_peak(cell.chips)
    record = layer_record(m, device) if trace else None
    # the program's state goes before the reference runs
    del p.bq, p.table, p.queries
    m["probes"] = None
    gc.collect()
    numbers, recalls = judge(p, m)
    limits = cell.traffic["limits"]
    recall = float(np.mean(recalls)) if recalls else 0.0
    summ = m["summary"]
    e2e = {"qps": summ["qps"], "p50_ms": finite(summ["p50_ms"]),
           "p95_ms": finite(summ["p95_ms"]), "recall": recall,
           "setup_s": p.setup_s}
    out = {"correct": check.verdict(numbers, limits),
           "attempted": summ["attempted"], "failed": summ["failed"],
           "metrics": {}, "device": dict(device, memory_peak_bytes=mem)}
    if not trace:
        for mt in cell.end_to_end:
            out["metrics"][mt["name"]] = {"value": e2e[mt["name"]],
                                          "unit": mt["unit"]}
    else:
        for mt in cell.per_layer:
            v = spec.reader(mt["name"])(record)
            if v is not None:
                out["metrics"][mt["name"]] = {"value": float(v),
                                              "unit": mt["unit"]}
        rec = record["trace"]
        out["device"]["busy_s"] = hqtrace.busy_seconds(rec)
        out["device"]["window_s"] = hqtrace.window_seconds(rec)
        out["breakdown"] = {"device_ops": hqtrace.top_ops(rec),
                            "idle_gaps": hqtrace.idle_by_span(rec)}
    out["compared"] = check.describe(numbers, limits)
    for k, v in out["compared"].items():
        log(f"compared {k}: {v['value']!r} (limit {v['limit']!r})")
    return out


def configure_process() -> None:
    """For a benchmark process only: the program on the path, and JAX's
    persistent compile cache at a fixed path inside the checkout, holding
    every program however fast it compiled, so a second run finds all."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.common.compile_cache import place_compile_cache

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache {place_compile_cache()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.hq import spec

    cell = spec.cell(args.workload)
    try:
        device = device_info(cell.chips)
    except NoChip as e:
        log(f"FAIL: {e}")
        return 2
    configure_process()
    log(f"device: {device}; cell {cell.name}, seed {args.seed}, "
        f"{args.seconds} s, trace {args.trace}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), device)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
