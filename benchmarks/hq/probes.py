"""What the benchmark observes of the program, from the outside.

``CompileCounter`` (every run) counts new programs from JAX's monitoring
events. ``PlanLog`` (every run) keeps which strategies the planner gave
each query in the window. ``Probes`` (traced runs only) wraps the program's layer entries,
as module attributes, in ``jax.profiler.TraceAnnotation`` spans named
``hq.<layer>``, keeps their host durations, and records each gather-kernel
group call so its bytes and operations can be counted after the window.
Nothing here changes what the program computes.
"""
from __future__ import annotations

import importlib
import time

import jax

from benchmarks.hq import costs

# (module, function, span): the layer entries wrapped in a traced run
GROUP_FNS = (
    ("repro.serve.batch", "_filter_first_batch", "hq.group.filter_first"),
    ("repro.serve.batch", "_search_batch", "hq.group.ivf_dense"),
    ("repro.serve.batch", "_rerank_batch", "hq.group.rerank_dense"),
    ("repro.serve.batch", "_dense_scores", "hq.group.dense_scores"),
    ("repro.serve.batch", "_gather_rerank_batch", "hq.group.rerank_local"),
    ("repro.vectordb.ivf", "search_local_batch", "hq.group.ivf_local"),
    ("repro.vectordb.ivf", "search_local_batch_int8",
     "hq.group.ivf_local_int8"),
    ("repro.vectordb.graph", "search_local_batch", "hq.group.graph"),
    ("repro.vectordb.flat", "filter_first_local_batch",
     "hq.group.filter_first_local"),
)


class CompileCounter:
    """Counts programs lowered (new in this process, whether compiled or
    read from the persistent cache) and backend compiles, with their
    instants, from ``jax.monitoring``."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.lowered: list = []
        self.compiled: list = []
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, fun_name: str = "", **_):
        if event == self.LOWER:
            self.lowered.append((time.perf_counter(), fun_name))
        elif event == self.COMPILE:
            self.compiled.append((time.perf_counter(), fun_name))
            self.compile_s += duration

    def between(self, t0: float, t1: float) -> tuple[list, list]:
        """Names of the programs lowered and compiled in [t0, t1]."""
        return ([n for t, n in self.lowered if t0 <= t <= t1],
                [n for t, n in self.compiled if t0 <= t <= t1])


class PlanLog:
    """The strategies ``bq.optimize_batch`` returned for each query object
    while ``active``, read through an attribute of this instance only."""

    def __init__(self, bq):
        self.bq = bq
        self.active = False
        self.strategies: dict = {}  # id(query) -> {strategy}
        plan = bq.optimize_batch
        log = self

        def optimize_batch(qs, *args, **kwargs):
            plans = plan(qs, *args, **kwargs)
            if log.active:
                for q, p in zip(qs, plans):
                    log.strategies.setdefault(id(q), set()).add(p.strategy)
            return plans

        bq.optimize_batch = optimize_batch

    def only(self, queries: list, strategy: str) -> frozenset:
        """Indices of ``queries`` planned, every time, as ``strategy``."""
        return frozenset(i for i, q in enumerate(queries)
                         if self.strategies.get(id(q)) == {strategy})

    def close(self) -> None:
        del self.bq.optimize_batch


class Probes:
    def __init__(self):
        self.active = False
        self.spans: dict = {}  # span name -> [host seconds]
        self.kernel_calls: list = []  # costs.KernelCall
        self._undo = []
        from repro.core.boomhq import BoomHQ

        self._wrap(BoomHQ, "optimize_batch", "hq.planner")
        self._wrap(BoomHQ, "execute_batch", "hq.execute_batch")
        for mod_name, fn_name, span in GROUP_FNS:
            mod = importlib.import_module(mod_name)
            group = f"{mod_name.rsplit('.', 1)[1]}.{fn_name}"
            self._wrap(mod, fn_name, span, group=group)

    def _wrap(self, owner, name: str, span: str, group: str | None = None):
        fn = getattr(owner, name)
        probes = self

        def wrapped(*args, **kwargs):
            if not probes.active:
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(span):
                out = fn(*args, **kwargs)
            probes.spans.setdefault(span, []).append(
                time.perf_counter() - t0)
            if group is not None:
                probes.kernel_calls.extend(
                    costs.calls_of(group, args, kwargs, out))
            return out

        wrapped.__wrapped__ = fn
        if hasattr(fn, "lower"):  # keep jit's API for callers that use it
            wrapped.lower = fn.lower
        setattr(owner, name, wrapped)
        self._undo.append((owner, name, fn))

    def close(self) -> None:
        for owner, name, fn in reversed(self._undo):
            setattr(owner, name, fn)
        self._undo = []
