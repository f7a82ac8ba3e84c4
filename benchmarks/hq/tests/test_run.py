"""A run refuses a machine without a TPU, and with the chip check skipped
it drives the whole path on the CPU: a sound system reads correct, a
broken one, or the lower-precision control, does not."""
import copy
import dataclasses
import json

import numpy as np
import pytest

from benchmarks.hq import check, control, run, spec
from benchmarks.hq.tests.conftest import part_cell, sift_cell

def test_run_refuses_a_machine_without_a_tpu(capsys, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
    rc = run.main(["--workload", "sift.narrow.steady", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc != 0
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def _tiny(cell):
    cfg = copy.deepcopy(cell.config)
    tr = copy.deepcopy(cell.traffic)
    cfg["table"]["rows"] = 1500
    cfg["boomhq"].update(n_clusters=16,
                         graph_degree=min(8, cfg["boomhq"]["graph_degree"]))
    cfg["boomhq"]["encoder"].update(frozen_steps=10, ae_steps=10, sample=512)
    cfg["boomhq"]["rewriter"].update(steps=20)
    cfg["fit"]["train_queries"] = 4
    tr["pool"]["size"] = 24
    tr["batch_size"] = 8
    tr["arrivals"]["rate_per_s"] = 24
    return dataclasses.replace(cell, config=cfg, traffic=tr)


@pytest.fixture(scope="module", params=["part.mhq.peak", "sift.steady"])
def prepared(request):
    cell = part_cell() if request.param == "part.mhq.peak" else sift_cell()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run, "REPLAY_SECONDS", 0.5)
        mp.setattr(run, "EXERCISE_ROUNDS", 1)
        return run.prepare(_tiny(cell), 2**31 + 3)


def _broken(fault: str, real):
    last = {}

    def execute_batch(self, queries, **kw):
        out = real(self, queries, **kw)
        if fault == "stale":
            # the state is never updated: every batch returns the first
            # batch's answers
            prev = last.setdefault("out", out)
            out = [prev[i % len(prev)] for i in range(len(queries))]
        elif fault == "half":
            # half of the batch left out: its answers come back empty
            h = (len(out) + 1) // 2
            out = out[:h] + [(np.full_like(ids, -1),
                              np.full_like(s, -np.inf))
                             for ids, s in out[h:]]
        elif fault == "altered":
            # an answer altered where it is produced: the best row of the
            # first query becomes its neighbour
            ids, s = out[0]
            ids = np.asarray(ids).copy()
            ids[0] = (ids[0] + 1) % self.table.n_rows
            out = [(ids, s)] + list(out[1:])
        return out

    return execute_batch


def _filter_first(self, qs, **kw):
    from repro.core.query import ExecutionPlan, SubqueryParams

    return [ExecutionPlan("filter_first",
                          tuple(SubqueryParams() for _ in range(q.n_vec)))
            for q in qs]


# (fault, every query planned as filter_first): only answers of that plan
# are owed in full, so the half left out is read where it is planned
CASES = ((None, False), (None, True), ("stale", False), ("half", True),
         ("altered", False))


@pytest.mark.parametrize("fault,pinned", CASES,
                         ids=[f"{f}{'-filter_first' if p else ''}"
                              for f, p in CASES])
def test_a_broken_timed_path_reads_not_correct(prepared, fault, pinned,
                                               monkeypatch):
    from repro.core.boomhq import BoomHQ

    if pinned:
        monkeypatch.setattr(BoomHQ, "optimize_batch", _filter_first)
    if fault is not None:
        monkeypatch.setattr(BoomHQ, "execute_batch",
                            _broken(fault, BoomHQ.execute_batch))
    m = run.measure(prepared, 11, 1.5,
                    prepared.cell.traffic["arrivals"]["rate_per_s"])
    numbers, recalls = run.judge(prepared, m)
    ok = check.verdict(numbers, prepared.cell.traffic["limits"])
    assert m["summary"]["attempted"] > 0 and len(recalls) > 0
    assert ok == (fault is None), numbers
    if pinned:
        assert m["filter_first"]
    if fault == "half":
        assert numbers["exact_short"] > 0


@pytest.mark.parametrize("name", ["part.mhq.peak", "sift.steady"])
def test_control_reads_not_correct(name):
    cell = part_cell() if name == "part.mhq.peak" else sift_cell()
    cfg = copy.deepcopy(cell.config)
    tr = copy.deepcopy(cell.traffic)
    cfg["table"]["rows"] = 3000 if name.startswith("part") else 20000
    tr["pool"]["size"] = 32
    cell = dataclasses.replace(cell, config=cfg, traffic=tr)
    numbers = control.control_numbers(cell, 2**31 + 9)
    assert not check.verdict(numbers, cell.traffic["limits"]), numbers
    assert numbers["score_err"] > cell.traffic["limits"]["score_err"]
