"""The trace reduction gives known busy, idle and kernel times."""
import pathlib

import pytest

from benchmarks.hq import spec, trace

DATA = pathlib.Path(__file__).parent / "data"
MS = 1e6  # ns


def _synthetic():
    return {
        "window": [0.0, 1000 * MS],
        "device_ops": [
            ["fusion.1", 100 * MS, 200 * MS, "jit_a"],
            ["%gather_score_blocks.1", 250 * MS, 100 * MS, "jit_search_local_batch"],
            ["fusion.2", 600 * MS, 100 * MS, "jit_b"],
            ["%gather_score_blocks.1", 950 * MS, 100 * MS, "jit_search_local_batch"],
            ["before", -50 * MS, 20 * MS, "jit_c"],
        ],
        "host_spans": [
            ["hq.planner", 0.0, 120 * MS],
            ["hq.execute_batch", 300 * MS, 500 * MS],
            ["hq.group.ivf_local", 650 * MS, 30 * MS],
        ],
    }


def test_busy_idle_and_gaps():
    rec = _synthetic()
    assert trace.window_seconds(rec) == pytest.approx(1.0)
    # 100..350 (two overlapping ops), 600..700, 950..1000 (clipped)
    assert trace.busy_seconds(rec) == pytest.approx(0.4)
    assert trace.idle_gaps(rec) == [[0.0, 100 * MS], [350 * MS, 600 * MS],
                                    [700 * MS, 950 * MS]]
    by = dict(trace.idle_by_span(rec))
    assert by == pytest.approx({"hq.planner": 0.1, "hq.execute_batch": 0.25,
                               "no span": 0.25})


def test_kernel_time_and_top_ops():
    rec = _synthetic()
    k = trace.op_seconds(rec, trace.is_gather_kernel)
    assert k == pytest.approx({"%gather_score_blocks.1": 0.15})
    top = trace.top_ops(rec)
    assert top[0] == ["fusion.1", pytest.approx(0.2)]
    assert {n for n, _ in top} == {"fusion.1", "%gather_score_blocks.1", "fusion.2"}


def test_readers_on_a_record():
    rec = {"trace": _synthetic(), "batches": 10, "requests": 250,
           "dispatch": {"dense": 30, "candidate_local": 10},
           "spans": {"hq.planner": [0.01] * 10},
           "kernel": {"least_s": 0.03, "bytes": 0.0, "ops": 0.0},
           "late_ms": [0.5] * 19 + [4.0], "p95_ms": 210.5,
           "lowered_in_window": 0}

    def read(name):
        return spec.reader(name + ".peak")(rec)

    assert read("device.idle_share") == pytest.approx(0.6)
    assert read("kernel.gather.ms_per_batch") == pytest.approx(15.0)
    assert read("gather_score_roofline") == pytest.approx(20.0)
    assert read("frontend.batch_fill") == pytest.approx(25.0)
    assert read("dispatch.groups_per_batch") == pytest.approx(4.0)
    assert read("dispatch.local_share") == pytest.approx(0.25)
    assert read("planner.ms_per_batch") == pytest.approx(10.0)
    assert read("jit.compiles_in_window") == 0.0
    assert read("latency.p95_ms") == 210.5
    # a window with no gather kernel reads nothing, never 0 %
    rec["trace"]["device_ops"] = [o for o in rec["trace"]["device_ops"]
                                  if o[0] != "%gather_score_blocks.1"]
    assert read("gather_score_roofline") is None
    assert read("kernel.gather.ms_per_batch") is None


def test_recorded_chip_trace():
    """A 300 ms slice of a traced `sift.steady` window on a TPU v5 lite
    (op names as the trace gives them, times in ns from the slice start):
    busy, idle and kernel times as measured by hand."""
    rec = trace.load(str(DATA / "sift_trace_small.json"))
    assert trace.window_seconds(rec) == pytest.approx(0.3)
    # 220 op events, nested and overlapping; their union is 13.765 ms
    assert len(rec["device_ops"]) == 220
    assert trace.busy_seconds(rec) == pytest.approx(0.013765006, rel=1e-9)
    gaps = trace.idle_gaps(rec)
    assert sum(e - s for s, e in gaps) * 1e-9 == pytest.approx(
        0.3 - 0.013765006, rel=1e-9)
    # the one gather-kernel launch in the slice: 1.022417 ms
    assert trace.op_seconds(rec, trace.is_gather_kernel) == pytest.approx(
        {"%gather_score_blocks.1": 0.001022417})
    # the host was inside execute_batch through every idle gap
    assert trace.idle_by_span(rec) == [["hq.execute_batch",
                                        pytest.approx(0.286234994)]]
