"""The readers of the program's own spans, on records and on a traced CPU
window: each reads its span or program, and nothing (never 0) where the
program lacks it."""
import copy
import dataclasses
import importlib.util
import pathlib

import pytest

from benchmarks.hq import run, spec, trace
from benchmarks.hq.tests.conftest import part_cell, sift_cell

MS = 1e6  # ns


def _record():
    """A window of 1000 ms and 10 batches: device busy 100..350, 600..700
    and 950..1000 ms; the program's spans; a filter-first program's ops."""
    return {"batches": 10, "trace": {
        "window": [0.0, 1000 * MS],
        "device_ops": [
            ["fusion.1", 100 * MS, 200 * MS, "jit_filter_first_local_batch"],
            ["%gather_score_blocks.1", 150 * MS, 50 * MS,
             "jit_filter_first_local_batch"],
            ["%gather_score_blocks.1", 250 * MS, 100 * MS,
             "jit_search_local_batch"],
            ["fusion.2", 600 * MS, 100 * MS, "jit_b"],
            ["%gather_score_blocks.1", 950 * MS, 100 * MS,
             "jit_search_local_batch"],
        ],
        "host_spans": [
            ["hq.planner", 0.0, 120 * MS],
            ["hq.execute_batch", 300 * MS, 500 * MS],
            # idle 0..100 and 350..600: 50 ms of the first, 100 of the second
            ["hq.frontend.cut_wait", 50 * MS, 100 * MS],
            ["hq.frontend.cut_wait", 400 * MS, 100 * MS],
            ["hq.frontend.await_arrival", 720 * MS, 100 * MS],
            # a sync that starts before the window counts from its start
            ["hq.planner.sync", -10 * MS, 30 * MS],
            ["hq.planner.sync", 500 * MS, 5 * MS],
            # four batches' groups in the window, two of them escalated; an
            # escalation that starts before the window is not counted
            ["hq.exec.escalate", -30 * MS, 20 * MS],
            ["hq.exec.groups", 310 * MS, 20 * MS],
            ["hq.exec.escalate", 330 * MS, 20 * MS],
            ["hq.exec.groups", 610 * MS, 20 * MS],
            ["hq.exec.groups", 710 * MS, 20 * MS],
            ["hq.exec.escalate", 730 * MS, 20 * MS],
            ["hq.exec.groups", 960 * MS, 20 * MS],
        ],
    }}


def _read(name, rec):
    return spec.reader(name + ".steady")(rec)


def test_program_readers_on_a_record():
    rec = _record()
    assert _read("device.idle_cut_wait_share", rec) == pytest.approx(0.15)
    assert _read("planner.sync_ms_per_batch", rec) == pytest.approx(2.5)
    # the filter-first program's ops less its kernel: fusion.1, 200 ms
    assert _read("kernel.filter_scan.ms_per_batch", rec) == \
        pytest.approx(20.0)
    assert _read("executor.escalation_pass_share", rec) == pytest.approx(0.2)
    # groups with no escalation read 0, not nothing
    rec["trace"]["host_spans"] = [s for s in rec["trace"]["host_spans"]
                                  if s[0] != "hq.exec.escalate"]
    assert _read("executor.escalation_pass_share", rec) == 0.0


@pytest.mark.parametrize("missing", ["spans", "filter_first", "batches"])
def test_program_readers_read_nothing_without_their_source(missing):
    """A program without the spans (the parent of the change that added
    them), a window without filter-first groups, or one that served no
    batch, reads nothing, never 0."""
    rec = _record()
    t = rec["trace"]
    if missing == "spans":
        t["host_spans"] = [s for s in t["host_spans"]
                           if s[0] in ("hq.planner", "hq.execute_batch")]
        names = ["device.idle_cut_wait_share", "planner.sync_ms_per_batch",
                 "executor.escalation_pass_share"]
    elif missing == "filter_first":
        t["device_ops"] = [o for o in t["device_ops"]
                           if o[3] != "jit_filter_first_local_batch"]
        names = ["kernel.filter_scan.ms_per_batch"]
    else:
        rec["batches"] = 0
        names = ["planner.sync_ms_per_batch",
                 "kernel.filter_scan.ms_per_batch",
                 "executor.escalation_pass_share"]
    for name in names:
        assert _read(name, rec) is None, name


def test_filter_scan_reads_the_filter_first_program():
    """``kernel.filter_scan.ms_per_batch`` picks its ops by the name of the
    program ``flat.filter_first_local_batch`` compiles to: a rename of the
    function, or a scan moved out of its program, must show here."""
    import jax.numpy as jnp

    from repro.kernels.gather_score import GatherRows
    from repro.vectordb import flat
    from repro.vectordb.predicates import Predicates, stack

    path = pathlib.Path(spec.__file__).parent / "metrics" / \
        "kernel.filter_scan.ms_per_batch.py"
    sp = importlib.util.spec_from_file_location("filter_scan_reader", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)

    n, d = 64, 8
    scalars = jnp.arange(n * 2, dtype=jnp.float32).reshape(n, 2)
    rows = GatherRows.build((jnp.ones((n, d), jnp.float32),), scalars)
    pred_b = stack([Predicates.from_conditions(2, {0: (0.0, 10.0)})] * 2)
    text = flat.filter_first_local_batch.lower(
        rows, pred_b, (jnp.ones((2, d), jnp.float32),),
        jnp.ones((2, 1), jnp.float32), k=4, max_candidates=16, n_vec=1,
        use_kernel=False).as_text()
    assert f"module @{mod.MODULE} " in text, text.splitlines()[0]
    # the predicate mask and its compaction lie inside that one program
    assert "stablehlo.compare" in text and "stablehlo.scatter" in text


def test_extract_names_each_ops_program(tmp_path):
    """``extract`` names each op's program from the module line and keeps
    the window and the ``hq.`` spans, a program's spans among them."""
    from jax.profiler import ProfileData

    text = """
    planes { id: 1 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
        events { metadata_id: 2 offset_ps: 9000000 duration_ps: 2000000 }
        events { metadata_id: 1 offset_ps: 1000000 duration_ps: 5000000 } }
      lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
        events { metadata_id: 3 offset_ps: 0 duration_ps: 20000000 } }
      event_metadata { key: 1 value { id: 1
        name: "%fusion.1 = pred[8]{0} fusion()" } }
      event_metadata { key: 2 value { id: 2
        name: "%gather_score_blocks.1 = f32[8]{0} custom-call()" } }
      event_metadata { key: 3 value { id: 3
        name: "jit_filter_first_local_batch(12)" } } }
    planes { id: 2 name: "/host:CPU"
      lines { id: 1 name: "python" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000 }
        events { metadata_id: 2 offset_ps: 4000000 duration_ps: 1000000
                 stats { metadata_id: 1 int64_value: 3 } }
        events { metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 } }
      event_metadata { key: 1 value { id: 1 name: "hq.window" } }
      event_metadata { key: 2 value { id: 2 name: "hq.exec.groups" } }
      event_metadata { key: 3 value { id: 3 name: "hq.planner" } }
      stat_metadata { key: 1 value { id: 1 name: "batch" } } }
    """
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    rec = trace.extract(str(path))
    assert rec["device_ops"] == [
        ["%fusion.1", 2000.0, 5000.0, "jit_filter_first_local_batch"],
        ["%gather_score_blocks.1", 10000.0, 2000.0,
         "jit_filter_first_local_batch"]]
    assert rec["window"] == [0.0, 30000.0]
    assert rec["host_spans"] == [["hq.exec.groups", 4000.0, 1000.0],
                                 ["hq.planner", 6000.0, 1000.0]]


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


@pytest.mark.parametrize("name", ["part.mhq.peak", "sift.steady"])
def test_a_traced_window_holds_the_programs_spans(name, monkeypatch):
    """A CPU profiler session over a short window: the trace holds the
    front end's, the planner's and the executor's own spans, each part of
    the planner inside the benchmark's ``hq.planner`` span, and the
    readers of the program's spans read them."""
    monkeypatch.setattr(run, "REPLAY_SECONDS", 0.5)
    monkeypatch.setattr(run, "EXERCISE_ROUNDS", 1)
    cell = part_cell() if name == "part.mhq.peak" else sift_cell()
    p = run.prepare(_tiny(cell), 2**31 + 5)
    m = run.measure(p, 12, 1.0, p.cell.traffic["arrivals"]["rate_per_s"],
                    trace=True)
    spans = m["trace"]["host_spans"]
    names = {n for n, _, _ in spans}
    assert {"hq.frontend.cut_wait", "hq.planner.sync",
            "hq.exec.groups", "hq.frontend.resolve"} <= names, names
    # each planner call the benchmark saw whole holds the three parts (a
    # call cut by the session's start or stop keeps its inner spans alone)
    outer = [(s, s + d) for n, s, d in spans if n == "hq.planner"]
    parts = [(n, s, s + d) for n, s, d in spans
             if n.startswith("hq.planner.")]
    assert outer
    for lo, hi in outer:
        assert sorted(n for n, s, e in parts if lo <= s and e <= hi) == [
            "hq.planner.decode", "hq.planner.prepare", "hq.planner.sync"]
    rec = run.layer_record(m, {"kind": "TPU v5 lite"})
    for metric in ("device.idle_cut_wait_share", "planner.sync_ms_per_batch",
                   "executor.escalation_pass_share"):
        assert spec.reader(metric + ".steady")(rec) is not None, metric
