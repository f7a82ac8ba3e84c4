"""BENCHMARK.json keeps to the benchmark's contract, and a cell, a traffic
mix or a per-layer metric is found by name from files alone."""
import json
import re
import shutil

import pytest

from benchmarks.hq import check, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and "\n" not in text and "\t" not in text


def test_top_level(bench):
    raw = (spec.ROOT / "BENCHMARK.json").read_bytes()
    assert len(raw) <= 64 * 1024
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (spec.ROOT / p).is_dir()
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line(w) for w in bench["command"])
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits the driver's budget
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_and_units_are_legal(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    for group in (metrics, bench["workloads"], bench["configs"]):
        ns = [x["name"] for x in group]
        assert len(ns) == len(set(ns))
        assert all(NAME.match(n) for n in ns), ns
    assert len(names) == len(set(names))
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    for c in bench["configs"]:
        assert _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])


def test_entries_have_exactly_their_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"])


def test_every_cell_reports_what_it_must(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    used = set()
    for w in bench["workloads"]:
        cell = spec.cell(w["name"])
        used.add(w["config"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            assert callable(spec.reader(m["name"]))
        assert set(cell.traffic["limits"]) == set(check.NUMBERS)
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        cfg = spec.load_json(spec.ROOT / c["file"])
        assert set(c["reduced"]) == set(cfg["reduced"])


def test_a_new_cell_is_found_by_name_from_files_alone(bench, tmp_path):
    """A traffic mix and a per-layer reader dropped into a directory of
    their own make a cell, with no edit to any file that is there."""
    hq = tmp_path / "hq"
    (hq / "workloads").mkdir(parents=True)
    (hq / "metrics").mkdir()
    traffic = json.loads(
        (spec.HERE / "workloads" / "part.mhq.peak.json").read_text())
    traffic["pool"]["selectivity"] = [0.0, 0.01]
    (hq / "workloads" / "part.narrow.peak.json").write_text(
        json.dumps(traffic))
    (hq / "metrics" / "probe.count.py").write_text(
        "def read(record):\n    return record['requests'] * 2\n")
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": "part_sf1", "source": "TPC-H",
                           "file": "benchmarks/hq/configs/part_sf1.json",
                           "reduced": ["train_queries"], "why": "two columns"})
    new["end_to_end"].append({"name": "qps", "unit": "queries/s",
                              "better": "higher", "bound": 0.1,
                              "source": "host_clock", "workloads": []})
    new["workloads"].append({"name": "part.narrow.peak",
                             "config": "part_sf1",
                             "traffic": "part.narrow.peak", "chips": 1,
                             "why": "narrow predicates"})
    new["per_layer"].append({"name": "probe.count.narrow", "unit": "count",
                             "better": "higher", "source": "program_counter",
                             "layer": "front end", "moves": "qps",
                             "workloads": ["part.narrow.peak"]})
    for m in new["end_to_end"]:
        if m["name"] in ("qps",):
            m["workloads"].append("part.narrow.peak")
    cell = spec.cell("part.narrow.peak", bench=new, hq=hq)
    assert cell.traffic["pool"]["selectivity"] == [0.0, 0.01]
    assert cell.config["name"] == "part_sf1"
    assert [m["name"] for m in cell.per_layer] == ["probe.count.narrow"]
    assert spec.reader("probe.count.narrow", hq=hq)({"requests": 3}) == 6
    # the benchmark's own metric files are found the same way
    shutil.copy(spec.HERE / "metrics" / "frontend.batch_fill.py",
                hq / "metrics")
    assert spec.reader("frontend.batch_fill.peak", hq=hq)(
        {"requests": 64, "batches": 2}) == 32
