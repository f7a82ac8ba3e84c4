"""Finds a cell's files by the names in BENCHMARK.json.

Everything that belongs to one deployment, one traffic mix or one per-layer
metric lives in a file of its own, found by name:

    configs/<config>.json      the deployment (table, indexes, fit)
    workloads/<traffic>.json   the traffic mix of a cell
    metrics/<metric>.py        the reader of one per-layer metric; a metric
                               named ``<base>.<suffix>`` falls back to
                               ``metrics/<base>.py``

A later cell, configuration or metric is added by adding files; nothing
here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict  # configs/<config>.json
    traffic: dict  # workloads/<traffic>.json
    end_to_end: tuple  # BENCHMARK.json end_to_end entries this cell reports
    per_layer: tuple  # BENCHMARK.json per_layer entries this cell reports


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def reports(metric: dict, cell: str, cell_e2e: set) -> bool:
    """Does ``cell`` report ``metric``? A metric with a ``workloads`` list
    is reported where it lists; an end-to-end metric without one in every
    cell; a per-layer metric without one wherever its ``moves`` is."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in cell_e2e
    return True


def cell(name: str, *, bench: dict | None = None,
         root: pathlib.Path = ROOT, hq: pathlib.Path = HERE) -> Cell:
    """The cell ``name`` of BENCHMARK.json (under ``root``) with its config
    (the file BENCHMARK.json names) and traffic (``hq/workloads``)."""
    bench = benchmark(root) if bench is None else bench
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(root / cfgs[w["config"]]["file"])
    traffic = load_json(hq / "workloads" / f"{w['traffic']}.json")
    e2e = tuple(m for m in bench["end_to_end"] if reports(m, name, set()))
    names = {m["name"] for m in e2e}
    layer = tuple(m for m in bench["per_layer"] if reports(m, name, names))
    return Cell(name, int(w["chips"]), cfg, traffic, e2e, layer)


def reader(metric: str, hq: pathlib.Path = HERE):
    """The ``read(record)`` function of a per-layer metric, loaded from
    ``metrics/<metric>.py`` or, for ``<base>.<suffix>``, from
    ``metrics/<base>.py``."""
    mdir = hq / "metrics"
    path = mdir / f"{metric}.py"
    if not path.exists() and "." in metric:
        path = mdir / f"{metric.rsplit('.', 1)[0]}.py"
    if not path.exists():
        raise FileNotFoundError(f"no reader for metric {metric!r} in {mdir}")
    mod_name = "hq_metric_" + "".join(
        ch if ch.isalnum() else "_" for ch in path.stem)
    sp = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read
