"""Small deployments for the benchmark's CPU tests."""
import copy
import dataclasses

import pytest


def tiny(cell, rows: int):
    cfg = copy.deepcopy(cell.config)
    tr = copy.deepcopy(cell.traffic)
    cfg["table"]["rows"] = rows
    return dataclasses.replace(cell, config=cfg, traffic=tr)


def file_cell(traffic: str, config: str):
    """A cell from its traffic and config files, whether or not
    BENCHMARK.json lists it."""
    from benchmarks.hq import spec

    return spec.Cell(
        traffic, 1, spec.load_json(spec.HERE / "configs" / f"{config}.json"),
        spec.load_json(spec.HERE / "workloads" / f"{traffic}.json"), (), ())


def part_cell():
    """The two-vector ``part`` cell. It is not in BENCHMARK.json (PERF.md:
    the program crashes the TPU compiler at its size), but its traffic is
    the DNF, two-column mix these tests need."""
    return file_cell("part.mhq.peak", "part_sf1")


def sift_cell():
    """The single-vector ``sift`` cell over every selectivity stratum."""
    return file_cell("sift.steady", "sift_1m")


@pytest.fixture(scope="session")
def part_small():
    from benchmarks.hq import data

    cell = tiny(part_cell(), 2000)
    return cell, data.make(cell.config["table"], 7)


@pytest.fixture(scope="session")
def sift_small():
    from benchmarks.hq import data

    cell = tiny(sift_cell(), 4000)
    return cell, data.make(cell.config["table"], 7)
