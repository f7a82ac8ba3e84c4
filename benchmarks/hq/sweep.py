"""Find a cell's knee: set-up once, then open-loop windows at several
offered rates, each judged against the reference.

    python3 -m benchmarks.hq.sweep --workload <cell> --seed <n> \\
        --seconds <s> --rates 50,100,200 [--keep-trace DIR]

The highest rate whose completions keep up with what is offered, with no
backlog growing through the window, is the knee. A cell's file fixes its
rate from this, as a number; the benchmark's runs never search.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from benchmarks.hq import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--keep-trace", default=None,
                    help="trace one more window at the cell's rate and keep "
                         "its record and layout here")
    args = ap.parse_args(argv)

    from benchmarks.hq import spec

    cell = spec.cell(args.workload)
    try:
        device = run.device_info(cell.chips)
    except run.NoChip as e:
        run.log(f"FAIL: {e}")
        return 2
    run.configure_process()
    p = run.prepare(cell, args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = run.measure(p, args.seed + i, args.seconds, rate)
        numbers, recalls = run.judge(p, m)
        s = m["summary"]
        print(json.dumps({
            "rate": rate, "qps": s["qps"], "p50_ms": s["p50_ms"],
            "p95_ms": s["p95_ms"], "p99_ms": s["p99_ms"],
            "served": s["served"], "attempted": s["attempted"],
            "late_p95_ms": float(np.quantile(s["late_ms"], 0.95)),
            "recall": float(np.mean(recalls)) if recalls else None,
            "compared": numbers}), flush=True)
    if args.keep_trace:
        m = run.measure(p, args.seed, args.seconds,
                        cell.traffic["arrivals"]["rate_per_s"], trace=True,
                        keep_trace=args.keep_trace)
        rec = run.layer_record(m, device)
        out = {}
        for mt in cell.per_layer:
            v = spec.reader(mt["name"])(rec)
            out[mt["name"]] = v
        print(json.dumps({"traced": out, "kernel": rec["kernel"],
                          "dispatch": rec["dispatch"],
                          "batches": rec["batches"]}), flush=True)
    print(json.dumps({"device": device,
                      "memory_peak_bytes": run.memory_peak(cell.chips)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
