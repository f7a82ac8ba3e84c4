"""The control: the plain reference put in the program's place, computed
one precision step lower, must come out as not correct.

    python3 -m benchmarks.hq.control --workload <cell> --seeds 1,2,3

For each seed: the cell's table and query pool at the cell's own size, the
reference's exact top-k, and the control's answers, the same reference
with its matmuls in three bfloat16 passes (``high``, the step below the
HIGHEST-precision float32 the configurations state), judged by the same
comparison as a run. Prints each seed's numbers beside the cell's limits
and exits non-zero unless every seed reads not correct. No program code
runs; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

from benchmarks.hq import run


def control_numbers(cell, seed: int) -> dict:
    from benchmarks.hq import check, data as hqdata, reference, traffic

    tr = cell.traffic
    data = hqdata.make(cell.config["table"], cell.config["data_seed"])
    m = len(data.scalar_cols)

    def selectivity(dnfs):
        return reference.qualifying_counts(dnfs, data.scalars, m) \
            / data.n_rows

    pool = traffic.make_pool(cell, data, selectivity, seed)
    kk = 2 * tr["k"]
    ref = reference.scan_topk(data.vectors, data.scalars, pool, kk,
                              data.metric)
    ids, sc, _ = reference.scan_topk(data.vectors, data.scalars, pool,
                                     tr["k"], data.metric, control=True)
    answers = [(i, [int(x) if s > -float("inf") else -1
                    for x, s in zip(ids[i], sc[i])], sc[i])
               for i in range(len(pool))]
    numbers, _, _ = check.judge(pool, answers, ref, data.vectors,
                                data.host_scalars, data.metric)
    return numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from benchmarks.hq import check, spec

    cell = spec.cell(args.workload)
    try:
        device = run.device_info(cell.chips)
    except run.NoChip as e:
        run.log(f"FAIL: {e}")
        return 2
    run.configure_process()
    limits = cell.traffic["limits"]
    refused = True
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = control_numbers(cell, seed)
        ok = check.verdict(numbers, limits)
        refused &= not ok
        print(json.dumps({"seed": seed, "correct": ok,
                          "compared": check.describe(numbers, limits),
                          "device": device}), flush=True)
    return 0 if refused else 1


if __name__ == "__main__":
    sys.exit(main())
