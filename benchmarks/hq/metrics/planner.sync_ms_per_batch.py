"""Host milliseconds inside ``hq.planner.sync`` (the plan program's launch
and its one device-to-host copy of the plan codes) per formed batch, from
the trace's spans clipped to the window. Nothing where the trace holds
no such span."""
from benchmarks.hq import trace

SPAN = "hq.planner.sync"


def read(record):
    rec = record["trace"]
    lo, hi = rec["window"]
    parts = [c for c in (trace.clip(s, d, lo, hi)
                         for name, s, d in rec["host_spans"] if name == SPAN)
             if c]
    b = record["batches"]
    if not parts or not b:
        return None
    return 1e-6 * sum(e - s for s, e in parts) / b
