"""Share of the window in which the device was idle while the front end
held a request and waited to cut its batch: the seconds of the device's
idle gaps that lie inside ``hq.frontend.cut_wait`` spans, over the
window's seconds. Nothing where the trace holds no such span."""
from benchmarks.hq import trace

SPAN = "hq.frontend.cut_wait"


def read(record):
    rec = record["trace"]
    lo, hi = rec["window"]
    waits = sorted(filter(None, (trace.clip(s, d, lo, hi)
                                 for name, s, d in rec["host_spans"]
                                 if name == SPAN)))
    w = trace.window_seconds(rec)
    if not waits or w <= 0:
        return None
    idle, i = 0.0, 0
    gaps = trace.idle_gaps(rec)
    for gs, ge in gaps:  # both sorted; cut_wait spans never overlap
        while i < len(waits) and waits[i][1] <= gs:
            i += 1
        j = i
        while j < len(waits) and waits[j][0] < ge:
            idle += min(ge, waits[j][1]) - max(gs, waits[j][0])
            j += 1
    return idle * 1e-9 / w
