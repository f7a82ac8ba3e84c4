"""1 - (union of device-op intervals) / traced window."""
from benchmarks.hq import trace


def read(record):
    w = trace.window_seconds(record["trace"])
    return 1.0 - trace.busy_seconds(record["trace"]) / w if w > 0 else None
