"""Device milliseconds of the gather+score kernel per formed batch."""
from benchmarks.hq import trace


def read(record):
    secs = sum(trace.op_seconds(record["trace"], trace.is_gather_kernel)
               .values())
    b = record["batches"]
    return 1e3 * secs / b if secs and b else None
