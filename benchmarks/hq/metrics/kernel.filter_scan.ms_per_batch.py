"""Device milliseconds per formed batch of the ops of
``flat.filter_first_local_batch``'s program other than the gather kernel:
the predicate masks, their ``nonzero`` compaction and the small top-k
merge after the kernel. Ops are picked by their program's name, since the
compaction's scatter runs as an XLA custom fusion that keeps no scope.
Nothing where the window ran no such op."""
from benchmarks.hq import trace

MODULE = "jit_filter_first_local_batch"


def read(record):
    rec = record["trace"]
    ops = [op for op in rec["device_ops"]
           if op[3] == MODULE and not trace.is_gather_kernel(op[0], op[3])]
    secs = trace.busy_seconds({"window": rec["window"], "device_ops": ops})
    b = record["batches"]
    return 1e3 * secs / b if secs and b else None
