"""Share of the gather+score kernel's roofline, %: the least time its
launches in the window could take (``costs``: the larger of operations
over peak and bytes over HBM bandwidth, per launch) over the device time
of its trace events. Nothing when the window ran no such kernel."""
from benchmarks.hq import trace


def read(record):
    k = record.get("kernel")
    secs = sum(trace.op_seconds(record["trace"], trace.is_gather_kernel)
               .values())
    if not k or not secs or not k["least_s"]:
        return None
    return 100.0 * k["least_s"] / secs
