"""95th percentile of latency from due time over every request due in the
window, ms; a failed request is infinitely late (1e12). The tail a caller
feels; it is a per-layer number because a host that stands still for a
second or two lifts it far above its usual reading in some windows."""


def read(record):
    return record.get("p95_ms")
