"""95th percentile of send time minus due time, ms: how late the load
generator ran (a starved generator reads as a fast server)."""
import numpy as np


def read(record):
    late = record["late_ms"]
    return float(np.quantile(late, 0.95)) if len(late) else None
