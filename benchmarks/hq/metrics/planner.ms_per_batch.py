"""Host milliseconds in ``BoomHQ.optimize_batch`` (its one device sync
included) per formed batch."""


def read(record):
    spans = record["spans"].get("hq.planner", [])
    b = record["batches"]
    return 1e3 * sum(spans) / b if spans and b else None
