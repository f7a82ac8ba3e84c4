"""Execution-group chunks the scoring dispatcher routed, per formed batch
(``ScoringDispatcher.counts``)."""


def read(record):
    n = sum(record["dispatch"].values())
    b = record["batches"]
    return n / b if b and n else None
