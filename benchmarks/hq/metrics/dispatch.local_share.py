"""Share of routed group chunks that took the candidate-local path
(``ScoringDispatcher.counts``)."""


def read(record):
    n = sum(record["dispatch"].values())
    return record["dispatch"].get("candidate_local", 0) / n if n else None
