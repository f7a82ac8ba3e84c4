"""Share of the window's formed batches that ran a second grouped pass to
retry their underfilled queries: the ``hq.exec.escalate`` spans that start
in the window over the batches served. Nothing where the window holds no
``hq.exec.groups`` span (a program without the executor's spans) or
served no batch."""

GROUPS, ESCALATE = "hq.exec.groups", "hq.exec.escalate"


def read(record):
    rec = record["trace"]
    lo, hi = rec["window"]
    starts = [(name, s) for name, s, _ in rec["host_spans"]
              if name in (GROUPS, ESCALATE) and lo <= s <= hi]
    b = record["batches"]
    if not b or not any(name == GROUPS for name, _ in starts):
        return None
    return sum(name == ESCALATE for name, _ in starts) / b
