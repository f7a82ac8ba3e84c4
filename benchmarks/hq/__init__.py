"""On-chip benchmark of hybrid-query serving (see run.py and PERF.md)."""
