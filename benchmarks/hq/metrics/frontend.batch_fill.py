"""Requests executed per batch the front end formed, over the window."""


def read(record):
    b = record["batches"]
    return record["requests"] / b if b else None
