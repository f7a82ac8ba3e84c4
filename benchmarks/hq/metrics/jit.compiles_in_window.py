"""Programs lowered inside the window (compiled, or read from the
persistent cache): each is a shape the warm-up missed. Should be 0."""


def read(record):
    return float(record["lowered_in_window"])
