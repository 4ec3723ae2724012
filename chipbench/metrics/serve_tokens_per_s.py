"""Output tokens produced in the window (first tokens and decoded ones) over
the window's seconds."""
from chipbench.metrics import window_s


def value(run, record):
    return record["tokens"] / window_s(record)
