"""Tokens of the train steps completed in the window over the window's
seconds, the stop included."""
from chipbench.metrics import window_s


def value(run, record):
    return record["tokens"] / window_s(record)
