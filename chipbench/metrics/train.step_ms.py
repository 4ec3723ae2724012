"""Mean host span of a train step in the window, from its dispatch to the
host's read of its loss (the next batch is made meanwhile)."""
from chipbench.metrics import mean_span


def value(run, record):
    s = mean_span(run, record, "train.step")
    return None if s is None else s * 1e3
