"""Mean host span of ``ServingEngine.step`` in the window: one token for
every active slot, ending in the host's read of the argmax."""
from chipbench.metrics import mean_span


def value(run, record):
    s = mean_span(run, record, "engine.step")
    return None if s is None else s * 1e3
