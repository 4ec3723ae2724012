"""Mean host span of ``ServingEngine.submit`` in the window: prefill, slot
write and the first token's argmax read (which waits for the device)."""
from chipbench.metrics import mean_span


def value(run, record):
    s = mean_span(run, record, "engine.submit")
    return None if s is None else s * 1e3
