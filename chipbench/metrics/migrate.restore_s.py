"""Mean host span of the move's restore in the window: the new engine or
state, ``ckpt.restore``, ``jax.device_put`` and the first step."""
from chipbench.metrics import mean_span


def value(run, record):
    return mean_span(run, record, "move.restore")
