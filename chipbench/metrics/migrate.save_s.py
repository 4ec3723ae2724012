"""Mean host span of the move's save in the window: ``ckpt.save`` (device to
host, msgpack + zstd, write) and the deletion of the device copy."""
from chipbench.metrics import mean_span


def value(run, record):
    return mean_span(run, record, "move.save")
