"""Seconds of ``ckpt.save.to_host`` per ``ckpt.save`` in the window: the
device-to-host copy of every leaf of the moved state."""
from chipbench import program_spans


def value(run, record):
    return program_spans.per_parent(record, "ckpt.save.to_host", "ckpt.save")
