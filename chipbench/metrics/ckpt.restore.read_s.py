"""Seconds of ``ckpt.restore.read`` per ``ckpt.restore`` in the window: the
manifest and leaf files read back."""
from chipbench import program_spans


def value(run, record):
    return program_spans.per_parent(record, "ckpt.restore.read",
                                    "ckpt.restore")
