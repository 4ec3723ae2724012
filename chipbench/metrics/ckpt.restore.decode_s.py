"""Seconds of ``ckpt.restore.decode`` per ``ckpt.restore`` in the window:
each leaf decompressed and unpacked into a host array."""
from chipbench import program_spans


def value(run, record):
    return program_spans.per_parent(record, "ckpt.restore.decode",
                                    "ckpt.restore")
