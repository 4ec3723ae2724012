"""Seconds of ``ckpt.save.write`` per ``ckpt.save`` in the window: the leaf
files, the manifest and the atomic publish of the image."""
from chipbench import program_spans


def value(run, record):
    return program_spans.per_parent(record, "ckpt.save.write", "ckpt.save")
