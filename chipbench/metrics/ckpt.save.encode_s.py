"""Seconds of ``ckpt.save.encode`` per ``ckpt.save`` in the window: each
leaf's bytes copied, framed with msgpack and compressed by zstd."""
from chipbench import program_spans


def value(run, record):
    return program_spans.per_parent(record, "ckpt.save.encode", "ckpt.save")
