"""Wall seconds of the window's engine dispatches (prefill, slot write,
decode) that traced, compiled or read a program from the cache, as the
program's load counter gave them."""
from chipbench import program_spans


def value(run, record):
    return program_spans.load_seconds(record)
