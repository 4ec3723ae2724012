"""Mean ``serving.host_gap`` in the window: from the engine's last host
read returning to its next dispatch, the time the engine had nothing
queued on the device (which includes any wait for work)."""
from chipbench import program_spans


def value(run, record):
    s = program_spans.mean(record, "serving.host_gap")
    return None if s is None else s * 1e3
