"""The whole train step's share of the chip's peak: forward and backward
operations of the window's steps (no recompute), over the window (stop
included), over the peak."""
from chipbench import flops
from chipbench.metrics import window_s


def value(run, record):
    ops = record["steps"] * flops.train_step(run.cfg, run.mix["batch"],
                                             run.mix["seq_len"])
    peak = flops.peaks(run.device_kind)["flops_per_s"]
    return 100.0 * ops / window_s(record) / (peak * run.chips)
