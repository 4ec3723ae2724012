"""The train step's share of the chip's peak: forward and backward
operations of one step (no recompute) over the mean host span of the
window's steps (``train.step``, from the dispatch to the host's read of the
loss; the stop and the first step after a restore lie outside it), over the
peak."""
from chipbench import flops
from chipbench.metrics import mean_span


def value(run, record):
    s = mean_span(run, record, "train.step")
    if s is None:
        return None
    ops = flops.train_step(run.cfg, run.mix["batch"], run.mix["seq_len"])
    peak = flops.peaks(run.device_kind)["flops_per_s"]
    return 100.0 * ops / s / (peak * run.chips)
