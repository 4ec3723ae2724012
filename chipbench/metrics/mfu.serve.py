"""The whole serving step's share of the chip's peak: forward operations of
the window's prefills and decoded tokens, over the window, over the peak."""
from chipbench import flops
from chipbench.metrics import window_s


def value(run, record):
    ops = sum(flops.prefill(run.cfg, S) for S in run.prefill_lengths) + \
        sum(flops.decode(run.cfg, k) for k in run.decode_contexts)
    peak = flops.peaks(run.device_kind)["flops_per_s"]
    return 100.0 * ops / window_s(record) / (peak * run.chips)
