"""Share of its roofline that the Pallas flash-attention kernel reached in
the traced window: the least time the chip could take for the window's
prefill attention calls (``flops.attention_roofline_s``, per layer per
prompt) over the device time of the kernel's events."""
from chipbench import flops


def value(run, record):
    tr = run.trace
    if not tr or not tr["kernels"].get("flash_attention"):
        return None
    peak = flops.peaks(run.device_kind)
    least = sum(flops.attention_roofline_s(run.cfg, S, peak)
                for S in run.prefill_lengths) * run.cfg["num_hidden_layers"]
    return 100.0 * least / tr["kernels"]["flash_attention"]
