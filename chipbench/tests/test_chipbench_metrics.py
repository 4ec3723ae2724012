"""Each metric module's arithmetic on synthetic span records."""
import types

import numpy as np
import pytest

from chipbench import flops
from chipbench.common import Spans
from chipbench.metrics import load

CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
       "intermediate_size": 160, "vocab_size": 512, "num_hidden_layers": 3}
PEAK = {"flops_per_s": 1e12, "bytes_per_s": 1e11}


def req(due, first):
    return types.SimpleNamespace(due=due, first=first)


def make_run(**kw):
    run = types.SimpleNamespace(cfg=CFG, device_kind="test", chips=1,
                                spans=Spans(), prefill_lengths=[],
                                decode_contexts=[], trace=None, setup_s=12.5,
                                mix={"batch": 2, "seq_len": 32})
    run.__dict__.update(kw)
    return run


@pytest.fixture(autouse=True)
def peaks(monkeypatch):
    monkeypatch.setattr(flops, "peaks", lambda kind: PEAK)


def test_ttft_p95_counts_censored_requests_at_the_close():
    due = [req(0.0, 0.1 * (i + 1)) for i in range(18)]
    due += [req(5.0, None), req(9.0, 12.0)]     # none, and after the close
    rec = {"due": due, "close": 10.0, "t0": 0.0}
    t = load("ttft_p95_ms").ttfts(rec)
    assert t[-2:] == [5.0, 1.0]
    want = np.percentile([0.1 * (i + 1) for i in range(18)] + [5.0, 1.0], 95)
    assert load("ttft_p95_ms").value(make_run(), rec) == pytest.approx(
        want * 1e3)
    assert load("ttft_p95_ms").value(make_run(), {"due": [], "close": 1}) \
        is None


def test_stop_window_is_the_mean_stop():
    rec = {"moves": [(1.0, 4.0, None, None), (10.0, 12.0, None, None)]}
    assert load("stop_window_s").value(make_run(), rec) == pytest.approx(2.5)
    assert load("stop_window_s").value(make_run(), {"moves": []}) is None


@pytest.mark.parametrize("name", ["serve_tokens_per_s", "train_tokens_per_s"])
def test_rates_are_over_the_whole_window(name):
    rec = {"tokens": 600, "t0": 100.0, "close": 104.0}
    assert load(name).value(make_run(), rec) == pytest.approx(150.0)


def test_setup():
    assert load("setup_s").value(make_run(), {}) == 12.5


@pytest.mark.parametrize("name,span,scale", [
    ("engine.prefill_ms", "engine.submit", 1e3),
    ("engine.decode_step_ms", "engine.step", 1e3),
    ("migrate.save_s", "move.save", 1.0),
    ("migrate.restore_s", "move.restore", 1.0),
    ("train.step_ms", "train.step", 1e3)])
def test_span_means_inside_the_window(name, span, scale):
    run = make_run()
    run.spans.items = [(span, 1.0, 1.5), (span, 2.0, 2.1), (span, 9.0, 9.9),
                       ("other", 1.0, 3.0)]
    rec = {"t0": 0.5, "close": 5.0}
    assert load(name).value(run, rec) == pytest.approx(0.3 * scale)
    assert load(name).value(run, {"t0": 20.0, "close": 30.0}) is None


def test_mfu_serve():
    run = make_run(prefill_lengths=[16, 32], decode_contexts=[17, 18, 33])
    rec = {"t0": 0.0, "close": 2.0}
    ops = flops.prefill(CFG, 16) + flops.prefill(CFG, 32) + sum(
        flops.decode(CFG, k) for k in (17, 18, 33))
    assert load("mfu.serve").value(run, rec) == pytest.approx(
        100 * ops / 2.0 / PEAK["flops_per_s"])


def test_mfu_train():
    rec = {"t0": 0.0, "close": 4.0, "steps": 10}
    want = 100 * 10 * flops.train_step(CFG, 2, 32) / 4.0 / 1e12
    assert load("mfu.train").value(make_run(), rec) == pytest.approx(want)


def test_flops_match_parameter_count():
    """Dense forward operations are twice the matrix parameters per token."""
    D, F, V, L = 64, 160, 512, 3
    params = L * (4 * D * D + 3 * D * F)
    one = flops.decode(CFG, 0)
    assert one == 2 * params + 2 * D * V
    assert flops.train_step(CFG, 1, 1) == 3 * flops.decode(CFG, 1)


def test_roofline_share():
    tr = {"kernels": {"flash_attention": 2e-3}, "busy_s": 1, "window_s": 2}
    run = make_run(trace=tr, prefill_lengths=[16, 32])
    least = sum(flops.attention_roofline_s(CFG, s, PEAK) for s in (16, 32))
    assert load("flash_attention_roofline").value(run, {}) == pytest.approx(
        100 * least * 3 / 2e-3)
    assert load("flash_attention_roofline").value(
        make_run(trace=dict(tr, kernels={"flash_attention": 0.0})), {}) is None
    assert load("flash_attention_roofline").value(make_run(), {}) is None


def test_attention_kernel_terms():
    ops, nbytes = flops.attention_kernel(CFG, 4)
    assert ops == 4 * 4 * 16 * 10          # H * hd * (1+2+3+4) * 4
    assert nbytes == 2 * 4 * 16 * (2 * 4 + 2 * 4)


@pytest.mark.parametrize("name", ["device.idle_share.serve",
                                  "device.idle_share.train"])
def test_idle_share(name):
    run = make_run(trace={"busy_s": 1.5, "window_s": 2.0, "kernels": {}})
    assert load(name).value(run, {}) == pytest.approx(25.0)
    assert load(name).value(make_run(), {}) is None


def test_unknown_device_kind_is_an_error(monkeypatch):
    monkeypatch.undo()
    with pytest.raises(KeyError):
        flops.peaks("no such chip")
    assert flops.peaks("TPU v5 lite")["flops_per_s"] == 197e12
