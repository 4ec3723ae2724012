"""``trace_reduce`` on a small trace recorded on a TPU v5e
(``data/probe.xplane.pb``: three turns of a jitted matmul and the Pallas
flash-attention kernel, each in a harness annotation, inside
``chipbench.window``), and on hand-made events."""
import os

import pytest

from chipbench import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "probe.xplane.pb")
NAMES = {"chipbench.window", "engine.step", "engine.submit", "gen.wait"}


@pytest.fixture(scope="module")
def probe():
    devices, host = T.read(DATA, NAMES)
    return devices, host, T.reduce(devices, host)


def test_reads_the_chip_trace(probe):
    devices, host, _ = probe
    assert list(devices) == ["/device:TPU:0"]
    assert sum(n == "engine.step" for n, _, _ in host) == 3
    mods = {n.split("/")[0] for n, _, _, _ in devices["/device:TPU:0"]}
    assert mods == {"jit__lambda"}


def test_reduces_the_chip_trace(probe):
    _, _, r = probe
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.03688688)
    assert 0 < r["busy_s"] < r["window_s"]
    # the kernel ran three times, and it is most of the device time
    k = r["kernels"]["flash_attention"]
    assert 0 < k < r["busy_s"] and k > 0.5 * r["busy_s"]
    assert r["device_ops"][0][0].endswith("custom-call")
    assert r["device_ops"][0][1] == pytest.approx(k)
    assert len(r["device_ops"]) <= T.TOP and len(r["idle_gaps"]) <= T.TOP
    assert r["idle_gaps"][0][0] == "gen.wait"
    assert r["idle_gaps"][0][1] > 0.01
    gaps = sum(s for _, s in r["idle_gaps"])
    assert gaps <= r["window_s"] - r["busy_s"] + 1e-9


def test_union_clip_and_labels():
    ops = [("m/a fusion", "a", 0, 40), ("m/b fusion", "b", 30, 60),
           ("m/k custom-call", 'x custom_call_target="tpu_custom_call"',
            80, 90), ("m/c fusion", "c", 95, 130)]
    host = [(T.WINDOW, 10, 110), ("engine.step", 0, 70),
            ("move.save", 60, 100), ("move.restore", 100, 120)]
    r = T.reduce({"/device:TPU:0": ops}, host)
    assert r["window_s"] == pytest.approx(100e-9)
    # busy: [10, 60] + [80, 90] + [95, 110]
    assert r["busy_s"] == pytest.approx(75e-9)
    assert r["kernels"]["flash_attention"] == pytest.approx(10e-9)
    assert r["idle_gaps"] == [["move.save", pytest.approx(20e-9)],
                              ["move.save", pytest.approx(5e-9)]]
    assert r["device_ops"][0] == ["m/a fusion", pytest.approx(30e-9)]


def test_two_devices_average():
    ops = [("m/a fusion", "a", 0, 50)]
    r = T.reduce({"/device:TPU:0": ops, "/device:TPU:1": []},
                 [(T.WINDOW, 0, 100)])
    assert r["busy_s"] == pytest.approx(25e-9)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        T.reduce({}, [("engine.step", 0, 1)])


def test_op_names_carry_module_and_opcode():
    ops = [("%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop", 5, 6),
           ("%copy-start = (bf16[2]{0}, u32[]{:S(2)}) copy-start(bf16[2] %a)",
            12, 13)]
    mods = [("jit_decode_step(123)", 0, 10), ("jit_prefill(9)", 11, 20)]
    got = [n for n, _, _, _ in T._in_modules(ops, mods)]
    assert got == ["jit_decode_step/fusion.3 fusion",
                   "jit_prefill/copy-start copy-start"]
