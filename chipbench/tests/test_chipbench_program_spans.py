"""The metrics read from the program's own spans and load counter
(``chipbench/program_spans.py``), on a synthetic recorder."""
import sys
import time
import types

import pytest

from chipbench import program_spans
from chipbench.metrics import load
from repro.obs import host

WINDOW = {"t0": 10.0, "close": 60.0}
SAVE = {"ckpt.save.to_host_s": host.CKPT_SAVE_TO_HOST,
        "ckpt.save.encode_s": host.CKPT_SAVE_ENCODE,
        "ckpt.save.write_s": host.CKPT_SAVE_WRITE}
RESTORE = {"ckpt.restore.read_s": host.CKPT_RESTORE_READ,
           "ckpt.restore.decode_s": host.CKPT_RESTORE_DECODE}
NEW = list(SAVE) + list(RESTORE) + ["serving.program_load_s",
                                    "serving.host_gap_ms"]


@pytest.fixture
def rec(monkeypatch):
    """A recorder of the program's kind that the readers see in place of
    the process's own."""
    r = host.Recorder()
    fake = types.SimpleNamespace(spans=r.spans, durations=r.durations,
                                 DISPATCH_SPANS=host.DISPATCH_SPANS)
    monkeypatch.setattr(program_spans, "recorder", lambda: fake)
    return r


def run():
    return types.SimpleNamespace()


def test_phases_are_per_save_and_restore_and_only_in_the_window(rec):
    for t in (20.0, 40.0):                       # two moves in the window
        rec.record(host.CKPT_SAVE, t, t + 10.0)
        rec.record(host.CKPT_SAVE_TO_HOST, t, t + 2.0)
        for k in range(4):                       # a span a leaf
            rec.record(host.CKPT_SAVE_ENCODE, t + 2 + k, t + 2.5 + k)
            rec.record(host.CKPT_SAVE_WRITE, t + 2.5 + k, t + 3 + k)
        rec.record(host.CKPT_RESTORE, t + 10, t + 14)
        rec.record(host.CKPT_RESTORE_READ, t + 10, t + 11)
        rec.record(host.CKPT_RESTORE_DECODE, t + 11, t + 13.5)
    rec.record(host.CKPT_SAVE, 1.0, 9.0)         # before the window
    rec.record(host.CKPT_SAVE_TO_HOST, 1.0, 8.0)
    rec.record(host.CKPT_SAVE_ENCODE, 55.0, 61.0)     # crosses the close
    want = {"ckpt.save.to_host_s": 2.0, "ckpt.save.encode_s": 2.0,
            "ckpt.save.write_s": 2.0, "ckpt.restore.read_s": 1.0,
            "ckpt.restore.decode_s": 2.5}
    for name, v in want.items():
        assert load(name).value(run(), WINDOW) == pytest.approx(v), name


@pytest.mark.parametrize("name", list(SAVE) + list(RESTORE))
def test_a_phase_without_its_parent_or_spans_gives_nothing(rec, name):
    assert load(name).value(run(), WINDOW) is None
    phase = {**SAVE, **RESTORE}[name]
    rec.record(phase, 20.0, 21.0)                # no save or restore
    assert load(name).value(run(), WINDOW) is None


def test_program_load_is_the_dispatches_that_loaded(rec):
    m = load("serving.program_load_s")
    assert m.value(run(), WINDOW) is None
    rec.record(host.SERVING_DECODE_DISPATCH, 11.0, 11.01)
    assert m.value(run(), WINDOW) == 0.0          # dispatched, no load
    with rec.span(host.SERVING_DECODE_DISPATCH):
        rec.on_duration(host.BACKEND_COMPILE, 0.1)    # before the window
    t0 = time.perf_counter()
    loaded = []
    for name, loads in ((host.SERVING_PREFILL_DISPATCH, True),
                        (host.SERVING_PREFILL_DISPATCH, False),
                        (host.SERVING_SLOT_WRITE_DISPATCH, True),
                        (host.SERVING_DECODE_DISPATCH, True),
                        (host.SERVING_STEP, True)):       # no dispatch
        with rec.span(name) as sp:
            if loads:
                rec.on_duration(host.BACKEND_COMPILE, 0.1)
            time.sleep(0.002)
        if loads and name in host.DISPATCH_SPANS:
            loaded.append(sp.end - sp.start)
    window = {"t0": t0, "close": time.perf_counter()}
    assert len(loaded) == 3
    assert m.value(run(), window) == pytest.approx(sum(loaded))


def test_host_gap_is_the_mean_gap_in_ms(rec):
    m = load("serving.host_gap_ms")
    assert m.value(run(), WINDOW) is None
    for k, g in enumerate((0.004, 0.006, 0.005)):
        rec.record(host.SERVING_HOST_GAP, 20.0 + k, 20.0 + k + g)
    rec.record(host.SERVING_HOST_GAP, 9.0, 9.5)   # before the window
    assert m.value(run(), WINDOW) == pytest.approx(5.0)


def test_a_lost_window_gives_nothing(monkeypatch):
    r = host.Recorder(size=2)
    fake = types.SimpleNamespace(spans=r.spans, durations=r.durations,
                                 DISPATCH_SPANS=host.DISPATCH_SPANS)
    monkeypatch.setattr(program_spans, "recorder", lambda: fake)
    r.record(host.SERVING_HOST_GAP, 20.0, 20.5)
    r.record(host.SERVING_HOST_GAP, 21.0, 21.5)
    assert load("serving.host_gap_ms").value(run(), WINDOW) == \
        pytest.approx(500.0)
    r.record(host.SERVING_HOST_GAP, 22.0, 22.5)   # pushes one out
    assert r.dropped == 1
    assert load("serving.host_gap_ms").value(run(), WINDOW) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_recorder_gives_nothing(monkeypatch, name):
    # a program that predates repro.obs.host: the import fails
    import repro.obs
    monkeypatch.delattr(repro.obs, "host")
    monkeypatch.setitem(sys.modules, "repro.obs.host", None)
    assert program_spans.recorder() is None
    assert load(name).value(run(), WINDOW) is None
