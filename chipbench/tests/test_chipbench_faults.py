"""Whole runs of the benchmark at a small size on the CPU, past the look
for a chip: a sound run is correct, and with the timed path broken
underneath (``faults.py``) or the control in the program's place, the same
run comes out not correct."""
import json
import time

import pytest

from chipbench import common, faults
from chipbench import run as R
from chipbench.drivers import open_loop

SERVE = "stablelm-1.6b.serve-migrate"
TRAIN = "stablelm-1.6b-l4.train-migrate"


def small(cfg, mix):
    # float32 compute: at this size the program then meets the reference
    # to rounding, and only the faults can cross the cell's limits
    cfg = dict(cfg, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=4, num_hidden_layers=2,
               intermediate_size=160, vocab_size=512, torch_dtype="float32")
    if "capacity" in mix:
        mix = dict(mix, capacity=128, rate_per_s=20.0,
                   prompt=dict(mix["prompt"], buckets=[16, 32], median=20),
                   output=dict(mix["output"], min=4, max=12, median=8),
                   check=dict(mix["check"], served_tokens=60))
    else:
        mix = dict(mix, batch=4, seq_len=32)
    return cfg, mix


class Clock:
    """The serving window's clock, read by the open-loop driver and by the
    harness's spans: each reading moves it on by one tick, and a sleep by
    what it asks. On the host's clock a loaded host (the test suite's other
    workers) served this small mix's few first requests to the end before
    the move, so the move carried an empty cache; on this clock which
    requests are due and in flight at the move is the same on any host."""

    TICK = 0.002

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        self.now += self.TICK
        return self.now

    def sleep(self, seconds):
        self.now += max(0.0, seconds)


@pytest.fixture
def serving_clock(monkeypatch):
    clock = Clock()
    monkeypatch.setattr(open_loop, "time", clock)
    monkeypatch.setattr(common, "time", clock)
    return clock


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(R, "_setup_jax", lambda: "off")


def run(name, **kw):
    lines = []
    res = R.execute(name, 2**40 + 17, 1.5, 0, require_tpu=False,
                    override=small, out=lines.append,
                    started=time.perf_counter(), **kw)
    info = json.loads(lines[0])
    got = {k: v["value"] for k, v in res["checks"].items()}
    got["served_logit_gap"] = info.get("served_logit_gap")
    got["compiles_in_window"] = info["compiles_in_window"]["backend_compiles"]
    got["move_cache_fill"] = info.get("move_cache_fill")
    return res, got


def test_sound_serving_run_is_correct(serving_clock):
    res, got = run(SERVE)
    assert res["correct"], got
    assert got["move_bits_differ"] == 0
    assert set(res["metrics"]) == {"setup_s", "stop_window_s", "ttft_p95_ms"}
    assert res["attempted"] == 30 and res["device"]["platform"] == "cpu"
    assert res["checks"]["logit_gap"]["limit"] == 0.25
    assert len(got["move_cache_fill"]) == 1
    assert 0.0 < got["move_cache_fill"][0] <= 1.0


def test_serving_control_is_not_correct(serving_clock):
    """The reference with float8 operands in the program's place."""
    res, got = run(SERVE, control="fp8")
    assert not res["correct"], got
    assert got["logit_gap"] > res["checks"]["logit_gap"]["limit"]
    # the same run's served tokens read as a sound run does
    assert got["served_logit_gap"] <= res["checks"]["logit_gap"]["limit"]


def test_altered_token_is_not_correct(serving_clock):
    with faults.altered_token():
        res, got = run(SERVE)
    assert not res["correct"]
    assert got["logit_gap"] > res["checks"]["logit_gap"]["limit"]


def test_sound_training_run_is_correct():
    res, got = run(TRAIN)
    assert res["correct"], got
    assert got["move_bits_differ"] == 0 and got["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"setup_s", "stop_window_s",
                                   "train_tokens_per_s"}


@pytest.mark.parametrize("fault", ["frozen_state", "half_batch"])
def test_training_fault_is_not_correct(fault):
    with faults.FAULTS[fault]():
        res, got = run(TRAIN)
    assert not res["correct"], got


def test_training_control_is_not_correct():
    res, got = run(TRAIN, program_options={"compress_grads": True})
    assert not res["correct"], got


def test_no_chip_no_result(capsys):
    assert R.main(["--workload", SERVE, "--seed", "1", "--seconds", "1"]) == 1
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err
