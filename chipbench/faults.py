"""Faults planted under the timed path, to show that the check catches them:
each is a context manager that patches one program entry point for the
runs made inside it. Used by ``control.py`` on the chip and by the CPU
tests; the benchmark's own runs use none."""
from __future__ import annotations

import contextlib

from repro.optim import adamw
from repro.serving.engine import ServingEngine


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _train_step_wrapped(wrap):
    make = adamw.make_train_step

    def patched(lm, cfg, **kw):
        return wrap(make(lm, cfg, **kw))
    return _patched(adamw, "make_train_step", patched)


def frozen_state():
    """The train step returns its state unchanged."""
    def wrap(step):
        def frozen(state, batch):
            return state, step(state, batch)[1]
        return frozen
    return _train_step_wrapped(wrap)


def half_batch():
    """The train step sees half of its batch: the mean over the rest."""
    def wrap(step):
        def half(state, batch):
            t = batch["tokens"]
            return step(state, dict(batch, tokens=t[: t.shape[0] // 2]))
        return half
    return _train_step_wrapped(wrap)


def altered_token():
    """Every decoded token is replaced by the next id where it is produced."""
    step = ServingEngine.step

    def altered(self):
        live = [r for r in self.active if r is not None]
        step(self)
        for r in live:
            r.out[-1] = (r.out[-1] + 1) % self.lm.cfg.vocab_size
    return _patched(ServingEngine, "step", altered)


FAULTS = {"frozen_state": frozen_state, "half_batch": half_batch,
          "altered_token": altered_token}
