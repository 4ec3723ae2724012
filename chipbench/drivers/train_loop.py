"""Training in a closed loop: the program's train step
(``adamw.make_train_step``), jitted with its state donated as a deployment
runs it, fed by ``TokenPipeline`` from the run's seed. Each step ends in the
host's read of its loss. Where the mix says ``move_at``, the train state is
moved (``move_stop_copy``) after the first step that returns past that share
of the window.

Set-up builds the one compiled step with its state and drives it through
the first ``checked_steps`` steps, which the plain reference follows after
the window; the window then goes on with the same step and state.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common, weights
from chipbench.arch import load as load_arch
from chipbench.drivers import move_stop_copy
from chipbench.reference import load as load_reference
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.models.model import LM
from repro.optim import adamw


class TrainDriver:
    def __init__(self, run):
        self.run = run
        self.cfg, self.mix = run.cfg, run.mix
        self.arch = load_arch(self.cfg["arch"])
        self.lm = LM(self.arch.program_config(self.cfg))
        self.opt = adamw.OptConfig(**self.mix["opt"],
                                   **self.run.program_options)
        self.moves = []
        self.readings = {}

    def _named(self, tree):
        return self.arch.from_program(tree, self.lm)

    def _feed(self):
        return {"tokens": jnp.asarray(self.pipe.next()["tokens"])}

    # -- set-up ---------------------------------------------------------------
    def setup(self):
        lm, cfg = self.lm, self.cfg
        self.step_fn = jax.jit(adamw.make_train_step(lm, self.opt),
                               donate_argnums=0)
        self.state = weights.make(cfg, self.run.seed, functools.partial(
            _init_state, to_program=self.arch.to_program_fn(lm)))
        self.template = jax.eval_shape(lambda s: s, self.state)
        self.pipe = TokenPipeline(DataConfig(
            cfg["vocab_size"], self.mix["seq_len"], self.mix["batch"],
            seed=self.run.seed))
        norms = jax.jit(lambda t: common.leaf_norms(self._named(t)))
        losses = []
        for i in range(self.mix["checked_steps"]):
            self.state, m = self.step_fn(self.state, self._feed())
            losses.append(float(m["loss"]))
            if i == 0:
                # the first gradient as Adam got it: m_1 = (1 - b1) g_1
                grad = norms(self.state["m"])
        change = jax.jit(functools.partial(
            _change_norms, cfg=cfg, named=self._named))(
                self.state["params"], *weights.seed_words(self.run.seed))
        self.readings = {"losses": losses,
                         "grad": {k: float(v) / (1 - self.opt.b1)
                                  for k, v in grad.items()},
                         "change": {k: float(v) for k, v in change.items()}}
        jax.block_until_ready(common.checksum(self.state))
        self.next_batch = self._feed()

    # -- window ---------------------------------------------------------------
    def _step(self, state):
        batch, self.next_batch = self.next_batch, None
        state, m = self.step_fn(state, batch)
        self.next_batch = self._feed()            # while the device works
        float(m["loss"])
        self.steps_done += 1
        return state, time.perf_counter()

    def window(self, seconds):
        spans = self.run.spans
        self.steps_done = 0
        move_at = self.mix.get("move_at")
        t0 = time.perf_counter()
        deadline = t0 + seconds
        move_time = None if move_at is None else t0 + move_at * seconds
        while True:
            with spans("train.step"):
                self.state, t = self._step(self.state)
            if t >= deadline:
                break
            if move_time is not None and t >= move_time:
                move_time = None
                self.state, before, after, resumed, nbytes = \
                    move_stop_copy.move_train_state(
                        self.state, self.template,
                        int(self.state["step"]), spans, self._step)
                self.moves.append((t, resumed, before, after, nbytes))
                if resumed >= deadline:
                    break
        close = time.perf_counter()
        tokens = self.steps_done * self.mix["batch"] * self.mix["seq_len"]
        return {"t0": t0, "close": close, "steps": self.steps_done,
                "tokens": tokens, "moves": self.moves}

    # -- after the window -----------------------------------------------------
    def release(self):
        for a in jax.tree.leaves((self.state, self.next_batch)):
            a.delete()
        self.state = self.next_batch = None

    def check(self, record):
        """The first steps against the plain reference, and the move's
        checksums."""
        ref = load_reference(self.cfg["arch"])
        want = reference_readings(ref, self.cfg, self.mix, self.run.seed)
        checks = compare(self.readings, want)
        if self.mix.get("move_at") is not None:
            checks["move_bits_differ"] = move_stop_copy.bits_differ(
                self.moves)
        return checks, {"losses": self.readings["losses"],
                        "reference_losses": want["losses"]}


def _init_state(w, to_program):
    return adamw.init_state(to_program(w))


def _change_norms(params, lo, hi, cfg, named):
    """Per-leaf norms of the change of the parameters since the seed's."""
    w0 = weights.generate(cfg, lo, hi)
    now = named(params)
    return common.leaf_norms({k: now[k] - w0[k] for k in w0})


def reference_readings(ref, cfg, mix, seed):
    """The reference's losses, first clipped gradient and change of the
    parameters over the first ``checked_steps`` steps of the same data."""
    w = weights.make(cfg, seed)
    w0 = w
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    pipe = TokenPipeline(DataConfig(cfg["vocab_size"], mix["seq_len"],
                                    mix["batch"], seed=seed))
    step = jax.jit(functools.partial(ref.adamw, mix["opt"]))
    norms = jax.jit(common.leaf_norms)
    losses = []
    for i in range(mix["checked_steps"]):
        loss, g = ref.loss_and_grad(cfg, w, jnp.asarray(
            pipe.next()["tokens"]))
        losses.append(float(loss))
        w, m, v, g = step(i + 1, w, m, v, g)
        if i == 0:
            grad = {k: float(x) for k, x in norms(g).items()}
    change = norms(jax.tree.map(jnp.subtract, w, w0))
    return {"losses": losses, "grad": grad,
            "change": {k: float(x) for k, x in change.items()}}


def gap(got: dict, want: dict, keys=None):
    """The worst leaf's gap between two per-leaf norms, against the larger of
    that leaf's reference norm and the median leaf's."""
    keys = list(want) if keys is None else keys
    med = float(np.median([want[k] for k in want]))
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in keys)


# leaves whose reference gradient is this share of the median leaf's or less
# move by round-off alone and are left out of the change
GRAD_FLOOR = 1e-3


def compare(got: dict, want: dict) -> dict:
    med = float(np.median(list(want["grad"].values())))
    moving = [k for k, g in want["grad"].items() if g > GRAD_FLOOR * med]
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in
                        zip(got["losses"], want["losses"])),
        "grad_gap": gap(got["grad"], want["grad"]),
        "update_gap": gap(got["change"], want["change"], moving),
    }


Driver = TrainDriver
