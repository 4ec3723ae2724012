"""Serving under an open loop: requests arrive on a fixed schedule whatever
the engine does, wait in a queue for a free slot, and are timed from when
they were due. One engine thread: each turn releases the requests that are
due, admits what the free slots take (``ServingEngine.submit``: prefill and
first token), and decodes one token for every active slot
(``ServingEngine.step``). Where the mix says ``move_at``, the engine is
moved (``move_stop_copy``) after the first step that returns past that
share of the window.
"""
from __future__ import annotations

import collections
import time

import jax
import numpy as np

from chipbench import weights
from chipbench.arch import load as load_arch
from chipbench.common import checksum
from chipbench.drivers import move_stop_copy
from chipbench.reference import load as load_reference
from chipbench.traffic import gen
from repro.models.model import LM
from repro.serving.engine import Request, ServingEngine


class Tracked:
    """A request of the run with its times (perf_counter seconds)."""

    def __init__(self, d: gen.Due, t0: float):
        self.spec = d
        self.due = t0 + d.due
        self.req = Request(d.index, d.prompt, max_new=d.max_new)
        self.sent = None          # when the loop released it to the queue
        self.first = None         # when its first token came back
        self.done = None          # when its last token came back


class ServeDriver:
    """Set-up, window and check of a serving cell."""

    def __init__(self, run):
        self.run = run
        self.cfg, self.mix = run.cfg, run.mix
        self.arch = load_arch(self.cfg["arch"])
        self.lm = LM(self.arch.program_config(self.cfg))
        self.moves = []   # (stopped at, resumed at, sums before, after, bytes)
        self.move_fill = []   # share of the cache's positions held at a move
        self.eng = None
        self.tokens, self.tracked = 0, {}

    # -- set-up ---------------------------------------------------------------
    def setup(self):
        post = self.arch.to_program_fn(self.lm)
        self.params = weights.make(self.cfg, self.run.seed, post)
        self.eng = self._engine()
        self._warm()
        if self.mix.get("move_at") is not None:
            # the destination of a move is a new engine whose cache comes
            # from host arrays through device_put, uncommitted: warm such an
            # engine, on a zero cache, as the window's move will make it
            # (the move's checksums of the cache before and after included)
            cache = jax.tree.map(lambda a: np.zeros(a.shape, a.dtype),
                                 self.eng.cache)
            checksum(self.eng.cache)
            jax.tree.map(lambda a: a.delete(), self.eng.cache)
            self.eng = self._engine()
            self.eng.load_state_dict({"cache": jax.device_put(cache),
                                      "steps": 0})
            del cache
            checksum(self.eng.cache)
            self._warm()
        jax.block_until_ready(self.eng.cache)

    def _engine(self):
        return ServingEngine(self.lm, self.params, slots=self.mix["slots"],
                             capacity=self.mix["capacity"])

    def _warm(self):
        """Every prompt bucket through submit, then decode to the end: the
        programs and host-side ops the window uses, on this engine."""
        rng = np.random.default_rng(0)
        for i, n in enumerate(self.mix["prompt"]["buckets"]):
            self.eng.submit(Request(-1 - i, rng.integers(
                0, self.cfg["vocab_size"], n, dtype=np.int32), max_new=2))
            if None not in self.eng.active:
                self.eng.run_until_done()
        self.eng.run_until_done()

    def _first_step(self, eng):
        self._decode(eng)
        return time.perf_counter()

    def _decode(self, eng):
        """One engine step, with its tokens and finished requests booked."""
        if not any(eng.active):
            return False
        live = [r for r in eng.active if r is not None]
        self.run.decode_contexts.extend(len(r.prompt) + len(r.out)
                                        for r in live)
        with self.run.spans("engine.step"):
            eng.step()
        t = time.perf_counter()
        self.tokens += len(live)
        for r in live:
            if r.done and id(r) in self.tracked:
                self.tracked[id(r)].done = t
        return True

    # -- window ---------------------------------------------------------------
    def window(self, seconds):
        """Drive the engine for ``seconds``; returns the window's record."""
        spans = self.run.spans
        reqs = gen.requests(self.mix, self.run.seed, seconds,
                            self.cfg["vocab_size"])
        move_at = self.mix.get("move_at")
        self.tokens, self.tracked = 0, {}
        self.run.decode_contexts.clear()
        t0 = time.perf_counter()
        due = [Tracked(d, t0) for d in reqs]
        deadline = t0 + seconds
        move_time = None if move_at is None else t0 + move_at * seconds
        queue = collections.deque()
        nxt = 0
        while True:
            now = time.perf_counter()
            if now >= deadline:
                break
            while nxt < len(due) and due[nxt].due <= now:
                due[nxt].sent = now
                queue.append(due[nxt])
                nxt += 1
            while queue and None in self.eng.active:
                r = queue.popleft()
                self.tracked[id(r.req)] = r
                with spans("engine.submit"):
                    self.eng.submit(r.req)
                r.first = time.perf_counter()
                self.tokens += 1
                self.run.prefill_lengths.append(len(r.spec.prompt))
            stepped = self._decode(self.eng)
            if not stepped and not queue and nxt < len(due):
                with spans("gen.wait"):
                    time.sleep(max(0.0, min(due[nxt].due, deadline)
                                   - time.perf_counter()))
            if move_time is not None and now >= move_time:
                move_time = None
                self._move(stepped)
        close = time.perf_counter()
        return {"t0": t0, "close": close, "released": due[:nxt],
                "due": [r for r in due if r.due <= close],
                "tokens": self.tokens, "moves": self.moves}

    def _move(self, stepped):
        stopped = time.perf_counter()
        if stepped:
            stopped = self.run.spans.items[-1][2]   # the last step's return
        held = sum(len(r.prompt) + len(r.out) - 1      # positions written
                   for r in self.eng.active if r is not None)
        self.move_fill.append(held / (self.eng.slots * self.eng.capacity))
        self.eng, before, after, resumed, nbytes = move_stop_copy.move_engine(
            self.eng, self.run.spans, self._first_step)
        self.moves.append((stopped, resumed, before, after, nbytes))

    # -- after the window -----------------------------------------------------
    def release(self):
        """Free the program's device state before the reference runs."""
        for a in jax.tree.leaves((self.eng.cache, self.params)):
            a.delete()
        self.eng = self.params = None

    def check(self, record):
        """The served tokens of a seeded sample of finished requests against
        the plain reference, and the move's checksums. With ``run.control``
        the reference in that precision takes the program's place: its first
        choices at the same positions are what ``logit_gap`` compares."""
        ref = load_reference(self.cfg["arch"])
        done = [r for r in record["released"] if r.done is not None]
        sample = sample_finished(done, self.mix["check"], self.run.seed)
        w = weights.make(self.cfg, self.run.seed)
        gaps = [served_gaps(ref, self.cfg, w, r.spec.prompt, r.req.out,
                            control=self.run.control) for r in sample]
        del w
        served = [g[0] for g in gaps]
        compared = [g[-1] for g in gaps]
        checks = {"logit_gap": max(compared) if compared else float("inf")}
        if self.mix.get("move_at") is not None:
            checks["move_bits_differ"] = move_stop_copy.bits_differ(self.moves)
        info = {"checked_requests": len(sample),
                "checked_tokens": sum(len(r.req.out) for r in sample),
                "move_cache_fill": self.move_fill}
        if self.run.control:
            info["control"] = self.run.control
            info["served_logit_gap"] = max(served) if served else None
        return checks, info


def sample_finished(done, check, seed):
    """The longest finished request, then others drawn from the seed, until
    ``served_tokens`` are covered or ``max_requests`` are taken."""
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.out), -r.spec.index))
    rest = [r for r in done if r is not longest]
    order = np.random.Generator(np.random.PCG64(int(seed) % 2 ** 128 + 1)
                                ).permutation(len(rest))
    out, n = [longest], len(longest.req.out)
    for i in order:
        if n >= check["served_tokens"] or len(out) >= check["max_requests"]:
            break
        out.append(rest[i])
        n += len(rest[i].req.out)
    return out


PAD = 256


def served_gaps(ref, cfg, w, prompt, out, control=None):
    """The widest gap of a request's served tokens (and of the control's
    first choices) below the reference's best logit at each position."""
    P, n = len(prompt), len(out)
    seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
    S = -(-len(seq) // PAD) * PAD
    tokens = np.zeros(S, np.int32)
    tokens[:len(seq)] = seq
    rows = np.full(PAD, P - 1 + n - 1, np.int32)    # padded with the last
    rows[:n] = np.arange(P - 1, P - 1 + n)
    served = np.full(PAD, out[-1], np.int32)
    served[:n] = out
    res = ref.serve_gaps(cfg, w, tokens, rows, served, control=control)
    return [float(np.max(np.asarray(x)[:n])) for x in res]
Driver = ServeDriver
