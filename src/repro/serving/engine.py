"""Batched serving engine: continuous batching over a fixed-size slot pool.

Requests join free slots; every engine step decodes one token for all
active slots (single jitted ``decode_step``). Prefill runs per request
(one jitted program per prompt length, cache written into the slot).
Slot state (KV caches + lengths) is an explicit pytree → the whole engine
is dumpable/migratable with the same MigrOS machinery as training state.

Host-clock spans (``repro.obs.host``): ``serving.submit`` and
``serving.step`` each hold the dispatch of their device work and the wait
of the host read that ends them; ``serving.host_gap`` runs from a wait
returning to the engine's next dispatch, the time this engine had nothing
queued on the device.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import LM
from repro.obs import host


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # [S] int32
    max_new: int = 16
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


def _write_slot(cache, req_cache, slot, length):
    """Copy a single-sequence prefill cache into slot ``slot`` of ``cache``.

    Leaves of the period-scanned core carry a leading period dim
    ([n_periods, B, ...]); head and tail leaves start with the batch dim.
    """
    def into(stacked):
        if stacked:
            return lambda dst, src: dst.at[:, slot].set(src[:, 0])
        return lambda dst, src: dst.at[slot].set(src[0])

    layers = {part: jax.tree.map(into(part == "core"), cache["layers"][part],
                                 req_cache["layers"][part])
              for part in ("head", "core", "tail")}
    return {"lengths": cache["lengths"].at[slot].set(length),
            "layers": layers}


class ServingEngine:
    def __init__(self, lm: LM, params, *, slots: int = 4,
                 capacity: int = 512):
        self.lm = lm
        self.params = params
        self.slots = slots
        self.capacity = capacity
        self.cache = lm.materialize_cache(slots, capacity)
        self.active: List[Optional[Request]] = [None] * slots
        self._prefill = jax.jit(lm.prefill, static_argnums=2)
        self._write = jax.jit(_write_slot)
        self._decode = jax.jit(lm.decode_step)
        self.steps = 0
        self._idle_since = None     # when this engine's last wait returned

    def _dispatched(self, at):
        """Close the host gap that the last wait opened at the dispatch
        that started at ``at``."""
        if self._idle_since is not None:
            host.record(host.SERVING_HOST_GAP, self._idle_since, at)
            self._idle_since = None

    def submit(self, req: Request) -> bool:
        for s in range(self.slots):
            if self.active[s] is None:
                with host.span(host.SERVING_SUBMIT):
                    with host.span(host.SERVING_PREFILL_DISPATCH) as d:
                        prompt = jnp.asarray(req.prompt)[None]
                        cache, logits = self._prefill(self.params,
                                                      {"tokens": prompt},
                                                      self.capacity)
                    self._dispatched(d.start)
                    with host.span(host.SERVING_SLOT_WRITE_DISPATCH):
                        self.cache = self._write(self.cache, cache, s,
                                                 len(req.prompt))
                    with host.span(host.SERVING_FIRST_TOKEN_WAIT) as w:
                        req.out.append(int(jnp.argmax(logits[0])))
                    self._idle_since = w.end
                    self.active[s] = req
                return True
        return False

    def step(self):
        """Decode one token for every active slot."""
        if not any(self.active):
            return
        with host.span(host.SERVING_STEP):
            toks = np.zeros((self.slots, 1), np.int32)
            for s, r in enumerate(self.active):
                if r is not None:
                    toks[s, 0] = r.out[-1]
            with host.span(host.SERVING_DECODE_DISPATCH) as d:
                self.cache, logits = self._decode(self.params, self.cache,
                                                  jnp.asarray(toks))
            self._dispatched(d.start)
            with host.span(host.SERVING_STEP_WAIT) as w:
                nxt = np.asarray(jnp.argmax(logits, -1))
            self._idle_since = w.end
            for s, r in enumerate(self.active):
                if r is None:
                    continue
                r.out.append(int(nxt[s]))
                if len(r.out) >= r.max_new:
                    r.done = True
                    self.active[s] = None
            self.steps += 1

    def run_until_done(self, max_steps: int = 1024):
        for _ in range(max_steps):
            if not any(self.active):
                break
            self.step()

    # -- migratability ------------------------------------------------------------
    def state_dict(self):
        return {"cache": self.cache, "steps": self.steps}

    def load_state_dict(self, d):
        self.cache = d["cache"]
        self.steps = d["steps"]
