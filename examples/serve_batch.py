"""Batched serving with continuous batching + engine state dump/restore
(the serving-side analogue of container migration: the whole engine state —
KV caches, lengths, in-flight requests — moves between 'nodes').

    PYTHONPATH=src python examples/serve_batch.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs.base import get_smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import LM
from repro.serving.engine import Request, ServingEngine


def main():
    use_compile_cache()
    cfg = get_smoke_config("gemma3-1b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    eng = ServingEngine(lm, params, slots=4, capacity=128)

    rng = np.random.RandomState(0)
    reqs = [Request(i, rng.randint(0, cfg.vocab_size, 16).astype(np.int32),
                    max_new=8) for i in range(6)]
    pending = list(reqs)
    submitted = []
    while pending or any(eng.active):
        while pending and eng.submit(pending[0]):
            submitted.append(pending.pop(0))
        eng.step()
        if eng.steps == 3:
            # live-migrate the engine: dump state, rebuild, restore
            blob = eng.state_dict()
            eng2 = ServingEngine(lm, params, slots=4, capacity=128)
            eng2.load_state_dict(blob)
            eng2.active = eng.active
            eng = eng2
            print("[engine migrated at step 3]")
    for r in reqs:
        print(f"req {r.rid}: prompt[:4]={r.prompt[:4].tolist()} "
              f"-> {r.out}")
    assert all(len(r.out) >= r.max_new for r in reqs)
    print("OK: all requests served (through a mid-flight engine migration)")


if __name__ == "__main__":
    main()
