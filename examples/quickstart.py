"""Quickstart: train a small LM for a few steps on CPU, checkpoint it,
restore it, and keep training — the 60-second tour of the public API.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt
from repro.configs.base import get_smoke_config
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import LM
from repro.optim import adamw


def main():
    use_compile_cache()
    cfg = get_smoke_config("deepseek-7b").replace(num_layers=2)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    state = adamw.init_state(params)
    opt = adamw.OptConfig(lr=3e-3, warmup_steps=5, total_steps=100)
    step_fn = jax.jit(adamw.make_train_step(lm, opt))

    pipe = TokenPipeline(DataConfig(cfg.vocab_size, seq_len=64,
                                    global_batch=8))
    print("training deepseek-7b (smoke config) for 20 steps...")
    for i in range(20):
        batch = {k: jnp.asarray(v) for k, v in pipe.next().items()}
        state, metrics = step_fn(state, batch)
        if i % 5 == 0:
            print(f"  step {i:3d} loss={float(metrics['loss']):.4f} "
                  f"grad_norm={float(metrics['grad_norm']):.3f}")

    with tempfile.TemporaryDirectory() as d:
        path = ckpt.save(d, state, step=20,
                         extra={"data": pipe.state_dict()})
        print(f"checkpointed to {path}")
        state2 = ckpt.restore(path, state)
        pipe2 = TokenPipeline(DataConfig(cfg.vocab_size, 64, 8))
        pipe2.load_state_dict(ckpt.manifest_extra(path)["data"])
        batch = {k: jnp.asarray(v) for k, v in pipe2.next().items()}
        state2, m2 = step_fn(state2, batch)
        print(f"restored + stepped: loss={float(m2['loss']):.4f}")
    print("OK")


if __name__ == "__main__":
    main()
