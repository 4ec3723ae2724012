"""End-to-end driver: train a ~100M-parameter LM with the full substrate
(config -> model -> sharded AdamW -> checkpointable data pipeline ->
periodic checkpoints + simulated failure restart mid-run).

    PYTHONPATH=src python examples/train_e2e.py --steps 300   # full run
    PYTHONPATH=src python examples/train_e2e.py --steps 20    # quick
"""
import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt
from repro.configs.lm_100m import CONFIG as CFG
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import LM
from repro.optim import adamw


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="simulate a crash+restart at this step")
    args = ap.parse_args()

    lm = LM(CFG)
    params = lm.init(jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {n/1e6:.1f}M params")

    state = adamw.init_state(params)
    opt = adamw.OptConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps)
    step_fn = jax.jit(adamw.make_train_step(lm, opt))
    pipe = TokenPipeline(DataConfig(CFG.vocab_size, args.seq, args.batch))

    fail_at = args.fail_at or (args.steps // 2 if args.steps >= 40 else None)
    ckpt_dir = tempfile.mkdtemp(prefix="repro_e2e_")
    t0 = time.time()
    s = 0
    while s < args.steps:
        if fail_at is not None and s == fail_at:
            print(f"-- simulated failure at step {s}: restarting from "
                  f"latest checkpoint --")
            latest = ckpt.latest(ckpt_dir)
            state = ckpt.restore(latest, state)
            extra = ckpt.manifest_extra(latest)
            pipe.load_state_dict(extra["data"])
            s = int(extra["step"])
            fail_at = None
            continue
        batch = {k: jnp.asarray(v) for k, v in pipe.next().items()}
        state, metrics = step_fn(state, batch)
        if s % 10 == 0:
            dt = time.time() - t0
            print(f"step {s:4d} loss={float(metrics['loss']):.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"({dt/(s+1):.2f}s/step)")
        if s % 25 == 0 and s > 0:
            ckpt.save(ckpt_dir, state, step=s,
                      extra={"step": s, "data": pipe.state_dict()})
        s += 1
    print(f"done: {args.steps} steps in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
