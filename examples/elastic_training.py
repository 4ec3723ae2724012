"""Elastic scaling + straggler mitigation by live migration.

Part 1: a sharded train state is re-meshed 4 -> 2 devices mid-run
(simulating node loss) and training continues from the same state.
Part 2: the straggler policy detects a persistently slow rank and the
scheduler live-migrates its container — the paper's HPC-scheduling use
case for migration.

    PYTHONPATH=src python examples/elastic_training.py
"""
import os
import sys

if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.configs.base import get_smoke_config
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.model import LM
from repro.optim import adamw
from repro.runtime.elastic import remesh_state
from repro.runtime.ft import FailureDetector, MigrationPolicy
from repro.runtime.trainer import FabricTrainer
from repro.sharding import partition as part


def part1_elastic_remesh():
    print("== part 1: elastic re-mesh 4 -> 2 devices mid-run ==")
    cfg = get_smoke_config("stablelm-1.6b")
    lm = LM(cfg)
    opt = adamw.OptConfig(lr=1e-3)
    pipe = TokenPipeline(DataConfig(cfg.vocab_size, 64, 8))
    state_logical = adamw.state_logical(lm.specs())

    mesh4 = make_mesh((4,), ("data",))
    with part.activate(mesh4):
        params = lm.init(jax.random.PRNGKey(0))
        state = adamw.init_state(params)
        state = remesh_state(state, state_logical, None, mesh4)
        step_fn = jax.jit(adamw.make_train_step(lm, opt))
        for i in range(4):
            batch = {k: jnp.asarray(v) for k, v in pipe.next().items()}
            state, m = step_fn(state, batch)
        print(f"  4-dev mesh: step 4 loss={float(m['loss']):.4f}")

    mesh2 = make_mesh((2,), ("data",))   # two devices lost
    with part.activate(mesh2):
        state = remesh_state(state, state_logical, mesh4, mesh2)
        step_fn2 = jax.jit(adamw.make_train_step(lm, opt))
        for i in range(4):
            batch = {k: jnp.asarray(v) for k, v in pipe.next().items()}
            state, m = step_fn2(state, batch)
        print(f"  2-dev mesh: step 8 loss={float(m['loss']):.4f} "
              f"(state re-sharded, no restart)")


def part2_straggler_migration():
    print("== part 2: straggler mitigation by live migration ==")
    tr = FabricTrainer(4, n_nodes=6, seed=2)
    det = FailureDetector(timeout_s=10)
    pol = MigrationPolicy(det, factor=1.5, patience=2)
    slow_rank = 2
    migrated = False
    for s in range(8):
        tr.step()
        for r in range(4):
            # node 2 is degraded; once rank2 leaves it, it runs at speed
            t = 2.5 if (r == slow_rank and not migrated) else 1.0
            det.heartbeat(r, step_time=t, now=float(s))
        for r in pol.stragglers():
            rep = tr.cluster.migrate(f"rank{r}", 5)
            migrated = True
            print(f"  step {s}: rank{r} flagged as straggler -> "
                  f"live-migrated to node 5 "
                  f"(image {rep.image_bytes//1024} KiB)")
            det.health[r].step_times.clear()
    loss = tr.step()
    print(f"  training healthy after migration: loss={loss:.4f}")


if __name__ == "__main__":
    use_compile_cache()
    part1_elastic_remesh()
    part2_straggler_migration()
    print("OK")
