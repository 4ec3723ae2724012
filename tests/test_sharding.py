"""Logical-axis resolver + small-mesh end-to-end lowering."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.sharding import partition as part


def test_resolver_basic_rules():
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    assert part.resolve(("embed", "ffn"), (64, 64), mesh) == \
        P("data", "model")
    assert part.resolve(("vocab", "embed"), (256, 64), mesh) == \
        P("model", "data")


def test_resolver_drops_nondivisible():
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    # 6 % 4 != 0 -> model dropped on that dim
    assert part.resolve(("embed", "ffn"), (64, 6), mesh) == P("data")
    # MQA: single kv head can't shard
    assert part.resolve((None, None, "heads", None), (8, 128, 1, 64),
                        mesh) == P()


def test_resolver_uses_unused_subset():
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    # batch takes data; seq_kv=("data","model") falls back to model only
    spec = part.resolve(("batch", "seq_kv", None), (8, 128, 16), mesh)
    assert spec == P("data", "model")
    # batch=1: batch dropped; seq_kv gets both axes
    spec = part.resolve(("batch", "seq_kv", None), (1, 128, 16), mesh)
    assert spec[0] is None and set(spec[1]) == {"data", "model"}


def test_resolver_missing_axes_single_pod():
    mesh = jax.sharding.AbstractMesh((4,), ("data",))
    # ("pod","data") with no pod axis -> data only
    assert part.resolve(("batch", None), (8, 16), mesh) == P("data")


def test_constrain_is_identity_without_mesh():
    x = jnp.ones((4, 4))
    assert part.constrain(x, ("batch", None)) is x


def test_small_mesh_train_step_runs():
    """Real (non-dry-run) sharded train step on host devices."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device")
    from repro.configs.base import get_smoke_config
    from repro.models.model import LM
    from repro.optim import adamw
    n = len(jax.devices())
    mesh = make_mesh((1, n), ("data", "model"))
    cfg = get_smoke_config("gemma3-1b")
    lm = LM(cfg)
    with part.activate(mesh):
        params = lm.init(jax.random.PRNGKey(0))
        state = adamw.init_state(params)
        step = jax.jit(adamw.make_train_step(lm, adamw.OptConfig()))
        batch = {"tokens": jnp.zeros((2, 64), jnp.int32)}
        state, m = step(state, batch)
        assert np.isfinite(float(m["loss"]))
