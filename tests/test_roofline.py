"""Loop-aware HLO analyzer: validated against XLA cost_analysis on
loop-free modules; exact trip-count scaling on scanned modules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.roofline import analysis as roof
from repro.roofline import hlo as hlolib


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 host device")
    return make_mesh((1, len(jax.devices())), ("data", "model"))


def test_loop_free_matches_cost_analysis():
    def f(a, b, c):
        return (jnp.tanh(a @ b) @ c).sum()

    co = jax.jit(f).lower(
        jax.ShapeDtypeStruct((128, 256), jnp.float32),
        jax.ShapeDtypeStruct((256, 512), jnp.float32),
        jax.ShapeDtypeStruct((512, 64), jnp.float32)).compile()
    ca = co.cost_analysis()
    mine = hlolib.analyze_text(co.as_text())
    # dots dominate; XLA adds elementwise flops we deliberately skip
    assert abs(mine["flops"] - ca["flops"]) / ca["flops"] < 0.05
    assert abs(mine["bytes"] - ca["bytes accessed"]) / \
        ca["bytes accessed"] < 0.05


def test_scan_bodies_are_trip_scaled():
    N = 12

    def g(a, ws):
        def body(x, w):
            return jnp.tanh(x @ w), None
        y, _ = jax.lax.scan(body, a, ws)
        return y.sum()

    co = jax.jit(g).lower(
        jax.ShapeDtypeStruct((128, 256), jnp.float32),
        jax.ShapeDtypeStruct((N, 256, 256), jnp.float32)).compile()
    mine = hlolib.analyze_text(co.as_text())
    expected = 2 * 128 * 256 * 256 * N
    assert abs(mine["flops"] - expected) / expected < 0.01
    # cost_analysis counts the body once: we must be ~N x larger
    ca = co.cost_analysis()
    assert mine["flops"] > 0.9 * N * ca["flops"] / 2


def test_collectives_are_found_and_loop_scaled():
    import os
    if len(jax.devices()) < 2:
        pytest.skip("needs multiple devices")
    mesh = make_mesh((len(jax.devices()),), ("model",))
    sh = NamedSharding(mesh, P(None, "model"))

    def f(a, ws):
        def body(x, w):
            y = x @ w                    # contract sharded dim: all-reduce
            return y, None
        out, _ = jax.lax.scan(body, a, ws)
        return out.sum()

    N = 4
    co = jax.jit(f, in_shardings=(sh, None)).lower(
        jax.ShapeDtypeStruct((64, 128), jnp.float32),
        jax.ShapeDtypeStruct((N, 128, 128), jnp.float32)).compile()
    total, by_op = hlolib.collective_bytes(co.as_text())
    assert total > 0


def test_roofline_terms_and_bottleneck():
    r = roof.analyze(flops_per_dev=197e12, bytes_per_dev=819e9 / 2,
                     coll_bytes_per_dev=0.0, model_flops_total=197e12 * 256,
                     n_devices=256)
    assert r.bottleneck == "compute"
    assert abs(r.compute_s - 1.0) < 1e-9
    assert abs(r.useful_ratio - 1.0) < 1e-9
    r2 = roof.analyze(flops_per_dev=1e9, bytes_per_dev=819e9,
                      coll_bytes_per_dev=0.0, model_flops_total=1.0,
                      n_devices=2)
    assert r2.bottleneck == "memory"


def test_model_flops_formulas():
    from repro.configs.base import SHAPES, get_config
    from repro.models.model import LM
    lm = LM(get_config("deepseek-7b"))
    counts = roof.count_params(lm)
    assert 6.5e9 < counts["total"] < 8e9
    mf_train = roof.model_flops(lm, SHAPES["train_4k"], counts)
    assert abs(mf_train - 6 * counts["total"] * 256 * 4096) < 1e-6 * mf_train
    lm2 = LM(get_config("deepseek-v2-236b"))
    c2 = roof.count_params(lm2)
    assert c2["active"] < 0.15 * c2["total"]   # MoE discount applies
