"""Compile the main path's kernels for a described TPU v5e at real widths.

No chip is needed: the TPU compiler is installed and compiles for a chip
that is described, not attached. A passing compile is not a chip run; it
catches what interpret mode cannot (tiling of blocks, VMEM limits, a
kernel with no backward pass under autodiff).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import gemma3_1b, lm_100m, mamba2_2_7b, recurrentgemma_9b
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru import rglru_scan
from repro.kernels.ssd import ssd_scan
from repro.optim import adamw


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for the described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("window", [0, gemma3_1b.CONFIG.local_window])
def test_flash_attention_gemma3_1b(one_chip, window):
    c = gemma3_1b.CONFIG
    q = _sds(one_chip, (1, 1024, c.num_heads, c.head_dim))
    kv = _sds(one_chip, (1, 1024, c.num_kv_heads, c.head_dim))
    hlo = _hlo(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                               window=window), q, kv, kv)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("with_h0", [False, True])
def test_rglru_scan_recurrentgemma_9b(one_chip, with_h0):
    R = recurrentgemma_9b.CONFIG.rnn_width
    x = _sds(one_chip, (2, 2048, R))
    args = [x, _sds(one_chip, (R,), jnp.float32), x, x]
    if with_h0:
        args.append(_sds(one_chip, (2, R), jnp.float32))
    hlo = _hlo(lambda x, al, ga, gx, *h0: rglru_scan(
        x, al, ga, gx, h0=h0[0] if h0 else None), *args)
    assert "tpu_custom_call" in hlo


def test_ssd_scan_mamba2_2_7b(one_chip):
    c = mamba2_2_7b.CONFIG
    s = c.ssm
    H = s.expand * c.d_model // s.head_dim
    b, S = 1, 2048
    f32 = jnp.float32
    hlo = _hlo(lambda x, dt, A, B, C, D: ssd_scan(x, dt, A, B, C, D=D,
                                                  chunk=s.chunk_size),
               _sds(one_chip, (b, S, H, s.head_dim)),
               _sds(one_chip, (b, S, H), f32), _sds(one_chip, (H,), f32),
               _sds(one_chip, (b, S, s.ngroups, s.d_state)),
               _sds(one_chip, (b, S, s.ngroups, s.d_state)),
               _sds(one_chip, (H,), f32))
    assert "tpu_custom_call" in hlo


def test_train_attention_grad_lm_100m(one_chip):
    """The gradient of attention under the implementation the train step
    selects, at lm-100m training widths (batch 8, seq 256, fp32)."""
    c = lm_100m.CONFIG
    q = _sds(one_chip, (8, 256, c.num_heads, c.head_dim), jnp.float32)
    kv = _sds(one_chip, (8, 256, c.num_kv_heads, c.head_dim), jnp.float32)

    def loss(q, k, v):
        return ops.attention(q, k, v, causal=True,
                             impl=adamw.TRAIN_IMPL).sum()

    _hlo(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)


def test_prefill_and_decode_carry_kernel_and_scope_names(one_chip,
                                                         monkeypatch):
    """The HLO of a prefill and a decode step for the chip names the flash
    kernel and the model's scopes; the kernel keeps its custom-call target,
    by which a trace's reducer finds it."""
    import functools

    from repro.configs import stablelm_1_6b
    from repro.models.model import LM
    # the lowering targets the described chip; the process's backend is
    # the CPU's
    monkeypatch.setattr(ops, "_require_tpu", lambda kernel: None)
    cfg = stablelm_1_6b.smoke().replace(d_model=256, head_dim=64,
                                        dtype="bfloat16", scan_layers=False)
    lm = LM(cfg)
    params = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype),
                          lm.abstract())
    prefill = jax.jit(functools.partial(lm.prefill, impl="pallas"),
                      static_argnums=2)
    hlo = prefill.lower(params, {"tokens": _sds(one_chip, (1, 256),
                                                jnp.int32)},
                        512).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo
    assert "%flash_attention" in hlo
    for scope in ("/prefill/attention/flash_attention/", "/prefill/mlp/",
                  "/prefill/head/"):
        assert scope in hlo, scope
    cache = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype),
                         lm.init_cache(2, 512))
    hlo = _hlo(lm.decode_step, params, cache,
               _sds(one_chip, (2, 1), jnp.int32))
    for scope in ("/decode/attention/", "/decode/mlp/", "/decode/head/"):
        assert scope in hlo, scope
