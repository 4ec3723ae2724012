"""Kernel tests: Pallas (interpret=True) and blocked-jnp vs ref oracles,
swept over shapes and dtypes as required for every kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru import rglru_scan
from repro.kernels.ssd import ssd_scan


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


def _mk_qkv(seed, B, S, H, Kh, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Kh, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Kh, hd), jnp.float32).astype(dtype)
    return q, k, v


# (1, 100, ...) is not a multiple of the block: the kernel pads and masks
ATTN_SHAPES = [(1, 128, 4, 4, 32), (2, 256, 8, 2, 64), (1, 192, 6, 1, 16),
               (1, 100, 4, 2, 32)]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("variant", ["causal", "bidir", "window",
                                     "softcap"])
def test_flash_attention_pallas_vs_ref(shape, dtype, variant):
    B, S, H, Kh, hd = shape
    q, k, v = _mk_qkv(0, B, S, H, Kh, hd, dtype)
    kw = {"causal": dict(causal=True),
          "bidir": dict(causal=False),
          "window": dict(causal=True, window=S // 3),
          "softcap": dict(causal=True, softcap=20.0)}[variant]
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True,
                          **kw)
    want = ref.attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), **kw)
    np.testing.assert_allclose(np.array(out, np.float32), np.array(want),
                               **_tol(dtype))


@pytest.mark.parametrize("sched", ["full", "triangular"])
def test_blocked_attention_schedules(sched):
    q, k, v = _mk_qkv(1, 2, 256, 8, 2, 64, jnp.float32)
    out = ops.attention(q, k, v, causal=True, impl="blocked",
                        schedule=sched, chunk_q=64, chunk_k=64)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.array(out), np.array(want), rtol=2e-5,
                               atol=2e-5)


def _flash_vjp_vs_ref(S):
    q, k, v = _mk_qkv(2, 2, S, 4, 2, 32, jnp.float32)
    do = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def f(impl):
        def loss(q, k, v):
            if impl == "ref":
                o = ref.attention_ref(q, k, v, causal=True, window=48)
            else:
                o = ops.attention(q, k, v, causal=True, window=48,
                                  impl="flash", chunk_q=32, chunk_k=32)
            return (o * do).sum()
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    for a, b in zip(f("ref"), f("flash")):
        np.testing.assert_allclose(np.array(a), np.array(b), rtol=1e-4,
                                   atol=1e-4)


def test_flash_vjp_grads_match_ref():
    # S=128 slices a window+chunk span per q chunk
    _flash_vjp_vs_ref(128)


def test_flash_vjp_window_wider_than_chunks():
    # S=64 is shorter than window+chunk: the global scan applies the window
    # as a mask (it must not be dropped)
    _flash_vjp_vs_ref(64)


@pytest.mark.parametrize("B,S,D", [(1, 64, 16), (2, 128, 48)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_pallas_vs_ref(B, S, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (B, S, D), jnp.float32).astype(dtype)
    al = jax.random.normal(ks[1], (D,))
    ga = jax.random.normal(ks[2], (B, S, D), jnp.float32).astype(dtype)
    gx = jax.random.normal(ks[3], (B, S, D), jnp.float32).astype(dtype)
    y, h = rglru_scan(x, al, ga, gx, block_d=16, block_t=32,
                      interpret=True)
    yr, hr = ref.rglru_ref(x.astype(jnp.float32), al,
                           ga.astype(jnp.float32),
                           gx.astype(jnp.float32))
    np.testing.assert_allclose(np.array(y, np.float32), np.array(yr),
                               **_tol(dtype))
    np.testing.assert_allclose(np.array(h), np.array(hr), **_tol(dtype))


def test_rglru_associative_scan_matches_ref():
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(ks[0], (2, 96, 24))
    al = jax.random.normal(ks[1], (24,))
    ga = jax.random.normal(ks[2], (2, 96, 24))
    gx = jax.random.normal(ks[3], (2, 96, 24))
    y, h = ops.rglru(x, al, ga, gx, impl="blocked")
    yr, hr = ref.rglru_ref(x, al, ga, gx)
    np.testing.assert_allclose(np.array(y), np.array(yr), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("B,S,H,P,G,N", [(1, 64, 2, 8, 1, 8),
                                         (2, 128, 4, 16, 2, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_pallas_vs_ref(B, S, H, P, G, N, dtype):
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    Al = jax.random.normal(ks[2], (H,)) * 0.5
    Bm = (jax.random.normal(ks[3], (B, S, G, N)) * 0.3).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, S, G, N)) * 0.3).astype(dtype)
    Dm = jax.random.normal(ks[5], (H,))
    y, h = ssd_scan(x, dt, Al, Bm, Cm, D=Dm, chunk=32, interpret=True)
    yr, hr = ref.ssd_ref(x.astype(jnp.float32), dt, Al,
                         Bm.astype(jnp.float32), Cm.astype(jnp.float32),
                         D=Dm)
    np.testing.assert_allclose(np.array(y, np.float32), np.array(yr),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ssd_chunked_jnp_matches_ref_with_state():
    """Chunked path with h0 carry == sequential oracle split in two."""
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    B, S, H, P, G, N = 2, 128, 4, 16, 2, 8
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    Al = jax.random.normal(ks[2], (H,)) * 0.5
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    y_full, h_full = ops.ssd(x, dt, Al, Bm, Cm, impl="blocked", chunk=32)
    h = None
    ys = []
    for lo in (0, S // 2):
        hi = lo + S // 2
        y, h = ops.ssd(x[:, lo:hi], dt[:, lo:hi], Al, Bm[:, lo:hi],
                       Cm[:, lo:hi], h0=h, impl="blocked", chunk=32)
        ys.append(y)
    np.testing.assert_allclose(np.array(jnp.concatenate(ys, 1)),
                               np.array(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.array(h), np.array(h_full), rtol=1e-4,
                               atol=1e-4)


def test_decode_kernels_match_full_scan():
    """Single-step decode == full-sequence scan at every position."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    B, S, D = 2, 16, 12
    x = jax.random.normal(ks[0], (B, S, D))
    al = jax.random.normal(ks[1], (D,))
    ga = jax.random.normal(ks[2], (B, S, D))
    gx = jax.random.normal(ks[3], (B, S, D))
    y_full, _ = ops.rglru(x, al, ga, gx, impl="blocked")
    h = jnp.zeros((B, D))
    for t in range(S):
        y_t, h = ops.rglru_decode(h, x[:, t], al, ga[:, t], gx[:, t])
        np.testing.assert_allclose(np.array(y_t), np.array(y_full[:, t]),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("op", ["attention", "rglru", "ssd"])
def test_pallas_impl_refuses_other_backends(op):
    """impl="pallas" off the chip raises instead of running the
    interpreter; tests reach interpret mode through the kernels only."""
    if jax.default_backend() == "tpu":
        pytest.skip("the pallas impl is the TPU path")
    x = jnp.zeros((1, 32, 2, 16))
    calls = {
        "attention": lambda: ops.attention(x, x, x, impl="pallas"),
        "rglru": lambda: ops.rglru(x[..., 0, :], x[0, 0, 0], x[..., 0, :],
                                   x[..., 0, :], impl="pallas"),
        "ssd": lambda: ops.ssd(x, x[..., 0], x[0, 0, :, 0], x, x,
                               impl="pallas"),
    }
    with pytest.raises(RuntimeError, match="TPU only"):
        calls[op]()
