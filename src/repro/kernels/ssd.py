"""Pallas TPU Mamba-2 SSD kernel (chunked state-space duality).

Grid (B, H, nc): chunks are the innermost "arbitrary" axis; the SSM state
[P, N] lives in VMEM scratch across chunks. Per chunk the kernel computes
the intra-chunk quadratic term (two MXU matmuls over [Q,N]×[N,Q] and
[Q,Q]×[Q,P]), the inter-chunk contribution from the carried state, and the
state update — the [Q,Q] decay-masked score matrix never leaves VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, lc_ref, lr_ref, ll_ref, b_ref, c_ref, h0_ref,
            y_ref, hout_ref, h_scr, *, Q, nc):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0, 0].astype(jnp.float32)          # [Q, P]
    dt = dt_ref[0, 0, 0]                            # [Q, 1]
    La = lc_ref[0, 0, 0]                            # [Q, 1] cum. log decay
    La_row = lr_ref[0, 0, 0]                        # [1, Q] same, as a row
    La_last = ll_ref[0, 0, 0]                       # [1, 1] chunk total
    Bm = b_ref[0, 0, 0].astype(jnp.float32)         # [Q, N]
    Cm = c_ref[0, 0, 0].astype(jnp.float32)         # [Q, N]

    xb = dt * x                                     # [Q, P]

    # intra-chunk: G[i,j] = (C_i · B_j) * exp(La_i - La_j), i >= j
    sc = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))   # [Q,Q]
    dec = jnp.exp(jnp.clip(La - La_row, -60.0, 0.0))
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    g = jnp.where(ii >= jj, sc * dec, 0.0)
    y = jax.lax.dot_general(g, xb, (((1,), (0,)), ((), ())))     # [Q,P]

    # inter-chunk: y += exp(La_i) * C_i · h_in
    h_in = h_scr[...]                                            # [P,N]
    y = y + jnp.exp(La) * jax.lax.dot_general(
        Cm, h_in, (((1,), (1,)), ((), ())))                      # [Q,P]

    # state update: h = exp(La_last) * h_in + sum_j exp(La_last-La_j) B_j xb_j
    dec_end = jnp.exp(La_last - La)                              # [Q,1]
    st = jax.lax.dot_general(xb * dec_end, Bm,
                             (((0,), (0,)), ((), ())))           # [P,N]
    h_scr[...] = jnp.exp(La_last) * h_in + st

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _finish():
        hout_ref[0, 0] = h_scr[...].astype(hout_ref.dtype)


def ssd_scan(x, dt, A_log, B, C, *, D=None, h0=None, chunk=256,
             interpret=False):
    """Shapes as in ``ref.ssd_ref``. Returns (y, h_final)."""
    b, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    Q = min(chunk, S)
    if S % Q:
        Q = math.gcd(S, Q)
    nc = S // Q

    # layout: chunk-major per head
    xr = x.reshape(b, nc, Q, H, P).transpose(0, 3, 1, 2, 4)      # [b,H,nc,Q,P]
    dtf = dt.astype(jnp.float32).reshape(b, nc, Q, H)
    # per-chunk cumulative log decay, taken here so the kernel needs no
    # scan; it goes in as a column and as a row for the [Q,Q] decay matrix
    La = jnp.cumsum(-jnp.exp(A_log.astype(jnp.float32)) * dtf, axis=2)
    dtr = dtf.transpose(0, 3, 1, 2)[..., None]                   # [b,H,nc,Q,1]
    Lc = La.transpose(0, 3, 1, 2)[..., None]
    Lr = La.transpose(0, 3, 1, 2)[..., None, :]                  # [b,H,nc,1,Q]
    Ll = Lr[..., -1:]                                            # [b,H,nc,1,1]
    Br = jnp.repeat(B, rep, 2).reshape(b, nc, Q, H, N).transpose(
        0, 3, 1, 2, 4)
    Cr = jnp.repeat(C, rep, 2).reshape(b, nc, Q, H, N).transpose(
        0, 3, 1, 2, 4)
    if h0 is None:
        h0 = jnp.zeros((b, H, P, N), jnp.float32)

    kernel = functools.partial(_kernel, Q=Q, nc=nc)
    y, hT = pl.pallas_call(
        kernel,
        grid=(b, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P),
                         lambda bb, h, ci: (bb, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1),
                         lambda bb, h, ci: (bb, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1),
                         lambda bb, h, ci: (bb, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q),
                         lambda bb, h, ci: (bb, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, 1),
                         lambda bb, h, ci: (bb, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N),
                         lambda bb, h, ci: (bb, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N),
                         lambda bb, h, ci: (bb, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bb, h, ci: (bb, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P),
                         lambda bb, h, ci: (bb, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bb, h, ci: (bb, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, nc, Q, P), x.dtype),
            jax.ShapeDtypeStruct((b, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(xr, dtr, Lc, Lr, Ll, Br, Cr, h0)
    y = y.transpose(0, 2, 3, 1, 4).reshape(b, S, H, P)
    if D is not None:
        y = (y.astype(jnp.float32) +
             D.astype(jnp.float32)[None, None, :, None] *
             x.astype(jnp.float32)).astype(x.dtype)
    return y, hT
