"""Pallas TPU RG-LRU scan kernel.

Grid (B, nd, nt): feature-blocked (bd lanes per program), time chunked
(bt steps per grid step, innermost "arbitrary" axis) with the recurrent
state h carried in VMEM scratch across time chunks. Inside a chunk the
recurrence is a dense fori_loop over rows — on TPU this is VPU work
entirely in VMEM; HBM traffic is exactly one read of (x, gates) and one
write of y. The gate math (a = exp(-c·softplus(Λ)·σ(r))) is fused here so
the decay never round-trips to HBM.

The per-row decay and input terms are staged in f32 VMEM scratch and the
loop reads and overwrites one row at a time, so the recurrence needs no
dynamic indexing of values, only of refs. ``h0``/``h_final`` travel as
[B, 1, D] so every block's last two dims meet the TPU tiling rule.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, al_ref, ga_ref, gx_ref, h0_ref, y_ref, hout_ref, h_scr,
            a_scr, b_scr, *, c, bt, nt):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)          # [bt, bd]
    al = al_ref[...].astype(jnp.float32)      # [1, bd] (broadcast row)
    ga = ga_ref[0].astype(jnp.float32)
    gx = gx_ref[0].astype(jnp.float32)

    log_a = -c * jax.nn.softplus(al) * jax.nn.sigmoid(ga)     # [bt, bd]
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    a_scr[...] = a
    b_scr[...] = beta * jax.nn.sigmoid(gx) * x

    def step(t, h):                            # h: [1, bd]
        row = pl.ds(t, 1)
        h = a_scr[row, :] * h + b_scr[row, :]
        b_scr[row, :] = h                      # b row t is dead: reuse as y
        return h

    h_scr[...] = jax.lax.fori_loop(0, bt, step, h_scr[...])
    y_ref[0] = b_scr[...].astype(y_ref.dtype)

    @pl.when(ti == nt - 1)
    def _finish():
        hout_ref[0] = h_scr[...].astype(hout_ref.dtype)


def rglru_scan(x, a_log, gate_a, gate_x, *, c=8.0, h0=None, block_d=512,
               block_t=256, interpret=False):
    """x/gates: [B,S,D]; a_log: [D]; h0: [B,D] or None -> (y, h_final)."""
    B, S, D = x.shape
    bd = min(block_d, D)
    if D % bd:
        bd = math.gcd(D, bd)
    bt = min(block_t, S)
    if S % bt:
        bt = math.gcd(S, bt)
    nd, nt = D // bd, S // bt
    h0 = (jnp.zeros((B, 1, D), jnp.float32) if h0 is None
          else h0.reshape(B, 1, D))
    al2 = jnp.broadcast_to(a_log[None], (1, D)).astype(jnp.float32)

    kernel = functools.partial(_kernel, c=c, bt=bt, nt=nt)
    y, hT = pl.pallas_call(
        kernel,
        grid=(B, nd, nt),
        in_specs=[
            pl.BlockSpec((1, bt, bd), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, bd), lambda b, d, t: (0, d)),
            pl.BlockSpec((1, bt, bd), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, bt, bd), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, 1, bd), lambda b, d, t: (b, 0, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bd), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, 1, bd), lambda b, d, t: (b, 0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, D), x.dtype),
            jax.ShapeDtypeStruct((B, 1, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32),
                        pltpu.VMEM((bt, bd), jnp.float32),
                        pltpu.VMEM((bt, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="rglru_scan",
    )(x, al2, gate_a, gate_x, h0)
    return y, hT.reshape(B, D)
