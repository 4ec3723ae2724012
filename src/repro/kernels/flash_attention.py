"""Pallas TPU flash attention (forward).

Grid (B, H, nq, nk): the kv dimension is innermost ("arbitrary" semantics)
so the online-softmax accumulators live in VMEM scratch across kv blocks.
Blocks are MXU-aligned (bq×hd, bk×hd with hd a multiple of 128 where the
model allows; smaller head dims still work, just underfill the MXU).
GQA: kv blocks index with h // group so G query heads share a kv head.
Causal/local masking skips fully-masked kv blocks via early exit.
Lengths that are not a multiple of the block are padded at the end: padded
keys are masked out and padded query rows are dropped, so any prompt length
compiles.

Forward only: a differentiated path selects ``ops.attention(impl="flash")``,
which has a backward pass. tests/test_kernels.py checks this kernel against
``ref.attention_ref`` in interpret mode; tests/test_tpu_compile.py compiles
it for a described TPU v5e at gemma3-1b widths, and chip_smoke.py checks
the compiled kernel on the chip against ``ops.attention(impl="blocked")``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, softcap, bq, bk, nk, q_off, kv_len):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    q0 = q_off + qi * bq                  # absolute position of first query
    k0 = kj * bk

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip blocks that the mask rules out entirely
    live = True
    if causal:
        live = k0 <= q0 + bq - 1           # some key <= last query pos
    if window > 0:
        live = jnp.logical_and(live, k0 + bk - 1 > q0 - window)
    if kv_len is not None:
        live = jnp.logical_and(live, k0 < kv_len)

    @pl.when(live if not isinstance(live, bool) else True)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window > 0:
            mask &= (qpos - kpos) < window
        if kv_len is not None:
            mask &= kpos < kv_len
        s = jnp.where(mask, s, NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(kj == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    scale=None, block_q=256, block_k=256, interpret=False):
    """q: [B,Sq,H,hd]; k,v: [B,Sk,Kh,hd] -> [B,Sq,H,hd].

    Blocks are multiples of 16 rows (the bf16 sublane tile); block_q and
    block_k must be too.
    """
    B, Sq, H, hd = q.shape
    _, Sk, Kh, _ = k.shape
    G = H // Kh
    scale = scale if scale is not None else hd ** -0.5
    bq = min(block_q, _round_up(Sq, 16))
    bk = min(block_k, _round_up(Sk, 16))
    Sq_p, Sk_p = _round_up(Sq, bq), _round_up(Sk, bk)
    nq, nk = Sq_p // bq, Sk_p // bk

    # padding at the end keeps queries right-aligned to keys (q_off is
    # unchanged), so a padded key lies after every real query
    qt = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0))
                 ).transpose(0, 2, 1, 3)       # [B,H,Sq_p,hd]
    kt = jnp.pad(k, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0))
                 ).transpose(0, 2, 1, 3)       # [B,Kh,Sk_p,hd]
    vt = jnp.pad(v, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0))
                 ).transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, nk=nk, q_off=Sk - Sq,
        kv_len=Sk if Sk_p != Sk else None)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),        # m
            pltpu.VMEM((bq,), jnp.float32),        # l
            pltpu.VMEM((bq, hd), jnp.float32),     # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)
