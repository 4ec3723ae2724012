"""Public kernel entry points with implementation dispatch.

Implementations:
  * ``pallas``  — TPU Pallas kernels (``flash_attention.py``, ``rglru.py``,
                  ``ssd.py``), forward only. Default on TPU; selecting them
                  on another backend raises. The kernel tests run them in
                  interpret mode by calling the kernels directly.
  * ``flash``   — attention: the blocked forward plus a hand-written
                  backward (custom_vjp). The recurrences have no separate
                  flash path and take ``blocked``. This is what a
                  differentiated path selects (``adamw.make_train_step``).
  * ``blocked`` — chunked pure-jnp paths computing the identical math with
                  flash-style online softmax / chunked state passing. These
                  lower on any backend and never materialise S×S buffers.
                  Default off TPU.
  * ``ref``     — naive oracles (``ref.py``), small shapes only.

``schedule`` (attention): "full" computes all (q-chunk × kv-chunk) blocks
with masking (2× causal FLOPs, smallest HLO); "triangular" statically skips
blocks above the diagonal (the §Perf hillclimb flips this).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

_NEG = -1e30


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "blocked"


def _require_tpu(kernel: str):
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"impl='pallas' ({kernel}) compiles for TPU only; the backend "
            f"is {backend!r}. Select 'blocked' or call the kernel with "
            f"interpret=True.")


def _chunk_of(s: int, want: int) -> int:
    return want if s % want == 0 else math.gcd(s, want)


# ===========================================================================
# Attention
# ===========================================================================


def attention(q, k, v, *, causal=True, window=0, softcap=0.0, scale=None,
              impl=None, schedule="full", chunk_q=512, chunk_k=512):
    """q: [B,Sq,H,hd]; k,v: [B,Sk,Kh,hd]. Queries right-aligned in keys.

    impl:
      * "blocked" — chunked online-softmax; autodiff saves per-chunk
        residuals (baseline; memory-heavy backward).
      * "flash"   — same forward + hand-written flash backward
        (custom_vjp): saves only (out, lse), recomputes scores per block.
      * "pallas" / "ref" — TPU kernel / naive oracle.
    """
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  softcap=softcap, scale=scale)
    if impl == "pallas":
        _require_tpu("flash_attention")
        from repro.kernels import flash_attention as fa
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  softcap=softcap, scale=scale)
    if impl == "flash":
        hd = q.shape[-1]
        scale = scale if scale is not None else hd ** -0.5
        cq = _chunk_of(q.shape[1], chunk_q)
        ck = _chunk_of(k.shape[1], chunk_k)
        return _flash(q, k, v, causal, window, softcap, scale, cq, ck)
    B, Sq, H, hd = q.shape
    _, Sk, Kh, _ = k.shape
    scale = scale if scale is not None else hd ** -0.5
    cq = _chunk_of(Sq, chunk_q)
    ck = _chunk_of(Sk, chunk_k)
    if window > 0:
        if Sk <= window + cq:  # window covers (almost) everything
            return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                      softcap=softcap, scale=scale)
        return _local_blocked(q, k, v, window=window, softcap=softcap,
                              scale=scale, cq=cq)
    if schedule == "triangular" and causal and Sq == Sk:
        return _triangular_blocked(q, k, v, softcap=softcap, scale=scale,
                                   cq=cq, ck=ck)
    return _full_blocked(q, k, v, causal=causal, softcap=softcap,
                         scale=scale, cq=cq, ck=ck)


def _block(qc, kc, vc, qpos, kpos, m, l, acc, *, causal, window, softcap,
           scale):
    """One online-softmax block update. qc:[B,cq,Kh,G,hd] kc:[B,ck,Kh,hd]."""
    s = jnp.einsum("bqkgh,bckh->bkgqc", qc.astype(jnp.float32),
                   kc.astype(jnp.float32)) * scale
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    mask = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window > 0:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    s = jnp.where(mask[None, None, None], s, _NEG)
    m_new = jnp.maximum(m, s.max(-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l = l * alpha + p.sum(-1)
    acc = acc * alpha[..., None] + jnp.einsum(
        "bkgqc,bckh->bkgqh", p, vc.astype(jnp.float32))
    return m_new, l, acc


def _finish(l, acc, B, cq_total, H, hd, dtype):
    out = acc / jnp.maximum(l, 1e-30)[..., None]       # [nq?,B,Kh,G,cq,hd]
    return out


def _full_blocked(q, k, v, *, causal, softcap, scale, cq, ck):
    B, Sq, H, hd = q.shape
    _, Sk, Kh, _ = k.shape
    G = H // Kh
    nq, nk = Sq // cq, Sk // ck
    off = Sk - Sq
    qr = q.reshape(B, nq, cq, Kh, G, hd).transpose(1, 0, 2, 3, 4, 5)
    kr = k.reshape(B, nk, ck, Kh, hd).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(B, nk, ck, Kh, hd).transpose(1, 0, 2, 3, 4)

    def q_step(_, qin):
        qi, qc = qin
        qpos = off + qi * cq + jnp.arange(cq)

        def k_step(carry, kin):
            kj, kc, vc = kin
            m, l, acc = carry
            kpos = kj * ck + jnp.arange(ck)
            m, l, acc = _block(qc, kc, vc, qpos, kpos, m, l, acc,
                               causal=causal, window=0, softcap=softcap,
                               scale=scale)
            return (m, l, acc), None

        init = (jnp.full((B, Kh, G, cq), _NEG, jnp.float32),
                jnp.zeros((B, Kh, G, cq), jnp.float32),
                jnp.zeros((B, Kh, G, cq, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(k_step, init,
                                      (jnp.arange(nk), kr, vr))
        return None, acc / jnp.maximum(l, 1e-30)[..., None]

    _, out = jax.lax.scan(q_step, None, (jnp.arange(nq), qr))
    # out: [nq, B, Kh, G, cq, hd]
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


def _triangular_blocked(q, k, v, *, softcap, scale, cq, ck):
    """Causal Sq==Sk: statically skip above-diagonal blocks (~2× less work).

    Unrolled over q chunks; HLO size O(nq) — used for the 4k train shape.
    """
    B, S, H, hd = q.shape
    Kh = k.shape[2]
    G = H // Kh
    nq = S // cq
    outs = []
    for qi in range(nq):
        qc = q[:, qi * cq:(qi + 1) * cq].reshape(B, cq, Kh, G, hd)
        qpos = qi * cq + jnp.arange(cq)
        hi = (qi + 1) * cq          # keys strictly needed: [0, hi)
        nkb = hi // ck
        kr = k[:, :hi].reshape(B, nkb, ck, Kh, hd).transpose(1, 0, 2, 3, 4)
        vr = v[:, :hi].reshape(B, nkb, ck, Kh, hd).transpose(1, 0, 2, 3, 4)

        def k_step(carry, kin, qc=qc, qpos=qpos):
            kj, kc, vc = kin
            m, l, acc = carry
            kpos = kj * ck + jnp.arange(ck)
            m, l, acc = _block(qc, kc, vc, qpos, kpos, m, l, acc,
                               causal=True, window=0, softcap=softcap,
                               scale=scale)
            return (m, l, acc), None

        init = (jnp.full((B, Kh, G, cq), _NEG, jnp.float32),
                jnp.zeros((B, Kh, G, cq), jnp.float32),
                jnp.zeros((B, Kh, G, cq, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(k_step, init,
                                      (jnp.arange(nkb), kr, vr))
        o = acc / jnp.maximum(l, 1e-30)[..., None]     # [B,Kh,G,cq,hd]
        outs.append(o.transpose(0, 3, 1, 2, 4).reshape(B, cq, H, hd))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def _local_blocked(q, k, v, *, window, softcap, scale, cq):
    """Sliding-window attention: each q chunk sees a length-(window+cq) slice."""
    B, Sq, H, hd = q.shape
    _, Sk, Kh, _ = k.shape
    G = H // Kh
    nq = Sq // cq
    off = Sk - Sq
    L = window + cq
    qr = q.reshape(B, nq, cq, Kh, G, hd).transpose(1, 0, 2, 3, 4, 5)

    def q_step(_, qin):
        qi, qc = qin
        q0 = off + qi * cq
        start = jnp.clip(q0 + cq - L, 0, Sk - L)
        kc = jax.lax.dynamic_slice(k, (0, start, 0, 0), (B, L, Kh, hd))
        vc = jax.lax.dynamic_slice(v, (0, start, 0, 0), (B, L, Kh, hd))
        qpos = q0 + jnp.arange(cq)
        kpos = start + jnp.arange(L)
        m = jnp.full((B, Kh, G, cq), _NEG, jnp.float32)
        l = jnp.zeros((B, Kh, G, cq), jnp.float32)
        acc = jnp.zeros((B, Kh, G, cq, hd), jnp.float32)
        m, l, acc = _block(qc, kc, vc, qpos, kpos, m, l, acc, causal=True,
                           window=window, softcap=softcap, scale=scale)
        return None, acc / jnp.maximum(l, 1e-30)[..., None]

    _, out = jax.lax.scan(q_step, None, (jnp.arange(nq), qr))
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash attention with custom VJP (XLA-level): forward = online softmax,
# backward recomputes scores blockwise from (q, k, v, out, lse). Saves O(S)
# residuals instead of O(S^2) — the standard flash backward, expressed in
# chunked jnp so it lowers on any backend.
# ---------------------------------------------------------------------------


def _window_sliced(window, cq, Sk):
    """A window narrower than the keys takes a length-(window+cq) slice per
    q chunk; otherwise the global scan applies the window as a mask."""
    return window > 0 and window + cq <= Sk


def _fwd_blocked_lse(q, k, v, causal, window, softcap, scale, cq, ck):
    """Forward producing (out, lse). Window path slices; global path scans."""
    B, Sq, H, hd = q.shape
    _, Sk, Kh, _ = k.shape
    G = H // Kh
    nq = Sq // cq
    off = Sk - Sq
    qr = q.reshape(B, nq, cq, Kh, G, hd).transpose(1, 0, 2, 3, 4, 5)

    if _window_sliced(window, cq, Sk):
        L = window + cq

        def q_step(_, qin):
            qi, qc = qin
            q0 = off + qi * cq
            start = jnp.clip(q0 + cq - L, 0, Sk - L)
            kc = jax.lax.dynamic_slice(k, (0, start, 0, 0), (B, L, Kh, hd))
            vc = jax.lax.dynamic_slice(v, (0, start, 0, 0), (B, L, Kh, hd))
            qpos = q0 + jnp.arange(cq)
            kpos = start + jnp.arange(L)
            m = jnp.full((B, Kh, G, cq), _NEG, jnp.float32)
            l = jnp.zeros((B, Kh, G, cq), jnp.float32)
            acc = jnp.zeros((B, Kh, G, cq, hd), jnp.float32)
            m, l, acc = _block(qc, kc, vc, qpos, kpos, m, l, acc,
                               causal=True, window=window, softcap=softcap,
                               scale=scale)
            o = acc / jnp.maximum(l, 1e-30)[..., None]
            return None, (o, m + jnp.log(jnp.maximum(l, 1e-30)))

        _, (out, lse) = jax.lax.scan(q_step, None, (jnp.arange(nq), qr))
    else:
        nk = Sk // ck
        kr = k.reshape(B, nk, ck, Kh, hd).transpose(1, 0, 2, 3, 4)
        vr = v.reshape(B, nk, ck, Kh, hd).transpose(1, 0, 2, 3, 4)

        def q_step(_, qin):
            qi, qc = qin
            qpos = off + qi * cq + jnp.arange(cq)

            def k_step(carry, kin):
                kj, kc, vc = kin
                m, l, acc = carry
                kpos = kj * ck + jnp.arange(ck)
                return _block(qc, kc, vc, qpos, kpos, m, l, acc,
                              causal=causal, window=window, softcap=softcap,
                              scale=scale), None

            init = (jnp.full((B, Kh, G, cq), _NEG, jnp.float32),
                    jnp.zeros((B, Kh, G, cq), jnp.float32),
                    jnp.zeros((B, Kh, G, cq, hd), jnp.float32))
            (m, l, acc), _ = jax.lax.scan(k_step, init,
                                          (jnp.arange(nk), kr, vr))
            o = acc / jnp.maximum(l, 1e-30)[..., None]
            return None, (o, m + jnp.log(jnp.maximum(l, 1e-30)))

        _, (out, lse) = jax.lax.scan(q_step, None, (jnp.arange(nq), qr))
    # out: [nq,B,Kh,G,cq,hd]; lse: [nq,B,Kh,G,cq]
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, hd)
    lse = lse.transpose(1, 0, 4, 2, 3).reshape(B, Sq, H)
    return out.astype(q.dtype), lse


def _mask_for(qpos, kpos, causal, window):
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window > 0:
        m &= (qpos[:, None] - kpos[None, :]) < window
    return m


def _scores(qc, kc, qpos, kpos, causal, window, softcap, scale):
    """Returns (p_unnorm_exp_arg-ready raw scores s, tanh-term for softcap)."""
    s = jnp.einsum("bqkgh,bckh->bkgqc", qc.astype(jnp.float32),
                   kc.astype(jnp.float32)) * scale
    t = None
    if softcap > 0:
        t = jnp.tanh(s / softcap)
        s = t * softcap
    mask = _mask_for(qpos, kpos, causal, window)
    s = jnp.where(mask[None, None, None], s, _NEG)
    return s, t, mask


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, softcap, scale, cq, ck):
    out, _ = _fwd_blocked_lse(q, k, v, causal, window, softcap, scale,
                              cq, ck)
    return out


def _flash_fwd(q, k, v, causal, window, softcap, scale, cq, ck):
    out, lse = _fwd_blocked_lse(q, k, v, causal, window, softcap, scale,
                                cq, ck)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, softcap, scale, cq, ck, res, do):
    q, k, v, out, lse = res
    B, Sq, H, hd = q.shape
    _, Sk, Kh, _ = k.shape
    G = H // Kh
    nq = Sq // cq
    off = Sk - Sq
    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * out.astype(jnp.float32), -1)          # [B,Sq,H]

    qr = q.reshape(B, nq, cq, Kh, G, hd).transpose(1, 0, 2, 3, 4, 5)
    dor = dof.reshape(B, nq, cq, Kh, G, hd).transpose(1, 0, 2, 3, 4, 5)
    lser = lse.reshape(B, nq, cq, Kh, G).transpose(1, 0, 3, 4, 2)
    dlr = delta.reshape(B, nq, cq, Kh, G).transpose(1, 0, 3, 4, 2)

    def block_grads(qc, kc, vc, doc, lsec, dc, qpos, kpos):
        """One (q-chunk × k-chunk) gradient block."""
        s, t, mask = _scores(qc, kc, qpos, kpos, causal, window, softcap,
                             scale)
        p = jnp.exp(s - lsec[..., None])                        # [B,Kh,G,q,c]
        p = jnp.where(mask[None, None, None], p, 0.0)
        dv = jnp.einsum("bkgqc,bqkgh->bckh", p, doc)
        dp = jnp.einsum("bqkgh,bckh->bkgqc", doc, vc.astype(jnp.float32))
        ds = p * (dp - dc[..., None])
        if softcap > 0:
            ds = ds * (1.0 - jnp.square(t))
        ds = ds * scale
        dq = jnp.einsum("bkgqc,bckh->bqkgh", ds, kc.astype(jnp.float32))
        dk = jnp.einsum("bkgqc,bqkgh->bckh", ds, qc.astype(jnp.float32))
        return dq, dk, dv

    if _window_sliced(window, cq, Sk):
        L = window + cq
        dk_full = jnp.zeros((B, Sk, Kh, hd), jnp.float32)
        dv_full = jnp.zeros((B, Sk, Kh, hd), jnp.float32)

        def q_step(carry, qin):
            dk_full, dv_full = carry
            qi, qc, doc, lsec, dc = qin
            q0 = off + qi * cq
            start = jnp.clip(q0 + cq - L, 0, Sk - L)
            kc = jax.lax.dynamic_slice(k, (0, start, 0, 0), (B, L, Kh, hd))
            vc = jax.lax.dynamic_slice(v, (0, start, 0, 0), (B, L, Kh, hd))
            qpos = q0 + jnp.arange(cq)
            kpos = start + jnp.arange(L)
            dq, dk, dv = block_grads(qc, kc, vc, doc, lsec, dc, qpos, kpos)
            upd_k = jax.lax.dynamic_slice(dk_full, (0, start, 0, 0),
                                          (B, L, Kh, hd)) + dk
            upd_v = jax.lax.dynamic_slice(dv_full, (0, start, 0, 0),
                                          (B, L, Kh, hd)) + dv
            dk_full = jax.lax.dynamic_update_slice(dk_full, upd_k,
                                                   (0, start, 0, 0))
            dv_full = jax.lax.dynamic_update_slice(dv_full, upd_v,
                                                   (0, start, 0, 0))
            return (dk_full, dv_full), dq

        (dk_full, dv_full), dq = jax.lax.scan(
            q_step, (dk_full, dv_full),
            (jnp.arange(nq), qr, dor, lser, dlr))
        dq = dq.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, hd)
        return (dq.astype(q.dtype), dk_full.astype(k.dtype),
                dv_full.astype(v.dtype))

    nk = Sk // ck
    kr = k.reshape(B, nk, ck, Kh, hd).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(B, nk, ck, Kh, hd).transpose(1, 0, 2, 3, 4)

    def k_step(dq_acc, kin):
        kj, kc, vc = kin
        kpos = kj * ck + jnp.arange(ck)

        def q_step(carry, qin):
            dk_acc, dv_acc = carry
            qi, qc, doc, lsec, dc = qin
            qpos = off + qi * cq + jnp.arange(cq)
            dq, dk, dv = block_grads(qc, kc, vc, doc, lsec, dc, qpos, kpos)
            return (dk_acc + dk, dv_acc + dv), dq

        init = (jnp.zeros((B, ck, Kh, hd), jnp.float32),
                jnp.zeros((B, ck, Kh, hd), jnp.float32))
        (dk, dv), dq_parts = jax.lax.scan(
            q_step, init, (jnp.arange(nq), qr, dor, lser, dlr))
        return dq_acc + dq_parts, (dk, dv)

    dq0 = jnp.zeros((nq, B, cq, Kh, G, hd), jnp.float32)
    dq, (dk, dv) = jax.lax.scan(k_step, dq0, (jnp.arange(nk), kr, vr))
    dq = dq.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, hd)
    dk = dk.transpose(1, 0, 2, 3, 4).reshape(B, Sk, Kh, hd)
    dv = dv.transpose(1, 0, 2, 3, 4).reshape(B, Sk, Kh, hd)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def attention_decode(q, k_cache, v_cache, lengths, *, window=0, softcap=0.0,
                     scale=None, slot_positions=None):
    """Single-token decode over a (possibly ring-buffered) KV cache.

    q: [B,1,H,hd]; caches: [B,S,Kh,hd]; lengths: [B] tokens written so far
    (including the current one). ``slot_positions``: [B,S] absolute position
    held by each cache slot (ring buffers); None ⇒ slot i holds position i.
    """
    B, _, H, hd = q.shape
    _, S, Kh, _ = k_cache.shape
    G = H // Kh
    scale = scale if scale is not None else hd ** -0.5
    kpos = (jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            if slot_positions is None else slot_positions)
    valid = (kpos >= 0) & (kpos < lengths[:, None])
    if window > 0:
        valid &= kpos >= (lengths[:, None] - window)
    qf = q.reshape(B, Kh, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgh,bskh->bkgs", qf, k_cache.astype(jnp.float32)) * scale
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(valid[:, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)


# ===========================================================================
# RG-LRU
# ===========================================================================


def rglru(x, a_log, gate_a, gate_x, *, c=8.0, h0=None, impl=None):
    """Parallel RG-LRU scan. Shapes as in ``ref.rglru_ref``; supports an
    initial state ``h0`` [B,D]. Returns (y, h_final)."""
    impl = impl or default_impl()
    if impl == "pallas":
        _require_tpu("rglru_scan")
        from repro.kernels import rglru as _pl
        return _pl.rglru_scan(x, a_log, gate_a, gate_x, c=c, h0=h0)
    if impl == "ref" and h0 is None:
        return _ref.rglru_ref(x, a_log, gate_a, gate_x, c=c)
    xf = x.astype(jnp.float32)
    log_a = -c * jax.nn.softplus(a_log.astype(jnp.float32)) * \
        jax.nn.sigmoid(gate_a.astype(jnp.float32))
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    b = beta * jax.nn.sigmoid(gate_x.astype(jnp.float32)) * xf
    if h0 is not None:
        # fold h0 in as a virtual first step with a=0, b=h0
        a = jnp.concatenate([jnp.zeros_like(a[:, :1]), a], 1)
        b = jnp.concatenate([h0.astype(jnp.float32)[:, None], b], 1)

    def combine(ca, cb):
        a1, b1 = ca
        a2, b2 = cb
        return a2 * a1, a2 * b1 + b2

    aa, bb = jax.lax.associative_scan(combine, (a, b), axis=1)
    ys = bb if h0 is None else bb[:, 1:]
    return ys.astype(x.dtype), bb[:, -1]


def rglru_decode(h, x, a_log, gate_a, gate_x, *, c=8.0):
    """One recurrence step. h: [B,D]; x/gates: [B,D]. Returns (y, h_new)."""
    xf = x.astype(jnp.float32)
    log_a = -c * jax.nn.softplus(a_log.astype(jnp.float32)) * \
        jax.nn.sigmoid(gate_a.astype(jnp.float32))
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    h_new = a * h + beta * jax.nn.sigmoid(gate_x.astype(jnp.float32)) * xf
    return h_new.astype(x.dtype), h_new


# ===========================================================================
# Mamba-2 SSD (chunked state-space duality)
# ===========================================================================


def ssd(x, dt, A_log, B, C, *, D=None, h0=None, chunk=256, impl=None):
    """Chunked SSD. Shapes as in ``ref.ssd_ref``. Returns (y, h_final)."""
    impl = impl or default_impl()
    if impl == "pallas":
        _require_tpu("ssd_scan")
        from repro.kernels import ssd as _pl
        return _pl.ssd_scan(x, dt, A_log, B, C, D=D, h0=h0, chunk=chunk)
    if impl == "ref":
        return _ref.ssd_ref(x, dt, A_log, B, C, D=D, h0=h0)
    b, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    Q = _chunk_of(S, chunk)
    nc = S // Q
    rep = H // G
    xf = x.astype(jnp.float32).reshape(b, nc, Q, H, P)
    dtf = dt.astype(jnp.float32).reshape(b, nc, Q, H)
    Bf = jnp.repeat(B.astype(jnp.float32), rep, 2).reshape(b, nc, Q, H, N)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, 2).reshape(b, nc, Q, H, N)
    la = -jnp.exp(A_log.astype(jnp.float32))[None, None, None] * dtf
    La = jnp.cumsum(la, axis=2)                       # [b,nc,Q,H]
    xb = dtf[..., None] * xf                          # dt-weighted inputs

    # --- intra-chunk (quadratic within chunk) ------------------------------
    idx = jnp.arange(Q)
    tri = idx[:, None] >= idx[None, :]
    # decay(i,j) = exp(La_i - La_j) for i >= j
    dec = jnp.exp(jnp.clip(La[:, :, :, None] - La[:, :, None, :], -60, 0.0))
    gsc = jnp.einsum("bcihn,bcjhn->bchij", Cf, Bf)    # [b,nc,H,Q,Q]
    gsc = gsc * dec.transpose(0, 1, 4, 2, 3)          # [b,nc,i,j,H]->[b,nc,H,i,j]
    gsc = jnp.where(tri[None, None, None], gsc, 0.0)
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", gsc, xb)

    # --- per-chunk end states ----------------------------------------------
    dec_end = jnp.exp(La[:, :, -1:, :] - La)          # [b,nc,Q,H]
    st = jnp.einsum("bcqh,bcqhn,bcqhp->bchpn", dec_end, Bf, xb)

    # --- inter-chunk recurrence ---------------------------------------------
    A_chunk = jnp.exp(La[:, :, -1])                   # [b,nc,H]

    def step(h, inp):
        a_c, s_c = inp
        h_out = h                                      # state ENTERING chunk
        h = a_c[..., None, None] * h + s_c
        return h, h_out

    if h0 is None:
        h0 = jnp.zeros((b, H, P, N), jnp.float32)
    hT, h_in = jax.lax.scan(step, h0.astype(jnp.float32),
                            (A_chunk.swapaxes(0, 1), st.swapaxes(0, 1)))
    h_in = h_in.swapaxes(0, 1)                        # [b,nc,H,P,N]

    # --- inter-chunk contribution -------------------------------------------
    y_inter = jnp.einsum("bcqh,bcqhn,bchpn->bcqhp", jnp.exp(La), Cf, h_in)

    y = (y_intra + y_inter).reshape(b, S, H, P)
    if D is not None:
        y = y + D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype), hT


def ssd_decode(h, x, dt, A_log, B, C, *, D=None):
    """One SSD step. h: [b,H,P,N]; x: [b,H,P]; dt: [b,H]; B,C: [b,G,N]."""
    b, H, P, N = h.shape
    G = B.shape[1]
    rep = H // G
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    a = jnp.exp(-jnp.exp(A_log.astype(jnp.float32))[None] * dtf)   # [b,H]
    Bf = jnp.repeat(B.astype(jnp.float32), rep, 1)                 # [b,H,N]
    Cf = jnp.repeat(C.astype(jnp.float32), rep, 1)
    h = a[..., None, None] * h + \
        (dtf[..., None] * xf)[..., None] * Bf[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", h, Cf)
    if D is not None:
        y = y + D.astype(jnp.float32)[None, :, None] * xf
    return y.astype(x.dtype), h
