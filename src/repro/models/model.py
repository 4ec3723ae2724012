"""Unified language model covering all assigned families.

Depth is organised as   head (unrolled) + core (period-scanned) + tail
(unrolled)   so heterogeneous layer patterns (gemma3 5:1 local:global,
recurrentgemma rec-rec-attn, deepseek first-k-dense) compile with O(period)
HLO. Parameters/caches for the core are stacked over periods and scanned.

Public API (all pure functions over explicit pytrees):
    LM(cfg).init(key) / .abstract() / .specs()
    .forward(params, batch)            -> (logits, aux)
    .loss(params, batch)               -> (loss, metrics)
    .prefill(params, batch, capacity)  -> (cache, last_logits)
    .decode_step(params, cache, tok)   -> (cache, logits)
    .init_cache(batch, capacity)       -> abstract cache tree
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import moe as MOE
from repro.models import rglru as REC
from repro.models import ssm as SSM
from repro.models.layers import (ParamDef, abstract_params, apply_mlp,
                                 apply_norm, init_params, logical_specs,
                                 mlp_def, norm_def)
from repro.sharding.partition import constrain

# ---------------------------------------------------------------------------
# Layer definitions
# ---------------------------------------------------------------------------

_MIXER_DEF = {
    "attn": A.attn_def, "local": A.attn_def, "enc": A.attn_def,
    "mla": A.mla_def, "rec": REC.rec_def, "ssm": SSM.ssm_def,
}


def _mlp_width(cfg: ModelConfig, mlpk: str) -> int:
    if cfg.moe is not None and mlpk == "dense":
        return cfg.moe.d_ff_dense or cfg.d_ff
    return cfg.d_ff


def layer_def(cfg: ModelConfig, kind: Tuple[str, str]):
    mixer, mlpk = kind
    d: Dict[str, Any] = {"ln1": norm_def(cfg)}
    if mixer == "xdec":
        d["mixer"] = A.attn_def(cfg)
        d["ln_x"] = norm_def(cfg)
        d["cross"] = A.xattn_def(cfg)
    else:
        d["mixer"] = _MIXER_DEF[mixer](cfg)
    if mlpk == "moe":
        d["ln2"] = norm_def(cfg)
        d["mlp"] = MOE.moe_def(cfg)
    elif mlpk == "dense":
        d["ln2"] = norm_def(cfg)
        d["mlp"] = mlp_def(cfg, _mlp_width(cfg, mlpk))
    return d


# each block's named scope, which the HLO's op names carry
_SCOPE = {"attn": "attention", "local": "attention", "enc": "attention",
          "mla": "attention", "xdec": "attention", "rec": "rglru",
          "ssm": "ssm"}


def layer_apply(cfg, kind, p, x, ctx):
    """Full-sequence layer. Returns (x, aux)."""
    mixer, mlpk = kind
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope(_SCOPE[mixer]):
        h = apply_norm(cfg, p["ln1"], x)
        if mixer in ("attn", "local", "enc"):
            mx = A.attn_forward(cfg, p["mixer"], h, ctx["positions"],
                                kind=mixer, causal=(mixer != "enc"),
                                impl=ctx.get("impl"),
                                schedule=ctx.get("schedule", "full"))
        elif mixer == "mla":
            mx = A.mla_forward(cfg, p["mixer"], h, ctx["positions"],
                               impl=ctx.get("impl"),
                               schedule=ctx.get("schedule", "full"))
        elif mixer == "rec":
            mx = REC.rec_forward(cfg, p["mixer"], h, impl=ctx.get("impl"))
        elif mixer == "ssm":
            mx = SSM.ssm_forward(cfg, p["mixer"], h, impl=ctx.get("impl"))
        elif mixer == "xdec":
            mx = A.attn_forward(cfg, p["mixer"], h, ctx["positions"],
                                kind="attn", impl=ctx.get("impl"),
                                schedule=ctx.get("schedule", "full"))
    x = x + mx
    if mixer == "xdec":
        hx = apply_norm(cfg, p["ln_x"], x)
        k, v = A.xattn_kv(cfg, p["cross"], ctx["enc_out"])
        x = x + A.xattn_forward(cfg, p["cross"], hx, k, v,
                                impl=ctx.get("impl"))
    with jax.named_scope("mlp"):
        if mlpk == "moe":
            h = apply_norm(cfg, p["ln2"], x)
            mo, a = MOE.moe_apply(cfg, p["mlp"], h)
            x, aux = x + mo, aux + a
        elif mlpk == "dense":
            h = apply_norm(cfg, p["ln2"], x)
            x = x + apply_mlp(cfg.replace(d_ff=_mlp_width(cfg, mlpk)),
                              p["mlp"], h)
    x = constrain(x, ("batch", "seq", None))
    return x, aux


def layer_cache_def(cfg, kind, batch, capacity, dtype):
    mixer, _ = kind
    if mixer in ("attn", "local"):
        return A.attn_cache_def(cfg, mixer, batch, capacity, dtype)
    if mixer == "mla":
        return A.mla_cache_def(cfg, batch, capacity, dtype)
    if mixer == "rec":
        return REC.rec_cache_def(cfg, batch, dtype)
    if mixer == "ssm":
        return SSM.ssm_cache_def(cfg, batch, dtype)
    if mixer == "xdec":
        d = A.attn_cache_def(cfg, "attn", batch, capacity, dtype)
        Se = cfg.frontend_tokens if capacity is None else None
        return d  # cross K/V added by prefill (shape depends on enc len)
    raise ValueError(mixer)


def layer_cache_axes(cfg, kind):
    mixer, _ = kind
    if mixer in ("attn", "local"):
        return A.attn_cache_axes(cfg, mixer)
    if mixer == "mla":
        return A.mla_cache_axes(cfg)
    if mixer == "rec":
        return REC.rec_cache_axes(cfg)
    if mixer == "ssm":
        return SSM.ssm_cache_axes(cfg)
    if mixer == "xdec":
        d = A.attn_cache_axes(cfg, "attn")
        x = ("batch", "seq_data", "heads", None)
        return dict(d, xk=x, xv=x)
    raise ValueError(mixer)


def layer_decode(cfg, kind, p, x, cache, ctx):
    mixer, mlpk = kind
    with jax.named_scope(_SCOPE[mixer]):
        h = apply_norm(cfg, p["ln1"], x)
        if mixer in ("attn", "local"):
            mx, cache = A.attn_decode(cfg, p["mixer"], h, cache,
                                      ctx["positions"], kind=mixer)
        elif mixer == "mla":
            mx, cache = A.mla_decode(cfg, p["mixer"], h, cache,
                                     ctx["positions"])
        elif mixer == "rec":
            mx, c2 = REC.rec_decode(cfg, p["mixer"], h,
                                    {"conv": cache["conv"], "h": cache["h"]})
            cache = dict(cache, **c2)
        elif mixer == "ssm":
            mx, c2 = SSM.ssm_decode(cfg, p["mixer"], h,
                                    {"conv": cache["conv"], "h": cache["h"]})
            cache = dict(cache, **c2)
        elif mixer == "xdec":
            sc = {k: cache[k] for k in ("k", "v")}
            mx, sc = A.attn_decode(cfg, p["mixer"], h, sc, ctx["positions"],
                                   kind="attn")
            cache = dict(cache, **sc)
    x = x + mx
    if mixer == "xdec":
        hx = apply_norm(cfg, p["ln_x"], x)
        x = x + A.xattn_decode(cfg, p["cross"], hx,
                               {"xk": cache["xk"], "xv": cache["xv"]})
    with jax.named_scope("mlp"):
        if mlpk == "moe":
            h = apply_norm(cfg, p["ln2"], x)
            mo, _ = MOE.moe_apply(cfg, p["mlp"], h)
            x = x + mo
        elif mlpk == "dense":
            h = apply_norm(cfg, p["ln2"], x)
            x = x + apply_mlp(cfg.replace(d_ff=_mlp_width(cfg, mlpk)),
                              p["mlp"], h)
    return x, cache


def layer_prefill(cfg, kind, p, x, ctx, capacity):
    """Full-sequence apply that also emits this layer's decode cache."""
    mixer, _ = kind
    h = apply_norm(cfg, p["ln1"], x)
    if mixer in ("attn", "local"):
        cache = A.attn_prefill_cache(cfg, p["mixer"], h, ctx["positions"],
                                     kind=mixer, capacity=capacity)
    elif mixer == "mla":
        cache = A.mla_prefill_cache(cfg, p["mixer"], h, ctx["positions"],
                                    capacity=capacity)
    elif mixer == "rec":
        dt = x.dtype
        u = h @ p["mixer"]["wx"].astype(dt)
        uc = REC._conv_full(u, p["mixer"]["conv_w"].astype(dt))
        R, nh, bh = REC._dims(cfg)
        ga = REC._block_gate(uc, p["mixer"]["w_ga"], p["mixer"]["b_ga"],
                             nh, bh)
        gx = REC._block_gate(uc, p["mixer"]["w_gx"], p["mixer"]["b_gx"],
                             nh, bh)
        from repro.kernels import ops
        _, hT = ops.rglru(uc, p["mixer"]["a_log"], ga, gx, c=cfg.rglru_c,
                          impl=ctx.get("impl"))
        K = cfg.rnn_conv
        cache = {"conv": u[:, -(K - 1):], "h": hT}
    elif mixer == "ssm":
        dt_ = x.dtype
        z, xBC, dtp, (s, d_inner, H, gn) = SSM._split(
            cfg, h @ p["mixer"]["in_proj"].astype(dt_))
        xc = SSM._conv_full(xBC, p["mixer"]["conv_w"].astype(dt_))
        B_, S_ = x.shape[0], x.shape[1]
        xs = xc[..., :d_inner].reshape(B_, S_, H, s.head_dim)
        Bm = xc[..., d_inner:d_inner + gn].reshape(B_, S_, s.ngroups,
                                                   s.d_state)
        Cm = xc[..., d_inner + gn:].reshape(B_, S_, s.ngroups, s.d_state)
        dtv = jax.nn.softplus(dtp.astype(jnp.float32) +
                              p["mixer"]["dt_bias"].astype(jnp.float32))
        from repro.kernels import ops
        _, hT = ops.ssd(xs, dtv, p["mixer"]["A_log"], Bm, Cm,
                        D=p["mixer"]["D"], chunk=s.chunk_size,
                        impl=ctx.get("impl"))
        cache = {"conv": xBC[:, -(s.d_conv - 1):], "h": hT}
    elif mixer == "xdec":
        cache = A.attn_prefill_cache(cfg, p["mixer"], h, ctx["positions"],
                                     kind="attn", capacity=capacity)
        k, v = A.xattn_kv(cfg, p["cross"], ctx["enc_out"])
        cache = dict(cache, xk=k, xv=v)
    else:
        raise ValueError(mixer)
    x, aux = layer_apply(cfg, kind, p, x, ctx)
    return x, cache, aux


# ---------------------------------------------------------------------------
# Depth segmentation + stacks
# ---------------------------------------------------------------------------


class Stack:
    """head (unrolled) + core (period-scanned) + tail (unrolled)."""

    def __init__(self, cfg: ModelConfig, kinds: Sequence[Tuple[str, str]],
                 period: int, head_n: int = 0):
        self.cfg = cfg
        self.kinds = list(kinds)
        L = len(kinds)
        if not cfg.scan_layers:
            head_n, period = 0, max(L, 1)
        self.head = self.kinds[:head_n]
        rest = L - head_n
        self.n_periods = rest // period if cfg.scan_layers else 0
        if self.n_periods <= 1:   # scanning 1 period is pure overhead
            self.n_periods = 0
        core_n = self.n_periods * period
        self.period_kinds = self.kinds[head_n:head_n + period] \
            if self.n_periods else []
        for i in range(core_n):
            assert self.kinds[head_n + i] == self.period_kinds[i % period]
        self.tail = self.kinds[head_n + core_n:]

    # -- parameter trees ------------------------------------------------------
    def defs(self):
        cfg = self.cfg

        def stacked(d: ParamDef) -> ParamDef:
            return ParamDef((self.n_periods,) + d.shape,
                            ("layers",) + d.axes, d.init, d.scale)

        return {
            "head": [layer_def(cfg, k) for k in self.head],
            "core": [jax.tree.map(stacked, layer_def(cfg, k),
                                  is_leaf=lambda t: isinstance(t, ParamDef))
                     for k in self.period_kinds],
            "tail": [layer_def(cfg, k) for k in self.tail],
        }

    def cache_defs(self, batch, capacity, dtype):
        cfg = self.cfg

        def stacked(s: jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct((self.n_periods,) + s.shape, s.dtype)

        return {
            "head": [layer_cache_def(cfg, k, batch, capacity, dtype)
                     for k in self.head],
            "core": [jax.tree.map(stacked,
                                  layer_cache_def(cfg, k, batch, capacity,
                                                  dtype))
                     for k in self.period_kinds],
            "tail": [layer_cache_def(cfg, k, batch, capacity, dtype)
                     for k in self.tail],
        }

    def cache_axes(self):
        cfg = self.cfg
        is_tup = lambda t: isinstance(t, tuple)  # noqa: E731

        def stacked(axes):
            return ("layers",) + axes

        return {
            "head": [layer_cache_axes(cfg, k) for k in self.head],
            "core": [jax.tree.map(stacked, layer_cache_axes(cfg, k),
                                  is_leaf=is_tup)
                     for k in self.period_kinds],
            "tail": [layer_cache_axes(cfg, k) for k in self.tail],
        }

    # -- forward ---------------------------------------------------------------
    def _remat(self, fn):
        r = self.cfg.remat
        if r == "none":
            return fn
        if r == "dots_saveable":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.dots_saveable)
        return jax.checkpoint(fn)

    def apply(self, params, x, ctx):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        for k, p in zip(self.head, params["head"]):
            body = self._remat(
                lambda p, x, k=k: layer_apply(cfg, k, p, x, ctx))
            x, a = body(p, x)
            aux = aux + a
        if self.n_periods:
            def period_body(carry, pslices):
                x, aux = carry
                for i, k in enumerate(self.period_kinds):
                    x, a = layer_apply(cfg, k, pslices[i], x, ctx)
                    aux = aux + a
                return (x, aux), None
            (x, aux), _ = jax.lax.scan(self._remat(period_body), (x, aux),
                                       tuple(params["core"]))
        for k, p in zip(self.tail, params["tail"]):
            body = self._remat(
                lambda p, x, k=k: layer_apply(cfg, k, p, x, ctx))
            x, a = body(p, x)
            aux = aux + a
        return x, aux

    def decode(self, params, x, cache, ctx):
        cfg = self.cfg
        new_head = []
        for k, p, c in zip(self.head, params["head"], cache["head"]):
            x, c = layer_decode(cfg, k, p, x, c, ctx)
            new_head.append(c)
        new_core = cache["core"]
        if self.n_periods:
            def period_body(x, sl):
                ps, cs = sl
                ncs = []
                for i, k in enumerate(self.period_kinds):
                    x, nc = layer_decode(cfg, k, ps[i], x, cs[i], ctx)
                    ncs.append(nc)
                return x, tuple(ncs)
            x, new_core = jax.lax.scan(
                period_body, x, (tuple(params["core"]),
                                 tuple(cache["core"])))
            new_core = list(new_core)
        new_tail = []
        for k, p, c in zip(self.tail, params["tail"], cache["tail"]):
            x, c = layer_decode(cfg, k, p, x, c, ctx)
            new_tail.append(c)
        return x, {"head": new_head, "core": new_core, "tail": new_tail}

    def prefill(self, params, x, ctx, capacity):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        head_c, tail_c = [], []
        for k, p in zip(self.head, params["head"]):
            x, c, a = layer_prefill(cfg, k, p, x, ctx, capacity)
            head_c.append(c)
            aux = aux + a
        core_c = []
        if self.n_periods:
            def period_body(carry, ps):
                x, aux = carry
                cs = []
                for i, k in enumerate(self.period_kinds):
                    x, c, a = layer_prefill(cfg, k, ps[i], x, ctx, capacity)
                    cs.append(c)
                    aux = aux + a
                return (x, aux), tuple(cs)
            (x, aux), core_c = jax.lax.scan(period_body, (x, aux),
                                            tuple(params["core"]))
            core_c = list(core_c)
        for k, p in zip(self.tail, params["tail"]):
            x, c, a = layer_prefill(cfg, k, p, x, ctx, capacity)
            tail_c.append(c)
            aux = aux + a
        return x, {"head": head_c, "core": core_c, "tail": tail_c}, aux


# ---------------------------------------------------------------------------
# LM
# ---------------------------------------------------------------------------


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        mixers = cfg.layer_kinds
        kinds = [(mixers[i], "none" if (cfg.d_ff == 0 and cfg.moe is None)
                  else cfg.mlp_kind_at(i)) for i in range(cfg.num_layers)]
        head_n = cfg.moe.first_k_dense if cfg.moe is not None else 0
        if cfg.encoder_layers:
            kinds = [("xdec", k[1]) for k in kinds]
            self.encoder = Stack(cfg, [("enc", "dense")] * cfg.encoder_layers,
                                 period=1)
        else:
            self.encoder = None
        self.decoder = Stack(cfg, kinds, period=len(cfg.layer_pattern),
                             head_n=head_n)
        self.compute_dtype = jnp.dtype(cfg.dtype)
        self.param_dtype = jnp.dtype(cfg.param_dtype)

    # -- params -----------------------------------------------------------------
    def defs(self):
        cfg = self.cfg
        D, V = cfg.d_model, cfg.padded_vocab
        d: Dict[str, Any] = {
            "embed": ParamDef((V, D), ("vocab", "embed"), "fixed",
                              scale=0.02),
            "final_norm": norm_def(cfg),
            "decoder": self.decoder.defs(),
        }
        if not cfg.tie_embeddings:
            d["head"] = ParamDef((D, V), ("embed", "vocab"))
        if self.encoder is not None:
            d["encoder"] = self.encoder.defs()
            d["enc_norm"] = norm_def(cfg)
        return d

    def init(self, key):
        return init_params(self.defs(), key, self.param_dtype)

    def abstract(self):
        return abstract_params(self.defs(), self.param_dtype)

    def specs(self):
        return logical_specs(self.defs())

    # -- embedding / logits -------------------------------------------------------
    def _embed(self, params, tokens):
        cfg = self.cfg
        x = params["embed"][tokens].astype(self.compute_dtype)
        if cfg.scale_embeddings:
            x = x * jnp.asarray(cfg.d_model ** 0.5, self.compute_dtype)
        return x

    def _logits(self, params, x):
        cfg = self.cfg
        with jax.named_scope("head"):
            x = apply_norm(cfg, params["final_norm"], x)
            w = (params["embed"].T if cfg.tie_embeddings else params["head"])
            logits = x @ w.astype(self.compute_dtype)
            if cfg.logits_softcap > 0:
                logits = jnp.tanh(logits / cfg.logits_softcap) * \
                    cfg.logits_softcap
        return constrain(logits, ("batch", "seq", "vocab"))

    def _inputs(self, params, batch):
        """Returns (x, positions, enc_out, loss_mask_offset)."""
        cfg = self.cfg
        if cfg.encoder_layers:
            enc = batch["frames"].astype(self.compute_dtype)
            B, Se, _ = enc.shape
            pos = jnp.broadcast_to(jnp.arange(Se)[None], (B, Se))
            enc, _ = self.encoder.apply(params["encoder"], enc,
                                        {"positions": pos})
            enc = apply_norm(cfg, params["enc_norm"], enc)
            tok = batch["tokens"]
            x = self._embed(params, tok)
            return x, None, enc, 0
        if cfg.frontend == "vision":
            ve = batch["vision_embeds"].astype(self.compute_dtype)
            x = jnp.concatenate([ve, self._embed(params, batch["tokens"])],
                                1)
            return x, None, None, ve.shape[1]
        return self._embed(params, batch["tokens"]), None, None, 0

    # -- full-sequence forward ------------------------------------------------------
    def forward(self, params, batch, *, impl=None, schedule="full"):
        x, _, enc_out, off = self._inputs(params, batch)
        B, S, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x = constrain(x, ("batch", "seq", None))
        ctx = {"positions": pos, "enc_out": enc_out, "impl": impl,
               "schedule": schedule}
        x, aux = self.decoder.apply(params["decoder"], x, ctx)
        return self._logits(params, x), aux, off

    def loss(self, params, batch, *, impl=None, schedule="full"):
        cfg = self.cfg
        logits, aux, off = self.forward(params, batch, impl=impl,
                                        schedule=schedule)
        B, S, V = logits.shape
        # predict token t+1 from position t, text region only
        lg = logits[:, off:S - 1]
        labels = batch["tokens"][:, 1:]
        lf = lg.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
        ce = jnp.mean(lse - gold)
        return ce + aux, {"ce": ce, "aux": aux}

    # -- serving ---------------------------------------------------------------------
    def init_cache(self, batch, capacity):
        d = {
            "lengths": jax.ShapeDtypeStruct((batch,), jnp.int32),
            "layers": self.decoder.cache_defs(batch, capacity,
                                              self.compute_dtype),
        }
        if self.cfg.encoder_layers:
            Kh, hd = self.cfg.num_kv_heads, self.cfg.head_dim
            Se = self.cfg.frontend_tokens
            x = jax.ShapeDtypeStruct((batch, Se, Kh, hd), self.compute_dtype)
            for part in ("head", "core", "tail"):
                lst = d["layers"][part]
                for i, c in enumerate(lst):
                    if part == "core":
                        n = self.decoder.n_periods
                        xs = jax.ShapeDtypeStruct((n,) + x.shape, x.dtype)
                        lst[i] = dict(c, xk=xs, xv=xs)
                    else:
                        lst[i] = dict(c, xk=x, xv=x)
        return d

    def cache_logical(self):
        """Logical-axis tree matching ``init_cache`` structure."""
        return {"lengths": ("batch",),
                "layers": self.decoder.cache_axes()}

    def materialize_cache(self, batch, capacity):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.init_cache(batch, capacity))

    @jax.named_scope("prefill")
    def prefill(self, params, batch, capacity, *, impl=None):
        x, _, enc_out, off = self._inputs(params, batch)
        B, S, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        ctx = {"positions": pos, "enc_out": enc_out, "impl": impl,
               "schedule": "full"}
        x, layer_cache, _ = self.decoder.prefill(params["decoder"], x, ctx,
                                                 capacity)
        cache = {"lengths": jnp.full((B,), S, jnp.int32),
                 "layers": layer_cache}
        logits = self._logits(params, x[:, -1:])
        return cache, logits[:, 0]

    @jax.named_scope("decode")
    def decode_step(self, params, cache, tokens, *, impl=None):
        """tokens: [B,1] -> (cache, logits [B,V])."""
        x = self._embed(params, tokens)
        positions = cache["lengths"]
        ctx = {"positions": positions, "impl": impl}
        x, layers = self.decoder.decode(params["decoder"], x,
                                        cache["layers"], ctx)
        logits = self._logits(params, x)
        new = {"lengths": cache["lengths"] + 1, "layers": layers}
        return new, logits[:, 0]
