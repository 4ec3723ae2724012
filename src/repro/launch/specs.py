"""Input/state specs per (arch × shape): ShapeDtypeStruct stand-ins and
NamedShardings — shared by the dry-run, trainer, and server. No allocation.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs.base import ModelConfig, ShapeConfig, get_config
from repro.models.model import LM
from repro.optim import adamw
from repro.sharding import partition as part


def batch_specs(cfg: ModelConfig, shape: ShapeConfig,
                compute_dtype=jnp.bfloat16) -> Tuple[Dict, Dict]:
    """(ShapeDtypeStructs, logical-axes) for one training/prefill batch."""
    B, S = shape.global_batch, shape.seq_len
    sds, axes = {}, {}
    if cfg.family == "vlm":
        Sv = cfg.frontend_tokens
        sds["vision_embeds"] = jax.ShapeDtypeStruct((B, Sv, cfg.d_model),
                                                    compute_dtype)
        axes["vision_embeds"] = ("batch", "seq", None)
        sds["tokens"] = jax.ShapeDtypeStruct((B, S - Sv), jnp.int32)
        axes["tokens"] = ("batch", "seq")
    elif cfg.family == "encdec":
        sds["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                             compute_dtype)
        axes["frames"] = ("batch", "seq", None)
        sds["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        axes["tokens"] = ("batch", "seq")
    else:
        sds["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        axes["tokens"] = ("batch", "seq")
    return sds, axes


def shardings_of(tree_sds, tree_axes, mesh, rules=None):
    return jax.tree.map(
        lambda s, a: NamedSharding(mesh, part.resolve(a, s.shape, mesh,
                                                      rules)),
        tree_sds, tree_axes,
        is_leaf=lambda t: isinstance(t, jax.ShapeDtypeStruct))


def input_specs(arch_or_cfg, shape: ShapeConfig, mesh, *, rules=None,
                cfg_overrides=None) -> Dict[str, Any]:
    """Everything needed to lower one cell.

    Returns dict with: cfg, lm, kind, args (ShapeDtypeStructs tuple),
    in_shardings, out_shardings, donate_argnums, fn-builder inputs.
    """
    cfg = (get_config(arch_or_cfg) if isinstance(arch_or_cfg, str)
           else arch_or_cfg)
    if shape.kind != "train":
        # decode/prefill shapes size the enc-dec frontend to the shape
        if cfg.family == "encdec":
            cfg = cfg.replace(frontend_tokens=shape.seq_len)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    lm = LM(cfg)
    cdt = jnp.dtype(cfg.dtype)
    p_abs = lm.abstract()
    p_axes = lm.specs()
    p_sh = shardings_of(p_abs, p_axes, mesh, rules)

    if shape.kind == "train":
        sds, axes = batch_specs(cfg, shape, cdt)
        st_abs = adamw.abstract_state(p_abs)
        st_axes = adamw.state_logical(p_axes)
        st_sh = shardings_of(st_abs, st_axes, mesh, rules)
        b_sh = shardings_of(sds, axes, mesh, rules)
        return dict(cfg=cfg, lm=lm, kind="train",
                    args=(st_abs, sds), in_shardings=(st_sh, b_sh),
                    out_shardings=(st_sh, None), donate_argnums=(0,))

    if shape.kind == "prefill":
        sds, axes = batch_specs(cfg, shape, cdt)
        b_sh = shardings_of(sds, axes, mesh, rules)
        return dict(cfg=cfg, lm=lm, kind="prefill", capacity=shape.seq_len,
                    args=(p_abs, sds), in_shardings=(p_sh, b_sh),
                    out_shardings=None, donate_argnums=())

    # decode: one new token with a cache of capacity seq_len
    B = shape.global_batch
    cache_abs = lm.init_cache(B, shape.seq_len)
    cache_axes = lm.cache_logical()
    c_sh = shardings_of(cache_abs, cache_axes, mesh, rules)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_sh = NamedSharding(mesh, part.resolve(("batch", None), (B, 1),
                                              mesh, rules))
    return dict(cfg=cfg, lm=lm, kind="decode",
                args=(p_abs, cache_abs, tok),
                in_shardings=(p_sh, c_sh, tok_sh),
                out_shardings=(c_sh, None), donate_argnums=(1,))


def build_fn(spec, *, opt_cfg=None, impl=None, schedule="full"):
    lm = spec["lm"]
    if spec["kind"] == "train":
        opt_cfg = opt_cfg or adamw.OptConfig()
        return adamw.make_train_step(lm, opt_cfg,
                                     impl=impl or adamw.TRAIN_IMPL,
                                     schedule_kind=schedule)
    if spec["kind"] == "prefill":
        cap = spec["capacity"]

        def prefill(params, batch):
            return lm.prefill(params, batch, cap, impl=impl)
        return prefill

    def decode(params, cache, tokens):
        return lm.decode_step(params, cache, tokens, impl=impl)
    return decode
