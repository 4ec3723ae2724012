"""Sharded AdamW with cosine schedule, global-norm clipping, and an optional
gradient-compression hook (int8 stochastic-rounding all-reduce emulation —
the beyond-paper distributed-optimisation knob; see EXPERIMENTS.md §Perf).

State layout is a plain dict pytree — {step, params, m, v} — so the MigrOS
dump/restore machinery serialises it like any other container state.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    compress_grads: bool = False     # int8 compression before reduction


def schedule(cfg: OptConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / max(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps) /
                    max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    return cfg.lr * warm * (0.5 * (1 + jnp.cos(jnp.pi * prog)))


def init_state(params) -> Dict[str, Any]:
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p), params)
    return {"step": jnp.zeros((), jnp.int32), "params": params,
            "m": zeros, "v": jax.tree.map(lambda p: jnp.zeros_like(p),
                                          params)}


def abstract_state(abstract_params) -> Dict[str, Any]:
    z = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                     abstract_params)
    return {"step": jax.ShapeDtypeStruct((), jnp.int32),
            "params": abstract_params, "m": z,
            "v": jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype), abstract_params)}


def state_logical(param_logical) -> Dict[str, Any]:
    return {"step": (), "params": param_logical, "m": param_logical,
            "v": param_logical}


def _compress(g, key):
    """int8 stochastic-rounding quantise/dequantise (per-tensor scale).

    Emulates compressed gradient reduction: the all-reduce then moves 1/4 of
    the bytes. Unbiased via stochastic rounding.
    """
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0
    q = g / scale
    noise = jax.random.uniform(key, g.shape, jnp.float32, -0.5, 0.5)
    q = jnp.clip(jnp.round(q + noise), -127, 127).astype(jnp.int8)
    return q.astype(jnp.float32) * scale


def apply_updates(cfg: OptConfig, state, grads, rng=None):
    step = state["step"] + 1
    lr = schedule(cfg, step)

    if cfg.compress_grads:
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        leaves, treedef = jax.tree.flatten(grads)
        keys = jax.random.split(jax.random.fold_in(rng, step), len(leaves))
        grads = jax.tree.unflatten(
            treedef, [_compress(g, k) for g, k in zip(leaves, keys)])

    if cfg.clip_norm > 0:
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gn, 1e-12))
        grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)
    else:
        gn = jnp.zeros((), jnp.float32)

    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32)
        m = b1 * m.astype(jnp.float32) + (1 - b1) * gf
        v = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(gf)
        u = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
        u = u + cfg.weight_decay * p.astype(jnp.float32)
        return ((p.astype(jnp.float32) - lr * u).astype(p.dtype),
                m.astype(p.dtype), v.astype(p.dtype))

    out = jax.tree.map(upd, state["params"], grads, state["m"], state["v"])
    params = jax.tree.map(lambda t: t[0], out,
                          is_leaf=lambda t: isinstance(t, tuple))
    m = jax.tree.map(lambda t: t[1], out,
                     is_leaf=lambda t: isinstance(t, tuple))
    v = jax.tree.map(lambda t: t[2], out,
                     is_leaf=lambda t: isinstance(t, tuple))
    new = {"step": step, "params": params, "m": m, "v": v}
    return new, {"grad_norm": gn, "lr": lr}


# The kernel implementation of the differentiated forward (see
# ``repro.kernels.ops``). It must have a backward pass: the Pallas kernels,
# which prefill and decode take on TPU, are forward only, so training takes
# the XLA flash path (hand-written backward) on every backend.
TRAIN_IMPL = "flash"


def make_train_step(lm, cfg: OptConfig, *, impl=TRAIN_IMPL,
                    schedule_kind="full"):
    """Returns train_step(state, batch) -> (state, metrics)."""

    def train_step(state, batch):
        def loss_fn(params):
            loss, metrics = lm.loss(params, batch, impl=impl,
                                    schedule=schedule_kind)
            return loss, metrics
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])
        state, om = apply_updates(cfg, state, grads)
        return state, dict(metrics, loss=loss, **om)

    return train_step
