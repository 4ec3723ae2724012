"""Seeded weights of a configuration, made on the device in one jitted call.

The weights are a flat dict of named arrays, each layer's matrices stacked
over the layers ([L, ...]). The benchmark hands them to the program (see
``arch/``) and makes them again for the plain reference after the window,
so the reference takes nothing that the program has made. This module
imports nothing of the program.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
NORM_STD = 0.1


def dims(cfg: dict) -> dict:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "D": D, "H": H,
            "Kh": cfg["num_key_value_heads"], "hd": D // H,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def spec(cfg: dict):
    """(name, shape, kind) of every weight; kind picks the initialiser."""
    d = dims(cfg)
    L, D, H, Kh, hd, F, V = (d[k] for k in ("L", "D", "H", "Kh", "hd", "F",
                                             "V"))
    out = [("embed", (V, D), "embed"), ("head", (D, V), "matrix"),
           ("final_norm.scale", (D,), "scale"),
           ("final_norm.bias", (D,), "bias")]
    for ln in ("ln1", "ln2"):
        out += [(f"{ln}.scale", (L, D), "scale"),
                (f"{ln}.bias", (L, D), "bias")]
    out += [("wq", (L, D, H * hd), "matrix"), ("wk", (L, D, Kh * hd), "matrix"),
            ("wv", (L, D, Kh * hd), "matrix"), ("wo", (L, H * hd, D), "matrix"),
            ("wi_gate", (L, D, F), "matrix"), ("wi_up", (L, D, F), "matrix"),
            ("wo_mlp", (L, F, D), "matrix")]
    return out


def seed_words(seed: int):
    """A seed of up to 64 bits as two uint32 words, traced by the jitted
    generator so that every seed shares one compiled program."""
    seed = int(seed) % 2 ** 64
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def _one(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "embed":
        return z * EMBED_STD
    if kind == "scale":
        return 1.0 + NORM_STD * z
    if kind == "bias":
        return NORM_STD * z
    fan_in = shape[-2]
    return z * (fan_in ** -0.5)


def generate(cfg: dict, lo, hi):
    """Traceable: the named weights for seed words (lo, hi)."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                             hi)
    return {name: _one(jax.random.fold_in(key, i), shape, kind)
            for i, (name, shape, kind) in enumerate(spec(cfg))}


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, post):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda lo, hi: post(generate(cfg, lo, hi)))


def make(cfg: dict, seed: int, post=lambda w: w):
    """The weights of ``seed`` on the default device, passed through
    ``post`` (a traceable re-layout) inside the same jitted call."""
    return _jitted(json.dumps(cfg, sort_keys=True), post)(*seed_words(seed))
