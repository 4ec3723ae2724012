"""Seeded weights of a configuration, made on the device in one jitted call.

The weights are a flat dict of named arrays, each layer's matrices stacked
over the layers ([L, ...]). The benchmark hands them to the program (see
``arch/``) and makes them again for the plain reference after the window,
so the reference takes nothing that the program has made. This module
imports nothing of the program.

What the weights are is the architecture's: its reference module
(``reference/<arch>.py``, found by the configuration's ``arch`` key) gives
``weight_spec(cfg)``, the ``(name, shape, kind)`` of every weight in the
order of their seeds, and ``STACKED``, the names whose leading axis is the
layer. This module keeps the initialiser of each kind and the generator.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import load as load_reference

EMBED_STD = 0.02
NORM_STD = 0.1


def spec(cfg: dict):
    """(name, shape, kind) of every weight; kind picks the initialiser."""
    return load_reference(cfg["arch"]).weight_spec(cfg)


def stacked(cfg: dict):
    """The names of the weights stacked over the layers."""
    return load_reference(cfg["arch"]).STACKED


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
    if kind != "matrix":
        raise ValueError(f"no initialiser for weights of kind {kind!r}")
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
