"""Operations and bytes the algorithm needs, from shapes alone. The
benchmark's yardstick for ``mfu.*`` and for ``flash_attention_roofline``:
recomputation is not counted, masked-out blocks of causal attention are not
counted, and a multiply-add is two operations.

The counts of one architecture are its reference module's
(``reference/<arch>.py``, found by the configuration's ``arch`` key):
``token_ops(cfg)``, the matrix operations of one token through every layer;
``score_ops(cfg, keys)``, those of attention scores and their values over
``keys`` attended keys in all, every layer; ``head_ops(cfg)``, the head's
for one position; and ``attention_kernel(cfg, S)``, the (operations, bytes)
of one layer's causal attention call over S tokens. This module sums them
into a prefill, a decoded token and a train step.
"""
from __future__ import annotations

import json
import os

from chipbench.reference import load as load_reference

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip of ``device_kind``."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def _arch(cfg):
    return load_reference(cfg["arch"])


def prefill(cfg, S):
    """One prompt of S tokens; logits of the last position only."""
    a = _arch(cfg)
    return S * a.token_ops(cfg) + a.score_ops(cfg, S * (S + 1) / 2) + \
        a.head_ops(cfg)


def decode(cfg, keys):
    """One decoded token that attends to ``keys`` positions."""
    a = _arch(cfg)
    return a.token_ops(cfg) + a.score_ops(cfg, keys) + a.head_ops(cfg)


def train_step(cfg, batch, S):
    """Forward and backward (twice the forward) of a batch of S-token rows
    with logits at every position."""
    a = _arch(cfg)
    fwd = S * a.token_ops(cfg) + a.score_ops(cfg, S * (S + 1) / 2) + \
        S * a.head_ops(cfg)
    return 3 * batch * fwd


def attention_kernel(cfg, S):
    """One causal self-attention call of one layer over S tokens (batch 1):
    (operations, bytes)."""
    return _arch(cfg).attention_kernel(cfg, S)


def attention_roofline_s(cfg, S, peak):
    """The least time the chip could take for that call: the larger of its
    compute and its memory term."""
    ops, nbytes = attention_kernel(cfg, S)
    return max(ops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
