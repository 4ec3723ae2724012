"""Operations and bytes the algorithm needs, from shapes alone. The
benchmark's yardstick for ``mfu.*`` and for ``flash_attention_roofline``:
recomputation is not counted, masked-out blocks of causal attention are not
counted, and a multiply-add is two operations."""
from __future__ import annotations

import json
import os

from chipbench.weights import dims

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip of ``device_kind``."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def _dense(d):
    """Matrix operations of one token through every layer (no attention
    scores, no head)."""
    D, H, Kh, hd, F, L = (d[k] for k in ("D", "H", "Kh", "hd", "F", "L"))
    return 2 * (D * H * hd + 2 * D * Kh * hd + H * hd * D + 3 * D * F) * L


def _scores(d, keys):
    """q.k and p.v of one query over ``keys`` keys, every layer."""
    return 4 * d["H"] * d["hd"] * keys * d["L"]


def _head(d):
    return 2 * d["D"] * d["V"]


def prefill(cfg, S):
    """One prompt of S tokens; logits of the last position only."""
    d = dims(cfg)
    return S * _dense(d) + _scores(d, S * (S + 1) / 2) + _head(d)


def decode(cfg, keys):
    """One decoded token that attends to ``keys`` positions."""
    d = dims(cfg)
    return _dense(d) + _scores(d, keys) + _head(d)


def train_step(cfg, batch, S):
    """Forward and backward (twice the forward) of a batch of S-token rows
    with logits at every position."""
    d = dims(cfg)
    fwd = S * _dense(d) + _scores(d, S * (S + 1) / 2) + S * _head(d)
    return 3 * batch * fwd


def attention_kernel(cfg, S):
    """One causal self-attention call of one layer over S tokens (batch 1):
    (operations, bytes of q, k, v read and o written once, in bf16)."""
    d = dims(cfg)
    ops = 4 * d["H"] * d["hd"] * S * (S + 1) / 2
    nbytes = 2 * S * d["hd"] * (2 * d["H"] + 2 * d["Kh"])
    return ops, nbytes


def attention_roofline_s(cfg, S, peak):
    """The least time the chip could take for that call: the larger of its
    compute and its memory term."""
    ops, nbytes = attention_kernel(cfg, S)
    return max(ops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
