"""Plain reference of the StableLM-2 decoder, in float32 at the highest
matmul precision, over the benchmark's named weights (``weights.py``).

Per layer: pre-LayerNorm (with bias), multi-head attention with rotary
embedding on the first ``partial_rotary_factor`` of each head (rotate-half
form), causal softmax, output projection, residual; pre-LayerNorm, SwiGLU
MLP (silu(x Wg) * x Wu) Wd, residual. A final LayerNorm and an untied head.
It follows the published StableLM-2 description except for what the
configuration file lists under ``departures`` (no q/k/v biases), and it
imports nothing of the program.

``mm`` is the one matrix product every contraction goes through, so a
control can run the same mathematics in a lower precision (``mm_fp8``).

The module also holds StableLM's shapes, which the shared modules find by
the configuration's ``arch`` key: ``weight_spec`` and ``STACKED`` for
``weights.py``; ``token_ops``, ``score_ops``, ``head_ops`` and
``attention_kernel`` for ``flops.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# -- shapes -------------------------------------------------------------------
def dims(cfg: dict) -> dict:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "D": D, "H": H,
            "Kh": cfg["num_key_value_heads"], "hd": D // H,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def weight_spec(cfg: dict):
    """(name, shape, kind) of every weight, in the order of their seeds;
    kind picks the initialiser in ``weights.py``: embed, matrix, scale or
    bias."""
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


# the weights stacked over the layers, whose leading axis is the layer
STACKED = ("ln1.scale", "ln1.bias", "ln2.scale", "ln2.bias", "wq", "wk", "wv",
           "wo", "wi_gate", "wi_up", "wo_mlp")


# -- operation counts (a multiply-add is two) ----------------------------------
def token_ops(cfg):
    """Matrix operations of one token through every layer (no attention
    scores, no head)."""
    d = dims(cfg)
    D, H, Kh, hd, F, L = (d[k] for k in ("D", "H", "Kh", "hd", "F", "L"))
    return 2 * (D * H * hd + 2 * D * Kh * hd + H * hd * D + 3 * D * F) * L


def score_ops(cfg, keys):
    """q.k and p.v of queries over ``keys`` attended keys in all, every
    layer."""
    d = dims(cfg)
    return 4 * d["H"] * d["hd"] * keys * d["L"]


def head_ops(cfg):
    """The head's operations for one position."""
    d = dims(cfg)
    return 2 * d["D"] * d["V"]


def attention_kernel(cfg, S):
    """One causal self-attention call of one layer over S tokens (batch 1):
    (operations, bytes of q, k, v read and o written once, in bf16)."""
    d = dims(cfg)
    ops = 4 * d["H"] * d["hd"] * S * (S + 1) / 2
    nbytes = 2 * S * d["hd"] * (2 * d["H"] + 2 * d["Kh"])
    return ops, nbytes


# -- mathematics --------------------------------------------------------------


def mm_f32(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def mm_fp8(spec, a, b):
    """Operands rounded to float8 (e4m3), products summed in float32."""
    f8 = jnp.float8_e4m3fn
    return mm_f32(spec, a.astype(f8), b.astype(f8))


MM = {"f32": mm_f32, "fp8": mm_fp8}


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def rotary(x, pos, pct, theta):
    """x: [S, H, hd]; rotate-half on the first ``pct`` of each head."""
    hd = x.shape[-1]
    rot = int(hd * pct) // 2 * 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * inv[None]       # [S, rot/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]   # [S, 1, rot]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    rotated = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([xr * cos + rotated * sin, xp], -1)


def in_blocks(f, block, *xs):
    """``f`` over the leading axis of ``xs`` ``block`` rows at a time,
    stacked [n / block, block, ...]; each block is recomputed in the backward
    pass, so that a gradient holds one block's intermediates at a time."""
    n = xs[0].shape[0]
    split = [x.reshape(n // block, block, *x.shape[1:]) for x in xs]
    return jax.lax.map(jax.checkpoint(lambda a: f(*a)), split)


def layer(cfg, mm, lw, x, block=0):
    """One decoder layer over one sequence. lw: this layer's weights. With
    ``block``, attention takes the queries ``block`` at a time (the same
    mathematics; no [H, S, S] array is held)."""
    S = x.shape[0]
    H, Kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    eps, pct, theta = (cfg["layer_norm_eps"], cfg["partial_rotary_factor"],
                       float(cfg["rope_theta"]))
    pos = jnp.arange(S)
    h = layer_norm(x, lw["ln1.scale"], lw["ln1.bias"], eps)
    q = mm("sd,de->se", h, lw["wq"]).reshape(S, H, hd)
    k = mm("sd,de->se", h, lw["wk"]).reshape(S, Kh, hd)
    v = mm("sd,de->se", h, lw["wv"]).reshape(S, Kh, hd)
    q, k = rotary(q, pos, pct, theta), rotary(k, pos, pct, theta)
    k = jnp.repeat(k, H // Kh, axis=1)
    v = jnp.repeat(v, H // Kh, axis=1)

    def attend(q, qpos):
        s = mm("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("hqk,khd->qhd", p, v)

    o = attend(q, pos) if not block else in_blocks(attend, block, q, pos)
    x = x + mm("se,ed->sd", o.reshape(S, H * hd), lw["wo"])
    h = layer_norm(x, lw["ln2.scale"], lw["ln2.bias"], eps)
    g = mm("sd,df->sf", h, lw["wi_gate"])
    u = mm("sd,df->sf", h, lw["wi_up"])
    return x + mm("sf,fd->sd", jax.nn.silu(g) * u, lw["wo_mlp"])


def _layers(w):
    return {k: w[k] for k in STACKED}


def hidden(cfg, mm, w, tokens, block=0):
    """tokens: [S] -> final hidden states [S, D], layers in a scan. With
    ``block``, attention in blocks of queries and each layer recomputed in
    the backward pass."""
    x = w["embed"][tokens].astype(jnp.float32)
    f = functools.partial(layer, cfg, mm, block=block)
    if block:
        f = jax.checkpoint(f)
    x, _ = jax.lax.scan(lambda x, lw: (f(lw, x), None), x, _layers(w))
    return layer_norm(x, w["final_norm.scale"], w["final_norm.bias"],
                      cfg["layer_norm_eps"])


def logits(cfg, mm, w, tokens, rows):
    """Logits [len(rows), V] at positions ``rows`` of the sequence."""
    return mm("sd,dv->sv", hidden(cfg, mm, w, tokens)[rows], w["head"])


# positions a block of the training reference takes at a time (a divisor of
# the sequence length): its attention and head, and their gradients, hold
# [H, BLOCK, S] and [BLOCK, V] at a time
BLOCK = 512


def row_loss_sum(cfg, mm, w, tokens):
    """Sum over positions of the next-token cross-entropy of one row, in
    blocks of positions (the last position predicts nothing)."""
    S = tokens.shape[0]
    block = math.gcd(S, BLOCK)
    h = hidden(cfg, mm, w, tokens, block)
    target = jnp.concatenate([tokens[1:], tokens[:1]])
    counts = (jnp.arange(S) < S - 1).astype(jnp.float32)

    def part(h, target, counts):
        lg = mm("sd,dv->sv", h, w["head"])
        lse = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, target[:, None], -1)[:, 0]
        return jnp.sum((lse - gold) * counts)

    return jnp.sum(in_blocks(part, block, h, target, counts))


@functools.lru_cache(maxsize=None)
def _jit_gaps(cfg_key, mm_name):
    cfg = dict(cfg_key)

    def gaps(w, tokens, rows, served):
        """Per row: the reference's best logit minus its logit of the
        served token; and of the token the ``mm_name`` path puts first."""
        ref = logits(cfg, mm_f32, w, tokens, rows)
        best = ref.max(-1)
        out = [best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]]
        if mm_name != "f32":
            low = logits(cfg, MM[mm_name], w, tokens, rows)
            pick = jnp.argmax(low, -1)
            out.append(best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0])
        return out

    return jax.jit(gaps)


def serve_gaps(cfg, w, tokens, rows, served, control=None):
    """tokens [S] (prompt and served tokens, padded at the end), rows [n]
    (the positions whose next token was served), served [n]. Returns the
    served tokens' gaps, and the control's where ``control`` names an
    ``MM`` entry."""
    key = tuple(sorted((k, v) for k, v in cfg.items()
                       if isinstance(v, (int, float, str, bool))))
    return _jit_gaps(key, control or "f32")(w, tokens, rows, served)


@functools.lru_cache(maxsize=None)
def _jit_grad(cfg_key, mm_name):
    cfg = dict(cfg_key)

    def add_row(w, t, total, grad):
        l, g = jax.value_and_grad(
            lambda w: row_loss_sum(cfg, MM[mm_name], w, t))(w)
        return total + l, jax.tree.map(jnp.add, grad, g)

    return jax.jit(add_row, donate_argnums=(2, 3))


@functools.partial(jax.jit, donate_argnums=0)
def _divide(tree, n):
    return jax.tree.map(lambda x: x / n, tree)


def loss_and_grad(cfg, w, tokens, mm_name="f32"):
    """Mean next-token loss over a batch [B, S] and its gradient, a row at a
    time into one gradient buffer, so that it fits beside the optimizer's
    state."""
    key = tuple(sorted((k, v) for k, v in cfg.items()
                       if isinstance(v, (int, float, str, bool))))
    f = _jit_grad(key, mm_name)
    B, S = tokens.shape
    total = jnp.zeros((), jnp.float32)
    grad = jax.tree.map(jnp.zeros_like, w)
    for b in range(B):
        total, grad = f(w, tokens[b], total, grad)
    n = B * (S - 1)
    return total / n, _divide(grad, n)


def schedule(opt, step):
    """The learning rate of step ``step`` (1-based): linear warm-up, then a
    cosine to zero at ``total_steps``."""
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    prog = jnp.clip((step - opt["warmup_steps"]) /
                    max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    return opt["lr"] * warm * (0.5 * (1 + jnp.cos(jnp.pi * prog)))


def adamw(opt, step, w, m, v, g):
    """One AdamW step with global-norm clipping; returns (w, m, v, clipped
    gradient)."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gn, 1e-12))
    g = {k: x * scale for k, x in g.items()}
    b1, b2, lr = opt["b1"], opt["b2"], schedule(opt, step)
    step = jnp.asarray(step, jnp.float32)
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
    m = {k: b1 * m[k] + (1 - b1) * g[k] for k in w}
    v = {k: b2 * v[k] + (1 - b2) * jnp.square(g[k]) for k in w}
    w = {k: w[k] - lr * ((m[k] / bc1) / (jnp.sqrt(v[k] / bc2) + opt["eps"])
                         + opt["weight_decay"] * w[k]) for k in w}
    return w, m, v, g
