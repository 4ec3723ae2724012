"""The program's StableLM: its ModelConfig and the layout of its parameters.

``program_config`` runs the program as the configuration file states, where
the program has the option (norm epsilon, rotary share, widths, depth, and
``scan_layers``, the program's choice between layers stacked in one scan and
layers unrolled with arrays of their own);
``to_program`` / ``from_program`` move the benchmark's named weights
(``chipbench/weights.py``) into and out of the tree that ``LM`` holds.
"""
from __future__ import annotations

import jax

from repro.configs.base import ModelConfig
from repro.models.model import LM

_LAYER = {"ln1.scale": ("ln1", "scale"), "ln1.bias": ("ln1", "bias"),
          "ln2.scale": ("ln2", "scale"), "ln2.bias": ("ln2", "bias"),
          "wq": ("mixer", "wq"), "wk": ("mixer", "wk"), "wv": ("mixer", "wv"),
          "wo": ("mixer", "wo"), "wi_gate": ("mlp", "wi_gate"),
          "wi_up": ("mlp", "wi_up"), "wo_mlp": ("mlp", "wo")}


def program_config(cfg: dict) -> ModelConfig:
    if cfg["model_type"] != "stablelm" or cfg["use_qkv_bias"] or \
            cfg["qk_layernorm"] or cfg["use_parallel_residual"]:
        raise ValueError(f"{cfg['name']}: the program has no such StableLM")
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=cfg["num_hidden_layers"],
        d_model=D, num_heads=H, num_kv_heads=cfg["num_key_value_heads"],
        head_dim=D // H, d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], layer_pattern=("attn",),
        rope_pct=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]), norm_kind="layernorm",
        norm_eps=cfg["layer_norm_eps"], mlp_kind="swiglu",
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"], param_dtype=cfg["param_dtype"],
        scan_layers=cfg.get("scan_layers", True))


def _core(lm: LM) -> bool:
    st = lm.decoder
    if st.head or (st.n_periods and st.tail):
        raise ValueError("unexpected layer segmentation")
    return bool(st.n_periods)


def to_program(w: dict, lm: LM):
    """Named weights -> the parameter tree of ``lm`` (traceable)."""
    def layer(sl):
        out = {"ln1": {}, "ln2": {}, "mixer": {}, "mlp": {}}
        for name, (grp, key) in _LAYER.items():
            out[grp][key] = sl(w[name])
        return out

    if _core(lm):
        dec = {"head": [], "core": [layer(lambda a: a)], "tail": []}
    else:
        dec = {"head": [], "core": [],
               "tail": [layer(lambda a, i=i: a[i])
                        for i in range(lm.cfg.num_layers)]}
    tree = {"embed": w["embed"], "head": w["head"],
            "final_norm": {"scale": w["final_norm.scale"],
                           "bias": w["final_norm.bias"]},
            "decoder": dec}
    want = jax.tree.map(lambda s: (s.shape, s.dtype), lm.abstract())
    got = jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    if got != want:
        raise ValueError("named weights do not fit the program's tree")
    return tree


def from_program(tree, lm: LM) -> dict:
    """The program's parameter tree (or a tree like it) -> named arrays."""
    import jax.numpy as jnp
    dec = tree["decoder"]
    if _core(lm):
        layers = {name: dec["core"][0][g][k] for name, (g, k) in _LAYER.items()}
    else:
        layers = {name: jnp.stack([lay[g][k] for lay in dec["tail"]])
                  for name, (g, k) in _LAYER.items()}
    return dict(layers, embed=tree["embed"], head=tree["head"],
                **{"final_norm.scale": tree["final_norm"]["scale"],
                   "final_norm.bias": tree["final_norm"]["bias"]})


def to_program_fn(lm: LM):
    """``to_program`` bound to ``lm``, for ``weights.make``."""
    import functools
    return functools.partial(to_program, lm=lm)
