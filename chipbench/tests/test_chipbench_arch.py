"""What ``weights.py`` and ``flops.py`` take from an architecture's reference
module, found by the configuration's ``arch`` key: StableLM reads what it read
when these counts and weights lived in the shared modules, and a second
architecture plugs in by a module of its own alone."""
import hashlib
import json
import os
import re
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common, flops, weights

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "configs", "stablelm-1.6b.json")) as f:
    FULL = json.load(f)
SMALL = {"arch": "stablelm", "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "num_hidden_layers": 3,
         "intermediate_size": 160, "vocab_size": 512}
NAMES = ["embed", "head", "final_norm.scale", "final_norm.bias", "ln1.scale",
         "ln1.bias", "ln2.scale", "ln2.bias", "wq", "wk", "wv", "wo",
         "wi_gate", "wi_up", "wo_mlp"]
PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def digest(cfg, w):
    h = hashlib.sha256()
    for name, _, _ in weights.spec(cfg):
        h.update(name.encode())
        h.update(np.asarray(w[name]).tobytes())
    return h.hexdigest()


def test_stablelm_weights_are_bit_for_bit_as_before():
    # recorded from the generator as it was when it named StableLM's
    # matrices itself: names, order, fold_in indices and initialisers
    w = weights.make(SMALL, 2**40 + 17)
    assert [n for n, _, _ in weights.spec(SMALL)] == NAMES
    assert digest(SMALL, w) == \
        "939788018642045dcfcc56b91e01efd1b6fd63d35b3d298af6875a4e61b88bb0"


# prefill(1792), decode(1000), train_step(4, 1024), attention_kernel(1536),
# attention_roofline_s(512) as the shared modules counted them before
COUNTS = [
    (SMALL, 1740341248.0, 1116160, 9114746880.0, (302186496.0, 786432),
     3.2007814407814407e-07),
    (FULL, 4735788646400.0, 3073900544, 36594329321472.0,
     (9669967872.0, 25165824), 1.024250061050061e-05),
    (dict(FULL, num_hidden_layers=4), 789640642560.0, 854851584,
     10308122836992.0, (9669967872.0, 25165824), 1.024250061050061e-05),
]


@pytest.mark.parametrize("cfg,pre,dec,train,kernel,roof", COUNTS,
                         ids=["small", "stablelm-1.6b", "stablelm-1.6b-l4"])
def test_stablelm_counts_are_as_before(cfg, pre, dec, train, kernel, roof):
    assert flops.prefill(cfg, 1792) == pre
    assert flops.decode(cfg, 1000) == dec
    assert flops.train_step(cfg, 4, 1024) == train
    assert flops.attention_kernel(cfg, 1536) == kernel
    assert flops.attention_roofline_s(cfg, 512, PEAK) == roof


def toy_module():
    """A made-up architecture: an embedding, per layer a gain and one matrix,
    and a head; its attention is counted as one dot product a key."""
    m = types.ModuleType("chipbench.reference.toy_arch")

    def dims(cfg):
        return cfg["layers"], cfg["width"], cfg["vocab_size"]

    def weight_spec(cfg):
        L, D, V = dims(cfg)
        return [("tok", (V, D), "embed"), ("gain", (L, D), "scale"),
                ("mix", (L, D, 2 * D), "matrix"), ("out", (D, V), "matrix")]

    m.weight_spec = weight_spec
    m.STACKED = ("gain", "mix")
    m.token_ops = lambda cfg: 4 * dims(cfg)[1] ** 2 * dims(cfg)[0]
    m.score_ops = lambda cfg, keys: 2 * dims(cfg)[1] * keys * dims(cfg)[0]
    m.head_ops = lambda cfg: 2 * dims(cfg)[1] * dims(cfg)[2]
    m.attention_kernel = lambda cfg, S: (2 * dims(cfg)[1] * S * (S + 1) / 2,
                                         4 * S * dims(cfg)[1])
    return m


TOY = {"arch": "toy_arch", "layers": 2, "width": 8, "vocab_size": 32}


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.setitem(sys.modules, "chipbench.reference.toy_arch",
                        toy_module())


def test_a_new_architecture_needs_only_its_module(toy):
    w = weights.make(TOY, 5)
    assert {k: v.shape for k, v in w.items()} == {
        "tok": (32, 8), "gain": (2, 8), "mix": (2, 8, 16), "out": (8, 32)}
    again = weights.make(TOY, 5)
    assert all(np.array_equal(w[k], again[k]) for k in w)
    assert not np.array_equal(w["mix"], weights.make(TOY, 6)["mix"])
    # initialisers by kind: scales about 1, matrices scaled by fan-in
    assert abs(float(jnp.mean(w["gain"])) - 1.0) < 0.2
    assert float(jnp.std(w["mix"])) == pytest.approx(8 ** -0.5, rel=0.3)
    norms = common.leaf_norms(w, weights.stacked(TOY))
    assert set(norms) == {"tok", "out", "gain[0]", "gain[1]", "mix[0]",
                          "mix[1]"}

    D, L, V = 8, 2, 32
    assert flops.decode(TOY, 10) == 4 * D * D * L + 2 * D * 10 * L + 2 * D * V
    assert flops.prefill(TOY, 6) == 6 * 4 * D * D * L + 2 * D * 21 * L + \
        2 * D * V
    assert flops.train_step(TOY, 3, 6) == 3 * 3 * (
        6 * 4 * D * D * L + 2 * D * 21 * L + 6 * 2 * D * V)
    assert flops.attention_kernel(TOY, 6) == (2 * D * 21, 4 * 6 * D)
    assert flops.attention_roofline_s(TOY, 6, PEAK) == max(
        2 * D * 21 / PEAK["flops_per_s"], 4 * 6 * D / PEAK["bytes_per_s"])


def test_an_unknown_kind_of_weight_is_an_error(toy, monkeypatch):
    mod = sys.modules["chipbench.reference.toy_arch"]
    monkeypatch.setattr(mod, "weight_spec",
                        lambda cfg: [("x", (2, 2), "lowrank")])
    with pytest.raises(ValueError, match="lowrank"):
        weights.make(dict(TOY, width=4), 1)


SHARED = ["weights.py", "flops.py"] + [
    os.path.join("drivers", f) for f in sorted(os.listdir(
        os.path.join(HERE, "drivers"))) if f.endswith(".py")]


@pytest.mark.parametrize("path", SHARED)
def test_shared_code_names_no_stablelm_weight_or_key(path):
    with open(os.path.join(HERE, path)) as f:
        src = f.read()
    # "embed" is also the generic name of an initialiser's kind
    for name in set(NAMES) - {"embed"}:
        assert not re.search(rf"""["']{re.escape(name)}["']""", src), name
    for key in ("intermediate_size", "num_key_value_heads"):
        assert key not in src, key
