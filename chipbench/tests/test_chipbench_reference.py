"""The plain StableLM-2 reference against the program's LM at a small size
on the CPU, in float32: full forward, prefill then decode through the cache,
and the training loss with its gradient."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common, weights
from chipbench.arch import stablelm as arch
from chipbench.reference import stablelm as ref
from repro.models.model import LM
from repro.optim import adamw

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "configs", "stablelm-1.6b.json")) as f:
    FULL = json.load(f)
SMALL = dict(FULL, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=4, num_hidden_layers=3, intermediate_size=160,
             vocab_size=512, torch_dtype="float32")
TOL = dict(atol=2e-4, rtol=2e-4)   # float32, the same math in another order


@pytest.fixture(scope="module")
def setup():
    lm = LM(arch.program_config(SMALL))
    w = weights.make(SMALL, 2**35 + 1)
    params = jax.jit(lambda w: arch.to_program(w, lm))(w)
    tokens = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
    return lm, w, params, tokens


def test_program_config_runs_the_file():
    c = arch.program_config(FULL)
    assert (c.num_layers, c.d_model, c.num_heads, c.head_dim, c.d_ff,
            c.vocab_size) == (24, 2048, 32, 64, 5632, 100352)
    assert c.norm_eps == 1e-5 and c.rope_pct == 0.25
    assert c.norm_kind == "layernorm" and not c.tie_embeddings
    with pytest.raises(ValueError):
        arch.program_config(dict(FULL, use_qkv_bias=True))


def test_named_weights_round_trip(setup):
    lm, w, params, _ = setup
    back = arch.from_program(params, lm)
    assert set(back) == set(w)
    assert all(np.array_equal(back[k], w[k]) for k in w)


def test_forward_matches(setup):
    lm, w, params, tokens = setup
    got, _, _ = lm.forward(params, {"tokens": jnp.asarray(tokens)[None]},
                           impl="blocked")
    want = ref.logits(SMALL, ref.mm_f32, w, jnp.asarray(tokens),
                      jnp.arange(len(tokens)))
    np.testing.assert_allclose(got[0], want, **TOL)


def test_prefill_and_decode_match(setup):
    lm, w, params, tokens = setup
    P = 24
    cache, lg = lm.prefill(params, {"tokens": jnp.asarray(tokens[:P])[None]},
                           64, impl="blocked")
    got = [lg[0]]
    for t in tokens[P:-1]:
        cache, lg = lm.decode_step(params, cache, jnp.asarray([[t]]))
        got.append(lg[0])
    want = ref.logits(SMALL, ref.mm_f32, w, jnp.asarray(tokens),
                      jnp.arange(P - 1, len(tokens) - 1))
    np.testing.assert_allclose(jnp.stack(got), want, **TOL)


def test_served_gaps_of_the_reference_own_tokens_are_zero(setup):
    from chipbench.drivers.open_loop import served_gaps
    _, w, _, tokens = setup
    P = 20
    lg = ref.logits(SMALL, ref.mm_f32, w, jnp.asarray(tokens),
                    jnp.arange(P - 1, len(tokens)))
    # greedy continuation by the reference itself reads a gap of zero
    out = [int(jnp.argmax(lg[0]))]
    seq = list(tokens[:P]) + out
    for _ in range(5):
        lg = ref.logits(SMALL, ref.mm_f32, w, jnp.asarray(seq),
                        jnp.asarray([len(seq) - 1]))
        out.append(int(jnp.argmax(lg[0])))
        seq.append(out[-1])
    gap, ctrl = served_gaps(ref, SMALL, w, tokens[:P], out, control="fp8")
    assert gap == pytest.approx(0.0, abs=1e-5)
    assert ctrl >= 0.0
    wrong = [(t + 1) % 512 for t in out]
    assert served_gaps(ref, SMALL, w, tokens[:P], wrong)[0] > 0.01


def test_loss_and_gradient_match(setup):
    lm, w, params, _ = setup
    batch = np.random.default_rng(1).integers(0, 512, (3, 16)).astype(np.int32)
    (loss, _), grads = jax.value_and_grad(
        lambda p: lm.loss(p, {"tokens": jnp.asarray(batch)}, impl="flash"),
        has_aux=True)(params)
    rloss, rgrad = ref.loss_and_grad(SMALL, w, jnp.asarray(batch))
    np.testing.assert_allclose(loss, rloss, **TOL)
    got = arch.from_program(grads, lm)
    for k in rgrad:
        np.testing.assert_allclose(got[k], rgrad[k], atol=1e-5, rtol=1e-3)


def test_adamw_matches_the_program(setup):
    lm, w, params, _ = setup
    opt = dict(lr=1e-3, warmup_steps=2, total_steps=10, b1=0.9, b2=0.95,
               eps=1e-8, weight_decay=0.1, clip_norm=1.0)
    g = jax.tree.map(lambda a: jnp.full_like(a, 0.01), w)
    m = v = jax.tree.map(jnp.zeros_like, w)
    state = adamw.init_state(params)
    pg = arch.to_program(g, lm)
    for step in (1, 2, 3):
        state, _ = adamw.apply_updates(adamw.OptConfig(**opt), state, pg)
        w, m, v, _ = ref.adamw(opt, step, w, m, v, g)
    got = arch.from_program(state["params"], lm)
    for k in w:
        np.testing.assert_allclose(got[k], w[k], atol=1e-6, rtol=1e-5)


def test_checksum_sees_one_bit():
    a = {"x": jnp.arange(1000, dtype=jnp.float32),
         "y": jnp.ones((4, 8), jnp.bfloat16), "n": jnp.arange(3)}
    s = common.checksum(a)
    b = dict(a, y=a["y"].at[2, 3].set(jnp.bfloat16(1.0078125)))
    assert np.array_equal(common.checksum(a), s)
    assert np.sum(np.asarray(common.checksum(b)) != np.asarray(s)) == 1
    swapped = dict(a, x=a["x"][::-1])
    assert not np.array_equal(common.checksum(swapped), s)
