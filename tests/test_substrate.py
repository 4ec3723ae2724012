"""Substrate tests: optimizer, data pipeline, checkpointing, serving,
elastic/FT policies, shadow interposition, fast-path overhead claim."""
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.optim import adamw
from repro.runtime.ft import FailureDetector, MigrationPolicy
from repro.runtime.trainer import FabricTrainer


def test_adamw_optimizes_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    state = adamw.init_state(params)
    cfg = adamw.OptConfig(lr=0.3, warmup_steps=0, total_steps=200,
                          weight_decay=0.0)
    for _ in range(150):
        grads = {"w": 2 * state["params"]["w"]}
        state, _ = adamw.apply_updates(cfg, state, grads)
    assert float(jnp.abs(state["params"]["w"]).max()) < 0.05


def test_grad_clipping():
    params = {"w": jnp.zeros(4)}
    state = adamw.init_state(params)
    cfg = adamw.OptConfig(clip_norm=1.0)
    _, m = adamw.apply_updates(cfg, state, {"w": jnp.full(4, 100.0)})
    assert float(m["grad_norm"]) == pytest.approx(200.0, rel=1e-3)


def test_grad_compression_roundtrip_is_unbiasedish():
    cfg = adamw.OptConfig(compress_grads=True)
    g = jax.random.normal(jax.random.PRNGKey(0), (4096,))
    outs = []
    for s in range(8):
        q = adamw._compress(g, jax.random.PRNGKey(s))
        outs.append(np.asarray(q))
    err = np.abs(np.mean(outs, 0) - np.asarray(g)).max()
    scale = float(jnp.abs(g).max()) / 127
    assert err < 2.5 * scale / np.sqrt(8)   # averages toward the truth


def test_pipeline_determinism_and_restore():
    cfg = DataConfig(1000, 32, 4, seed=9)
    p1 = TokenPipeline(cfg)
    seq = [p1.next()["tokens"] for _ in range(5)]
    p2 = TokenPipeline(cfg)
    p2.load_state_dict({"step": 3, "seed": 9})
    np.testing.assert_array_equal(p2.next()["tokens"], seq[3])
    np.testing.assert_array_equal(p2.next()["tokens"], seq[4])


def test_checkpoint_roundtrip_and_latest():
    tree = {"a": jnp.arange(12).reshape(3, 4).astype(jnp.float32),
            "b": {"c": jnp.ones((5,), jnp.int32)}}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, tree, step=3, extra={"x": 1})
        ckpt.save(d, tree, step=7, extra={"x": 2})
        latest = ckpt.latest(d)
        assert latest.endswith("step_00000007")
        out = ckpt.restore(latest, tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert ckpt.manifest_extra(latest)["x"] == 2


def test_checkpoint_async_writer():
    tree = {"w": jnp.ones((256, 256))}
    with tempfile.TemporaryDirectory() as d:
        _, t = ckpt.save(d, tree, step=1, async_write=True)
        t.join(10)
        out = ckpt.restore(ckpt.latest(d), tree)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.ones((256, 256)))


def test_serving_engine_decodes_and_migrates():
    """An engine moved mid-decode to a new engine on the same ``LM`` (and so
    the same programs) gives every request the tokens of an unmoved run."""
    from repro.configs.base import get_smoke_config
    from repro.models.model import LM
    from repro.serving.engine import Request, ServingEngine
    cfg = get_smoke_config("deepseek-7b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(2)]

    def requests():
        return [Request(i, p, max_new=4) for i, p in enumerate(prompts)]

    still = ServingEngine(lm, params, slots=2, capacity=64)
    want = requests()
    for r in want:
        assert still.submit(r)
    still.run_until_done()

    eng = ServingEngine(lm, params, slots=2, capacity=64)
    reqs = requests()
    for r in reqs:
        assert eng.submit(r)
    eng.step()
    # migrate the engine state mid-decode
    blob = eng.state_dict()
    eng2 = ServingEngine(lm, params, slots=2, capacity=64)
    eng2.load_state_dict(blob)
    eng2.active = eng.active
    eng2.run_until_done()
    assert all(len(r.out) == 4 for r in reqs)
    assert [r.out for r in reqs] == [r.out for r in want]


@pytest.mark.parametrize("engines", [((2, 48),), ((2, 48), (3, 64))],
                         ids=["one_engine", "two_capacities_one_lm"])
def test_serving_engine_matches_unbatched_decode(engines):
    """Continuous batching gives every request the tokens of its own greedy
    decode, with slots recycled, also where the scanned core's period count
    equals the slot count (core cache leaves are [n_periods, slots, ...]).
    Engines of different slots and capacity on one ``LM`` share its programs
    object, and each still gets the tokens of its own capacity."""
    from repro.configs.base import get_smoke_config
    from repro.models.model import LM
    from repro.serving.engine import Request, ServingEngine
    cfg = get_smoke_config("gemma3-1b").replace(num_layers=12)
    lm = LM(cfg)
    assert lm.decoder.n_periods == 2
    params = lm.init(jax.random.PRNGKey(1))
    for slots, capacity in engines:
        rng = np.random.RandomState(1)
        reqs = [Request(i, rng.randint(0, cfg.vocab_size, 6 + 5 * i).astype(
            np.int32), max_new=5) for i in range(3)]
        eng = ServingEngine(lm, params, slots=slots, capacity=capacity)
        pending = list(reqs)
        while pending or any(eng.active):
            while pending and eng.submit(pending[0]):
                pending.pop(0)
            eng.step()
        for r in reqs:
            cache, logits = lm.prefill(params, {"tokens": r.prompt[None]},
                                       capacity)
            want = [int(jnp.argmax(logits[0]))]
            while len(want) < r.max_new:
                cache, logits = lm.decode_step(
                    params, cache, jnp.asarray([[want[-1]]], jnp.int32))
                want.append(int(jnp.argmax(logits[0])))
            assert r.out == want, (capacity, r.rid)


def test_failure_detector_and_straggler_policy():
    det = FailureDetector(timeout_s=1.0)
    det.heartbeat(0, step_time=1.0, now=0.0)
    det.heartbeat(1, step_time=1.0, now=0.0)
    assert det.failed(now=0.5) == []
    assert det.failed(now=2.0) == [0, 1]

    det2 = FailureDetector()
    pol = MigrationPolicy(det2, factor=1.5, patience=2)
    flagged = set()
    for s in range(3):
        for r in range(4):
            det2.heartbeat(r, step_time=3.0 if r == 2 else 1.0,
                           now=float(s))
        flagged.update(pol.stragglers())
    assert flagged == {2}


def test_elastic_remesh_roundtrip():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from repro.launch.mesh import make_mesh
    from repro.runtime.elastic import remesh_state
    m4 = make_mesh((4,), ("data",))
    m2 = make_mesh((2,), ("data",))
    state = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    logical = {"w": ("embed", None)}
    s4 = remesh_state(state, logical, None, m4)
    s2 = remesh_state(s4, logical, m4, m2)
    np.testing.assert_array_equal(np.asarray(s2["w"]),
                                  np.asarray(state["w"]))


def test_checkpoint_restart_manager():
    from repro.runtime.ft import CheckpointRestartManager
    saved = {}

    def save_fn(step):
        saved[step] = f"ck{step}"
        return f"ck{step}"

    def restore_fn(cid, world):
        return (cid, world)

    mgr = CheckpointRestartManager(save_fn, restore_fn, interval_steps=5)
    for s in range(12):
        mgr.maybe_checkpoint(s)
    assert mgr.last_ckpt == "ck10"
    assert mgr.restart(6) == ("ck10", 6)
    assert mgr.restarts == 1


def test_shadow_interposition_does_extra_copies():
    """Fig. 8 mechanism: every send is bounced through a shadow MR and
    every recv completion is copied back (DMTCP architecture)."""
    from repro.core.shadow import ShadowVerbs, _ShadowMR
    from repro.runtime.cluster import SimCluster
    from repro.runtime.collectives import Channel, connect_pair
    from repro.core.verbs import SGE, SendWR
    from repro.core.packets import Op

    cl = SimCluster(2)
    ca, cb = cl.launch("a", 0), cl.launch("b", 1)
    c1, c2 = Channel(ca.ctx, 8192), Channel(cb.ctx, 8192)
    connect_pair(c1, c2)
    sh = ShadowVerbs(ca.ctx)
    pd = ca.ctx.pds[0]
    user = c1.h.mr(c1.mrn_send)
    sh._mrs[user.mrn] = _ShadowMR(user, pd.reg_mr(user.size))
    qp1 = c1.h.qp(c1.qpn)
    c2.post_recv(64)
    user.write(0, b"A" * 64)
    sh.post_send(qp1, SendWR(1, Op.SEND, SGE(user, 0, 64)))
    shadow_mr = sh._mrs[user.mrn].shadow
    assert shadow_mr.read(0, 64) == b"A" * 64     # bounce copy happened
    cl.run_until_idle()
    sh.poll(c1.h.cq(c1.cqn), 8)
    assert c2.recv_bytes(0, 64) == b"A" * 64      # delivery correct
    assert sh._qp_log[qp1.qpn]                    # bookkeeping maintained
