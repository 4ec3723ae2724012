"""Drive the JAX serving and training paths once on one TPU, and check them.

    python3 chip_smoke.py               # one chip: every phase below
    python3 chip_smoke.py --four-chips  # elastic re-mesh 4 -> 2 chips only

Phases on one chip, in order; the first failure exits non-zero:

  serving   ServingEngine on gemma3-1b at published widths: 4 slots,
            capacity 4096, 6 seeded requests of 512-1024 prompt tokens,
            32 new tokens each. The run is repeated with the engine moved
            at decode step 8: its KV state is saved to host bytes with
            ``repro.checkpoint.ckpt``, the device copy is deleted, and a new
            engine restores it. Every request's tokens must equal the
            unmoved run's exactly.
  kernel    the Pallas flash-attention kernel against
            ``ops.attention(impl="blocked")`` at gemma3-1b prefill shapes,
            global and window 512, within a bf16 tolerance (ATTN_ATOL,
            ATTN_RTOL).
  scans     the Pallas ``rglru_scan`` (recurrentgemma-9b RG-LRU width) and
            ``ssd_scan`` (mamba2-2.7b SSD widths), 2048 steps in f32,
            against the blocked XLA path within SCAN_ATOL, SCAN_RTOL.
  train     10 steps of ``adamw.make_train_step`` on lm-100m (batch 8,
            seq 256): every loss finite, and the state survives a ckpt save
            and restore bitwise (one more step from either gives the same
            loss).
  fabric    the host-side FabricTrainer live migration with pre-copy: the
            loss trajectory is bitwise the unmigrated one.

``--four-chips`` runs only the elastic re-mesh: lm-100m trained on a 4-chip
data mesh, re-sharded onto 2 chips with ``remesh_state`` and trained on.
The state gathered to the host before and after the re-shard must be
bitwise equal, and the losses must agree with the same steps on one chip
within REMESH_RTOL.

Every line but the last is one JSON object of information: the compile
cache in use, then per phase its wall and compile seconds labelled with the
device kind, then the totals. None of it is a benchmark number. The last
line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
JAX's persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` where
that is set, and ``<repo>/.jax_cache`` otherwise.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.configs import gemma3_1b, lm_100m, mamba2_2_7b, recurrentgemma_9b
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru import rglru_scan
from repro.kernels.ssd import ssd_scan
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.model import LM
from repro.obs import host
from repro.optim import adamw
from repro.runtime.elastic import remesh_state
from repro.runtime.trainer import FabricTrainer
from repro.serving.engine import Request, ServingEngine
from repro.sharding import partition as part

# bf16 outputs of two f32-accumulating paths, elementwise:
# |pallas - blocked| <= ATTN_ATOL + ATTN_RTOL * |blocked|. The floor holds a
# few bf16 ulps at |out| < 2; the relative term is one ulp (at most 2^-7 of
# the value) for the rarer larger outputs
ATTN_ATOL = 2e-2
ATTN_RTOL = 2.0 ** -7
# the f32 recurrences: the same math in another order, so a few f32 ulps
SCAN_ATOL = 1e-5
SCAN_RTOL = 1e-5
# fp32 losses of the same steps, data-parallel over 4 and 2 chips vs one
REMESH_RTOL = 2e-3


def compile_seconds(t0, t1):
    """JAX's backend-compile durations inside [t0, t1], from the program's
    load counter (a persistent-cache hit counts its retrieval time); None
    where the counter's ring lost events of that stretch."""
    got = host.loads(t0, t1)
    if got is None:
        return None
    return sum(e.end - e.start for e in got if e.event == host.BACKEND_COMPILE)


class SmokeFailure(AssertionError):
    pass


def check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def make_requests(cfg, n, lens, max_new, seed):
    rng = np.random.RandomState(seed)
    return [Request(i, rng.randint(0, cfg.vocab_size, int(L)).astype(
        np.int32), max_new=max_new) for i, L in enumerate(rng.choice(lens, n))]


def move_engine(eng, lm, params):
    """Device -> host bytes -> device: save the engine state with ckpt,
    delete the device copy, and restore into a new engine."""
    with tempfile.TemporaryDirectory() as d:
        path = ckpt.save(d, eng.cache, step=eng.steps)
        jax.tree.map(lambda a: a.delete(), eng.cache)
        dst = ServingEngine(lm, params, slots=eng.slots,
                            capacity=eng.capacity)
        dst.load_state_dict({"cache": jax.device_put(ckpt.restore(
            path, dst.cache)), "steps": eng.steps})
    dst.active = eng.active
    return dst


def serve(lm, params, reqs, *, slots, capacity, move_at=None):
    eng = ServingEngine(lm, params, slots=slots, capacity=capacity)
    pending = list(reqs)
    while pending or any(eng.active):
        while pending and eng.submit(pending[0]):
            pending.pop(0)
        eng.step()
        if eng.steps == move_at:
            eng = move_engine(eng, lm, params)
    check(eng.steps > (move_at or 0), "engine finished before the move")
    return [list(r.out) for r in reqs]


def phase_serving(cfg, *, slots=4, capacity=4096, n_requests=6,
                  prompt_lens=(512, 768, 1024), max_new=32, move_at=8,
                  seed=0):
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(seed))

    def run(move):
        reqs = make_requests(cfg, n_requests, prompt_lens, max_new, seed)
        t = time.perf_counter()
        outs = serve(lm, params, reqs, slots=slots, capacity=capacity,
                     move_at=move)
        return outs, time.perf_counter() - t

    ref, ref_s = run(None)
    moved, moved_s = run(move_at)
    check(all(len(o) == max_new for o in ref), "a request came back short")
    check(all(0 <= t < cfg.vocab_size for o in ref for t in o),
          "a token outside the vocabulary")
    same = [a == b for a, b in zip(ref, moved)]
    check(all(same), f"moved engine diverged on requests "
          f"{[i for i, s in enumerate(same) if not s]}")
    return {"requests": n_requests, "tokens": sum(map(len, ref)),
            "unmoved_wall_s": ref_s, "moved_wall_s": moved_s,
            "moved_at_step": move_at, "tokens_equal": True}


# ---------------------------------------------------------------------------
# kernel vs XLA
# ---------------------------------------------------------------------------


def compare(got, want, atol, rtol, what):
    """Elementwise |got - want| <= atol + rtol * |want|, or a failure."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    diff = jnp.abs(got - want)
    worst = float(jnp.max(diff / (atol + rtol * jnp.abs(want))))
    check(math.isfinite(worst) and worst <= 1.0,
          f"{what}: error {worst} times the bound")
    return {"max_abs_err": float(jnp.max(diff)), "worst_over_bound": worst}


def phase_kernel(cfg, *, seq=1024, seed=0, interpret=False):
    H, Kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (1, seq, H, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (1, seq, Kh, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (1, seq, Kh, hd), jnp.bfloat16)
    out = {}
    for window in (0, cfg.local_window):
        got = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, interpret=interpret))(q, k, v)
        want = jax.jit(lambda q, k, v: ops.attention(
            q, k, v, causal=True, window=window, impl="blocked"))(q, k, v)
        out[f"window{window}"] = compare(
            got, want, ATTN_ATOL, ATTN_RTOL,
            f"pallas vs blocked attention (window={window})")
    return dict(out, atol=ATTN_ATOL, rtol=ATTN_RTOL, shape=[1, seq, H, hd])


def phase_scans(rg_cfg, ssm_cfg, *, seq=2048, seed=0, interpret=False):
    """The Pallas recurrences that prefill takes on TPU, in f32, against the
    blocked XLA path: ``rglru_scan`` at the RG-LRU width of ``rg_cfg`` and
    ``ssd_scan`` at the SSD widths of ``ssm_cfg``."""
    R = rg_cfg.rnn_width
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x, ga, gx = (jax.random.normal(ks[i], (2, seq, R)) for i in (0, 2, 3))
    al = jax.random.normal(ks[1], (R,))
    h0 = jax.random.normal(ks[4], (2, R))
    got = jax.jit(lambda *a: rglru_scan(*a, h0=h0, interpret=interpret))(
        x, al, ga, gx)
    want = jax.jit(lambda *a: ops.rglru(*a, h0=h0, impl="blocked"))(
        x, al, ga, gx)
    out = {f"rglru_{n}": compare(g, w, SCAN_ATOL, SCAN_RTOL,
                                 f"rglru_scan vs blocked ({n})")
           for n, g, w in zip(("y", "h"), got, want)}

    s = ssm_cfg.ssm
    H = s.expand * ssm_cfg.d_model // s.head_dim
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 6)
    xs = jax.random.normal(ks[0], (1, seq, H, s.head_dim))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, seq, H)))
    A = jax.random.normal(ks[2], (H,)) * 0.5
    Bm, Cm = (jax.random.normal(ks[i], (1, seq, s.ngroups, s.d_state)) * 0.3
              for i in (3, 4))
    D = jax.random.normal(ks[5], (H,))
    got = jax.jit(lambda *a: ssd_scan(*a, D=D, chunk=s.chunk_size,
                                      interpret=interpret))(xs, dt, A, Bm, Cm)
    want = jax.jit(lambda *a: ops.ssd(*a, D=D, chunk=s.chunk_size,
                                      impl="blocked"))(xs, dt, A, Bm, Cm)
    out.update({f"ssd_{n}": compare(g, w, SCAN_ATOL, SCAN_RTOL,
                                    f"ssd_scan vs blocked ({n})")
                for n, g, w in zip(("y", "h"), got, want)})
    return dict(out, atol=SCAN_ATOL, rtol=SCAN_RTOL, seq=seq, rnn_width=R,
                ssd_heads=H)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def _batches(cfg, seq, batch, n):
    pipe = TokenPipeline(DataConfig(cfg.vocab_size, seq, batch))
    return [{"tokens": jnp.asarray(pipe.next()["tokens"])} for _ in range(n)]


def _opt(steps):
    return adamw.OptConfig(lr=1e-3, warmup_steps=20, total_steps=steps)


def phase_train(cfg, *, batch=8, seq=256, steps=10, seed=0):
    lm = LM(cfg)
    state = adamw.init_state(lm.init(jax.random.PRNGKey(seed)))
    step_fn = jax.jit(adamw.make_train_step(lm, _opt(steps)))
    data = _batches(cfg, seq, batch, steps + 1)
    losses, times = [], []
    for b in data[:steps]:
        t = time.perf_counter()
        state, m = step_fn(state, b)
        losses.append(float(m["loss"]))
        times.append(time.perf_counter() - t)
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")

    host = jax.device_get(state)
    with tempfile.TemporaryDirectory() as d:
        back = ckpt.restore(ckpt.save(d, state, step=steps), state)
    leaves, back_leaves = jax.tree.leaves(host), jax.tree.leaves(back)
    check(all(a.dtype == b.dtype and np.array_equal(a, b)
              for a, b in zip(leaves, back_leaves)),
          "train state changed through ckpt save/restore")
    _, m_orig = step_fn(state, data[steps])
    _, m_back = step_fn(jax.device_put(back), data[steps])
    check(float(m_orig["loss"]) == float(m_back["loss"]),
          "a step from the restored state gave another loss")
    return {"steps": steps, "batch": batch, "seq": seq,
            "first_loss": losses[0], "last_loss": losses[-1],
            "first_step_s": times[0],
            "median_step_s": float(np.median(times[1:])) if steps > 1
            else None, "ckpt_roundtrip_bitwise": True}


# ---------------------------------------------------------------------------
# host migration path
# ---------------------------------------------------------------------------


def phase_fabric(*, ranks=4, steps=12, migrate_at=6, seed=11):
    ref = FabricTrainer(ranks, seed=seed)
    l_ref = ref.train(steps)
    pre = FabricTrainer(ranks, seed=seed)
    l_pre = []
    for s in range(steps):
        if s == migrate_at:
            rep = pre.cluster.migrate("rank1", len(pre.cluster.nodes) - 1,
                                      strategy="pre_copy")
        l_pre.append(pre.step())
    check(l_pre == l_ref, "pre-copy migration changed the loss trajectory")
    check(all(np.array_equal(ref.weights(r), pre.weights(r))
              for r in range(ranks)), "pre-copy migration changed weights")
    return {"steps": steps, "precopy_rounds": len(rep.rounds),
            "losses_bitwise_equal": True}


# ---------------------------------------------------------------------------
# four chips: elastic re-mesh
# ---------------------------------------------------------------------------


def _train_steps(lm, opt, state, data):
    step_fn = jax.jit(adamw.make_train_step(lm, opt))
    losses = []
    for b in data:
        state, m = step_fn(state, b)
        losses.append(float(m["loss"]))
    return state, losses


def bytes_in_use(devices):
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def phase_remesh(cfg, *, big=4, small=2, batch=8, seq=256, steps=4,
                 seed=0):
    lm = LM(cfg)
    opt = _opt(2 * steps)
    logical = adamw.state_logical(lm.specs())
    data = _batches(cfg, seq, batch, 2 * steps)

    mesh_big = make_mesh((big,), ("data",))
    with part.activate(mesh_big):
        state = adamw.init_state(lm.init(jax.random.PRNGKey(seed)))
        state = remesh_state(state, logical, None, mesh_big)
        state, l_big = _train_steps(lm, opt, state, data[:steps])
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
    in_use = bytes_in_use(list(mesh_big.devices.flat))
    check(min(in_use) >= 0.5 * state_bytes / big,
          f"state not spread over the {big} chips: bytes in use {in_use}, "
          f"state {state_bytes}")

    before = jax.device_get(state)
    mesh_small = make_mesh((small,), ("data",))
    with part.activate(mesh_small):
        state = remesh_state(state, logical, mesh_big, mesh_small)
        check(all(x.sharding.device_set <= set(mesh_small.devices.flat)
                  for x in jax.tree.leaves(state)),
              "re-meshed state left on a dropped chip")
        in_use_small = bytes_in_use(list(mesh_small.devices.flat))
        check(min(in_use_small) >= 0.5 * state_bytes / small,
              f"state not spread over the {small} chips: bytes in use "
              f"{in_use_small}, state {state_bytes}")
        after = jax.device_get(state)
        check(all(np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(before), jax.tree.leaves(after))),
            "remesh_state changed the state")
        state, l_small = _train_steps(lm, opt, state, data[steps:])

    one = adamw.init_state(lm.init(jax.random.PRNGKey(seed)))
    _, l_one = _train_steps(lm, opt, one, data)
    meshed = l_big + l_small
    rel = max(abs(a - b) / abs(b) for a, b in zip(meshed, l_one))
    check(rel <= REMESH_RTOL, f"meshed losses {meshed} vs one chip {l_one}: "
          f"relative difference {rel} > {REMESH_RTOL}")
    return {"meshes": [big, small], "steps_per_mesh": steps,
            "losses_meshed": meshed, "losses_one_chip": l_one,
            "max_rel_diff": rel, "rtol": REMESH_RTOL,
            "bytes_in_use_per_chip": {str(big): in_use,
                                      str(small): in_use_small},
            "state_bytes": state_bytes,
            "remesh_bitwise": True}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_phases(phases, kind):
    """Run (name, thunk) pairs in order, printing one JSON line each."""
    for name, fn in phases:
        t0 = time.perf_counter()
        info = fn()
        t1 = time.perf_counter()
        print(json.dumps({"phase": name, "device_kind": kind,
                          "wall_s": t1 - t0,
                          "compile_s": compile_seconds(t0, t1), **info}),
              flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the elastic re-mesh across 4 chips")
    args = ap.parse_args(argv)

    cache_dir = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips; JAX found {len(devices)}",
              file=sys.stderr)
        return 1

    kind = dev.device_kind
    print(json.dumps({"compile_cache": cache_dir, "device_kind": kind,
                      "devices": len(devices)}), flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        phases = [("remesh_4_to_2", lambda: phase_remesh(lm_100m.CONFIG))]
    else:
        phases = [("serving", lambda: phase_serving(gemma3_1b.CONFIG)),
                  ("kernel", lambda: phase_kernel(gemma3_1b.CONFIG)),
                  ("scans", lambda: phase_scans(recurrentgemma_9b.CONFIG,
                                                mamba2_2_7b.CONFIG)),
                  ("train", lambda: phase_train(lm_100m.CONFIG)),
                  ("fabric", phase_fabric)]
    run_phases(phases, kind)
    t1 = time.perf_counter()
    print(json.dumps({"total_wall_s": t1 - t0,
                      "total_compile_s": compile_seconds(t0, t1)}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
