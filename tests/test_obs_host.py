"""Host-clock spans of the chip path (repro.obs.host): the recorder, the
serving engine's spans and the checkpoint's phases, on the CPU."""
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.obs import host
from repro.obs.host import Recorder


def within(child, parents):
    return any(p.start <= child.start and child.end <= p.end for p in parents)


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------


def test_spans_nest_and_queries_filter_by_window():
    rec = Recorder()
    t0 = time.perf_counter()
    with rec.span("outer") as o:
        with rec.span("inner") as i:
            pass
    t1 = time.perf_counter()
    with rec.span("outer"):
        pass
    outer = rec.spans(("outer",), t0, t1)
    inner = rec.spans(("inner",), t0, t1)
    assert [s.name for s in outer] == ["outer"] and len(inner) == 1
    assert o.start <= i.start <= i.end <= o.end
    assert (outer[0].start, outer[0].end) == (o.start, o.end)
    assert rec.durations("outer", t0, t1) == [o.end - o.start]
    assert len(rec.durations("outer")) == 2
    assert rec.durations("outer", t1, time.perf_counter()) == \
        [rec.spans(("outer",))[1].end - rec.spans(("outer",))[1].start]
    rec.record("gap", t0, t1)
    assert rec.durations("gap") == [t1 - t0]
    assert rec.dropped == 0


def test_ring_overflow_is_counted_and_the_lost_window_gives_none():
    rec = Recorder(size=4)
    for k in range(6):
        rec.record("s", float(k), k + 0.5)
    assert rec.dropped == 2
    # entries 0 and 1 were pushed out: a window reaching back to them
    # cannot be answered; one that starts after them can
    assert rec.durations("s", 0.0, 10.0) is None
    assert rec.spans(("s",), 1.0, 10.0) is None
    assert rec.loads(0.0, 10.0) is None
    assert rec.durations("s", 2.0, 10.0) == [0.5] * 4
    assert rec.loads(2.0, 10.0) == []


def test_a_load_goes_to_the_innermost_span_of_its_own_thread():
    rec = Recorder()
    ready, go = threading.Event(), threading.Event()

    def other():
        with rec.span("other"):
            ready.set()
            go.wait(5)

    t = threading.Thread(target=other)
    t.start()
    assert ready.wait(5)
    with rec.span("outer"):
        with rec.span("inner"):
            rec.on_duration(host.BACKEND_COMPILE, 0.25)
        rec.on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                        0.01)
        rec.on_duration("/jax/some/other_event", 1.0)    # not a load
    go.set()
    t.join(5)
    assert not t.is_alive()
    rec.on_duration(host.BACKEND_COMPILE, 0.5)             # no span open
    by = {s.name: s.loads for s in rec.spans(("outer", "inner", "other"))}
    assert by == {"outer": 1, "inner": 1, "other": 0}
    loads = rec.loads()
    assert [ld.span for ld in loads] == ["inner", "outer", None]
    assert loads[0].event == host.BACKEND_COMPILE
    assert loads[0].end - loads[0].start == pytest.approx(0.25)


def test_the_process_recorder_counts_a_real_compile():
    t0 = time.perf_counter()
    with host.span(host.SERVING_DECODE_DISPATCH) as d:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    got = host.spans(host.DISPATCH_SPANS, t0, time.perf_counter())
    assert [s.loads > 0 for s in got] == [True] and d.loads > 0
    events = {ld.event for ld in host.loads(t0, time.perf_counter())}
    assert host.BACKEND_COMPILE in events


def test_threads_share_the_ring_without_losing_a_count():
    """More threads than cores, switching often: every append is either
    in the ring or counted as dropped, and each thread's spans nest on its
    own stack."""
    import os
    import sys
    rec = Recorder(size=5000)
    n_threads, per = 2 * (os.cpu_count() or 2) + 2, 1500
    errors = []

    def work(k):
        try:
            for j in range(per):
                with rec.span(f"t{k}") as o:
                    with rec.span(f"t{k}.in") as i:
                        pass
                assert o.start <= i.start <= i.end <= o.end
                assert rec._stack() == []
        except AssertionError as e:
            errors.append(e)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads) and not errors
    total = 2 * n_threads * per
    assert rec.dropped == total - 5000
    assert len(rec._ring) == 5000


def test_every_span_name_is_unique_and_dotted():
    assert len(set(host.SPAN_NAMES)) == len(host.SPAN_NAMES)
    assert all(n.split(".")[0] in ("serving", "ckpt")
               for n in host.SPAN_NAMES)
    assert set(host.DISPATCH_SPANS) <= set(host.SPAN_NAMES)


# ---------------------------------------------------------------------------
# the serving engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_lm():
    from repro.configs import stablelm_1_6b
    from repro.models.model import LM
    lm = LM(stablelm_1_6b.smoke())
    return lm, lm.init(jax.random.PRNGKey(0))


def _serve(lm, params, prompts, max_new=4):
    from repro.serving.engine import Request, ServingEngine
    eng = ServingEngine(lm, params, slots=2, capacity=32)
    reqs = [Request(i, p, max_new=max_new) for i, p in enumerate(prompts)]
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_done()
    return eng, reqs


def test_engine_spans_nest_and_the_gap_sits_between_wait_and_dispatch(
        tiny_lm):
    lm, params = tiny_lm
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 512, 8).astype(np.int32) for _ in range(2)]
    t0 = time.perf_counter()
    eng, reqs = _serve(lm, params, prompts)
    t1 = time.perf_counter()
    S = {n: host.spans((n,), t0, t1) for n in host.SPAN_NAMES
         if n.startswith("serving.")}
    assert all(S.values()), {n: len(v) for n, v in S.items()}
    assert len(S[host.SERVING_SUBMIT]) == 2
    assert len(S[host.SERVING_STEP]) == eng.steps == 3
    for child in (host.SERVING_PREFILL_DISPATCH,
                  host.SERVING_SLOT_WRITE_DISPATCH,
                  host.SERVING_FIRST_TOKEN_WAIT):
        assert len(S[child]) == 2
        assert all(within(c, S[host.SERVING_SUBMIT]) for c in S[child])
    for child in (host.SERVING_DECODE_DISPATCH, host.SERVING_STEP_WAIT):
        assert len(S[child]) == eng.steps
        assert all(within(c, S[host.SERVING_STEP]) for c in S[child])
    for parent, kids in ((host.SERVING_SUBMIT,
                          (host.SERVING_PREFILL_DISPATCH,
                           host.SERVING_SLOT_WRITE_DISPATCH,
                           host.SERVING_FIRST_TOKEN_WAIT)),
                         (host.SERVING_STEP, (host.SERVING_DECODE_DISPATCH,
                                              host.SERVING_STEP_WAIT))):
        for p in S[parent]:
            inside = [c for k in kids for c in S[k]
                      if p.start <= c.start and c.end <= p.end]
            assert len(inside) == len(kids)
            assert sum(c.end - c.start for c in inside) <= p.end - p.start
    # a gap from every wait but the last to the next dispatch: the second
    # submit, then each step
    by_start = lambda s: s.start  # noqa: E731
    waits = sorted(S[host.SERVING_FIRST_TOKEN_WAIT] +
                   S[host.SERVING_STEP_WAIT], key=by_start)
    dispatches = sorted(S[host.SERVING_PREFILL_DISPATCH] +
                        S[host.SERVING_DECODE_DISPATCH], key=by_start)
    gaps = sorted(S[host.SERVING_HOST_GAP], key=by_start)
    assert len(gaps) == len(waits) - 1 == len(dispatches) - 1
    for g, w, d in zip(gaps, waits, dispatches[1:]):
        assert (g.start, g.end) == (w.end, d.start)


def test_a_new_engine_loads_its_programs_again(tiny_lm):
    lm, params = tiny_lm
    rng = np.random.RandomState(1)
    prompt = [rng.randint(0, 512, 12).astype(np.int32)]
    _serve(lm, params, prompt, max_new=2)       # loads and warms
    t0 = time.perf_counter()
    _serve(lm, params, prompt, max_new=2)       # the same shapes
    got = host.spans((host.SERVING_PREFILL_DISPATCH,), t0,
                     time.perf_counter())
    assert len(got) == 1 and got[0].loads > 0


# ---------------------------------------------------------------------------
# the checkpoint
# ---------------------------------------------------------------------------


def _tree():
    return {"a": jnp.arange(4096, dtype=jnp.float32).reshape(64, 64),
            "b": [jnp.ones((3, 5), jnp.bfloat16), jnp.arange(7)]}


def _phases(t0, t1, names):
    return {n: host.spans((n,), t0, t1) for n in names}


SAVE_PHASES = (host.CKPT_SAVE_TO_HOST, host.CKPT_SAVE_ENCODE,
               host.CKPT_SAVE_WRITE)


@pytest.mark.parametrize("async_write", [False, True])
def test_ckpt_phases_lie_inside_save_and_restore(tmp_path, async_write):
    tree = _tree()
    t0 = time.perf_counter()
    got = ckpt.save(str(tmp_path), tree, step=3, async_write=async_write)
    if async_write:
        d, th = got
        th.join(30)
        assert not th.is_alive()
    else:
        d = got
    t1 = time.perf_counter()
    P = _phases(t0, t1, (host.CKPT_SAVE, host.CKPT_SAVE_ENCODE_LEAF) +
                SAVE_PHASES)
    n_leaves = len(jax.tree.leaves(tree))
    assert len(P[host.CKPT_SAVE]) == 1 and len(P[host.CKPT_SAVE_TO_HOST]) == 1
    assert len(P[host.CKPT_SAVE_ENCODE]) == 1       # one wall span per save
    assert len(P[host.CKPT_SAVE_ENCODE_LEAF]) == n_leaves
    assert all(within(s, P[host.CKPT_SAVE_ENCODE])
               for s in P[host.CKPT_SAVE_ENCODE_LEAF])
    assert len(P[host.CKPT_SAVE_WRITE]) == n_leaves + 1     # + manifest
    save = P[host.CKPT_SAVE][0]
    assert within(P[host.CKPT_SAVE_TO_HOST][0], [save])
    phases = [s for n in SAVE_PHASES for s in P[n]]
    # with async_write the encode and write spans are the writer thread's,
    # after the save call has returned: the whole runs to the last of them
    whole = max([save.end] + [s.end for s in phases]) - save.start
    assert all(s.start >= save.start for s in phases)
    assert sum(s.end - s.start for s in phases) <= whole
    if not async_write:
        assert all(within(s, [save]) for s in phases)

    t2 = time.perf_counter()
    out = ckpt.restore(d, tree)
    R = _phases(t2, time.perf_counter(),
                (host.CKPT_RESTORE, host.CKPT_RESTORE_READ,
                 host.CKPT_RESTORE_DECODE, host.CKPT_RESTORE_DECODE_LEAF))
    assert len(R[host.CKPT_RESTORE]) == 1
    assert len(R[host.CKPT_RESTORE_READ]) == n_leaves + 1   # + manifest
    assert len(R[host.CKPT_RESTORE_DECODE]) == 1    # one wall span
    assert len(R[host.CKPT_RESTORE_DECODE_LEAF]) == n_leaves
    assert all(within(s, R[host.CKPT_RESTORE_DECODE])
               for s in R[host.CKPT_RESTORE_DECODE_LEAF])
    rs = R[host.CKPT_RESTORE][0]
    inner = R[host.CKPT_RESTORE_READ] + R[host.CKPT_RESTORE_DECODE]
    assert all(within(s, [rs]) for s in inner)
    assert sum(s.end - s.start for s in inner) <= rs.end - rs.start
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), b)


def test_a_leaf_over_100_mib_restores_bitwise(tmp_path):
    n = (101 << 20) // 4
    bits = np.random.default_rng(0).integers(0, 2 ** 32, n, dtype=np.uint32)
    leaf = bits.view(np.float32)
    d = ckpt.save(str(tmp_path), {"w": leaf}, step=0)
    out = ckpt.restore(d, {"w": leaf})["w"]
    assert out.dtype == np.float32 and out.shape == leaf.shape
    np.testing.assert_array_equal(out.view(np.uint32), bits)


def test_a_many_leaf_tree_round_trips_bitwise_on_the_pool(tmp_path):
    """Leaves decoded at once on the pool come back bitwise, in order,
    with their dtypes and shapes; a cut leaf file raises."""
    import ml_dtypes
    rng = np.random.default_rng(1)
    big = (101 << 20) // 4

    def f32(n):
        return rng.integers(0, 2 ** 32, n, dtype=np.uint32).view(np.float32)

    def bf16(shape):
        return rng.integers(0, 2 ** 16, shape, dtype=np.uint16).view(
            ml_dtypes.bfloat16)

    leaves = [f32(big).reshape(-1, 1024), bf16((8, 64, 128)),
              np.float32(2.5).reshape(()), np.zeros((0, 3), np.int32),
              f32(big + 7), bf16((3, 5)), np.arange(11, dtype=np.int64),
              bf16((2, 2048, 96)), np.asfortranarray(f32(600).reshape(20, 30))]
    tree = {f"l{k}": a for k, a in enumerate(leaves)}
    t0 = time.perf_counter()
    d = ckpt.save(str(tmp_path), tree, step=0)
    out = ckpt.restore(d, tree)
    t1 = time.perf_counter()
    assert len(host.spans((host.CKPT_RESTORE_DECODE_LEAF,), t0, t1)) == \
        len(leaves)
    # in order: each leaf's neighbours differ in dtype or shape
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert b.dtype == a.dtype and b.shape == a.shape
        assert np.ascontiguousarray(a).tobytes() == b.tobytes()

    leaf = os.path.join(d, "leaf_00001.bin")
    with open(leaf, "rb") as f:
        blob = f.read()
    with open(leaf, "wb") as f:
        f.write(blob[:len(blob) - 9])
    with pytest.raises(ValueError, match="decoded to"):
        ckpt.restore(d, tree)


def test_the_docs_gate_reads_every_span_name():
    from tools.check_docs import check_span_names, span_names
    assert span_names() == list(host.SPAN_NAMES)
    assert check_span_names(span_names()) == []
    assert check_span_names(["serving.nowhere"]) != []
