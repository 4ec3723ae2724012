"""Host-clock spans and the program-load counter of the chip path.

The simulator's tracer (``repro.obs.trace``) runs on the fabric's sim
clock. The chip path (``ServingEngine``, ``ckpt``) runs on real time, so
its spans are taken on the host clock, ``time.perf_counter``: the clock
of a benchmark window's ``t0`` and ``close``. A span is
``(name, start, end)``. Each one is also a
``jax.profiler.TraceAnnotation`` of the same name, so while a profiler
trace runs it lands in the host plane of the same ``.xplane.pb`` as the
device ops, on the profiler's clock: that trace is the exporter, and this
module writes no file of its own.

Recording is always on, into one process-wide ring of ``RING`` entries;
an entry pushed out of a full ring is counted in ``dropped``, and a query
whose window reaches back to a pushed-out entry returns ``None`` rather
than a short answer. A span costs two clock reads, an annotation and an
append: a few microseconds (``docs/observability.md``).

Every span name is a constant below, listed in ``SPAN_NAMES``
(``tools/check_docs.py`` gates that each is documented).

The load counter is the one ``jax.monitoring`` listener of the program.
It gives each trace, lowering, backend compile or persistent-cache read
(``LOAD_EVENTS``) to the innermost span open on the calling thread (a
dispatch that loaded a program), and records the event itself as a
``Load``.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import List, NamedTuple, Optional

import jax

RING = 65_536

SERVING_SUBMIT = "serving.submit"
SERVING_PREFILL_DISPATCH = "serving.prefill.dispatch"
SERVING_SLOT_WRITE_DISPATCH = "serving.slot_write.dispatch"
SERVING_FIRST_TOKEN_WAIT = "serving.first_token.wait"
SERVING_STEP = "serving.step"
SERVING_DECODE_DISPATCH = "serving.decode.dispatch"
SERVING_STEP_WAIT = "serving.step.wait"
SERVING_HOST_GAP = "serving.host_gap"
CKPT_SAVE = "ckpt.save"
CKPT_SAVE_TO_HOST = "ckpt.save.to_host"
CKPT_SAVE_ENCODE = "ckpt.save.encode"
CKPT_SAVE_ENCODE_LEAF = "ckpt.save.encode.leaf"
CKPT_SAVE_WRITE = "ckpt.save.write"
CKPT_RESTORE = "ckpt.restore"
CKPT_RESTORE_READ = "ckpt.restore.read"
CKPT_RESTORE_DECODE = "ckpt.restore.decode"
CKPT_RESTORE_DECODE_LEAF = "ckpt.restore.decode.leaf"

SPAN_NAMES = (
    SERVING_SUBMIT, SERVING_PREFILL_DISPATCH, SERVING_SLOT_WRITE_DISPATCH,
    SERVING_FIRST_TOKEN_WAIT, SERVING_STEP, SERVING_DECODE_DISPATCH,
    SERVING_STEP_WAIT, SERVING_HOST_GAP,
    CKPT_SAVE, CKPT_SAVE_TO_HOST, CKPT_SAVE_ENCODE, CKPT_SAVE_ENCODE_LEAF,
    CKPT_SAVE_WRITE, CKPT_RESTORE, CKPT_RESTORE_READ, CKPT_RESTORE_DECODE,
    CKPT_RESTORE_DECODE_LEAF,
)

# the engine's calls that hand work to the device: one that saw a load
# event loaded a program
DISPATCH_SPANS = (SERVING_PREFILL_DISPATCH, SERVING_SLOT_WRITE_DISPATCH,
                  SERVING_DECODE_DISPATCH)

# JAX's monitoring durations of tracing, lowering, the backend compile (a
# persistent-cache hit inside it) and the cache's read
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
LOAD_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    BACKEND_COMPILE,
    "/jax/compilation_cache/cache_retrieval_time_sec",
})


class Span(NamedTuple):
    name: str
    start: float
    end: float
    loads: int              # load events given to this span


class Load(NamedTuple):
    event: str
    start: float            # end less the event's duration
    end: float              # when JAX reported it
    span: Optional[str]     # the innermost open span of the thread


_SPAN, _LOAD = 0, 1


class _Open:
    """A span while it is open: what ``Recorder.span`` returns."""
    __slots__ = ("rec", "name", "loads", "start", "end", "_ann")

    def __init__(self, rec, name):
        self.rec, self.name, self.loads = rec, name, 0
        self.end = None

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.rec._stack().append(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.end = time.perf_counter()
        self.rec._stack().pop()
        self._ann.__exit__(*exc)
        self.rec._append((_SPAN, self.name, self.start, self.end,
                          self.loads))
        return False


class Recorder:
    """A bounded ring of spans and load events; see the module docstring.
    ``RECORDER`` is the process's; tests make small ones of their own."""

    def __init__(self, size: int = RING):
        self._ring = collections.deque(maxlen=size)
        self._lock = threading.Lock()
        self._local = threading.local()
        self.dropped = 0            # entries pushed out of the full ring
        self._lost_until = float("-inf")    # the latest end pushed out

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _append(self, entry):
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
                self._lost_until = max(self._lost_until, self._ring[0][3])
            self._ring.append(entry)

    def span(self, name: str) -> _Open:
        """``with recorder.span(NAME) as s:``; ``s.start`` and, after the
        block, ``s.end`` are its times."""
        return _Open(self, name)

    def record(self, name: str, start: float, end: float):
        """A span taken without an annotation (a stretch between calls)."""
        self._append((_SPAN, name, start, end, 0))

    def on_duration(self, event: str, seconds: float, **_):
        """The ``jax.monitoring`` duration listener."""
        if event not in LOAD_EVENTS:
            return
        end = time.perf_counter()
        stack = self._stack()
        owner = stack[-1] if stack else None
        if owner is not None:
            owner.loads += 1
        self._append((_LOAD, event, end - seconds, end,
                      owner.name if owner is not None else None))

    def _window(self, kind, t0, t1) -> Optional[list]:
        with self._lock:
            entries = list(self._ring)
            lost = self.dropped and self._lost_until >= t0
        if lost:
            return None
        return [e[1:] for e in entries
                if e[0] == kind and e[2] >= t0 and e[3] <= t1]

    def spans(self, names, t0=float("-inf"),
              t1=float("inf")) -> Optional[List[Span]]:
        """The spans named in ``names`` that lie inside [t0, t1]."""
        got = self._window(_SPAN, t0, t1)
        return None if got is None else [Span(*e) for e in got
                                         if e[0] in names]

    def durations(self, name: str, t0=float("-inf"),
                  t1=float("inf")) -> Optional[List[float]]:
        """Seconds of each span ``name`` inside [t0, t1]."""
        got = self.spans((name,), t0, t1)
        return None if got is None else [s.end - s.start for s in got]

    def loads(self, t0=float("-inf"), t1=float("inf")) -> Optional[List[Load]]:
        """The load events inside [t0, t1]."""
        got = self._window(_LOAD, t0, t1)
        return None if got is None else [Load(*e) for e in got]


RECORDER = Recorder()
jax.monitoring.register_event_duration_secs_listener(RECORDER.on_duration)

span = RECORDER.span
record = RECORDER.record
spans = RECORDER.spans
durations = RECORDER.durations
loads = RECORDER.loads
