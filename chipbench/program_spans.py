"""What the per-layer metrics read of the program's own host-clock spans and
load counter (``repro.obs.host``), inside a window ``[t0, close]``.

A program without that module, or whose recorder lost entries inside the
window, gives nothing to read: each reader then returns None and the
harness leaves the metric out.
"""


def recorder():
    """``repro.obs.host``, or None where the program has none."""
    try:
        from repro.obs import host
    except ImportError:
        return None
    return host


def per_parent(record, phase, parent):
    """Seconds of the spans ``phase`` in the window over the number of the
    spans ``parent`` there: a phase's time per save or per restore."""
    host = recorder()
    if host is None:
        return None
    t0, t1 = record["t0"], record["close"]
    parts = host.durations(phase, t0, t1)
    parents = host.durations(parent, t0, t1)
    if not parts or not parents:
        return None
    return sum(parts) / len(parents)


def mean(record, name):
    """Mean seconds of the spans ``name`` in the window."""
    host = recorder()
    if host is None:
        return None
    d = host.durations(name, record["t0"], record["close"])
    return sum(d) / len(d) if d else None


def load_seconds(record):
    """Wall seconds of the window's dispatch spans that saw a load event
    (a trace, lowering, compile or persistent-cache read); 0 where the
    window dispatched and loaded nothing."""
    host = recorder()
    if host is None:
        return None
    got = host.spans(host.DISPATCH_SPANS, record["t0"], record["close"])
    if not got:
        return None
    return sum(s.end - s.start for s in got if s.loads)
