"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy seconds in the traced window, device seconds of each
named kernel, and a breakdown (the device ops that took most time, and the
longest idle gaps labelled by what the host was doing).

Read with ``jax.profiler.ProfileData``. The window is the host event of the
harness's ``chipbench.window`` annotation. Device ops are the events of each
TPU plane's op line; busy time is the union of their intervals inside the
window, averaged over the device planes. A gap is labelled with the
innermost host annotation of the harness (``engine.step``,
``move.save``, ...) that covers its middle, or ``other``.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "chipbench.window"
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
# a kernel's events, by a piece of their HLO text: the Pallas kernels carry
# no name of their own yet, and flash attention is the only one that the
# StableLM cells run
KERNELS = {"flash_attention": ('custom_call_target="tpu_custom_call"',)}
TOP = 10
_OPCODE = re.compile(r"\b([a-z][\w\-]*)\(")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    """Merge [start, end) intervals; returns the sorted merged list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def read(path: str, host_names):
    """(device planes {name: [(op, start_ns, end_ns)]}, host spans
    [(name, start_ns, end_ns)]) of a trace. An op is named by its module
    and the head of its HLO text. Host spans are the events whose name is
    in ``host_names``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: [(ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns)
                                 for ev in line.events]
                     for line in plane.lines
                     if line.name in (OP_LINE, MODULE_LINE)}
            if lines.get(OP_LINE):
                devices[plane.name] = _in_modules(lines[OP_LINE],
                                                  lines.get(MODULE_LINE, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host_names:
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return devices, host


def _in_modules(ops, modules):
    """(module/op head, full text, start, end) for each op."""
    out, mods, i = [], sorted(modules, key=lambda m: m[1]), 0
    for text, s, e in sorted(ops, key=lambda o: o[1]):
        while i + 1 < len(mods) and mods[i + 1][1] <= s:
            i += 1
        mod = mods[i][0].split("(")[0] if mods and mods[i][1] <= s else "?"
        head, _, rest = text.partition(" = ")
        kind = _OPCODE.search(rest)
        out.append((f"{mod}/{head.lstrip('%')}"
                    f"{' ' + kind.group(1) if kind else ''}", text, s, e))
    return out


def reduce(devices, host, kernels=KERNELS):
    """The numbers of a trace read by ``read``."""
    win = [(s, e) for n, s, e in host if n == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    lo, hi = max(win, key=lambda w: w[1] - w[0])
    busy, per_op, kern, first = [], {}, {k: 0.0 for k in kernels}, None
    for plane in sorted(devices):
        ops = [(n, t, max(s, lo), min(e, hi))
               for n, t, s, e in devices[plane] if e > lo and s < hi]
        merged = _union([(s, e) for _, _, s, e in ops])
        busy.append(sum(e - s for s, e in merged))
        for n, t, s, e in ops:
            per_op[n] = per_op.get(n, 0.0) + (e - s)
            for k, pieces in kernels.items():
                if any(p in t for p in pieces):
                    kern[k] += e - s
        if first is None:
            first = merged
    window_ns = hi - lo
    spans = [(n, s, e) for n, s, e in host if n != WINDOW]
    gaps = []
    edges = [lo] + [x for iv in (first or []) for x in iv] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            gaps.append((_label(spans, (s + e) / 2), (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    n_dev = max(len(busy), 1)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "kernels": {k: v / n_dev / 1e9 for k, v in kern.items()},
        "device_ops": [[n, v / n_dev / 1e9] for n, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[n, s] for n, s in gaps[:TOP]],
        "devices": len(busy),
    }


def _label(spans, t):
    inner = [(e - s, n) for n, s, e in spans if s <= t <= e]
    return min(inner)[1] if inner else "other"


def reduce_dir(trace_dir: str, host_names):
    devices, host = read(find_xplane(trace_dir), set(host_names) | {WINDOW})
    return reduce(devices, host)
