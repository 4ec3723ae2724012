"""Run one cell of the chip benchmark once, on the machine it is started on.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>
    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --sweep 2,3,4    # the knee sweep of an open-loop cell: no move,
                         # one window per rate, one table line per rate

Everything a cell is made of is found by name: the workload in
BENCHMARK.json names a configuration (``configs/<config>.json``) and a
traffic mix (``traffic/<traffic>.json``, whose ``driver`` names
``drivers/<driver>.py``); each metric is ``metrics/<name>.py``.

A run: set-up (weights from the seed on the device, every shape warmed),
then a window of ``--seconds``, then the check of what the window produced
against the plain reference. With ``--trace 1`` the window is traced and the
per-layer metrics are reported; with ``--trace 0`` the end-to-end ones.
Earlier stdout lines are information; the last is the result. The numbers
compared, each with its limit, are the last lines on stderr and the last
key of the result. It needs a TPU and as many chips as the cell asks for,
and exits 1 without a result otherwise.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import shutil     # noqa: E402
import sys        # noqa: E402
import tempfile   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Refused(RuntimeError):
    """The run cannot be made here; no result is printed."""


def load_cell(name, root=ROOT):
    """The workload ``name`` with its manifest, configuration and mix."""
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise Refused(f"no BENCHMARK.json at {root}")
    with open(path) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", f"{cell['traffic']}.json")) as f:
        mix = json.load(f)
    return manifest, cell, cfg, mix


def cell_metrics(manifest, cell, trace):
    """The metric entries this cell reports in this kind of run."""
    group = manifest["per_layer"] if trace else manifest["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell["name"] in m["workloads"]]


class Run:
    """What one run carries from set-up through the window to the check."""

    def __init__(self, cfg, mix, seed, chips, device_kind, control=None,
                 program_options=None):
        from chipbench.common import CompileCounter, Spans
        self.cfg, self.mix, self.seed, self.chips = cfg, mix, seed, chips
        self.device_kind = device_kind
        self.spans = Spans()
        self.compiles = CompileCounter()
        self.prefill_lengths, self.decode_contexts = [], []
        self.control = control
        self.program_options = program_options or {}
        self.trace = None
        self.setup_s = None


def judge(checks, limits):
    """``correct`` and the numbers compared beside their limits. A number
    that has no limit makes the run not correct."""
    table = {k: {"value": v, "limit": limits.get(k)}
             for k, v in checks.items()}
    correct = bool(table) and all(
        t["limit"] is not None and t["value"] <= t["limit"]
        for t in table.values())
    return correct, table


def _device_check(chips, require_tpu):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found platform "
                      f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise Refused(f"needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def _setup_jax():
    import jax
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # A Pallas kernel's body carries the locations of its trace, and with
    # full tracebacks those name every caller: a program traced from the
    # window would then miss the entry its warm-up wrote from another line.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    return cache


def window_seconds(mix, seconds, trace):
    """A traced run of a mix with ``trace_seconds`` measures that long: its
    trace would otherwise outgrow the time a run may take to read it."""
    return min(seconds, mix.get("trace_seconds", seconds)) if trace \
        else seconds


def _percentiles(xs, qs):
    import numpy as np
    return {f"p{q}": float(np.percentile(xs, q)) for q in qs} if xs else {}


def execute(name, seed, seconds, trace, *, root=ROOT, require_tpu=True,
            override=None, control=None, program_options=None, out=print,
            started=T_START):
    """One run of a cell; returns the result dict (also printed through
    ``out`` as the last line). ``override(cfg, mix)`` may replace the
    configuration and mix (the CPU tests use it for small sizes);
    ``started`` is when set-up began (the process start)."""
    manifest, cell, cfg, mix = load_cell(name, root)
    if override is not None:
        cfg, mix = override(cfg, mix)
    devices = _device_check(cell["chips"], require_tpu)
    import jax
    from chipbench import drivers
    from chipbench.metrics import load as load_metric
    cache = _setup_jax()
    dev = devices[0]
    run = Run(cfg, mix, seed, cell["chips"], dev.device_kind,
              control=control, program_options=program_options)
    driver = drivers.load(mix["driver"]).Driver(run)
    driver.setup()
    run.setup_s = time.perf_counter() - started
    snap = run.compiles.snapshot()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        if trace:
            # device ops and the harness's annotations; no Python tracer,
            # whose per-call events would slow the host being measured
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level, opts.host_tracer_level = 0, 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with run.spans("chipbench.window"):
                record = driver.window(window_seconds(mix, seconds, trace))
        finally:
            if trace:
                jax.profiler.stop_trace()
        in_window = run.compiles.since(snap)
        memory_peak = _memory_peak(devices)
        if trace:
            from chipbench import trace_reduce
            names = {n for n, _, _ in run.spans.items}
            run.trace = trace_reduce.reduce_dir(trace_dir, names)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    driver.release()
    checks, info = driver.check(record)
    correct, table = judge(checks, cfg["limits"])

    metrics = {}
    for m in cell_metrics(manifest, cell, trace):
        v = load_metric(m["name"]).value(run, record)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if run.trace:
        device.update(busy_s=run.trace["busy_s"],
                      window_s=run.trace["window_s"])
    lateness = [r.sent - r.due for r in record.get("released", [])]
    out(json.dumps({"info": "run", "workload": name, "seed": seed,
                    "seconds": seconds, "trace": bool(trace),
                    "compile_cache": cache,
                    "compiles_in_window": in_window,
                    "generator_lateness_s": _percentiles(lateness, (50, 99)),
                    "move_image_bytes": [m[4] for m in record.get("moves",
                                                                  [])],
                    **info}))
    result = {"correct": correct, "attempted": _attempted(record),
              "failed": 0, "metrics": metrics, "device": device}
    if run.trace:
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = table
    for k, t in table.items():
        print(f"check {k}: {t['value']!r} limit {t['limit']!r}",
              file=sys.stderr)
    out(json.dumps(result))
    return result


def _attempted(record):
    if "due" in record:
        return len(record["due"])
    return record["steps"]


def _memory_peak(devices):
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"])
    return max(peaks) if peaks else None


def sweep(name, seed, seconds, rates, *, root=ROOT, require_tpu=True,
          override=None, out=print):
    """The knee sweep of an open-loop cell: one process, one set-up, then a
    window per rate with no move. Prints one line per rate."""
    manifest, cell, cfg, mix = load_cell(name, root)
    if override is not None:
        cfg, mix = override(cfg, mix)
    devices = _device_check(cell["chips"], require_tpu)
    from chipbench import drivers
    from chipbench.metrics import load as load_metric
    _setup_jax()
    run = Run(cfg, dict(mix, move_at=None), seed, cell["chips"],
              devices[0].device_kind)
    driver = drivers.load(mix["driver"]).Driver(run)
    driver.setup()
    ttft = load_metric("ttft_p95_ms")
    rows = []
    for rate in rates:
        run.mix = driver.mix = dict(run.mix, rate_per_s=rate)
        record = driver.window(seconds)
        t = ttft.ttfts(record)
        row = {"info": "sweep", "rate_per_s": rate,
               "due": len(record["due"]),
               "ttft_p50_ms": _percentiles(t, (50,)).get("p50", 0) * 1e3,
               "ttft_p95_ms": ttft.value(run, record),
               "tokens_per_s": record["tokens"] /
               (record["close"] - record["t0"]),
               "unserved_at_close": sum(r.first is None
                                        for r in record["due"])}
        # let the backlog drain before the next rate
        driver.eng.run_until_done(max_steps=100_000)
        rows.append(row)
        out(json.dumps(row))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated request rates (open-loop cells)")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        if args.sweep:
            sweep(args.workload, args.seed, args.seconds,
                  [float(r) for r in args.sweep.split(",")])
        else:
            execute(args.workload, args.seed, args.seconds, args.trace)
    except (Refused, ImportError, FileNotFoundError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
