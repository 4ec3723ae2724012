"""One module per metric, named as in BENCHMARK.json
(``chipbench/metrics/<name>.py``). Each has ``value(run, record)``, which
returns the metric's number in its unit, or None where the run gives it
nothing to read (the harness then leaves the metric out)."""
import importlib.util
import os

_DIR = os.path.dirname(os.path.abspath(__file__))


def load(name):
    path = os.path.join(_DIR, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics._{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def window_s(record):
    return record["close"] - record["t0"]


def mean_span(run, record, name):
    """Mean seconds of the host spans ``name`` inside the window."""
    d = run.spans.durations(name, record["t0"], record["close"])
    return sum(d) / len(d) if d else None
