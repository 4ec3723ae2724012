"""BENCHMARK.json against the rules its readers rely on: names, units, files, and
that every per-layer metric's cells report the metric it moves."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    M = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = re.compile(r"^(hidden_size|.*intermediate_size|.*latent.*|.*state.*|"
                    r".*proj.*|.*_dim|.*_rank|.*head_size|expand|"
                    r"num_experts_per_tok)$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def metrics():
    return M["end_to_end"] + M["per_layer"]


def test_top_level():
    assert set(M) == KEYS
    assert M["command"][0] == "python3" and len(M["command"]) <= 32
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in M["paths"])
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    n = len(M["workloads"])
    assert 1 <= n <= 24
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(1, n // 2)


def test_names_and_units():
    names = [x["name"] for x in M["configs"] + M["workloads"] + metrics()]
    assert all(NAME.match(n) for n in names), names
    for kind in (M["configs"], M["workloads"], metrics()):
        assert len({x["name"] for x in kind}) == len(kind)
    for w in M["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in metrics():
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_entries_have_just_their_keys():
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def reports(cell, name):
    m = next(x for x in metrics() if x["name"] == name)
    return "workloads" not in m or cell in m["workloads"]


def test_every_cell_reports_setup_another_and_a_layer():
    cells = [w["name"] for w in M["workloads"]]
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in M["end_to_end"])
    for c in cells:
        assert sum(reports(c, m["name"]) for m in M["end_to_end"]) >= 2
        assert any(reports(c, m["name"]) for m in M["per_layer"])
    for m in metrics():
        assert set(m.get("workloads", cells)) <= set(cells)


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"] for m in M["end_to_end"]}
    for m in M["per_layer"]:
        assert m["moves"] in e2e
        for c in m.get("workloads", [w["name"] for w in M["workloads"]]):
            assert reports(c, m["moves"]), (m["name"], c)


def test_roofline_and_mfu_names():
    for m in M["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_everything_is_found_by_name():
    here = os.path.join(ROOT, "chipbench")
    for m in metrics():
        assert os.path.isfile(os.path.join(here, "metrics", f"{m['name']}.py"))
    for w in M["workloads"]:
        path = os.path.join(here, "traffic", f"{w['traffic']}.json")
        with open(path) as f:
            mix = json.load(f)
        assert os.path.isfile(os.path.join(here, "drivers",
                                           f"{mix['driver']}.py"))
    files = [c["file"] for c in M["configs"]]
    assert len(set(files)) == len(files)
    for c in M["configs"]:
        assert any(c["file"].startswith(p + "/") for p in M["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert not any(WIDTHS.search(k) for k in c["reduced"]), c["reduced"]
        assert os.path.isfile(os.path.join(here, "reference",
                                           f"{cfg['arch']}.py"))


def test_command_stays_inside_paths():
    for word in M["command"][1:]:
        assert not word.startswith("/") and ".." not in word.split("/")
        assert any(word.startswith(p + "/") for p in M["paths"])


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_metrics_selection(trace):
    from chipbench.run import cell_metrics
    for w in M["workloads"]:
        got = {m["name"] for m in cell_metrics(M, w, trace)}
        group = M["per_layer"] if trace else M["end_to_end"]
        assert got == {m["name"] for m in group if reports(w["name"],
                                                             m["name"])}
