"""Readings for the limits of ``correct``, on the chip at a cell's own size.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 \\
        --seconds 25 [--control-seeds 1,2,3] [--faults half_batch]

In one process, for each seed: a run of the cell as the benchmark makes it
(a window at the cell's own load), whose numbers compared are the program's
readings. Serving cells run with the control in the program's place: the
reference with float8 (e4m3) operands read at the same positions, whose
``logit_gap`` the run judges (it has to come out not correct); the same run
reports the program's served tokens' gap, which is the program's reading. Training cells run the
program again on ``--control-seeds`` with its own lower-precision path on
(``compress_grads``: int8 gradients), and with each fault of ``--faults``
(``faults.py``) planted. Prints one JSON line per run and a summary: the
largest program reading and the smallest control or fault reading of each
number. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(name, seed, seconds, **kw):
    """(numbers compared, correct, the run's information line)."""
    from chipbench import run as R
    lines = []
    res = R.execute(name, seed, seconds, 0, out=lines.append,
                    started=time.perf_counter(), **kw)
    return ({k: v["value"] for k, v in res["checks"].items()},
            res["correct"], json.loads(lines[0]))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import faults
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    serving = "serve" in args.workload
    program, lower = {}, {}

    def note(kind, seed, got, correct):
        print(json.dumps({"kind": kind, "seed": seed, "correct": correct,
                          **got}), flush=True)
        for k, v in got.items():
            if kind == "program":
                program[k] = max(program.get(k, 0.0), v)
            else:
                lower.setdefault(kind, {})[k] = min(
                    lower.get(kind, {}).get(k, float("inf")), v)

    from chipbench.run import judge, load_cell
    limits = load_cell(args.workload)[2]["limits"]
    for s in seeds:
        if serving:
            ctrl, ctrl_ok, info = readings(args.workload, s, args.seconds,
                                           control="fp8")
            got = dict(ctrl, logit_gap=info["served_logit_gap"])
            note("program", s, got, judge(got, limits)[0])
            note("control_fp8", s, {"logit_gap": ctrl["logit_gap"]}, ctrl_ok)
        else:
            got, ok, _ = readings(args.workload, s, args.seconds)
            note("program", s, got, ok)
    for s in ctrl_seeds:
        got, ok, _ = readings(args.workload, s, args.seconds,
                              program_options={"compress_grads": True})
        note("control_int8_grads", s, got, ok)
    for f in filter(None, args.faults.split(",")):
        for s in ctrl_seeds:
            with faults.FAULTS[f]():
                got, ok, _ = readings(args.workload, s, args.seconds)
            note(f"fault_{f}", s, got, ok)
    print(json.dumps({"summary": args.workload, "program_max": program,
                      "lower_min": lower}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
