"""Readings that a cell's limits are set from, in one process.

    python bench/control.py --workload <name> --seeds <a>:<b> [--control 3]

For every seed it builds the cell as ``run.py`` does, runs a short
window through the cell's own loop and reads each number the check
compares (the program's readings, the lower end of each limit). For
the first ``--control`` seeds it also reads the control: the plain
reference computed one precision below the configuration's, put in the
program's place on the same inputs (the upper end). Prints one JSON
line per seed and a summary line; ``benchmark`` runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import shutil
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import run  # noqa: E402
from harness.loops import LOOPS  # noqa: E402


def seeds_of(text: str) -> list[int]:
    if ":" in text:
        a, b = (int(x) for x in text.split(":"))
        return list(range(a, b))
    return [int(x) for x in text.split(",")]


def readings(workload: str, seeds, n_control: int, seconds: float,
             rehearse: bool = False) -> dict:
    cell, devices = run.start(workload, rehearse)
    loop, unit_class = LOOPS[cell.traffic["loop"]]
    cls = getattr(run.cells.problem(cell), unit_class)
    lower: dict = {}
    upper: dict = {}
    for i, seed in enumerate(seeds):
        shutil.rmtree(run.SCRATCH, ignore_errors=True)
        run.SCRATCH.mkdir(parents=True)
        try:
            unit = cls(cell.sizes(rehearse), cell.traffic, seed, run.SCRATCH,
                       devices)
            window = loop(unit, seconds, cell.traffic, seed, False)
            unit.release()
            control = unit.control(window) if i < n_control else {}
            checks = unit.check(window)
        finally:
            run.cleanup()
        program = {name: v for name, v, _ in checks}
        for name, v in program.items():
            lower[name] = max(lower.get(name, v), v)
        for name, v in control.items():
            upper[name] = min(upper.get(name, v), v)
        print(json.dumps({"seed": seed, "attempted": window.attempted,
                          "failed": window.failed, "program": program,
                          "control": control,
                          "limits": {n: lim for n, _, lim in checks}}),
              flush=True)
        del unit, window
        gc.collect()
    summary = {"workload": workload, "seeds": len(seeds),
               "control_seeds": min(n_control, len(seeds)),
               "lower": lower, "upper": upper}
    print(json.dumps(summary), flush=True)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="a:b or a,b,c")
    ap.add_argument("--control", type=int, default=3,
                    help="how many of the seeds also read the control")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    readings(args.workload, seeds_of(args.seeds), args.control,
             args.seconds, args.rehearse)
    return 0


if __name__ == "__main__":
    sys.exit(main())
