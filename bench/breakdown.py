"""Idle device time of a kept trace, labelled by the program's spans.

    python bench/breakdown.py <window.xplane.pb> [--top 10]

Reads a trace that ``run.py --trace 1 --keep-trace <path>`` kept and
prints one JSON line: the window and busy seconds, the idle gaps summed
by the innermost ``bench.*`` annotation around each (``bench``, as the
result line of a traced run gives them), and the same gaps summed by
the innermost span of either kind, the benchmark's or the program's
(``program``). A program that does not mirror its spans into the trace
gives the same list twice.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness import program, xplane  # noqa: E402


def breakdown(path, top: int = 10) -> dict:
    bench = xplane.reduce(path)
    both = program.with_program_spans(bench, path)
    return {"window_s": bench.window_s, "busy_s": bench.busy_s(),
            "idle_gaps": {"bench": bench.idle_gaps(top),
                          "program": both.idle_gaps(top)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", type=pathlib.Path)
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    print(json.dumps(breakdown(args.trace, args.top)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
