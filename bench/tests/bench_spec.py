"""The benchmark the tests run: ``BENCHMARK.json`` as committed, plus
one cell of the tests' own, and a checkout of it for a child process.

The tests' cell, ``spmv_paper_ranks.call``, runs the paper's SpMV over 4
chips through the spmv problem's ``ranks`` option, which no committed
cell uses yet. Its configuration is ``spmv_paper``'s with ``ranks`` set
to 4, written into each checkout."""
from __future__ import annotations

import copy
import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
RANKS_CELL = "spmv_paper_ranks.call"


def with_ranks_cell(spec: dict) -> dict:
    """``spec`` with the tests' 4-chip cell, reporting what
    ``spmv_paper.call`` reports."""
    spec = copy.deepcopy(spec)
    spec["configs"].append({"name": "spmv_paper_ranks", "source": "test",
                            "file": "bench/configs/spmv_paper_ranks.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": RANKS_CELL, "config": "spmv_paper_ranks",
                              "traffic": "call", "chips": 4, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "spmv_paper.call" in m.get("workloads", ()):
            m["workloads"].append(RANKS_CELL)
    return spec


TESTED = with_ranks_cell(SPEC)
CHIPS = {w["name"]: w["chips"] for w in TESTED["workloads"]}


def reported(cell: str, kind: str, spec: dict = TESTED) -> set[str]:
    """The ``kind`` (``end_to_end`` or ``per_layer``) metrics of
    ``cell``."""
    return {m["name"] for m in spec[kind]
            if cell in m.get("workloads", [cell])}


def bench_copy(tmp: pathlib.Path, spec: dict = TESTED) -> pathlib.Path:
    """A checkout in ``tmp``: ``bench/`` without its tests, the tests'
    configuration, ``spec`` as its ``BENCHMARK.json``, and the program
    linked in; returns its root."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("_run", "__pycache__",
                                                  "tests", "testdata"))
    cfg = json.loads((BENCH / "configs" / "spmv_paper.json").read_text())
    cfg.update(ranks=4, deployment="row blocks on 4 chips, one rank each")
    del cfg["published"]
    (tmp / "bench" / "configs" / "spmv_paper_ranks.json").write_text(
        json.dumps(cfg))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp / "src").symlink_to(ROOT / "src")
    return tmp
