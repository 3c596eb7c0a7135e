"""Find a cell's pieces by name: nothing here lists them.

``BENCHMARK.json`` names the cell's configuration and traffic. The
configuration is ``bench/configs/<config>.json`` (its ``file``), which
names its problem, ``bench/problems/<problem>.py``; the traffic is
``bench/traffic/<traffic>.json``, which names its loop; each per-layer
metric is read by ``bench/metrics/<metric>.py``. A new cell, traffic
mix or metric is a new file and a new entry, never an edit here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re

BENCH = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    root: pathlib.Path

    def sizes(self, rehearse: bool) -> dict:
        """The configuration as run; ``rehearse`` overlays its tiny
        CPU sizes."""
        sizes = {k: v for k, v in self.config.items() if k != "rehearsal"}
        if rehearse:
            sizes.update(self.config.get("rehearsal", {}))
        return sizes


def load_cell(name: str, root: pathlib.Path | None = None) -> Cell:
    bench_dir = BENCH if root is None else pathlib.Path(root) / "bench"
    root = bench_dir.parent
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in reported]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer, root)


def load_module(path: pathlib.Path):
    """Import one file of the benchmark by its path."""
    mod_name = "bench_" + re.sub(r"\W", "_", path.relative_to(
        path.parents[1]).with_suffix("").as_posix())
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def problem(cell: Cell):
    return load_module(cell.root / "bench" / "problems"
                       / f"{cell.config['problem']}.py")


def reader(cell: Cell, metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    return load_module(cell.root / "bench" / "metrics" / f"{metric}.py").read
