"""One tuning job through the program's own entry points, from
``make_space`` to the rendered rule report:

``make_space`` -> ``make_evaluator(space, "wallclock", store_path=<a
fresh file>)`` -> ``run_search(ExhaustiveSearch)`` -> ``distill`` ->
``render``.

Where the job's outputs are kept for the check, each candidate's first
output (the one the program's own gate compared) is held as the
program returned it; nothing is recomputed.
"""
from __future__ import annotations

import dataclasses
import os
import time

from harness.loops import annotate


@dataclasses.dataclass
class Job:
    index: int
    space_s: float
    n_candidates: int
    n_measured: int
    n_gated: int
    best_measured: bool
    best: dict
    outputs: dict | None = None    # candidate -> output, when kept


def _keep_first_outputs(space, outputs: dict) -> None:
    runner = space.runner
    build = runner.build

    def build_kept(params: dict):
        run = build(params)
        key = tuple(sorted(params.items()))

        def run_kept():
            out = run()
            outputs.setdefault(key, out)
            return out
        return run_kept

    runner.build = build_kept


def run_job(index: int, space_name: str, space_kwargs: dict, store_path,
            *, keep: bool, traced: bool) -> Job:
    import repro.search as S  # before repro.space: the program's import order
    from repro.engine import make_evaluator
    from repro.rules import distill
    from repro.space import make_space

    t0 = time.perf_counter()
    with annotate(traced, "bench.space"):
        space = make_space(space_name, **space_kwargs)
    space_s = time.perf_counter() - t0
    outputs: dict | None = {} if keep else None
    if keep:
        _keep_first_outputs(space, outputs)
    try:
        with annotate(traced, "bench.search"):
            with make_evaluator(space, "wallclock",
                                store_path=os.fspath(store_path)) as ev:
                res = S.run_search(space, S.ExhaustiveSearch(space),
                                   budget=None, evaluator=ev)
                n_gated = ev.n_checked
        with annotate(traced, "bench.distill"):
            distill(res).render()
    finally:
        if os.path.exists(store_path):
            os.remove(store_path)
    best = tuple(res.best()[0])
    return Job(index, space_s, space.n_candidates(), res.cache_misses,
               n_gated, best in {tuple(s) for s in res.schedules},
               space.as_dict(best), outputs)


def exact_checks(jobs: list[Job]) -> list[tuple[str, float, float]]:
    """What every job in the window must show, each with the limit 0:
    every candidate measured and gated, the best one among them."""
    return [
        ("unmeasured", sum(j.n_candidates - j.n_measured for j in jobs), 0),
        ("ungated", sum(j.n_candidates - j.n_gated for j in jobs), 0),
        ("best_unmeasured", sum(not j.best_measured for j in jobs), 0),
    ]
