"""Bring-up check: the autotuner's measured path on one TPU chip.

Drives the main path once through the entry points a user calls —
``run_search`` -> ``make_evaluator(..., "wallclock")`` -> ``EvalStore``
-> ``distill`` — at sizes users of the paper's technique call real:

  1. **spmv_mulsum**: the paper's ELL SpMV kernel (150,000 rows, 1.5M
     non-zeros) autotuned over ``block_n``; a warm second pass must
     replay from the store with no measurement, and the candidate's
     compiled program must hold a Mosaic kernel (``tpu_custom_call``).
  2. **flash_attention**: the 4x4 block grid at smollm-360m's attention
     width (15 heads x head dim 64, seq 2048).
  3. **spmv schedules**: the paper's SpMV DAG, MCTS over measured
     token-chain runners; every schedule must reproduce the reference
     outputs.

Each phase value-gates every candidate against the kernel's reference,
measures each candidate exactly once into a fresh store, and distills a
rule report. ``--chips 4`` instead runs only the distributed SpMV over a
4-chip mesh, against the float64 oracle and one chip's ``ell_matvec``.

All phases run in this one process (a chip belongs to one process).
Every failure raises; without a TPU the script exits non-zero before
any phase. The last stdout line is the verdict:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Usage: python chip_smoke.py [--chips 1|4] [--cpu-rehearsal]
(``--cpu-rehearsal`` runs every phase at tiny sizes on any backend,
kernels in interpret mode on the CPU, and prints no verdict line.)
Stores and ``summary.json`` go to ``chip_smoke_out/`` beside this
script, cleared at the start of every run.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / "chip_smoke_out"
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

import repro.search as S  # noqa: E402  (before repro.kernels: import order)
from repro import obs  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import spmv_dag  # noqa: E402
from repro.engine import demo_spmv_impls, make_evaluator  # noqa: E402
from repro.rules import distill  # noqa: E402
from repro.space import make_space  # noqa: E402

# (real, rehearsal) sizes per phase.
SPMV = {"real": dict(n=150_000, k=10, block_values=(128, 512, 2048)),
        "tiny": dict(n=4096, k=10, block_values=(128, 512, 2048))}
FLASH = {"real": dict(batch=1, heads=15, head_dim=64, seq=2048),
         "tiny": dict(batch=1, heads=2, head_dim=64, seq=128)}
SCHED_N = {"real": 4096, "tiny": 64}
DIST = {"real": dict(n=150_000, nnz=1_500_000),
        "tiny": dict(n=4096, nnz=40_960)}


def log(msg: str) -> None:
    print(msg, flush=True)


class Phase:
    """One phase's telemetry registry and wall clock."""

    def __init__(self, name: str):
        self.name = name
        self.mem = obs.MemoryExporter()
        self.tel = obs.Telemetry(exporters=[self.mem])

    def __enter__(self) -> "Phase":
        log(f"== phase {self.name}")
        obs.set_current(self.tel)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.wall_s = time.perf_counter() - self.t0
        obs.set_current(None)

    def seconds(self, span: str) -> float | None:
        """Total seconds in ``span``; None where the phase has none (the
        schedule backend measures inside one ``engine.measure``)."""
        return self.tel.spans_by_name().get(span, {}).get("total_s")

    def compile_seconds(self) -> float | None:
        """``kernel.compile`` wall less the value gate run inside it."""
        total = self.seconds("kernel.compile")
        if total is None:
            return None
        return total - sum(e["args"].get("gate_s", 0.0)
                           for e in self.mem.events
                           if e["name"] == "kernel.compile"
                           and e["ph"] == "E")


def search_and_gate(space, strategy, store: str, budget=None, **kwargs):
    """One cold search into ``store`` (a path in the output directory,
    cleared at start): every candidate measured once and value-gated."""
    with make_evaluator(space, "wallclock", store_path=store,
                        **kwargs) as ev:
        res = S.run_search(space, strategy, budget=budget, evaluator=ev)
        n = len(res.schedules)
        if res.cache_misses != n or res.store_hits != 0:
            raise AssertionError(
                f"cold search measured {res.cache_misses} of {n} "
                f"candidates ({res.store_hits} store hits)")
        if ev.n_checked != n:
            raise AssertionError(
                f"{ev.n_checked} of {n} candidates passed the gate")
    return res


def print_report(res, space) -> dict:
    report = distill(res)
    best, best_t = res.best()
    log(f"best {space.describe(best)}: {best_t * 1e6:.3f} us")
    log(report.render(top_k=2).rstrip())
    return report.summary()


def phase_spmv(size: str) -> dict:
    import jax

    from repro.kernels.spmv.ops import ell_matvec

    cfg = SPMV[size]
    with Phase("spmv_mulsum") as ph:
        space = make_space("spmv_mulsum", **cfg)
        store = str(OUT / "spmv_mulsum.evalstore")
        n_cand = space.n_candidates()
        res = search_and_gate(space, S.ExhaustiveSearch(space), store)
        log(f"{n_cand} candidates measured and gated "
            f"(n={cfg['n']}, k={cfg['k']})")
        with make_evaluator(space, "wallclock", store_path=store) as ev:
            warm = S.run_search(space, S.ExhaustiveSearch(space),
                                budget=None, evaluator=ev)
        if (warm.cache_misses, warm.store_hits) != (0, n_cand) \
                or warm.times != res.times:
            raise AssertionError(
                f"warm pass: {warm.cache_misses} misses, "
                f"{warm.store_hits} store hits of {n_cand}")
        log(f"warm pass: 0 misses, {warm.store_hits} store hits")
        summary = print_report(res, space)

        # The candidate's own program (same shapes and static args as
        # the measured call) must hold a compiled Mosaic kernel.
        f32 = jax.ShapeDtypeStruct((cfg["n"], cfg["k"]), np.float32)
        i32 = jax.ShapeDtypeStruct((cfg["n"], cfg["k"]), np.int32)
        x = jax.ShapeDtypeStruct((cfg["n"],), np.float32)
        bn = cfg["block_values"][0]
        t0 = time.perf_counter()
        hlo = ell_matvec.lower(f32, i32, x, block_n=bn).compile().as_text()
        hlo_s = time.perf_counter() - t0
        kernel = "tpu_custom_call" in hlo
        if not kernel and jax.default_backend() != "cpu":
            raise AssertionError(
                f"block_n={bn}: no tpu_custom_call in the compiled "
                "candidate — the kernel ran in the interpreter")
        log(f"block_n={bn} compiled HLO holds tpu_custom_call: {kernel} "
            f"({hlo_s:.3f} s to compile again)")
    return _phase_row(ph, n_cand, summary, res,
                      warm_store_hits=warm.store_hits,
                      tpu_custom_call=kernel)


def phase_flash(size: str) -> dict:
    cfg = FLASH[size]
    with Phase("flash_attention") as ph:
        space = make_space("flash_attention", **cfg)
        store = str(OUT / "flash_attention.evalstore")
        res = search_and_gate(space, S.ExhaustiveSearch(space), store)
        ref = np.asarray(space.runner.reference())
        err = max(float(np.abs(np.asarray(
            space.runner.build(space.as_dict(c))()) - ref).max())
            for c in res.schedules)
        log(f"{len(res.schedules)} candidates measured and gated "
            f"({cfg}); largest |kernel - reference| {err:.3e} "
            f"(gate atol {space.runner.atol}, max |reference| "
            f"{np.abs(ref).max():.3e})")
        summary = print_report(res, space)
    return _phase_row(ph, len(res.schedules), summary, res,
                      max_abs_err=err)


def phase_schedules(size: str) -> dict:
    g = spmv_dag()
    impls, env = demo_spmv_impls(g, n=SCHED_N[size])
    with Phase("spmv_schedules") as ph:
        store = str(OUT / "spmv_schedules.evalstore")
        res = search_and_gate(g, S.MCTSSearch(g, 2, seed=0), store,
                              budget=24, impls=impls, env=env)
        log(f"{len(res.schedules)} schedules measured; every one "
            f"reproduced the reference outputs (n={SCHED_N[size]})")
        summary = print_report(res, S.ExhaustiveSearch(g, 2).space)
    return _phase_row(ph, len(res.schedules), summary, res)


def _phase_row(ph: Phase, n: int, summary: dict, res, **extra) -> dict:
    row = {"phase": ph.name, "candidates": n,
           "wall_s": ph.wall_s,
           "measure_s": ph.seconds("engine.measure"),
           "compile_s": ph.compile_seconds(),
           "timing_s": ph.seconds("kernel.timing"),
           "distill_s": ph.seconds("rules.distill"),
           "best_us": res.best()[1] * 1e6,
           "n_classes": summary["n_classes"],
           "n_rulesets": summary["n_rulesets"], **extra}
    log("phase " + json.dumps(row))
    return row


def _time(fn, *args, repeats: int = 20) -> tuple[float, float, object]:
    """(first call incl. compile, median steady call) seconds, output."""
    import jax

    t0 = time.perf_counter()
    y = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return first, statistics.median(times), y


def phase_distributed(size: str) -> dict:
    """The paper's multi-rank SpMV over 4 chips vs one chip and the
    float64 oracle."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.kernels.spmv.ops import ell_matvec
    from repro.spmv.distributed import make_distributed_spmv
    from repro.spmv.matrix import band_matrix, partition, stack_partitions

    cfg = DIST[size]
    with Phase("distributed_spmv") as ph:
        a = band_matrix(cfg["n"], cfg["nnz"])
        x = np.random.default_rng(1).standard_normal(
            cfg["n"]).astype(np.float32)
        oracle = a.matvec(x)
        scale = np.abs(oracle).max()
        st = stack_partitions(partition(a, 4))
        args = (st["local_vals"], st["local_cols"], st["remote_vals"],
                st["remote_cols"], x.reshape(4, -1))
        mesh = Mesh(np.array(jax.devices()[:4]), ("ranks",))
        rows = []
        variants = [("overlap_local=True, kernel", True, True),
                    ("overlap_local=False, kernel", True, False),
                    ("overlap_local=True, use_kernel=False", False, True)]
        for label, use_kernel, overlap in variants:
            run = make_distributed_spmv(mesh, use_kernel=use_kernel,
                                        overlap_local=overlap)
            first, med, y = _time(run, *args)
            devices = sorted({str(s.device) for s in y.addressable_shards})
            err = float(np.abs(np.asarray(y).reshape(-1)
                               - oracle).max() / scale)
            log(f"4 ranks, {label}: first call {first:.3f} s, median "
                f"{med * 1e6:.1f} us, rel err {err:.2e}, shards on "
                f"{devices}")
            if len(devices) != 4 or err >= 1e-5:
                raise AssertionError(
                    f"{label}: shards on {devices}, rel err {err:.2e}")
            rows.append({"variant": label, "first_s": first,
                         "median_us": med * 1e6, "rel_err": err})
        one = jax.devices()[0]
        vals, cols, xv = (jax.device_put(jnp.asarray(v), one)
                          for v in (a.vals, a.cols, x))
        first, med, y = _time(ell_matvec, vals, cols, xv)
        err = float(np.abs(np.asarray(y) - oracle).max() / scale)
        log(f"1 chip, ell_matvec on the whole matrix: first call "
            f"{first:.3f} s, median {med * 1e6:.1f} us, rel err "
            f"{err:.2e}")
        if err >= 1e-5:
            raise AssertionError(f"one-chip ell_matvec rel err {err:.2e}")
        rows.append({"variant": "1 chip ell_matvec", "first_s": first,
                     "median_us": med * 1e6, "rel_err": err})
    row = {"phase": ph.name, "wall_s": ph.wall_s, "runs": rows}
    log("phase " + json.dumps(row))
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the three tuning phases on one chip; 4: "
                         "only the distributed SpMV over a 4-chip mesh")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on any backend; prints no verdict")
    args = ap.parse_args(argv)
    size = "tiny" if args.cpu_rehearsal else "real"

    enable_compile_cache()
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"== phase device: {device}")
    if device["platform"] != "tpu" and not args.cpu_rehearsal:
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found "
                         f"platform {device['platform']!r} "
                         f"({device['kind']}, {device['count']} devices)")
    if device["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, found {device['count']}")

    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)
    if args.chips == 4:
        phases = [phase_distributed(size)]
    else:
        phases = [phase_spmv(size), phase_flash(size),
                  phase_schedules(size)]
    (OUT / "summary.json").write_text(json.dumps(
        {"device": device, "size": size, "phases": phases}, indent=1))
    if args.cpu_rehearsal:
        log(f"cpu rehearsal passed on {device}")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
