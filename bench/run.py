"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, builds its instance from
``--seed``, warms every shape the cell's traffic uses (from JAX's
persistent compilation cache after the first run in a checkout),
measures for ``--seconds``, checks what the timed path produced against
the benchmark's plain reference, and prints one JSON object as the last
line of standard output. ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` profiles the window and reports its per-layer
metrics, the device's busy time and a breakdown instead.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. ``--rehearse`` runs the cell at the tiny
sizes its configuration file gives, on any backend (Pallas kernels in
the interpreter on the CPU); it is for tests and is never measured.

Per-run scratch (tuning stores, the trace) lives in
``bench/_run/<pid>`` and is removed at exit.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
SCRATCH = BENCH / "_run" / str(os.getpid())     # this run's alone
sys.path.insert(0, str(BENCH))

from harness import cells, xplane  # noqa: E402
from harness.loops import LOOPS, annotate  # noqa: E402

# JAX monitoring events that mean a program was traced, compiled or
# loaded from the persistent cache.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


def log(msg: str) -> None:
    print(msg, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; never a measurement")
    ap.add_argument("--keep-trace", type=pathlib.Path, default=None,
                    help="copy the window's .xplane.pb to this path")
    return ap.parse_args(argv)


class CompileCounter:
    def __init__(self):
        from jax import monitoring

        self.count = 0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        self.count += event in COMPILE_EVENTS

    def _duration(self, event, duration, **_):
        self.count += event in COMPILE_EVENTS


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def start(workload: str, rehearse: bool):
    """The cell and the devices it runs on, with JAX started and its
    persistent compilation cache on; exits with 2 where there is no
    program, no TPU (unless ``rehearse``) or too few chips."""
    cell = cells.load_cell(workload)
    if not (ROOT / "src" / "repro").is_dir():
        _refuse(f"the program (src/repro) is not in {ROOT}")
    # The TPU runtime logs under /tmp unless told otherwise; a run writes
    # nothing outside its checkout and the directories it is given.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import repro.search  # noqa: F401  (before repro.space: import order)
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not rehearse:
        _refuse(f"needs a TPU, JAX found {devices[0].platform!r} "
                f"({len(devices)} x {devices[0].device_kind})")
    if len(devices) < cell.chips:
        _refuse(f"{cell.name} needs {cell.chips} chips, JAX found "
                f"{len(devices)}")
    log(f"cell {cell.name}: {cell.chips} x {devices[0].device_kind}, "
        f"compile cache {cache_dir}")
    return cell, devices[:cell.chips]


def _refuse(msg: str):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def main(argv=None) -> int:
    args = parse(argv)
    cell, devices = start(args.workload, args.rehearse)
    compiles = CompileCounter()
    loop, unit_class = LOOPS[cell.traffic["loop"]]
    shutil.rmtree(SCRATCH, ignore_errors=True)
    SCRATCH.mkdir(parents=True)
    try:
        unit = getattr(cells.problem(cell), unit_class)(
            cell.sizes(args.rehearse), cell.traffic, args.seed, SCRATCH,
            devices)
        setup_s = time.perf_counter() - T0
        log(f"set-up {setup_s:.3f} s")
        before = compiles.count
        window, events = measure(unit, loop, cell, args)
        log(f"window {window.seconds:.3f} s: {window.attempted} attempted, "
            f"{window.failed} failed, {window.metrics}")
        log(f"compiles in window: {compiles.count - before}")
        device = device_info(devices)
        unit.release()
        checks = unit.check(window)
        result = {"correct": (window.attempted > window.failed == 0
                              and all(v <= lim for _, v, lim in checks)),
                  "attempted": window.attempted, "failed": window.failed}
        if args.trace:
            trace = xplane.reduce(xplane.find(SCRATCH / "trace"))
            if args.keep_trace:
                shutil.copy(xplane.find(SCRATCH / "trace"), args.keep_trace)
            ctx = types.SimpleNamespace(trace=trace, events=events,
                                        window=window, unit=unit)
            values = {m["name"]: cells.reader(cell, m["name"])(ctx)
                      for m in cell.per_layer}
            device.update(busy_s=trace.busy_s(), window_s=trace.window_s)
        else:
            values = dict(window.metrics, setup_s=setup_s)
        units = {m["name"]: m["unit"]
                 for m in (cell.per_layer if args.trace else cell.end_to_end)}
        result["metrics"] = {k: {"value": values[k], "unit": u}
                             for k, u in units.items()
                             if values.get(k) is not None}
        result["device"] = device
        if args.trace:
            result["breakdown"] = {"device_ops": trace.top_ops(),
                                   "idle_gaps": trace.idle_gaps()}
        result["checks"] = {name: {"value": v, "limit": lim}
                            for name, v, lim in checks}
        for name, v, lim in checks:
            print(f"check {name}: {v!r} (limit {lim!r})", file=sys.stderr,
                  flush=True)
        print(json.dumps(result), flush=True)
        return 0
    finally:
        cleanup()


def cleanup() -> None:
    shutil.rmtree(SCRATCH, ignore_errors=True)
    try:
        SCRATCH.parent.rmdir()
    except OSError:
        pass        # another run's scratch is still there


def measure(unit, loop, cell, args):
    """The window, profiled and with the program's telemetry on when
    ``--trace 1``; returns it with the telemetry's events."""
    import jax

    if not args.trace:
        return loop(unit, args.seconds, cell.traffic, args.seed, False), []
    from repro import obs

    mem = obs.MemoryExporter()
    obs.set_current(obs.Telemetry(exporters=[mem]))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(SCRATCH / "trace"), profiler_options=opts)
    try:
        with annotate(True, "bench.window"):
            window = loop(unit, args.seconds, cell.traffic, args.seed, True)
    finally:
        jax.profiler.stop_trace()
        obs.set_current(None)
    return window, mem.events


if __name__ == "__main__":
    sys.exit(main())
