"""Param-space wall-clock backend: measure the repo's own kernels.

The counterpart of :mod:`repro.engine.wallclock` for
:class:`~repro.space.params.ParamSpace` candidates: instead of
rendering a schedule into a token-chain runner, each candidate's
parameter assignment is handed to the space's
:class:`~repro.space.params.KernelRunner` (``build(params)`` → a
zero-argument jitted callable on a fixed problem instance). Everything
search-visible — memo cache, three-way hit/miss meters, persistent
:class:`~repro.engine.store.EvalStore` warm starts, noise seeding,
salvage — is inherited from :class:`~repro.engine.base.EvaluatorBase`
unchanged, so a kernel autotune run is driven, deduped, budgeted, and
warm-started exactly like a schedule search.

Measurement protocol per canonical-unique candidate:

  1. **compile phase** — build every candidate's runner and run it
     once (``block_until_ready``), asserting value correctness against
     ``runner.reference()`` via the shared wallclock gate. With
     ``compile_mode="batch"`` (the default) this phase covers the
     *whole batch before any timing starts*, so XLA compile time
     amortizes the way the vectorized backend amortizes Python
     dispatch — timings never absorb a neighbor's compile;
     ``compile_mode="per_candidate"`` interleaves (the naive loop,
     kept for the BENCH comparison).
  2. **timing phase** — ``warmup - 1`` further calls, then ``repeats``
     timed calls (``block_until_ready`` inside the stopwatch), record
     the median.

The value gate compares each candidate's first output with the
reference where both are. The reference is computed once per
evaluator and stays as the runner returned it. Where every output is a
float32 ``jax.Array`` of its reference's shape, and the reference is
one too, the tolerance check runs on the device
(:func:`repro.engine.wallclock.outputs_close_on_device`) and only its
int32 counts come back; the device is stricter than NumPy, never
looser, so a non-zero count is handed to the host. Otherwise, and
after such a count, both sides are copied to the host and
:func:`~repro.engine.wallclock.assert_outputs_close` decides, raising
with the candidate named.

Spans (:mod:`repro.obs`): ``kernel.compile`` and ``kernel.timing``
around the phases, each with the ``gate_s`` spent in it; one
``kernel.build`` per candidate around its build and first call; and
the gate in three parts, ``kernel.reference`` (once per evaluator),
``kernel.compare`` (the tolerance check, with ``on`` set to
``"device"`` or ``"host"``) and ``kernel.fetch`` (what comes to the
host: the device check's counts, or the outputs for the host's
check). No timed call is inside a span of its own.

The store fingerprint keys on the measuring device (platform, device
kind and count — :func:`repro.engine.wallclock.device_identity`) in
addition to the timing protocol: a CPU interpret-mode sweep and a TPU
sweep of the same grid, or sweeps on two chip generations, are
different experiments and must never warm-start each other.
"""
from __future__ import annotations

import statistics
import time
from typing import Sequence

import numpy as np

from repro import obs
from repro.core.costmodel import Machine
from repro.engine.base import EvaluatorBase
from repro.engine.wallclock import (_as_output_map, _output_map,
                                    assert_outputs_close,
                                    comparable_on_device, device_identity,
                                    outputs_close_on_device)
from repro.space.params import ParamSpace


class KernelWallclockEvaluator(EvaluatorBase):
    """Wall-clock evaluation of a :class:`ParamSpace` with a runner."""

    backend = "wallclock"

    def __init__(self, space: ParamSpace,
                 machine: Machine | None = None,
                 noise_sigma: float = 0.0, noise_seed: int = 0, *,
                 repeats: int = 5, warmup: int = 1,
                 check_values: bool = True, rtol: float = 1e-4,
                 atol: float | None = None, compile_mode: str = "batch",
                 **base_kwargs):
        super().__init__(space, machine, noise_sigma, noise_seed,
                         **base_kwargs)
        runner = getattr(self.space, "runner", None)
        if runner is None:
            raise ValueError(
                f"design space {self.space.name!r} has no KernelRunner "
                "attached; the param-space wallclock backend needs "
                "runner= on the ParamSpace (build + reference)")
        if compile_mode not in ("batch", "per_candidate"):
            raise ValueError(
                f"compile_mode must be 'batch' or 'per_candidate', "
                f"got {compile_mode!r}")
        self.runner = runner
        self.repeats = max(1, repeats)
        self.warmup = max(1, warmup)
        self.check_values = check_values
        # Python floats, so that NumPy's check is in the outputs' own
        # precision, as the device's is. The absolute tolerance is the
        # kernel's own (KernelRunner.atol) unless the caller overrides it.
        self.rtol = float(rtol)
        self.atol = float(runner.atol if atol is None else atol)
        self.compile_mode = compile_mode
        self.n_checked = 0
        self._reference: dict | None = None

    def _objective_key(self) -> str:
        """Kernel wall clock is device-specific on top of being
        protocol-specific: CPU interpret-mode and TPU sweeps of the
        same grid must never share store entries. (``compile_mode`` is
        deliberately excluded — it moves compile cost around but the
        timed quantity is the same.)"""
        return (f"kernel-wallclock:{device_identity()}:"
                f"repeats={self.repeats}:warmup={self.warmup}")

    # -- reference outputs (computed lazily, once) -------------------------
    def _reference_outputs(self) -> dict:
        if self._reference is None:
            import jax

            with obs.span("kernel.reference") as sp:
                self._reference = jax.block_until_ready(
                    _output_map(self.runner.reference()))
                sp.set(bytes=_nbytes(self._reference))
        return self._reference

    def _check(self, out, candidate) -> None:
        # The gate in three spans: the reference (its first use only),
        # the tolerance check, and what it brings to the host.
        ref = self._reference_outputs()
        got = _output_map(out)
        if comparable_on_device(got, ref):
            with obs.span("kernel.compare", bytes=_nbytes(got),
                          on="device"):
                failing = outputs_close_on_device(
                    got, ref, rtol=self.rtol, atol=self.atol
                ).block_until_ready()
            with obs.span("kernel.fetch", bytes=failing.nbytes):
                passed = not np.asarray(failing).any()
            if passed:
                self.n_checked += 1
                return
        # The host's check: the only one that raises.
        with obs.span("kernel.fetch") as sp:
            got, ref = _as_output_map(got), _as_output_map(ref)
            sp.set(bytes=_nbytes(got))
        with obs.span("kernel.compare", bytes=_nbytes(got), on="host"):
            assert_outputs_close(
                got, ref, rtol=self.rtol, atol=self.atol,
                context=(f" for candidate "
                         f"({self.space.describe(candidate)}) — kernel "
                         "output failed the value-correctness gate"))
        self.n_checked += 1

    def _measure_batch(self, candidates: Sequence,
                       encoded: np.ndarray | None = None) -> list[float]:
        import jax

        out: list[float] = []
        # The compile-vs-gate-vs-timing split per miss batch: gate wall
        # is accumulated inside whichever phase runs the value check so
        # the compile/timing spans report pure XLA-compile and pure
        # stopwatch time. Telemetry is observational only — the
        # stopwatch readings that become results never include span
        # bookkeeping (spans wrap whole loops, not timed calls).
        gate_s = 0.0

        def _gated_check(result, cand):
            nonlocal gate_s
            g0 = time.perf_counter()
            self._check(result, cand)
            gate_s += time.perf_counter() - g0

        def _first_call(cand, run=None):
            # kernel.build: the runner's build where it is still to
            # come, then the first call: the trace, the compile (or the
            # load from the persistent cache) and the first execution.
            with obs.span("kernel.build") as sp:
                if run is None:
                    run = self.runner.build(self.space.as_dict(cand))
                result = jax.block_until_ready(run())
                sp.set(bytes=_nbytes(result))
            return run, result

        try:
            runs = []
            with obs.span("kernel.compile", n=len(candidates),
                          mode=self.compile_mode) as compile_span:
                for cand in candidates:
                    if self.compile_mode == "batch":
                        # Compile + gate the whole batch ahead of timing.
                        run, result = _first_call(cand)
                        if self.check_values:
                            _gated_check(result, cand)
                    else:
                        run = self.runner.build(self.space.as_dict(cand))
                    runs.append(run)
                compile_span.set(gate_s=gate_s)
            compile_gate_s = gate_s
            with obs.span("kernel.timing", n=len(candidates),
                          repeats=self.repeats) as timing_span:
                for cand, run in zip(candidates, runs):
                    if self.compile_mode == "per_candidate":
                        _, result = _first_call(cand, run)
                        if self.check_values:
                            _gated_check(result, cand)
                    for _ in range(self.warmup - 1):
                        jax.block_until_ready(run())
                    times = []
                    for _ in range(self.repeats):
                        t0 = time.perf_counter()
                        jax.block_until_ready(run())
                        times.append(time.perf_counter() - t0)
                    out.append(statistics.median(times))
                timing_span.set(gate_s=gate_s - compile_gate_s)
        finally:
            # Same salvage contract as the executor backend: if a
            # candidate fails the value gate mid-batch, the timings
            # already paid for are banked (memo cache + store) and
            # metered as misses on their next lookup.
            if encoded is not None and len(out) < len(candidates):
                self._salvage_partial(encoded[:len(out)], out)
        return out


def _nbytes(out) -> int:
    """Bytes of a runner's outputs: an array, or a mapping or sequence
    of arrays."""
    return sum(int(getattr(v, "nbytes", 0))
               for v in _output_map(out).values())
