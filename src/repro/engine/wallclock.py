"""Wall-clock evaluation backend: the jitted token-chain runner as the
search objective.

:mod:`repro.core.executor` renders a schedule as a real JAX program
whose token chains reproduce the CUDA stream/event semantics; this
backend routes it through the evaluator contract, so *measured* time
shares the memo cache, dedup, and ``sim_budget`` accounting that the
analytic backends use — a search strategy cannot tell it is optimizing
wall clock instead of the machine model.

Per canonical-unique schedule it:

  1. builds and jits the runner (compile time excluded from timing);
  2. runs ``warmup`` calls, asserting **value correctness** on the
     first: every output must match the reference outputs computed
     once from a canonical (topological, single-stream) schedule —
     the sync insertion must make any valid schedule compute the same
     values (the executor's schedule-invariance property);
  3. times ``repeats`` calls (``block_until_ready`` inside the stopwatch
     — JAX dispatch is async) and records the **median**, the usual
     robust estimator for multimodal timing jitter.

On a CPU container the measured numbers rank schedules by Python/XLA
dispatch cost rather than TPU overlap quality — the point on this
hardware is the end-to-end plumbing (real measurements driving
``run_search``) and the correctness gate; on a TPU host the same class
is the paper's wall-clock objective.

:func:`demo_spmv_impls` supplies a tiny CPU-sized implementation set
for the coarse SpMV DAG so smoke tests and examples can run an
end-to-end wall-clock search anywhere.
"""
from __future__ import annotations

import functools
import statistics
import time
from typing import Callable, Mapping, Sequence

import numpy as np

from repro.core.costmodel import Machine
from repro.core.dag import BoundOp, Graph, OpKind, Schedule
from repro.engine.base import EvaluatorBase


def reference_schedule(graph: Graph) -> Schedule:
    """A canonical valid schedule: topological order, all on stream 0."""
    return Schedule(tuple(
        BoundOp(n, 0 if graph.ops[n].kind is OpKind.GPU else None)
        for n in graph.topological_order()))


def device_identity() -> str:
    """The measuring hardware, as it goes into a wall-clock store key.

    Platform, device kind and device count: ``jax.default_backend()``
    alone says "tpu" for every chip generation, and a store filled on
    one machine must never replay as a measurement of another.
    """
    import jax

    devices = jax.devices()
    return (f"platform={devices[0].platform}:"
            f"kind={devices[0].device_kind}:count={len(devices)}")


def _output_map(out) -> dict:
    """Name a runner's outputs (mapping / sequence / single array),
    leaving each where it is."""
    if isinstance(out, Mapping):
        return {str(k): v for k, v in out.items()}
    if isinstance(out, (tuple, list)):
        return {f"out{i}": v for i, v in enumerate(out)}
    return {"out": out}


def _as_output_map(out) -> dict[str, np.ndarray]:
    """A runner's outputs as named numpy arrays, for comparison on the
    host."""
    return {k: np.asarray(v) for k, v in _output_map(out).items()}


def assert_outputs_close(got, ref, *, rtol: float, atol: float = 0.0,
                         context: str = "") -> None:
    """The wallclock value-correctness gate: every reference output
    must be reproduced within tolerance.

    Shared by the schedule-space executor backend (outputs are the
    token-chain environment) and the param-space kernel backend
    (outputs are whatever the kernel returns); ``context`` names the
    failing candidate in the assertion message.
    """
    ref_map = _as_output_map(ref)
    got_map = _as_output_map(got)
    missing = sorted(set(ref_map) - set(got_map))
    if missing:
        raise AssertionError(
            f"candidate is missing reference output(s) {missing}"
            f"{context}")
    for k, r in ref_map.items():
        np.testing.assert_allclose(
            got_map[k], r, rtol=rtol, atol=atol,
            err_msg=f"output {k!r} diverged{context}")


def comparable_on_device(got, ref) -> bool:
    """Whether :func:`outputs_close_on_device` may judge ``got`` against
    ``ref``: every reference output is a float32 ``jax.Array``, and
    ``got`` has a float32 ``jax.Array`` of the same shape under its
    name. Anything else (a NumPy reference, a missing output, another
    shape or dtype) is for :func:`assert_outputs_close` on the host.
    """
    import jax
    import jax.numpy as jnp

    got, ref = _output_map(got), _output_map(ref)

    def on_device(a) -> bool:
        return isinstance(a, jax.Array) and a.dtype == jnp.float32

    return all(k in got and on_device(r) and on_device(got[k])
               and got[k].shape == r.shape for k, r in ref.items())


def _failing(g, r, rtol, atol):
    """The elements of ``g`` the device cannot pass against ``r``.

    NumPy's ``isclose(g, r, rtol, atol, equal_nan=True)``, which is what
    ``np.testing.assert_allclose`` tests, in float32: ``|g - r| <= atol
    + rtol*|r|`` where ``r`` is finite, or ``g == r``, or both NaN. The
    device may round the bound differently (a fused multiply-add) and
    flushes subnormals to zero, so the check is stricter than NumPy's,
    never looser: the bound is taken one ulp down and must be normal,
    and a subnormal in either array fails here and is left to the host.
    """
    import jax.numpy as jnp
    from jax import lax

    def subnormal(x):
        bits = lax.bitcast_convert_type(x, jnp.uint32)
        return ((bits & 0x7F800000) == 0) & ((bits & 0x007FFFFF) != 0)

    bound = (jnp.asarray(atol, r.dtype)
             + jnp.asarray(rtol, r.dtype) * jnp.abs(r))
    bound = jnp.nextafter(bound, jnp.asarray(-jnp.inf, r.dtype))
    close = ((jnp.abs(g - r) <= bound)
             & (bound >= jnp.finfo(r.dtype).tiny) & jnp.isfinite(r))
    ok = close | (g == r) | (jnp.isnan(g) & jnp.isnan(r))
    return ~ok | subnormal(g) | subnormal(r)


def _count_failing(got: dict, ref: dict, rtol, atol):
    import jax.numpy as jnp

    return jnp.stack([jnp.sum(_failing(got[k], r, rtol, atol),
                              dtype=jnp.int32) for k, r in ref.items()])


@functools.cache
def _count_failing_jit():
    import jax

    return jax.jit(_count_failing, static_argnames=("rtol", "atol"))


def outputs_close_on_device(got, ref, *, rtol: float, atol: float = 0.0):
    """The value gate's tolerance check, run where the outputs are.

    ``got`` and ``ref`` must pass :func:`comparable_on_device`. Returns
    an int32 ``jax.Array`` with the number of elements of each reference
    output, in the order of their sorted names, that the device cannot
    pass (:func:`_failing`): all zeros only where
    :func:`assert_outputs_close` passes too. One jitted reduction over
    the outputs, compiled once per set of shapes and tolerances (a
    space has one pair); the tolerances are constants of it, so a call
    copies nothing to the device. A non-zero count is not a verdict:
    the host decides it with :func:`assert_outputs_close`.
    """
    got, ref = _output_map(got), _output_map(ref)
    return _count_failing_jit()({k: got[k] for k in ref}, ref,
                                rtol=float(rtol), atol=float(atol))


class ExecutorEvaluator(EvaluatorBase):
    """Evaluation backend measuring jitted token-chain runners.

    ``impls`` maps op names to :func:`repro.core.executor.op_impl`
    implementations; ``env`` is the initial value environment. Ops
    without an impl (start/end/pure-control) are skipped by the runner.
    ``check_values=False`` disables the output assertion (e.g. for
    intentionally stochastic kernels).
    """

    backend = "wallclock"

    def __init__(self, graph: Graph, machine: Machine | None = None,
                 noise_sigma: float = 0.0, noise_seed: int = 0, *,
                 impls: Mapping[str, Callable] | None = None,
                 env: Mapping | None = None,
                 repeats: int = 5, warmup: int = 1,
                 check_values: bool = True, rtol: float = 1e-5,
                 **base_kwargs):
        if impls is None or env is None:
            raise ValueError(
                "wallclock backend needs impls= (op implementations) "
                "and env= (initial values); see engine/README.md")
        super().__init__(graph, machine, noise_sigma, noise_seed,
                         **base_kwargs)
        if self.graph is None:
            raise TypeError(
                "the executor wallclock backend renders schedules of a "
                f"Graph; design space {self.space.name!r} has no graph "
                "(parameter spaces evaluate through the param-space "
                "wallclock runner — attach a KernelRunner and use "
                "make_evaluator)")
        self.impls = dict(impls)
        self.env = dict(env)
        self.repeats = max(1, repeats)
        self.warmup = max(1, warmup)
        self.check_values = check_values
        self.rtol = rtol
        self.n_checked = 0
        self._reference: dict | None = None

    def _objective_key(self) -> str:
        """Measured wall-clock time is machine- and protocol-specific:
        never share store entries with the analytic family, with another
        device (:func:`device_identity`), nor with a differently-
        configured timing protocol. Distinct impl/env sets on the same
        graph should be disambiguated with ``store_tag=``.
        """
        return (f"wallclock:{device_identity()}:"
                f"repeats={self.repeats}:warmup={self.warmup}")

    # -- reference outputs (computed lazily, once) -------------------------
    def _reference_outputs(self) -> dict:
        if self._reference is None:
            from repro.core.executor import build_runner
            ref = build_runner(self.graph, reference_schedule(self.graph),
                               self.impls)(self.env)
            self._reference = {k: np.asarray(v) for k, v in ref.items()
                               if k not in self.env}
        return self._reference

    def _check(self, out: Mapping, schedule: Schedule) -> None:
        assert_outputs_close(
            {k: out[k] for k in self._reference_outputs()},
            self._reference_outputs(), rtol=self.rtol,
            context=(f" under schedule "
                     f"{[str(i) for i in schedule.items]} — sync "
                     "insertion failed to enforce a dependency"))
        self.n_checked += 1

    def _measure_batch(self, schedules: Sequence[Schedule],
                       encoded: np.ndarray | None = None) -> list[float]:
        import jax

        from repro.core.executor import build_runner
        out: list[float] = []
        try:
            for sched in schedules:
                run = jax.jit(build_runner(self.graph, sched,
                                           self.impls))
                result = jax.block_until_ready(run(self.env))
                if self.check_values:
                    self._check(result, sched)
                for _ in range(self.warmup - 1):
                    jax.block_until_ready(run(self.env))
                times = []
                for _ in range(self.repeats):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(self.env))
                    times.append(time.perf_counter() - t0)
                out.append(statistics.median(times))
        finally:
            # Measurements here are expensive (jit compile + repeats);
            # if a later schedule fails the value gate, salvage the
            # completed ones into the memo cache and persistent store
            # so a retry doesn't re-pay them. The base class remembers
            # them as salvaged: their first post-salvage lookup counts
            # as a miss (the measurement was paid), not a free hit.
            if encoded is not None and len(out) < len(schedules):
                self._salvage_partial(encoded[:len(out)], out)
        return out


def demo_spmv_impls(graph: Graph, n: int = 16, seed: int = 0
                    ) -> tuple[dict, dict]:
    """(impls, env) realizing the coarse SpMV DAG with tiny dense ops.

    Small enough that a wall-clock smoke search finishes in seconds on
    CPU; the dataflow (pack -> send -> recv-wait -> remote multiply)
    matches the DAG, so the value-correctness gate is meaningful. The
    matrices travel in ``env`` as runner inputs: closed over, they
    would be baked into every schedule's executable as constants
    (2 x 64 MiB at ``n=4096``), and each compile would pay for them.
    """
    import jax.numpy as jnp

    from repro.core.executor import op_impl

    rng = np.random.default_rng(seed)
    AL = jnp.asarray(rng.normal(size=(n, n)), jnp.float32)
    AR = jnp.asarray(rng.normal(size=(n, n)), jnp.float32)
    xL = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    impls = {
        "Pack": op_impl(lambda x: x * 1.0, ["xL"], ["sendbuf"]),
        "PostSend": op_impl(lambda b: b, ["sendbuf"], ["wire"]),
        "PostRecv": op_impl(lambda: jnp.zeros((n,), jnp.float32),
                            [], ["recvbuf"]),
        "WaitSend": op_impl(lambda w: w, ["wire"], ["sent"]),
        "WaitRecv": op_impl(lambda w, r: w + r, ["wire", "recvbuf"],
                            ["xR"]),
        "yL": op_impl(lambda a, x: a @ x, ["AL", "xL"], ["yL"]),
        "yR": op_impl(lambda a, x: a @ x, ["AR", "xR"], ["yR"]),
    }
    return impls, {"xL": xL, "AL": AL, "AR": AR}
