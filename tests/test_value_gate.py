"""The wall-clock value gate's tolerance check on the device.

``outputs_close_on_device`` counts, per output, the elements the device
cannot pass; the evaluator hands any non-zero count to the host's
``np.testing.assert_allclose``. These tests plant float32 outputs at and
around the tolerance bound, NaNs, infinities and subnormals, and check
that the device never passes an element NumPy rejects, and that the gate
as a whole (device, then host on a count) decides exactly as NumPy does.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.engine as E
from repro import obs
from repro.engine import wallclock
from repro.engine.wallclock import (comparable_on_device,
                                    outputs_close_on_device)
from repro.space import KernelRunner, ParamSpace

F32 = np.float32
# References from zero through the tolerances' scale to far above it,
# so that both atol and rtol*|r| set the bound somewhere.
REFS = F32([0.0, 2.5e-7, -7.5e-7, 3e-3, -0.0172, 1.0, -3.7, 1234.5,
            -6.5e5])
TOLERANCES = [(1e-4, 1e-6), (1e-4, 2e-2)]   # spmv and pack; flash


def _bound(r, rtol, atol):
    """NumPy's float32 bound: atol + rtol*|r|, each step rounded."""
    return F32(atol) + F32(rtol) * np.abs(r)


def _near_bound(r, rtol, atol):
    """Outputs g near r + bound, a few float32 steps either way, with
    their distance |g - r| as NumPy computes it."""
    g = F32(r + _bound(r, rtol, atol))
    gs = {g}
    for direction in (np.inf, -np.inf):
        x = g
        for _ in range(4):
            x = np.nextafter(x, F32(direction))
            gs.add(x)
    return [(x, np.abs(x - r)) for x in sorted(gs)]


def _planted(case, rtol, atol):
    """(got, ref) float32 arrays whose every element is one ``case``."""
    g, r = [], []
    for rv in REFS:
        bound = _bound(rv, rtol, atol)
        near = _near_bound(rv, rtol, atol)
        if case == "at":                  # |g - r| == bound exactly
            hits = [x for x, d in near if d == bound]
        elif case == "ulp_inside":        # the largest |g - r| below it
            below = [(d, x) for x, d in near if d < bound]
            hits = [max(below)[1]] if below else []
        elif case == "ulp_outside":       # the smallest |g - r| above it
            hits = [min((d, x) for x, d in near if d > bound)[1]]
        elif case == "well_inside":
            hits = [F32(rv + bound / 4)] if bound / 4 > 0 else []
        else:
            continue
        g += hits[:1]
        r += [rv] * len(hits[:1])
    if case == "nan_both":
        g, r = [np.nan, np.nan], [np.nan, np.nan]
    elif case == "nan_one":
        g, r = [np.nan, 1.0], [1.0, np.nan]
    elif case == "inf_both":
        g, r = [np.inf, -np.inf], [np.inf, -np.inf]
    elif case == "inf_opposite":
        g, r = [np.inf, -np.inf], [-np.inf, np.inf]
    elif case == "subnormal":
        g, r = [1e-40, -3e-41, 0.0], [2e-40, 3e-41, 1e-44]
    assert len(g) >= 2, case
    return F32(g), F32(r)


CASES = ["at", "ulp_inside", "ulp_outside", "well_inside", "nan_both",
         "nan_one", "inf_both", "inf_opposite", "subnormal"]
# Honest outputs the device must pass itself, with no host fallback.
DEVICE_PASSES = {"well_inside", "nan_both", "inf_both"}


def _shaped(structure, g, r):
    """(outputs, reference) as a runner returns them: one array, a
    tuple or a mapping; the planted array is always the first output by
    sorted name, beside an honest output equal to its reference."""
    g, r = jnp.asarray(g), jnp.asarray(r)
    honest = jnp.linspace(-2.0, 3.0, 5, dtype=jnp.float32)
    if structure == "array":
        return g, r
    if structure == "tuple":
        return (g, honest), (r, honest)
    return {"y": g, "z": honest}, {"y": r, "z": honest}


def _numpy_passes(g, r, rtol, atol) -> bool:
    try:
        np.testing.assert_allclose(g, r, rtol=rtol, atol=atol)
    except AssertionError:
        return False
    return True


def _stub(out, ref, atol) -> ParamSpace:
    return ParamSpace("stub", [("block", (1,))],
                      runner=KernelRunner(build=lambda p: lambda: out,
                                          reference=lambda: ref, atol=atol),
                      signature="stub:planted")


@pytest.mark.parametrize("structure", ["array", "tuple", "mapping"])
@pytest.mark.parametrize("rtol,atol", TOLERANCES)
@pytest.mark.parametrize("case", CASES)
def test_device_check_agrees_with_numpy(case, rtol, atol, structure):
    g, r = _planted(case, rtol, atol)
    numpy_fails = int(np.count_nonzero(
        ~np.isclose(g, r, rtol=rtol, atol=atol, equal_nan=True)))
    # every planted element shares one verdict, so a count is elementwise
    assert numpy_fails in (0, g.size)
    passes = _numpy_passes(g, r, rtol, atol)
    assert passes == (numpy_fails == 0)
    assert passes == (case not in ("ulp_outside", "nan_one",
                                   "inf_opposite"))

    got, ref = _shaped(structure, g, r)
    assert comparable_on_device(got, ref)
    counts = np.asarray(outputs_close_on_device(got, ref, rtol=rtol,
                                                atol=atol))
    assert counts.dtype == np.int32
    assert counts.shape == ((1,) if structure == "array" else (2,))
    assert counts[0] >= numpy_fails       # never looser than NumPy
    assert not counts[1:].any()           # the honest one passes
    if case in DEVICE_PASSES:
        assert counts[0] == 0

    # The gate as a whole: the device, then the host on a count.
    ev = E.make_evaluator(_stub(got, ref, atol), "wallclock", repeats=1,
                          rtol=rtol)
    if passes:
        assert ev.evaluate([(1,)])[0] > 0.0
        assert ev.n_checked == 1
    else:
        with pytest.raises(AssertionError,
                           match=r"block=1.*value-correctness gate"):
            ev.evaluate([(1,)])
        assert ev.n_checked == 0


@pytest.mark.parametrize("rtol,atol", [*TOLERANCES, (1e-4, 0.0),
                                       (0.0, 1e-30)])
def test_device_predicate_never_passes_what_numpy_fails(rtol, atol):
    """Element for element, over outputs a few float32 steps either
    side of the bound at many magnitudes, subnormals among them."""
    rng = np.random.default_rng(7)
    r = F32(rng.choice([-1, 1], 4096)
            * 10.0 ** rng.uniform(-44, 6, 4096))
    steps = rng.integers(-3, 4, 4096)
    g = F32(r + _bound(r, rtol, atol) * rng.choice([-1, 1], 4096))
    for k in range(1, 4):
        g = np.where(steps >= k, np.nextafter(g, F32(np.inf)), g)
        g = np.where(-steps >= k, np.nextafter(g, F32(-np.inf)), g)
    numpy_fails = ~np.isclose(g, r, rtol=rtol, atol=atol, equal_nan=True)
    failing = jax.jit(wallclock._failing, static_argnames=("rtol", "atol"))
    device_fails = np.asarray(failing(jnp.asarray(g), jnp.asarray(r),
                                      rtol=rtol, atol=atol))
    assert numpy_fails.any() and (~numpy_fails).any()
    assert not (numpy_fails & ~device_fails).any()


def test_subnormals_are_left_to_the_host():
    """With no absolute tolerance NumPy tells 1e-40 from 2e-40; a device
    that flushes subnormals to zero could not, so it passes neither."""
    g, r = F32([1e-40, 1e-40]), F32([2e-40, 1e-40])
    counts = outputs_close_on_device(jnp.asarray(g), jnp.asarray(r),
                                     rtol=1e-4, atol=0.0)
    assert int(counts[0]) == 2
    assert not _numpy_passes(g[:1], r[:1], 1e-4, 0.0)
    assert _numpy_passes(g[1:], r[1:], 1e-4, 0.0)


def _gate_events(space, n_candidates=1, **kwargs):
    ex = obs.MemoryExporter()
    with obs.use(obs.Telemetry(exporters=[ex])):
        ev = E.make_evaluator(space, "wallclock", repeats=1, **kwargs)
        ev.evaluate([(b,) for b in range(1, n_candidates + 1)])
    ends = [e for e in ex.events if e["ph"] == "E"]
    return ev, [(e["name"], e["args"]) for e in ends
                if e["name"] in ("kernel.reference", "kernel.fetch",
                                 "kernel.compare")]


@pytest.mark.parametrize("structure", ["array", "tuple"])
def test_gate_compares_on_device_and_fetches_the_verdict(structure):
    r = jnp.arange(8, dtype=jnp.float32)
    out, ref = _shaped(structure, r + 1e-7, r)
    space = ParamSpace("stub", [("block", (1, 2, 3))],
                       runner=KernelRunner(build=lambda p: lambda: out,
                                           reference=lambda: ref),
                       signature=f"stub:device:{structure}")
    ev, spans = _gate_events(space, n_candidates=3)
    n_out = len(jax.tree.leaves(ref))
    nbytes = sum(a.nbytes for a in jax.tree.leaves(ref))
    assert ev.n_checked == 3
    assert spans[0] == ("kernel.reference", {"bytes": nbytes})
    assert spans[1:] == [("kernel.compare", {"bytes": nbytes,
                                             "on": "device"}),
                         ("kernel.fetch", {"bytes": 4 * n_out})] * 3


@pytest.mark.parametrize("ref", [
    np.arange(8, dtype=np.float32),               # a NumPy reference
    jnp.zeros((), jnp.float32),                   # another shape
    jnp.arange(8, dtype=jnp.int32),               # another dtype
], ids=["numpy_reference", "shape", "dtype"])
def test_gate_takes_the_host_path(ref):
    out = jnp.asarray(np.broadcast_to(np.asarray(ref, np.float32), (8,)))
    space = ParamSpace("stub", [("block", (1, 2))],
                       runner=KernelRunner(build=lambda p: lambda: out,
                                           reference=lambda: ref),
                       signature="stub:host")
    assert not comparable_on_device(out, ref)
    ev, spans = _gate_events(space, n_candidates=2)
    assert ev.n_checked == 2
    assert spans[1:] == [("kernel.fetch", {"bytes": 32}),
                         ("kernel.compare", {"bytes": 32, "on": "host"})] * 2


def test_device_count_is_decided_on_the_host():
    """Outputs exactly at the bound, atol from a zero reference: the
    device, one ulp stricter, counts them; the host passes them, as
    NumPy does."""
    g, r = F32([2e-2, -2e-2]), F32([0.0, 0.0])
    assert _numpy_passes(g, r, 1e-4, 2e-2)
    ev, spans = _gate_events(_stub(jnp.asarray(g), jnp.asarray(r), 2e-2))
    assert ev.n_checked == 1
    assert [(name, args.get("on")) for name, args in spans[1:]] == [
        ("kernel.compare", "device"), ("kernel.fetch", None),
        ("kernel.fetch", None), ("kernel.compare", "host")]
