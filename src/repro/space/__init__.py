"""Design spaces: the candidate-type seam of the whole stack.

``repro.space`` defines what a search needs from a space
(:class:`DesignSpace`), the paper's schedule spaces as its first
registered instance (:class:`ScheduleSpace` — bit-compatible with the
pre-protocol pipeline), parameter grids (:class:`ParamSpace`) for
tunable knobs like kernel block sizes, and a name registry
(:func:`register_space` / :func:`make_space`) so examples and CLIs
select spaces by name.

The kernel parameter spaces (``flash_attention``, ``spmv_mulsum``,
``pack``, ``mla_decode`` — :mod:`repro.kernels.autotune`) are
registered through lazy factories: importing this package never
imports JAX.
"""
from repro.space.base import (SPACES, DesignSpace, as_space, make_space,
                              register_space)
from repro.space.params import (KernelRunner, ParamFeature, ParamSpace,
                                demo_param_space)
from repro.space.schedule import (ScheduleSpace, canonical_key,
                                  eligible_items, random_schedule)

__all__ = [
    "DesignSpace", "ScheduleSpace", "ParamSpace", "ParamFeature",
    "KernelRunner", "SPACES", "register_space", "make_space",
    "as_space", "canonical_key", "eligible_items", "random_schedule",
    "demo_param_space",
]


def _schedule_factory(builder):
    def make(n_streams: int = 2, **kwargs) -> ScheduleSpace:
        return ScheduleSpace(builder(**kwargs), n_streams)
    return make


def _spmv(**kw):
    from repro.core.dag import spmv_dag
    return spmv_dag(**kw)


def _spmv_fine(**kw):
    from repro.core.dag import spmv_dag_fine
    return spmv_dag_fine(**kw)


def _halo3d(**kw):
    from repro.core.dag import halo3d_dag
    return halo3d_dag(**kw)


def _kernel_factory(name):
    def make(**kwargs) -> ParamSpace:
        import repro.kernels.autotune as autotune
        return getattr(autotune, name)(**kwargs)
    return make


# The paper's DAG schedule spaces.
register_space("spmv", _schedule_factory(_spmv))
register_space("spmv_fine", _schedule_factory(_spmv_fine))
register_space("halo3d", _schedule_factory(_halo3d))
# The repo's own Pallas kernel grids (lazy: factories import JAX).
register_space("flash_attention", _kernel_factory("flash_attention_space"))
register_space("spmv_mulsum", _kernel_factory("spmv_mulsum_space"))
register_space("pack", _kernel_factory("pack_space"))
register_space("mla_decode", _kernel_factory("mla_decode_space"))
# Analytic demo grid (tests, smoke runs; no JAX).
register_space("demo", demo_param_space)
