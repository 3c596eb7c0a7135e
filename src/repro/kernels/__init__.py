"""The repo's Pallas TPU kernels (spmv, pack, flash_attention).

Every kernel and wrapper takes ``interpret: bool | None = None`` and
resolves it through :func:`resolve_interpret`: the Pallas interpreter
runs only on the CPU backend, where there is no Mosaic compiler to
target. On any other backend the kernel is compiled, so a measurement
on the chip never times the interpreter by accident.
"""
from __future__ import annotations


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` if given, else ``True`` only on the CPU backend."""
    if interpret is not None:
        return interpret
    import jax

    return jax.default_backend() == "cpu"
