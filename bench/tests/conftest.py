"""Helpers for the benchmark's CPU tests: every run of the harness is a
child process on the CPU backend, at its configuration's rehearsal
sizes, with a compile cache of its own."""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench_spec import CHIPS, bench_copy  # noqa: E402


def child_env(cache: pathlib.Path, devices: int = 1) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    env.pop("XLA_FLAGS", None)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


@pytest.fixture
def bench(tmp_path):
    """``bench(workload, *extra, prelude="")`` runs ``bench/run.py
    --rehearse`` for ``workload``, in a copy of the benchmark, in a
    child process (after the Python code ``prelude``, which may
    plant a fault) and returns (returncode, stdout, stderr)."""
    root = bench_copy(tmp_path / "checkout")

    def run(workload: str, *extra: str, prelude: str = "", seed: int = 2**31 + 5,
            seconds: float = 0.5, timeout: float = 240):
        args = ["--workload", workload, "--seed", str(seed), "--seconds",
                str(seconds), "--rehearse", *extra]
        code = (f"import sys; sys.path[:0] = [{str(root / 'bench')!r}, "
                f"{str(ROOT / 'src')!r}]\n{prelude}\n"
                f"import run; sys.exit(run.main({args!r}))")
        p = subprocess.run([sys.executable, "-c", code], cwd=root,
                           env=child_env(tmp_path / "cache", CHIPS[workload]),
                           capture_output=True, text=True, timeout=timeout)
        return p.returncode, p.stdout, p.stderr

    return run


@pytest.fixture
def child(tmp_path):
    """``child(script, *args, devices=1, root=ROOT)`` runs
    ``<root>/bench/<script>`` (or the script at an absolute path) on the
    CPU backend; returns the finished process."""

    def run(script: str, *args: str, devices: int = 1,
            root: pathlib.Path = ROOT, timeout: float = 240):
        return subprocess.run([sys.executable, str(root / "bench" / script),
                               *args],
                              cwd=root, env=child_env(tmp_path / "cache",
                                                      devices),
                              capture_output=True, text=True,
                              timeout=timeout)

    return run
