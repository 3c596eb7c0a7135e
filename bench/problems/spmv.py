"""SpMV on the paper's band matrix: the call (the tuned ``ell_matvec``
on one chip, or the distributed SpMV over ``ranks`` chips) and the
tuning job over ``block_n``.

The call cells' instance is the benchmark's own (``reference.band``
from the seed); the tuning jobs' instance is the one the program's
space factory draws from the job's seed, which the check draws again
with the benchmark's copy of the generator.
"""
from __future__ import annotations

import numpy as np

from harness import work
from harness.peaks import least_time_s
from harness.tuning import exact_checks, run_job
from reference import band
from reference import spmv as ref

CHECK = "spmv_row_err"


class Call:
    """The timed entry, jitted by the benchmark under a stable name."""

    def __init__(self, sizes: dict, traffic: dict, seed, scratch, devices):
        import jax

        n, nnz, ranks = sizes["n_rows"], sizes["nnz"], sizes["ranks"]
        self.limits = sizes["limits"]
        self.kind = devices[0].device_kind
        self.vals, self.cols = band.band_matrix(n, nnz, [seed, 0])
        self.xs = [band.vector(n, [seed, 1, p])
                   for p in range(int(traffic["inputs"]))]
        if ranks == 1:
            from repro.kernels.spmv.ops import ell_matvec

            job = run_job(0, "spmv_mulsum",
                          dict(n=n, k=nnz // n, seed=[seed, 0],
                               block_values=tuple(sizes["block_n"])),
                          scratch / "setup.evalstore", keep=False,
                          traced=False)
            self.block_n = job.best["block_n"]
            print(f"tuner chose block_n={self.block_n}", flush=True)

            def spmv_call(vals, cols, x):
                return ell_matvec(vals, cols, x, block_n=self.block_n)

            put = lambda a: jax.device_put(a, devices[0])  # noqa: E731
            self.args = [put(self.vals), put(self.cols)]
            self.dev_xs = [put(x) for x in self.xs]
            self.fn = jax.jit(spmv_call)
            self.bytes = work.spmv_bytes(nnz, n, n)
        else:
            from jax.sharding import Mesh, NamedSharding
            from jax.sharding import PartitionSpec as P

            from repro.spmv.distributed import make_distributed_spmv
            from repro.spmv.matrix import (EllMatrix, partition,
                                           stack_partitions)

            mesh = Mesh(np.array(devices[:ranks]), ("ranks",))
            shard = NamedSharding(mesh, P("ranks"))
            st = stack_partitions(partition(
                EllMatrix(self.vals, self.cols, n), ranks))
            self.args = [jax.device_put(st[k], shard) for k in
                         ("local_vals", "local_cols", "remote_vals",
                          "remote_cols")]
            self.dev_xs = [jax.device_put(x.reshape(ranks, -1), shard)
                           for x in self.xs]
            run = make_distributed_spmv(mesh, use_kernel=True,
                                        overlap_local=True)

            def spmv_ranks_call(lv, lc, rv, rc, xb):
                return run(lv, lc, rv, rc, xb)

            self.fn = jax.jit(spmv_ranks_call)
            m = n // ranks
            self.bytes = max(
                work.spmv_rank_bytes(int((self.vals[r * m:(r + 1) * m] != 0)
                                         .sum()), m) for r in range(ranks))
        self.module = f"jit_{self.fn.__name__}"
        for i in range(len(self.dev_xs)):      # every shape the window uses
            jax.block_until_ready(self.call(i))

    def call(self, i: int):
        return self.fn(*self.args, self.dev_xs[i % len(self.dev_xs)])

    def least_time_s(self) -> float:
        return least_time_s(self.kind, bytes_moved=self.bytes)

    def release(self) -> None:
        self.args = self.dev_xs = self.fn = None

    def check(self, window) -> list:
        err = max(ref.row_error(np.asarray(y).reshape(-1), self.vals,
                                self.cols, self.xs[i % len(self.xs)])
                  for i, y in window.samples)
        return [(CHECK, err, self.limits[CHECK])]

    def control(self, window) -> dict:
        used = sorted({i % len(self.xs) for i, _ in window.samples})
        return {CHECK: max(ref.row_error(
            ref.control(self.vals, self.cols, self.xs[p]), self.vals,
            self.cols, self.xs[p]) for p in used)}


class Tune:
    """Closed-loop tuning jobs over ``block_n``, one instance per job."""

    space = "spmv_mulsum"
    module = "jit_ell_matvec"

    def __init__(self, sizes: dict, traffic: dict, seed, scratch, devices):
        self.n, self.nnz = sizes["n_rows"], sizes["nnz"]
        self.limits = sizes["limits"]
        self.kind = devices[0].device_kind
        self.seed, self.scratch = seed, scratch
        self.kw = dict(n=self.n, k=self.nnz // self.n,
                       block_values=tuple(sizes["block_n"]))
        self.job(0, keep=False, traced=False)     # warms every shape

    def job(self, j: int, *, keep: bool, traced: bool):
        return run_job(j, self.space, dict(self.kw, seed=[self.seed, j]),
                       self.scratch / "job.evalstore", keep=keep,
                       traced=traced)

    def least_time_s(self) -> float:
        return least_time_s(self.kind, bytes_moved=work.spmv_bytes(
            self.nnz, self.n, self.n))

    def release(self) -> None:
        pass

    def _instance(self, j: int):
        vals, cols = band.band_matrix(self.n, self.nnz, [self.seed, j])
        return vals, cols, band.vector(self.n, [self.seed, j])

    def check(self, window) -> list:
        err = 0.0
        for job in window.samples:
            vals, cols, x = self._instance(job.index)
            for out in job.outputs.values():
                err = max(err, ref.row_error(np.asarray(out), vals, cols, x))
        return [(CHECK, err, self.limits[CHECK])] + exact_checks(
            window.records)

    def control(self, window) -> dict:
        err = 0.0
        for job in window.samples:
            vals, cols, x = self._instance(job.index)
            err = max(err, ref.row_error(ref.control(vals, cols, x), vals,
                                         cols, x))
        return {CHECK: err}
