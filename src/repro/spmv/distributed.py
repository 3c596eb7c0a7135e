"""Distributed SpMV with shard_map (the paper's workload, TPU-native).

The paper's MPI point-to-point halo exchange maps to two
``lax.ppermute`` shifts over a 1-D device mesh axis ("ranks"):
each rank sends its x block to its right and left neighbors, which
together assemble the halo = [left block, right block]. Local and remote
multiplications use the ELL kernels from :mod:`repro.kernels.spmv`.

The op decomposition intentionally mirrors the paper's DAG:

    Pack      -> (band matrices: the pack is the identity on the block —
                  contiguous halo; the general gather kernel lives in
                  repro.kernels.pack and is exercised for irregular inputs)
    PostSend/PostRecv/Wait -> ppermute (XLA schedules the wire transfer;
                  emission order = our schedule decision)
    yL / yR   -> ELL multiply kernels
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.spmv import ops as spmv_ops

AXIS = "ranks"


def _halo_exchange(x_block: jax.Array, axis: str = AXIS) -> jax.Array:
    """Assemble halo = [left neighbor block, right neighbor block]."""
    n = lax.axis_size(axis)
    # perm (i -> i+1) means device j receives from j-1: its LEFT neighbor.
    from_left = lax.ppermute(x_block, axis,
                             [(i, (i + 1) % n) for i in range(n)])
    from_right = lax.ppermute(x_block, axis,
                              [(i, (i - 1) % n) for i in range(n)])
    return jnp.concatenate([from_left, from_right], axis=0)


def spmv_shard(local_vals, local_cols, remote_vals, remote_cols, x_block,
               *, use_kernel: bool = True, overlap_local: bool = True,
               axis: str = AXIS):
    """Per-shard body: one distributed SpMV step.

    ``overlap_local``: emit the local multiply before the halo exchange's
    consumer so XLA can overlap compute with the permutes (the schedule
    decision the paper's rules produce for the fast class: local multiply
    runs while communication is in flight).
    """
    mv = spmv_ops.ell_matvec if use_kernel else spmv_ops.ell_matvec_ref

    if overlap_local:
        halo = _halo_exchange(x_block, axis)
        y_local = mv(local_vals, local_cols, x_block)
        y_remote = mv(remote_vals, remote_cols, halo)
    else:
        # Slow-class ordering: remote path fully serialized first.
        halo = _halo_exchange(x_block, axis)
        y_remote = mv(remote_vals, remote_cols, halo)
        y_local = mv(local_vals, local_cols, x_block)
    return y_local + y_remote


def make_distributed_spmv(mesh: Mesh, *, use_kernel: bool = True,
                          overlap_local: bool = True):
    """jit-compiled distributed SpMV over ``mesh`` axis "ranks".

    Inputs are the stacked per-rank arrays from
    :func:`repro.spmv.matrix.stack_partitions` (leading rank axis) and
    the stacked x blocks (n_ranks, m).
    """
    spec = P(AXIS)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec),
        out_specs=spec,
        # pallas_call outputs carry no varying-mesh-axis metadata yet.
        check_vma=False)
    def _shard(lv, lc, rv, rc, xb):
        y = spmv_shard(lv[0], lc[0], rv[0], rc[0], xb[0],
                       use_kernel=use_kernel,
                       overlap_local=overlap_local)
        return y[None]

    sharding = NamedSharding(mesh, spec)

    @jax.jit
    def run(lv, lc, rv, rc, xb):
        args = [jax.device_put(a, sharding) for a in (lv, lc, rv, rc, xb)]
        return _shard(*args)

    return run
