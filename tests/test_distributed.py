"""Multi-device tests (shard_map SpMV, compressed DP sync, elastic
re-mesh, dry-run cell builder). These need >1 device, so each runs in a
subprocess with XLA_FLAGS set before jax initializes — the main test
process keeps the default single device (per the launch-layer rule)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_distributed_spmv_matches_oracle():
    run_sub("""
import numpy as np, jax
from jax.sharding import Mesh
from repro.spmv.matrix import band_matrix, partition, stack_partitions
from repro.spmv.distributed import make_distributed_spmv
A = band_matrix(n=1024, nnz=8192, half_bandwidth=256, seed=1)
x = np.random.default_rng(2).standard_normal(1024).astype(np.float32)
parts = partition(A, 4)
st = stack_partitions(parts)
mesh = Mesh(np.array(jax.devices()[:4]), ("ranks",))
ref = A.matvec(x)
for uk in (False, True):
    run = make_distributed_spmv(mesh, use_kernel=uk)
    y = np.asarray(run(st["local_vals"], st["local_cols"],
                       st["remote_vals"], st["remote_cols"],
                       x.reshape(4, 256))).reshape(-1)
    err = np.abs(y - ref).max() / np.abs(ref).max()
    assert err < 1e-5, (uk, err)
print("OK")
""", devices=4)


def test_compressed_dp_sync_bounded_error():
    run_sub("""
import numpy as np, jax, jax.numpy as jnp, functools
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.dist.compress import compressed_psum_mean, init_ef, psum_mean
mesh = Mesh(np.array(jax.devices()), ("data",))
g_local = jnp.arange(8 * 64, dtype=jnp.float32).reshape(8, 64) / 1000.0

@functools.partial(shard_map, mesh=mesh,
                   in_specs=(P("data"), P("data")),
                   out_specs=(P("data"), P("data")), check_vma=False)
def sync(g, e):
    out, new_e = compressed_psum_mean({"w": g[0]}, {"w": e[0]}, "data")
    return out["w"][None], new_e["w"][None]

e0 = jnp.zeros((8, 64), jnp.float32)
synced, ef = sync(g_local, e0)
exact = np.asarray(g_local).mean(axis=0)
got = np.asarray(synced)[0]
rel = np.abs(got - exact).max() / (np.abs(exact).max() + 1e-9)
assert rel < 1e-2, rel
# error feedback holds the quantization residual
assert np.abs(np.asarray(ef)).max() > 0
print("OK")
""")


def test_elastic_remesh_resharding():
    run_sub("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.ft.elastic import degraded_mesh, remesh_state
from repro.dist.sharding import tree_shardings
devs = np.array(jax.devices()).reshape(4, 2)
mesh = Mesh(devs, ("data", "model"))
state = {"w": jnp.arange(16 * 8, dtype=jnp.float32).reshape(16, 8)}
axes = {"w": ("batch", "d_ff")}
sh = tree_shardings(axes, mesh, None,
                    jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape,
                                 a.dtype), state))
state = jax.tree.map(jax.device_put, state, sh)
# lose 2 devices -> (2, 2) mesh, reshard
new_mesh = degraded_mesh(devs, ("data", "model"), lost=2)
assert new_mesh.devices.shape == (3, 2)
out = remesh_state(state, axes, new_mesh)
np.testing.assert_array_equal(np.asarray(out["w"]),
                              np.asarray(state["w"]))
print("OK")
""")


def test_dryrun_cell_builder_small_mesh():
    """build_cell + lower + compile on an 8-device (2x4) mesh with a
    reduced arch config — the same code path the 512-device dry-run
    exercises, kept cheap for CI."""
    run_sub("""
import numpy as np, jax
import dataclasses
from jax.sharding import Mesh
import repro.launch.inputs as inputs
import repro.configs as cfgs
from repro.launch import hlo as H

mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))

# monkeypatch: reduced config + tiny shape cell
from repro.configs.shapes import SHAPES, ShapeCell
SHAPES["tiny_train"] = ShapeCell("tiny_train", 64, 8, "train")
SHAPES["tiny_decode"] = ShapeCell("tiny_decode", 64, 8, "decode")
real_get = cfgs.get_config
cfgs.get_config = lambda name: cfgs.get_reduced(name)
inputs.cfgs = cfgs

for arch in ("granite-3-8b", "deepseek-moe-16b", "jamba-v0.1-52b"):
    for shape in ("tiny_train", "tiny_decode"):
        cell = inputs.build_cell(arch, shape, mesh)
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings)
        compiled = jitted.lower(*cell.args).compile()
        assert compiled.memory_analysis() is not None
        a = H.analyze(compiled.as_text())
        assert a.dot_flops > 0
        print(arch, shape, "OK")
""")


def test_production_mesh_multi_pod_shapes():
    run_sub("""
from repro.launch.mesh import make_production_mesh
m1 = make_production_mesh()
assert m1.devices.shape == (16, 16)
assert m1.axis_names == ("data", "model")
m2 = make_production_mesh(multi_pod=True)
assert m2.devices.shape == (2, 16, 16)
assert m2.axis_names == ("pod", "data", "model")
print("OK")
""", devices=512)
