"""Ahead-of-time compiles of the main path's kernels for one TPU v5e chip.

Nothing runs: each case lowers and compiles at a deployment shape for a
described (not attached) v5e topology, so the chip's compiler refuses
here what interpret mode cannot see — block tilings, VMEM limits. The
CPU backend still answers ``jax.default_backend()``, so every kernel is
called with ``interpret=False``. The topology is described inside a
fixture, never at import: only the worker that runs this file loads
the TPU library.
"""
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import spmv_dag
from repro.core.executor import build_runner
from repro.engine import wallclock
from repro.engine.wallclock import demo_spmv_impls, reference_schedule
from repro.kernels.flash_attention.ops import mha
from repro.kernels.mla_decode.kernel import mla_decode
from repro.kernels.pack.kernel import pack
from repro.kernels.spmv.kernel import ell_mulsum
from repro.kernels.spmv.ops import ell_matvec_onehot

PAPER_N, PAPER_K = 150_000, 10          # the paper's matrix (spmv/matrix.py)
# The MLA decode cell's configuration: its size and its block grid.
MLA = json.loads((pathlib.Path(__file__).resolve().parents[1] / "bench"
                  / "configs" / "moonlight_mla_decode.json").read_text())
# The causal attention cell's configuration: its shape and its blocks.
ATTN = json.loads((pathlib.Path(__file__).resolve().parents[1] / "bench"
                   / "configs" / "dsmoe16b_attn.json").read_text())


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shape(one_chip):
    def make(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def _kernel_compiled(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text       # a Mosaic kernel, not interpreted
    return text


@pytest.mark.parametrize("block_n", [128, 512])
def test_ell_mulsum_compiles_at_paper_size(shape, block_n):
    kt = shape((PAPER_K, PAPER_N))
    _kernel_compiled(ell_mulsum.lower(kt, kt, block_n=block_n,
                                      interpret=False))


def test_ell_mulsum_refuses_unaligned_lane_block(shape):
    kt = shape((PAPER_K, PAPER_N))
    with pytest.raises(Exception, match="128"):
        ell_mulsum.lower(kt, kt, block_n=64, interpret=False).compile()


def test_ell_onehot_compiles_narrow_band(shape):
    n, k = 65_536, PAPER_K
    _kernel_compiled(ell_matvec_onehot.lower(
        shape((n, k)), shape((n, k), jnp.int32), shape((n,)),
        half_bandwidth=128, block_r=256, interpret=False))


def test_flash_attention_compiles_at_smollm_width(shape):
    qkv = shape((1, 15, 2048, 64))        # smollm-360m: 15 heads x 64
    _kernel_compiled(mha.lower(qkv, qkv, qkv, causal=True, block_q=128,
                               block_k=128, interpret=False))


@pytest.mark.parametrize("block", [min(ATTN["blocks"]),
                                   max(ATTN["blocks"])])
def test_flash_attention_compiles_at_the_cells_size(shape, block):
    """The attention cell's 16 heads x 4,096 positions x 128 at its
    smallest block, whose table (8,256 steps) takes the most SMEM, and
    at its largest."""
    h = ATTN["num_attention_heads"]
    qkv = shape((ATTN["batch"], h, ATTN["seq"], ATTN["hidden_size"] // h))
    _kernel_compiled(mha.lower(qkv, qkv, qkv, causal=True, block_q=block,
                               block_k=block, interpret=False))


def _mla_shapes(shape):
    b, h = MLA["batch"], MLA["num_attention_heads"]
    d = MLA["kv_lora_rank"] + MLA["qk_rope_head_dim"]
    return (shape((b, h, d), jnp.bfloat16),
            shape((b, d, MLA["s_max"]), jnp.bfloat16),
            shape((b,), jnp.int32))


@pytest.mark.parametrize("block_b", MLA["block_b"])
@pytest.mark.parametrize("block_k", MLA["block_k"])
def test_mla_decode_compiles_at_the_cells_size(shape, block_k, block_b):
    """Every candidate of the Moonlight decode cell, at 128 sequences x
    8,192 positions x 576 features, with no copy of the cache beside
    the kernel (its feature-major layout is the chip's default)."""
    lowered = mla_decode.lower(*_mla_shapes(shape), block_k=block_k,
                               block_b=block_b, scale=192 ** -0.5,
                               value_dim=MLA["kv_lora_rank"],
                               interpret=False)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_mla_decode_refuses_unaligned_lane_block(shape):
    q, cache, lengths = _mla_shapes(shape)
    with pytest.raises(Exception, match="128"):
        mla_decode.lower(q, cache, lengths, block_k=64, block_b=1,
                         scale=192 ** -0.5, interpret=False).compile()


def test_pack_compiles(shape):
    _kernel_compiled(pack.lower(shape((PAPER_N,)),
                                shape((4096,), jnp.int32),
                                interpret=False))


def test_token_chain_runner_compiles(shape):
    g = spmv_dag()
    impls, env = demo_spmv_impls(g, n=256)
    run = build_runner(g, reference_schedule(g), impls)
    env_shapes = {k: shape(v.shape, v.dtype) for k, v in env.items()}
    compiled = jax.jit(run).lower(env_shapes).compile()
    assert compiled.memory_analysis() is not None


def test_value_gate_check_compiles_to_one_reduction(shape):
    """The gate's device check at the attention cell's output (1 x 16 x
    4096 x 128 float32): one fused reduction, no full-size temporary."""
    out = {"out": shape((1, 16, 4096, 128))}
    compiled = wallclock._count_failing_jit().lower(
        out, out, rtol=1e-4, atol=2e-2).compile()
    fusions = [line for line in compiled.as_text().splitlines()
               if " fusion(" in line]
    assert len(fusions) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
