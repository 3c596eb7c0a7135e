"""Latent attention (MLA) at decode: the Pallas kernel against its plain
references, the ``mla_decode`` space through the tuner's normal path,
and the faults its value gate must refuse.

Everything runs on the CPU at small sizes, kernels in interpret mode,
at Moonlight-16B-A3B's published widths (16 heads, 512 + 64 latent,
128 + 64 query halves); ``tests/test_tpu_compile.py`` compiles the
kernel at the benchmark cell's size for a v5e.
"""
import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

import repro.engine as E
import repro.search as S
from repro import obs
from repro.kernels.autotune import (mla_decode_instance, mla_decode_space,
                                    prng_key)
from repro.kernels.mla_decode import kernel as mla_kernel
from repro.kernels.mla_decode import ref as R
from repro.kernels.mla_decode.ops import (log_uniform_lengths, mla_decode,
                                          mla_decode_ref, softmax_scale)
from repro.rules import distill
from repro.space import KernelRunner, ParamSpace, make_space

HEADS, DV, ROPE, NOPE, V = 16, 512, 64, 128, 128
WIDTH = DV + ROPE
SCALE = softmax_scale(NOPE, ROPE)
S_MAX = 256
# ragged: a single token, a length that is no multiple of any block,
# exactly one block, the whole cache
LENGTHS = (1, 77, 128, 256)


def _instance(dtype, lengths=LENGTHS, seed=0):
    kq, kc = jax.random.split(jax.random.key(seed))
    b = len(lengths)
    return (jax.random.normal(kq, (b, HEADS, WIDTH), dtype),
            jax.random.normal(kc, (b, WIDTH, S_MAX), dtype),
            jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("block_k,block_b", [(128, 1), (128, 2), (128, 4),
                                             (256, 1), (256, 4)])
def test_kernel_matches_reference_over_ragged_lengths(block_k, block_b):
    q, cache, lengths = _instance(jnp.float32)
    out = mla_decode(q, cache, lengths, block_k=block_k, block_b=block_b,
                     scale=SCALE)
    assert out.shape == (4, HEADS, DV) and out.dtype == jnp.float32
    np.testing.assert_allclose(out, mla_decode_ref(q, cache, lengths, SCALE),
                               rtol=1e-5, atol=1e-5)


def test_blocks_past_a_groups_longest_sequence_are_never_read():
    """Blocks wholly past a group's longest sequence may hold anything,
    NaN included: the kernel neither computes on them nor loads them
    (their index clamps to the group's last valid block)."""
    q, cache, _ = _instance(jnp.float32)
    lengths = jnp.asarray([1, 100, 130, 20], jnp.int32)     # groups of 2
    ref = mla_decode_ref(q, cache, lengths, SCALE)
    past = jnp.asarray([128, 128, 256, 256])[:, None, None]  # block ends
    poisoned = jnp.where(jnp.arange(S_MAX)[None, None, :] >= past,
                         jnp.nan, cache)
    out = mla_decode(q, poisoned, lengths, block_k=128, block_b=2,
                     scale=SCALE)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    assert [int(mla_kernel._last_block(np.asarray(lengths), g, 2, 128))
            for g in range(2)] == [0, 1]


def test_kernel_refuses_blocks_that_do_not_divide():
    q, cache, lengths = _instance(jnp.float32)
    with pytest.raises(ValueError, match="divide"):
        mla_decode(q, cache, lengths, block_k=96, block_b=1, scale=SCALE)


def test_kernel_path_matches_the_published_layer():
    """Hidden states -> the latent cache and the absorbed query ->
    kernel -> W_uv -> W_o, in float32, against the unabsorbed MLA layer
    (arXiv:2405.04434 §2.1) at each sequence's own length. The hidden
    size is cut (the kernel never sees it); every attention width is
    Moonlight's."""
    hidden, eps, theta = 256, 1e-5, 50000.0
    w = R.init_weights(jax.random.key(3), hidden=hidden, heads=HEADS,
                       kv_lora_rank=DV, qk_nope_head_dim=NOPE,
                       qk_rope_head_dim=ROPE, v_head_dim=V)
    lengths = (1, 77, 200)
    xs = jax.random.normal(jax.random.key(4), (len(lengths), S_MAX, hidden))
    pos = jnp.arange(S_MAX)
    stale = jax.random.normal(jax.random.key(5), (S_MAX, WIDTH))
    caches, queries = [], []
    for x, n in zip(xs, lengths):
        rows = R.latent_cache(x[:n], w, pos[:n], eps=eps, theta=theta)
        caches.append(jnp.concatenate([rows, stale[n:]]).T)   # (D, S)
        q_nope, q_rope = R.decode_query(x[n - 1], w, n - 1, theta=theta)
        queries.append(R.absorb_query(q_nope, q_rope, w["w_uk"]))
    o_lat = mla_decode(jnp.stack(queries), jnp.stack(caches),
                       jnp.asarray(lengths, jnp.int32), block_k=128,
                       block_b=1, scale=SCALE)
    for b, (x, n) in enumerate(zip(xs, lengths)):
        got = jnp.dot(R.apply_uv(o_lat[b], w["w_uv"]).reshape(-1),
                      w["w_o"], precision=R.HI)
        want = R.mla_attention_ref(x[:n], w, pos[:n], eps=eps, theta=theta)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -- the lengths' rule and the seeds ------------------------------------------

def test_lengths_are_the_configured_quantiles_whatever_the_seed():
    lengths = log_uniform_lengths(128, 1024, 8192, 16)
    assert lengths.dtype == np.int32 and lengths.sum() == 441_226
    assert 1024 <= lengths.min() and lengths.max() <= 8192
    u = (np.arange(128) + 0.5) / 128
    np.testing.assert_array_equal(np.sort(lengths),
                                  np.rint(1024 * 8 ** u).astype(np.int32))
    np.testing.assert_array_equal(lengths,
                                  log_uniform_lengths(128, 1024, 8192, 16))
    assert not np.array_equal(lengths,
                              log_uniform_lengths(128, 1024, 8192, 17))


def test_seeds_keep_their_high_bits():
    data = [jax.random.key_data(prng_key(s)) for s in
            (5, 2**32 + 5, [2**31 + 5, 0], [2**31 + 5, 1])]
    assert len({np.asarray(d).tobytes() for d in data}) == 4
    with pytest.raises(ValueError):
        prng_key(-1)


# -- the space through the tuner's normal path --------------------------------

def _space(**kw):
    return mla_decode_space(batch=4, s_max=S_MAX, min_len=16, max_len=256,
                            **{"block_k_values": (128,),
                               "block_b_values": (2,), **kw})


def test_space_tunes_gates_and_distills(tmp_path):
    """make_space -> wallclock evaluator (batch-ahead compile, the
    device gate) -> exhaustive search -> store -> distill, with the
    instance drawn on the device under ``space.instance``."""
    ex = obs.MemoryExporter()
    with obs.use(obs.Telemetry(exporters=[ex])):
        sp = make_space("mla_decode", layers=2, batch=4, s_max=S_MAX,
                        min_len=16, max_len=256,
                        block_k_values=(128, 256, 96),
                        block_b_values=(1, 2, 3), seed=[2**31 + 5, 0])
        with E.make_evaluator(sp, "wallclock", repeats=1,
                              store_path=str(tmp_path / "s.store")) as ev:
            res = S.run_search(sp, S.ExhaustiveSearch(sp), budget=None,
                               evaluator=ev)
    assert dict(sp.dims) == {"block_k": (128, 256), "block_b": (1, 2)}
    assert sp.n_candidates() == len(res.schedules) == ev.n_checked == 4
    report = distill(res)
    assert report.n_schedules == 4 and report.render()
    for token in ("layers=2", "b=4", "s_max=256", "loguniform(16,256,order=0)",
                  "dtype=bfloat16", f"seed={[2**31 + 5, 0]}"):
        assert token in sp.signature
    ends = [e for e in ex.events if e["ph"] == "E"]
    inst = [e["args"] for e in ends if e["name"] == "space.instance"]
    assert inst == [{"on": "device", "bytes": 2 * (4 * HEADS * WIDTH * 2
                                                   + 4 * WIDTH * S_MAX * 2)
                     + 4 * 4}]
    assert {e["args"]["on"] for e in ends
            if e["name"] == "kernel.compare"} == {"device"}


def test_space_instance_is_the_seeds():
    a, b, c = (_space(seed=s) for s in ([7, 0], [7, 0], [7, 1]))
    ra, rb, rc = (np.asarray(x.runner.reference()) for x in (a, b, c))
    np.testing.assert_array_equal(ra, rb)
    assert not np.array_equal(ra, rc)


def test_space_refuses_lengths_past_the_cache():
    with pytest.raises(ValueError, match="exceeds"):
        mla_decode_space(s_max=256, max_len=512)


def _float8_control(sp, seed=0):
    """The reference on the space's queries and cache (drawn again from
    its seed) rounded to float8 e4m3."""
    (q,), (cache,) = mla_decode_instance(4, HEADS, WIDTH, S_MAX, seed)
    lengths = log_uniform_lengths(4, 16, 256, 0)
    np.testing.assert_array_equal(mla_decode_ref(q, cache, lengths, SCALE),
                                  sp.runner.reference()[0])

    def rounded(x):
        return jnp.asarray(np.asarray(x).astype(ml_dtypes.float8_e4m3fn)
                           .astype(np.float32))
    return mla_decode_ref(rounded(q), rounded(cache), lengths, SCALE)


@pytest.mark.parametrize("case,passes", [("bf16_kernel", True),
                                         ("float8_control", False)])
def test_gate_passes_the_bf16_kernel_and_refuses_float8(case, passes):
    sp = _space()
    if case == "float8_control":
        out = _float8_control(sp)[None]
        sp = ParamSpace(sp.name, sp.dims, runner=KernelRunner(
            build=lambda p: lambda: out, reference=sp.runner.reference,
            atol=sp.runner.atol), signature=sp.signature)
    ev = E.make_evaluator(sp, "wallclock", repeats=1)
    if passes:
        ev.evaluate([(128, 2)])
        assert ev.n_checked == 1
    else:
        with pytest.raises(AssertionError, match="value-correctness gate"):
            ev.evaluate([(128, 2)])


# -- planted faults the gate must refuse --------------------------------------

def _mask_dropped(m):
    m.setattr(mla_kernel, "_masked", lambda s, start, length: s)


def _wrong_value_columns(m):
    m.setattr(mla_kernel, "_values", lambda cols, dv: cols[-dv:])


def _scale_of_the_whole_width(m):
    body = mla_kernel._mla_body
    m.setattr(mla_kernel, "_mla_body", lambda *refs, **kw: body(
        *refs, **{**kw, "scale": WIDTH ** -0.5}))


def _skipped_blocks_computed_unmasked(m):
    m.setattr(mla_kernel, "_live", lambda start, length: start >= 0)
    m.setattr(mla_kernel, "_masked", lambda s, start, length: s)


@pytest.mark.parametrize("fault", [_mask_dropped, _wrong_value_columns,
                                   _scale_of_the_whole_width,
                                   _skipped_blocks_computed_unmasked])
def test_gate_refuses_planted_faults(monkeypatch, fault):
    """The gate's atol, set for the chip's bfloat16 probabilities, still
    refuses stale tokens let in, values from the wrong features, the
    wrong softmax scale and a group's skipped blocks computed, each
    far outside it."""
    sp = _space()
    ref = np.asarray(sp.runner.reference())
    run = sp.runner.build({"block_k": 128, "block_b": 2})
    with monkeypatch.context() as m:
        fault(m)
        jax.clear_caches()
        err = np.abs(np.asarray(run()) - ref)
        assert err.max() > 10 * sp.runner.atol
        with pytest.raises(AssertionError, match="value-correctness gate"):
            E.make_evaluator(sp, "wallclock", repeats=1).evaluate(
                [(128, 2)])
    jax.clear_caches()
