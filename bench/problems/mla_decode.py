"""Latent attention (MLA) at decode over a ragged batch: the call, one
decode step's attention over the layers this chip holds, for every live
sequence, through the kernel the tuner chose.

Set-up runs one tuning job over the ``mla_decode`` space's
``(block_k, block_b)`` grid at the configuration's sizes and keeps the
best block. The window then calls that kernel back to back, one call a
layer in one dispatch jitted by the benchmark as ``mla_decode_call``,
on the benchmark's own instance: the layers' latent caches drawn from
``[seed, 0]`` (the tuning job's instance) and ``inputs`` query sets
from ``[seed, 1, p]``.
"""
from __future__ import annotations

from harness import mla_work
from harness.tuning import run_job
from reference import mla_decode as ref
from reference.attention import BELOW, gaps


class Call:
    """The timed entry, jitted by the benchmark under a stable name."""

    module = "jit_mla_decode_call"

    def __init__(self, sizes: dict, traffic: dict, seed, scratch, devices):
        import jax

        from repro.kernels.mla_decode.ops import (mla_decode_layers,
                                                  softmax_scale)

        self.sizes, self.seed = sizes, seed
        self.shape = mla_work.shape(sizes)
        self.scale = softmax_scale(sizes["qk_nope_head_dim"],
                                   sizes["qk_rope_head_dim"])
        self.limits = sizes["limits"]
        self.kind = devices[0].device_kind
        self.inputs = int(traffic["inputs"])
        job = run_job(0, "mla_decode", dict(
            layers=self.shape["layers"], batch=sizes["batch"],
            heads=self.shape["heads"], kv_lora_rank=sizes["kv_lora_rank"],
            qk_rope_head_dim=sizes["qk_rope_head_dim"],
            qk_nope_head_dim=sizes["qk_nope_head_dim"],
            s_max=sizes["s_max"], min_len=sizes["min_len"],
            max_len=sizes["max_len"], order_seed=sizes["order_seed"],
            block_k_values=tuple(sizes["block_k"]),
            block_b_values=tuple(sizes["block_b"]), seed=[seed, 0]),
            scratch / "setup.evalstore", keep=False, traced=False)
        block_k, block_b = job.best["block_k"], job.best["block_b"]
        print(f"tuner chose block_k={block_k}, block_b={block_b}",
              flush=True)
        value_dim = self.shape["value_dim"]

        def mla_decode_call(qs, caches, lengths):
            return mla_decode_layers(qs, caches, lengths, block_k=block_k,
                                     block_b=block_b, scale=self.scale,
                                     value_dim=value_dim)

        self.caches, self.qs = self._instance()
        self.lengths = jax.device_put(self.shape["lengths"], devices[0])
        self.fn = jax.jit(mla_decode_call)
        for i in range(self.inputs):           # every input the window uses
            jax.block_until_ready(self.call(i))

    def _instance(self):
        """Each layer's cache, and each query set's queries per layer."""
        s = self.shape
        layers = range(s["layers"])
        caches = [ref.cache(s["batch"], s["width"], self.sizes["s_max"],
                            [self.seed, 0], lyr) for lyr in layers]
        qs = [[ref.queries(s["batch"], s["heads"], s["width"],
                           [self.seed, 1, p], lyr) for lyr in layers]
              for p in range(self.inputs)]
        return caches, qs

    def call(self, i: int):
        return self.fn(self.qs[i % self.inputs], self.caches, self.lengths)

    def least_time_s(self) -> float:
        return mla_work.least_time_s(self.kind, self.sizes)

    def release(self) -> None:
        self.caches = self.qs = self.lengths = self.fn = None

    def _reference(self, caches, qs) -> list:
        s = self.shape
        return [ref.attention(q, c, s["lengths"], self.scale,
                              s["value_dim"]) for q, c in zip(qs, caches)]

    def check(self, window) -> list:
        caches, qs = self._instance()
        refs: dict = {}
        worst = {"max_abs": 0.0, "rel_rms": 0.0}
        for i, ys in window.samples:
            p = i % self.inputs
            if p not in refs:
                refs[p] = self._reference(caches, qs[p])
            for y, r in zip(ys, refs[p]):
                for name, v in gaps(y, r).items():
                    worst[name] = max(worst[name], v)
        return [(f"mla_{k}", v, self.limits[f"mla_{k}"])
                for k, v in worst.items()]

    def control(self, window) -> dict:
        """The reference on queries and caches rounded one precision
        below the configuration's, against the reference."""
        below = BELOW[self.sizes["torch_dtype"]]
        caches, qs = self._instance()
        low = [ref.rounded(c, below) for c in caches]
        worst = {"max_abs": 0.0, "rel_rms": 0.0}
        for p in sorted({i % self.inputs for i, _ in window.samples}):
            outs = self._reference(low, [ref.rounded(q, below)
                                         for q in qs[p]])
            for out, r in zip(outs, self._reference(caches, qs[p])):
                for name, v in gaps(out, r).items():
                    worst[name] = max(worst[name], v)
        return {f"mla_{k}": v for k, v in worst.items()}
