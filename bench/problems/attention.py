"""Causal self-attention at one model's attention shape: the tuning job
over the flash kernel's ``(block_q, block_k)`` grid.

Each job's instance (q, k, v) is the one the program's space factory
draws from the job's seed; the check draws it again with the
benchmark's own generator and compares every kept candidate's output
with the plain reference.
"""
from __future__ import annotations

from harness import work
from harness.peaks import least_time_s
from harness.tuning import exact_checks, run_job
from reference import attention as ref


class Tune:
    space = "flash_attention"
    module = "jit_mha"

    def __init__(self, sizes: dict, traffic: dict, seed, scratch, devices):
        self.shape = dict(batch=sizes["batch"],
                          heads=sizes["num_attention_heads"],
                          seq=sizes["seq"],
                          head_dim=(sizes["hidden_size"]
                                    // sizes["num_attention_heads"]))
        self.round_to = ref.BELOW[sizes["matmul_precision"]]
        self.limits = sizes["limits"]
        self.kind = devices[0].device_kind
        self.seed, self.scratch = seed, scratch
        self.kw = dict(self.shape, causal=True,
                       block_values=tuple(sizes["blocks"]))
        self.job(0, keep=False, traced=False)     # warms every shape

    def job(self, j: int, *, keep: bool, traced: bool):
        return run_job(j, self.space, dict(self.kw, seed=[self.seed, j]),
                       self.scratch / "job.evalstore", keep=keep,
                       traced=traced)

    def least_time_s(self) -> float:
        s = self.shape
        return least_time_s(self.kind, flops=work.causal_attention_flops(
            s["batch"], s["heads"], s["head_dim"], s["seq"]))

    def release(self) -> None:
        pass

    def _reference(self, j: int):
        q, k, v = ref.instance(*self.shape.values(), [self.seed, j])
        return ref.attention(q, k, v), (q, k, v)

    def check(self, window) -> list:
        worst = {"max_abs": 0.0, "rel_rms": 0.0}
        for job in window.samples:
            r, _ = self._reference(job.index)
            for out in job.outputs.values():
                for name, v in ref.gaps(out, r).items():
                    worst[name] = max(worst[name], v)
            job.outputs = None
        return [(f"attn_{k}", v, self.limits[f"attn_{k}"])
                for k, v in worst.items()] + exact_checks(window.records)

    def control(self, window) -> dict:
        worst = {"max_abs": 0.0, "rel_rms": 0.0}
        for job in window.samples:
            r, qkv = self._reference(job.index)
            for name, v in ref.gaps(ref.attention(*qkv, self.round_to),
                                    r).items():
                worst[name] = max(worst[name], v)
        return {f"attn_{k}": v for k, v in worst.items()}
