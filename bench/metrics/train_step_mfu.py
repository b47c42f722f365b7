"""train_step_mfu: the whole train step's model FLOPs over the traced
window, as a share of the chip's peak bf16 FLOP/s.

Model FLOPs (``bench/flops.py``): 6 per matmul parameter per token with the
head and without the embedding gather, plus the causal score and value
products forward and backward; recomputation does not count. Steps: those
run inside the traced window (the whole measured window is traced). Layer:
train step (``train/step.py``, ``models/``). Moves train_tokens_per_s.
"""
from bench import flops
from bench.models import dense_decoder as D


def read(ctx):
    steps = ctx.counts.get("steps")
    if (not steps or ctx.trace.window_s <= 0
            or not any(ctx.trace.ops.values())):
        return None
    per_step = flops.train_step_flops(D.dims(ctx.cfg), ctx.traffic["batch"],
                                      ctx.traffic["seq"])
    rate = per_step * steps / ctx.trace.window_s
    return 100.0 * rate / ctx.peaks["bf16_flops_per_s"]
