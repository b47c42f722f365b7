"""moe_train_step_mfu: the whole train step's model FLOPs over the traced
window, as a share of the chip's peak bf16 FLOP/s, for a cell of window/
full attention and held-expert MoE layers.

Model FLOPs (``bench/flops_window_moe.train_step_flops``): 6 per
parameter per token for the attention projections, the router and the
head; 6 per expert parameter per slot routed to a held expert, at the even
router's share (tokens × top-k × held / router experts); 12 per attended
(query, key) pair per head dim per head, counting the pairs each layer's
mask leaves (windowed or causal). Recomputation does not count. Layer:
train step. Moves train_tokens_per_s.
"""
from bench import flops_window_moe as F


def read(ctx):
    steps = ctx.counts.get("steps")
    if (not steps or ctx.trace.window_s <= 0
            or not any(ctx.trace.ops.values())):
        return None
    per_step = F.train_step_flops(ctx.cfg, ctx.traffic["batch"],
                                  ctx.traffic["seq"])
    rate = per_step * steps / ctx.trace.window_s
    return 100.0 * rate / ctx.peaks["bf16_flops_per_s"]
