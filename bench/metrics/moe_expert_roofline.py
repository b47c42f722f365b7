"""moe_expert_roofline: the least time the held experts' work of a step
requires (``flops_window_moe.experts_min``: FLOPs at the even router's
share, and the experts' weight and routed-row bytes), over
``step_ms.moe_experts``, the device time of the program's
``layer.moe_experts`` scope a step.

Layer: moe experts. Moves train_tokens_per_s. A program without the scope
gives nothing.
"""
from bench import flops
from bench import flops_window_moe as F
from bench import step_layers


def read(ctx):
    ms = step_layers.read(ctx, "moe_experts")
    if not ms:
        return None
    tokens = ctx.traffic["batch"] * ctx.traffic["seq"]
    least, _ = flops.roofline_s(*F.experts_min(ctx.cfg, tokens), ctx.peaks)
    return 100.0 * least / (ms * 1e-3)
