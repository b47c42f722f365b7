"""step_ms.moe: device time a training step spends in the program's
``layer.moe`` scope less its nested ``layer.moe_experts``: the pre-norm,
the float32 router over all experts, the dispatch of routed slots to the
held experts' rows and the fixed-order combine, forward, recompute and
backward (``models/moe.apply_moe_held``).

Layer: moe. Moves train_tokens_per_s. Milliseconds a step on chip 0, read
by ``bench/step_layers.py`` (an op counts under its innermost scope). A
program without the scope (no held-expert MoE) gives nothing.
"""
from bench import step_layers


def read(ctx):
    return step_layers.read(ctx, "moe")
