"""step_ms.moe_experts: device time a training step spends in the
program's ``layer.moe_experts`` scope: the held experts' grouped products
(gate, up, SwiGLU and down over the rows routed to each held expert), with
their gradients and their recompute under remat
(``models/moe.apply_moe_held``).

Layer: moe experts. Moves train_tokens_per_s. Milliseconds a step on chip
0, read by ``bench/step_layers.py``. A program without the scope gives
nothing.
"""
from bench import step_layers


def read(ctx):
    return step_layers.read(ctx, "moe_experts")
