"""step_ms.unscoped: device time a training step spends in ops that no
layer scope of the program names: residual adds, the reshapes and copies
between layers, the gradient's loop carries, and any op the map lacks.

Layer: train step. Moves train_tokens_per_s. Milliseconds a step on chip
0, read by ``bench/step_layers.py``; with the six ``step_ms.<layer>``
metrics it sums to the step module's device time a step.
"""
from bench import step_layers


def read(ctx):
    return step_layers.read(ctx, step_layers.UNSCOPED)
