"""step_ms.embed: device time a training step spends in the program's
``layer.embed`` scope: the token embedding: the gather forward and the
deterministic one-hot matmul backward (``models/layers.apply_embed``).

Layer: embed. Moves train_tokens_per_s. Milliseconds a step on chip 0,
read by ``bench/step_layers.py`` from the ops of the step module that
the compiled step maps to the scope, forward, recompute and backward
together.
"""
from bench import step_layers


def read(ctx):
    return step_layers.read(ctx, "embed")
