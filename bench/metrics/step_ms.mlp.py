"""step_ms.mlp: device time a training step spends in the program's
``layer.mlp`` scope: the MLP sublayers with their pre-norm
(``models/layers.apply_mlp``).

Layer: mlp. Moves train_tokens_per_s. Milliseconds a step on chip 0,
read by ``bench/step_layers.py`` from the ops of the step module that
the compiled step maps to the scope, forward, recompute and backward
together.
"""
from bench import step_layers


def read(ctx):
    return step_layers.read(ctx, "mlp")
