"""step_ms.head_loss: device time a training step spends in the
program's ``layer.head_loss`` scope: the final norm, the LM head and the
float32 cross-entropy over the vocabulary (``models/transformer``).

Layer: head_loss. Moves train_tokens_per_s. Milliseconds a step on chip
0, read by ``bench/step_layers.py`` from the ops of the step module that
the compiled step maps to the scope, forward, recompute and backward
together.
"""
from bench import step_layers


def read(ctx):
    return step_layers.read(ctx, "head_loss")
