"""step_ms.optimizer: device time a training step spends in the
program's ``layer.optimizer`` scope: gradient clipping and the AdamW
update (``train/optimizer.opt_update``).

Layer: optimizer. Moves train_tokens_per_s. Milliseconds a step on chip
0, read by ``bench/step_layers.py`` from the ops of the step module that
the compiled step maps to the scope, forward, recompute and backward
together.
"""
from bench import step_layers


def read(ctx):
    return step_layers.read(ctx, "optimizer")
