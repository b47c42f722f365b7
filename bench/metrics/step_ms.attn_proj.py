"""step_ms.attn_proj: device time a training step spends in the
program's ``layer.attn_proj`` scope: the attention sublayers less their
kernel: pre-norm, q/k/v/o projections, biases and rotary
(``models/transformer._apply_block``,
``models/layers.attention_block``).

Layer: attn_proj. Moves train_tokens_per_s. Milliseconds a step on chip
0, read by ``bench/step_layers.py`` from the ops of the step module that
the compiled step maps to the scope, forward, recompute and backward
together.
"""
from bench import step_layers


def read(ctx):
    return step_layers.read(ctx, "attn_proj")
