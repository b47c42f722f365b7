"""step_ms.attention: device time a training step spends in the
program's ``layer.attention`` scope: the attention kernel calls: the
DASH forward, its recompute under remat, the deterministic backward and
the dQ fold (``models/layers._sdpa_full``).

Layer: attention kernels. Moves train_tokens_per_s. Milliseconds a step
on chip 0, read by ``bench/step_layers.py`` from the ops of the step
module that the compiled step maps to the scope, forward, recompute and
backward together.
"""
from bench import step_layers


def read(ctx):
    return step_layers.read(ctx, "attention")
