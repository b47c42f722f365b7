"""device_idle.train: share of the traced training window in which no
operation ran on the device (1 - union of device op intervals / window).

Layer: device, under the host loop that dispatches the train step. Moves
train_tokens_per_s. Reads every event of the device planes' ``XLA Ops``
line; no name pattern.
"""
from bench import trace as TR


def read(ctx):
    busy_s, window_s = TR.device_busy(ctx.trace)
    if window_s <= 0 or not any(ctx.trace.ops.values()):
        return None
    return 100.0 * (1.0 - busy_s / window_s)
