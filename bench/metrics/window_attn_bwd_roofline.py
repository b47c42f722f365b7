"""window_attn_bwd_roofline: the least time the attention backward of every
layer requires (``flops_window_moe.attn_bwd_min``, from the pairs each
layer's mask leaves: windowed or causal), over the summed device time of
the backward kernels in the traced window.

Layer: attention kernels (``kernels/flash_bwd.py``: the serialized and
worker-parallel backward kernels and ``fold_combine``, which folds the dQ
partials and the dK/dV of each query-head group). Matched by the names of
their jitted wrappers, as ``attn_bwd_roofline`` matches them. Moves
train_tokens_per_s.
"""
from bench import flops
from bench import flops_window_moe as F
from bench import trace as TR

PATTERNS = (r"^_flash_bwd_worker_call\b", r"^_flash_bwd_call\b",
            r"^_fold_combine_call\b")


def read(ctx):
    ops = ctx.trace.clipped_ops(0)
    t_ns, n = TR.time_matching(ops, PATTERNS)
    steps = ctx.counts.get("steps")
    if n == 0 or not steps:
        return None
    b, s = ctx.traffic["batch"], ctx.traffic["seq"]
    least = sum(flops.roofline_s(*F.attn_bwd_min(ctx.cfg, b, s, pairs),
                                 ctx.peaks)[0]
                for pairs in F.layer_pairs(ctx.cfg, s))
    return 100.0 * least * steps / (t_ns * 1e-9)
