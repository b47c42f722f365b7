"""attn_bwd_roofline: the least time the causal attention backward requires
at the cell's shape (``flops.attn_bwd_min``, FLOP-bound here), over the
summed device time of the backward kernels in the traced window.

Layer: attention kernels (``kernels/flash_bwd.py``: the backward kernel and
``fold_combine``). Moves train_tokens_per_s. The kernels carry no ``name=``;
their custom calls take the names of the jitted wrappers around them, and
are matched by those instruction names (``trace.short_name``): the
worker-parallel and serialized backward kernels, and the fixed-order fold
of the dQ partials (and of dK/dV per KV group).
"""
from bench import flops
from bench import trace as TR
from bench.models import dense_decoder as D

PATTERNS = (r"^_flash_bwd_worker_call\b", r"^_flash_bwd_call\b",
            r"^_fold_combine_call\b")


def read(ctx):
    ops = ctx.trace.clipped_ops(0)
    t_ns, n = TR.time_matching(ops, PATTERNS)
    steps = ctx.counts.get("steps")
    if n == 0 or not steps:
        return None
    m = D.dims(ctx.cfg)
    f, b = flops.attn_bwd_min(m, ctx.traffic["batch"], ctx.traffic["seq"])
    least, _ = flops.roofline_s(f, b, ctx.peaks)
    return 100.0 * least * m["layers"] * steps / (t_ns * 1e-9)
