"""attn_kernel_share: share of the device's busy time in the traced
training window spent in the DASH attention kernels (forward, backward and
``fold_combine``).

Layer: attention kernels (``kernels/flash_fwd.py``, ``kernels/flash_bwd.py``).
Moves train_tokens_per_s. The kernels carry no ``name=``; their custom
calls take the names of the jitted wrappers around them, and are matched by
those instruction names (``trace.short_name``):
"""
from bench import trace as TR

PATTERNS = (r"^flash_fwd\b", r"^_flash_bwd_worker_call\b",
            r"^_flash_bwd_call\b", r"^_fold_combine_call\b")


def read(ctx):
    ops = ctx.trace.clipped_ops(0)
    t_ns, n = TR.time_matching(ops, PATTERNS)
    busy = TR.busy_ns(ops)
    if n == 0 or busy <= 0:
        return None
    return 100.0 * t_ns / busy
