"""Operations and bytes the work of a cell requires, from its shapes.

These count what the algorithm needs, whatever implements it; they are the
numerators of the roofline and utilization shares. Sizes come from a
configuration file through ``models/<model>.dims``.
"""
from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Parameters that enter a matrix product per token: every layer's
    projections and MLP, and the output head. The embedding is a gather."""
    d, h, hk, hd, f = m["d"], m["h"], m["hk"], m["hd"], m["f"]
    per_layer = d * h * hd + 2 * d * hk * hd + h * hd * d + 3 * d * f
    return m["layers"] * per_layer + d * m["v"]


def causal_pairs(s: int) -> int:
    """(query, key) pairs a causal sequence of ``s`` tokens attends."""
    return s * (s + 1) // 2


def train_step_flops(m: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: 6 per matmul parameter per token
    (forward 2, backward 4), plus the causal score and value products
    forward (2 products of 2 FLOPs per pair per head dim) and backward
    (twice that). Recomputation does not count."""
    tokens = batch * seq
    attn = 12 * causal_pairs(seq) * m["hd"] * m["h"] * batch * m["layers"]
    return 6.0 * matmul_params(m) * tokens + attn


def attn_bwd_min(m: dict, batch: int, seq: int, elem_bytes: int = 2):
    """(FLOPs, bytes) the causal attention backward of one layer requires.

    FLOPs: five products per (query, key) pair and head dim, 2 FLOPs each:
    the scores recomputed from Q and K (P is not kept: it is S² per head),
    dP = dO·Vᵀ, dV = Pᵀ·dO, dQ = dS·K and dK = dSᵀ·Q.
    Bytes: Q, K, V, O and dO read once and dQ, dK and dV written once, in
    the model's type, plus the float32 log-sum-exp of each query row. The
    kernel's own dQ partials are its choice and are not counted.
    """
    b, h, hk, hd, s = batch, m["h"], m["hk"], m["hd"], seq
    flops = 10.0 * causal_pairs(s) * hd * b * h
    q_like = b * h * s * hd            # Q, O, dO, dQ
    kv_like = b * hk * s * hd          # K, V, dK, dV
    nbytes = elem_bytes * (4 * q_like + 4 * kv_like) + 4 * b * h * s
    return flops, float(nbytes)


def roofline_s(flops: float, nbytes: float, peaks: dict):
    """(least seconds, which bound) for the work on one chip."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
