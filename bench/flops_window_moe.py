"""Operations and bytes the work of a window/full attention, held-expert
MoE cell requires, from its configuration file and traffic.

These count what the algorithm needs, whatever implements it: the
numerators of ``moe_train_step_mfu``, ``window_attn_bwd_roofline`` and
``moe_expert_roofline``. Sizes come from ``models/window_moe_decoder.dims``.
"""
from __future__ import annotations

from bench.models import window_moe_decoder as W


def attended_pairs(seq: int, window: int = 0) -> int:
    """(query, key) pairs one sequence attends: causal, or the keys in
    (q - window, q]."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_pairs(cfg: dict, seq: int):
    """Attended pairs of one sequence in each layer."""
    m = W.dims(cfg)
    return [attended_pairs(seq, m["window"] if t == "sliding_attention"
                           else 0) for t in m["types"]]


def dense_params(cfg: dict) -> int:
    """Parameters that every token passes through a matrix product: the
    attention projections, the router and the head (the embedding is a
    gather)."""
    m = W.dims(cfg)
    d, h, hk, hd = m["d"], m["h"], m["hk"], m["hd"]
    per_layer = 2 * d * h * hd + 2 * d * hk * hd + d * m["e"]
    return m["layers"] * per_layer + d * m["v"]


def routed_slots(cfg: dict, tokens: int) -> float:
    """Slots a step routes to the held experts at the even router's share:
    tokens × top-k × held / router experts, per layer."""
    m = W.dims(cfg)
    return tokens * m["k"] * len(m["held"]) / m["e"]


def expert_params(cfg: dict) -> int:
    """Parameters of one SwiGLU expert."""
    m = W.dims(cfg)
    return 3 * m["d"] * m["f"]


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one step: 6 per parameter per token for the dense
    products, 6 per expert parameter per routed slot for the held experts,
    and 12 per attended (query, key) pair per head dim per head (score and
    value products, forward 2 × 2, backward twice that). Recomputation
    does not count."""
    m = W.dims(cfg)
    tokens = batch * seq
    experts = 6.0 * expert_params(cfg) * routed_slots(cfg, tokens) * m["layers"]
    attn = 12.0 * sum(layer_pairs(cfg, seq)) * m["hd"] * m["h"] * batch
    return 6.0 * dense_params(cfg) * tokens + experts + attn


def attn_bwd_min(cfg: dict, batch: int, seq: int, pairs: int,
                 elem_bytes: int = 2):
    """(FLOPs, bytes) one layer's attention backward requires for
    ``pairs`` attended pairs a sequence: five products per pair and head
    dim (scores recomputed, dP, dV, dQ, dK), 2 FLOPs each; Q, K, V, O and
    dO read and dQ, dK, dV written once in the model's type, and the
    float32 log-sum-exp of each query row."""
    m = W.dims(cfg)
    b, h, hk, hd = batch, m["h"], m["hk"], m["hd"]
    flops = 10.0 * pairs * hd * b * h
    q_like, kv_like = b * h * seq * hd, b * hk * seq * hd
    return flops, float(elem_bytes * (4 * q_like + 4 * kv_like)
                        + 4 * b * h * seq)


def experts_min(cfg: dict, tokens: int, elem_bytes: int = 2):
    """(FLOPs, bytes) a step of the held experts requires, all layers:
    6 FLOPs per expert parameter per routed slot; bytes: each held
    expert's weights read forward and backward and their gradient written,
    and per routed slot its input row read forward and backward, its
    output row written and the output's gradient read, and its hidden row
    (gate and up, then the product) written and read back, in the model's
    type."""
    m = W.dims(cfg)
    slots = routed_slots(cfg, tokens)
    weights = 3 * expert_params(cfg) * len(m["held"])
    rows = slots * (4 * m["d"] + 4 * m["f"])
    flops = 6.0 * expert_params(cfg) * slots * m["layers"]
    return flops, float(elem_bytes * (weights + rows) * m["layers"])
