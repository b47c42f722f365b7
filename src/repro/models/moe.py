"""Mixture-of-Experts FFN with a deterministic router and EP sharding.

Routing determinism (DESIGN.md §Arch-applicability): ``jax.lax.top_k`` breaks ties
by lowest index — a fixed, data-only function. Dispatch uses the Mesh-TensorFlow
one-hot einsum formulation with per-group capacity: tokens are grouped by the data
shards, the dispatch tensor is sharded (groups→data, experts→model/EP) so its
footprint stays local. The einsum dispatch burns extra FLOPs proportional to
``tokens·E·C·d`` — visible in the roofline's MODEL_FLOPS/HLO ratio; the
scatter-based alternative is a §Perf hillclimb (see EXPERIMENTS.md).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import shard
from repro.models.module import ParamDef as PD
from repro.obs.layers import scope

F32 = jnp.float32


def moe_defs(cfg):
    """Router over all ``n_experts``; expert weights for the held experts
    (``cfg.moe_held``), else for all of them."""
    d, f, e = cfg.d_model, cfg.d_ff, len(cfg.moe_held) or cfg.n_experts
    p = {
        "router": PD((d, cfg.n_experts), ("embed", None), "scaled", F32),
        "w_up": PD((e, d, f), ("experts", "embed", "mlp")),
        "w_down": PD((e, f, d), ("experts", "mlp", "embed"), "scaled"),
    }
    if cfg.activation in ("silu", "geglu"):
        p["w_gate"] = PD((e, d, f), ("experts", "embed", "mlp"))
    return p


def _act(h_gate, h_up, cfg):
    if cfg.activation == "silu":
        return jax.nn.silu(h_gate) * h_up
    if cfg.activation == "geglu":
        return jax.nn.gelu(h_gate) * h_up
    if cfg.activation == "relu2":
        return jnp.square(jax.nn.relu(h_up))
    return jax.nn.gelu(h_up)


def apply_moe(p, x, cfg) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) → (y, aux_loss).

    Groups = batch dim (sharded over data); capacity per group
    C = ceil(S · top_k / E · capacity_factor), rounded to 8 lanes.
    With ``cfg.moe_groups > 1``: token-parallel sub-groups along the sequence
    (sharded over (data, model)) — the one-hot/cumsum/einsum pipeline partitions
    cleanly under SPMD (unlike sort/gather), so tokens stay seq-sharded and the
    only model-axis collective is the expert all-to-all (GShard pattern).
    """
    b0, s0, d = x.shape
    gpr = cfg.moe_groups
    grouped = gpr > 1 and s0 % gpr == 0 and (s0 // gpr) * cfg.top_k >= cfg.n_experts
    if grouped:
        x = x.reshape(b0 * gpr, s0 // gpr, d)
        x = shard(x, "moe_group", None, "act_embed")
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = int(s * k / e * cfg.capacity_factor)
    cap = max(8, (cap + 7) // 8 * 8)

    logits = jnp.einsum("bsd,de->bse", x.astype(F32), p["router"])  # fp32 routing
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                   # deterministic
    if cfg.renorm_topk:
        gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # position of each (token, k) within its expert queue, in (s, k) scan order —
    # a pure function of the routing decisions → deterministic capacity drops.
    onehot = jax.nn.one_hot(gate_idx, e, dtype=F32)                 # (b,s,k,e)
    flat = onehot.reshape(b, s * k, e)
    pos_in_expert = (jnp.cumsum(flat, axis=1) - flat).reshape(b, s, k, e)
    pos = jnp.sum(pos_in_expert * onehot, -1)                       # (b,s,k)
    keep = pos < cap
    gate_vals = gate_vals * keep

    # dispatch (b,s,k,e,c) one-hot → combine weights; sharded (data, …, model, …)
    cap_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=F32) \
        * keep[..., None]                                           # (b,s,k,c)
    dispatch = jnp.einsum("bske,bskc->bsec", onehot, cap_oh)
    combine = jnp.einsum("bsk,bske,bskc->bsec", gate_vals, onehot, cap_oh)
    grp_ax = "moe_group" if grouped else "batch"
    dispatch = shard(dispatch, grp_ax, None, None, None)
    combine = shard(combine, grp_ax, None, None, None)

    xin = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(cfg.dtype), x)
    # groups→experts exchange (all-to-all under token-parallel grouping)
    xin = shard(xin, "experts", "batch" if not grouped else None, None,
                "act_embed")
    up = jnp.einsum("ebcd,edf->ebcf", xin, p["w_up"].astype(cfg.dtype))
    if "w_gate" in p:
        gate = jnp.einsum("ebcd,edf->ebcf", xin, p["w_gate"].astype(cfg.dtype))
    else:
        gate = up
    h = _act(gate, up, cfg).astype(cfg.dtype)
    h = shard(h, "experts", "batch", None, "act_mlp")
    out = jnp.einsum("ebcf,efd->ebcd", h, p["w_down"].astype(cfg.dtype))
    y = jnp.einsum("bsec,ebcd->bsd", combine.astype(cfg.dtype), out)

    # load-balancing aux loss (Switch-style), deterministic
    me = jnp.mean(probs, axis=(0, 1))                   # mean router prob per expert
    ce = jnp.mean(onehot[:, :, 0, :], axis=(0, 1))      # top-1 assignment fraction
    aux = e * jnp.sum(me * ce)
    y = y.astype(x.dtype)
    if grouped:
        y = shard(y, "moe_group", None, "act_embed").reshape(b0, s0, d)
        return shard(y, "batch", "seq_sp", "act_embed"), aux
    return shard(y, "batch", "seq", "act_embed"), aux


def apply_moe_gather(p, x, cfg) -> Tuple[jax.Array, jax.Array]:
    """Sort/gather ("megablocks-lite") dispatch — beyond-paper optimization.

    The einsum dispatch pays ~4·s·E·C·d FLOPs and materializes a (b,s,e,c)
    one-hot; this path replaces it with a stable argsort over expert ids and two
    gathers (≈0 FLOPs, O(s·d) traffic). Determinism: ``jnp.argsort`` is stable
    (ties by position), so capacity drops are the *same* deterministic set as the
    einsum path — results match bitwise up to dot-product association.
    See EXPERIMENTS.md §Perf (llama4/phi3.5 hillclimbs).

    With ``cfg.moe_groups > 1`` the sequence is split into token-parallel dispatch
    groups sharded over (data, model) — tokens never leave seq-sharded form
    (GShard-style), so the MoE branch needs NO sequence all-gather/reduce-scatter;
    the only model-axis collective is the expert all-to-all.
    """
    b0, s0, d = x.shape
    gpr = cfg.moe_groups
    if gpr > 1 and s0 % gpr == 0 and (s0 // gpr) * cfg.top_k >= cfg.n_experts:
        x = x.reshape(b0 * gpr, s0 // gpr, d)
        x = shard(x, "moe_group", None, "act_embed")
    b, s, _ = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = int(s * k / e * cfg.capacity_factor)
    cap = max(8, (cap + 7) // 8 * 8)
    sk = s * k

    logits = jnp.einsum("bsd,de->bse", x.astype(F32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)       # deterministic tie-break
    if cfg.renorm_topk:
        gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    eid = gate_idx.reshape(b, sk)                       # (b, sk) expert of each slot
    gates = gate_vals.reshape(b, sk)
    order = jnp.argsort(eid, axis=1, stable=True)       # slots grouped by expert
    inv = jnp.argsort(order, axis=1, stable=True)       # slot -> sorted position

    counts = jnp.sum(jax.nn.one_hot(eid, e, dtype=jnp.int32), axis=1)  # (b, e)
    starts = jnp.cumsum(counts, axis=1) - counts                        # exclusive

    # ---- dispatch: expert_in[b, e, c] = x[token of c-th routed slot of e] ----
    cpos = jnp.arange(cap)[None, None, :]
    src_slot = jnp.clip(starts[:, :, None] + cpos, 0, sk - 1)          # (b,e,cap)
    valid_in = cpos < counts[:, :, None]
    tok_of_sorted = jnp.take_along_axis(order, src_slot.reshape(b, e * cap), 1)
    tok_idx = tok_of_sorted // k                                       # (b, e*cap)
    xin = jnp.take_along_axis(x, tok_idx[..., None], axis=1)           # (b,e*cap,d)
    xin = xin.reshape(b, e, cap, d) * valid_in[..., None].astype(x.dtype)
    xin = jnp.transpose(xin, (1, 0, 2, 3))                             # (e,b,cap,d)
    # groups→experts exchange: with token-parallel groups this is the all-to-all
    xin = shard(xin, "experts", "batch" if gpr == 1 else None, None, "act_embed")

    up = jnp.einsum("ebcd,edf->ebcf", xin, p["w_up"].astype(cfg.dtype))
    gate_h = (jnp.einsum("ebcd,edf->ebcf", xin, p["w_gate"].astype(cfg.dtype))
              if "w_gate" in p else up)
    h = _act(gate_h, up, cfg).astype(cfg.dtype)
    h = shard(h, "experts", "batch", None, "act_mlp")
    out = jnp.einsum("ebcf,efd->ebcd", h, p["w_down"].astype(cfg.dtype))

    # ---- combine: slot's output lives at (eid, rank) if rank < cap ----
    rank = jnp.take_along_axis(inv, jnp.arange(sk)[None, :], 1) \
        - jnp.take_along_axis(starts, eid, 1)                          # (b, sk)
    keep = rank < cap
    slot = jnp.clip(eid * cap + rank, 0, e * cap - 1)
    out_flat = jnp.transpose(out, (1, 0, 2, 3)).reshape(b, e * cap, d)
    y_slots = jnp.take_along_axis(out_flat, slot[..., None], axis=1)   # (b,sk,d)
    y_slots = y_slots * (gates * keep)[..., None].astype(cfg.dtype)
    y = jnp.sum(y_slots.reshape(b, s, k, d), axis=2)

    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jax.nn.one_hot(gate_idx[:, :, 0], e, dtype=F32), axis=(0, 1))
    aux = e * jnp.sum(me * ce)
    y = y.astype(x.dtype)
    if gpr > 1 and b != b0:
        y = shard(y, "moe_group", None, "act_embed")
        y = y.reshape(b0, s0, d)   # back to the seq-sharded residual layout
        return shard(y, "batch", "seq_sp", "act_embed"), aux
    return shard(y, "batch", "seq", "act_embed"), aux


# --------------------------------------------------------------------------- #
# one chip's expert-parallel share: held experts, no token dropped
# --------------------------------------------------------------------------- #
@jax.custom_vjp
def _dispatch(xt, src, valid, pos, mask):
    """Rows (N, D) of the grouped buffer: row r holds token ``src[r]``
    where ``valid[r]``, else zeros. Its backward gathers each token's rows
    (``pos``, where ``mask``) and sums them in ascending held-expert order:
    no scatter-add."""
    return jnp.where(valid[:, None], jnp.take(xt, src, axis=0), 0)


def _dispatch_fwd(xt, src, valid, pos, mask):
    return _dispatch(xt, src, valid, pos, mask), (pos, mask)


def _dispatch_bwd(res, g):
    pos, mask = res
    dx = jnp.zeros((pos.shape[0], g.shape[1]), F32)
    for j in range(pos.shape[1]):
        dx = dx + jnp.where(mask[:, j, None],
                            jnp.take(g, pos[:, j], axis=0).astype(F32), 0.0)
    return dx.astype(g.dtype), None, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out, gates, src, row_expert, valid, pos, mask):
    """y[t] = Σ_j gates[t, j] · out[pos[t, j]] over the held experts j that
    token t routed to, summed in ascending j, in float32. The backward
    gathers too: d_out[r] = gates[src[r], row_expert[r]] · dy[src[r]]."""
    y = jnp.zeros((pos.shape[0], out.shape[1]), F32)
    for j in range(pos.shape[1]):
        y = y + jnp.where(mask[:, j, None], gates[:, j, None]
                          * jnp.take(out, pos[:, j], axis=0).astype(F32), 0.0)
    return y


def _combine_fwd(out, gates, src, row_expert, valid, pos, mask):
    return (_combine(out, gates, src, row_expert, valid, pos, mask),
            (out, gates, src, row_expert, valid, pos, mask))


def _combine_bwd(res, dy):
    out, gates, src, row_expert, valid, pos, mask = res
    g_row = gates[src, row_expert]
    d_out = jnp.where(valid[:, None],
                      g_row[:, None] * jnp.take(dy, src, axis=0), 0.0)
    d_gates = jnp.stack(
        [jnp.where(mask[:, j], jnp.sum(
            dy * jnp.take(out, pos[:, j], axis=0).astype(F32), -1), 0.0)
         for j in range(pos.shape[1])], axis=1)
    return (d_out.astype(out.dtype), d_gates, None, None, None, None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


def apply_moe_held(p, x, cfg):
    """The part of an MoE layer that the experts ``cfg.moe_held`` give, as
    one chip of an expert-parallel deployment computes it.
    x: (B, S, D) -> (y, aux_loss, routing counts).

    The router is the whole layer's: float32 softmax over all
    ``n_experts``, top ``top_k`` (ties to the lowest index), renormalised
    over the k (``renorm_topk``). Every slot routed to a held expert is
    computed: the grouped buffer has a row for each (token, held expert)
    pair, so no capacity can drop one. Rows are ordered by held expert,
    then token (a stable sort), and the products are grouped matmuls
    (``lax.ragged_dot``) over the held experts' rows only. The combine
    weights each expert's output by its gate and sums a token's experts in
    ascending order (:func:`_combine`); neither direction scatters.

    aux: Switch-style load balance over all experts, n_experts · Σ_e f_e ·
    P_e, with f_e the share of tokens that route a slot to e (Σ_e f_e =
    top_k) and P_e the mean router probability. Routing counts, per held
    expert: ``load`` (slots routed to it); ``elsewhere`` (slots routed to
    experts another chip holds) and ``dropped`` (slots the buffer could not
    hold: 0 by construction). The grouped products run in the
    ``moe_experts`` layer scope, inside the block's ``moe`` scope.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    nh, t = len(cfg.moe_held), b * s
    xt = x.reshape(t, d)
    logits = jnp.dot(xt.astype(F32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)    # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    # top_k picks the experts; the gates are read from probs by mask and
    # static slices, so that no gradient passes through top_k's scatter
    _, gate_idx = jax.lax.top_k(probs, k)
    chosen = jnp.sum(jax.nn.one_hot(gate_idx, e, dtype=F32), axis=1)  # (T, E)
    held_of = lambda a: jnp.stack([a[:, i] for i in cfg.moe_held], axis=1)
    mask = held_of(chosen) > 0                               # (T, J)
    gates = jnp.where(mask, held_of(probs), 0.0)
    if cfg.renorm_topk:
        gates = gates / jnp.sum(probs * chosen, -1, keepdims=True)

    # grouped rows: held expert major, token minor; unrouted pairs last
    counts = jnp.sum(mask, axis=0, dtype=jnp.int32)          # (J,)
    starts = jnp.cumsum(counts) - counts
    key = jnp.where(mask.T, jnp.arange(nh, dtype=jnp.int32)[:, None], nh)
    order = jnp.argsort(key.reshape(-1), stable=True)        # (J*T,)
    src, row_expert = order % t, order // t
    total = jnp.sum(counts)
    valid = jnp.arange(nh * t) < total
    pos = (starts[None, :]
           + jnp.cumsum(mask, axis=0, dtype=jnp.int32) - 1)  # (T, J)
    pos = jnp.where(mask, pos, 0)

    xin = _dispatch(xt, src, valid, pos, mask)               # (J*T, D)
    with scope("moe_experts"):
        wd = cfg.dtype
        up = jax.lax.ragged_dot(xin, p["w_up"].astype(wd), counts,
                                preferred_element_type=F32)
        gate_h = (jax.lax.ragged_dot(xin, p["w_gate"].astype(wd), counts,
                                     preferred_element_type=F32)
                  if "w_gate" in p else up)
        h = _act(gate_h, up, cfg).astype(wd)
        out = jax.lax.ragged_dot(h, p["w_down"].astype(wd), counts,
                                 preferred_element_type=wd)
    y = _combine(out, gates, src, row_expert, valid, pos, mask)

    aux = e * jnp.sum(jnp.mean(chosen, axis=0) * jnp.mean(probs, axis=0))
    routing = {"load": counts,
               "elsewhere": jnp.int32(t * k) - total,
               "dropped": total - jnp.sum(valid, dtype=jnp.int32)}
    return y.astype(x.dtype).reshape(b, s, d), aux, routing


def routing_event(routing) -> dict:
    """The ``moe_routing`` event of one step from its metrics'
    ``moe_routing`` ({block: counts stacked over repeats}): routed slots per
    held expert and layer, the max and mean load, slots routed to experts
    elsewhere and dropped slots, summed over layers. Host-side."""
    load = np.concatenate([np.asarray(r["load"]) for r in routing.values()])
    return {"load": load.tolist(), "max_load": int(load.max()),
            "mean_load": float(load.mean()),
            "elsewhere": int(sum(np.sum(r["elsewhere"])
                                 for r in routing.values())),
            "dropped": int(sum(np.sum(r["dropped"])
                               for r in routing.values()))}
