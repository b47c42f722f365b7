"""Model assembly: decoder LMs, hybrid (attn/mamba/moe) stacks, xLSTM stacks,
encoder-decoder (whisper) and VLM (InternVL-style stub frontend).

Layer stacking uses ``lax.scan`` over *pattern repeats*: a config declares a
``block_pattern`` (e.g. jamba's ``("mamba","mamba_moe",…,"attn",…)``); parameters
are stacked (n_repeats, …) per pattern position, so the lowered HLO is O(pattern)
instead of O(n_layers) — essential for 80-layer configs on the 512-device dry-run.

Three entry modes share the block code:
  train/prefill:  full-sequence forward (optionally remat'd per repeat),
  decode:         one-token step threading a heterogeneous cache pytree,
with cross-attention (enc-dec) and frontend embeddings (audio/VLM stubs) handled
at the top level.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.dist import fold
from repro.dist.sharding import shard
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models import xlstm as X
from repro.models.module import ParamDef, init_tree, spec_tree, stacked
from repro.obs.layers import scope

F32 = jnp.float32


# --------------------------------------------------------------------------- #
# block definitions
# --------------------------------------------------------------------------- #
def _block_defs(cfg, kind: str):
    d = {}
    if kind in ("attn", "attn_moe", "attn_cross"):
        d["ln1"] = L.norm_defs(cfg)
        d["attn"] = L.attn_defs(cfg)
        if kind == "attn_cross":
            d["ln_x"] = L.norm_defs(cfg)
            d["xattn"] = L.attn_defs(cfg)
        d["ln2"] = L.norm_defs(cfg)
        d["moe" if kind == "attn_moe" else "mlp"] = (
            MOE.moe_defs(cfg) if kind == "attn_moe" else L.mlp_defs(cfg))
        if kind == "attn_moe" and cfg.n_shared_experts:
            d["shared_mlp"] = L.mlp_defs(cfg)
    elif kind in ("mamba", "mamba_moe"):
        d["ln1"] = L.norm_defs(cfg)
        d["mamba"] = M.mamba_defs(cfg)
        d["ln2"] = L.norm_defs(cfg)
        d["moe" if kind == "mamba_moe" else "mlp"] = (
            MOE.moe_defs(cfg) if kind == "mamba_moe" else L.mlp_defs(cfg))
    elif kind == "mlstm":
        d["ln1"] = L.norm_defs(cfg)
        d["mlstm"] = X.mlstm_defs(cfg)
    elif kind == "slstm":
        d["ln1"] = L.norm_defs(cfg)
        d["slstm"] = X.slstm_defs(cfg)
    else:
        raise ValueError(kind)
    return d


def _moe(p, x, cfg):
    """(y, aux_loss, routing counts or None)."""
    if cfg.moe_held:
        return MOE.apply_moe_held(p, x, cfg)
    fn = MOE.apply_moe_gather if cfg.moe_impl == "gather" else MOE.apply_moe
    return fn(p, x, cfg) + (None,)


def position_config(cfg, i: int):
    """The config position ``i`` of the block pattern runs under: with
    ``cfg.layer_windows``, its own ``attn_window`` (0: full attention),
    and YaRN rope only where it attends in full."""
    if not cfg.layer_windows:
        return cfg
    w = cfg.layer_windows[i]
    return cfg.replace(attn_window=w, rope_yarn=None if w else cfg.rope_yarn)


def _apply_block(p, x, cfg, kind: str, *, positions, cache, cache_pos, cross_x,
                 causal=True, paged=None, segment_ids=None):
    """Returns (x, new_cache, aux_loss, routing counts of a held-expert MoE
    layer or None)."""
    aux = jnp.zeros((), F32)
    routing = None
    new_cache: Dict[str, Any] = {}
    if kind in ("attn", "attn_moe", "attn_cross"):
        with scope("attn_proj"):
            h, c_attn = L.attention_block(
                p["attn"], L.apply_norm(p["ln1"], x, cfg), cfg,
                positions=positions,
                cache=None if cache is None else cache.get("attn"),
                cache_pos=cache_pos, causal=causal, paged=paged,
                segment_ids=segment_ids)
        x = x + h
        x = checkpoint_name(x, "attn_out")
        if c_attn is not None:
            new_cache["attn"] = c_attn
        if kind == "attn_cross":
            hx, _ = L.attention_block(
                p["xattn"], L.apply_norm(p["ln_x"], x, cfg), cfg,
                positions=positions, cross_x=cross_x, causal=False)
            x = x + hx
        with scope("moe" if kind == "attn_moe" and cfg.moe_held else "mlp"):
            y_in = checkpoint_name(
                L.apply_norm(p["ln2"], x, cfg), "ffn_in")
            if kind == "attn_moe":
                y, aux, routing = _moe(p["moe"], y_in, cfg)
                if cfg.n_shared_experts:
                    y = y + L.apply_mlp(p["shared_mlp"], y_in, cfg)
            else:
                y = L.apply_mlp(p["mlp"], y_in, cfg)
        x = x + y
    elif kind in ("mamba", "mamba_moe"):
        h, c_m = M.apply_mamba(p["mamba"], L.apply_norm(p["ln1"], x, cfg), cfg,
                               state=None if cache is None else cache.get("mamba"),
                               chunk=cfg.ssm_chunk)
        x = x + h
        x = checkpoint_name(x, "ssm_out")
        if cache is not None:
            new_cache["mamba"] = c_m
        y_in = L.apply_norm(p["ln2"], x, cfg)
        if kind == "mamba_moe":
            y, aux, routing = _moe(p["moe"], y_in, cfg)
        else:
            y = L.apply_mlp(p["mlp"], y_in, cfg)
        x = x + y
    elif kind == "mlstm":
        h, c_x = X.apply_mlstm(p["mlstm"], L.apply_norm(p["ln1"], x, cfg), cfg,
                               state=None if cache is None else cache.get("mlstm"))
        x = x + h
        if cache is not None:
            new_cache["mlstm"] = c_x
    elif kind == "slstm":
        h, c_x = X.apply_slstm(p["slstm"], L.apply_norm(p["ln1"], x, cfg), cfg,
                               state=None if cache is None else cache.get("slstm"))
        x = x + h
        if cache is not None:
            new_cache["slstm"] = c_x
    # Sequence-parallel residual stream: the scan carry (= the remat-saved
    # activation stack) lives sharded over the model axis along sequence.
    x = shard(x, "batch", "seq_sp", "act_embed")
    return x, (new_cache if cache is not None else None), aux, routing


# --------------------------------------------------------------------------- #
# parameter trees
# --------------------------------------------------------------------------- #
def param_defs(cfg):
    n_rep, rem = divmod(cfg.n_layers, len(cfg.block_pattern))
    assert rem == 0, (cfg.n_layers, cfg.block_pattern)
    defs: Dict[str, Any] = {
        "embed": L.embed_defs(cfg),
        "ln_f": L.norm_defs(cfg),
        "blocks": {f"b{i}_{kind}": stacked(_block_defs(cfg, kind), n_rep)
                   for i, kind in enumerate(cfg.block_pattern)},
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = L.lm_head_defs(cfg)
    if cfg.pos_embed == "learned":
        defs["pos_embed"] = ParamDef((cfg.max_seq, cfg.d_model), (None, "embed"))
    if cfg.encoder is not None:
        ecfg = cfg.encoder
        n_rep_e, rem_e = divmod(ecfg.n_layers, len(ecfg.block_pattern))
        assert rem_e == 0
        defs["encoder"] = {
            "frontend_proj": ParamDef((ecfg.frontend_dim, ecfg.d_model),
                                      (None, "embed"), "scaled"),
            "ln_f": L.norm_defs(ecfg),
            "blocks": {f"b{i}_{kind}": stacked(_block_defs(ecfg, kind), n_rep_e)
                       for i, kind in enumerate(ecfg.block_pattern)},
        }
        if ecfg.pos_embed == "learned":
            defs["encoder"]["pos_embed"] = ParamDef(
                (ecfg.frontend_len, ecfg.d_model), (None, "embed"))
    if cfg.frontend == "vision":
        defs["vision_proj"] = ParamDef((cfg.frontend_dim, cfg.d_model),
                                       (None, "embed"), "scaled")
    return defs


def init(cfg, key):
    return init_tree(param_defs(cfg), key, cfg.dtype)


def specs(cfg):
    return spec_tree(param_defs(cfg))


# --------------------------------------------------------------------------- #
# stack application (scan over repeats)
# --------------------------------------------------------------------------- #
REMAT_POLICIES = {
    "none": None,                                   # recompute everything
    "dots": jax.checkpoint_policies.dots_saveable,  # save MXU outputs
    # save only the (seq-sharded, small) block-boundary activations tagged in
    # _apply_block — bwd of sub-block i does not re-run sub-blocks < i
    "names": jax.checkpoint_policies.save_only_these_names(
        "attn_out", "ffn_in", "ssm_out"),
}


def _apply_stack(blocks, x, cfg, *, positions, caches, cache_pos, cross_x,
                 causal=True, remat=False, remat_policy="none", paged=None,
                 segment_ids=None):
    """blocks: dict of stacked param trees keyed 'b{i}_{kind}'.
    Returns (x, new caches, aux loss, {block key: routing counts stacked
    over repeats} of the held-expert MoE blocks)."""
    aux_total = jnp.zeros((), F32)
    new_caches = {} if caches is not None else None
    routing = {}
    for key_name in sorted(blocks, key=lambda s: int(s.split("_")[0][1:])):
        kind = key_name.split("_", 1)[1]
        stacked_p = blocks[key_name]
        bcfg = position_config(cfg, int(key_name.split("_")[0][1:]))

        def body(carry, scan_in, bcfg=bcfg, kind=kind):
            x_, aux_ = carry
            p_, cache_ = scan_in if caches is not None else (scan_in, None)
            x_, c_, a_, r_ = _apply_block(
                p_, x_, bcfg, kind, positions=positions, cache=cache_,
                cache_pos=cache_pos, cross_x=cross_x, causal=causal,
                paged=paged, segment_ids=segment_ids)
            return (x_, aux_ + a_), (c_, r_)

        if remat:
            body = jax.checkpoint(body, policy=REMAT_POLICIES[remat_policy])
        scan_xs = (stacked_p, caches[key_name]) if caches is not None else stacked_p
        n_rep = jax.tree.leaves(stacked_p)[0].shape[0]
        (x, aux_total), (cs, rs) = jax.lax.scan(
            body, (x, aux_total), scan_xs,
            unroll=n_rep if cfg.scan_unroll else 1)
        if caches is not None:
            new_caches[key_name] = cs
        if rs is not None:
            routing[key_name] = rs
    return x, new_caches, aux_total, routing


# --------------------------------------------------------------------------- #
# public entry points
# --------------------------------------------------------------------------- #
def _lm_logits(params, x, cfg):
    """Final-norm'd activations → vocab logits (tied or separate head)."""
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x,
                          params["embed"]["tok"].astype(cfg.dtype))
    return L.apply_lm_head(params["lm_head"], x, cfg)


def _encode(params, cfg, frames, remat=False):
    ecfg = cfg.encoder
    h = L.dot(frames, params["encoder"]["frontend_proj"]).astype(ecfg.dtype)
    if ecfg.pos_embed == "learned":
        h = h + params["encoder"]["pos_embed"][: h.shape[1]].astype(ecfg.dtype)
    h, _, _, _ = _apply_stack(params["encoder"]["blocks"], h, ecfg,
                              positions=jnp.arange(h.shape[1])[None, :],
                              caches=None, cache_pos=None, cross_x=None,
                              causal=False, remat=remat)
    return L.apply_norm(params["encoder"]["ln_f"], h, ecfg)


def _embed_inputs(params, cfg, batch):
    """Token (+frontend) embedding. batch: dict(tokens, [vision_embeds|frames])."""
    x = L.apply_embed(params["embed"], batch["tokens"], cfg)
    if cfg.frontend == "vision":
        vis = L.dot(batch["vision_embeds"], params["vision_proj"]).astype(cfg.dtype)
        x = jnp.concatenate([vis, x], axis=1)
    return x


def forward(params, batch, cfg, *, remat=False, remat_policy="none"):
    """Train/prefill forward → (logits, aux_loss). batch['tokens']: (B, S).

    Packed-document batches (``cfg.packed_inputs`` / the
    ``data.pipeline.pack_documents`` format) additionally carry
    ``positions`` (B, S) — RoPE restarts at 0 inside each document — and
    ``segment_ids`` (B, S) — cross-document attention is masked out.

    ``cfg.canonical_reductions = N`` switches the forward into serve-canonical
    mode (see :mod:`repro.dist.fold`): attention runs the literal paged-KV
    serve kernel over an N-token page walk and the row-parallel projections
    use the topology-invariant canonical fold, making these logits bitwise
    equal to ``ContinuousEngine`` chunked prefill at ``page_size=N``.
    """
    logits, aux, _ = _forward(params, batch, cfg, remat=remat,
                              remat_policy=remat_policy)
    return logits, aux


def _forward(params, batch, cfg, *, remat, remat_policy):
    """(logits, aux loss, routing counts of the held-expert MoE blocks)."""
    if cfg.canonical_reductions:
        with fold.canonical_scope(page_size=cfg.canonical_reductions):
            return _forward_body(params, batch, cfg, remat=remat,
                                 remat_policy=remat_policy)
    return _forward_body(params, batch, cfg, remat=remat,
                         remat_policy=remat_policy)


def _forward_body(params, batch, cfg, *, remat, remat_policy):
    x = _embed_inputs(params, cfg, batch)
    if cfg.pos_embed == "learned":
        x = x + params["pos_embed"][: x.shape[1]].astype(cfg.dtype)
    cross_x = (_encode(params, cfg, batch["frames"], remat=remat)
               if cfg.encoder else None)
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.arange(x.shape[1])[None, :]
    x, _, aux, routing = _apply_stack(
        params["blocks"], x, cfg, positions=positions, caches=None,
        cache_pos=None, cross_x=cross_x, remat=remat,
        remat_policy=remat_policy, segment_ids=batch.get("segment_ids"))
    with scope("head_loss"):
        x = L.apply_norm(params["ln_f"], x, cfg)
        logits = _lm_logits(params, x, cfg)
    if cfg.frontend == "vision":  # logits for text positions only
        logits = logits[:, -batch["tokens"].shape[1]:]
    return logits, aux, routing


def init_cache(cfg, batch_size: int, max_seq: int):
    """Cache pytree matching the scan structure (stacked over repeats)."""
    n_rep = cfg.n_layers // len(cfg.block_pattern)
    caches = {}
    hk, hd = cfg.n_kv_heads, cfg.head_dim
    d_in, _, d_state, k_conv = M.mamba_dims(cfg)
    for i, kind in enumerate(cfg.block_pattern):
        key_name = f"b{i}_{kind}"
        if kind.startswith("attn"):
            kv = lambda: jnp.zeros((n_rep, batch_size, max_seq, hk, hd), cfg.dtype)
            caches[key_name] = {"attn": (kv(), kv())}
        elif kind.startswith("mamba"):
            caches[key_name] = {"mamba": (
                jnp.zeros((n_rep, batch_size, k_conv - 1, d_in), cfg.dtype),
                jnp.zeros((n_rep, batch_size, d_in, d_state), F32))}
        elif kind == "mlstm":
            caches[key_name] = {"mlstm": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_rep,) + x.shape),
                X.mlstm_init_state(cfg, batch_size))}
        elif kind == "slstm":
            caches[key_name] = {"slstm": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_rep,) + x.shape),
                X.slstm_init_state(cfg, batch_size))}
    return caches


def prefill_step(params, batch, cfg, *, max_seq=None):
    """Prompt processing that also fills the caches.
    Returns (last-token logits (B,1,V), caches, cross_x|None)."""
    x = _embed_inputs(params, cfg, batch)
    if cfg.pos_embed == "learned":
        x = x + params["pos_embed"][: x.shape[1]].astype(cfg.dtype)
    cross_x = _encode(params, cfg, batch["frames"]) if cfg.encoder else None
    s = x.shape[1]
    caches = init_cache(cfg, x.shape[0], max_seq or s)
    positions = jnp.arange(s)[None, :]
    x, caches, _, _ = _apply_stack(params["blocks"], x, cfg,
                                   positions=positions, caches=caches,
                                   cache_pos=0, cross_x=cross_x)
    x = L.apply_norm(params["ln_f"], x[:, -1:], cfg)
    return _lm_logits(params, x, cfg), caches, cross_x


def decode_step(params, caches, tokens, cache_pos, cfg, *, cross_x=None):
    """One decode step. tokens: (B, 1); cache_pos: scalar index into the cache.
    Returns (logits (B,1,V), new_caches)."""
    x = L.apply_embed(params["embed"], tokens, cfg)
    if cfg.pos_embed == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(
            params["pos_embed"], cache_pos, 1, 0).astype(cfg.dtype)[None]
    positions = jnp.full((tokens.shape[0], 1), cache_pos, jnp.int32)
    x, new_caches, _, _ = _apply_stack(params["blocks"], x, cfg,
                                       positions=positions, caches=caches,
                                       cache_pos=cache_pos, cross_x=cross_x)
    x = L.apply_norm(params["ln_f"], x, cfg)
    return _lm_logits(params, x, cfg), new_caches


# --------------------------------------------------------------------------- #
# paged serving entry points (continuous batching; see repro.serve)
# --------------------------------------------------------------------------- #
def supports_paged(cfg) -> bool:
    """True iff the paged serving path covers this config: decoder-only with
    an attention-only pattern (the one capability rule — engine asserts it,
    ``init_paged_cache`` raises on it, examples filter with it)."""
    return (cfg.frontend is None and cfg.encoder is None
            and all(k == "attn" for k in cfg.block_pattern))


def init_paged_cache(cfg, n_pages: int, page_size: int):
    """Paged KV pools matching the scan structure: per attn block key,
    ``{"attn": (k_pages, v_pages)}`` of shape (n_rep, n_pages, page_size, Hk, D).

    Serving over pages is attention-only: SSM/xLSTM states are not paged, and
    MoE capacity routing is batch-*dependent* by construction (token dropping
    couples rows), which would break the batch-invariance contract.
    """
    bad = [k for k in cfg.block_pattern if k != "attn"]
    if bad:
        raise NotImplementedError(
            f"paged serving supports attention-only patterns; got {bad} "
            f"(SSM states are unpaged; MoE capacity routing is batch-coupled)")
    n_rep = cfg.n_layers // len(cfg.block_pattern)
    hk, hd = cfg.n_kv_heads, cfg.head_dim
    kv = lambda: jnp.zeros((n_rep, n_pages, page_size, hk, hd), cfg.dtype)
    return {f"b{i}_attn": {"attn": (kv(), kv())}
            for i in range(len(cfg.block_pattern))}


def paged_step(params, caches, tokens, positions, page_table, write_pages,
               write_offsets, cfg):
    """One paged serving step: a prefill chunk OR a batched one-token decode.

    tokens / positions: (B, L) token ids and absolute positions (L=1 for the
    cross-slot decode step; B=1, L=chunk for per-request chunked prefill).
    page_table: (B, max_pages) physical page per logical page slot.
    write_pages / write_offsets: (B·L,) token-major scatter targets for the
    fresh K/V (the engine points pad tokens at its trash page).
    Returns (logits (B, L, V), new caches).  Every op is row-independent and
    the KV reduction order is fixed (repro.kernels.decode), so a row's logits
    are a pure function of its own (params, tokens, positions, page history).

    Speculative decoding (repro.serve.spec) reuses this exact entry point in
    its L=1 decode shape, scanned k+1 times inside one jit (draft self-feed
    and teacher-forced verify alike).  Because each scan step writes its
    position's K/V before attending, and steps run in ascending position
    order, a rejected draft's stale K/V is always overwritten before any
    later query reads it — cache self-healing with no rollback pass.

    Always runs under :func:`repro.dist.fold.canonical_scope`: the serve-side
    row-parallel reductions (wo, w_down) take the canonical fold form at every
    topology, so the single-device engine and every TP degree agree bitwise
    (the sharded step builder re-enters the scope with its mesh axis; this
    local entry is then a no-op — outer wins).
    """
    with fold.canonical_scope():
        x = L.apply_embed(params["embed"], tokens, cfg)
        if cfg.pos_embed == "learned":
            x = x + params["pos_embed"][positions].astype(cfg.dtype)
        paged = dict(page_table=page_table, write_pages=write_pages,
                     write_offsets=write_offsets)
        x, new_caches, _, _ = _apply_stack(params["blocks"], x, cfg,
                                           positions=positions, caches=caches,
                                           cache_pos=0, cross_x=None,
                                           paged=paged)
        with scope("head_loss"):
            x = L.apply_norm(params["ln_f"], x, cfg)
            return _lm_logits(params, x, cfg), new_caches


def loss_fn(params, batch, cfg, *, remat=False, remat_policy="none"):
    """Next-token CE (+ MoE aux). batch: tokens (B,S), labels (B,S) with -100 pad.
    The metrics carry ``moe_routing`` where the model has held-expert MoE
    blocks (:func:`repro.models.moe.routing_event` reads it)."""
    logits, aux, routing = _forward(params, batch, cfg, remat=remat,
                                    remat_policy=remat_policy)
    labels = batch["labels"]
    # The (B,S,V) logits are sharded over (data, …, model/vocab). Both reductions
    # below are elementwise-masked sums over the vocab axis, which XLA fuses
    # (iota-compare-select-reduce) without materializing a gathered or fp32 copy —
    # a take_along_axis here would all-gather the vocab axis instead.
    with scope("head_loss"):
        viota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        gold = jnp.sum(jnp.where(viota == labels[..., None].clip(0),
                                 logits.astype(F32), 0.0), axis=-1)
        lse = jax.nn.logsumexp(logits.astype(F32), axis=-1)
        mask = (labels >= 0).astype(F32)
        ce = jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    metrics = {"ce": ce, "aux": aux}
    if routing:
        metrics["moe_routing"] = routing
    return ce + cfg.moe_aux_weight * aux, metrics
