"""Transformer building blocks: norms, RoPE, GQA attention (train + cached decode +
cross), MLP variants. Pure functions over ParamDef-declared pytrees.

All matmuls run with fp32 accumulation (`preferred_element_type`); activations are
annotated with logical sharding axes via :func:`repro.dist.sharding.shard` so the
same model code lowers correctly under every rule set (TP / FSDP+TP / CP).
GQA runs native on every path: K/V tensors (and KV caches/pools) keep
``n_kv_heads`` heads — the attention ops group query heads instead of repeating
K/V, so llama4/qwen/nemotron-style configs pay no replication tax in HBM.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import fold
from repro.dist.sharding import shard
from repro.kernels.decode import paged_attention
from repro.kernels.ops import attention as attention_op
from repro.models.module import ParamDef as PD
from repro.obs.layers import scope

F32 = jnp.float32


def dot(x, w, out_dtype=None):
    return jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=out_dtype or F32)


# ----------------------------------------------------------------- norms
def norm_defs(cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": PD((d,), (None,), "ones", F32),
                "bias": PD((d,), (None,), "zeros", F32)}
    return {"scale": PD((d,), (None,), "ones", F32)}


def apply_norm(p, x, cfg, eps=1e-5):
    xf = x.astype(F32)
    if "bias" in p:  # layernorm
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:            # rmsnorm
        ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(x.dtype)


# ----------------------------------------------------------------- RoPE
def rope(x, positions, theta: float, pct: float = 1.0):
    """Rotary embedding on the leading `pct` fraction of head_dim.
    x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    dr = int(d * pct)
    if dr == 0:
        return x
    dr -= dr % 2
    xr, xp = x[..., :dr], x[..., dr:]
    half = dr // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=F32) / half)
    ang = positions.astype(F32)[..., None, None] * freqs        # (..., S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = xr[..., :half], xr[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.astype(x.dtype), xp], -1)


# ----------------------------------------------------------------- attention
def attn_defs(cfg, cross: bool = False):
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    heads_ax = "heads" if cfg.shard_heads else None
    kv_ax = "kv" if cfg.shard_kv else None
    p = {
        "wq": PD((d, h * hd), ("embed", heads_ax)),
        "wk": PD((d, hk * hd), ("embed", kv_ax)),
        "wv": PD((d, hk * hd), ("embed", kv_ax)),
        "wo": PD((h * hd, d), (heads_ax, "embed"), "scaled"),
    }
    if cfg.qkv_bias:
        p["bq"] = PD((h * hd,), (heads_ax,), "zeros")
        p["bk"] = PD((hk * hd,), (kv_ax,), "zeros")
        p["bv"] = PD((hk * hd,), (kv_ax,), "zeros")
    return p


def _project_qkv(p, xq, xkv, cfg, q_pos, kv_pos, use_rope=True):
    hd = cfg.head_dim
    q = dot(xq, p["wq"])
    k = dot(xkv, p["wk"])
    v = dot(xkv, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    # head counts come from the projection widths, not the config: under the
    # sharded serving step the params arrive column-sliced (h/tp, hk/tp heads)
    h, hk = q.shape[-1] // hd, k.shape[-1] // hd
    q = q.reshape(xq.shape[:-1] + (h, hd)).astype(cfg.dtype)
    k = k.reshape(xkv.shape[:-1] + (hk, hd)).astype(cfg.dtype)
    v = v.reshape(xkv.shape[:-1] + (hk, hd)).astype(cfg.dtype)
    if use_rope and cfg.rope_pct > 0:
        q = rope(q, q_pos, cfg.rope_theta, cfg.rope_pct)
        k = rope(k, kv_pos, cfg.rope_theta, cfg.rope_pct)
    return q, k, v


def _sdpa_full(q, k, v, cfg, causal, window=None, segment_ids=None):
    """(B,S,H,D)x(B,S,Hk,D) -> (B,S,H,D); dispatches to the configured impl.

    ``window`` (tokens) lowers as a :class:`repro.masks.spec.SlidingWindow`
    spec — on the pallas impl that compiles a block-sparse grid skipping every
    out-of-window tile; ``segment_ids`` (B, S) is the dynamic packed-document
    mask (xla path; see :func:`repro.kernels.ops.attention`).

    K/V stay at Hk heads end to end — both attention impls are GQA-native
    (kernel index maps / grouped einsums address KV by ``head // group``), so
    the group factor is saved in residuals, and the seq-shard all-gather below
    moves Hk/H of the bytes the old repeat-to-H path did.

    When the head count does not divide the model axis (shard_heads=False:
    llama4's 40, internvl's 14, whisper's 8 heads on tp=16), attention compute
    would replicate across all model ranks. Instead shard the *query sequence*
    over the model axis (k/v gathered): scores/out are seq-sharded — sequence-
    parallel attention, 16× less compute than replication at the cost of one
    k/v all-gather per layer (EXPERIMENTS.md §Perf, llama4 hillclimb h2)."""
    mask = None
    if window:
        from repro.masks.spec import SlidingWindow
        assert causal, "sliding windows assume causal self-attention"
        mask = SlidingWindow(int(window))
        causal = False  # the window spec subsumes causality
    qt = jnp.swapaxes(q, 1, 2)  # (B,H,S,D)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    seq_shard = not cfg.shard_heads and cfg.attn_seq_shard
    if seq_shard:
        qt = shard(qt, "batch", None, "seq_sp", None)
        kt = shard(kt, "batch", None, None, None)
        vt = shard(vt, "batch", None, None, None)
    with scope("attention"):
        out = attention_op(qt, kt, vt, causal=causal, impl=cfg.attention_impl,
                           schedule=cfg.dash_schedule, chunk_q=cfg.attn_chunk_q,
                           mask=mask, segment_ids=segment_ids)
    if seq_shard:
        out = shard(out, "batch", None, "seq_sp", None)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _sdpa_decode(q, k_cache, v_cache, valid_len, window=None):
    """One-step decode: q (B,1,H,D); caches (B,S,Hk,D); attends to
    [0, valid_len), or to the last ``window`` of it — matching
    masks.SlidingWindow's (q-w, q] semantics so windowed training and decode
    see the same distribution."""
    b, _, h, hd = q.shape
    s, hk = k_cache.shape[1], k_cache.shape[2]
    g = h // hk
    qg = q.reshape(b, 1, hk, g, hd)
    scores = jnp.einsum("bokgd,bskd->bkgs", qg.astype(F32),
                        k_cache.astype(F32)) / math.sqrt(hd)
    pos = jnp.arange(s)[None, None, None, :]
    visible = pos < valid_len
    if window:
        visible = jnp.logical_and(visible, pos >= valid_len - window)
    scores = jnp.where(visible, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v_cache.astype(F32))
    return out.reshape(b, 1, h, hd).astype(q.dtype)


def _canonical_paged_sdpa(q, k, v, cfg, window=None, segment_ids=None):
    """Training-side attention computed with the *literal serve kernel*.

    Fresh K/V are laid out as trivially-paged pools (logical page ``j`` of row
    ``b`` is pool page ``b·n_pg + j``) and reduced by the same fixed-order
    split-KV walk :func:`repro.kernels.decode.paged_attention` runs in the
    engine, at the page size carried by the canonical scope
    (``cfg.canonical_reductions``).  That makes the train forward bitwise
    equal to ``ContinuousEngine`` chunked prefill at the same ``page_size`` —
    the train≡serve half of the topology-invariance contract.

    Causality is taken over the **row index** (not the RoPE positions, which
    restart per document in packed batches): within a document row order and
    position order coincide, and ``segment_ids`` mask everything across
    documents — matching the engine, where each request is its own batch row
    with absolute positions.
    """
    b, s, hk, hd = k.shape
    ps = fold.scope_pages() or 16
    n_pg = -(-s // ps)
    pad = n_pg * ps - s

    def pool(t):   # (B, S, Hk, D) -> (B·n_pg, ps, Hk, D); pad rows masked out
        t = jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return t.reshape(b * n_pg, ps, hk, hd)

    table = (jnp.arange(b, dtype=jnp.int32)[:, None] * n_pg
             + jnp.arange(n_pg, dtype=jnp.int32)[None, :])
    qpos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    kv_seg = None
    if segment_ids is not None:
        kv_seg = jnp.pad(segment_ids.astype(jnp.int32), ((0, 0), (0, pad)),
                         constant_values=-1).reshape(b * n_pg, ps)
        segment_ids = segment_ids.astype(jnp.int32)
    with scope("attention"):
        return paged_attention(q, pool(k), pool(v), table, qpos,
                               window=window or None, q_segments=segment_ids,
                               kv_segments=kv_seg)


def attention_block(p, x, cfg, *, positions=None, cache=None, cache_pos=None,
                    causal=True, cross_x=None, window=None, paged=None,
                    segment_ids=None):
    """GQA attention. Modes:
      train/prefill: cache=None → full (causal or not) self/cross attention.
      decode:        cache=(k,v) (B,S,Hk,D), cache_pos scalar → 1-token step;
                     returns updated cache.
      paged:         cache=(k_pages, v_pages) pools, ``paged`` a dict with
                     ``page_table`` (B, max_pages), ``write_pages`` /
                     ``write_offsets`` (B·S,) token-major scatter targets —
                     fresh K/V are written into the pools, then the
                     batch-invariant fixed-order split-KV reduction runs
                     (:mod:`repro.kernels.decode`); serves both chunked prefill
                     and batched one-token decode.
      window:        optional sliding-window size in tokens (defaults to
                     ``cfg.attn_window``); honored on train/prefill (as a
                     masks.SlidingWindow spec), on cached decode (the score
                     mask keeps the last ``window`` positions) AND on the
                     paged serving path (the page walk masks out-of-window
                     lanes to exact zeros), so windowed training, generation
                     and serving all see the same distribution.
      segment_ids:   optional (B, S) packed-document ids (train/prefill);
                     cross-segment attention is masked out.
    Returns (y, new_cache).
    """
    if window is None and cfg.attn_window:
        window = cfg.attn_window
    b = x.shape[0]
    if positions is None:
        positions = jnp.arange(x.shape[1])[None, :]
    xkv = cross_x if cross_x is not None else x
    use_rope = cross_x is None
    kv_positions = positions if cross_x is None else (
        jnp.arange(xkv.shape[1])[None, :])

    if paged is not None:
        k_pages, v_pages = cache
        q, k, v = _project_qkv(p, x, x, cfg, positions, positions, use_rope=True)
        k_flat = k.reshape((-1,) + k.shape[2:]).astype(k_pages.dtype)
        v_flat = v.reshape((-1,) + v.shape[2:]).astype(v_pages.dtype)
        # unique_indices: every *live* token owns a distinct (page, offset)
        # pair by construction of the engine's write targets; duplicates only
        # ever land on the trash page, whose content is unreachable (the
        # kernel's position mask multiplies its lanes to exact zeros — proven
        # by the stale-pool/padding invariance tests), so the order-free
        # scatter is sound and passes verify.trace's unordered-scatter lint.
        k_pages = k_pages.at[paged["write_pages"], paged["write_offsets"]].set(
            k_flat, unique_indices=True)
        v_pages = v_pages.at[paged["write_pages"], paged["write_offsets"]].set(
            v_flat, unique_indices=True)
        # under TP the projections arrive column-sliced: this rank computes
        # h_loc = H/tp query heads. When the pool keeps more kv heads than
        # those queries need (kv heads replicated because they don't divide
        # the mesh axis), select the contiguous kv slice backing them.
        h_loc = q.shape[-2]
        g = cfg.n_heads // cfg.n_kv_heads
        kv_needed = max(1, h_loc // g)
        kp, vp = k_pages, v_pages
        if k_pages.shape[-2] != kv_needed:
            start = (jax.lax.axis_index(fold.scope_axis()) * h_loc) // g
            kp = jax.lax.dynamic_slice_in_dim(k_pages, start, kv_needed, -2)
            vp = jax.lax.dynamic_slice_in_dim(v_pages, start, kv_needed, -2)
        with scope("attention"):
            out = paged_attention(q, kp, vp, paged["page_table"], positions,
                                  window=window or None)
        out = out.reshape(x.shape[:-1] + (h_loc * cfg.head_dim,))
        # canonical fold (virtual shard = one head): the serve-side wo
        # reduction is identical at every TP degree including 1
        y = fold.canonical_row_dot(out, p["wo"], cfg.head_dim, out_dtype=x.dtype)
        return shard(y, "batch", "seq", "act_embed"), (k_pages, v_pages)

    if cache is None:
        q, k, v = _project_qkv(p, x, xkv, cfg, positions, kv_positions, use_rope)
        if fold.active() and causal and cross_x is None:
            out = _canonical_paged_sdpa(q, k, v, cfg, window=window,
                                        segment_ids=segment_ids)
        else:
            q = shard(q, "batch", "seq", "act_heads", None)
            out = _sdpa_full(q, k, v, cfg, causal and cross_x is None,
                             window=window if cross_x is None else None,
                             segment_ids=segment_ids if cross_x is None else None)
        new_cache = None
    else:
        k_cache, v_cache = cache
        q, k, v = _project_qkv(p, x, xkv, cfg, positions, kv_positions, use_rope)
        if cross_x is None:  # self-attention: append to cache
            k_cache = jax.lax.dynamic_update_slice(
                k_cache, k.astype(k_cache.dtype), (0, cache_pos, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(
                v_cache, v.astype(v_cache.dtype), (0, cache_pos, 0, 0))
        if x.shape[1] > 1:  # prefill-fill: full attention over the fresh k/v
            out = _sdpa_full(q, k, v, cfg, causal and cross_x is None,
                             window=window if cross_x is None else None)
        else:
            out = _sdpa_decode(q, k_cache, v_cache, cache_pos + 1,
                               window=window if cross_x is None else None)
        new_cache = (k_cache, v_cache)

    out = out.reshape(out.shape[:-2] + (out.shape[-2] * out.shape[-1],))
    if fold.active():
        y = fold.canonical_row_dot(out, p["wo"], cfg.head_dim, out_dtype=x.dtype)
    else:
        # row-parallel product emitted in bf16: the TP partial-sum all-reduce
        # then moves half the bytes (f32→bf16); MXU accumulates f32 internally.
        y = dot(out, p["wo"], out_dtype=x.dtype)
    return shard(y, "batch", "seq", "act_embed"), new_cache


# ----------------------------------------------------------------- MLP
def mlp_defs(cfg):
    d, f = cfg.d_model, cfg.d_ff
    p = {"w_up": PD((d, f), ("embed", "mlp")),
         "w_down": PD((f, d), ("mlp", "embed"), "scaled")}
    if cfg.activation in ("silu", "geglu"):
        p["w_gate"] = PD((d, f), ("embed", "mlp"))
    return p


def apply_mlp(p, x, cfg):
    up = dot(x, p["w_up"])
    if cfg.activation == "silu":
        h = jax.nn.silu(dot(x, p["w_gate"])) * up
    elif cfg.activation == "geglu":
        h = jax.nn.gelu(dot(x, p["w_gate"])) * up
    elif cfg.activation == "gelu":
        h = jax.nn.gelu(up)
    elif cfg.activation == "relu2":           # Nemotron-4 squared ReLU
        h = jnp.square(jax.nn.relu(up))
    else:
        raise ValueError(cfg.activation)
    h = shard(h.astype(x.dtype), "batch", "seq", "act_mlp")
    if fold.active():
        # canonical virtual grid for the down-projection: V = n_heads (a model
        # property, never the mesh), so d_ff must split evenly over it
        width, rem = divmod(cfg.d_ff, cfg.n_heads)
        assert rem == 0, (
            "canonical reductions need n_heads | d_ff", cfg.d_ff, cfg.n_heads)
        y = fold.canonical_row_dot(h, p["w_down"], width, out_dtype=x.dtype)
    else:
        y = dot(h, p["w_down"], out_dtype=x.dtype)
    return shard(y, "batch", "seq", "act_embed")  # bf16 row-parallel all-reduce


# ----------------------------------------------------------------- embeddings
def embed_defs(cfg):
    return {"tok": PD((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"))}


# one-hot transient budget for the deterministic embedding backward:
# block = ~2^25 fp32 elements (~128 MB) regardless of vocab size
_EMBED_BWD_ELEMS = 1 << 25


@functools.lru_cache(maxsize=None)
def _det_embed_lookup(vocab: int, dtype_name: str):
    """Embedding lookup with a deterministic backward.

    dtable = scatter-add(dy at tokens) ≡ one_hot(tokens)ᵀ @ dy, but the
    matmul's reduction association is pinned at compile time on every
    backend, where the scatter-add reduces duplicate tokens in
    backend-defined order (GPU atomics — the Fig. 1 baseline). fp32
    accumulation as everywhere. The token axis is processed in fixed-size
    blocks (ascending scan, ~128 MB one-hot transient each) so the
    determinism doesn't cost a (B·S, V) allocation at full vocab; block
    padding uses index == vocab, whose one-hot row is all-zero.
    """
    dtype = jnp.dtype(dtype_name)

    @jax.custom_vjp
    def lookup(table, tokens):
        return table[tokens]

    def fwd(table, tokens):
        return table[tokens], tokens

    def block_grad(tok_blk, dy_blk):
        onehot = jax.nn.one_hot(tok_blk, vocab, dtype=F32)
        return jax.lax.dot_general(onehot, dy_blk, (((0,), (0,)), ((), ())),
                                   preferred_element_type=F32)

    def bwd(tokens, dy):
        flat_tok = tokens.reshape(-1)
        flat_dy = dy.reshape(-1, dy.shape[-1]).astype(F32)
        t = flat_tok.shape[0]
        block = min(t, max(64, _EMBED_BWD_ELEMS // vocab))
        n_blocks = -(-t // block)
        if n_blocks == 1:
            dtable = block_grad(flat_tok, flat_dy)
        else:
            pad = n_blocks * block - t
            if pad:
                flat_tok = jnp.concatenate(
                    [flat_tok, jnp.full((pad,), vocab, flat_tok.dtype)])
                flat_dy = jnp.concatenate(
                    [flat_dy, jnp.zeros((pad, flat_dy.shape[1]), F32)])

            def acc(dtable, blk):
                tok_blk, dy_blk = blk
                return dtable + block_grad(tok_blk, dy_blk), None

            dtable, _ = jax.lax.scan(
                acc, jnp.zeros((vocab, flat_dy.shape[1]), F32),
                (flat_tok.reshape(n_blocks, block),
                 flat_dy.reshape(n_blocks, block, -1)))
        return dtable.astype(dtype), np.zeros(tokens.shape, jax.dtypes.float0)

    lookup.defvjp(fwd, bwd)
    return lookup


def apply_embed(p, tokens, cfg):
    with scope("embed"):
        table = p["tok"].astype(cfg.dtype)
        if cfg.det_embed_grad:
            emb = _det_embed_lookup(table.shape[0], str(table.dtype))(table,
                                                                      tokens)
        else:
            emb = table[tokens]
        return shard(emb, "batch", "seq", "act_embed")


def lm_head_defs(cfg):
    return {"w": PD((cfg.d_model, cfg.padded_vocab), ("embed", "vocab"))}


def apply_lm_head(p, x, cfg):
    return shard(dot(x, p["w"]), "batch", "seq", "vocab")
