"""Public attention op: jit'd custom_vjp wrapper around the DASH kernels.

``dash_attention(q, k, v, causal=..., schedule=..., mask=...)`` runs the Pallas
forward and the schedule-driven deterministic Pallas backward; ``mask`` takes
any :class:`repro.masks.spec.MaskSpec` (``causal=True`` is sugar for
``mask=Causal()``) and compiles a block-sparse grid + ragged schedule keyed by
the spec hash.  ``attention(..., impl=...)`` is the model-facing dispatcher:

  impl="xla"     — reference jnp attention (used by model code on CPU, in smoke
                   tests and in the multi-pod dry-run, where a custom kernel would
                   obscure cost_analysis and explode CPU compile times);
  impl="pallas"  — the DASH kernels: tested in interpret mode on CPU, and run
                   on a TPU v5e chip against the f32 reference and through
                   the train step (chip_smoke.py).

Public shapes are (batch, heads, seq, head_dim). GQA is **native** on both
paths: K/V keep their (batch, kv_heads, seq, head_dim) shape end to end — no
``jnp.repeat`` materialization, group-factor less KV residual memory — and the
kernels/einsums address KV by ``query_head // group``. dK/dV reduce per KV head
in ascending query-head order (fixed-order fold; deterministic).
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.schedules import cached_schedule, resolve_schedule
from repro.kernels import ref as ref_mod
from repro.kernels.flash_bwd import dq_bytes, flash_bwd, worker_grid
from repro.kernels.flash_fwd import flash_fwd
from repro.kernels.gqa import validate_group
from repro.kernels.vmem import best_block

# The tiling the last traced dash_attention call resolved (``last_tiling``)
# and the tracker ``log_tilings`` arms to receive each one. Host-side only:
# written while a call is traced, never read by the computation.
_TILING = {"last": None, "tracker": None}


def _flatten(x):  # (B, H, S, D) -> (BH, S, D)
    b, h, s, d = x.shape
    return x.reshape(b * h, s, d)


def _unflatten(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _dash_attention(q, k, v, causal, schedule_name, sm_scale, block, interpret,
                    mask, worker_parallel):
    out, _ = _fwd_impl(q, k, v, causal, sm_scale, block, interpret, mask)
    return out


def _fwd_impl(q, k, v, causal, sm_scale, block, interpret, mask=None):
    """q (B,H,S,D), k/v (B,Hk,S,D) — flattened here, never head-repeated."""
    b, h = q.shape[0], q.shape[1]
    out, lse = flash_fwd(_flatten(q), _flatten(k), _flatten(v), causal=causal,
                         sm_scale=sm_scale, block_q=block, block_k=block,
                         interpret=interpret, n_heads=h, n_kv_heads=k.shape[1],
                         mask=mask)
    return _unflatten(out, b, h), lse


def _fwd_rule(q, k, v, causal, schedule_name, sm_scale, block, interpret,
              mask, worker_parallel):
    out, lse = _fwd_impl(q, k, v, causal, sm_scale, block, interpret, mask)
    # residuals keep K/V at Hk heads: group-factor less residual memory vs the
    # old repeat-to-H path.
    return out, (q, k, v, out, lse)


def _bwd_rule(causal, schedule_name, sm_scale, block, interpret, mask,
              worker_parallel, res, do):
    q, k, v, out, lse = res
    b, h = q.shape[0], q.shape[1]
    hk = k.shape[1]
    n = q.shape[2] // block
    # cached_schedule's key includes the mask spec (hashable): two distinct
    # block-sparse masks with equal tile counts never share a schedule.
    schedule = cached_schedule(schedule_name, n, n_heads=1, causal=causal,
                               mask=mask, block_q=block, block_k=block)
    dq, dk, dv = flash_bwd(_flatten(q), _flatten(k), _flatten(v),
                           _flatten(out), lse, _flatten(do), schedule,
                           causal=causal, sm_scale=sm_scale, block_q=block,
                           block_k=block, interpret=interpret,
                           n_heads=h, n_kv_heads=hk, mask=mask,
                           worker_parallel=worker_parallel)
    return (_unflatten(dq, b, h).astype(q.dtype),
            _unflatten(dk, b, hk).astype(k.dtype),
            _unflatten(dv, b, hk).astype(v.dtype))


_dash_attention.defvjp(_fwd_rule, _bwd_rule)


def last_tiling() -> Optional[dict]:
    """What the last ``dash_attention`` call traced in this process runs
    on: its ``seq``, ``head_dim`` and ``dtype``, the ``mask`` (``causal``,
    ``full`` or the spec) with its ``window`` (a ``SlidingWindow``'s, else
    None), the square ``block``, the schedule and its (``n_kv``, ``n_q``)
    tiles, and the backward's ``realization``, ``grid`` with its
    ``real_steps`` and no-op ``sentinel_steps``, and ``dq_partial_bytes``:
    the float32 dQ buffers it keeps (one per worker, or the serialized
    realization's one); per (batch, head), ``computed_pairs``, every pair
    of the tiles the kernels visit, and ``attended_pairs``, the pairs the
    mask admits."""
    return _TILING["last"]


@contextlib.contextmanager
def log_tilings(tracker):
    """Log the tiling of every ``dash_attention`` call traced inside the
    block into ``tracker``, one ``"attn_tiling"`` event each."""
    prev, _TILING["tracker"] = _TILING["tracker"], tracker
    try:
        yield tracker
    finally:
        _TILING["tracker"] = prev


def dash_attention(q, k, v, causal: bool = False,
                   schedule: str = "symmetric_shift_or_shift",
                   sm_scale: Optional[float] = None,
                   block: Optional[int] = None,
                   interpret: bool = False, mask=None,
                   worker_parallel: bool = True):
    """DASH attention with deterministic scheduled backward.

    Args:
      q: (B, H, S, D); k, v: (B, Hk, S, D) with H a multiple of Hk (native GQA —
        KV heads are addressed by group, never repeated).
      causal: sugar for ``mask=repro.masks.Causal()``.
      mask: optional :class:`repro.masks.spec.MaskSpec`. ``Full()``/``Causal()``
        normalize onto the registry-schedule fast paths (bitwise identical to
        the flag form); any other spec compiles a block-sparse grid + schedule
        (EMPTY tiles skipped, PARTIAL tiles mask-multiplied) keyed by the spec.
      schedule: "fa3" | "descending" | "shift" | "symmetric_shift" |
        "symmetric_shift_or_shift" (pick the paper-optimal one for the mask).
        For block-sparse masks this selects the *placement*: "shift" (the
        generalized optimum) or "fa3" (ascending baseline).
      block: square tile size (MXU-aligned). By default the largest of
        (512, 256, 128) that divides S and whose forward and backward VMEM
        footprints fit (:func:`repro.kernels.vmem.best_block`, from S, the
        head dim and the dtype); under a block-sparse ``mask``, also the
        largest whose computed pairs (its live tiles, PARTIAL ones whole)
        stay within ``vmem.MASK_PAIRS_MAX_RATIO`` of the 128 tiling's.
      worker_parallel: realize the backward across schedule worker chains
        (bitwise-equal to serialized when the schedule is single-visit;
        :func:`repro.kernels.flash_bwd.worker_grid` degrades to serialized
        otherwise).
    Returns: (B, H, S, D) attention output.
    """
    b, h, s, d = q.shape
    validate_group(h, k.shape[1])
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if mask is not None:
        from repro.masks.spec import Causal, Full
        # Full/Causal are exactly the paper masks: route to the registry
        # schedules (causal=flag) so the spec form is bitwise the flag form.
        if isinstance(mask, Full):
            causal, mask = False, None
        elif isinstance(mask, Causal):
            causal, mask = True, None
        else:
            assert not causal, "mask supersedes the causal flag"
    if block is None:
        block = best_block(s, d, q.dtype.itemsize, causal, mask=mask)
    schedule = resolve_schedule(schedule, causal, mask)
    if mask is not None and schedule not in ("shift", "fa3"):
        raise ValueError(
            f"block-sparse masks take placement 'shift' or 'fa3'; got "
            f"{schedule!r}")
    if s % block:
        raise ValueError(f"sequence length {s} is not a multiple of the "
                         f"{block} tile")
    _record_tiling(q, causal, schedule, block, mask, worker_parallel)
    return _dash_attention(q, k, v, causal, schedule, sm_scale, block,
                           interpret, mask, worker_parallel)


def _record_tiling(q, causal, schedule, block, mask, worker_parallel):
    b, h, s, d = q.shape
    # the same memoized schedule the backward rule builds
    sch = cached_schedule(schedule, s // block, n_heads=1, causal=causal,
                          mask=mask, block_q=block, block_k=block)
    one = dq_bytes(b * h, s, d)
    wc = worker_grid(sch, worker_parallel, one)
    if wc is None:
        grid = [b * h, int(sch.prefetch_arrays(0)[0].shape[0])]
        real = math.prod(grid)
    else:
        grid = [b * h, *(int(n) for n in wc["valid"].shape)]
        real = b * h * int(wc["valid"].sum())
    rec = {"seq": s, "head_dim": d, "dtype": jnp.dtype(q.dtype).name,
           "mask": ("causal" if causal else "full") if mask is None
           else repr(mask),
           "window": getattr(mask, "window", None),
           "schedule": schedule, "block": block,
           "n_kv": sch.n_kv, "n_q": sch.n_q,
           "realization": "serialized" if wc is None else "worker_parallel",
           "grid": grid, "real_steps": real,
           "sentinel_steps": math.prod(grid) - real,
           "dq_partial_bytes": one * (1 if wc is None else grid[1]),
           "computed_pairs": real // (b * h) * block * block,
           "attended_pairs": (mask.pair_counts(s, block)[1] if mask is not None
                              else s * (s + 1) // 2 if causal else s * s)}
    _TILING["last"] = rec
    if _TILING["tracker"] is not None:
        _TILING["tracker"].log("attn_tiling", rec)


def _grouped_logits_mask(logits, causal):
    if not causal:
        return logits
    sq, sk = logits.shape[-2], logits.shape[-1]
    qpos = jnp.arange(sq)
    kpos = jnp.arange(sk)
    return jnp.where((qpos[:, None] >= kpos[None, :] + sq - sk), logits, -1e30)


def _extra_mask(mask, segment_ids, sq: int, sk: int):
    """Combine a static MaskSpec and dynamic per-row segment ids into one
    (B|1, Sq, Sk) boolean visibility array (None if neither given).

    The segment mask is the *dynamic* documents path (ids are traced, differ
    per batch row); a static ``Document`` spec takes the block-sparse kernel
    grid instead. Both AND with the ``causal`` flag applied elsewhere.

    Only for the **unchunked** paths (bounded by the chunk threshold): the
    chunked scan evaluates masks per chunk (:func:`_chunk_extra`) so the
    O(Sq·Sk) dense array is never resident — the whole point of chunking.
    """
    ex = None
    if mask is not None:
        ex = jnp.asarray(mask.materialize(sq, sk))[None]
    if segment_ids is not None:
        seg = segment_ids[:, :, None] == segment_ids[:, None, :]
        ex = seg if ex is None else ex & seg
    return ex


def _chunk_extra(mask, segment_ids, off, chunk_q: int, sk: int):
    """(B|1, chunk, Sk) visibility for one query chunk, built on the fly.

    The spec evaluates its ``mask_fn`` on chunk iotas (O(chunk·Sk) work, no
    dense S² constant); segment ids dynamic-slice the query rows.
    """
    ex = None
    if mask is not None:
        qpos = (off + jnp.arange(chunk_q))[:, None]
        kpos = jnp.arange(sk)[None, :]
        ex = mask.mask_fn(qpos, kpos)[None]
    if segment_ids is not None:
        seg_q = jax.lax.dynamic_slice_in_dim(segment_ids, off, chunk_q, axis=1)
        seg = seg_q[:, :, None] == segment_ids[:, None, :]
        ex = seg if ex is None else ex & seg
    return ex


def xla_attention(q, k, v, causal: bool = False, sm_scale: Optional[float] = None,
                  chunk_q: Optional[int] = None, mask=None, segment_ids=None):
    """Reference jnp attention (B, H, S, D) — differentiable, deterministic on TPU.

    GQA-native: k/v may carry Hk < H heads; the einsums contract per KV-head
    group (``bkgqd,bksd->bkgqs``) instead of repeating K/V.

    ``chunk_q``: scan over query chunks so the (B,H,S,S) score matrix is never
    materialized — peak temp drops from O(S²) to O(S·chunk). Identical math and
    FLOPs; required for the 4k–32k training/prefill cells to fit HBM.

    ``mask``: optional static :class:`repro.masks.spec.MaskSpec`, applied as a
    dense reference mask. ``segment_ids``: optional (B, S) int array — packed-
    document visibility (q sees k iff same segment), ANDed with ``causal`` and
    ``mask``; this is the dynamic path for per-row packing layouts the static
    block-sparse kernels cannot express.
    """
    b, h, s, d = q.shape
    hk = k.shape[1]
    g = validate_group(h, hk)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    chunked = chunk_q and s > chunk_q and s % chunk_q == 0
    # dense masks only on the unchunked (small-S) paths; the chunked scan
    # builds per-chunk masks inside the loop (no O(S²) resident constant)
    extra = None if chunked else _extra_mask(mask, segment_ids, s, k.shape[2])

    if g == 1:
        if not chunked:
            if extra is None:
                out, _ = ref_mod.mha_fwd(_flatten(q), _flatten(k), _flatten(v),
                                         causal, sm_scale)
                return _unflatten(out, b, h)
            logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                                k.astype(jnp.float32)) * sm_scale
            logits = _grouped_logits_mask(logits, causal)
            logits = jnp.where(extra[:, None], logits, -1e30)
            w = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32))
            return out.astype(q.dtype)
        return _chunked(q, k, v, causal, sm_scale, chunk_q,
                        "bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd",
                        mask=mask, segment_ids=segment_ids)

    qg = q.reshape(b, hk, g, s, d)
    if not chunked:
        logits = jnp.einsum("bkgqd,bksd->bkgqs", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * sm_scale
        logits = _grouped_logits_mask(logits, causal)
        if extra is not None:
            logits = jnp.where(extra[:, None, None], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkgqs,bksd->bkgqd", w, v.astype(jnp.float32))
        return out.reshape(b, h, s, d).astype(q.dtype)
    out = _chunked(qg, k, v, causal, sm_scale, chunk_q,
                   "bkgqd,bksd->bkgqs", "bkgqs,bksd->bkgqd",
                   mask=mask, segment_ids=segment_ids)
    return out.reshape(b, h, s, d)


def _chunked(q, k, v, causal, sm_scale, chunk_q, score_eq, out_eq, mask=None,
             segment_ids=None):
    """Query-chunked attention scan shared by the flat and grouped GQA paths.

    q: (..., S, D) with leading batch/head(/group) axes named by the einsum
    equations; k/v: (B, Hk|H, S, D). ``mask``/``segment_ids`` are evaluated
    **per chunk** inside the scan (:func:`_chunk_extra`) — peak mask temp is
    O(chunk·Sk), preserving the memory bound chunking exists for.
    """
    s = q.shape[-2]
    nc = s // chunk_q
    lead = q.shape[:-2]
    qc = q.reshape(lead + (nc, chunk_q, q.shape[-1]))
    qc = jnp.moveaxis(qc, -3, 0)                       # (nc, ..., chunk, d)
    offsets = jnp.arange(nc) * chunk_q
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    kpos = jnp.arange(k.shape[-2])

    def one_chunk(carry, qc_off):
        qch, off = qc_off
        logits = jnp.einsum(score_eq, qch.astype(jnp.float32), kf) * sm_scale
        if causal:
            # end-aligned causal convention (matches ref._mask's tril(k=sk-sq)
            # and _grouped_logits_mask): query i may see keys ≤ i + sk - sq.
            qpos = off + jnp.arange(chunk_q) + (k.shape[-2] - s)
            cmask = qpos[:, None] >= kpos[None, :]
            logits = jnp.where(cmask.reshape((1,) * (logits.ndim - 2)
                                             + cmask.shape), logits, -1e30)
        if mask is not None or segment_ids is not None:
            ex = _chunk_extra(mask, segment_ids, off, chunk_q, k.shape[-2])
            # (B|1, chunk, Sk) → broadcast over head (and group) axes
            ex = ex.reshape((ex.shape[0],) + (1,) * (logits.ndim - 3)
                            + ex.shape[1:])
            logits = jnp.where(ex, logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum(out_eq, w, vf)
        return carry, o.astype(q.dtype)

    # remat per chunk: the backward recomputes one chunk's scores at a time
    # instead of saving every chunk's f32 logits/mask across the scan.
    # unroll: keeps every chunk visible to cost_analysis (a rolled loop is
    # counted once) and lets the TPU scheduler software-pipeline the chunks.
    _, out = jax.lax.scan(jax.checkpoint(one_chunk), (), (qc, offsets),
                          unroll=True)
    out = jnp.moveaxis(out, 0, -3)                     # (..., nc, chunk, d)
    return out.reshape(lead + (s, q.shape[-1]))


def attention(q, k, v, causal: bool = False, impl: str = "xla",
              schedule: str = "symmetric_shift_or_shift",
              sm_scale: Optional[float] = None, interpret: bool = False,
              chunk_q: Optional[int] = None, mask=None, segment_ids=None):
    """Model-facing dispatcher; see module docstring.

    Validates GQA group divisibility up front: q carries ``n_heads`` heads, k/v
    carry ``n_kv_heads`` — the former must be a multiple of the latter.

    ``mask`` (static MaskSpec) reaches both impls; ``segment_ids`` (dynamic
    per-row packing) has no static block map, so only the xla impl takes it —
    static packing layouts that should hit the Pallas grid go through
    ``mask=Document(...)`` instead.
    """
    validate_group(q.shape[1], k.shape[1])
    if impl == "xla":
        return xla_attention(q, k, v, causal, sm_scale, chunk_q=chunk_q,
                             mask=mask, segment_ids=segment_ids)
    if impl == "pallas":
        if segment_ids is not None:
            raise ValueError(
                "impl='pallas' takes no dynamic segment_ids: pass a static "
                "mask=Document(...) or use impl='xla'")
        return dash_attention(q, k, v, causal, schedule, sm_scale,
                              interpret=interpret, mask=mask)
    raise ValueError(f"unknown attention impl {impl!r}")
