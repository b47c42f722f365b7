"""Flash-attention forward Pallas TPU kernel.

Online-softmax tiling (FlashAttention dataflow adapted to the TPU memory
hierarchy). Two grids:

* **Full mask** — dense ``grid = (batch·heads, q_tiles, kv_tiles)`` with the kv
  dimension innermost and "arbitrary" (sequential) so the running (max, sum,
  acc) state lives in VMEM scratch across kv steps.
* **Causal mask** — the dense grid would waste ~half its steps on fully-masked
  kv tiles (previously skipped with ``pl.when``, but still burning grid
  bookkeeping and DMAs for the q/o/lse blocks of dead steps). Instead the grid
  is **schedule-driven** like the DASH backward: scalar-prefetch arrays
  enumerate only the valid ``(q_tile, kv_tile)`` tasks — masked tiles are
  removed from the grid entirely — with **descending q-tile iteration**
  (longest rows first, the §3.3 traversal, so the tail of the grid drains with
  the shortest rows). ``causal_grid()`` exposes the task list; CI asserts it
  contains zero fully-masked tiles.

* **Block-sparse masks** (``mask=MaskSpec``) — the fully general form of the
  causal grid: the mask's block map (:mod:`repro.masks.spec`) classifies every
  tile FULL / PARTIAL / EMPTY; EMPTY tiles never enter the grid
  (:func:`mask_grid`), FULL tiles run the unmasked math bit-for-bit, and
  PARTIAL tiles evaluate the spec's ``mask_fn`` on block iotas and
  **mask-multiply the probabilities with exact-zero lanes** — masked lanes
  contribute exact ``0.0`` to every accumulation (robust even when a whole
  row of a tile is masked, where the ``exp(NEG_INF - NEG_INF) == 1`` trap
  would otherwise corrupt the online softmax).

K/V are addressed **natively for GQA** — ``(B·Hk, S, D)``, never repeated to
the query head count: K/V index maps resolve the program's KV head via
:func:`repro.kernels.gqa.kv_head_index`.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gqa import kv_head_index

NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# causal task grid (schedule-driven: no masked tiles, descending q)
# --------------------------------------------------------------------------- #
@functools.lru_cache(maxsize=256)
def causal_grid(n_q: int, n_k: int, block_q: int, block_k: int
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(kv_ids, q_ids, first, last) int32 task arrays for the causal forward.

    Tasks visit q tiles in **descending** order; within a q tile, kv ascends
    (the online-softmax chain). Only tiles with at least one unmasked element —
    ``kv·block_k < (q+1)·block_q`` — are emitted, so the grid contains zero
    fully-masked tiles by construction. ``first``/``last`` flag each q tile's
    chain boundaries (scratch init / finalize).
    """
    kv_ids, q_ids, first, last = [], [], [], []
    for qi in range(n_q - 1, -1, -1):
        n_valid = min(n_k, -(-((qi + 1) * block_q) // block_k))
        for ki in range(n_valid):
            kv_ids.append(ki)
            q_ids.append(qi)
            first.append(1 if ki == 0 else 0)
            last.append(1 if ki == n_valid - 1 else 0)
    return (np.asarray(kv_ids, np.int32), np.asarray(q_ids, np.int32),
            np.asarray(first, np.int32), np.asarray(last, np.int32))


@functools.lru_cache(maxsize=256)
def mask_grid(mask_spec, n_q: int, n_k: int, block_q: int, block_k: int
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray,
                         np.ndarray]:
    """(kv_ids, q_ids, first, last, partial) int32 task arrays for a
    block-sparse mask forward.

    Same traversal as :func:`causal_grid` — descending q tiles, kv ascending
    within each q tile's online-softmax chain — but the valid set comes from
    the mask spec's block map: EMPTY tiles are excluded by construction, and
    ``partial`` flags the PARTIAL tiles. The flags feed accounting (gantt
    hatching, BENCH_masks grid stats); the kernels themselves evaluate the
    tile predicate on every surviving tile — the same choice as the causal
    scheduled kernel — because the predicate is a handful of VPU ops against
    two MXU dots per tile, it is exact (`p·1.0` is bitwise `p` on FULL
    tiles), and a ``pl.when`` dual body would duplicate the dots in every
    grid step. Cached on the (hashable) spec, so distinct masks never share
    a grid.
    """
    from repro.masks.spec import EMPTY, PARTIAL
    bm = mask_spec.block_map(n_k, n_q, block_q, block_k)      # (n_kv, n_q)
    kv_ids, q_ids, first, last, partial = [], [], [], [], []
    for qi in range(n_q - 1, -1, -1):
        ks = [ki for ki in range(n_k) if bm[ki, qi] != EMPTY]
        assert ks, (f"{mask_spec!r}: q tile {qi} attends to nothing — "
                    "undefined softmax rows")
        for j, ki in enumerate(ks):
            kv_ids.append(ki)
            q_ids.append(qi)
            first.append(1 if j == 0 else 0)
            last.append(1 if j == len(ks) - 1 else 0)
            partial.append(1 if bm[ki, qi] == PARTIAL else 0)
    return tuple(np.asarray(a, np.int32)
                 for a in (kv_ids, q_ids, first, last, partial))


def _fwd_body(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *, sm_scale, causal,
              q_start, k_start, mask_spec=None, q_info=None, k_info=None):
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    msk = None
    if causal:
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    elif mask_spec is not None:
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        msk = mask_spec.tile_mask(rows, cols, q_info, k_info)
        s = jnp.where(msk, s, NEG_INF)
    m_prev = m_ref[...]
    m_cur = jnp.max(s, axis=-1)[:, None]
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    if msk is not None:
        # exact-zero masked lanes: a tile row that is fully masked keeps
        # m_new == NEG_INF and exp(s - m_new) == exp(0) == 1 — the multiply
        # is what guarantees those lanes contribute literal 0.0. On FULL
        # tiles msk is all-ones and p·1.0 is bitwise p (p >= 0).
        p = p * msk.astype(jnp.float32)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1)[:, None]
    v = v_ref[0].astype(jnp.float32)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new


def col_to_row(x):
    """(n, 1) column -> (1, n) row by a 128-lane transpose: Mosaic has no
    sublane<->lane reshape, but transposes whole (n, 128) f32 tiles."""
    return jnp.broadcast_to(x, (x.shape[0], 128)).T[:1]


def _finalize(o_ref, lse_ref, acc_ref, m_ref, l_ref):
    l = l_ref[...]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
    # lse leaves as a lane-dense (1, block_q) row of a (BH, 1, S) array: a
    # (1, block_q) block of a (BH, S) array breaks the TPU (8, 128) block rule
    lse_ref[0] = col_to_row(m_ref[...] + jnp.log(l_safe))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, block_q, block_k,
                n_kv_tiles):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    _fwd_body(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, sm_scale=sm_scale,
              causal=False, q_start=qi * block_q, k_start=ki * block_k)

    @pl.when(ki == n_kv_tiles - 1)
    def _fin():
        _finalize(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _fwd_sched_kernel(kv_ids, q_ids, first, last,      # scalar prefetch (SMEM)
                      q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *, sm_scale, block_q, block_k):
    t = pl.program_id(1)
    qi = q_ids[t]
    ki = kv_ids[t]

    @pl.when(first[t] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    _fwd_body(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, sm_scale=sm_scale,
              causal=True, q_start=qi * block_q, k_start=ki * block_k)

    @pl.when(last[t] == 1)
    def _fin():
        _finalize(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def split_info_refs(refs, has_info):
    """(q_info, k_info, rest) from a kernel's trailing refs: the token_info
    tile pair (a (block_q, 1) column and a (1, block_k) row) leads ``refs``
    when the mask spec ships a table, and is absent otherwise."""
    if not has_info:
        return None, None, refs
    return refs[0][...], refs[1][...], refs[2:]


def info_inputs(mask, s, q_map, k_map, block_q, block_k):
    """BlockSpecs + arrays threading ``mask.token_info(s)`` into a kernel:
    the q side as an (S, 1) column, the k side as a (1, S) row, so each tile
    is 2-D and meets the TPU (8, 128) block rule. Empty when there is no
    table (position-only specs never ship one)."""
    info = None if mask is None else mask.token_info(s)
    if info is None:
        return [], []
    info = jnp.asarray(info, jnp.int32)
    return ([pl.BlockSpec((block_q, 1), q_map),
             pl.BlockSpec((1, block_k), k_map)],
            [info.reshape(s, 1), info.reshape(1, s)])


def _fwd_mask_kernel(kv_ids, q_ids, first, last,       # scalar prefetch (SMEM)
                     q_ref, k_ref, v_ref, *refs, sm_scale, block_q, block_k,
                     mask_spec, has_info):
    """Block-sparse-mask forward: like the causal scheduled kernel but the
    tile predicate comes from the spec, with per-tile slices of the spec's
    token_info table threaded as real inputs (Pallas kernels cannot capture
    array constants)."""
    q_info, k_info, (o_ref, lse_ref, acc_ref, m_ref, l_ref) = \
        split_info_refs(refs, has_info)
    t = pl.program_id(1)
    qi = q_ids[t]
    ki = kv_ids[t]

    @pl.when(first[t] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    _fwd_body(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, sm_scale=sm_scale,
              causal=False, q_start=qi * block_q, k_start=ki * block_k,
              mask_spec=mask_spec, q_info=q_info, k_info=k_info)

    @pl.when(last[t] == 1)
    def _fin():
        _finalize(o_ref, lse_ref, acc_ref, m_ref, l_ref)


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale", "block_q",
                                             "block_k", "interpret",
                                             "n_heads", "n_kv_heads", "mask"))
def flash_fwd(q, k, v, causal=False, sm_scale=None, block_q=128, block_k=128,
              interpret=False, n_heads: Optional[int] = None,
              n_kv_heads: Optional[int] = None, mask=None):
    """Flash attention forward.

    Args:   q: (BH, S, D); k, v: (B·Hk, S, D) — pass ``n_heads``/``n_kv_heads``
            when the head counts differ (native GQA; no KV repetition).
            S divisible by the block sizes.
            mask: optional :class:`repro.masks.spec.MaskSpec` — block-sparse
            grid (EMPTY tiles skipped, PARTIAL tiles mask-multiplied with
            exact-zero lanes). Mutually exclusive with ``causal`` (which
            stays the registry-schedule fast path); square masks only.
    Returns: out (BH, S, D) q.dtype, lse (BH, S) fp32.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert mask is None or not causal, "mask supersedes the causal flag"
    assert mask is None or sq == sk, "block-sparse masks are square"
    if n_heads is None or n_kv_heads is None:
        assert k.shape[0] == bh, ("k/v have fewer heads than q: pass n_heads "
                                  "and n_kv_heads for native GQA")
        n_heads = n_kv_heads = 1
    assert bh % n_heads == 0 and k.shape[0] == (bh // n_heads) * n_kv_heads, (
        f"flattened shapes {bh}x{k.shape[0]} inconsistent with heads "
        f"{n_heads}/{n_kv_heads}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    # causal attention is square-only here: the repo's causal convention for
    # sq != sk is end-aligned (ref._mask / xla_attention), while this kernel's
    # mask and causal_grid() are start-aligned — refuse rather than silently
    # diverge (the DASH causal schedules are square anyway).
    assert not causal or sq == sk, "causal flash_fwd requires sq == sk"
    n_q, n_k = sq // block_q, sk // block_k
    assert sq % block_q == 0 and sk % block_k == 0
    kvb = functools.partial(kv_head_index, n_heads=n_heads,
                            n_kv_heads=n_kv_heads)
    out_shape = [
        jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q, d), jnp.float32),   # acc
        pltpu.VMEM((block_q, 1), jnp.float32),   # running max
        pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
    ]

    if mask is not None or causal:
        # scheduled grid: only the (q, kv) tiles that attend anything
        if mask is not None:
            kv_ids, q_ids, first, last, _ = mask_grid(mask, n_q, n_k,
                                                      block_q, block_k)
            info_specs, info_args = info_inputs(
                mask, sq, lambda b, t, kvi, qi, fi, la: (qi[t], 0),
                lambda b, t, kvi, qi, fi, la: (0, kvi[t]), block_q, block_k)
            kernel = functools.partial(
                _fwd_mask_kernel, sm_scale=sm_scale, block_q=block_q,
                block_k=block_k, mask_spec=mask, has_info=bool(info_args))
        else:
            kv_ids, q_ids, first, last = causal_grid(n_q, n_k, block_q,
                                                     block_k)
            info_specs, info_args = [], []
            kernel = functools.partial(
                _fwd_sched_kernel, sm_scale=sm_scale, block_q=block_q,
                block_k=block_k)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(bh, int(kv_ids.shape[0])),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda b, t, kvi, qi, fi, la: (b, qi[t], 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda b, t, kvi, qi, fi, la: (kvb(b), kvi[t], 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda b, t, kvi, qi, fi, la: (kvb(b), kvi[t], 0)),
            ] + info_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda b, t, kvi, qi, fi, la: (b, qi[t], 0)),
                pl.BlockSpec((1, 1, block_q),
                             lambda b, t, kvi, qi, fi, la: (b, 0, qi[t])),
            ],
            scratch_shapes=scratch_shapes,
        )
        out, lse = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(jnp.asarray(kv_ids), jnp.asarray(q_ids), jnp.asarray(first),
          jnp.asarray(last), q, k, v, *info_args)
        return out, lse.reshape(bh, sq)

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, n_kv_tiles=n_k)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (kvb(b), ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (kvb(b), ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out, lse.reshape(bh, sq)
