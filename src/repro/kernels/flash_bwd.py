"""DASH deterministic flash-attention backward Pallas TPU kernel (paper §3 + Alg. 1).

Two realizations of the same schedule, bitwise-identical on the registry
generators and both pure functions of the schedule (never of worker timing):

**Serialized** (``worker_parallel=False``) — the original TPU adaptation: the
grid is ``(bh, n_tasks)`` and one sequential core plays all worker chains in
turn, concatenated worker-major via the scalar-prefetch arrays
``kv_ids[t], q_ids[t]``. Simple, but the makespan is Σ over chains — the DASH
schedule's parallel dimension never reaches the hardware.

**Worker-parallel** (``worker_parallel=True``, the default) — the schedule's
worker axis becomes a real grid dimension: ``grid = (bh, n_workers,
max_chain_len)`` with ``n_workers`` marked *parallel* (megacore-mappable; on a
W-core part the modeled makespan drops from Σ-chains to max-chain — the paper's
Figs. 8/9 win). Per worker:

* **dK/dV stay VMEM-resident** for the worker's own KV rows. Legal by the
  paper's §3.1 row-ownership constraint: every task of a KV row runs
  contiguously on exactly one worker, so the dK/dV output block index is
  constant across the worker's chain segment and workers write disjoint rows —
  the compute phase of DAG cost ``c`` runs with no cross-worker traffic at all.
* **dQ goes to a worker-private fp32 partial buffer** ``(BH, W, S, D)`` via the
  explicit DMA read-modify-write used by the serialized path (order within a
  worker = chain order). The global reduction of DAG cost ``r`` is deferred to a
  small combine kernel that folds the W partials **in ascending worker order**
  (:func:`fold_combine`) — a fixed left fold, so the result is bitwise
  reproducible and *independent of worker timing*. Because the serialized
  realization also accumulates each dQ column worker-major (chains are
  concatenated ascending), the two paths produce bitwise-identical dQ whenever
  each worker contributes at most one task per (head, q) column — true for
  every registry schedule (``Schedule.worker_chains()['single_visit']``).
* Chains have unequal lengths (causal masks); short chains are padded with
  **no-op sentinel tasks** that repeat the worker's last tile indices, so the
  padding issues no DMAs and burns no bandwidth — only grid bookkeeping.

Causal schedules contain only valid tiles, so masked blocks never enter either
grid (the GPU baseline merely idles on them).

**Native GQA**: K/V arrive as ``(B·Hk, S, D)`` — never repeated to the query
head count. K/V BlockSpec index maps address the group's KV head via
:func:`repro.kernels.gqa.kv_head_index`; dK/dV are emitted per *query* head and
reduced per KV head in **ascending query-head order** by the same
:func:`fold_combine` — the second fixed-order reduction. Residual memory and KV
HBM footprint drop by the group factor.
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

from repro.core.schedules import Schedule
from repro.kernels.flash_fwd import info_inputs, split_info_refs
from repro.kernels.gqa import kv_head_index, validate_group

NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# schedule serialization
# --------------------------------------------------------------------------- #
def serialize_schedule(schedule: Schedule, head: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Serialized (kv_ids, q_ids) for one head of the schedule.

    Worker chains are concatenated (the sequential TPU core plays all workers in
    turn); within-chain order and chain order are preserved, so the dQ accumulation
    order is a pure function of the schedule — the determinism contract.
    Delegates to (memoized) :meth:`Schedule.prefetch_arrays`.
    """
    return schedule.prefetch_arrays(head)


def first_visit_flags(kv_ids: np.ndarray, q_ids: np.ndarray) -> np.ndarray:
    """q_first[t] = 1 iff task t is the first in serialized order touching q_ids[t]."""
    seen = set()
    flags = np.zeros_like(q_ids)
    for t, q in enumerate(q_ids):
        if int(q) not in seen:
            flags[t] = 1
            seen.add(int(q))
    return flags.astype(np.int32)


def dq_lanes(d: int) -> int:
    """Lane width of the fp32 dQ buffers the kernels DMA into: ``d`` rounded
    up to 128. The TPU DMA moves whole 128-lane tiles, so at head_dim 64 the
    (block_q, d) read-modify-write window is refused; the kernels store dQ in
    the first ``d`` lanes and the wrapper slices the rest away."""
    return -(-d // 128) * 128


def _row_to_col(x):
    """(1, n) row -> (n, 1) column (inverse of flash_fwd.col_to_row)."""
    return jnp.broadcast_to(x, (128, x.shape[1])).T[:, :1]


# --------------------------------------------------------------------------- #
# shared task math (one (kv, q) tile of Alg. 1)
# --------------------------------------------------------------------------- #
def _task_grads(q, k, v, do, lse, delta, kv, qi, *, sm_scale, causal,
                block_q, block_k, mask_spec=None, q_info=None, k_info=None):
    """Compute phase (DAG cost c): p/ds and the three tile contributions.
    ``lse``/``delta`` arrive as (block_q, 1) columns."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    msk = None
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = kv * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    elif mask_spec is not None:
        rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = kv * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        msk = mask_spec.tile_mask(rows, cols, q_info, k_info)
        s = jnp.where(msk, s, NEG_INF)
    p = jnp.exp(s - lse)                                            # (bq, bk)
    if msk is not None:
        # exact-zero masked lanes (see flash_fwd._fwd_body): PARTIAL tiles
        # contribute literal 0.0 outside the mask, so both realizations stay
        # bitwise identical and FULL tiles run the unmasked math bit-for-bit.
        p = p * msk.astype(jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)    # (bq, bk)
    ds = p * (dp - delta) * sm_scale
    dv_contrib = jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    dk_contrib = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    dq_contrib = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    return dq_contrib, dk_contrib, dv_contrib


# --------------------------------------------------------------------------- #
# serialized kernel body (grid = (bh, n_tasks), one core plays every chain)
# --------------------------------------------------------------------------- #
def _bwd_kernel(kv_ids, q_ids, q_first,        # scalar prefetch (SMEM)
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                sm_scale, causal, block_q, block_k, mask_spec=None,
                has_info=False):
    q_info, k_info, (dq_hbm, dk_ref, dv_ref, dq_scratch, sem_in, sem_out) = \
        split_info_refs(refs, has_info)
    b = pl.program_id(0)
    t = pl.program_id(1)
    kv = kv_ids[t]
    qi = q_ids[t]

    dq_contrib, dk_contrib, dv_contrib = _task_grads(
        q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32),
        v_ref[0].astype(jnp.float32), do_ref[0].astype(jnp.float32),
        _row_to_col(lse_ref[0]), _row_to_col(delta_ref[0]), kv, qi,
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k,
        mask_spec=mask_spec, q_info=q_info, k_info=k_info)

    # ---- dV/dK: chain-contiguous accumulation; block stays VMEM-resident ----
    first_of_chain = jnp.logical_or(t == 0, kv_ids[jnp.maximum(t - 1, 0)] != kv)

    @pl.when(first_of_chain)
    def _init():
        dv_ref[0] = dv_contrib
        dk_ref[0] = dk_contrib

    @pl.when(jnp.logical_not(first_of_chain))
    def _acc():
        dv_ref[0] += dv_contrib
        dk_ref[0] += dk_contrib

    # ---- dQ: ordered deterministic global reduction (Alg. 1 l.30–36) ----
    # reduction phase (cost r in the DAG model): explicit HBM<->VMEM RMW, order =
    # serialized schedule order.
    _dq_rmw(dq_hbm.at[b, pl.ds(qi * block_q, block_q), :], dq_scratch,
            dq_contrib, q_first[t] == 1, sem_in, sem_out)


def _dq_rmw(dq_slice, dq_scratch, dq_contrib, fresh, sem_in, sem_out):
    """Add ``dq_contrib`` into the HBM window ``dq_slice`` through VMEM.

    Semaphore waits pin the order; no implicit pipelining is involved, so no
    stale-buffer hazards regardless of schedule. Only the first ``d`` of the
    window's ``dq_lanes(d)`` lanes carry dQ."""
    d = dq_contrib.shape[1]

    @pl.when(fresh)
    def _fresh():
        dq_scratch[:, :d] = dq_contrib

    @pl.when(jnp.logical_not(fresh))
    def _rmw():
        cp_in = pltpu.make_async_copy(dq_slice, dq_scratch, sem_in)
        cp_in.start()
        cp_in.wait()
        dq_scratch[:, :d] += dq_contrib

    cp_out = pltpu.make_async_copy(dq_scratch, dq_slice, sem_out)
    cp_out.start()
    cp_out.wait()


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale", "block_q",
                                             "block_k", "interpret",
                                             "n_heads", "n_kv_heads", "mask"))
def _flash_bwd_call(q, k, v, do, lse, delta, kv_ids, q_ids, q_first, causal,
                    sm_scale, block_q, block_k, interpret, n_heads, n_kv_heads,
                    mask=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    n_tasks = int(kv_ids.shape[0])
    grid = (bh, n_tasks)
    dp = dq_lanes(d)
    info_specs, info_args = info_inputs(
        mask, sq, lambda b, t, kvi, qi, qf: (qi[t], 0),
        lambda b, t, kvi, qi, qf: (0, kvi[t]), block_q, block_k)
    kernel = functools.partial(
        _bwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, mask_spec=mask, has_info=bool(info_args))
    kvb = functools.partial(kv_head_index, n_heads=n_heads,
                            n_kv_heads=n_kv_heads)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, t, kvi, qi, qf: (b, qi[t], 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, t, kvi, qi, qf: (kvb(b), kvi[t], 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, t, kvi, qi, qf: (kvb(b), kvi[t], 0)),
            pl.BlockSpec((1, block_q, d), lambda b, t, kvi, qi, qf: (b, qi[t], 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, t, kvi, qi, qf: (b, 0, qi[t])),
            pl.BlockSpec((1, 1, block_q), lambda b, t, kvi, qi, qf: (b, 0, qi[t])),
        ] + info_specs,
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # dq: explicit DMA RMW
            pl.BlockSpec((1, block_k, d), lambda b, t, kvi, qi, qf: (b, kvi[t], 0)),
            pl.BlockSpec((1, block_k, d), lambda b, t, kvi, qi, qf: (b, kvi[t], 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dp), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    # dk/dv are per *query* head here; the caller folds groups per KV head.
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dp), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(kv_ids, q_ids, q_first, q, k, v, do, lse, delta, *info_args)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# worker-parallel kernel body (grid = (bh, n_workers, max_chain_len))
# --------------------------------------------------------------------------- #
def _worker_bwd_kernel(kv_ids, q_ids, valid, q_first,  # (W, T) scalar prefetch
                       q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                       sm_scale, causal, block_q, block_k, mask_spec=None,
                       has_info=False):
    q_info, k_info, (dq_hbm, dk_ref, dv_ref, dq_scratch, sem_in, sem_out) = \
        split_info_refs(refs, has_info)
    b = pl.program_id(0)
    w = pl.program_id(1)
    t = pl.program_id(2)
    kv = kv_ids[w, t]
    qi = q_ids[w, t]

    # Sentinel padding repeats the last task's tile indices, so every BlockSpec
    # below resolves to the already-resident blocks; the guarded body makes the
    # grid step a pure no-op.
    @pl.when(valid[w, t] == 1)
    def _task():
        dq_contrib, dk_contrib, dv_contrib = _task_grads(
            q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32),
            v_ref[0].astype(jnp.float32), do_ref[0].astype(jnp.float32),
            _row_to_col(lse_ref[0]), _row_to_col(delta_ref[0]), kv, qi,
            sm_scale=sm_scale, causal=causal, block_q=block_q,
            block_k=block_k, mask_spec=mask_spec, q_info=q_info,
            k_info=k_info)

        # dK/dV: the worker owns this KV row outright (§3.1), so the block is
        # private to (b, w) and stays VMEM-resident across the row's chain run.
        first_of_chain = jnp.logical_or(
            t == 0, kv_ids[w, jnp.maximum(t - 1, 0)] != kv)

        @pl.when(first_of_chain)
        def _init():
            dv_ref[0] = dv_contrib
            dk_ref[0] = dk_contrib

        @pl.when(jnp.logical_not(first_of_chain))
        def _acc():
            dv_ref[0] += dv_contrib
            dk_ref[0] += dk_contrib

        # dQ: accumulate into the worker-PRIVATE fp32 partial (b, w, :, :).
        # No cross-worker ordering is needed — the fixed-order combine kernel
        # realizes the reduction phase (cost r) after the grid completes.
        _dq_rmw(dq_hbm.at[b, w, pl.ds(qi * block_q, block_q), :], dq_scratch,
                dq_contrib, q_first[w, t] == 1, sem_in, sem_out)


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale", "block_q",
                                             "block_k", "interpret",
                                             "n_heads", "n_kv_heads", "mask"))
def _flash_bwd_worker_call(q, k, v, do, lse, delta, kv_ids, q_ids, valid,
                           q_first, causal, sm_scale, block_q, block_k,
                           interpret, n_heads, n_kv_heads, mask=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    n_workers, max_chain = (int(s) for s in kv_ids.shape)
    grid = (bh, n_workers, max_chain)
    dp = dq_lanes(d)
    info_specs, info_args = info_inputs(
        mask, sq, lambda b, w, t, kvi, qi, va, qf: (qi[w, t], 0),
        lambda b, w, t, kvi, qi, va, qf: (0, kvi[w, t]), block_q, block_k)
    kernel = functools.partial(
        _worker_bwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, mask_spec=mask, has_info=bool(info_args))
    kvb = functools.partial(kv_head_index, n_heads=n_heads,
                            n_kv_heads=n_kv_heads)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda b, w, t, kvi, qi, va, qf: (b, qi[w, t], 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, w, t, kvi, qi, va, qf: (kvb(b), kvi[w, t], 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, w, t, kvi, qi, va, qf: (kvb(b), kvi[w, t], 0)),
            pl.BlockSpec((1, block_q, d),
                         lambda b, w, t, kvi, qi, va, qf: (b, qi[w, t], 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda b, w, t, kvi, qi, va, qf: (b, 0, qi[w, t])),
            pl.BlockSpec((1, 1, block_q),
                         lambda b, w, t, kvi, qi, va, qf: (b, 0, qi[w, t])),
        ] + info_specs,
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # dq partials: explicit DMA RMW
            pl.BlockSpec((1, block_k, d),
                         lambda b, w, t, kvi, qi, va, qf: (b, kvi[w, t], 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, w, t, kvi, qi, va, qf: (b, kvi[w, t], 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dp), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    dq_part, dk, dv = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, n_workers, sq, dp), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(kv_ids, q_ids, valid, q_first, q, k, v, do, lse, delta, *info_args)
    return dq_part, dk, dv


# --------------------------------------------------------------------------- #
# fixed-order fold combine (the deterministic reduction phase, cost r)
# --------------------------------------------------------------------------- #
def _fold_kernel(visited, p_ref, o_ref, *, n_partials):
    ti = pl.program_id(1)
    acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
    started = jnp.zeros((), jnp.bool_)
    for r in range(n_partials):       # static unroll: a fixed left fold
        m = visited[r, ti] != 0
        pr = p_ref[0, r]
        # first live partial *replaces* acc (never `0.0 + x`, which would flip
        # -0.0 lanes); later ones append to the fold. Skipped partials may hold
        # uninitialized HBM — computed then discarded by the select.
        acc = jnp.where(m, jnp.where(started, acc + pr, pr), acc)
        started = jnp.logical_or(started, m)
    o_ref[0] = acc


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _fold_combine_call(partials, visited, block, interpret):
    n, r, s, d = partials.shape
    n_tiles = s // block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, n_tiles),
        in_specs=[
            pl.BlockSpec((1, r, block, d), lambda nb, ti, vis: (nb, 0, ti, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, d), lambda nb, ti, vis: (nb, ti, 0)),
    )
    return pl.pallas_call(
        functools.partial(_fold_kernel, n_partials=r),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, s, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(visited, partials)


def fold_combine(partials, visited, block, interpret=False):
    """Reduce ``partials (N, R, S, D)`` over axis 1 → ``(N, S, D)`` fp32.

    The fold runs in **ascending r order** (r = worker id for the dQ combine,
    r = query head within the KV group for the dK/dV combine), one partial at a
    time — a left fold fixed by construction, so the result is a pure function
    of the inputs regardless of how the producing grid was parallelized.
    ``visited (R, S//block)`` masks partials that were never written (int32).
    """
    assert partials.ndim == 4 and visited.shape[0] == partials.shape[1]
    return _fold_combine_call(partials, jnp.asarray(visited, jnp.int32),
                              block, interpret)


# --------------------------------------------------------------------------- #
# host wrapper
# --------------------------------------------------------------------------- #
def flash_bwd(q, k, v, out, lse, do, schedule: Schedule, causal=False,
              sm_scale=None, block_q=128, block_k=128, interpret=False,
              worker_parallel=True, n_heads: Optional[int] = None,
              n_kv_heads: Optional[int] = None, mask=None):
    """DASH backward. q/do: (BH, S, D); k/v: (B·Hk, S, D) — native GQA, no
    repetition (pass ``n_heads``/``n_kv_heads`` when they differ). The
    schedule's (n_kv, n_q) must match (S // block_k, S // block_q).

    ``mask``: optional :class:`repro.masks.spec.MaskSpec`; the schedule must
    then be the mask's own compiled schedule (pinned by ``mask_key`` — two
    distinct masks can never share a schedule or a kernel grid). EMPTY tiles
    are absent from the schedule's ragged chains; PARTIAL tiles mask-multiply
    with exact-zero lanes, so both realizations below stay bitwise identical
    under any mask. KV rows the mask leaves without tasks are zeroed (their
    output blocks are never written by the grid).

    ``worker_parallel=True`` (default) realizes the schedule's worker dimension
    as a parallel grid axis with the fixed-order dQ combine;
    ``worker_parallel=False`` keeps the single-core serialized realization.
    Both are bitwise-deterministic; they are bitwise-*equal* to each other for
    every registry schedule (see module docstring). Returns dq (BH, S, D),
    dk/dv (B·Hk, S, D), all fp32.
    """
    bh, sq, d = q.shape
    bkh, sk, _ = k.shape
    if n_heads is None or n_kv_heads is None:
        assert bh == bkh, ("k/v have fewer heads than q: pass n_heads and "
                           "n_kv_heads for native GQA")
        n_heads = n_kv_heads = 1
        group = 1
    else:
        group = validate_group(n_heads, n_kv_heads)
        assert bh % n_heads == 0 and bkh == (bh // n_heads) * n_kv_heads, (
            f"flattened shapes {bh}x{bkh} inconsistent with heads "
            f"{n_heads}/{n_kv_heads}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if causal:
        assert block_q == block_k, "causal schedules assume square tiles"
    assert schedule.causal == causal
    if mask is not None:
        assert not causal, "mask supersedes the causal flag"
        assert schedule.mask_key == mask.key(), (
            f"schedule {schedule.name!r} was compiled for mask "
            f"{schedule.mask_key}, not {mask.key()} — cache-key collision?")
    else:
        assert schedule.mask_key is None, (
            "block-sparse schedule requires its mask to be passed")
    assert schedule.n_kv == sk // block_k and schedule.n_q == sq // block_q, (
        f"schedule ({schedule.n_kv}x{schedule.n_q}) != tiling "
        f"({sk // block_k}x{sq // block_q})")
    # D = rowsum(dO ∘ O)  (Alg. 1 line 1 — preprocessing); lse and D enter
    # the kernels as lane-dense (BH, 1, S) rows
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    lse, delta = lse.reshape(bh, 1, sq), delta.reshape(bh, 1, sq)

    if worker_parallel:
        # Non-registry schedules degrade to the serialized realization instead
        # of changing numerics or crashing: a worker visiting one q column
        # twice would regroup that column's partial sums vs the serialized
        # fold, and a worker with no head-0 tasks has no grid row at all.
        try:
            wc = schedule.worker_chains()
            worker_parallel = wc["single_visit"]
        except ValueError:
            worker_parallel = False
    if worker_parallel:
        dq_part, dk, dv = _flash_bwd_worker_call(
            q, k, v, do, lse, delta,
            jnp.asarray(wc["kv_ids"]), jnp.asarray(wc["q_ids"]),
            jnp.asarray(wc["valid"]), jnp.asarray(wc["q_first"]),
            causal, sm_scale, block_q, block_k, interpret, n_heads, n_kv_heads,
            mask=mask)
        dq = fold_combine(dq_part, wc["visited"], block_q, interpret)[..., :d]
    else:
        kv_ids, q_ids = serialize_schedule(schedule)
        q_first = first_visit_flags(kv_ids, q_ids)
        dq, dk, dv = _flash_bwd_call(
            q, k, v, do, lse, delta, jnp.asarray(kv_ids), jnp.asarray(q_ids),
            jnp.asarray(q_first), causal, sm_scale, block_q, block_k,
            interpret, n_heads, n_kv_heads, mask=mask)
        dq = dq[..., :d]

    if mask is not None and schedule.cells is not None:
        # a KV row with no surviving tiles (e.g. keys beyond every sliding
        # window) is never visited by the grid, so its dk/dv output block
        # holds uninitialized memory — force the mathematically-correct zero.
        live_rows = {kv for (kv, _q) in schedule.cells}
        if len(live_rows) < schedule.n_kv:
            live = np.zeros(sk, bool)
            for kv in live_rows:
                live[kv * block_k:(kv + 1) * block_k] = True
            lv = jnp.asarray(live)[None, :, None]
            dk = jnp.where(lv, dk, 0.0)
            dv = jnp.where(lv, dv, 0.0)

    if group > 1:
        # dK/dV were produced per query head; fold each KV-head group in
        # ascending query-head order (query heads of a group are contiguous in
        # the flattened head axis: b·H + kh·g + j ↦ (b·Hk + kh)·g + j).
        ones = np.ones((group, sk // block_k), np.int32)
        dk = fold_combine(dk.reshape(bkh, group, sk, d), ones, block_k, interpret)
        dv = fold_combine(dv.reshape(bkh, group, sk, d), ones, block_k, interpret)
    return dq, dk, dv
