"""Cross-chip DASH: context-parallel ring attention (shard_map + ppermute).

The paper's schedules are step orders for (worker, kv_tile, q_tile) task grids;
a context-parallel ring is the same grid with chips as workers, so the two
optimal generators in :mod:`repro.core.schedules` transfer directly:

  ``shift`` (full mask, §3.4)
      Worker *i* visits Q tiles ``(i, i+1, …)`` cyclically.  Inverted to the
      query-stationary ring view: at step *t*, the device holding Q block *i*
      processes the KV block of device ``(i - t) mod n`` — i.e. KV blocks
      rotate one hop per step via ``jax.lax.ppermute`` (lowering to
      ``collective-permute``, never an all-gather of the sequence).

  ``symmetric_shift`` (causal mask, §3.4)
      Worker *i* owns KV rows *i* and *n-1-i* (longest-with-shortest fold of
      the causal triangle).  The **zigzag layout** realizes exactly this fold
      across chips: :func:`zigzag_permutation` places sequence chunk pair
      ``(i, 2n-1-i)`` on device *i*, so every device carries ``n+1`` virtual
      tiles of work per round and the ring is load-balanced; the traversal is
      the same cyclic shift.

:func:`ring_step_offsets` *derives* the per-step offsets from the generators
(and asserts they are the cyclic order the ppermute ring implements), keeping
``repro.core.schedules`` the single source of truth for step orders.

Determinism: forward online-softmax accumulation and the custom-VJP backward's
dQ (local, ascending ring step) and dK/dV (accumulators traveling with their
KV block around the full ring) reductions all happen in the fixed schedule
order under ``lax.scan`` — bitwise run-to-run reproducible, the cross-chip
analogue of the paper's Table-1 property and of the concern in
"Deterministic Inference across Tensor Parallel Sizes" (PAPERS.md).

Note the grade of guarantee: the ring order is *per-topology* deterministic —
fixed mesh, fixed bits — but resizing the ring re-associates the softmax
accumulation.  The serving path needs the stronger *topology-invariant* grade
(same bits for every TP degree); that is :func:`repro.dist.fold.fixed_fold_psum`,
which folds a canonical mesh-independent virtual-shard grid instead of
per-device partials.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import schedules as schedules_mod

F32 = jnp.float32
NEG = -1e30


# ------------------------------------------------------------------- layouts
def zigzag_permutation(seq: int, n_devices: int) -> np.ndarray:
    """Gather indices placing sequence chunk pair ``(i, 2n-1-i)`` on device i.

    ``x[:, zigzag_permutation(S, n)]`` re-lays a (B, S, …) sequence so that an
    even split over n devices gives device i the half-chunks i and 2n-1-i —
    the symmetric-shift pairing of the causal triangle (paper §3.4, Fig. 7).
    """
    assert seq % (2 * n_devices) == 0, (seq, n_devices)
    c = seq // (2 * n_devices)
    idx = []
    for i in range(n_devices):
        idx.extend(range(i * c, (i + 1) * c))
        j = 2 * n_devices - 1 - i
        idx.extend(range(j * c, (j + 1) * c))
    return np.asarray(idx, np.int32)


def zigzag_inverse(seq: int, n_devices: int) -> np.ndarray:
    """Inverse of :func:`zigzag_permutation` (restores the contiguous layout)."""
    return np.argsort(zigzag_permutation(seq, n_devices)).astype(np.int32)


@functools.lru_cache(maxsize=64)
def ring_step_offsets(n: int, causal: bool) -> Tuple[int, ...]:
    """Per-step KV offsets derived from the DASH generators.

    Returns ``offs`` such that at ring step t the device holding Q block i
    processes the KV block owned by device ``(i - offs[t]) % n``.  Asserts the
    generator's order is the cyclic one the ppermute ring implements.
    """
    if n == 1:
        return (0,)
    if not causal:
        sch = schedules_mod.shift(n)
        offs = []
        for t in range(n):
            # at slot t, worker w computes q tile (w+t)%n  ⇒  the q block i is
            # visited by kv owner w = (i - t) % n: one offset for all devices.
            step = {(chain[t][2] - w) % n for w, chain in enumerate(sch.chains)}
            assert len(step) == 1, "shift schedule is not a cyclic ring order"
            offs.append(step.pop())
    else:
        # symmetric_shift folds KV rows (w, n-1-w) onto worker w over a head
        # pair — exactly the zigzag chunk pairing (i, 2n-1-i); the traversal is
        # the same cyclic shift with per-worker start offsets.
        sch = schedules_mod.symmetric_shift(n, n_heads=2)
        for w, chain in enumerate(sch.chains):
            rows = {(h, kv) for (h, kv, _q) in chain}
            assert rows == {(0, w), (1, n - 1 - w)}, (
                "symmetric_shift pairing does not match the zigzag fold")
        offs = list(range(n))
    assert tuple(offs) == tuple(range(n))
    return tuple(offs)


def _block_positions(i, block_len: int, n: int, layout: str):
    """Global token positions held by device ``i`` (traced scalar ok)."""
    if layout == "zigzag":
        c = block_len // 2
        base = jnp.arange(c, dtype=jnp.int32)
        return jnp.concatenate([i * c + base, (2 * n - 1 - i) * c + base])
    return i * block_len + jnp.arange(block_len, dtype=jnp.int32)


# ------------------------------------------------------- per-device ring core
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_block(q, k, v, axis, n, causal, layout, scale):
    out, _ = _ring_fwd_impl(q, k, v, axis, n, causal, layout, scale)
    return out


def _ring_fwd_impl(q, k, v, axis, n, causal, layout, scale):
    """Online-softmax ring forward. q/k/v: local (B, L, H, D) blocks."""
    i = jax.lax.axis_index(axis) if causal else None
    b, l, h, d = q.shape
    # NB: axis_index-derived values must stay out of traces that don't use
    # them — a dead partition-id inside the custom_vjp'd scan survives DCE and
    # the SPMD partitioner rejects it.  Hence everything position-dependent is
    # computed strictly under `causal`.
    qp = _block_positions(i, l, n, layout) if causal else None
    qf = q.astype(F32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def update(o, m, lsum, kc, vc, t):
        """One online-softmax accumulation against the KV block of device
        (i - t) % n — the DASH shift step order."""
        s = jnp.einsum("blhd,bmhd->bhlm", qf, kc.astype(F32)) * scale
        if causal:
            src = (i - t) % n
            kp = _block_positions(src, l, n, layout)
            s = jnp.where(qp[:, None] >= kp[None, :], s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        lsum = lsum * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum("bhlm,bmhd->bhld", p,
                                             vc.astype(F32))
        return o, m_new, lsum

    # step 0 runs on the local block; each scan step permutes first, so the
    # ring does exactly n-1 hops (no dead final rotation).
    o0 = jnp.zeros((b, h, l, d), F32)
    m0 = jnp.full((b, h, l), NEG, F32)
    l0 = jnp.zeros((b, h, l), F32)
    o, m, lsum = update(o0, m0, l0, k, v, 0)

    def step(carry, t):
        o, m, lsum, kc, vc = carry
        kc, vc = jax.lax.ppermute((kc, vc), axis, perm)
        o, m, lsum = update(o, m, lsum, kc, vc, t)
        return (o, m, lsum, kc, vc), None

    (o, m, lsum, _, _), _ = jax.lax.scan(step, (o, m, lsum, k, v),
                                         jnp.arange(1, n))
    out = (o / lsum[..., None]).transpose(0, 2, 1, 3).astype(q.dtype)
    lse = m + jnp.log(lsum)                   # (B, H, L)
    return out, lse


def _ring_vjp_fwd(q, k, v, axis, n, causal, layout, scale):
    out, lse = _ring_fwd_impl(q, k, v, axis, n, causal, layout, scale)
    return out, (q, k, v, out, lse)


def _ring_vjp_bwd(axis, n, causal, layout, scale, res, do):
    """Deterministic scheduled backward: recompute-p flash backward where dQ
    accumulates locally in ascending ring-step order and dK/dV accumulators
    travel the full ring with their KV block (landing home after n hops)."""
    q, k, v, out, lse = res
    i = jax.lax.axis_index(axis) if causal else None
    b, l, h, d = q.shape
    qp = _block_positions(i, l, n, layout) if causal else None
    qf, dof = q.astype(F32), do.astype(F32)
    delta = jnp.einsum("blhd,blhd->bhl", dof, out.astype(F32))
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, t):
        dq, kc, vc, dkc, dvc = carry
        kf, vf = kc.astype(F32), vc.astype(F32)
        s = jnp.einsum("blhd,bmhd->bhlm", qf, kf) * scale
        if causal:
            src = (i - t) % n
            kp = _block_positions(src, l, n, layout)
            s = jnp.where(qp[:, None] >= kp[None, :], s, NEG)
        p = jnp.exp(s - lse[..., None])
        dv_blk = jnp.einsum("bhlm,blhd->bmhd", p, dof)
        dp = jnp.einsum("blhd,bmhd->bhlm", dof, vf)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhlm,bmhd->blhd", ds, kf)
        dk_blk = jnp.einsum("bhlm,blhd->bmhd", ds, qf)
        kc, vc, dkc, dvc = jax.lax.ppermute(
            (kc, vc, dkc + dk_blk, dvc + dv_blk), axis, perm)
        return (dq, kc, vc, dkc, dvc), None

    init = (jnp.zeros((b, l, h, d), F32), k, v,
            jnp.zeros(k.shape, F32), jnp.zeros(v.shape, F32))
    (dq, _, _, dk, dv), _ = jax.lax.scan(step, init, jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_block.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


# ------------------------------------------------------------------ public
def ring_attention(q, k, v, mesh: Mesh, axis: str, causal: bool = False,
                   layout: Optional[str] = None,
                   sm_scale: Optional[float] = None):
    """Context-parallel attention over ``mesh`` axis ``axis``.

    Args:
      q, k, v: (B, S, H, D) with the sequence axis sharded (or shardable) over
        ``axis``.  For ``layout="zigzag"`` the caller must pre-permute the
        sequence with :func:`zigzag_permutation` (and un-permute the output
        with :func:`zigzag_inverse`) — see tests/test_ring_attention.py.
      causal: mask.  Defaults the layout to "zigzag" (the symmetric-shift
        fold); full masks default to "contig" (the shift schedule).
      layout: "contig" | "zigzag" override (benchmarks compare both).
    Returns: (B, S, H, D), same layout as the inputs.
    """
    n = mesh.shape[axis]
    b, s, h, d = q.shape
    if layout is None:
        layout = "zigzag" if causal else "contig"
    if layout not in ("contig", "zigzag"):
        raise ValueError(f"unknown layout {layout!r}")
    if s % n:
        raise ValueError(f"seq {s} not divisible by ring size {n}")
    if layout == "zigzag" and s % (2 * n):
        raise ValueError(f"zigzag needs seq % (2·n) == 0, got {s} on {n}")
    scale = float(sm_scale) if sm_scale is not None else 1.0 / math.sqrt(d)
    ring_step_offsets(n, causal)   # derive + assert the DASH step order

    spec = P(None, axis, None, None)
    fn = jax.shard_map(
        lambda q_, k_, v_: _ring_block(q_, k_, v_, axis, n, causal, layout,
                                       scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
