"""Deterministic reduction primitives (paper §1–§2, Table 1).

Floating-point addition is non-associative; an accumulation whose order depends on
execution timing (GPU atomics) is not run-to-run reproducible.  On TPU, XLA already
fixes reduction orders *within one compiled program*, but the order still changes
with sharding layout, mesh size, or compiler version.  This module provides
reductions with an **explicitly pinned association**, so that the numerical result
is a pure function of (inputs, declared order) — the substrate for:

  * the DASH backward kernel's dQ accumulation order (the schedule defines it),
  * cross-device gradient accumulation with a mesh-size-independent association
    (sequential or fixed-arity tree), enabling bitwise-reproducible elastic restarts,
  * the Table-1 style experiments (ordered vs. permuted accumulation deviation).

Scope note: ``ring_ordered_psum`` below pins the association *per topology*
(ascending device index — run-to-run stable for a fixed mesh, but a 2-device
ring and a 4-device ring fold different partials).  When the answer must be
identical *across* topologies — the serving contract — use
:func:`repro.dist.fold.fixed_fold_psum`, which folds a canonical virtual-shard
grid in a device-count-independent order and degenerates to
:func:`ordered_sum` on one device.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


def ordered_sum(parts: jax.Array, axis: int = 0) -> jax.Array:
    """Strict left-to-right fold along ``axis`` — association ((x0+x1)+x2)+…

    Unlike ``jnp.sum`` (whose reduction tree XLA may rebalance), the scan pins the
    association order, making the result independent of backend tiling.
    """
    parts = jnp.moveaxis(parts, axis, 0)
    init = jnp.zeros(parts.shape[1:], parts.dtype)

    def step(acc, x):
        return acc + x, None

    acc, _ = jax.lax.scan(step, init, parts)
    return acc


def tree_sum_fixed(parts: jax.Array, axis: int = 0, arity: int = 2) -> jax.Array:
    """Fixed-shape balanced tree reduction (deterministic, log-depth).

    Pads with zeros to a power of ``arity`` so the tree shape — hence association —
    depends only on the padded length, not on execution order.
    """
    parts = jnp.moveaxis(parts, axis, 0)
    n = parts.shape[0]
    size = 1
    while size < n:
        size *= arity
    if size != n:
        pad = jnp.zeros((size - n,) + parts.shape[1:], parts.dtype)
        parts = jnp.concatenate([parts, pad], 0)
    while parts.shape[0] > 1:
        parts = parts.reshape((parts.shape[0] // arity, arity) + parts.shape[1:])
        acc = parts[:, 0]
        for k in range(1, arity):  # pinned order within each tree node
            acc = acc + parts[:, k]
        parts = acc
    return parts[0]


def permuted_sum(parts: jax.Array, perm: np.ndarray, axis: int = 0) -> jax.Array:
    """Left-to-right fold in an arbitrary order — emulates the *non*-deterministic
    atomicAdd accumulation of the paper's baseline (Fig. 1 middle) for Table-1
    style deviation measurements."""
    parts = jnp.moveaxis(parts, axis, 0)
    return ordered_sum(parts[jnp.asarray(perm)], axis=0)


def schedule_ordered_dq(partials: jax.Array, reduction_order: Sequence[int]) -> jax.Array:
    """Accumulate dQ partials (stacked along axis 0, one per KV tile) in the order
    prescribed by a DASH schedule column. Deterministic by construction; different
    schedules give (bitwise) different but individually reproducible results."""
    return permuted_sum(partials, np.asarray(reduction_order, np.int32))


# --------------------------------------------------------------------------- #
# cross-device ordered accumulation
# --------------------------------------------------------------------------- #
def ring_ordered_psum(x: jax.Array, axis_name: str) -> jax.Array:
    """All-reduce whose association order is pinned to ascending device index.

    Implemented as an (n-1)-step ``ppermute`` ring pass accumulating left-to-right,
    followed by a broadcast of the completed sum from the last rank. Association is
    ((x0+x1)+x2)+… regardless of mesh topology — the cross-chip analogue of the
    paper's ordered dQ accumulation. Cost: 2(n-1) hops vs. all-reduce's optimal
    bandwidth; use for reproducibility-critical, latency-tolerant reductions
    (e.g. metrics, or full gradients when bitwise elasticity is required).
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    acc = x
    for step in range(n - 1):
        shifted = jax.lax.ppermute(acc, axis_name, fwd)
        # rank k at step s holds the running sum of ranks [0..k] once s >= k
        acc = jnp.where(idx == step + 1, shifted + x, jnp.where(idx > step + 1, x, acc))
    # ranks < n-1 now need the total: broadcast from the last rank. psum of a
    # one-hot-masked operand is bitwise-exact (x + 0.0 == x for finite x), so the
    # broadcast does not perturb the pinned association.
    return jax.lax.psum(jnp.where(idx == n - 1, acc, jnp.zeros_like(acc)), axis_name)


def max_deviation(fn, key: jax.Array, n_runs: int = 10) -> float:
    """Max elementwise deviation of ``fn(run_index)`` across runs vs. run 0 —
    the paper's Table-1 metric ``M_r = max |q_r - q_ref|``."""
    ref = fn(0)
    dev = 0.0
    for i in range(1, n_runs):
        out = fn(i)
        dev = max(dev, float(jnp.max(jnp.abs(out - ref))))
    return dev
