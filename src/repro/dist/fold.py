"""Topology-invariant reductions over a canonical virtual-shard grid.

:func:`repro.core.determinism.ring_ordered_psum` pins a reduction's
association to ascending *device* index — bitwise-deterministic per topology,
but the fold tree still changes with the device count (TP=2 folds 2 operands,
TP=4 folds 4).  Serving needs one notch more (HEAL / "Deterministic Inference
across Tensor Parallel Sizes", PAPERS.md): the association must be a pure
function of a **logical** grid chosen once per model, so that TP=1, TP=2 and
TP=4 all compute the *same* fold tree and a request's tokens are bitwise
independent of the mesh it happened to be served on.

The mechanism is a strict left fold over **virtual shards**:

* every row-parallel contraction (attention ``wo``, MLP ``w_down``) is cut
  into ``V`` fixed-width partial products — ``V`` depends only on the model
  config (the canonical grid is ``V = n_heads``), never on the mesh;
* the partials are summed as ``((0 + p_0) + p_1) + … + p_{V-1}`` in ascending
  virtual-shard order.  A strict left fold is *device-boundary invariant*:
  cutting the sequence of partials into per-device runs changes which rank
  holds which operands but not the association, so rank ``r`` can continue the
  fold exactly where rank ``r-1`` left off.

:func:`fixed_fold_psum` implements that continuation as an (n−1)-step
``ppermute`` ring (rank 0 folds its partials from zero, passes the running
accumulator right, each rank folds its own partials on top one at a time),
then broadcasts the completed total with the auditor-blessed one-hot ``psum``
(every non-final rank contributes exact float zeros — see
``repro.verify.trace``).  With no mesh axis the same function degenerates to
the local left fold, which is why the single-device serve path and every TP
degree agree bitwise.

:func:`canonical_scope` is how the model code switches into this discipline:
``transformer.paged_step`` always enters it (serve math is canonical at every
topology), and ``transformer.forward`` enters it when
``cfg.canonical_reductions`` is set (train≡serve parity mode).  Column-
parallel projections (wq/wk/wv, w_up/w_gate, lm_head) need no special form:
slicing the *output* columns of a matmul is bitwise-stable, and is verified
by the property tests in tests/test_dist_collectives.py.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


# --------------------------------------------------------------------------- #
# canonical-reduction scope
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class _Scope:
    axis_name: Optional[str]      # mesh axis carrying the fold ring (None=local)
    page_size: int                # paged-walk granularity for train-side attention


_STATE = threading.local()


@contextlib.contextmanager
def canonical_scope(axis_name: Optional[str] = None, page_size: int = 0):
    """Enter canonical-reduction mode for the code traced inside.

    Re-entrant with outer-wins semantics: ``paged_step`` unconditionally opens
    a local scope, and the sharded step builder wraps it with the mesh axis —
    the inner (axis-less) entry must not clobber the outer ring axis.  This is
    trace-time state: the decisions it gates are baked into the jaxpr.
    """
    if getattr(_STATE, "scope", None) is not None:
        yield
        return
    _STATE.scope = _Scope(axis_name, page_size)
    try:
        yield
    finally:
        _STATE.scope = None


def active() -> bool:
    return getattr(_STATE, "scope", None) is not None


def scope_axis() -> Optional[str]:
    s = getattr(_STATE, "scope", None)
    return s.axis_name if s is not None else None


def scope_pages() -> int:
    s = getattr(_STATE, "scope", None)
    return s.page_size if s is not None else 0


def exact_jit(fun, **kw):
    """``jax.jit`` for a program whose bits must not depend on the mesh.

    By default XLA may keep a fused bf16 intermediate in f32 ("excess
    precision"), and whether it does follows the fusion, which differs
    between the single-device and the sharded paged step. On a TPU v5e that
    alone moved ``x + attn_out`` off by a bf16 ulp at tp=4 while both
    operands were bitwise equal. With it off every bf16 value is rounded
    where the program says, whatever the fusion.
    """
    return jax.jit(fun, compiler_options={"xla_allow_excess_precision": False},
                   **kw)


# --------------------------------------------------------------------------- #
# the fold
# --------------------------------------------------------------------------- #
def _fold_onto(init: jax.Array, parts: jax.Array) -> jax.Array:
    """Continue a strict left fold: ((init + p_0) + p_1) + … ."""

    def step(acc, p):
        return acc + p, None

    acc, _ = jax.lax.scan(step, init, parts)
    return acc


def fixed_fold_psum(parts: jax.Array, axis_name: Optional[str] = None) -> jax.Array:
    """Sum ``parts`` in ascending virtual-shard order, mesh-independently.

    Args:
      parts: ``(v_local, …)`` — this rank's consecutive slice of the canonical
        virtual-shard grid, stacked ascending along axis 0.  With a mesh axis
        of size ``n``, rank ``r`` holds virtual shards
        ``[r·v_local, (r+1)·v_local)`` of the ``V = n·v_local`` global grid.
      axis_name: mesh axis to ring over; ``None`` (or size 1) folds locally.

    Returns:
      ``((0 + p_0) + p_1) + … + p_{V-1}`` — identical bits for every ``n``
      dividing ``V``, including ``n = 1``; equal to
      ``core.determinism.ordered_sum`` of the full grid.
    """
    zero = jnp.zeros(parts.shape[1:], parts.dtype)
    if axis_name is None or jax.lax.axis_size(axis_name) == 1:
        return _fold_onto(zero, parts)
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    # rank 0's fold is final for its prefix; every other rank pre-folds too but
    # overwrites below once the true prefix arrives over the ring
    acc = _fold_onto(zero, parts)
    for step in range(n - 1):
        shifted = jax.lax.ppermute(acc, axis_name, fwd)
        # rank step+1 now holds the completed prefix of ranks [0..step]:
        # continue the left fold through its own partials, one at a time
        acc = jnp.where(idx == step + 1, _fold_onto(shifted, parts), acc)
    # broadcast the completed total from the last rank: psum of a one-hot
    # masked operand adds exact float zeros (blessed by verify.trace), so the
    # pinned association survives the collective
    return jax.lax.psum(
        jnp.where(idx == n - 1, acc, jnp.zeros_like(acc)), axis_name)


def canonical_row_dot(x: jax.Array, w: jax.Array, shard_width: int,
                      out_dtype=None) -> jax.Array:
    """Row-parallel matmul in canonical fold form: ``x @ w`` with the
    contraction cut into ``shard_width``-wide virtual shards and the partial
    products summed by :func:`fixed_fold_psum`.

    ``shard_width = K_global / V`` must be mesh-independent (callers derive it
    from the *global* config: ``head_dim`` for ``wo``, ``d_ff / n_heads`` for
    ``w_down``); under TP the local operands carry ``K_local = K_global / n``
    rows, i.e. ``V / n`` whole virtual shards.  Partials accumulate in fp32
    (each partial is its own fp32-accumulated ``dot_general``, bitwise equal
    to the same columns inside a wider contraction only because the *split*
    boundaries are fixed by the grid — that is the whole point).
    """
    k_local = x.shape[-1]
    v_local, rem = divmod(k_local, shard_width)
    assert rem == 0, (k_local, shard_width)
    xs = jnp.moveaxis(
        x.reshape(x.shape[:-1] + (v_local, shard_width)), -2, 0)
    ws = w.reshape((v_local, shard_width) + w.shape[1:])

    def one(operands):
        xv, wv = operands
        return jax.lax.dot_general(xv, wv, (((xv.ndim - 1,), (0,)), ((), ())),
                                   preferred_element_type=F32)

    parts = jax.lax.map(one, (xs, ws))
    out = fixed_fold_psum(parts, scope_axis())
    return out.astype(out_dtype) if out_dtype is not None else out
