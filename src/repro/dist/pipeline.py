"""GPipe-style pipeline parallelism over a mesh "stage" axis (shard_map).

``pipeline_apply`` runs ``x → stage_{S-1}(… stage_0(x))`` with the batch split
into ``n_micro`` microbatches streamed through the stage ring: activations hop
stage→stage via ``jax.lax.ppermute`` (lowering to ``collective-permute``),
every device executes the same program, and microbatch *j* occupies stage *i*
at tick ``j + i`` — the classic GPipe fill/drain diagram.

The pipeline is a DAG of (stage, microbatch) tasks with the same startup-term
structure as the paper's §3.2 analysis of FA3's reduction cascade: the first
output cannot leave before tick ``S-1``, so of the ``n_micro + S - 1`` total
ticks ``S-1`` are bubbles.  :func:`bubble_fraction` is that closed form.

Determinism: the tick loop is a ``lax.scan`` with a fixed per-tick collective
order, so results are bitwise run-to-run reproducible; gradients flow through
the scanned ppermute chain (its transpose is the reverse ring).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble fraction: (S-1) / (S-1 + M) — the §3.2 startup term of the
    pipeline DAG (zero for a single stage)."""
    if n_stages <= 1:
        return 0.0
    return (n_stages - 1) / (n_stages - 1 + n_micro)


def pipeline_apply(stage_fn: Callable, ws, x, mesh: Mesh, axis: str,
                   n_micro: int):
    """Apply ``n_stages`` shape-preserving stages to ``x`` with microbatching.

    Args:
      stage_fn: ``(stage_params, h) -> h`` with ``h`` shape-preserving (the
        activation buffer circulates the ring, so all stages share one shape).
      ws: pytree of stage parameters stacked on a leading ``(S, …)`` axis;
        device *i* of the stage mesh holds (only) ``ws[i]``.
      x: (B, …) global batch, replicated; ``B % n_micro == 0``.
      mesh, axis: stage mesh and its axis name (size S).
      n_micro: number of microbatches streamed through the pipeline.
    Returns: (B, …) outputs, replicated (identical on every stage device).
    """
    n_stages = mesh.shape[axis]
    batch = x.shape[0]
    if batch % n_micro:
        raise ValueError(f"batch {batch} not divisible by n_micro {n_micro}")
    mb_shape = (batch // n_micro,) + x.shape[1:]
    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]
    n_ticks = n_micro + n_stages - 1

    def per_device(w_loc, x_rep):
        w = jax.tree.map(lambda a: a[0], w_loc)      # this device's stage
        i = jax.lax.axis_index(axis)
        mbs = x_rep.reshape((n_micro,) + mb_shape)

        def tick(carry, t):
            act, buf = carry
            # stage 0 injects microbatch t (garbage beyond n_micro-1 drains
            # past the last tick and is never stored); others consume the
            # activation ppermuted from stage i-1 at the previous tick.
            mb = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            h = stage_fn(w, jnp.where(i == 0, mb, act))
            idx = t - (n_stages - 1)                 # microbatch leaving stage S-1
            upd = jax.lax.dynamic_update_slice_in_dim(
                buf, h[None].astype(buf.dtype), jnp.maximum(idx, 0), 0)
            buf = jnp.where(idx >= 0, upd, buf)
            act = jax.lax.ppermute(h, axis, perm)
            return (act, buf), None

        carry0 = (jnp.zeros(mb_shape, x_rep.dtype),
                  jnp.zeros((n_micro,) + mb_shape, x_rep.dtype))
        (_, buf), _ = jax.lax.scan(tick, carry0, jnp.arange(n_ticks))
        # only the last stage's buffer holds real outputs; mask + psum
        # replicates it to every device.
        out = jax.lax.psum(
            jnp.where(i == n_stages - 1, buf, jnp.zeros_like(buf)), axis)
        return out.reshape((batch,) + x.shape[1:])

    w_specs = jax.tree.map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), ws)
    rep = P(*([None] * x.ndim))
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=(w_specs, rep),
                       out_specs=rep, check_vma=False)
    return fn(ws, x)
