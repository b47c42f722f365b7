"""Production mesh builders (spec: MULTI-POD DRY-RUN step 1).

A function — never a module-level constant — so importing never touches jax
device state (the dry-run pins the placeholder device count before first init).

How the context-parallel ("cp") axis composes with the production mesh
----------------------------------------------------------------------
The ring in :mod:`repro.dist.ring_attention` permutes KV blocks over one named
mesh axis.  Three deployments, in increasing intrusiveness:

  1. **Dedicated ring (tests/examples):** a 1-D ``("cp",)`` mesh — what the
     8-device CPU tests and ``examples/ring_attention_demo.py`` build.
  2. **Reuse the model axis:** on the production ``(data, model)`` mesh the
     ``RULE_SETS["cp"]`` rules shard the *sequence* over ``model`` and pass
     ``axis="model"`` to the ring; weights stay replicated along it.  This is
     the zero-topology-change option: the ``model`` axis's ICI ring carries
     the KV rotation, and per-chip attention work drops n×.
  3. **Dedicated cp sub-axis:** ``make_cp_mesh`` splits a pod into
     ``(data, cp, model)`` so TP and CP coexist — e.g. ``16×2×8``: data-
     parallel groups of 16 chips each running a 2-way KV ring around 8-way TP.
     Sequence shards over ``cp``, heads/MLP over ``model``; the ``cp`` ring
     hops are nearest-neighbour on the same ICI torus, so the shift/zigzag
     schedules' one-hop-per-step structure maps onto hardware links.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes. The sharding code places arrays with
    ``with_sharding_constraint`` and ``shard_map`` under a mesh it does not
    type, which the Explicit axes ``jax.make_mesh`` makes by default refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per v5e pod; the multi-pod mesh stacks 2 pods (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_cp_mesh(n_data: int = 16, n_cp: int = 2, n_model: int = 8):
    """Single-pod mesh with a dedicated context-parallel ring axis.

    ``n_data · n_cp · n_model`` must equal the chip count (256 for a v5e pod).
    The ``cp`` axis is the ring :func:`repro.dist.ring_attention.ring_attention`
    permutes over; ``RULE_SETS["cp"]``-style rules should map ``seq → cp`` and
    keep TP rules on ``model``.
    """
    return auto_mesh((n_data, n_cp, n_model), ("data", "cp", "model"))


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *, multi_pod: bool = False):
    """Small mesh for in-test lowering on forced-multi-device CPU."""
    if multi_pod:
        return auto_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return auto_mesh((n_data, n_model), ("data", "model"))
