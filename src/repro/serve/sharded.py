"""Tensor-parallel paged serving step: shard_map over a TP/CP mesh.

The sharded step is the *same* :func:`repro.models.transformer.paged_step`
traced under :func:`repro.dist.fold.canonical_scope` with the mesh's model
axis — no second model implementation.  What the mesh changes is only *where*
slices of column/row-parallel operands live:

  * wq/bq, w_up/w_gate sliced over output columns; lm_head over vocab columns
    (slicing matmul output columns is bitwise-stable — property-tested in
    tests/test_dist_collectives.py);
  * wk/wv (and the KV pools, on their head axis) sliced when ``tp`` divides
    ``n_kv_heads``, replicated otherwise (each rank then selects the
    contiguous kv-head slice backing its query heads inside the block);
  * wo / w_down sliced over contraction rows — whole virtual shards of the
    canonical fold grid, reduced by :func:`repro.dist.fold.fixed_fold_psum`
    in the mesh-independent ascending virtual order.

Per-request tokens are therefore bitwise identical across TP degrees, mesh
reshapes, and vs. the single-device engine (tests/test_serve_invariance.py
proves it under forced host devices).  The host-side machinery — FCFS
scheduler, page allocator, samplers — is untouched: it only ever sees full
(replicated) logits.
"""
from __future__ import annotations

import functools
import json

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import fold
from repro.models import transformer as T

AXIS = "model"


def _spec_at(ndim: int, dim: int) -> P:
    """PartitionSpec sharding dimension ``dim`` (negative ok) over the model
    axis, replicating the rest."""
    axes = [None] * ndim
    axes[dim] = AXIS
    return P(*axes)


def validate_tp(cfg, tp: int) -> None:
    """Loud preconditions for a mesh-invariant sharded engine."""
    if cfg.n_heads % tp != 0:
        raise ValueError(
            f"tp={tp} must divide n_heads={cfg.n_heads} (query heads are "
            f"column-sliced; the canonical fold grid is per-head)")
    if cfg.d_ff % cfg.n_heads != 0:
        raise ValueError(
            f"canonical reductions need n_heads | d_ff; got d_ff={cfg.d_ff}, "
            f"n_heads={cfg.n_heads}")
    h_loc = cfg.n_heads // tp
    g = cfg.n_heads // cfg.n_kv_heads
    if h_loc % g != 0 and g % h_loc != 0:
        raise ValueError(
            f"tp={tp} leaves {h_loc} query heads per rank spanning a "
            f"non-contiguous slice of {cfg.n_kv_heads} kv heads (group {g})")


def _param_specs(cfg, params, tp: int):
    """Per-leaf PartitionSpecs keyed on the parameter names layers declares."""
    kv_ok = cfg.n_kv_heads % tp == 0
    vocab_ok = (not cfg.tie_embeddings) and cfg.padded_vocab % tp == 0

    def leaf_spec(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        parent = str(getattr(path[-2], "key", path[-2])) if len(path) > 1 else ""
        nd = leaf.ndim
        if name in ("wq", "bq", "w_up", "w_gate"):
            return _spec_at(nd, -1)                     # output columns
        if name in ("wk", "wv", "bk", "bv"):
            return _spec_at(nd, -1) if kv_ok else P(*([None] * nd))
        if name in ("wo", "w_down"):
            return _spec_at(nd, -2)                     # contraction rows
        if name == "w" and parent == "lm_head":
            return _spec_at(nd, -1) if vocab_ok else P(*([None] * nd))
        return P(*([None] * nd))                        # norms, embed, biases

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def _pool_specs(cfg, caches, tp: int):
    """KV pools (n_rep, n_pages, page_size, Hk, D): shard the head axis when
    it divides, else replicate (every rank computes/writes all kv heads)."""
    kv_ok = cfg.n_kv_heads % tp == 0
    return jax.tree.map(
        lambda leaf: _spec_at(leaf.ndim, -2) if kv_ok
        else P(*([None] * leaf.ndim)), caches)


@functools.lru_cache(maxsize=None)
def _builder_cache(cfg, mesh):
    tp = int(mesh.shape[AXIS])
    validate_tp(cfg, tp)
    vocab_ok = (not cfg.tie_embeddings) and cfg.padded_vocab % tp == 0
    logits_spec = P(None, None, AXIS) if vocab_ok else P(None, None, None)

    def step(params, caches, tokens, positions, page_table, wp, wo):
        with fold.canonical_scope(axis_name=AXIS):
            return T.paged_step(params, caches, tokens, positions,
                                page_table, wp, wo, cfg=cfg)

    def make(params, caches):
        in_specs = (_param_specs(cfg, params, tp),
                    _pool_specs(cfg, caches, tp),
                    P(None, None), P(None, None), P(None, None),
                    P(None), P(None))
        out_specs = (logits_spec, _pool_specs(cfg, caches, tp))
        return fold.exact_jit(jax.shard_map(step, mesh=mesh,
                                            in_specs=in_specs,
                                            out_specs=out_specs,
                                            check_vma=False))

    return make


def place_on_mesh(cfg, mesh, params, caches):
    """``(params, caches)`` moved onto ``mesh`` with the sharded step's own
    in_specs. Done once: a step fed arrays that live on one device reshards
    the whole model on every call, and that device keeps a full copy."""
    tp = int(mesh.shape[AXIS])

    def put(tree, specs):
        return jax.device_put(tree, jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P)))

    return (put(params, _param_specs(cfg, params, tp)),
            put(caches, _pool_specs(cfg, caches, tp)))


def make_sharded_paged_step(cfg, mesh, params, caches, prof=None):
    """Build the jitted TP-sharded paged step for ``cfg`` on ``mesh``.

    ``params`` / ``caches`` are example pytrees (specs are per-leaf); the
    returned callable has the exact :func:`transformer.paged_step` signature
    minus ``cfg``.  The mesh must carry a ``"model"`` axis; any other axes
    (e.g. a ``"data"`` axis from a mesh reshape) are replicated over, which is
    how a (2, 2) mesh serves bitwise-identically to a (4,) mesh.

    ``prof``: optional :class:`repro.obs.prof.Profiler` — wraps the build in
    a ``sharded_build`` span recording the TP degree and mesh axes (a no-op
    when disarmed; the step itself is never profiled from inside, trackers
    stay host-side only).
    """
    if prof is None:
        return _builder_cache(cfg, mesh)(params, caches)
    axes = {str(k): int(v) for k, v in mesh.shape.items()}
    with prof.span("sharded_build", scope=f"mesh:{sorted(axes.items())}",
                   lane="engine", tp=axes.get("model", 1),
                   mesh_axes=json.dumps(axes, sort_keys=True)):
        return _builder_cache(cfg, mesh)(params, caches)
