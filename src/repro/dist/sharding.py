"""Logical-axis sharding (levanter/haliax-style) for the whole repo.

Model code annotates parameters (``ParamDef.axes``) and activations
(:func:`shard`) with *logical* axis names — "embed", "heads", "batch",
"seq_sp", … — and a **rule set** maps each logical name onto zero or more
*mesh* axes at lowering time.  The same model code therefore lowers correctly
under every parallelism style; switching TP → FSDP+TP → CP is a rules swap,
not a model edit.

Layers:
  * ``RULE_SETS[name](multi_pod) -> rules``: logical name → tuple of mesh axes
    (or None).  ``tp`` (tensor parallel), ``fsdp_tp`` (ZeRO-3 over the data
    axis + TP), ``zero3_pod`` (ZeRO-3 over (pod, data) — the multi-pod
    variant), ``cp`` (context parallel: sequence over the model axis).
  * ``use_rules(rules, mesh)``: context manager activating a rule set; inside
    it :func:`shard` becomes a ``with_sharding_constraint`` (``jax.set_mesh``
    lets ``jax.jit`` resolve bare ``PartitionSpec`` shardings).
  * ``logical_to_spec`` / ``spec_tree_to_pspecs``: logical axes →
    ``PartitionSpec`` (trees), used by ``train/step.py`` and the dry-run.
  * ``sanitize_pspecs``: drop mesh axes that are absent from the mesh or do
    not divide the concrete dim (heads=14 on tp=16, …).

Outside any ``use_rules`` context :func:`shard` is the identity, so pure
single-device unit tests never touch mesh machinery.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Dict[str, Optional[Tuple[str, ...]]]

_state = threading.local()


def _stack():
    if not hasattr(_state, "stack"):
        _state.stack = []
    return _state.stack


def _current_rules_mesh():
    for rules, mesh in reversed(_stack()):
        if rules is not None:
            return rules, mesh
    return None


@contextlib.contextmanager
def use_rules(rules: Rules, mesh: Mesh):
    """Activate a logical→mesh rule set for :func:`shard`."""
    _stack().append((rules, mesh))
    try:
        yield
    finally:
        _stack().pop()


# --------------------------------------------------------------------- specs
def _axes_of(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def logical_to_spec(axes, rules: Rules) -> P:
    """Map a tuple of logical axis names (or None) to a PartitionSpec."""
    entries = []
    for a in axes:
        v = rules.get(a) if a is not None else None
        v = _axes_of(v)
        entries.append(None if not v else (v[0] if len(v) == 1 else v))
    return P(*entries)


def _is_axes_leaf(x) -> bool:
    return isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x)


def spec_tree_to_pspecs(spec_tree, rules: Rules):
    """Logical-axes tree (from ``models.module.spec_tree``) → PartitionSpec tree."""
    return jax.tree.map(lambda a: logical_to_spec(a, rules), spec_tree,
                        is_leaf=_is_axes_leaf)


def _sanitize_one(spec: P, shape, mesh: Mesh) -> P:
    """Drop axes not on the mesh, non-dividing axes, and duplicate uses."""
    used = set()
    out = []
    for d, entry in enumerate(tuple(spec)):
        axes = tuple(a for a in _axes_of(entry)
                     if a in mesh.shape and a not in used)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if not axes or d >= len(shape) or shape[d] % size != 0:
            out.append(None)
        else:
            used.update(axes)
            out.append(axes[0] if len(axes) == 1 else axes)
    return P(*out)


def sanitize_pspecs(pspecs, shaped, mesh: Mesh):
    """Sanitize a PartitionSpec tree against a matching (ShapeDtypeStruct or
    array) tree: axes absent from ``mesh`` or not dividing the dim become None."""
    return jax.tree.map(lambda s, a: _sanitize_one(s, a.shape, mesh),
                        pspecs, shaped, is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------- shard
def shard(x, *logical):
    """Constrain ``x`` to the sharding its logical axes resolve to.

    Identity when no ``use_rules`` context is active (single-device tests);
    axes that are absent from the mesh or do not divide the dim are dropped
    (heads=14 on tp=16 replicates instead of failing).
    """
    ctx = _current_rules_mesh()
    if ctx is None:
        return x
    rules, mesh = ctx
    spec = _sanitize_one(logical_to_spec(logical, rules), x.shape, mesh)
    if all(e is None for e in tuple(spec)):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ----------------------------------------------------------------- rule sets
def _batch_axes(multi_pod: bool) -> Tuple[str, ...]:
    return ("pod", "data") if multi_pod else ("data",)


def _tp(multi_pod: bool = False) -> Rules:
    """Tensor parallel over "model"; batch over ("pod",) "data"; params
    replicated along data (fits small/medium archs)."""
    batch = _batch_axes(multi_pod)
    return {
        # activations
        "batch": batch,
        "moe_group": batch + ("model",),
        "seq": None,
        "seq_sp": ("model",),          # sequence-parallel residual stream
        "act_embed": None,
        "act_heads": ("model",),
        "act_mlp": ("model",),
        # parameters
        "embed": None,
        "heads": ("model",),
        "kv": ("model",),
        "mlp": ("model",),
        "vocab": ("model",),
        "experts": ("model",),
        "layers": None,
    }


def _fsdp_tp(multi_pod: bool = False) -> Rules:
    """ZeRO-3: parameters/optimizer sharded over "data" along their embed dim,
    on top of TP — required for the BIG archs (see launch/dryrun.py)."""
    rules = _tp(multi_pod)
    rules["embed"] = ("data",)
    return rules


def _zero3_pod(multi_pod: bool = True) -> Rules:
    """Cross-pod ZeRO-3: parameters sharded over ("pod", "data") — halves the
    per-device optimizer footprint again on the 2-pod mesh at the price of a
    cross-pod all-gather per layer."""
    rules = _tp(multi_pod)
    rules["embed"] = ("pod", "data") if multi_pod else ("data",)
    return rules


def _cp(multi_pod: bool = False) -> Rules:
    """Context parallel: the "model" axis doubles as the ring ("cp") axis —
    sequence sharded, weights replicated along it (see launch/mesh.py for how
    a dedicated cp axis composes with the production (data, model) mesh)."""
    batch = _batch_axes(multi_pod)
    return {
        "batch": batch,
        "moe_group": batch + ("model",),
        "seq": ("model",),
        "seq_sp": ("model",),
        "act_embed": None,
        "act_heads": None,
        "act_mlp": None,
        "embed": None,
        "heads": None,
        "kv": None,
        "mlp": None,
        "vocab": None,
        "experts": ("model",),
        "layers": None,
    }


RULE_SETS = {
    "tp": _tp,
    "fsdp_tp": _fsdp_tp,
    "zero3_pod": _zero3_pod,
    "cp": _cp,
}
