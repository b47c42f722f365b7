"""Layer names inside the compiled step, and the map back from its ops.

The train and serve steps open :func:`scope` around each layer of the model
(``jax.named_scope("layer.<name>")``). A named scope is metadata only: it
lands in the ``op_name`` of every HLO instruction the layer lowers to, in
forward (``jvp(layer.mlp)``), recomputed (``…/rematted_computation/…``) and
backward (``transpose(jvp(layer.mlp))``) form, and changes no instruction of
the compiled program. :func:`op_layers` reads those names back out of a
compiled executable's optimized HLO (``Compiled.as_text()``), so a profiler
trace's device ops can be summed per layer by instruction name.

The vocabulary (where each scope sits):

  ``embed``      the token embedding, with its one-hot backward
                 (``models/layers.apply_embed``);
  ``attn_proj``  the pre-norm, q/k/v/o projections, biases and rotary of an
                 attention sublayer, not the attention kernel;
  ``attention``  the attention kernel call (``kernels.ops.attention`` in
                 ``models/layers._sdpa_full``) and the paged decode kernel;
  ``mlp``        the pre-norm and the MLP of a block;
  ``head_loss``  the final norm, the LM head and the cross-entropy;
  ``optimizer``  gradient clipping and the optimizer update;
  ``sample``     the serving engine's sampler (serving only).
"""
from __future__ import annotations

import collections
import re
from typing import Dict, Optional, Tuple

import jax

LAYERS = ("embed", "attn_proj", "attention", "mlp", "head_loss", "optimizer",
          "sample")
TRAIN_LAYERS = LAYERS[:6]
PREFIX = "layer."
CONTAINERS = ("while", "conditional", "call")


def scope(name: str):
    """``with scope("mlp"): ...`` names every op traced inside it."""
    if name not in LAYERS:
        raise ValueError(f"unknown layer {name!r}; one of {LAYERS}")
    return jax.named_scope(PREFIX + name)


_WRAPPER = re.compile(r"^[\w\-]+\((.*)\)$")


def _unwrap(segment: str) -> str:
    """``transpose(jvp(layer.mlp))`` -> ``layer.mlp``."""
    m = _WRAPPER.match(segment)
    while m:
        segment = m.group(1)
        m = _WRAPPER.match(segment)
    return segment


def op_name_scope(op_name: str) -> Tuple[Optional[str], str]:
    """(layer, pass) of one ``op_name``: the innermost ``layer.<name>`` in
    its path (None where it has none), and ``backward`` under a
    ``transpose(…)``, ``recompute`` under a ``rematted_computation`` of
    the backward, else ``forward``."""
    layer = None
    for seg in op_name.split("/"):
        inner = _unwrap(seg)
        if inner.startswith(PREFIX) and inner[len(PREFIX):] in LAYERS:
            layer = inner[len(PREFIX):]
    if "rematted_computation" in op_name:
        pass_ = "recompute"
    elif "transpose(" in op_name:
        pass_ = "backward"
    else:
        pass_ = "forward"
    return layer, pass_


_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (?:\(.*?\)|\S+) "
                          r"([\w\-]+)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLED = re.compile(r"\b(calls|to_apply|body|condition|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")

Scope = Tuple[Optional[str], str]


def _computations(hlo_text: str):
    """({computation: [(instruction, opcode, op_name or None, fusion's
    computation or None)]}, computations that run as sequences of device
    ops: the entry and what control flow calls from it)."""
    comps: Dict[str, list] = {}
    edges: Dict[str, list] = collections.defaultdict(list)
    entry, comp = None, None
    for line in hlo_text.splitlines():
        if comp is None:
            m = _COMPUTATION.match(line)
            if m:
                comp = m.group(2)
                comps[comp] = []
                if m.group(1):
                    entry = comp
            continue
        if line.startswith("}"):
            comp = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, opcode = m.groups()
        op = _OP_NAME.search(line)
        fused = None
        for kind, callee in _CALLED.findall(line):
            if opcode == "fusion" and kind == "calls":
                fused = callee
            elif kind != "to_apply" or opcode == "call":
                edges[comp].append(callee)
        for branches in _BRANCHES.findall(line):
            edges[comp] += [b.strip().lstrip("%") for b in branches.split(",")]
        comps[comp].append((name, opcode, op.group(1) if op else None, fused))
    run, todo = set(), [entry] if entry else []
    while todo:
        c = todo.pop()
        if c not in run:
            run.add(c)
            todo += edges.get(c, [])
    return comps, run


def op_scopes(hlo_text: str) -> Dict[str, Scope]:
    """{instruction: (layer or None, pass)} for every instruction of an
    optimized HLO module that runs as an op of its own on the device: those
    of the entry computation and of the bodies that control flow runs, but
    not the instructions inside fusions or reductions, nor ``while``/
    ``call``/``conditional`` containers, whose events span the ops of their
    bodies.

    A fusion takes its own ``op_name``; one without takes the most common
    scope among its fused instructions (:func:`_majority`)."""
    comps, run = _computations(hlo_text)
    out: Dict[str, Scope] = {}
    for comp in run:
        for name, opcode, op_name, fused in comps[comp]:
            if opcode in CONTAINERS:
                continue
            if op_name is None and fused is not None:
                out[name] = _majority(o for _, _, o, _ in comps.get(fused, ()))
            else:
                out[name] = op_name_scope(op_name or "")
    return out


def _majority(op_names) -> Scope:
    """The most common scope that names a layer among ``op_names``, else
    the most common scope; ties go to the first seen."""
    counts = collections.Counter(op_name_scope(o) for o in op_names if o)
    named = [s for s in counts if s[0] is not None]
    pool = named or list(counts)
    if not pool:
        return None, "forward"
    return max(pool, key=lambda s: counts[s])


def op_layers(hlo_text: str) -> Dict[str, Optional[str]]:
    """{instruction: layer or None} (see :func:`op_scopes`)."""
    return {k: layer for k, (layer, _) in op_scopes(hlo_text).items()}
