"""Jaxpr nondeterminism auditor — a lint and a test oracle.

Walks every equation of a (closed) jaxpr, recursing into control-flow and
call sub-jaxprs (``scan``/``while``/``cond``/``pjit``/``remat``/``shard_map``
/ ``custom_vjp`` …), and flags primitives whose result can depend on
execution order rather than on (inputs, declared order):

* ``unordered-scatter`` — scatters with ``unique_indices=False``: for the
  accumulating variants (``scatter-add`` / ``-mul`` / ``-min`` / ``-max``)
  duplicate index groups accumulate in whatever order the backend picks (GPU
  atomics; the paper's Fig. 1 baseline), and for plain overwrite ``scatter``
  which duplicate *wins* is equally backend-defined.  Only
  ``unique_indices=True`` scatters are order-free and pass.
* ``unordered-psum`` — cross-replica ``psum``/``psum_scatter`` whose
  association follows mesh topology, so bits change with device count.  The
  blessed exception is ``core.determinism.ring_ordered_psum``'s broadcast
  idiom: a psum whose operand is masked by ``select_n`` with a predicate
  comparing against ``axis_index`` — one rank contributes, every other adds
  exact zeros, so the pinned association is preserved.  A generic
  ``where``-masked psum is *not* blessed (its mask may select many ranks).
* ``reduce-precision-mismatch`` / ``nonstandard-reduce-precision`` —
  ``reduce_precision`` calls outside the IEEE set {f32, bf16, f16, f64}, or
  two different (exponent, mantissa) targets inside one program (a classic
  source of silently diverging replicas).
* ``unstable-sort`` — ``sort`` with ``is_stable=False``: tie order is
  backend-defined.

Used three ways: as a CI lint over the default lowered train step
(``python -m repro.verify.trace``), as a test oracle
(tests/test_verify_trace.py seeds a nondeterministic scatter and asserts it
is caught), and ad hoc via :func:`audit_fn` on any traceable callable.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, FrozenSet, List, Optional, Sequence

import jax
from jax.extend import core as jex_core

UNORDERED_SCATTERS = frozenset(
    {"scatter", "scatter-add", "scatter-mul", "scatter-min", "scatter-max"})
CROSS_REPLICA_SUMS = frozenset({"psum", "psum2", "psum_scatter"})
# IEEE (exponent_bits, mantissa_bits): f64, f32, bf16, f16
BLESSED_PRECISIONS = frozenset({(11, 52), (8, 23), (8, 7), (5, 10)})


@dataclasses.dataclass(frozen=True)
class Finding:
    code: str          # e.g. "unordered-scatter"
    primitive: str
    detail: str

    def __str__(self):
        return f"[{self.code}] {self.primitive}: {self.detail}"


def _subjaxprs(params: Dict[str, Any]):
    """Yield every Jaxpr/ClosedJaxpr reachable from an eqn's params."""
    for v in params.values():
        items = v if isinstance(v, (list, tuple)) else (v,)
        for item in items:
            if isinstance(item, jex_core.Jaxpr):
                yield item
            elif isinstance(item, jex_core.ClosedJaxpr):
                yield item.jaxpr


_LOOK_THROUGH = frozenset({"convert_element_type", "reshape", "squeeze",
                           "broadcast_in_dim", "copy"})
_CALL_LIKE = frozenset({"jit", "closed_call", "core_call", "custom_jvp_call",
                        "custom_vjp_call", "remat2", "checkpoint"})
_COMPARISONS = frozenset({"eq", "ne", "lt", "le", "gt", "ge"})


class _Frame:
    """One jaxpr plus its producer map and the call eqn that entered it, so
    variable origins can be chased across sub-jaxpr boundaries both downward
    (call outvar → inner outvar) and upward (inner invar → call operand)."""

    def __init__(self, jaxpr, parent=None, call_eqn=None):
        self.jaxpr = jaxpr
        self.producers = {id(o): e for e in jaxpr.eqns for o in e.outvars}
        self.parent = parent
        self.call_eqn = call_eqn


def _origin(var, frame: _Frame, depth: int = 0):
    """(eqn, frame) producing ``var``, looking through bit/shape-preserving
    ops and call wrappers; (None, None) when the chase leaves known ground."""
    if depth > 16 or frame is None:
        return None, None
    src = frame.producers.get(id(var))
    if src is None:
        # an invar of this jaxpr: map positionally to the parent call operand
        if frame.parent is None or frame.call_eqn is None:
            return None, None
        for i, v in enumerate(frame.jaxpr.invars):
            if v is var and i < len(frame.call_eqn.invars):
                return _origin(frame.call_eqn.invars[i], frame.parent,
                               depth + 1)
        return None, None
    name = src.primitive.name
    if name in _LOOK_THROUGH:
        return _origin(src.invars[0], frame, depth + 1)
    if name in _CALL_LIKE:
        sub = list(_subjaxprs(src.params))
        if len(sub) == 1:
            try:
                i = src.outvars.index(var)
            except ValueError:
                return None, None
            inner = _Frame(sub[0], parent=frame, call_eqn=src)
            return _origin(inner.jaxpr.outvars[i], inner, depth + 1)
        return None, None
    return src, frame


def _is_axis_index_one_hot(eqn, frame: _Frame) -> bool:
    """True iff every operand of ``eqn`` is a ``select_n`` whose predicate is
    a comparison against ``axis_index`` — the ring_ordered_psum broadcast
    idiom (psum of a value masked to exactly one rank adds exact zeros,
    preserving the pinned association).  An arbitrary ``where``-masked psum
    is NOT blessed: its mask can select many ranks and the sum re-associates
    with topology."""
    if not eqn.invars:
        return False
    for var in eqn.invars:
        sel, sel_frame = _origin(var, frame)
        if sel is None or sel.primitive.name != "select_n":
            return False
        cmp, cmp_frame = _origin(sel.invars[0], sel_frame)   # the predicate
        if cmp is None or cmp.primitive.name not in _COMPARISONS:
            return False
        sides = [_origin(cv, cmp_frame)[0] for cv in cmp.invars]
        if not any(s is not None and s.primitive.name == "axis_index"
                   for s in sides):
            return False
    return True


def audit_jaxpr(jaxpr, *, allow: Sequence[str] = ()) -> List[Finding]:
    """Audit a ``Jaxpr``/``ClosedJaxpr``; returns findings (empty == clean).

    ``allow`` suppresses finding codes by name (e.g. a job that accepts
    topology-dependent gradient bits may allow ``unordered-psum``).
    """
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    allow_set: FrozenSet[str] = frozenset(allow)
    findings: List[Finding] = []
    precisions = {}

    def emit(code, prim, detail):
        if code not in allow_set:
            findings.append(Finding(code, prim, detail))

    def walk(frame: _Frame):
        for eqn in frame.jaxpr.eqns:
            name = eqn.primitive.name
            if name in UNORDERED_SCATTERS:
                if not eqn.params.get("unique_indices", False):
                    emit("unordered-scatter", name,
                         "scatter with unique_indices=False — duplicate "
                         "indices reduce (or last-write-win) in "
                         "backend-defined order")
            elif name in CROSS_REPLICA_SUMS:
                if not _is_axis_index_one_hot(eqn, frame):
                    axes = eqn.params.get("axes",
                                          eqn.params.get("axis_name", "?"))
                    emit("unordered-psum", name,
                         f"cross-replica sum over axes {axes} — association "
                         "follows mesh topology; use core.determinism."
                         "ring_ordered_psum for pinned association")
            elif name == "reduce_precision":
                pair = (eqn.params.get("exponent_bits"),
                        eqn.params.get("mantissa_bits"))
                precisions.setdefault(pair, name)
                if pair not in BLESSED_PRECISIONS:
                    emit("nonstandard-reduce-precision", name,
                         f"(exponent, mantissa) = {pair} is not an IEEE "
                         "format; replicas disagreeing on this truncation "
                         "diverge silently")
            elif name == "sort":
                if not eqn.params.get("is_stable", True):
                    emit("unstable-sort", name,
                         "is_stable=False — tie order is backend-defined")
            for sub in _subjaxprs(eqn.params):
                walk(_Frame(sub, parent=frame, call_eqn=eqn))

    walk(_Frame(jaxpr))
    if len(precisions) > 1:
        emit("reduce-precision-mismatch", "reduce_precision",
             f"program mixes reduce_precision targets {sorted(precisions)}")
    return findings


def audit_fn(fn, *args, allow: Sequence[str] = (), **kwargs) -> List[Finding]:
    """Trace ``fn(*args, **kwargs)`` and audit the resulting jaxpr.

    A jitted ``fn`` is traced through its own ``trace``: wrapped in
    ``make_jaxpr`` it would be a nested jit, and a jit that carries
    ``compiler_options`` (``fold.exact_jit``) may only be top-level."""
    if hasattr(fn, "trace"):
        return audit_jaxpr(fn.trace(*args, **kwargs).jaxpr, allow=allow)
    return audit_jaxpr(jax.make_jaxpr(fn)(*args, **kwargs), allow=allow)


# ----------------------------------------------------------------- lint CLI
def _lint_train_step(arch: str, reduced: bool, microbatches: int,
                     grad_compression: Optional[str],
                     allow: Sequence[str]) -> List[Finding]:
    from repro.configs import registry
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.train import optimizer as O
    from repro.train import step as S

    cfg = registry.get(arch)
    if reduced:
        cfg = cfg.reduced()
    tcfg = S.TrainConfig(opt=O.OptConfig(total_steps=10),
                         microbatches=microbatches,
                         grad_compression=grad_compression)
    state = S.init_state(cfg, tcfg, jax.random.PRNGKey(0))
    data = SyntheticLM(DataConfig(seed=0, batch=max(2, microbatches),
                                  seq=16, vocab=cfg.vocab))
    return audit_fn(S.make_train_step(cfg, tcfg), state, data.batch(0),
                    allow=allow)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="lint a lowered train step for nondeterminism-prone "
                    "primitives (exit 1 on findings)")
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--full", action="store_true",
                    help="audit the full-size config (default: reduced)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", default=None, choices=[None, "int8"])
    ap.add_argument("--allow", action="append", default=[],
                    help="finding code to suppress (repeatable)")
    args = ap.parse_args(argv)

    findings = _lint_train_step(args.arch, not args.full, args.microbatches,
                                args.grad_compression, args.allow)
    if findings:
        print(f"verify.trace: {len(findings)} finding(s) for {args.arch}:")
        for f in findings:
            print(f"  {f}")
        return 1
    print(f"verify.trace: {args.arch} train step is clean "
          "(no nondeterminism-prone primitives)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
