"""Device time of the train step by the program's own layer names.

The program names each layer inside its compiled step
(``repro.obs.layers``: ``jax.named_scope("layer.<name>")``), and
``repro.obs.layers.op_layers`` maps each instruction of a compiled
executable back to its layer. The readers ``metrics/step_ms.<layer>.py``
sum, on chip 0, the device time of the step module's ops in each layer,
clipped to the traced window, over the steps of the window.

The map comes from ``counts["op_layers"]`` and ``counts["step_module"]``
where a traffic kind puts them there. Otherwise it is made once per traced
run, by the first reader, after the window has closed: the step is lowered
again at the cell's shapes, built as the window's executable was, and
compiled, which the run's compilation cache answers. A program without
layer scopes (no ``repro.obs.layers``) gives no map, and every ``step_ms``
metric is left out of the line.
"""
from __future__ import annotations

import bisect
import importlib
import sys
import time
from typing import Dict, Optional, Tuple

from bench import trace as TR

UNSCOPED = None          # the layer key of ops in no layer scope


def in_module(ops, modules, module: str):
    """The ops that lie inside an execution of ``module`` (the name before
    ``(`` on the ``XLA Modules`` line): other modules reuse instruction
    names such as ``fusion.3``."""
    runs = sorted((s, e) for n, s, e in modules if n.split("(")[0] == module)
    starts = [s for s, _ in runs]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[2] <= runs[i][1]:
            out.append(op)
    return out


def time_by_layer(trace, op_layers: Dict[str, object], module: str,
                  device: int = 0) -> Dict[object, float]:
    """Nanoseconds of device time in the traced window, by the value
    ``op_layers`` gives each op of ``module``'s executions (an op it lacks
    counts under :data:`UNSCOPED`). ``while``/``call``/``conditional``
    containers are left out: their events span the ops of their bodies."""
    ops = in_module(trace.clipped_ops(device), trace.modules.get(device, []),
                    module)
    out: Dict[object, float] = {}
    for name, s, e in ops:
        if TR.is_container(name):
            continue
        key = op_layers.get(TR.short_name(name), UNSCOPED)
        out[key] = out.get(key, 0.0) + (e - s)
    return out


def module_busy_ns(trace, module: str, device: int = 0) -> float:
    """Busy time (union of op intervals) of ``module``'s executions in the
    traced window, containers left out."""
    ops = in_module(trace.clipped_ops(device), trace.modules.get(device, []),
                    module)
    return TR.busy_ns([op for op in ops if not TR.is_container(op[0])])


def compiled_op_scopes(cfg: dict, traffic: dict
                       ) -> Optional[Tuple[dict, str]]:
    """({instruction: (layer, pass)}, module name) of the cell's train step,
    compiled as the window's step was built; None where the program has no
    layer scopes."""
    try:
        from repro.obs.layers import op_scopes
    except ImportError:
        return None
    import jax
    import jax.numpy as jnp

    ad = importlib.import_module(f"bench.adapters.{cfg['model']}")
    ref = importlib.import_module(f"bench.models.{cfg['model']}")
    mc, tcfg = ad.program_config(cfg), ad.train_config(traffic)
    words = jnp.asarray(ref.seed_words(0))
    params = jax.eval_shape(
        lambda w: ad.to_program(mc, ref.make_stacked(cfg, w)), words)
    state = jax.eval_shape(lambda p: ad.init_state(p, tcfg), params)
    ids = jax.ShapeDtypeStruct((traffic["batch"], traffic["seq"]), jnp.int32)
    text = ad.build_train_step(mc, tcfg).lower(
        state, {"tokens": ids, "labels": ids}).compile().as_text()
    module = text.split(None, 2)[1].rstrip(",")     # "HloModule jit_step, …"
    return op_scopes(text), module


def layer_ms(ctx) -> Optional[Dict[object, float]]:
    """{layer or UNSCOPED: milliseconds a step}, made once per run and kept
    in ``ctx.counts``; None without a map, steps or a step module."""
    counts = ctx.counts
    if "step_layer_ms" in counts:
        return counts["step_layer_ms"]
    counts["step_layer_ms"] = None
    steps = counts.get("steps")
    if not steps or not any(ctx.trace.ops.values()):
        return None
    if "op_layers" in counts:
        scopes = {k: (v, None) for k, v in counts["op_layers"].items()}
        module = counts["step_module"]
    else:
        t0 = time.perf_counter()
        made = compiled_op_scopes(ctx.cfg, ctx.traffic)
        if made is None:
            return None
        scopes, module = made
        print(f"step_layers: {len(scopes)} ops of {module} mapped in "
              f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
    by_scope = time_by_layer(ctx.trace, scopes, module)
    if not by_scope:
        return None
    out: Dict[object, float] = {}
    for key, ns in by_scope.items():
        layer = key[0] if key is not UNSCOPED else UNSCOPED
        out[layer] = out.get(layer, 0.0) + ns * 1e-6 / steps
    split = {f"{k[0]}.{k[1]}" if k is not UNSCOPED else "unmapped":
             round(ns * 1e-6 / steps, 3) for k, ns in sorted(
                 by_scope.items(), key=lambda kv: -kv[1])}
    print(f"step_layers: ms a step by layer and pass {split}; module busy "
          f"{module_busy_ns(ctx.trace, module) * 1e-6 / steps:.3f} ms a step",
          file=sys.stderr)
    counts["step_layer_ms"] = out
    return out


def read(ctx, layer) -> Optional[float]:
    """Milliseconds a step in ``layer`` (``UNSCOPED``: in none)."""
    ms = layer_ms(ctx)
    if ms is None:
        return None
    return ms.get(layer, 0.0)
