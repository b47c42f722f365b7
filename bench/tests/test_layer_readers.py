"""The ``step_ms.*`` readers: device time of the step module's ops by the
program's layer scopes, on the small recorded trace with a hand-written map
of its ops, on made-up traces, and with the map the compiled step gives."""
import json
import os
import sys

import pytest

from bench import harness, step_layers
from bench import trace as TR

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
MS = 1e6
LAYERS = ("embed", "attn_proj", "attention", "mlp", "head_loss",
          "optimizer", "unscoped")


def _load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def ctx_of(trace, counts, cfg=None, traffic=None):
    cfg = cfg or _load(BENCH, "configs", "stablelm-1.6b-4l.json")
    traffic = traffic or _load(BENCH, "traffic", "train-4k.json")
    return harness.ReadContext(trace=trace, cfg=cfg, traffic=traffic,
                               counts=dict(counts), peaks={},
                               workload="train-stablelm-4k")


def read_all(c):
    spec = harness.Spec(workload={"name": "train-stablelm-4k", "chips": 1},
                        cfg=c.cfg, traffic=c.traffic, limits={}, bench={})
    return {n: harness.reader(spec, f"step_ms.{n}").read(c) for n in LAYERS}


# the small trace: three calls of jit_f (a matmul and tanh fusion, a Pallas
# kernel ``f.1``, and a prefetch copy), two of them inside the window
SMALL_MAP = {"convolution_tanh_fusion": "mlp", "f.1": "attention"}


def test_recorded_trace_sums_to_the_module_busy_time():
    tr = TR.read(SMALL)
    by = step_layers.time_by_layer(tr, SMALL_MAP, "jit_f")
    assert set(by) == {"mlp", "attention", None}      # copies are unmapped
    busy = step_layers.module_busy_ns(tr, "jit_f")
    assert 0 < busy and sum(by.values()) == pytest.approx(busy, rel=1e-9)
    runs = [m for m in tr.modules[0] if m[1] < tr.window[1]
            and m[2] > tr.window[0]]
    c = ctx_of(tr, {"steps": len(runs), "op_layers": SMALL_MAP,
                    "step_module": "jit_f"})
    got = read_all(c)
    assert got["embed"] == got["optimizer"] == 0.0
    assert got["mlp"] > 0 and got["attention"] > 0 and got["unscoped"] > 0
    assert sum(got.values()) == pytest.approx(busy / MS / len(runs),
                                              rel=1e-9)


def test_only_the_step_module_counts_and_containers_are_left_out():
    ops = [("%while.2 = (f32[]) while()", 0, 90 * MS),
           ("fusion.3", 0, 30 * MS), ("fusion.4", 30 * MS, 60 * MS),
           ("fusion.9", 60 * MS, 90 * MS),
           # the feed's module reuses the name fusion.3
           ("fusion.3", 100 * MS, 150 * MS)]
    modules = [("jit_step(11)", 0, 95 * MS), ("jit_make(7)", 95 * MS,
                                              160 * MS)]
    tr = TR.Trace(ops={0: ops}, modules={0: modules},
                  host=[("bench.window", 0, 1000 * MS)], window=(0, 1000 * MS))
    by = step_layers.time_by_layer(
        tr, {"fusion.3": "mlp", "fusion.4": "head_loss"}, "jit_step")
    assert by == {"mlp": 30 * MS, "head_loss": 30 * MS, None: 30 * MS}
    got = read_all(ctx_of(tr, {"steps": 2, "step_module": "jit_step",
                               "op_layers": {"fusion.3": "mlp",
                                             "fusion.4": "head_loss"}}))
    assert got == {"embed": 0.0, "attn_proj": 0.0, "attention": 0.0,
                   "mlp": 15.0, "head_loss": 15.0, "optimizer": 0.0,
                   "unscoped": 15.0}


def test_readers_are_silent_without_ops_steps_or_layer_scopes(monkeypatch):
    tr = TR.read(SMALL)
    empty = TR.Trace(ops={}, modules={}, host=[], window=(0, 1))
    assert set(read_all(ctx_of(empty, {"steps": 3})).values()) == {None}
    assert set(read_all(ctx_of(tr, {"steps": 0})).values()) == {None}
    # a program without repro.obs.layers (an earlier commit) gives no map
    monkeypatch.setitem(sys.modules, "repro.obs.layers", None)
    assert step_layers.compiled_op_scopes({}, {}) is None
    assert set(read_all(ctx_of(tr, {"steps": 2})).values()) == {None}


def test_the_compiled_step_maps_its_ops_to_every_layer():
    """At a tiny size on the CPU, the map the readers make on the chip."""
    cfg = dict(_load(DATA, "tiny-stablelm.json"), name="tiny")
    scopes, module = step_layers.compiled_op_scopes(
        cfg, _load(DATA, "tiny-train.json"))
    assert module == "jit_step"
    layers = {layer for layer, _ in scopes.values()}
    assert {"embed", "attn_proj", "attention", "mlp", "head_loss",
            "optimizer"} <= layers
