"""Layer scopes inside the compiled step (``repro.obs.layers``), the names
of the serving steps' modules, and program spans on the profiler's clock
(``repro.obs.span``)."""
import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.obs import MemoryTracker, NoopTracker, Profiler
from repro.obs import layers as LY
from repro.obs import span as SP
from repro.train import optimizer as O
from repro.train import step as S


# ------------------------------------------------------------ op_name parsing
@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp(layer.mlp)/dot_general", ("mlp", "forward")),
    ("jit(step)/transpose(jvp(layer.embed))/while/body/dot_general",
     ("embed", "backward")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/layer.attn_proj/layer.attention/jit(flash_fwd)/"
     "pallas_call", ("attention", "recompute")),
    ("jit(step)/layer.optimizer/mul", ("optimizer", "forward")),
    ("jit(step)/jvp(layer.moe)/layer.moe_experts/ragged_dot_general",
     ("moe_experts", "forward")),
    ("jit(step)/transpose(jvp(layer.moe))/argsort", ("moe", "backward")),
    ("jit(step)/transpose(jvp())/add_any", (None, "backward")),
    ("jit(step)/jvp(layer.nonesuch)/add", (None, "forward")),
    ("", (None, "forward")),
])
def test_op_name_scope(op_name, want):
    assert LY.op_name_scope(op_name) == want


HLO = """HloModule jit_step, is_scheduled=true

%add.clone (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%x, %y), metadata={op_name="jit(step)/jvp(layer.mlp)/add"}
}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/transpose(jvp(layer.head_loss))/mul"}
  %mul.2 = f32[8]{0} multiply(%mul.1, %param_0), metadata={op_name="jit(step)/transpose(jvp(layer.head_loss))/mul"}
  ROOT %neg.1 = f32[8]{0} negate(%mul.2), metadata={op_name="jit(step)/transpose(jvp())/neg"}
}

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%p), index=1
  %fusion.1 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1
  %reduce.1 = f32[] reduce(%fusion.1, %c), dimensions={0}, to_apply=%add.clone, metadata={op_name="jit(step)/jvp(layer.mlp)/reduce_sum"}
  ROOT %t = (s32[], f32[8]{0}) tuple(%i, %fusion.1)
}

%cond.1 (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %w = (s32[], f32[8]{0}) while(%init), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/jvp(layer.mlp)/while"}
  %cc.1 = (f32[8]{0}, f32[8]{0}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(layer.attention)/jit(flash_fwd)/pallas_call"}
  ROOT %out = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(step)/layer.optimizer/mul"}
}
"""


def test_op_scopes_on_handwritten_hlo():
    got = LY.op_scopes(HLO)
    # fused and reduction-body instructions are not ops of their own; the
    # while container is left out
    assert "mul.1" not in got and "add.9" not in got and "w" not in got
    # a fusion with no metadata takes its instructions' majority layer
    assert got["fusion.1"] == ("head_loss", "backward")
    assert got["reduce.1"] == ("mlp", "forward")
    assert got["cc.1"] == ("attention", "forward")
    assert got["out"] == ("optimizer", "forward")
    assert got["gte.1"] == (None, "forward")
    assert LY.op_layers(HLO)["fusion.1"] == "head_loss"


KERNELS = """HloModule jit_step, is_scheduled=true

ENTRY %main.2 (a: f32[8], w: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %w = f32[8]{0} parameter(1)
  %ragged-dot-none.3 = f32[8]{0} custom-call(%a, %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %act.1 = f32[8]{0} multiply(%ragged-dot-none.3, %a), metadata={op_name="jit(step)/transpose(jvp(layer.moe))/layer.moe_experts/mul"}
  %ragged-dot-none.4 = f32[8]{0} custom-call(%act.1, %a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %flash.1 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(layer.attention)/pallas_call"}
  ROOT %upd.1 = f32[8]{0} subtract(%w, %ragged-dot-none.4), metadata={op_name="jit(step)/layer.optimizer/sub"}
}
"""


def test_compiler_made_kernels_take_the_layer_of_their_primitive():
    """The TPU compiler's ragged-dot kernels name no layer: they map to the
    grouped products' layer, in the pass of the ops that read them (a
    weight gradient, read by the optimizer alone, is backward); a kernel
    the program names keeps its own scope."""
    got = LY.op_scopes(KERNELS)
    assert got["ragged-dot-none.3"] == ("moe_experts", "backward")
    assert got["ragged-dot-none.4"] == ("moe_experts", "backward")
    assert got["flash.1"] == ("attention", "forward")
    assert got["upd.1"] == ("optimizer", "forward")


def test_scope_refuses_unknown_layer():
    with pytest.raises(ValueError, match="unknown layer"):
        LY.scope("nonesuch")


# -------------------------------------------- the compiled train step, CPU
def _train_step_hlo():
    """Optimized HLO of the trainer's step on a tiny StableLM, on the CPU."""
    cfg = registry.get("stablelm-1.6b").reduced()
    tcfg = S.TrainConfig(opt=O.OptConfig(name="adamw"), remat=True)
    state = jax.eval_shape(
        lambda: S.init_state(cfg, tcfg, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
    step = S.jit(cfg, S.make_train_step(cfg, tcfg))
    return step.lower(state, batch).compile().as_text()


@pytest.fixture(scope="module")
def train_step_hlo():
    return _train_step_hlo()


def test_op_layers_name_every_train_layer(train_step_hlo):
    scopes = LY.op_scopes(train_step_hlo)
    layers = {layer for layer, _ in scopes.values()}
    assert set(LY.TRAIN_LAYERS) <= layers
    passes = {}
    for layer, pass_ in scopes.values():
        passes.setdefault(layer, set()).add(pass_)
    for layer in ("embed", "mlp", "head_loss"):
        assert {"forward", "backward"} <= passes[layer], (layer, passes)
    # remat recomputes the blocks' forward inside the backward
    assert "recompute" in passes["mlp"]


def test_stablelm_step_reads_no_new_layer(train_step_hlo):
    """The MoE scopes leave a dense model's readings as they were: its ops
    map to the six train layers and to no scope, nothing else."""
    layers = {layer for layer, _ in LY.op_scopes(train_step_hlo).values()}
    assert layers == set(LY.TRAIN_LAYERS) | {None}


def test_held_moe_ops_map_to_moe_and_moe_experts():
    """A held-expert MoE step (Mellum2 reduced): the router, dispatch and
    combine map to ``moe``, the grouped products to ``moe_experts``, in
    every pass; no op maps to ``mlp``."""
    cfg = registry.get("mellum2-12b-a2.5b").reduced()
    tcfg = S.TrainConfig(opt=O.OptConfig(name="adamw"), remat=True)
    state = jax.eval_shape(
        lambda: S.init_state(cfg, tcfg, jax.random.PRNGKey(0)))
    ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    hlo = S.jit(cfg, S.make_train_step(cfg, tcfg)).lower(
        state, {"tokens": ids, "labels": ids}).compile().as_text()
    passes = {}
    for layer, pass_ in LY.op_scopes(hlo).values():
        passes.setdefault(layer, set()).add(pass_)
    assert "mlp" not in passes
    for layer in ("moe", "moe_experts"):
        assert {"forward", "recompute", "backward"} <= passes[layer], (
            layer, passes)


def strip_metadata(hlo_text):
    """An HLO module's instructions without their metadata or the tables
    of source locations above them."""
    lines = hlo_text.splitlines()
    first = next(i for i, line in enumerate(lines)
                 if line.startswith(("%", "ENTRY")))
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines[first:]))


def test_scopes_are_metadata_only(train_step_hlo, monkeypatch):
    """The same step traced with every scope a no-op compiles to the same
    instructions once metadata is stripped."""
    monkeypatch.setattr(LY.jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _train_step_hlo()
    assert "layer." not in bare and "layer." in train_step_hlo
    assert strip_metadata(bare) == strip_metadata(train_step_hlo)


# ----------------------------------------------- serving modules have names
def test_serve_steps_compile_as_named_modules():
    from repro.models import transformer as T
    from repro.serve.engine import (ContinuousEngine, Engine, SampleConfig,
                                    _sampler_fn)

    cfg = registry.get("stablelm-1.6b").reduced()
    params = T.init(cfg, jax.random.PRNGKey(0))
    eng = ContinuousEngine(cfg, params, n_slots=2, max_seq=32, page_size=8)
    i32 = jnp.zeros((2, 1), jnp.int32)
    lowered = eng._step.lower(params, eng.cache.pools, i32, i32,
                              eng.cache.device_page_table(),
                              jnp.zeros((2,), jnp.int32),
                              jnp.zeros((2,), jnp.int32))
    assert lowered.as_text().startswith("module @jit_paged_step")
    sampler = _sampler_fn(SampleConfig()).lower(
        jnp.zeros((2, cfg.padded_vocab)), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), jnp.int32))
    assert sampler.as_text().startswith("module @jit_sample_rows")
    static = Engine(cfg, params, max_seq=32)
    batch = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    assert static._prefill.lower(params, batch).as_text().startswith(
        "module @jit_prefill")
    _, caches, cross = jax.eval_shape(static._prefill, params, batch)
    tok = jnp.zeros((1, 1), jnp.int32)
    assert static._decode.lower(params, caches, tok, jnp.asarray(8),
                                cross).as_text().startswith(
        "module @jit_decode")


# -------------------------------------------- spans on the profiler's clock
def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            out += [(e.name, e.start_ns, e.end_ns)
                    for line in plane.lines for e in line.events
                    if e.name.startswith(SP.ANNOTATION_PREFIX)]
    return out


@pytest.mark.parametrize("armed", [True, False], ids=["armed", "disarmed"])
def test_spans_reach_the_profiler_trace(tmp_path, armed):
    prof = Profiler(MemoryTracker() if armed else NoopTracker())
    with jax.profiler.trace(str(tmp_path)):
        step = prof.begin("train_step", "step:1")
        # a span that overlaps the first without nesting in it
        queue = prof.begin("queue", "req:0")
        jnp.ones(4).block_until_ready()
        prof.end(step)
        jnp.ones(4).block_until_ready()
        prof.end(queue)
    events = {name: (s, e) for name, s, e in _host_events(str(tmp_path))}
    assert set(events) == {"repro.train_step", "repro.queue"}
    (s0, e0), (s1, e1) = events["repro.train_step"], events["repro.queue"]
    assert s0 <= s1 < e0 < e1
    if armed:
        assert [r["phase"] for r in prof.tracker.of("span")] == [
            "train_step", "queue"]


def test_disarmed_tracer_makes_no_annotation(monkeypatch):
    made = []

    class Counting:
        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

    def bomb():
        raise AssertionError("disarmed tracer read the clock")

    monkeypatch.setattr(SP, "TraceAnnotation", Counting)
    assert not SP.profiling()
    prof = Profiler(NoopTracker(), clock=bomb)
    assert prof.begin("train_step", "step:1") is None
    with prof.span("decode", "step:1") as s:
        assert s is None
    assert made == []
    armed = Profiler(MemoryTracker(), clock=lambda: 1.0)
    armed.end(armed.begin("train_step", "step:1"))
    assert made == ["repro.train_step"]
    np.testing.assert_equal(len(armed.tracker.of("span")), 1)
