"""The DASH kernels compile for a TPU v5e chip (no chip needed).

Interpret mode never runs Mosaic's lowering, so a kernel can pass every
interpret-mode test and still be refused by the chip's compiler (block
layouts, DMA tiling). Each case here compiles ahead of time for one chip of a
described ``v5e:2x2`` topology, at stablelm-1.6b attention widths (BH 32,
S 4096, D 64, bf16), and asserts the Mosaic kernel (``tpu_custom_call``) is
in the compiled program. The embedding backward's case asserts instead that
no scatter and no vocabulary-long convolution is left in it.

The topology is described inside a fixture, never while a module is imported:
only one process may load the TPU library, and every test worker imports
every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.schedules import cached_schedule
from repro.kernels.flash_bwd import dq_lanes, flash_bwd, fold_combine
from repro.kernels.flash_fwd import flash_fwd
from repro.kernels.ops import dash_attention

BH, S, D = 32, 4096, 64
# the fixed tile of old and the one the default rule picks at S 4096, D 64
BLOCKS = (128, 512)


def _cases(ids):
    """``(id, value)`` pairs crossed with every block: the 128 case keeps the
    bare id, the others append the block to it."""
    return [pytest.param(value, block,
                         id=name if block == 128 else f"{name}-{block}")
            for block in BLOCKS for name, value in ids]


CAUSAL = _cases([("causal", True), ("full", False)])


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def aot(one_chip):
    """Compile ``fn`` at the given shapes for one described chip, with the
    persistent compilation cache off: an entry written for a chip that is
    not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in shapes]
        return jax.jit(fn).lower(*args).compile()

    yield run
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compile_for(aot):
    """``aot``, asserting a Mosaic kernel is in the compiled program."""
    def run(fn, *shapes):
        compiled = aot(fn, *shapes)
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    return run


def _schedule(causal, block):
    return cached_schedule("symmetric_shift" if causal else "shift",
                           S // block, n_heads=1, causal=causal,
                           block_q=block, block_k=block)


@pytest.mark.parametrize("causal,block", CAUSAL)
def test_flash_fwd_compiles(compile_for, causal, block):
    x = ((BH, S, D), jnp.bfloat16)
    compile_for(lambda q, k, v: flash_fwd(q, k, v, causal=causal,
                                          block_q=block, block_k=block),
                x, x, x)


@pytest.mark.parametrize("causal,block", CAUSAL)
@pytest.mark.parametrize("worker_parallel", [True, False],
                         ids=["worker_parallel", "serialized"])
def test_flash_bwd_compiles(compile_for, worker_parallel, causal, block):
    sch = _schedule(causal, block)
    x = ((BH, S, D), jnp.bfloat16)

    def bwd(q, k, v, out, lse, do):
        return flash_bwd(q, k, v, out, lse, do, sch, causal=causal,
                         block_q=block, block_k=block,
                         worker_parallel=worker_parallel)

    compile_for(bwd, x, x, x, x, ((BH, S), jnp.float32), x)


@pytest.mark.parametrize("width,block",
                         _cases([("dq", dq_lanes(D)), ("gqa_dkdv", D)]))
def test_fold_combine_compiles(compile_for, width, block):
    """At both widths the backward hands it: the worker-parallel dQ partials
    padded to ``dq_lanes(D)``, and the GQA group's dK/dV at ``D``."""
    n_workers = _schedule(True, block).worker_chains()["kv_ids"].shape[0]
    visited = np.ones((n_workers, S // block), np.int32)
    compile_for(lambda p: fold_combine(p, visited, block),
                ((BH, n_workers, S, width), jnp.float32))


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_dash_attention_grad_compiles(compile_for, causal, d):
    def loss(q, k, v):
        return dash_attention(q, k, v, causal=causal).astype(
            jnp.float32).sum()

    x = ((1, BH, S, d), jnp.bfloat16)
    compile_for(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)


def test_attention_scope_names_every_kernel(compile_for):
    """Through the model's call site (``layers._sdpa_full``, which opens the
    ``attention`` layer scope): every Mosaic kernel of the compiled
    ``grad(dash_attention)`` maps to that layer, forward and backward, and
    keeps the instruction name the trace readers match it by."""
    import re

    from repro.configs import registry
    from repro.models import layers as L
    from repro.obs.layers import op_layers

    cfg = registry.get("stablelm-1.6b").replace(attention_impl="pallas")

    def loss(q, k, v):
        return L._sdpa_full(q, k, v, cfg, True).astype(jnp.float32).sum()

    x = ((1, S, BH, D), jnp.bfloat16)
    hlo = compile_for(jax.grad(loss, argnums=(0, 1, 2)), x, x, x).as_text()
    kernels = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = .*"
                         r'custom_call_target="tpu_custom_call"', hlo, re.M)
    layers = op_layers(hlo)
    assert kernels and {layers[k] for k in kernels} == {"attention"}
    for name in ("flash_fwd", "_flash_bwd_worker_call", "_fold_combine_call"):
        assert any(re.match(rf"{name}\b", k) for k in kernels), (name, kernels)


def test_embed_grad_compiles_without_scatter_or_vocab_matmul(aot):
    """``grad`` through ``apply_embed`` at train-stablelm-4k's shape (2 x
    4096 tokens, 100,352 x 2048 table, bf16 cotangent) compiles for one v5e
    with no scatter and no convolution over a 100,352-long axis: each table
    row is gathered once, never summed by a one-hot product."""
    import re

    from repro.configs import registry
    from repro.models import layers as L

    cfg = registry.get("stablelm-1.6b")
    v, d = cfg.padded_vocab, cfg.d_model

    def loss(table, tokens, dy):
        emb = L.apply_embed({"tok": table}, tokens, cfg)
        return jnp.sum(emb.astype(jnp.float32) * dy.astype(jnp.float32))

    hlo = aot(jax.grad(loss), ((v, d), jnp.float32), ((2, 4096), jnp.int32),
              ((2, 4096, d), jnp.bfloat16)).as_text()
    assert v == 100_352
    assert not re.search(r"\bscatter\(", hlo)
    convs = re.findall(r"^.*\bconvolution\(.*$", hlo, re.M)
    assert not [c for c in convs if str(v) in c.split("convolution(")[0]], convs


# Mellum2-12B-A2.5B's attention: GQA 32/4 at head dim 128, S 8192, two rows
MB, MH, MHK, MS, MD = 2, 32, 4, 8192, 128


@pytest.mark.parametrize("window", [1024, 0], ids=["window", "causal"])
def test_mellum_attention_compiles(compile_for, window):
    """Forward and backward of the sliding-window layer (512 tiles, the
    band's rule; block-sparse Shift, worker-parallel over 16 workers) and
    the full causal layer (512 tiles, worker-parallel) at the cell's shape.
    Each keeps at most the full layer's 4 GiB of dQ partials."""
    from repro.kernels.ops import last_tiling
    from repro.masks.spec import SlidingWindow

    kw = dict(mask=SlidingWindow(window)) if window else dict(causal=True)

    def fwd(q, k, v):
        return dash_attention(q, k, v, **kw)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    q = ((MB, MH, MS, MD), jnp.bfloat16)
    kv = ((MB, MHK, MS, MD), jnp.bfloat16)
    compile_for(fwd, q, kv, kv)
    compile_for(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    rec = last_tiling()
    assert (rec["block"], rec["realization"], rec["window"]) == (
        512, "worker_parallel", window or None)
    assert rec["dq_partial_bytes"] <= 4 * 2**30


def test_mellum_train_step_compiles_and_fits(one_chip):
    """The whole train step of ``train-mellum2-8k`` (launch.train.build on
    the benchmark's configuration: 4 layers, 8 held experts of 64, the
    12,288-id vocabulary slice, 2 × 8192 tokens) compiles for one v5e at
    the default scoped VMEM, and its arguments and temporaries fit the
    chip's 16 GB."""
    import json

    from jax.experimental.compilation_cache import compilation_cache as cc

    from bench.adapters import window_moe_decoder as A
    from bench.models import window_moe_decoder as W

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench", "configs",
                           "mellum2-12b-a2.5b-4l-ep8.json")) as f:
        cfg = dict(json.load(f), name="mellum2-12b-a2.5b-4l-ep8")
    with open(os.path.join(root, "bench", "traffic", "train-8k.json")) as f:
        tr = json.load(f)
    mc, tcfg = A.program_config(cfg), A.train_config(tr)
    params = jax.eval_shape(lambda w: A.to_program(
        mc, W.make_stacked(cfg, w)), jnp.zeros(2, jnp.uint32))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda p: A.init_state(p, tcfg), params))
    ids = jax.ShapeDtypeStruct((tr["batch"], tr["seq"]), jnp.int32,
                               sharding=one_chip)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        exe = A.build_train_step(mc, tcfg).lower(
            state, {"tokens": ids, "labels": ids}).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    ma = exe.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert sum(x.size for x in jax.tree.leaves(params)) == 340_349_184
    assert peak < 16e9, peak
