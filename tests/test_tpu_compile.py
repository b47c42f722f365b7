"""The DASH kernels compile for a TPU v5e chip (no chip needed).

Interpret mode never runs Mosaic's lowering, so a kernel can pass every
interpret-mode test and still be refused by the chip's compiler (block
layouts, DMA tiling). Each case here compiles ahead of time for one chip of a
described ``v5e:2x2`` topology, at stablelm-1.6b attention widths (BH 32,
S 4096, D 64, bf16), and asserts the Mosaic kernel (``tpu_custom_call``) is
in the compiled program.

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
def compile_for(one_chip):
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
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    yield run
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


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
