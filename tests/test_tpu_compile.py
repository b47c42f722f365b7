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

BH, S, D, BLOCK = 32, 4096, 64, 128


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


def _schedule(causal):
    return cached_schedule("symmetric_shift" if causal else "shift",
                           S // BLOCK, n_heads=1, causal=causal,
                           block_q=BLOCK, block_k=BLOCK)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_fwd_compiles(compile_for, causal):
    x = ((BH, S, D), jnp.bfloat16)
    compile_for(lambda q, k, v: flash_fwd(q, k, v, causal=causal), x, x, x)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("worker_parallel", [True, False],
                         ids=["worker_parallel", "serialized"])
def test_flash_bwd_compiles(compile_for, worker_parallel, causal):
    sch = _schedule(causal)
    x = ((BH, S, D), jnp.bfloat16)

    def bwd(q, k, v, out, lse, do):
        return flash_bwd(q, k, v, out, lse, do, sch, causal=causal,
                         worker_parallel=worker_parallel)

    compile_for(bwd, x, x, x, x, ((BH, S), jnp.float32), x)


@pytest.mark.parametrize("width", [dq_lanes(D), D], ids=["dq", "gqa_dkdv"])
def test_fold_combine_compiles(compile_for, width):
    """At both widths the backward hands it: the worker-parallel dQ partials
    padded to ``dq_lanes(D)``, and the GQA group's dK/dV at ``D``."""
    n_workers = _schedule(True).worker_chains()["kv_ids"].shape[0]
    visited = np.ones((n_workers, S // BLOCK), np.int32)
    compile_for(lambda p: fold_combine(p, visited, BLOCK),
                ((BH, n_workers, S, width), jnp.float32))


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_dash_attention_grad_compiles(compile_for, causal, d):
    def loss(q, k, v):
        return dash_attention(q, k, v, causal=causal).astype(
            jnp.float32).sum()

    x = ((1, BH, S, d), jnp.bfloat16)
    compile_for(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
