"""VMEM budget tests + block-size sweep for the DASH kernels."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.schedules import make_schedule
from repro.kernels import ref
from repro.kernels.flash_bwd import flash_bwd
from repro.kernels.flash_fwd import flash_fwd
from repro.kernels.vmem import (VMEM_BYTES, best_block, bwd_footprint,
                                fwd_footprint)


@pytest.mark.parametrize("d", [64, 128, 160, 256])
def test_default_blocks_fit_vmem(d):
    assert fwd_footprint(128, 128, d).fits()
    assert bwd_footprint(128, 128, d).fits()


def test_footprint_discriminates_block_sizes():
    """The footprint math must actually discriminate: monotone in block size,
    and a 512² block at hd512 exceeds the 50% headroom (best_block backs off)."""
    fr = [bwd_footprint(b, b, 128).fraction for b in (128, 256, 512)]
    assert fr[0] < fr[1] < fr[2]
    assert bwd_footprint(512, 512, 512).fraction > 0.5
    assert best_block(4096, 512, causal=True) in (128, 256)
    assert best_block(4096, 64, causal=True) == 512


@pytest.mark.parametrize("block", [128, 256, 512])
@pytest.mark.parametrize("d", [64, 128, 256])
def test_footprint_counts_the_live_set_of_one_task(block, d):
    """The backward's four f32 (bq × bk) tiles s/p/dp/ds, the causal body's
    iotas and mask, and the dQ scratch at the 128-lane width the DMA moves
    (not ``d``); the forward's s/p tiles. The full mask has no iotas."""
    tile = block * block * 4
    lanes = max(d, 128)
    causal = bwd_footprint(block, block, d, causal=True).buffers
    assert causal["s/p/dp/ds tiles"] == 4 * tile
    assert causal["iotas/mask"] == 3 * tile
    assert causal["dq_scratch"] == block * lanes * 4
    assert "iotas/mask" not in bwd_footprint(block, block, d,
                                             causal=False).buffers
    fwd = fwd_footprint(block, block, d, causal=True).buffers
    assert fwd["s/p tiles"] == 2 * tile and fwd["iotas/mask"] == 3 * tile
    # the (bq × bk) tiles, not the blocks, dominate at 512
    if block == 512 and d <= 128:
        assert 7 * tile > 0.5 * sum(causal.values())


def test_512_tile_fits_the_rule_budget_at_head_dim_64():
    """At d 64 the 512 tile's backward takes about 65 % of the 16 MiB scoped
    VMEM, inside the rule's 80 % budget and outside the old 50 %."""
    fr = bwd_footprint(512, 512, 64).fraction
    assert 0.5 < fr <= 0.8
    assert fwd_footprint(512, 512, 64).fraction < fr
    assert bwd_footprint(512, 512, 64).total < VMEM_BYTES


@pytest.mark.parametrize("block", [128, 256])
def test_bwd_correct_across_block_sizes(block):
    """The schedule adapts to the tile count; numerics must hold for any block."""
    s, d = 512, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(kk, (1, s, d), jnp.float32) for kk in ks)
    out, lse = flash_fwd(q, k, v, causal=True, block_q=block, block_k=block,
                         interpret=True)
    sch = make_schedule("symmetric_shift", s // block, 1, True)
    dq, dk, dv = flash_bwd(q, k, v, out, lse, do, sch, causal=True,
                           block_q=block, block_k=block, interpret=True)
    rdq, rdk, rdv = ref.mha_bwd(q, k, v, out, lse, do, causal=True)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk), atol=2e-5,
                               rtol=2e-5)
