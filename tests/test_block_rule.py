"""The square tile ``dash_attention`` resolves when no ``block`` is given.

The rule reads only the call's shape, dtype and mask kind: the largest of
(512, 256, 128) that divides S and whose forward and backward VMEM
footprints fit (``repro.kernels.vmem.best_block``), and 128 under a
block-sparse mask. Each traced call records what it resolved; the calls
here are traced with ``jax.eval_shape``, so no kernel runs.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ops import dash_attention, last_tiling, log_tilings
from repro.kernels.vmem import best_block
from repro.masks.spec import SlidingWindow
from repro.obs import MemoryTracker


def _trace(s, d, dtype=jnp.bfloat16, b=2, h=32, **kw):
    x = jax.ShapeDtypeStruct((b, h, s, d), dtype)
    jax.eval_shape(lambda q, k, v: dash_attention(q, k, v, **kw), x, x, x)
    return last_tiling()


@pytest.mark.parametrize("s,d,dtype,causal,want", [
    (4096, 64, jnp.bfloat16, True, 512),
    (4096, 128, jnp.bfloat16, True, 512),
    (4096, 64, jnp.bfloat16, False, 512),
    (256, 64, jnp.float32, True, 256),
    (384, 64, jnp.float32, True, 128),
    (384, 64, jnp.bfloat16, False, 128),
], ids=["4096-d64", "4096-d128", "4096-d64-full", "256", "384",
        "384-full"])
def test_default_block_follows_the_shape(s, d, dtype, causal, want):
    rec = _trace(s, d, dtype, causal=causal)
    assert rec["block"] == want == best_block(s, d, jnp.dtype(dtype).itemsize,
                                              causal)
    assert (rec["n_kv"], rec["n_q"]) == (s // want, s // want)


@pytest.mark.parametrize("kw,want", [
    (dict(mask=SlidingWindow(256)), 128),
    (dict(causal=True, block=128), 128),
    (dict(causal=True, block=256), 256),
    (dict(causal=False, block=128), 128),
], ids=["block_sparse", "explicit-128", "explicit-256", "explicit-full-128"])
def test_block_sparse_and_explicit_blocks(kw, want):
    """A block-sparse spec keeps 128 (its PARTIAL-tile waste grows with the
    tile); an explicit ``block`` is kept as given."""
    assert _trace(1024, 64, **kw)["block"] == want


def test_tune_resolves_its_own_block():
    from repro.tune import tune_attention
    want = tune_attention(seq=1024, head_dim=64, dtype=jnp.bfloat16,
                          causal=True, n_heads=4, n_kv_heads=4).candidate
    rec = _trace(1024, 64, h=4, causal=True, tune=True)
    assert (rec["block"], rec["schedule"]) == (want.block_q, want.schedule)


@pytest.mark.parametrize("causal,grid,real", [
    (True, [64, 8, 8], 64 * 36),
    (False, [64, 8, 8], 64 * 64),
], ids=["causal", "full"])
def test_attn_tiling_record_carries_the_grid(causal, grid, real):
    """At S 4096, d 64 the backward's worker grid is (B·H, 8, 8): the 36
    lower-triangle tiles of 8 × 8 are real steps and the rest are no-op
    sentinels (none under the full mask); one event per traced call."""
    tracker = MemoryTracker()
    with log_tilings(tracker):
        rec = _trace(4096, 64, causal=causal)
    _trace(4096, 64, causal=causal)          # outside the block: not logged
    assert tracker.of("attn_tiling") == [dict(event="attn_tiling", **rec)]
    assert rec["realization"] == "worker_parallel"
    assert rec["grid"] == grid
    assert rec["real_steps"] == real
    assert rec["sentinel_steps"] == 64 * 64 - real
    assert (rec["seq"], rec["head_dim"], rec["dtype"]) == (4096, 64,
                                                           "bfloat16")


def test_serialized_record_has_no_sentinels():
    rec = _trace(1024, 64, causal=True, worker_parallel=False)
    assert rec["realization"] == "serialized"
    assert rec["grid"] == [64, 3] and rec["sentinel_steps"] == 0


def test_sequence_not_a_multiple_of_the_tile_is_refused():
    with pytest.raises(ValueError, match="not a multiple"):
        _trace(1024 + 64, 64, causal=True, block=128)
