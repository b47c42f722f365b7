"""The square tile ``dash_attention`` resolves when no ``block`` is given.

The rule reads only the call's shape, dtype and mask: the largest of
(512, 256, 128) that divides S and whose forward and backward VMEM
footprints fit (``repro.kernels.vmem.best_block``); under a block-sparse
mask, also the largest whose computed pairs stay within
``MASK_PAIRS_MAX_RATIO`` of the 128 tiling's. Each traced call records what
it resolved; the calls here are traced with ``jax.eval_shape``, so no
kernel runs.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ops import dash_attention, last_tiling, log_tilings
from repro.kernels.vmem import (BLOCKS, MASK_PAIRS_MAX_RATIO, best_block,
                                 bwd_footprint, fwd_footprint)
from repro.masks.spec import Document, PrefixLM, SlidingWindow, streaming_mask
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
    (dict(mask=SlidingWindow(256)), 256),
    (dict(causal=True, block=128), 128),
    (dict(causal=True, block=256), 256),
    (dict(causal=False, block=128), 128),
    (dict(mask=SlidingWindow(256), block=128), 128),
], ids=["block_sparse", "explicit-128", "explicit-256", "explicit-full-128",
        "explicit-mask-128"])
def test_block_sparse_and_explicit_blocks(kw, want):
    """A block-sparse spec takes the tile its band allows: at S 1024 a
    window of 256 computes 1.33× the 128 tiling's pairs at 256 tiles and
    2.29× at 512. An explicit ``block`` is kept as given, under a mask too."""
    assert _trace(1024, 64, **kw)["block"] == want


BAND_CASES = [
    (8192, 128, SlidingWindow(1024), 512),
    (1024, 64, SlidingWindow(256), 256),
    (4096, 64, SlidingWindow(256), 256),
    (1024, 64, Document.from_lengths((300, 724)), 512),
    (4096, 64, streaming_mask(256, 16), 256),
    (1024, 64, PrefixLM(300), 512),
]
BAND_IDS = ["window1024-8192", "window256-1024", "window256-4096",
            "document-1024", "streaming-4096", "prefix-1024"]


@pytest.mark.parametrize("s,d,mask,want", BAND_CASES, ids=BAND_IDS)
def test_block_sparse_tile_follows_the_band(s, d, mask, want):
    """The tile comes from the mask's own block map: Mellum2's window of
    1024 at S 8192 takes 512 tiles (1.33× the pairs of the 128 tiling);
    narrower bands keep a smaller tile. The record counts the pairs the
    schedule's tiles compute and the pairs the mask admits."""
    rec = _trace(s, d, mask=mask)
    assert rec["block"] == want == best_block(s, d, 2, False, mask=mask)
    assert rec["computed_pairs"] == mask.pair_counts(s, want)[0]
    assert rec["attended_pairs"] == int(mask.materialize(s).sum())
    assert rec["attended_pairs"] <= rec["computed_pairs"]


@pytest.mark.parametrize("s,d,mask,want", BAND_CASES, ids=BAND_IDS)
def test_block_sparse_tile_is_the_largest_within_the_bound(s, d, mask, want):
    """The chosen tile computes at most ``MASK_PAIRS_MAX_RATIO`` times the
    pairs of the 128 tiling, and every larger tile of ``BLOCKS`` that
    divides S would compute more than that."""
    base = mask.pair_counts(s, 128)[0]
    assert mask.pair_counts(s, want)[0] <= MASK_PAIRS_MAX_RATIO * base
    for b in BLOCKS:
        if b > want and s % b == 0:
            assert mask.pair_counts(s, b)[0] > MASK_PAIRS_MAX_RATIO * base


@pytest.mark.parametrize("causal,attended", [(True, 1024 * 1025 // 2),
                                             (False, 1024 * 1024)],
                         ids=["causal", "full"])
def test_paper_masks_count_their_pairs(causal, attended):
    """Without a spec the record counts the causal or full pairs, and the
    512 tiles the causal grid visits (the diagonal ones whole)."""
    rec = _trace(1024, 64, causal=causal)
    assert rec["attended_pairs"] == attended
    assert rec["block"] == 512
    assert rec["computed_pairs"] == (3 if causal else 4) * 512 * 512


def _fits(b, s, d, dtype_bytes, causal, mask):
    info = mask is not None and mask.token_info(s) is not None
    return all(fp(b, b, d, dtype_bytes, causal or mask is not None,
                  info).fits(0.8) for fp in (fwd_footprint, bwd_footprint))


def _within_pairs(b, s, mask):
    return mask is None or (mask.pair_counts(s, b)[0] <= MASK_PAIRS_MAX_RATIO
                            * mask.pair_counts(s, 128)[0])


@pytest.mark.parametrize("kind", ["full", "causal", "window"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("d", [64, 128, 256])
def test_rule_picks_the_largest_legal_tile(d, dtype, kind):
    """Over the rule's domain, the tile a call records is ``best_block``'s,
    divides S, has forward and backward footprints within the 0.8 budget,
    and is the largest such: no larger tile of ``BLOCKS`` divides S, fits
    and (under the window of S / 8) stays within ``MASK_PAIRS_MAX_RATIO``."""
    nbytes, causal = jnp.dtype(dtype).itemsize, kind == "causal"
    for s in (384, 640, 1024, 4096, 8192):
        mask = SlidingWindow(s // 8) if kind == "window" else None
        kw = dict(mask=mask) if mask is not None else dict(causal=causal)
        block = _trace(s, d, dtype, **kw)["block"]
        assert block == best_block(s, d, nbytes, causal, mask=mask), s
        assert s % block == 0, s
        assert _fits(block, s, d, nbytes, causal, mask), (s, block)
        assert _within_pairs(block, s, mask), (s, block)
        for b in BLOCKS:
            if b > block and s % b == 0:
                assert not (_fits(b, s, d, nbytes, causal, mask)
                            and _within_pairs(b, s, mask)), (s, block, b)


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
