"""VMEM working-set accounting for the DASH kernels (TPU v5e: 16 MiB of VMEM
scoped to a kernel by default, of 128 MiB physical; Pallas double-buffers
every blocked operand).

BlockSpec shapes determine the footprint the kernel claims; this module makes
that arithmetic explicit so block sizes are chosen — not guessed — and tests
assert the budget (structural reasoning per the dry-run profiling methodology:
no wall-clock on this host, so the IR/footprint is the profile).

A footprint counts the live set of one grid step: the pipelined blocks, the
scratch, and the (bq × bk) f32 tiles the body computes. VMEM holds arrays in
(8, 128) tiles, so a d-wide buffer takes ``lanes(d)`` lanes and a
(1, block) row takes 8 sublanes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

VMEM_BYTES = 16 * 1024 * 1024
# Pallas double-buffers every blocked operand (fetch t+1 during compute t)
PIPELINE_FACTOR = 2
SUBLANES = 8
# square tiles the default block rule chooses from, largest first
BLOCKS = (512, 256, 128)
# A block-sparse mask computes its PARTIAL tiles whole, so a larger tile
# computes more pairs the mask leaves out, in fewer grid steps. The mask
# takes the largest tile whose computed pairs stay within this factor of
# the 128 tiling's. On a TPU v5e a grid step's fixed cost dominates: a
# window of 1024 at S 8192, d 128 ran forward and backward in 77.9 ms at
# 128 tiles, 38.1 at 256 and 22.6 at 512, computing 1.00, 1.11 and 1.33
# times the pairs. At a factor of 2 the added MXU work equals all of the
# 128 tiling's, about a tenth of its time; no band was measured beyond.
MASK_PAIRS_MAX_RATIO = 2.0


def lanes(d: int) -> int:
    """``d`` rounded up to the 128 lanes of a VMEM tile (also the width of
    the fp32 dQ buffers the backward DMAs into: ``flash_bwd.dq_lanes``)."""
    return -(-d // 128) * 128


@dataclasses.dataclass(frozen=True)
class KernelFootprint:
    buffers: Dict[str, int]

    @property
    def total(self) -> int:
        return sum(self.buffers.values())

    @property
    def fraction(self) -> float:
        return self.total / VMEM_BYTES

    def fits(self, budget: float = 0.8) -> bool:
        return self.fraction <= budget


def _mask_tiles(block_q: int, block_k: int, causal: bool,
                info: bool) -> Dict[str, int]:
    """The masked body's row and column iotas and their comparison, each a
    (bq × bk) 32-bit tile; with ``info``, a spec's token_info slices, a
    (bq, 1) int32 column on bq rows of 128 lanes and a (1, bk) row on 8
    sublanes, double-buffered."""
    tiles = {"iotas/mask": 3 * block_q * block_k * 4} if causal else {}
    if info:
        tiles["q/k info"] = (PIPELINE_FACTOR
                             * (block_q * 128 + SUBLANES * block_k) * 4)
    return tiles


def fwd_footprint(block_q: int, block_k: int, d: int,
                  in_dtype_bytes: int = 2, causal: bool = True,
                  info: bool = False) -> KernelFootprint:
    """flash_fwd: q/k/v blocks and the out/lse blocks double-buffered, fp32
    scratch (acc, running max and sum), the f32 copies of q/k/v, and the
    score and probability tiles."""
    dl = lanes(d)
    return KernelFootprint({
        "q": PIPELINE_FACTOR * block_q * dl * in_dtype_bytes,
        "k": PIPELINE_FACTOR * block_k * dl * in_dtype_bytes,
        "v": PIPELINE_FACTOR * block_k * dl * in_dtype_bytes,
        "o": PIPELINE_FACTOR * block_q * dl * in_dtype_bytes,
        "lse": PIPELINE_FACTOR * SUBLANES * block_q * 4,
        "acc": block_q * dl * 4,
        "m/l": 2 * block_q * 128 * 4,
        "f32 operands": (block_q + 2 * block_k) * dl * 4,
        "s/p tiles": 2 * block_q * block_k * 4,
        **_mask_tiles(block_q, block_k, causal, info),
    })


def bwd_footprint(block_q: int, block_k: int, d: int,
                  in_dtype_bytes: int = 2, causal: bool = True,
                  info: bool = False) -> KernelFootprint:
    """flash_bwd, one task: q/do/k/v and the lse/delta rows double-buffered,
    the fp32 dk/dv output blocks (VMEM-resident across the contiguous KV
    chain, double-buffered), the dQ RMW scratch at ``lanes(d)``, the f32
    copies of the four operands, and the s/p/dp/ds tiles."""
    dl = lanes(d)
    return KernelFootprint({
        "q": PIPELINE_FACTOR * block_q * dl * in_dtype_bytes,
        "do": PIPELINE_FACTOR * block_q * dl * in_dtype_bytes,
        "k": PIPELINE_FACTOR * block_k * dl * in_dtype_bytes,
        "v": PIPELINE_FACTOR * block_k * dl * in_dtype_bytes,
        "lse/delta": 2 * PIPELINE_FACTOR * SUBLANES * block_q * 4,
        "dk/dv": 2 * PIPELINE_FACTOR * block_k * dl * 4,
        "dq_scratch": block_q * dl * 4,
        "f32 operands": 2 * (block_q + block_k) * dl * 4,
        "s/p/dp/ds tiles": 4 * block_q * block_k * 4,
        **_mask_tiles(block_q, block_k, causal, info),
    })


def best_block(seq: int, d: int, in_dtype_bytes: int = 2,
               causal: bool = True, budget: float = 0.8, mask=None) -> int:
    """Largest square block of :data:`BLOCKS` that divides ``seq`` and whose
    forward and backward footprints fit the budget; 128 when none does.
    Larger blocks amortize the fixed cost of a grid step and the per-task dQ
    RMW (the paper's r) over more compute (c), and fewer KV tiles mean fewer
    workers, so fewer dQ partials for the fold to read.

    Under a block-sparse ``mask`` (a :class:`repro.masks.spec.MaskSpec`)
    the footprints count the mask's iota tiles and its token_info rows, and
    the block must also compute at most :data:`MASK_PAIRS_MAX_RATIO` times
    the pairs that 128 tiles compute (``mask.pair_counts``)."""
    info = mask is not None and mask.token_info(seq) is not None
    for b in BLOCKS:
        if seq % b or not all(
                fp(b, b, d, in_dtype_bytes, causal or mask is not None,
                   info).fits(budget)
                for fp in (fwd_footprint, bwd_footprint)):
            continue
        if mask is None or (mask.pair_counts(seq, b)[0] <= MASK_PAIRS_MAX_RATIO
                            * mask.pair_counts(seq, 128)[0]):
            return b
    return 128
