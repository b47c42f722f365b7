"""Block-sparse mask kernels vs the dense-materialized oracle.

ISSUE 5 acceptance: for every new MaskSpec family {sliding-window, prefix-LM,
document, sink/streaming} × {fp32, bf16} × GQA groups {1, 2}:
  * forward and backward match ``kernels/ref`` under the dense
    ``MaskSpec.materialize()`` mask;
  * serialized and worker-parallel backward realizations are **bitwise
    identical** (exact-zero PARTIAL lanes + single-visit ragged chains);
  * 20-rep bitwise soaks;
  * the lowered masked step passes the ``verify.trace`` nondeterminism audit.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_bwd import flash_bwd
from repro.kernels.flash_fwd import flash_fwd, mask_grid
from repro.kernels.ops import attention, dash_attention, xla_attention
from repro.masks import (Document, PrefixLM, SlidingWindow,
                         compile_block_schedule, streaming_mask)
from repro.masks.spec import EMPTY
from repro.verify.trace import audit_fn

S, D, BLK = 256, 64, 64
N = S // BLK


def _rand(shape, dtype, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


def _tols(dtype):
    return (dict(atol=0.1, rtol=5e-2) if dtype == jnp.bfloat16
            else dict(atol=3e-5, rtol=3e-5))


MASKS = [
    ("window", SlidingWindow(96)),
    ("prefix", PrefixLM(80)),
    ("document", Document.from_lengths((100, 156))),
    ("streaming", streaming_mask(64, 16)),   # sink ∨ window, ∧ causal
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("name,mask", MASKS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_masked_fwd_matches_dense_ref(name, mask, dtype):
    q, k, v = (_rand((2, S, D), dtype, i) for i in range(3))
    out, lse = flash_fwd(q, k, v, mask=mask, block_q=BLK, block_k=BLK,
                         interpret=True)
    rout, rlse = ref.mha_fwd(q, k, v, mask=mask.materialize(S))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(rout, np.float32),
                               **(_tols(dtype) if dtype != jnp.bfloat16
                                  else dict(atol=2e-2, rtol=2e-2)))
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse),
                               atol=1e-2, rtol=1e-3)


@pytest.mark.parametrize("name,mask", MASKS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("placement", ["shift", "fa3"])
def test_masked_bwd_serialized_parallel_bitwise(name, mask, dtype, placement):
    """The exact-zero-lane contract: ser ≡ par bit for bit under every mask
    and placement."""
    q, k, v, do = (_rand((2, S, D), dtype, i) for i in range(4))
    out, lse = flash_fwd(q, k, v, mask=mask, block_q=BLK, block_k=BLK,
                         interpret=True)
    sch = compile_block_schedule(mask, N, N, BLK, BLK, placement=placement)
    args = dict(block_q=BLK, block_k=BLK, interpret=True, mask=mask)
    par = flash_bwd(q, k, v, out, lse, do, sch, worker_parallel=True, **args)
    ser = flash_bwd(q, k, v, out, lse, do, sch, worker_parallel=False, **args)
    for a, b, nm in zip(par, ser, ("dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{name} {nm}")


@pytest.mark.parametrize("name,mask", MASKS)
def test_masked_bwd_matches_dense_ref(name, mask):
    q, k, v, do = (_rand((1, S, D), jnp.float32, i + 7) for i in range(4))
    dense = mask.materialize(S)
    out, lse = flash_fwd(q, k, v, mask=mask, block_q=BLK, block_k=BLK,
                         interpret=True)
    sch = compile_block_schedule(mask, N, N, BLK, BLK)
    dq, dk, dv = flash_bwd(q, k, v, out, lse, do, sch, block_q=BLK,
                           block_k=BLK, interpret=True, mask=mask)
    rdq, rdk, rdv = ref.mha_bwd(q, k, v, out, lse, do, mask=dense)
    for got, want, nm in ((dq, rdq, "dq"), (dk, rdk, "dk"), (dv, rdv, "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   err_msg=f"{name} {nm}", atol=3e-5,
                                   rtol=3e-5)


@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("name,mask", MASKS[:2] + MASKS[2:3])
def test_masked_attention_gqa_grads_vs_oracle(group, name, mask):
    """dash_attention(mask=…) end-to-end grads vs jax.vjp on the dense-masked
    reference, with native GQA (KV heads never repeated)."""
    B, H = 1, 4
    HK = H // group
    q = _rand((B, H, S, D), jnp.float32, 0)
    k = _rand((B, HK, S, D), jnp.float32, 1)
    v = _rand((B, HK, S, D), jnp.float32, 2)
    do = _rand((B, H, S, D), jnp.float32, 3)

    f = functools.partial(dash_attention, mask=mask, interpret=True, block=BLK)
    out, pull = jax.vjp(f, q, k, v)
    dq, dk, dv = pull(do)

    def g(q_, k_, v_):
        return xla_attention(q_, k_, v_, mask=mask)

    rout, rpull = jax.vjp(g, q, k, v)
    rdq, rdk, rdv = rpull(do)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rout), atol=3e-5,
                               rtol=3e-5)
    for got, want, nm in ((dq, rdq, "dq"), (dk, rdk, "dk"), (dv, rdv, "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"{name} g{group} {nm}")


@pytest.mark.parametrize("name,mask", [MASKS[0], MASKS[2]])
def test_masked_bwd_bitwise_soak_20_reps(name, mask):
    """Same inputs, 20 runs: identical bits every time (paper Table 1 det)."""
    q, k, v, do = (_rand((2, S, D), jnp.bfloat16, i + 10) for i in range(4))
    out, lse = flash_fwd(q, k, v, mask=mask, block_q=BLK, block_k=BLK,
                         interpret=True)
    sch = compile_block_schedule(mask, N, N, BLK, BLK)
    first = None
    for _ in range(20):
        grads = flash_bwd(q, k, v, out, lse, do, sch, block_q=BLK,
                          block_k=BLK, interpret=True, mask=mask)
        got = [np.asarray(g) for g in grads]
        if first is None:
            first = got
        else:
            for a, b in zip(first, got):
                np.testing.assert_array_equal(a, b)


def test_masked_fwd_bitwise_soak_20_reps():
    mask = streaming_mask(64, 16)
    q, k, v = (_rand((2, S, D), jnp.bfloat16, i + 30) for i in range(3))
    first = None
    for _ in range(20):
        out, lse = flash_fwd(q, k, v, mask=mask, block_q=BLK, block_k=BLK,
                             interpret=True)
        got = [np.asarray(out), np.asarray(lse)]
        if first is None:
            first = got
        else:
            for a, b in zip(first, got):
                np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------- grid structure
def test_mask_grid_skips_empty_tiles_exactly():
    """The forward grid contains exactly the non-EMPTY tiles, q descending."""
    for _, mask in MASKS:
        bm = mask.block_map(N, N, BLK, BLK)
        kv_ids, q_ids, first, last, partial = mask_grid(mask, N, N, BLK, BLK)
        want = {(int(kv), int(q)) for kv in range(N) for q in range(N)
                if bm[kv, q] != EMPTY}
        got = set(zip(kv_ids.tolist(), q_ids.tolist()))
        assert got == want and len(kv_ids) == len(want)
        q_order = [q for i, q in enumerate(q_ids.tolist()) if first[i]]
        assert q_order == sorted(q_order, reverse=True)
        assert int(first.sum()) == N and int(last.sum()) == N


def test_masked_fwd_rect_blocks_match_ref():
    """Rectangular (block_q != block_k) tiling through the masked grid."""
    mask = PrefixLM(80)
    q, k, v = (_rand((2, S, D), jnp.float32, i) for i in range(3))
    out, lse = flash_fwd(q, k, v, mask=mask, block_q=128, block_k=64,
                         interpret=True)
    rout, rlse = ref.mha_fwd(q, k, v, mask=mask.materialize(S))
    np.testing.assert_allclose(np.asarray(out), np.asarray(rout), atol=3e-5,
                               rtol=3e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse), atol=1e-2,
                               rtol=1e-3)


def test_masked_bwd_rect_blocks_match_ref():
    """Rectangular tiles in the masked backward (ragged non-square tile
    grid: n_kv != n_q)."""
    mask = SlidingWindow(96)
    bq, bk = 128, 64
    q, k, v, do = (_rand((1, S, D), jnp.float32, i) for i in range(4))
    out, lse = flash_fwd(q, k, v, mask=mask, block_q=bq, block_k=bk,
                         interpret=True)
    sch = compile_block_schedule(mask, S // bk, S // bq, bq, bk)
    dq, dk, dv = flash_bwd(q, k, v, out, lse, do, sch, block_q=bq,
                           block_k=bk, interpret=True, mask=mask)
    rdq, rdk, rdv = ref.mha_bwd(q, k, v, out, lse, do,
                                mask=mask.materialize(S))
    for got, want, nm in ((dq, rdq, "dq"), (dk, rdk, "dk"), (dv, rdv, "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5, err_msg=nm)


def test_dead_kv_rows_zeroed_in_bwd():
    """KV rows with zero surviving tiles never enter the grid; their dk/dv
    must come back exact-zero, not uninitialized."""
    # tight non-causal window band leaves far-off rows empty at small blocks
    from repro.masks.spec import Document as Doc
    mask = Doc.from_lengths((64, 192)) & SlidingWindow(64)
    sch = compile_block_schedule(mask, N, N, BLK, BLK)
    dead = set(range(N)) - {kv for (kv, _q) in sch.cells}
    q, k, v, do = (_rand((1, S, D), jnp.float32, i) for i in range(4))
    out, lse = flash_fwd(q, k, v, mask=mask, block_q=BLK, block_k=BLK,
                         interpret=True)
    dq, dk, dv = flash_bwd(q, k, v, out, lse, do, sch, block_q=BLK,
                           block_k=BLK, interpret=True, mask=mask)
    dense = mask.materialize(S)
    rdq, rdk, rdv = ref.mha_bwd(q, k, v, out, lse, do, mask=dense)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk), atol=3e-5,
                               rtol=3e-5)
    for kv in dead:
        blk = np.asarray(dk)[:, kv * BLK:(kv + 1) * BLK]
        np.testing.assert_array_equal(blk, np.zeros_like(blk))


def test_schedule_mask_mismatch_rejected():
    """A schedule compiled for one mask must refuse a different mask — the
    kernel-side guard behind the cache-key extension."""
    a, b = SlidingWindow(96), SlidingWindow(97)
    sch = compile_block_schedule(a, N, N, BLK, BLK)
    q, k, v, do = (_rand((1, S, D), jnp.float32, i) for i in range(4))
    out, lse = flash_fwd(q, k, v, mask=a, block_q=BLK, block_k=BLK,
                         interpret=True)
    with pytest.raises(AssertionError, match="compiled for mask"):
        flash_bwd(q, k, v, out, lse, do, sch, block_q=BLK, block_k=BLK,
                  interpret=True, mask=b)


def test_pallas_impl_refuses_dynamic_segment_ids():
    """Dynamic segment ids have no static block map: the pallas impl refuses
    them rather than quietly running the xla path."""
    q = _rand((1, 2, S, D), jnp.float32, 0)
    seg = jnp.ones((1, S), jnp.int32)
    with pytest.raises(ValueError, match="segment_ids"):
        attention(q, q, q, causal=True, impl="pallas", interpret=True,
                  segment_ids=seg)


# ----------------------------------------------------------- verify.trace
def test_masked_attention_lowering_audit_clean():
    """The lowered masked forward+backward contains no nondeterminism-prone
    primitives (unordered scatters etc.) — verify.trace must come back empty
    on both the xla segment path and the dash block-sparse path."""
    B, H, HK = 1, 2, 2
    q = _rand((B, H, S, D), jnp.float32, 0)
    k = _rand((B, HK, S, D), jnp.float32, 1)
    v = _rand((B, HK, S, D), jnp.float32, 2)
    seg = jnp.concatenate([jnp.full((B, 100), 1, jnp.int32),
                           jnp.full((B, 156), 2, jnp.int32)], 1)

    def seg_loss(q_, k_, v_):
        return jnp.sum(attention(q_, k_, v_, causal=True,
                                 segment_ids=seg).astype(jnp.float32))

    assert audit_fn(jax.grad(seg_loss), q, k, v) == []

    mask = SlidingWindow(96)

    def dash_loss(q_, k_, v_):
        return jnp.sum(dash_attention(q_, k_, v_, mask=mask, interpret=True,
                                      block=BLK).astype(jnp.float32))

    assert audit_fn(jax.grad(dash_loss), q, k, v) == []
