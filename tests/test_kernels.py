"""Pallas kernel tests: shape/dtype sweeps vs. the ref.py oracle (interpret mode)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.schedules import make_schedule
from repro.kernels import ref
from repro.kernels.flash_bwd import first_visit_flags, flash_bwd, serialize_schedule
from repro.kernels.flash_fwd import causal_grid, flash_fwd
from repro.kernels.ops import attention, dash_attention


def _rand(shape, dtype, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


def _tols(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


SHAPES = [  # (bh, seq, d, block)
    (1, 256, 64, 128),
    (2, 512, 128, 128),
    (3, 384, 64, 128),   # non-power-of-two tiles (3 tiles)
    (1, 1024, 64, 512),  # the tile the default rule picks at long S
    (2, 256, 96, 128),   # ragged head dim
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("bh,s,d,blk", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("causal", [False, True])
def test_fwd_matches_ref(bh, s, d, blk, dtype, causal):
    q, k, v = (_rand((bh, s, d), dtype, i) for i in range(3))
    out, lse = flash_fwd(q, k, v, causal=causal, block_q=blk, block_k=blk,
                         interpret=True)
    rout, rlse = ref.mha_fwd(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(rout, np.float32), **_tols(dtype))
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse), atol=1e-2, rtol=1e-3)


@pytest.mark.parametrize("bh,s,d,blk", SHAPES[:4])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("causal,sched", [
    (False, "fa3"), (False, "descending"), (False, "shift"),
    (True, "fa3"), (True, "descending"), (True, "symmetric_shift"),
])
def test_bwd_matches_ref(bh, s, d, blk, dtype, causal, sched):
    q, k, v, do = (_rand((bh, s, d), dtype, i) for i in range(4))
    out, lse = flash_fwd(q, k, v, causal=causal, block_q=blk, block_k=blk,
                         interpret=True)
    schedule = make_schedule(sched, s // blk, 1, causal)
    dq, dk, dv = flash_bwd(q, k, v, out, lse, do, schedule, causal=causal,
                           block_q=blk, block_k=blk, interpret=True)
    rdq, rdk, rdv = ref.mha_bwd(q, k, v, out, lse, do, causal=causal)
    tol = dict(atol=0.1, rtol=5e-2) if dtype == jnp.bfloat16 else _tols(dtype)
    for got, want, nm in ((dq, rdq, "dq"), (dk, rdk, "dk"), (dv, rdv, "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), err_msg=nm, **tol)


@pytest.mark.parametrize("causal,sched,s,blk", [
    pytest.param(True, "symmetric_shift", 256, 128, id="True-symmetric_shift"),
    pytest.param(False, "shift", 256, 128, id="False-shift"),
    pytest.param(True, "symmetric_shift", 1024, 512,
                 id="True-symmetric_shift-1024-512"),
    pytest.param(False, "shift", 1024, 512, id="False-shift-1024-512"),
])
def test_bwd_bitwise_deterministic(causal, sched, s, blk):
    """Same schedule => bitwise identical grads across runs (paper Table 1, det column)."""
    q, k, v, do = (_rand((2, s, 64), jnp.bfloat16, i + 10) for i in range(4))
    out, lse = flash_fwd(q, k, v, causal=causal, block_q=blk, block_k=blk,
                         interpret=True)
    schedule = make_schedule(sched, s // blk, 1, causal)
    f = lambda: flash_bwd(q, k, v, out, lse, do, schedule, causal=causal,
                          block_q=blk, block_k=blk, interpret=True)
    a, b = f(), f()
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_bwd_schedules_numerically_close_not_identical():
    """Different schedules fix different accumulation orders: each reproducible,
    mutually only numerically close (paper §1 non-associativity)."""
    q, k, v, do = (_rand((1, 512, 64), jnp.float32, i + 20) for i in range(4))
    out, lse = flash_fwd(q, k, v, causal=True, interpret=True)
    n = 4
    g = {}
    for sched in ("fa3", "descending", "symmetric_shift"):
        schedule = make_schedule(sched, n, 1, True)
        g[sched] = flash_bwd(q, k, v, out, lse, do, schedule, causal=True,
                             interpret=True)[0]
    np.testing.assert_allclose(np.asarray(g["fa3"]), np.asarray(g["symmetric_shift"]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g["fa3"]), np.asarray(g["descending"]),
                               atol=1e-5, rtol=1e-5)


def test_serialization_contiguity_and_first_visits():
    for sched, causal in [("fa3", True), ("descending", True),
                          ("symmetric_shift", True), ("shift", False), ("fa3", False)]:
        schedule = make_schedule(sched, 8, 1, causal)
        kv_ids, q_ids = serialize_schedule(schedule)
        # kv chains contiguous in serialized order
        seen = set()
        prev = None
        for kv in kv_ids:
            if kv != prev:
                assert kv not in seen, f"{sched}: kv chain split"
                seen.add(kv)
            prev = kv
        flags = first_visit_flags(kv_ids, q_ids)
        assert flags.sum() == len(set(q_ids.tolist()))
        # cell cover matches the mask
        cells = set(zip(kv_ids.tolist(), q_ids.tolist()))
        want = {(kv, qq) for kv in range(8) for qq in range(8)
                if (not causal) or qq >= kv}
        assert cells == want


@pytest.mark.parametrize("n_q,n_k,bq,bk", [
    (8, 8, 128, 128), (3, 3, 128, 128), (2, 4, 128, 64), (4, 2, 64, 128),
])
def test_causal_fwd_grid_has_zero_masked_tiles(n_q, n_k, bq, bk):
    """The schedule-driven causal forward removes masked tiles from the grid
    entirely: every emitted task intersects the mask, the valid set is covered
    exactly once, and q tiles are visited in descending order (§3.3). Shares
    the validator with the CI gate (benchmarks/check_causal_grid.py)."""
    from benchmarks.check_causal_grid import check
    res = check(n_q, n_k, bq, bk)
    assert not isinstance(res, str), res
    _, n_tasks, dense = res
    assert n_tasks < dense  # some masked tiles actually removed
    _, _, first, last = causal_grid(n_q, n_k, bq, bk)
    assert int(first.sum()) == n_q and int(last.sum()) == n_q


def test_causal_fwd_rect_blocks_match_ref():
    """Rectangular (block_q != block_k) causal tiling through the scheduled grid."""
    q, k, v = (_rand((2, 256, 64), jnp.float32, i) for i in range(3))
    out, lse = flash_fwd(q, k, v, causal=True, block_q=128, block_k=64,
                         interpret=True)
    rout, rlse = ref.mha_fwd(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rout), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse), atol=1e-2,
                               rtol=1e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_custom_vjp_wrapper_grads(causal):
    """dash_attention end-to-end grad vs. jax.vjp oracle, incl. native GQA."""
    B, H, HK, S, D = 1, 4, 2, 256, 64
    q = _rand((B, H, S, D), jnp.float32, 0)
    k = _rand((B, HK, S, D), jnp.float32, 1)
    v = _rand((B, HK, S, D), jnp.float32, 2)
    do = _rand((B, H, S, D), jnp.float32, 3)

    f = functools.partial(dash_attention, causal=causal, interpret=True)
    out, pull = jax.vjp(f, q, k, v)
    dq, dk, dv = pull(do)

    def g(q_, k_, v_):
        return attention(q_, k_, v_, causal=causal, impl="xla")
    rout, rpull = jax.vjp(g, q, k, v)
    rdq, rdk, rdv = rpull(do)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rout), atol=2e-5, rtol=2e-5)
    for got, want, nm in ((dq, rdq, "dq"), (dk, rdk, "dk"), (dv, rdv, "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=5e-5, err_msg=nm)
