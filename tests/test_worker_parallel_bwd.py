"""Worker-parallel DASH backward: the schedule's parallel dimension on the grid.

Contract under test (ISSUE 3 acceptance):
  * bitwise identity between the W=1 serialized realization and the W=n
    worker-parallel realization of the same schedule, for every registry
    generator on causal + full masks;
  * 20-rep bitwise-determinism soak of the worker-parallel path;
  * numerical correctness vs the untiled jnp oracle;
  * structure of the padded per-worker prefetch arrays (no-op sentinels).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.schedules import make_schedule
from repro.kernels import ref
from repro.kernels.flash_bwd import flash_bwd, fold_combine, serialize_schedule
from repro.kernels.flash_fwd import flash_fwd

SCHEDULES = [
    ("fa3", False), ("fa3", True),
    ("descending", False), ("descending", True),
    ("shift", False), ("symmetric_shift", True),
]


def _rand(shape, dtype, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


def _bwd(sched, causal, dtype, worker_parallel, bh=2, s=512, d=64, blk=128):
    q, k, v, do = (_rand((bh, s, d), dtype, i) for i in range(4))
    out, lse = flash_fwd(q, k, v, causal=causal, block_q=blk, block_k=blk,
                         interpret=True)
    schedule = make_schedule(sched, s // blk, 1, causal)
    return flash_bwd(q, k, v, out, lse, do, schedule, causal=causal,
                     block_q=blk, block_k=blk, interpret=True,
                     worker_parallel=worker_parallel)


@pytest.mark.parametrize("sched,causal,s,blk", [
    *(pytest.param(sched, causal, 512, 128, id=f"{sched}-{causal}")
      for sched, causal in SCHEDULES),
    # the tile the default rule picks at long S
    pytest.param("symmetric_shift", True, 1024, 512,
                 id="symmetric_shift-True-1024-512"),
    pytest.param("shift", False, 1024, 512, id="shift-False-1024-512"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_parallel_bitwise_matches_serialized(sched, causal, s, blk, dtype):
    """W=n parallel realization == W=1 serialized realization, bit for bit.

    Both paths reduce every dQ column worker-major (the serialized core plays
    chains concatenated ascending; the parallel combine folds partials in
    ascending worker order), and registry schedules give each worker at most
    one task per column — so the fp32 folds have identical association."""
    par = _bwd(sched, causal, dtype, worker_parallel=True, s=s, blk=blk)
    ser = _bwd(sched, causal, dtype, worker_parallel=False, s=s, blk=blk)
    for got, want, nm in zip(par, ser, ("dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"{sched} {nm}")


@pytest.mark.parametrize("sched,causal", [("symmetric_shift", True),
                                          ("shift", False)])
def test_parallel_bitwise_soak_20_reps(sched, causal):
    """Same inputs, 20 runs: identical bits every time (paper Table 1 det)."""
    q, k, v, do = (_rand((2, 256, 64), jnp.bfloat16, i + 10) for i in range(4))
    out, lse = flash_fwd(q, k, v, causal=causal, interpret=True)
    schedule = make_schedule(sched, 2, 1, causal)
    first = None
    for _ in range(20):
        grads = flash_bwd(q, k, v, out, lse, do, schedule, causal=causal,
                          interpret=True, worker_parallel=True)
        got = [np.asarray(g) for g in grads]
        if first is None:
            first = got
        else:
            for a, b in zip(first, got):
                np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("sched,causal", SCHEDULES)
def test_parallel_matches_ref(sched, causal):
    """Correctness independent of the serialized path: vs the untiled oracle."""
    dq, dk, dv = _bwd(sched, causal, jnp.float32, worker_parallel=True,
                      bh=1, s=384, d=64, blk=128)
    q, k, v, do = (_rand((1, 384, 64), jnp.float32, i) for i in range(4))
    out, lse = ref.mha_fwd(q, k, v, causal=causal)
    rdq, rdk, rdv = ref.mha_bwd(q, k, v, out, lse, do, causal=causal)
    for got, want, nm in ((dq, rdq, "dq"), (dk, rdk, "dk"), (dv, rdv, "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5, err_msg=nm)


@pytest.mark.parametrize("sched,causal", SCHEDULES)
def test_worker_chains_structure(sched, causal):
    """Padded arrays: sentinels repeat the last task (no index churn), valid
    flags cover exactly the serialized task set, registry schedules are
    single-visit (the bitwise-identity precondition)."""
    n = 8
    schedule = make_schedule(sched, n, 1, causal)
    wc = schedule.worker_chains()
    kv_ids, q_ids, valid = wc["kv_ids"], wc["q_ids"], wc["valid"]
    assert wc["single_visit"]
    assert kv_ids.shape == (n, kv_ids.shape[1])
    # valid tasks == serialized task multiset
    ser_kv, ser_q = serialize_schedule(schedule)
    par_tasks = sorted((int(kv_ids[w, t]), int(q_ids[w, t]))
                       for w in range(n) for t in range(kv_ids.shape[1])
                       if valid[w, t])
    assert par_tasks == sorted(zip(ser_kv.tolist(), ser_q.tolist()))
    for w in range(n):
        chain_len = int(valid[w].sum())
        # padding is a contiguous tail repeating the last valid task
        assert valid[w, :chain_len].all() and not valid[w, chain_len:].any()
        assert (kv_ids[w, chain_len:] == kv_ids[w, chain_len - 1]).all()
        assert (q_ids[w, chain_len:] == q_ids[w, chain_len - 1]).all()
        # visited mask agrees with the q columns this worker touches
        touched = {int(q_ids[w, t]) for t in range(chain_len)}
        assert {q for q in range(n) if wc["visited"][w, q]} == touched


def test_non_registry_schedule_falls_back_to_serialized():
    """A schedule whose head-0 tasks leave a worker empty cannot build the
    parallel grid; flash_bwd must degrade to the serialized realization
    (same bits) rather than crash or change numerics."""
    from repro.core.schedules import Schedule
    base = make_schedule("fa3", 2, 1, False)
    sch = Schedule("custom", False, 2, 2, 2, 1,
                   ((), base.chains[0] + base.chains[1]), base.reduction_order)
    with pytest.raises(ValueError, match="empty worker chain"):
        sch.worker_chains()
    q, k, v, do = (_rand((1, 256, 64), jnp.float32, i) for i in range(4))
    out, lse = flash_fwd(q, k, v, interpret=True)
    par = flash_bwd(q, k, v, out, lse, do, sch, interpret=True,
                    worker_parallel=True)
    ser = flash_bwd(q, k, v, out, lse, do, sch, interpret=True,
                    worker_parallel=False)
    for a, b in zip(par, ser):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fold_combine_is_ascending_left_fold():
    """The combine is a left fold in ascending partial order — verified bitwise
    against a numpy fp32 fold, including masked-out (garbage) partials."""
    rng = np.random.default_rng(0)
    n, r, s, d, blk = 2, 4, 256, 64, 128
    parts = rng.standard_normal((n, r, s, d), dtype=np.float32) * 100
    visited = np.ones((r, s // blk), np.int32)
    visited[2, 0] = 0  # partial 2 never wrote tile 0: must be skipped, not added
    got = np.asarray(fold_combine(jnp.asarray(parts), visited, blk,
                                  interpret=True))
    want = np.zeros((n, s, d), np.float32)
    for ti in range(s // blk):
        sl = slice(ti * blk, (ti + 1) * blk)
        acc, started = None, False
        for j in range(r):
            if not visited[j, ti]:
                continue
            acc = parts[:, j, sl, :].copy() if not started else acc + parts[:, j, sl, :]
            started = True
        want[:, sl, :] = acc
    np.testing.assert_array_equal(got, want)
