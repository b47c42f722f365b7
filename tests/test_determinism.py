"""Determinism substrate tests (paper §1/§2/Table 1 analogue)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import determinism as det
from repro.core import schedules as S

jax.config.update("jax_enable_x64", False)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _parts(seed, n=16, shape=(8, 4), dtype=jnp.float32, scale=1e4):
    k = jax.random.PRNGKey(seed)
    # wide dynamic range to excite non-associativity
    mag = jax.random.uniform(k, (n,) + shape, minval=-scale, maxval=scale)
    return mag.astype(dtype)


def test_ordered_sum_bitwise_stable():
    p = _parts(0)
    a = det.ordered_sum(p)
    b = det.ordered_sum(p)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_permuted_sum_deviates():
    """Fig. 1 / Table 1: permuted (atomic-like) accumulation orders give different
    bits; the deviation is O(eps * scale) but nonzero."""
    p = _parts(1, n=64, scale=1e6).astype(jnp.float32)
    rng = np.random.RandomState(0)

    def run(i):
        perm = rng.permutation(64) if i else np.arange(64)
        return det.permuted_sum(p, perm)

    dev = det.max_deviation(run, None, n_runs=10)
    assert dev > 0.0                       # non-deterministic order => deviation
    ordered_dev = det.max_deviation(lambda i: det.ordered_sum(p), None, 10)
    assert ordered_dev == 0.0              # pinned order => bitwise identical


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 33), arity=st.sampled_from([2, 4]))
def test_tree_sum_fixed_matches_fp64(n, arity):
    p = _parts(2, n=n, shape=(4,), scale=10.0)
    got = det.tree_sum_fixed(p, arity=arity)
    # fp64 reference via numpy — x64 is disabled above, so an astype(float64)
    # inside jax would silently stay f32.  atol covers the f32 rounding of the
    # tree sum itself when the true sum cancels toward zero (n·scale·eps).
    want = np.sum(np.asarray(p, np.float64), axis=0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=n * 10.0 * 1.2e-7)
    # determinism: same tree shape, same bits
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(det.tree_sum_fixed(p, arity=arity)))


def test_schedule_ordered_dq_follows_schedule():
    """The dQ accumulation order comes from the schedule's reduction_order; two
    different schedules may give different bits, each individually reproducible."""
    n = 8
    p = _parts(3, n=n, shape=(16,), dtype=jnp.bfloat16, scale=100.0)
    fa3_order = [kv for kv, _ in S.fa3(n, 1, causal=False).reduction_order[(0, 3)]]
    shift_order = [kv for kv, _ in S.shift(n, 1).reduction_order[(0, 3)]]
    a1 = det.schedule_ordered_dq(p, fa3_order)
    a2 = det.schedule_ordered_dq(p, fa3_order)
    b = det.schedule_ordered_dq(p, shift_order)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    # close numerically (same math), not necessarily identical bits. bf16 eps is
    # ~0.8% of the +/-100 input scale, and cancellation makes *relative* output
    # error unbounded — compare with an absolute tolerance scaled to the inputs.
    np.testing.assert_allclose(np.asarray(a1, np.float32), np.asarray(b, np.float32),
                               atol=8 * 0.008 * 100.0)


# --------------------------------------------- property tests (PR 4 satellite)
@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 1000), n=st.integers(2, 24))
def test_ordered_sum_permutation_sensitive_but_stable(seed, n):
    """ordered_sum pins ((x0+x1)+x2)+…: bitwise stable across calls, but a
    permuted operand order is a *different* association and (for wide dynamic
    range) gives different bits — exactly the property the DASH schedules
    exploit."""
    p = _parts(seed, n=n, shape=(16,), scale=1e6)
    a = det.ordered_sum(p)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(det.ordered_sum(p)))
    rng = np.random.RandomState(seed)
    deviated = False
    for _ in range(8):
        perm = rng.permutation(n)
        b = det.permuted_sum(p, perm)
        # same multiset of addends, so equality is only plausible when the
        # permutation is the identity
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            deviated = True
    if n > 4:       # small n: too few distinct associations to guarantee it
        assert deviated


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 1000), n=st.integers(1, 20),
       arity=st.sampled_from([2, 4]))
def test_tree_sum_fixed_stable_and_shape_pinned(seed, n, arity):
    p = _parts(seed, n=n, shape=(8,), scale=1e5)
    a = det.tree_sum_fixed(p, arity=arity)
    np.testing.assert_array_equal(
        np.asarray(a), np.asarray(det.tree_sum_fixed(p, arity=arity)))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000))
def test_schedule_ordered_dq_stable_and_order_sensitive(seed):
    n = 8
    p = _parts(seed, n=n, shape=(16,), scale=1e6)
    fwd = list(range(n))
    rev = fwd[::-1]
    a = det.schedule_ordered_dq(p, fwd)
    np.testing.assert_array_equal(np.asarray(a),
                                  np.asarray(det.schedule_ordered_dq(p, fwd)))
    b = det.schedule_ordered_dq(p, rev)
    np.testing.assert_array_equal(np.asarray(b),
                                  np.asarray(det.schedule_ordered_dq(p, rev)))
    # the reduction order is part of the contract: reversed order is allowed
    # to (and at this dynamic range does) change bits
    assert not np.array_equal(np.asarray(a), np.asarray(b))


_RING_FOLD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import determinism as det

    x = jax.random.uniform(jax.random.PRNGKey(0), (8, 64), minval=-1e4,
                           maxval=1e4)
    for n in (2, 4, 8):
        mesh = Mesh(np.array(jax.devices()[:n]), ("x",))
        f = jax.jit(jax.shard_map(lambda v: det.ring_ordered_psum(v[0], "x"),
                                  mesh=mesh, in_specs=(P("x"),),
                                  out_specs=P(None), check_vma=False))
        got = f(x[:n])
        # sequential left fold over the n shards — the declared association
        want = det.ordered_sum(x[:n])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        print(f"n={n} ring fold matches sequential association")
""")


def test_ring_ordered_psum_matches_sequential_fold_n248():
    """PR 4 satellite: the pinned ring association equals the sequential fold
    for n ∈ {2, 4, 8} — i.e. the association is mesh-size-declared, not
    topology-derived (subprocess: forced 8-CPU-device platform)."""
    r = subprocess.run([sys.executable, "-c", _RING_FOLD_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": "src"}, cwd=REPO_ROOT)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    for n in (2, 4, 8):
        assert f"n={n} ring fold matches sequential association" in r.stdout


def test_ring_ordered_psum_single_device():
    """Association check on a 1D mesh of size 1 (CPU) — full multi-device variant
    is exercised in test_dist_collectives.py under a forced 8-device platform."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    x = jnp.arange(4, dtype=jnp.float32)
    f = jax.shard_map(lambda v: det.ring_ordered_psum(v, "x"), mesh=mesh,
                      in_specs=(jax.sharding.PartitionSpec("x"),),
                      out_specs=jax.sharding.PartitionSpec())
    # n=1: identity
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))
