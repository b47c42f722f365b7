"""Multi-device collective determinism tests on a forced 8-CPU-device platform
(subprocess, so the main test process keeps 1 device).

Referenced by tests/test_determinism.py: the full multi-device variant of
``ring_ordered_psum``, plus the rule-set → PartitionSpec layer from
``repro.dist.sharding`` under a real mesh, plus the *topology-invariant*
``repro.dist.fold.fixed_fold_psum`` (the serving-side canonical fold: one
answer for every shard count, not merely one answer per shard count).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import PartitionSpec as P

from repro.dist.ring_attention import (ring_step_offsets, zigzag_inverse,
                                       zigzag_permutation)
from repro.dist.sharding import (RULE_SETS, logical_to_spec, sanitize_pspecs,
                                 spec_tree_to_pspecs)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import auto_mesh
    from jax.sharding import PartitionSpec as P
    from repro.core import determinism as det

    mesh = auto_mesh((8,), ("x",))
    x = jax.random.uniform(jax.random.PRNGKey(0), (8, 64), minval=-1e4,
                           maxval=1e4)

    f = jax.jit(jax.shard_map(lambda v: det.ring_ordered_psum(v[0], "x"),
                              mesh=mesh, in_specs=(P("x"),), out_specs=P(None),
                              check_vma=False))
    got = f(x)
    # association pinned to ascending device index == strict left-to-right fold
    want = det.ordered_sum(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    print("ring_ordered_psum matches ordered fold bitwise")

    # bitwise repeatable across two executions
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(got))
    txt = f.lower(x).compile().as_text()
    assert "collective-permute" in txt
    print("ring_ordered_psum deterministic + ppermute OK")
""")


def test_ring_ordered_psum_multidevice():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": "src"}, cwd=REPO_ROOT)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "ring_ordered_psum matches ordered fold bitwise" in r.stdout
    assert "ring_ordered_psum deterministic + ppermute OK" in r.stdout


# ---------------------------------------------------------- pure-python layer
def test_rule_sets_cover_model_logical_axes():
    """Every logical axis the models annotate must resolve under every rule
    set (unknown names resolve to None, but the canonical ones must be
    declared so typos fail loudly here)."""
    logical = {"batch", "seq", "seq_sp", "act_embed", "act_heads", "act_mlp",
               "moe_group", "embed", "heads", "kv", "mlp", "vocab", "experts",
               "layers"}
    for name, factory in RULE_SETS.items():
        for multi_pod in (False, True):
            rules = factory(multi_pod)
            missing = logical - set(rules)
            assert not missing, (name, multi_pod, missing)


def test_logical_to_spec_and_tree():
    rules = RULE_SETS["fsdp_tp"](False)
    assert logical_to_spec(("batch", None), rules) == P("data", None)
    assert logical_to_spec(("embed", "heads"), rules) == P("data", "model")
    tree = {"w": ("embed", "mlp"), "b": (None,)}
    specs = spec_tree_to_pspecs(tree, rules)
    assert specs == {"w": P("data", "model"), "b": P(None)}


def test_sanitize_drops_nondividing_and_foreign_axes():
    import jax

    class _Shape:
        def __init__(self, shape):
            self.shape = shape

    mesh = type("M", (), {"shape": {"data": 2, "model": 4}})()
    # 14 heads on model=4 does not divide -> replicated; "cp" not on the mesh
    got = sanitize_pspecs({"a": P("data", "model"), "b": P("cp", "model")},
                          {"a": _Shape((8, 14)), "b": _Shape((8, 16))}, mesh)
    assert got == {"a": P("data", None), "b": P(None, "model")}


def test_zigzag_permutation_roundtrip_and_pairing():
    perm = zigzag_permutation(32, 4)
    inv = zigzag_inverse(32, 4)
    assert (perm[inv] == range(32)).all()
    # device i holds half-chunks (i, 2n-1-i): check chunk ids per device block
    chunks = perm.reshape(4, 2, 4)[:, :, 0] // 4
    assert [tuple(c) for c in chunks] == [(0, 7), (1, 6), (2, 5), (3, 4)]


def test_ring_step_offsets_are_schedule_cyclic():
    for n in (1, 2, 4, 8):
        assert ring_step_offsets(n, False) == tuple(range(n))
        assert ring_step_offsets(n, True) == tuple(range(n))


# --------------------------------------------------- canonical fold (serving)
FOLD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import auto_mesh
    from jax.sharding import PartitionSpec as P
    from repro.core import determinism as det
    from repro.dist import fold
    from repro.verify import trace

    V = 8                                    # virtual shards (canonical grid)
    for dtype in (jnp.float32, jnp.bfloat16):
        x = jax.random.uniform(jax.random.PRNGKey(0), (V, 4, 64),
                               minval=-1e3, maxval=1e3).astype(dtype)
        want = np.asarray(fold.fixed_fold_psum(x, None))
        assert np.array_equal(
            want, np.asarray(det.ordered_sum(x.astype(jnp.float32))
                             if dtype == jnp.float32 else want))
        for n in (1, 2, 4, 8):
            mesh = auto_mesh((n,), ("m",))
            f = jax.jit(jax.shard_map(
                lambda v: fold.fixed_fold_psum(v, "m"), mesh=mesh,
                in_specs=(P("m"),), out_specs=P(None), check_vma=False))
            got = np.asarray(f(x))
            assert np.array_equal(got, want), (str(dtype), n)
        print(f"fixed_fold_psum invariant over n in (1,2,4,8) {dtype.__name__}")

    # the fold's collectives pass the nondeterminism auditor: the ppermute
    # ring moves data only and the final psum is the blessed one-hot
    # axis_index broadcast
    mesh = auto_mesh((4,), ("m",))
    x = jax.random.uniform(jax.random.PRNGKey(1), (8, 4, 64))
    f = jax.jit(jax.shard_map(lambda v: fold.fixed_fold_psum(v, "m"), mesh=mesh,
                              in_specs=(P("m"),), out_specs=P(None),
                              check_vma=False))
    findings = trace.audit_fn(f, x)
    assert findings == [], findings
    print("fixed_fold_psum trace audit clean")
""")


def test_fixed_fold_psum_topology_invariant():
    """The tentpole collective: one bitwise answer for every shard count
    (1/2/4/8 devices), fp32 and bf16, equal to the sequential left fold —
    and its jaxpr is clean under verify.trace."""
    r = subprocess.run([sys.executable, "-c", FOLD_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": "src"}, cwd=REPO_ROOT)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "fixed_fold_psum invariant over n in (1,2,4,8) float32" in r.stdout
    assert "fixed_fold_psum invariant over n in (1,2,4,8) bfloat16" in r.stdout
    assert "fixed_fold_psum trace audit clean" in r.stdout


@settings(max_examples=10, deadline=None)
@given(v=st.sampled_from([1, 2, 4, 8]), rows=st.integers(1, 6),
       cols=st.sampled_from([1, 3, 32]), bf16=st.booleans(),
       seed=st.integers(0, 2 ** 16))
def test_fixed_fold_matches_sequential_fold(v, rows, cols, bf16, seed):
    """Single-process property: fixed_fold_psum with no axis is exactly the
    strict left fold ((0 + p0) + p1) + … over the virtual-shard axis."""
    import jax
    import jax.numpy as jnp
    from repro.dist import fold

    dt = jnp.bfloat16 if bf16 else jnp.float32
    x = jax.random.uniform(jax.random.PRNGKey(seed), (v, rows, cols),
                           minval=-1e3, maxval=1e3).astype(dt)
    got = np.asarray(fold.fixed_fold_psum(x, None))
    acc = jnp.zeros(x.shape[1:], dt)
    for i in range(v):
        acc = acc + x[i]
    np.testing.assert_array_equal(got, np.asarray(acc))


@settings(max_examples=6, deadline=None)
@given(width=st.sampled_from([16, 32, 64]), bf16=st.booleans(),
       seed=st.integers(0, 2 ** 16))
def test_canonical_row_dot_matches_folded_partials(width, bf16, seed):
    """canonical_row_dot == explicitly folding the per-virtual-shard partial
    products in ascending order (f32 accumulation, cast at the end)."""
    import jax
    import jax.numpy as jnp
    from repro.dist import fold

    dt = jnp.bfloat16 if bf16 else jnp.float32
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    K, N = 4 * width, 24
    x = jax.random.uniform(k1, (2, 5, K), minval=-2, maxval=2).astype(dt)
    w = jax.random.uniform(k2, (K, N), minval=-2, maxval=2).astype(dt)
    got = np.asarray(fold.canonical_row_dot(x, w, width, out_dtype=dt))
    acc = jnp.zeros((2, 5, N), jnp.float32)
    for i in range(4):
        xs = x[..., i * width:(i + 1) * width]
        ws = w[i * width:(i + 1) * width]
        acc = acc + jnp.dot(xs, ws, preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(got, np.asarray(acc.astype(dt)))
