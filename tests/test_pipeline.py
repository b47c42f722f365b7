"""Pipeline-parallel (GPipe/shard_map) tests on a forced 4-device stage mesh."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.dist.pipeline import bubble_fraction

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import auto_mesh
    from repro.dist.pipeline import pipeline_apply

    mesh = auto_mesh((4,), ("stage",))
    S, B, D = 4, 8, 32
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (S, D, D)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    # sequential reference
    ref = x
    for s in range(S):
        ref = stage_fn(ws[s], ref)

    for n_micro in (4, 8):
        y = pipeline_apply(stage_fn, ws, x, mesh, "stage", n_micro=n_micro)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
    print("pipeline forward OK")

    # gradients through the pipeline == sequential gradients
    def loss_pp(ws_, x_):
        return jnp.sum(pipeline_apply(stage_fn, ws_, x_, mesh, "stage", 4) ** 2)

    def loss_seq(ws_, x_):
        h = x_
        for s in range(S):
            h = stage_fn(ws_[s], h)
        return jnp.sum(h ** 2)

    g_pp = jax.jit(jax.grad(loss_pp))(ws, x)
    g_seq = jax.grad(loss_seq)(ws, x)
    np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_seq),
                               atol=1e-4, rtol=1e-4)
    print("pipeline grads OK")

    # determinism + collective structure
    y1 = jax.jit(lambda w, z: pipeline_apply(stage_fn, w, z, mesh, "stage", 4))(ws, x)
    y2 = jax.jit(lambda w, z: pipeline_apply(stage_fn, w, z, mesh, "stage", 4))(ws, x)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    txt = jax.jit(lambda w, z: pipeline_apply(stage_fn, w, z, mesh, "stage", 4)) \\
        .lower(ws, x).compile().as_text()
    assert "collective-permute" in txt
    print("pipeline determinism + ppermute OK")
""")


def test_pipeline_parallel_multidevice():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": "src"}, cwd=REPO_ROOT)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    for line in ("pipeline forward OK", "pipeline grads OK",
                 "pipeline determinism + ppermute OK"):
        assert line in r.stdout


def test_bubble_fraction_formula():
    """The GPipe bubble is the §3.2 startup term of the pipeline DAG: (S-1)/T."""
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert bubble_fraction(4, 32) == pytest.approx(3 / 35)
    assert bubble_fraction(1, 8) == 0.0
