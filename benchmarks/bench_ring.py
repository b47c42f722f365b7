"""Ring-attention fwd/bwd timing: contig vs. zigzag layouts on a forced
8-CPU-device ring (the cross-chip analogue of Figs. 8/9's per-schedule kernel
timing).  Runs in a subprocess so the forced device count never leaks into the
benchmark process; emits CSV rows plus benchmarks/BENCH_ring.json so the perf
trajectory tracks the new repro.dist subsystem.  The child is pinned to
``JAX_PLATFORMS=cpu``: a parent that has touched JAX holds the accelerator,
and these are CPU times, never device metrics.

Expected shape of the result (paper §3.4 economics at CP granularity): under a
causal mask the zigzag/symmetric-shift layout balances every device at (n+1)/2
tiles of work per ring pass, while the contig layout leaves device 0 with one
valid tile and device n-1 with n — the bwd gap is the cross-chip version of
the Fig. 7 makespan gap (on CPU the gap is noisy; the json records it rather
than asserting it).
"""
import json
import os
import subprocess
import sys
import textwrap

ART = os.path.join(os.path.dirname(__file__), "BENCH_ring.json")

SCRIPT = textwrap.dedent("""
    import os, json, time, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro.launch.mesh import auto_mesh
    from repro.dist.ring_attention import ring_attention, zigzag_permutation

    mesh = auto_mesh((8,), ("cp",))
    B, S, H, D = 1, 1024, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in ks)
    perm = zigzag_permutation(S, 8)

    def timed(fn, *args, iters=10):
        fn(*args)                      # compile
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e6

    results = {"platform": jax.devices()[0].platform, "device_count": 8,
               "B": B, "S": S, "H": H, "D": D, "cases": {}}
    for layout in ("contig", "zigzag"):
        qq, kk_, vv, dd = ((x[:, perm] if layout == "zigzag" else x)
                           for x in (q, k, v, do))
        fwd = jax.jit(lambda a, b, c: ring_attention(
            a, b, c, mesh, "cp", causal=True, layout=layout))
        def loss(a, b, c):
            return jnp.sum(ring_attention(a, b, c, mesh, "cp", causal=True,
                                          layout=layout) * dd)
        bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        us_f = timed(fwd, qq, kk_, vv)
        us_b = timed(bwd, qq, kk_, vv)
        results["cases"][f"ring_fwd_causal_{layout}"] = us_f
        results["cases"][f"ring_bwd_causal_{layout}"] = us_b
        print(f"ring_fwd_causal_{layout},{us_f:.0f},S={S}", flush=True)
        print(f"ring_bwd_causal_{layout},{us_b:.0f},S={S}", flush=True)
    fwd_full = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh, "cp",
                                                      causal=False))
    us = timed(fwd_full, q, k, v)
    results["cases"]["ring_fwd_full_contig"] = us
    print(f"ring_fwd_full_contig,{us:.0f},S={S}", flush=True)
    json.dump(results, open(sys.argv[1], "w"), indent=1)
""")


def main() -> None:
    r = subprocess.run([sys.executable, "-c", SCRIPT, ART],
                       capture_output=True, text=True, timeout=1200,
                       env={**os.environ, "PYTHONPATH": "src",
                            "JAX_PLATFORMS": "cpu"},
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    print("# bench_ring: CPU run (JAX_PLATFORMS=cpu, 8 forced host devices);"
          " CPU times, not device metrics", flush=True)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        sys.stderr.write(r.stderr)
        raise RuntimeError("bench_ring subprocess failed")


if __name__ == "__main__":
    main()
