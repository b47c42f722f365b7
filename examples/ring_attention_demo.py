"""Cross-chip DASH: ring attention with shift/zigzag schedules on 8 forced CPU
devices (subprocess-free version of tests/test_ring_attention.py).

    PYTHONPATH=src python examples/ring_attention_demo.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.ring_attention import (ring_attention, zigzag_inverse,
                                       zigzag_permutation)
from repro.kernels.ops import xla_attention
from repro.launch.mesh import auto_mesh


def main():
    mesh = auto_mesh((8,), ("cp",))
    B, S, H, D = 2, 512, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in ks)

    def ref(causal):
        return jnp.swapaxes(xla_attention(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), causal), 1, 2)

    out_full = ring_attention(q, k, v, mesh, "cp", causal=False)
    print("full-mask shift-ring max err:",
          float(jnp.max(jnp.abs(out_full - ref(False)))))

    perm, inv = zigzag_permutation(S, 8), zigzag_inverse(S, 8)
    out_z = ring_attention(q[:, perm], k[:, perm], v[:, perm], mesh, "cp",
                           causal=True)[:, inv]
    print("causal zigzag (symmetric-shift) ring max err:",
          float(jnp.max(jnp.abs(out_z - ref(True)))))


if __name__ == "__main__":
    main()
