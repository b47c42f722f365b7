"""Pallas TPU kernels: flash attention forward + DASH-scheduled deterministic
backward (scalar-prefetch grid order = the paper's SM schedule). ops.py is the
jit'd custom_vjp wrapper; ref.py the pure-jnp oracle; vmem.py the footprint
accounting. Tested in interpret mode on CPU; on a TPU v5e chip the forward and
both backward realizations match ref.py in f32 at stablelm widths and agree
bitwise with each other (chip_smoke.py).

decode.py is the serving-side sibling: batch-invariant paged split-KV
attention whose page reduction order is serialized (ascending page-table
position) the same way flash_bwd serializes the dQ accumulation order."""
