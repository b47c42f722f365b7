"""Native GQA through the DASH kernel stack: no KV repetition anywhere.

Covers (ISSUE 3): grad parity vs kernels/ref for group sizes 1/2/8 in interpret
mode; jaxpr/shape inspection proving the Pallas calls consume (B·Hk, S, D) K/V
(never a repeated (B·H, S, D) copy); the ascending-query-head dK/dV fold; and
the up-front group-divisibility validation in ``attention(...)``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from repro.core.schedules import cached_schedule, make_schedule
from repro.kernels import ref
from repro.kernels.flash_bwd import flash_bwd
from repro.kernels.flash_fwd import flash_fwd
from repro.kernels.gqa import kv_head_index, validate_group
from repro.kernels.ops import attention, dash_attention, xla_attention

B, S, D = 1, 256, 64


def _rand(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _qkvdo(h, hk):
    return (_rand((B, h, S, D), 0), _rand((B, hk, S, D), 1),
            _rand((B, hk, S, D), 2), _rand((B, h, S, D), 3))


@pytest.mark.parametrize("group", [1, 2, 8])
@pytest.mark.parametrize("causal", [False, True])
def test_gqa_grad_parity_vs_ref(group, causal):
    """dash_attention grads vs the kernels/ref vjp oracle run on explicitly
    repeated K/V (dk/dv reduced over each group) — group sizes 1/2/8."""
    h = 8
    hk = h // group
    q, k, v, do = _qkvdo(h, hk)
    f = functools.partial(dash_attention, causal=causal, interpret=True)
    out, pull = jax.vjp(f, q, k, v)
    dq, dk, dv = pull(do)
    assert dk.shape == (B, hk, S, D) and dv.shape == (B, hk, S, D)

    krep = jnp.repeat(k, group, axis=1).reshape(B * h, S, D)
    vrep = jnp.repeat(v, group, axis=1).reshape(B * h, S, D)
    rdq, rdk, rdv = ref.vjp_oracle(q.reshape(B * h, S, D), krep, vrep,
                                   do.reshape(B * h, S, D), causal=causal)
    rout, _ = ref.mha_fwd(q.reshape(B * h, S, D), krep, vrep, causal=causal)
    np.testing.assert_allclose(np.asarray(out).reshape(B * h, S, D),
                               np.asarray(rout), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(dq).reshape(B * h, S, D),
                               np.asarray(rdq), atol=5e-5, rtol=5e-5)
    for got, want, nm in ((dk, rdk, "dk"), (dv, rdv, "dv")):
        want_grouped = np.asarray(want).reshape(B, hk, group, S, D).sum(2)
        np.testing.assert_allclose(np.asarray(got), want_grouped,
                                   atol=1e-4, rtol=5e-5, err_msg=nm)


def _collect_pallas_eqns(jaxpr, acc):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            acc.append(eqn)
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                _collect_pallas_eqns(sub, acc)
    return acc


def _subjaxprs(val):
    if isinstance(val, jex_core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jex_core.Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for item in val:
            yield from _subjaxprs(item)


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_kernels_allocate_no_repeated_kv(causal):
    """jaxpr inspection: every Pallas call reads K/V at (B·Hk, S, D); the
    repeated (B·H, S, D) K/V copy of the old path never exists."""
    h, hk = 8, 2
    q, k, v, do = _qkvdo(h, hk)
    f = functools.partial(dash_attention, causal=causal, interpret=True)

    def fwd_and_grads(q_, k_, v_):
        out, pull = jax.vjp(f, q_, k_, v_)
        return out, pull(do)

    jaxpr = jax.make_jaxpr(fwd_and_grads)(q, k, v)
    eqns = _collect_pallas_eqns(jaxpr.jaxpr, [])
    assert eqns, "no pallas_call found"
    kv_shape, q_shape = (B * hk, S, D), (B * h, S, D)
    attn_eqns = 0
    for eqn in eqns:
        shapes = [tuple(x.aval.shape) for x in eqn.invars]
        if kv_shape in shapes:
            attn_eqns += 1
            # exactly k and v at Hk heads; q/do/out at H heads are distinct
            assert shapes.count(kv_shape) == 2, shapes
    # both the forward and the backward attention kernels consume native KV
    assert attn_eqns >= 2, [e.primitive.name for e in eqns]
    # and no equation anywhere materializes a repeated KV-sized array from a
    # KV-headed input (the old jnp.repeat lowering)
    for eqn in _all_eqns(jaxpr.jaxpr, []):
        in_shapes = {tuple(x.aval.shape) for x in eqn.invars
                     if hasattr(x, "aval")}
        out_shapes = {tuple(x.aval.shape) for x in eqn.outvars}
        assert not ((B, hk, S, D) in in_shapes and (B, h, S, D) in out_shapes
                    and eqn.primitive.name in ("gather", "broadcast_in_dim",
                                               "concatenate")), eqn

def _all_eqns(jaxpr, acc):
    for eqn in jaxpr.eqns:
        acc.append(eqn)
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                _all_eqns(sub, acc)
    return acc


def test_flash_fwd_gqa_bitwise_matches_repeated():
    """Per-pane compute is untouched by the KV index mapping: grouped flash_fwd
    == flash_fwd on explicitly repeated KV, bit for bit."""
    h, hk = 4, 2
    q, k, v, _ = _qkvdo(h, hk)
    out_g, lse_g = flash_fwd(q.reshape(B * h, S, D), k.reshape(B * hk, S, D),
                             v.reshape(B * hk, S, D), causal=True,
                             interpret=True, n_heads=h, n_kv_heads=hk)
    krep = jnp.repeat(k, h // hk, axis=1).reshape(B * h, S, D)
    vrep = jnp.repeat(v, h // hk, axis=1).reshape(B * h, S, D)
    out_r, lse_r = flash_fwd(q.reshape(B * h, S, D), krep, vrep, causal=True,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(out_g), np.asarray(out_r))
    np.testing.assert_array_equal(np.asarray(lse_g), np.asarray(lse_r))


def test_flash_bwd_gqa_fold_is_ascending_query_head_order():
    """dK/dV of the native path == left fold (ascending query head) of the
    per-query-head grads from the repeated-KV path — bitwise."""
    h, hk = 4, 2
    g = h // hk
    q, k, v, do = _qkvdo(h, hk)
    qf, dof = q.reshape(B * h, S, D), do.reshape(B * h, S, D)
    krep = jnp.repeat(k, g, axis=1).reshape(B * h, S, D)
    vrep = jnp.repeat(v, g, axis=1).reshape(B * h, S, D)
    out, lse = flash_fwd(qf, krep, vrep, causal=True, interpret=True)
    sch = make_schedule("symmetric_shift", S // 128, 1, True)
    _, dk_g, dv_g = flash_bwd(qf, k.reshape(B * hk, S, D),
                              v.reshape(B * hk, S, D), out, lse, dof, sch,
                              causal=True, interpret=True, n_heads=h,
                              n_kv_heads=hk)
    _, dk_r, dv_r = flash_bwd(qf, krep, vrep, out, lse, dof, sch, causal=True,
                              interpret=True)
    for got, per_head in ((dk_g, dk_r), (dv_g, dv_r)):
        part = np.asarray(per_head).reshape(B * hk, g, S, D)
        want = part[:, 0].copy()
        for j in range(1, g):
            want = want + part[:, j]
        np.testing.assert_array_equal(np.asarray(got), want)


def test_xla_gqa_chunked_matches_unchunked():
    h, hk = 8, 2
    q, k, v, _ = _qkvdo(h, hk)
    full = xla_attention(q, k, v, causal=True)
    chunked = xla_attention(q, k, v, causal=True, chunk_q=64)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunked),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("hk", [2, 8])
def test_xla_chunked_rect_causal_end_aligned(hk):
    """sq < sk causal: the chunked scan must use the same end-aligned mask
    convention as the unchunked paths (query i sees keys ≤ i + sk - sq)."""
    h, sq, sk = 8, 64, 256
    q = _rand((B, h, sq, D), 0)
    k = _rand((B, hk, sk, D), 1)
    v = _rand((B, hk, sk, D), 2)
    full = xla_attention(q, k, v, causal=True)
    chunked = xla_attention(q, k, v, causal=True, chunk_q=16)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunked),
                               atol=2e-5, rtol=2e-5)


def test_group_divisibility_validated_up_front():
    """h % hk != 0 must fail immediately with an error naming n_kv_heads."""
    q = _rand((B, 6, S, D), 0)
    k = _rand((B, 4, S, D), 1)
    for fn in (lambda: attention(q, k, k, impl="xla"),
               lambda: attention(q, k, k, impl="pallas", interpret=True),
               lambda: dash_attention(q, k, k, interpret=True)):
        with pytest.raises(ValueError, match="n_kv_heads"):
            fn()
    assert validate_group(8, 2) == 4
    assert kv_head_index(5, 8, 2) == 1  # batch 0, head 5 -> kv head 1


def test_schedule_construction_is_cached():
    """ops._bwd_rule path: one Schedule instance per key, derived kernel arrays
    memoized on it (no per-trace reconstruction)."""
    a = cached_schedule("symmetric_shift", 4, n_heads=1, causal=True)
    b = cached_schedule("symmetric_shift", 4, n_heads=1, causal=True)
    assert a is b
    wc1 = a.worker_chains()
    wc2 = b.worker_chains()
    assert wc1 is wc2
    assert cached_schedule("fa3", 4) is not a
