"""Determinism + correctness property tests for the paged decode attention.

`repro.kernels.decode.paged_attention` is the serving engine's load-bearing
kernel: its split-KV reduction order is serialized (ascending page-table
position — the decode analogue of ``flash_bwd.serialize_schedule``), so a
query row's output must be

  * numerically equal to the untiled oracle (:mod:`repro.kernels.ref`),
  * **bitwise** stable run-to-run (>= 20 repeats),
  * **bitwise** invariant to page-table permutations (physical placement),
    trailing unallocated pages, and the content of other batch rows.

Property tests go through ``hypothesis``.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings
import hypothesis.strategies as st

from repro.kernels import ref
from repro.kernels.decode import gather_kv, page_reduction_order, paged_attention

D = 16


def build_paged(k, v, page_size, n_extra_pages=0, perm_seed=None):
    """Scatter contiguous (B, S, Hk, D) K/V into page pools + a page table."""
    b, s, hk, d = k.shape
    ppr = -(-s // page_size)                      # pages per row
    n_pages = b * ppr + n_extra_pages
    rng = np.random.RandomState(0 if perm_seed is None else perm_seed)
    phys = np.arange(n_pages) if perm_seed is None else rng.permutation(n_pages)
    k_pages = np.zeros((n_pages, page_size, hk, d), np.float32)
    v_pages = np.zeros((n_pages, page_size, hk, d), np.float32)
    table = np.zeros((b, ppr), np.int32)
    pad = ppr * page_size - s
    kp = np.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = np.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    for i in range(b):
        for j in range(ppr):
            p = phys[i * ppr + j]
            table[i, j] = p
            k_pages[p] = kp[i, j * page_size:(j + 1) * page_size]
            v_pages[p] = vp[i, j * page_size:(j + 1) * page_size]
    return jnp.asarray(k_pages), jnp.asarray(v_pages), jnp.asarray(table)


def rand_qkv(seed, b, s, h, hk):
    rng = np.random.RandomState(seed)
    q = rng.randn(b, 1, h, D).astype(np.float32)
    k = rng.randn(b, s, hk, D).astype(np.float32)
    v = rng.randn(b, s, hk, D).astype(np.float32)
    lens = rng.randint(1, s + 1, size=b)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), lens


def ref_rows(q, k, v, lens):
    """Oracle per row: untiled softmax attention over that row's valid prefix."""
    b, _, h, d = q.shape
    hk = k.shape[2]
    outs = []
    for i in range(b):
        ki = np.repeat(np.asarray(k)[i, :lens[i]], h // hk, axis=1)  # (L, H, D)
        vi = np.repeat(np.asarray(v)[i, :lens[i]], h // hk, axis=1)
        o, _ = ref.mha_fwd(jnp.asarray(q)[i].transpose(1, 0, 2),     # (H, 1, D)
                           jnp.asarray(ki).transpose(1, 0, 2),
                           jnp.asarray(vi).transpose(1, 0, 2))
        outs.append(np.asarray(o).transpose(1, 0, 2))
    return np.stack(outs)                                            # (B,1,H,D)


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10_000), page_size=st.sampled_from([4, 8, 16]),
       gqa=st.booleans())
def test_decode_matches_ref(seed, page_size, gqa):
    """Paged decode == untiled oracle for random lengths / page sizes / GQA."""
    h, hk = 4, (2 if gqa else 4)
    q, k, v, lens = rand_qkv(seed, 3, 24, h, hk)
    kp, vp, tbl = build_paged(k, v, page_size)
    qpos = jnp.asarray(lens - 1, jnp.int32)[:, None]
    out = paged_attention(q, kp, vp, tbl, qpos)
    np.testing.assert_allclose(np.asarray(out), ref_rows(q, k, v, lens),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000), chunk=st.sampled_from([1, 3, 8]))
def test_prefill_rows_match_ref(seed, chunk):
    """Multi-query (chunked-prefill) rows: query at position p attends [0, p]."""
    rng = np.random.RandomState(seed)
    s, h = 16, 4
    q = jnp.asarray(rng.randn(1, chunk, h, D).astype(np.float32))
    k = jnp.asarray(rng.randn(1, s, h, D).astype(np.float32))
    v = jnp.asarray(rng.randn(1, s, h, D).astype(np.float32))
    start = rng.randint(0, s - chunk + 1)
    kp, vp, tbl = build_paged(k, v, page_size=4)
    qpos = jnp.arange(start, start + chunk, dtype=jnp.int32)[None]
    out = np.asarray(paged_attention(q, kp, vp, tbl, qpos))
    for j in range(chunk):
        want = ref_rows(q[:, j:j + 1], k, v, np.asarray([start + j + 1]))
        np.testing.assert_allclose(out[:, j:j + 1], want, rtol=2e-5, atol=2e-5)


def test_page_table_permutation_bitwise():
    """Physical pool placement is unreachable by the math: permuting pages
    (with the table following) leaves the output bitwise unchanged."""
    q, k, v, lens = rand_qkv(0, 3, 24, 4, 4)
    qpos = jnp.asarray(lens - 1, jnp.int32)[:, None]
    base = None
    for perm_seed in (None, 1, 2, 3):
        kp, vp, tbl = build_paged(k, v, 8, n_extra_pages=5, perm_seed=perm_seed)
        out = np.asarray(paged_attention(q, kp, vp, tbl, qpos))
        if base is None:
            base = out
        np.testing.assert_array_equal(base, out)


def test_trailing_pages_bitwise():
    """Extra masked page-table columns accumulate exact float zeros —
    lengthening the serialized reduction changes nothing, bitwise."""
    q, k, v, lens = rand_qkv(1, 3, 24, 4, 2)
    qpos = jnp.asarray(lens - 1, jnp.int32)[:, None]
    kp, vp, tbl = build_paged(k, v, 8, n_extra_pages=4)
    out = np.asarray(paged_attention(q, kp, vp, tbl, qpos))
    # point the extra columns at pages full of garbage: all beyond qpos → masked
    garbage = jnp.asarray(
        np.random.RandomState(9).randint(0, kp.shape[0], size=(3, 6)), jnp.int32)
    tbl_long = jnp.concatenate([tbl, garbage], axis=1)
    out_long = np.asarray(paged_attention(q, kp, vp, tbl_long, qpos))
    np.testing.assert_array_equal(out, out_long)


def test_cobatch_rows_bitwise():
    """Row 0's output is a pure function of row 0's q and pages: overwriting
    every other row's queries, pages, and table leaves it bitwise unchanged."""
    q, k, v, lens = rand_qkv(2, 4, 24, 4, 4)
    qpos = jnp.asarray(lens - 1, jnp.int32)[:, None]
    kp, vp, tbl = build_paged(k, v, 8)
    base = np.asarray(paged_attention(q, kp, vp, tbl, qpos))[0]
    rng = np.random.RandomState(7)
    q2 = np.asarray(q).copy()
    q2[1:] = rng.randn(*q2[1:].shape)
    ppr = tbl.shape[1]
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    kp2[ppr:] = rng.randn(*kp2[ppr:].shape)      # rows 1.. own pages ppr..
    vp2[ppr:] = rng.randn(*vp2[ppr:].shape)
    tbl2 = np.asarray(tbl).copy()
    tbl2[1:] = tbl2[1:][:, ::-1]                  # scramble their tables too
    qpos2 = np.asarray(qpos).copy()
    qpos2[1:] = 5
    out = np.asarray(paged_attention(jnp.asarray(q2), jnp.asarray(kp2),
                                     jnp.asarray(vp2), jnp.asarray(tbl2),
                                     jnp.asarray(qpos2)))[0]
    np.testing.assert_array_equal(base, out)


def test_reduction_order_is_serialized():
    """The published page order is plain ascending data — the contract tests
    (and docs) can state it without reading kernel internals."""
    order = page_reduction_order(7)
    np.testing.assert_array_equal(order, np.arange(7, dtype=np.int32))


def test_gather_kv_roundtrip():
    q, k, v, lens = rand_qkv(3, 3, 24, 4, 4)
    kp, vp, tbl = build_paged(k, v, 8, perm_seed=11)
    np.testing.assert_array_equal(np.asarray(gather_kv(kp, tbl, 24)),
                                  np.asarray(k))


@pytest.mark.slow
def test_run_to_run_bitwise_20_reps():
    """>= 20 repeats (fresh device arrays each time) are bitwise identical,
    greedy path and permuted-pool path alike."""
    q, k, v, lens = rand_qkv(4, 3, 24, 4, 2)
    qpos = jnp.asarray(lens - 1, jnp.int32)[:, None]
    base = None
    for rep in range(20):
        perm = (rep % 5) if rep % 5 else None     # rotate pool placements too
        kp, vp, tbl = build_paged(k, v, 8, perm_seed=perm)
        out = np.asarray(paged_attention(jnp.asarray(np.asarray(q)), kp, vp,
                                         tbl, qpos))
        if base is None:
            base = out
        np.testing.assert_array_equal(base, out)
