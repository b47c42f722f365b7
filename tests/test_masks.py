"""Mask-spec layer: materialize ↔ block-map ↔ tile_mask consistency, algebra,
hashability/cache-key identity (hypothesis property tests)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.masks import (EMPTY, FULL, PARTIAL, And, Causal, Document, Full,
                         Or, PrefixLM, Sink, SlidingWindow, streaming_mask)


def _specs(s):
    """A deterministic family of specs parameterized by sequence length."""
    return [
        Full(),
        Causal(),
        SlidingWindow(max(1, s // 3)),
        PrefixLM(s // 4),
        Document.from_lengths((s // 3, s - s // 3)),
        Document.from_lengths((s // 4, s // 2, s - s // 4 - s // 2),
                              causal=False),
        streaming_mask(max(1, s // 4), max(1, s // 8)),
        Causal() & PrefixLM(s // 5 + 1),
        SlidingWindow(s // 2 + 1) | (Causal() & Sink(s // 6 + 1)),
    ]


# ------------------------------------------------------------ block map layer
@settings(max_examples=12, deadline=None)
@given(s=st.sampled_from([16, 32, 48]), bq=st.sampled_from([4, 8, 16]),
       bk=st.sampled_from([4, 8, 16]))
def test_block_map_matches_materialize(s, bq, bk):
    """The classifier is exactly the per-tile reduction of the dense mask —
    square token canvas (the kernel contract), rectangular tiles allowed."""
    n_q, n_kv = s // bq, s // bk
    sq = sk = s
    for spec in _specs(s):
        dense = spec.materialize(sq, sk)
        bm = spec.block_map(n_kv, n_q, bq, bk)
        assert bm.shape == (n_kv, n_q)
        for kv in range(n_kv):
            for q in range(n_q):
                tile = dense[q * bq:(q + 1) * bq, kv * bk:(kv + 1) * bk]
                want = (EMPTY if not tile.any()
                        else FULL if tile.all() else PARTIAL)
                assert bm[kv, q] == want, (spec, kv, q)


@settings(max_examples=10, deadline=None)
@given(s=st.integers(16, 64))
def test_tile_mask_agrees_with_mask_fn(s, ):
    """The kernel-facing tile evaluation reproduces the dense reference on
    every tile, including specs that ship token_info tables."""
    for spec in _specs(s):
        dense = spec.materialize(s)
        info = spec.token_info(s)
        info = np.zeros((s,), np.int32) if info is None else info
        b = max(1, s // 4)
        for q0 in range(0, s - s % b, b):
            for k0 in range(0, s - s % b, b):
                rows = q0 + np.arange(b)[:, None] + np.zeros((1, b), np.int64)
                cols = k0 + np.arange(b)[None, :] + np.zeros((b, 1), np.int64)
                got = np.asarray(spec.tile_mask(rows, cols,
                                                info[q0:q0 + b, None],
                                                info[None, k0:k0 + b]), bool)
                np.testing.assert_array_equal(
                    got, dense[q0:q0 + b, k0:k0 + b], err_msg=repr(spec))


# ----------------------------------------------------------------- semantics
def test_atom_semantics():
    s = 12
    c = Causal().materialize(s)
    np.testing.assert_array_equal(c, np.tril(np.ones((s, s), bool)))
    w = SlidingWindow(3).materialize(s)
    assert w[5, 5] and w[5, 4] and w[5, 3] and not w[5, 2] and not w[4, 5]
    p = PrefixLM(4).materialize(s)
    assert p[0, 3] and p[2, 3] and p[6, 3] and p[6, 5] and not p[5, 6]
    snk = Sink(2).materialize(s)
    assert snk[:, :2].all() and not snk[:, 2:].any()
    d = Document.from_lengths((5, 7)).materialize(s)
    assert d[4, 0] and not d[5, 0] and d[11, 5] and not d[4, 5]
    assert not d[0, 4]  # causal inside segments by default


def test_streaming_mask_composition():
    s, w, k = 16, 4, 2
    m = streaming_mask(w, k).materialize(s)
    for q in range(s):
        for j in range(s):
            want = j <= q and (j > q - w or j < k)
            assert m[q, j] == want, (q, j)


def test_and_or_algebra_matches_numpy():
    s = 24
    a, b = SlidingWindow(7), PrefixLM(5)
    np.testing.assert_array_equal((a & b).materialize(s),
                                  a.materialize(s) & b.materialize(s))
    np.testing.assert_array_equal((a | b).materialize(s),
                                  a.materialize(s) | b.materialize(s))


def test_full_row_check_catches_empty_rows():
    # a pure sink mask with n_sink=0 leaves every row empty
    with pytest.raises(ValueError, match="attend to nothing"):
        Sink(0).check(8)
    # ... and the block-map classifier refuses it too
    with pytest.raises(ValueError, match="attend to nothing"):
        Sink(0).block_map(2, 2, 4, 4)
    Causal().check(8)  # fine


def test_document_requires_square_and_matching_length():
    d = Document.from_lengths((4, 4))
    with pytest.raises(AssertionError):
        d.materialize(12)


# ------------------------------------------------------- identity / cache keys
def test_specs_are_hashable_and_keys_distinct():
    """Frozen specs hash; distinct masks with identical *tile counts* still get
    distinct keys — the property the schedule/kernel caches key on."""
    a = SlidingWindow(64)
    b = SlidingWindow(65)
    c = Document.from_lengths((100, 156))
    d = Document.from_lengths((101, 155))
    assert len({a, b, c, d, SlidingWindow(64)}) == 4
    keys = {s.key() for s in (a, b, c, d)}
    assert len(keys) == 4
    assert a.key() == SlidingWindow(64).key()


def test_binary_token_info_conflict_detected():
    d1 = Document.from_lengths((4, 4))
    d2 = Document.from_lengths((3, 5))
    assert (d1 & d1).token_info(8) is not None
    with pytest.raises(AssertionError, match="conflicting token_info"):
        (d1 & d2).token_info(8)
