"""Block-sparse schedule compiler: validity on ragged cell sets, shift-placement
optimality (simulator == DAG critical path == lower bound), deadlock freedom,
cache-key isolation, and the ragged worker_chains/sentinel regression."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dag as dag_mod
from repro.core import simulator as sim
from repro.core.schedules import Schedule, cached_schedule, make_schedule
from repro.masks import (Causal, Document, Full, PrefixLM, SlidingWindow,
                         cache_info, compile_block_schedule, ragged_columns,
                         streaming_mask)

C, R = 1.0, 0.5


def _mask_cases(n, blk):
    s = n * blk
    return [
        ("window", SlidingWindow(max(1, s // 3))),
        ("prefix", PrefixLM(s // 3)),
        ("document", Document.from_lengths((s // 4, s // 2,
                                            s - s // 4 - s // 2))),
        ("streaming", streaming_mask(max(1, s // 4), blk)),
    ]


# ----------------------------------------------------------------- validity
@settings(max_examples=10, deadline=None)
@given(n=st.integers(3, 10), blk=st.sampled_from([4, 8]))
def test_compiled_schedules_validate(n, blk):
    for _, mask in _mask_cases(n, blk):
        for placement in ("shift", "fa3"):
            sch = compile_block_schedule(mask, n, n, blk, blk,
                                        placement=placement)
            sch.validate()   # exact cover of cells + contiguity + reductions
            assert sch.mask_key == mask.key()
            # every chain is one KV row (the §3.1 ownership constraint)
            for chain in sch.chains:
                assert len({kv for (_h, kv, _q) in chain}) == 1


def test_ragged_columns_generalizes_columns():
    cells = [(0, 0), (0, 1), (2, 1), (2, 2)]
    assert ragged_columns(cells) == {0: [0], 1: [0, 2], 2: [2]}


# ------------------------------------------- optimality / simulator agreement
@settings(max_examples=10, deadline=None)
@given(n=st.integers(3, 10), blk=st.sampled_from([4, 8]),
       c=st.floats(0.5, 2.0), r=st.floats(0.2, 1.0))
def test_shift_placement_hits_lower_bound(n, blk, c, r):
    """Shift placement is collision-free on the window/document/streaming/
    prefix families ⇒ zero stalls ⇒ makespan == the ragged lower bound ==
    the DAG critical path (the generalized Lemma-1 optimality certificate)."""
    for name, mask in _mask_cases(n, blk):
        sch = compile_block_schedule(mask, n, n, blk, blk)
        res = sim.simulate(sch, c, r)
        lb = sim.ragged_lower_bound(sch, c, r)
        assert res.makespan >= lb - 1e-9
        assert res.makespan == pytest.approx(lb), (name, n, blk)
        d = dag_mod.build_dag(sch, c, r)
        assert d.lemma1_monotone(), name
        assert res.makespan == pytest.approx(d.critical_path(True)), name


@settings(max_examples=8, deadline=None)
@given(n=st.integers(3, 10), blk=st.sampled_from([4, 8]))
def test_fa3_placement_never_beats_shift_and_never_deadlocks(n, blk):
    """The ascending baseline simulates fine (deadlock-free reduction orders)
    but can only stall more than shift."""
    for name, mask in _mask_cases(n, blk):
        shift_t = sim.simulate(compile_block_schedule(mask, n, n, blk, blk),
                               C, R).makespan
        fa3_t = sim.simulate(compile_block_schedule(mask, n, n, blk, blk,
                                                    placement="fa3"),
                             C, R).makespan
        assert fa3_t >= shift_t - 1e-9, name


def test_shift_strictly_beats_fa3_on_stacked_columns():
    """Document and prefix masks stack full-height columns — the fa3 walk
    serializes their heads (the Fig. 3 cascade); shift staggers them."""
    s, blk = 32, 4
    for mask in (Document.from_lengths((12, 20)), PrefixLM(12)):
        n = s // blk
        shift_t = sim.simulate(compile_block_schedule(mask, n, n, blk, blk),
                               C, R).makespan
        fa3_t = sim.simulate(compile_block_schedule(mask, n, n, blk, blk,
                                                    placement="fa3"),
                             C, R).makespan
        assert shift_t < fa3_t, mask.key()


def test_full_mask_recovers_paper_shift():
    """compile(Full) ≡ the paper's shift schedule: worker i starts at column
    i and cycles; makespan = n·(c+r), the full-mask optimum."""
    n, blk = 6, 4
    sch = compile_block_schedule(Full(), n, n, blk, blk)
    for w, chain in enumerate(sch.chains):
        assert [q for (_h, _kv, q) in chain] == [(w + t) % n for t in range(n)]
    assert sim.simulate(sch, C, R).makespan == pytest.approx(n * (C + R))


def test_empty_kv_rows_are_dropped_from_workers():
    """A window mask's far-past KV rows own zero tiles: they get no worker
    (the kernel zeroes their dk/dv instead)."""
    s, blk = 64, 4           # window 8 tokens on 16 tiles
    n = s // blk
    sch = compile_block_schedule(SlidingWindow(8), n, n, blk, blk)
    assert sch.n_workers == n  # causal window: every row keeps its diagonal
    # a non-causal document pair mask with padding rows dropped:
    doc = Document.from_lengths((32, 32))
    sch2 = compile_block_schedule(doc & SlidingWindow(8), n, n, blk, blk)
    assert sch2.n_workers == n
    sch2.validate()


# ------------------------------------------------------- cache-key isolation
def test_cached_schedule_key_includes_mask():
    """Two distinct masks with identical tile counts must get distinct cached
    schedules — the old (name, n, n_heads, causal, n_q) key space collided."""
    a = cached_schedule("shift", 4, mask=SlidingWindow(40), block_q=16,
                        block_k=16)
    b = cached_schedule("shift", 4, mask=SlidingWindow(41), block_q=16,
                        block_k=16)
    # the two windows classify the same tiles PARTIAL at this block size —
    # precisely the collision the spec-hash key must prevent
    assert a.mask_key != b.mask_key
    assert a is not b
    # same mask → the very same memoized instance (shared derived arrays)
    assert cached_schedule("shift", 4, mask=SlidingWindow(40), block_q=16,
                           block_k=16) is a


def test_make_schedule_rejects_pairing_generators_for_masks():
    with pytest.raises(ValueError, match="placements"):
        make_schedule("symmetric_shift", 4, mask=SlidingWindow(16),
                      block_q=16, block_k=16)


# --------------------------------------- ragged worker_chains / validate fix
def test_worker_chains_ragged_sentinels():
    """Regression (ISSUE 5 satellite): padded per-worker arrays must stay
    correct when chain lengths differ wildly — sentinels repeat each worker's
    *own* last task, valid flags cover exactly the cell set, and visited
    matches the ragged columns."""
    s, blk = 64, 4
    n = s // blk
    mask = Document.from_lengths((8, 40, 16))  # chain lengths 2,1,10,...,4...
    sch = compile_block_schedule(mask, n, n, blk, blk)
    wc = sch.worker_chains()
    kv_ids, q_ids, valid = wc["kv_ids"], wc["q_ids"], wc["valid"]
    assert wc["single_visit"]  # one row per worker ⇒ at most one visit per col
    lens = [len(c) for c in sch.chains]
    assert kv_ids.shape == (sch.n_workers, max(lens))
    par_tasks = sorted((int(kv_ids[w, t]), int(q_ids[w, t]))
                       for w in range(kv_ids.shape[0])
                       for t in range(kv_ids.shape[1]) if valid[w, t])
    assert par_tasks == sorted(sch.cells)
    for w, chain in enumerate(sch.chains):
        ln = len(chain)
        assert valid[w, :ln].all() and not valid[w, ln:].any()
        # sentinel tail repeats this worker's own last (kv, q)
        assert (kv_ids[w, ln:] == kv_ids[w, ln - 1]).all()
        assert (q_ids[w, ln:] == q_ids[w, ln - 1]).all()
        touched = {q for (_h, _kv, q) in chain}
        assert {q for q in range(sch.n_q) if wc["visited"][w, q]} == touched


def test_validate_catches_ragged_violations():
    """validate() must work from the explicit cell set, not the causal flag."""
    mask = SlidingWindow(16)
    sch = compile_block_schedule(mask, 4, 4, 8, 8)
    sch.validate()
    # drop one task from a chain → cover violation
    broken = Schedule(sch.name, sch.causal, sch.n_workers, sch.n_kv, sch.n_q,
                      sch.n_heads, tuple(c[:-1] if i == 0 else c
                                         for i, c in enumerate(sch.chains)),
                      sch.reduction_order, cells=sch.cells,
                      partial_cells=sch.partial_cells, mask_key=sch.mask_key)
    with pytest.raises(AssertionError):
        broken.validate()
    # reduction order for a column the mask leaves EMPTY → key mismatch
    extra = dict(sch.reduction_order)
    extra[(0, 999)] = ((0, 0),)
    broken2 = Schedule(sch.name, sch.causal, sch.n_workers, sch.n_kv, sch.n_q,
                       sch.n_heads, sch.chains, extra, cells=sch.cells,
                       partial_cells=sch.partial_cells, mask_key=sch.mask_key)
    with pytest.raises(AssertionError, match="reduction orders"):
        broken2.validate()


def test_masks_cache_info_exposed():
    info = cache_info()
    assert set(info) == {"cached_schedule", "cached_block_schedule",
                         "block_map"}
    for stats in info.values():
        assert {"hits", "misses", "maxsize", "currsize"} <= set(stats)
        assert stats["maxsize"] is not None      # explicit bound, never inf
