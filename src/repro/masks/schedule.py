"""Compile a block-sparse mask into a deterministic DASH schedule.

Generalizes the registry generators (:mod:`repro.core.schedules`) from
rectangular/triangular cell sets to **ragged per-column cell lists**: the cells
are whatever the mask's block map keeps (non-EMPTY tiles), each surviving KV
row becomes one worker (preserving the paper's §3.1 row-ownership constraint —
dK/dV stay accumulator-resident), and the per-(head, q) reduction order is
derived from the placement's execution slots.

Placements
----------
``shift`` (default) — generalized shift placement. Each worker's valid q list
  is rotated by a greedily chosen offset so that, at any execution slot, as few
  workers as possible occupy the same q column. Deterministic: workers are
  processed in ascending KV-row order and the earliest rotation with the fewest
  collisions wins. On a full mask this recovers the paper's shift schedule
  (worker *i* starts at column *i*); on a block-diagonal document mask it
  recovers shift per document block.

``fa3`` — the FlashAttention-3-style baseline: every worker walks its valid q
  list ascending from the start, reductions ordered by ascending KV row. On
  ragged columns whose heights stack (documents, prefix-LM) this serializes the
  column head exactly like the paper's Fig. 3 startup cascade.

Optimality (the generalized Lemma-1 argument). With unit-cost slots
(compute ``c`` then reduction ``r`` per task) every schedule's makespan is
lower-bounded by ``max_chain · (c + r)`` (some worker must run its whole row
back to back), by ``c + h·r`` for the tallest column height ``h`` (a column's
reductions are serialized), and by ``work / n_workers``
(:func:`repro.core.simulator.ragged_lower_bound`).  If the shift placement
finds a **collision-free** rotation assignment — every (slot, column) pair
used at most once — then each reduction's predecessor in its column finished
a full slot earlier, no task ever stalls, and the simulated makespan equals
``max_chain · (c + r)``: the lower bound, hence the minimum.  The placement's
dependency edges are then depth-monotone, so DAG critical path and simulator
agree (Lemma 1); both are asserted by the tests and the CI golden check.

Deadlock-freedom (any collision count): the reduction order of every column is
sorted by ``(slot, worker)``; chain edges increase ``slot`` and reduction edges
increase ``(slot, worker)`` lexicographically, so the union of both orders is
acyclic — the simulator can always make progress.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import numpy as np

from repro.core.schedules import SCHEDULE_CACHE_MAXSIZE, Schedule
from repro.masks.spec import EMPTY, PARTIAL, MaskSpec

PLACEMENTS = ("shift", "fa3")


def ragged_columns(cells) -> Dict[int, List[int]]:
    """Per-q-column ragged KV lists — the generalization of
    ``core.schedules._columns`` to arbitrary cell sets."""
    cols: Dict[int, List[int]] = {}
    for kv, q in cells:
        cols.setdefault(q, []).append(kv)
    return {q: sorted(kvs) for q, kvs in cols.items()}


def _shift_orders(rows: List[int], row_qs: Dict[int, List[int]],
                  n_q: int) -> Dict[int, List[int]]:
    """Greedy rotation per worker minimizing (slot, column) collisions.

    Vectorized: per worker, all L rotations are scored in one numpy fancy
    lookup against the (slot, column) occupancy table — O(L²) array ops per
    worker instead of O(L²) python set probes, which matters at hundreds of
    tiles (long-context prefix/full-ish masks). ``argmin`` picks the earliest
    minimal-collision offset, the same deterministic choice as a sequential
    scan with first-zero early exit.
    """
    max_slots = max((len(row_qs[kv]) for kv in rows), default=0)
    occupancy = np.zeros((max_slots, n_q), bool)
    orders: Dict[int, List[int]] = {}
    for kv in rows:
        qs = np.asarray(row_qs[kv], np.int64)
        L = len(qs)
        rot_idx = (np.arange(L)[:, None] + np.arange(L)[None, :]) % L
        rotations = qs[rot_idx]                     # (offset, slot) -> column
        colls = occupancy[np.arange(L)[None, :], rotations].sum(axis=1)
        rot = rotations[int(np.argmin(colls))]
        occupancy[np.arange(L), rot] = True
        orders[kv] = rot.tolist()
    return orders


def compile_block_schedule(mask: MaskSpec, n_kv: int, n_q: int,
                           block_q: int = 128, block_k: int = 128,
                           placement: str = "shift") -> Schedule:
    """Compile ``mask``'s block map into a single-head ragged Schedule.

    The result drives both kernel realizations (the ``bh`` grid axis covers
    batch·heads, so kernels always consume head-0 chains) and the simulator /
    DAG model. ``Schedule.cells`` records the ragged cell set,
    ``Schedule.partial_cells`` the tiles the kernels must mask-multiply, and
    ``Schedule.mask_key`` pins the schedule to its mask spec so kernel-side
    assertions catch schedule/mask mismatches.
    """
    if placement not in PLACEMENTS:
        raise KeyError(f"unknown placement {placement!r}; "
                       f"available: {PLACEMENTS}")
    bm = mask.block_map(n_kv, n_q, block_q, block_k)
    cells = tuple((kv, q) for kv in range(n_kv) for q in range(n_q)
                  if bm[kv, q] != EMPTY)
    partial = tuple((kv, q) for kv, q in cells if bm[kv, q] == PARTIAL)
    cols = ragged_columns(cells)
    missing = [q for q in range(n_q) if q not in cols]
    assert not missing, (
        f"q tiles {missing} have no visible KV tile — the mask leaves those "
        "query rows attending to nothing")
    rows = sorted({kv for kv, _ in cells})
    row_qs = {kv: sorted(q for r, q in cells if r == kv) for kv in rows}

    if placement == "shift":
        orders = _shift_orders(rows, row_qs, n_q)
    else:  # fa3-style ascending walk
        orders = {kv: row_qs[kv] for kv in rows}

    chains: List[Tuple] = []
    slot_of: Dict[Tuple[int, int], int] = {}
    worker_of: Dict[int, int] = {}
    for w, kv in enumerate(rows):
        worker_of[kv] = w
        chains.append(tuple((0, kv, q) for q in orders[kv]))
        for t, q in enumerate(orders[kv]):
            slot_of[(kv, q)] = t

    red: Dict[Tuple[int, int], Tuple[Tuple[int, int], ...]] = {}
    for q, kvs in cols.items():
        if placement == "shift":
            # by execution slot; ties broken by worker — provably acyclic
            order = sorted(kvs, key=lambda kv: (slot_of[(kv, q)],
                                                worker_of[kv]))
        else:
            order = kvs  # ascending KV row, the fa3 convention
        red[(0, q)] = tuple((kv, worker_of[kv]) for kv in order)

    sch = Schedule(f"block_{placement}", False, len(rows), n_kv, n_q, 1,
                   tuple(chains), red, cells=cells, partial_cells=partial,
                   mask_key=mask.key())
    sch.validate()
    return sch


@functools.lru_cache(maxsize=SCHEDULE_CACHE_MAXSIZE)
def _cached_block_schedule(mask, n_kv, n_q, block_q, block_k, placement):
    return compile_block_schedule(mask, n_kv, n_q, block_q, block_k, placement)


def cached_block_schedule(mask: MaskSpec, n_kv: int, n_q: int,
                          block_q: int = 128, block_k: int = 128,
                          placement: str = "shift") -> Schedule:
    """Memoized :func:`compile_block_schedule`. The lru key includes the mask
    spec itself (hashable by construction), so two distinct masks with equal
    tile counts can never collide — the failure mode the old
    ``(name, n, n_heads, causal, n_q)`` key space allowed.

    The lru bound is :data:`repro.core.schedules.SCHEDULE_CACHE_MAXSIZE`;
    hit/miss counters surface through ``repro.masks.cache_info()``."""
    return _cached_block_schedule(mask, n_kv, n_q, block_q, block_k, placement)


# lru introspection for repro.masks.cache_info() / tests
cached_block_schedule.cache_info = _cached_block_schedule.cache_info
cached_block_schedule.cache_clear = _cached_block_schedule.cache_clear
