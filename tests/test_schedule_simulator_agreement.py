"""Schedule ↔ simulator agreement sweep (every generator in GENERATORS).

For each generator and a grid of (n, m) — including the even-m requirement of
descending/symmetric_shift — the event-driven simulator's makespan must equal
the paper's closed form, and the schedule must satisfy Schedule.validate().
Also covers the rectangular-grid path through the uniform make_schedule entry
point (n_q forwarding).
"""
import os
import sys

import pytest

from repro.core import schedules as S
from repro.core import simulator as sim
from repro.core.simulator import task_costs

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# compute-bound cost point: every closed form is exact here (descending's
# formula only holds for c >= r — see test_core_schedules for the r > c band).
C, R = 1.0, 0.5

GRID = [(2, 2), (3, 2), (4, 2), (4, 4), (6, 2), (8, 2), (8, 4), (5, 2)]


def _build(name, n, m, causal):
    return S.make_schedule(name, n, n_heads=m, causal=causal)


@pytest.mark.parametrize("n,m", GRID)
@pytest.mark.parametrize("name", sorted(S.GENERATORS))
def test_simulator_matches_closed_form(name, n, m):
    """simulate() == closed_form() on each generator's native mask."""
    causal = name in ("descending", "symmetric_shift")
    sch = _build(name, n, m, causal)
    sch.validate()
    res = sim.simulate(sch, C, R)
    assert res.makespan == pytest.approx(
        sim.closed_form(name, n, m, C, R, causal))


@pytest.mark.parametrize("n,m", GRID)
def test_fa3_causal_closed_form_too(n, m):
    """fa3 also has a causal closed form (same as full — the Fig. 3b bubble)."""
    sch = _build("fa3", n, m, True)
    sch.validate()
    assert sim.simulate(sch, C, R).makespan == pytest.approx(
        sim.closed_form("fa3", n, m, C, R, True))


@pytest.mark.parametrize("m", [1, 2, 3])   # odd m: validity must still hold
def test_odd_m_schedules_remain_valid(m):
    for name in sorted(S.GENERATORS):
        causal = name in ("descending", "symmetric_shift")
        _build(name, 4, m, causal).validate()


# ------------------------------------------------- rectangular grids via n_q
@pytest.mark.parametrize("n,n_q", [(4, 8), (4, 2), (8, 24), (3, 9)])
def test_make_schedule_forwards_n_q(n, n_q):
    """fa3/shift accept rectangular (n_kv × n_q) grids from the uniform entry
    point; the shift optimum T = m·n_q·(c+r) generalizes (workers stay
    conflict-free on distinct Q columns as long as they cycle mod n_q)."""
    for name in ("fa3", "shift"):
        sch = S.make_schedule(name, n, n_heads=2, causal=False, n_q=n_q)
        assert (sch.n_kv, sch.n_q) == (n, n_q)
        sch.validate()
    if n <= n_q:  # distinct Q columns per slot need n workers ≤ n_q columns
        res = sim.simulate(S.make_schedule("shift", n, 2, False, n_q=n_q), C, R)
        assert res.makespan == pytest.approx(2 * n_q * (C + R))


def test_make_schedule_rejects_n_q_on_square_generators():
    with pytest.raises(ValueError):
        S.make_schedule("symmetric_shift", 4, causal=True, n_q=8)
    with pytest.raises(ValueError):
        S.make_schedule("descending", 4, causal=True, n_q=8)
    # n_q == n is the square case and stays accepted
    S.make_schedule("symmetric_shift", 4, n_heads=2, causal=True, n_q=4).validate()


def test_calibration_matches_bench_schedule_sim():
    sys.path.insert(0, REPO_ROOT)
    try:
        from benchmarks.bench_schedule_sim import rc_ratio
    finally:
        sys.path.remove(REPO_ROOT)
    for d in (64, 128):
        c, r = task_costs(128, 128, d)
        assert r / c == pytest.approx(rc_ratio(d, 128))
