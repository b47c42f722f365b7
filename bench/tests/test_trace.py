"""The trace reduction: interval arithmetic by hand, then a small trace
recorded on a TPU v5e (``data/small.xplane.pb``: three calls of a jitted
matmul, tanh and Pallas kernel, each inside ``bench.step_dispatch`` and
``bench.sync`` spans, in a ``bench.window``)."""
import os

import pytest

from bench import trace as TR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")


def test_union_busy_and_gaps():
    ops = [("a", 10, 20), ("b", 15, 30), ("c", 40, 50), ("d", 45, 48)]
    assert TR.union(ops) == [(10, 30), (40, 50)]
    assert TR.busy_ns(ops) == 30
    assert TR.gaps(ops, (0, 60)) == [(0, 10), (30, 40), (50, 60)]
    assert TR.gaps(ops, (12, 45)) == [(30, 40)]
    assert TR.clip(ops, (12, 45)) == [("a", 12, 20), ("b", 15, 30),
                                      ("c", 40, 45)]


def test_matching_and_host_tags():
    ops = [("fusion.1", 0, 5), ("_bwd_kernel", 5, 9),
           ("_worker_bwd_kernel", 9, 20)]
    assert TR.time_matching(ops, [r"^_worker_bwd_kernel$"]) == (11, 1)
    assert TR.time_matching(ops, [r"bwd_kernel"]) == (15, 2)
    host = [("bench.window", 0, 100), ("bench.step_dispatch", 0, 50),
            ("bench.data", 10, 20)]
    assert TR.host_span_at(host, 15) == "bench.data"
    assert TR.host_span_at(host, 30) == "bench.step_dispatch"
    assert TR.host_span_at(host, 70) == "(no benchmark span)"


def test_recorded_tpu_trace():
    tr = TR.read(SMALL)
    assert 0 in tr.ops and tr.ops[0]
    busy_s, window_s = TR.device_busy(tr)
    assert 0 < busy_s < window_s
    names = [n for n, _ in TR.top_ops(tr)]
    assert names
    gaps = TR.longest_gaps(tr)
    assert gaps and all(s > 0 for _, s in gaps)
    assert sum(s for _, s in gaps) + busy_s == pytest.approx(window_s, rel=1e-6)
    assert {n for n, _ in gaps} <= {"bench.step_dispatch", "bench.sync",
                                    "(no benchmark span)"}
