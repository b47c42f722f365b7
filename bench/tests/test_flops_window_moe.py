"""Counts of ``bench/flops_window_moe.py`` at the Mellum2 cell's shapes."""
import json
import os

from bench import flops_window_moe as F

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg():
    with open(os.path.join(BENCH, "configs",
                           "mellum2-12b-a2.5b-4l-ep8.json")) as f:
        return json.load(f)


def test_attended_pairs():
    assert F.attended_pairs(8192, 1024) == 7_864_832
    assert F.attended_pairs(8192) == 33_558_528
    assert F.attended_pairs(64, 16) == sum(min(q + 1, 16) for q in range(64))
    assert F.layer_pairs(cfg(), 8192) == [7_864_832] * 3 + [33_558_528]


def test_step_counts():
    c = cfg()
    # each held expert sees 2,048 of a step's 16,384 tokens at the even share
    assert F.routed_slots(c, 16_384) == 8 * 2_048
    assert F.expert_params(c) == 3 * 2304 * 896
    attn = 21_233_664 + 2304 * 64          # projections and router a layer
    assert F.dense_params(c) == 4 * attn + 2304 * 12_288
    flops, nbytes = F.experts_min(c, 16_384)
    assert flops == 6 * 3 * 2304 * 896 * 16_384 * 4
    assert flops / nbytes > 240            # FLOP-bound on a v5e
