"""The operation and byte counters against hand-computed values for the
cells' shapes."""
import json
import os

import pytest

from bench import flops
from bench.models import dense_decoder as D

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def dims(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return D.dims(json.load(f))


def test_stablelm_train_step_flops():
    m = dims("stablelm-1.6b-4l")
    # per layer: q,k,v,o 4 * 2048 * 2048 + MLP 3 * 2048 * 5632; head 2048 * 100352
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert flops.matmul_params(m) == 4 * per_layer + 2048 * 100352 == 411041792
    # 6 * 411.0 M * 8192 tokens + 12 * (4096 * 4097 / 2) * 64 * 32 * 2 * 4
    attn = 12 * (4096 * 4097 // 2) * 64 * 32 * 2 * 4
    assert attn == 1649670094848
    want = 6 * 411041792 * 8192 + attn
    assert flops.train_step_flops(m, 2, 4096) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(2.185e13, rel=1e-3)


def test_stablelm_attention_backward_minimum():
    m = dims("stablelm-1.6b-4l")
    f, b = flops.attn_bwd_min(m, 2, 4096)
    # 5 products * 2 FLOPs * 4096 * 4097 / 2 pairs * 64 dims * 64 heads (B*H)
    assert f == 10 * (4096 * 4097 // 2) * 64 * 64 == 343681269760
    # Q, O, dO, dQ, K, V, dK, dV: 8 * 64 * 4096 * 64 bf16 + lse 64 * 4096 f32
    assert b == 8 * 64 * 4096 * 64 * 2 + 64 * 4096 * 4 == 269484032
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.roofline_s(f, b, peaks)
    assert bound == "flops" and t == pytest.approx(343681269760 / 197e12)
