"""The per-layer metric readers on a made-up trace whose answers are known
by hand, and silence where a reader finds nothing to read."""
import json
import os

import pytest

from bench import flops, harness
from bench import trace as TR

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1e6                                   # nanoseconds in a millisecond


def spec_of(config, traffic):
    """The files a cell of ``config`` under ``traffic`` reads."""
    def load(*path):
        with open(os.path.join(BENCH, *path)) as f:
            return json.load(f)
    return harness.Spec(workload={"name": traffic, "chips": 1},
                        cfg=load("configs", f"{config}.json"),
                        traffic=load("traffic", f"{traffic}.json"),
                        limits={}, bench={})


TRAIN = ("stablelm-1.6b-4l", "train-4k")


def ctx(cell, ops=(), modules=(), host=(), steps=2):
    spec = spec_of(*cell)
    tr = TR.Trace(ops={0: list(ops)}, modules={0: list(modules)},
                  host=[("bench.window", 0, 1000 * MS)] + list(host),
                  window=(0, 1000 * MS))
    return spec, harness.ReadContext(trace=tr, cfg=spec.cfg,
                                     traffic=spec.traffic,
                                     counts={"steps": steps}, peaks=PEAKS,
                                     workload=spec.workload["name"])


def read(spec, name, c):
    return harness.reader(spec, name).read(c)


TRAIN_OPS = [("fusion.1", 0, 100 * MS),
             ("flash_fwd.20", 100 * MS, 200 * MS),
             ("_flash_bwd_worker_call.10", 200 * MS, 500 * MS),
             ("_fold_combine_call.10", 500 * MS, 600 * MS),
             ("fusion.2", 650 * MS, 900 * MS)]


def test_train_readers():
    spec, c = ctx(TRAIN, ops=TRAIN_OPS)
    assert read(spec, "device_idle.train", c) == pytest.approx(15.0)
    # attention: 100 + 300 + 100 ms of 850 ms busy
    assert read(spec, "attn_kernel_share", c) == pytest.approx(
        100 * 500 / 850)
    with open(os.path.join(BENCH, "configs", "stablelm-1.6b-4l.json")) as f:
        from bench.models import dense_decoder as D
        m = D.dims(json.load(f))
    least, bound = flops.roofline_s(*flops.attn_bwd_min(m, 2, 4096), PEAKS)
    assert bound == "flops"
    # 4 layers x 2 steps of the least time, over 400 ms of backward kernels
    assert read(spec, "attn_bwd_roofline", c) == pytest.approx(
        100 * least * 4 * 2 / 0.4)
    assert read(spec, "train_step_mfu", c) == pytest.approx(
        100 * 2 * flops.train_step_flops(m, 2, 4096) / 1.0 / 197e12)


def test_readers_are_silent_without_their_events():
    spec, c = ctx(TRAIN, ops=[("fusion.1", 0, 10 * MS)])
    assert read(spec, "attn_kernel_share", c) is None
    assert read(spec, "attn_bwd_roofline", c) is None
