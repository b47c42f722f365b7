"""``correct`` comes out false when the timed path is broken underneath, and
for the control, at a tiny size on the CPU.

Each test drives the rest of a run (set-up, window, reference, comparison)
with the chip check skipped, and with one fault planted where it is
produced: a train step that returns its state unchanged; half of each batch
left out, the mean taken over the rest. The control is the reference
computed in float8 e4m3 (one scale per tensor) in the program's place.
Exchanges between chips do not exist in this one-chip cell.

The limits at this size (``data/tiny-limits-*.json``) are set by the same
rule as the cells', from readings at this size, and the file gives them.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.kinds import train
from bench.models import dense_decoder as D

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _json(name):
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return json.load(f)


def tiny_spec(cell, cfg_name, traffic_name, limits_name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return harness.Spec(workload={"name": cell, "chips": 1},
                        cfg=dict(_json(cfg_name), name=cfg_name),
                        traffic=dict(_json(traffic_name), name=traffic_name),
                        limits=_json(limits_name), bench=bench)


@pytest.fixture
def train_spec():
    return tiny_spec("tiny-train", "tiny-stablelm", "tiny-train",
                     "tiny-limits-train")


def _patch_adapter(monkeypatch, spec, name, wrap):
    ad = harness.adapter(spec)
    monkeypatch.setattr(ad, name, wrap(getattr(ad, name)))
    monkeypatch.setattr(harness, "adapter", lambda _spec: ad)


def test_sound_runs_are_correct(train_spec):
    res = harness.run(train_spec, seed=2**31 + 3, seconds=0.5, traced=False,
                      device=CPU)
    assert res["correct"], res["checks"]


def test_state_left_unchanged(monkeypatch, train_spec):
    from repro.train import step as S

    def wrap(build):
        def broken(mc, tcfg):
            real = jax.jit(S.make_train_step(mc, tcfg))

            def step(state, batch):
                _, metrics = real(state, batch)
                return state, metrics
            return step
        return broken

    _patch_adapter(monkeypatch, train_spec, "build_train_step", wrap)
    res = harness.run(train_spec, seed=11, seconds=0.3, traced=False,
                      device=CPU)
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(monkeypatch, train_spec):
    def wrap(build):
        def broken(mc, tcfg):
            real = build(mc, tcfg)

            def step(state, batch):
                half = batch["tokens"].shape[0] // 2
                return real(state, {k: v[:half] for k, v in batch.items()})
            return step
        return broken

    _patch_adapter(monkeypatch, train_spec, "build_train_step", wrap)
    res = harness.run(train_spec, seed=12, seconds=0.3, traced=False,
                      device=CPU)
    assert not res["correct"], res["checks"]


def test_control_fails_training(train_spec):
    cfg, tr = train_spec.cfg, train_spec.traffic
    feed = train.batch_fn(cfg["vocab_size"], tr["batch"], tr["seq"],
                          D.root_key)
    for seed in (21, 22, 23):
        words = jnp.asarray(D.seed_words(seed))
        batches = [(feed(words, i)["tokens"], feed(words, i)["labels"])
                   for i in range(tr["check_steps"])]
        ref = D.train_reference(cfg, tr["optimizer"], seed, batches)
        ctl = D.train_reference(cfg, tr["optimizer"], seed, batches,
                                precision="fp8")
        got = train.compare(ctl, ref, tr["leaf_rule"])
        over = [k for k in ("loss_gap", "grad_gap", "change_gap")
                if got[k] > train_spec.limits[k]["limit"]]
        assert over, got
