"""repro.obs — tracker sinks, metrics instruments, divergence alarm.

The two load-bearing contracts:

  * a tracker can never change the computation it observes — the serving
    engine emits bitwise-identical tokens with a JSONL tracker attached and
    with none (the same invariance bar tests/test_serve_invariance.py holds
    the engine itself to);
  * the JSONL stream is canonical — sorted keys, monotone ``seq``, and with
    ``timestamps=False`` two identical runs produce byte-identical files.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
import jax

from repro.configs import registry
from repro.models import transformer as T
from repro.obs import (CompositeTracker, Counter, DivergenceAlarm, Histogram,
                       JsonlTracker, MemoryTracker, NoopTracker, StepMeter,
                       Timer, open_tracker, read_jsonl)
from repro.obs.metrics import MetricSet
from repro.serve.engine import ContinuousEngine, SampleConfig

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ trackers
def test_jsonl_tracker_schema_and_seq(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with JsonlTracker(path) as tr:
        tr.log("alpha", {"x": 1})
        tr.log("beta", {"y": 2.5}, step=7)
        tr.log("alpha", {})
    recs = read_jsonl(path)
    assert [r["seq"] for r in recs] == [0, 1, 2]
    assert [r["event"] for r in recs] == ["alpha", "beta", "alpha"]
    assert recs[1]["step"] == 7 and recs[1]["y"] == 2.5
    assert all("t" in r for r in recs)          # timestamps on by default
    # canonical encoding: each line is json with sorted keys
    for line in open(path):
        rec = json.loads(line)
        assert line == json.dumps(rec, sort_keys=True) + "\n"


def test_jsonl_tracker_byte_reproducible(tmp_path):
    """timestamps=False → the stream is a pure function of the events."""
    paths = [str(tmp_path / f"r{i}.jsonl") for i in (0, 1)]
    for p in paths:
        with JsonlTracker(p, timestamps=False) as tr:
            for s in range(5):
                tr.log("step", {"loss": 1.0 / (s + 1), "tokens_per_s": 256.0},
                       step=s)
    assert open(paths[0], "rb").read() == open(paths[1], "rb").read()


def test_read_jsonl_event_filter(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with JsonlTracker(path) as tr:
        tr.log("a", {"v": 1})
        tr.log("b", {"v": 2})
        tr.log("a", {"v": 3})
    assert [r["v"] for r in read_jsonl(path, event="a")] == [1, 3]


def test_composite_memory_noop():
    m1, m2 = MemoryTracker(), MemoryTracker()
    comp = CompositeTracker([m1, m2, NoopTracker()])
    comp.log("e", {"k": 1}, step=3)
    comp.close()
    assert m1.events == m2.events == [{"event": "e", "k": 1, "step": 3}]
    assert m1.of("e") and not m1.of("other")


def test_open_tracker(tmp_path):
    assert isinstance(open_tracker(None), NoopTracker)
    tr = open_tracker(str(tmp_path / "x.jsonl"))
    assert isinstance(tr, JsonlTracker)
    tr.close()


# ------------------------------------------------------------------- metrics
def test_counter_timer_histogram():
    c = Counter("hits")
    c.inc()
    c.inc(4)
    assert c.snapshot() == {"hits": 5.0}

    t = Timer("step")
    t.add(0.2)
    t.add(0.4)
    assert t.snapshot()["step_total_s"] == pytest.approx(0.6)
    assert t.snapshot()["step_mean_s"] == pytest.approx(0.3)
    with t:
        pass
    assert t.count == 3

    h = Histogram("lat", boundaries=[1.0, 10.0])
    for v in (0.5, 2.0, 3.0, 50.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["lat_count"] == 4.0
    assert snap["lat_le_1"] == 1.0 and snap["lat_le_10"] == 2.0
    assert snap["lat_le_inf"] == 1.0
    assert snap["lat_max"] == 50.0


def test_metric_set_emit():
    ms = MetricSet()
    ms.counter("n").inc(2)
    ms.timer("t").add(1.0)
    mem = MemoryTracker()
    snap = ms.emit(mem, step=5)
    assert snap["n"] == 2.0 and snap["t_count"] == 1.0
    assert mem.events[0]["step"] == 5


def test_step_meter_throughput_and_utilization():
    """Throughput only: the meter reports no utilization, since nothing on
    the host measures one (device shares come from a profiler trace)."""
    m = StepMeter()
    ev = m.update(tokens=1024, dt_s=1.0)
    assert ev["tokens_per_s"] == pytest.approx(1024.0)
    assert ev["step_ms"] == pytest.approx(1000.0)
    ev = m.update(tokens=1024, dt_s=0.5)
    assert ev["tokens_per_s"] == pytest.approx(2048.0)
    assert ev["tokens_per_s_avg"] == pytest.approx(2048 / 1.5)
    assert ev["steps"] == 2.0
    assert not any("utilization" in k for k in ev)


# --------------------------------------------------------------------- alarm
def test_divergence_alarm_records_without_reference():
    mem = MemoryTracker()
    alarm = DivergenceAlarm(tracker=mem)
    assert alarm.observe(1, 111) is False
    assert alarm.observe(2, 222) is False
    assert alarm.ok and alarm.seen == {1: 111, 2: 222}
    assert [e["fingerprint"] for e in mem.of("fingerprint")] == [111, 222]
    assert not mem.of("fingerprint_divergence")


def test_divergence_alarm_fires_once_and_latches():
    mem = MemoryTracker()
    alarm = DivergenceAlarm(tracker=mem, reference={1: 111, 2: 222, 3: 333})
    assert alarm.observe(1, 111) is False
    assert alarm.observe(2, 999) is True          # first divergence
    assert alarm.observe(3, 888) is False         # latched: fires only once
    assert not alarm.ok and alarm.diverged_at == 2
    div = mem.of("fingerprint_divergence")
    assert len(div) == 1
    assert div[0]["step"] == 2 and div[0]["reference_fingerprint"] == 222


def test_divergence_alarm_from_jsonl_roundtrip(tmp_path):
    """A run's JSONL is the next run's reference."""
    path = str(tmp_path / "ref.jsonl")
    with JsonlTracker(path) as tr:
        ref = DivergenceAlarm(tracker=tr)
        for s, fp in [(1, 10), (2, 20), (3, 30)]:
            ref.observe(s, fp)
    live = DivergenceAlarm.from_jsonl(path)
    assert live.reference == {1: 10, 2: 20, 3: 30}
    assert live.observe(1, 10) is False
    assert live.observe(2, 21) is True


# ---------------------------------------------- tracker ⊥ computation (serve)
@pytest.fixture(scope="module")
def serve_setup():
    cfg = registry.get("stablelm-1.6b").reduced()
    params = T.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = {i: rng.randint(1, cfg.vocab, size=n).tolist()
               for i, n in enumerate([5, 13, 7])}
    return cfg, params, prompts


def _serve(serve_setup, tracker):
    cfg, params, prompts = serve_setup
    eng = ContinuousEngine(cfg, params, n_slots=2, max_seq=64, page_size=8,
                           prefill_chunk=16,
                           scfg=SampleConfig(temperature=0.7, seed=3),
                           tracker=tracker)
    for i, toks in prompts.items():
        eng.submit(toks, req_id=i, max_new_tokens=6)
    return eng.run()


def test_engine_tracker_token_invariance(serve_setup):
    """Attaching a tracker cannot change a single emitted token."""
    mem = MemoryTracker()
    tracked = _serve(serve_setup, mem)
    plain = _serve(serve_setup, None)
    for i in plain:
        np.testing.assert_array_equal(tracked[i], plain[i])
    # the stream saw the request lifecycle
    assert len(mem.of("serve_submit")) == 3
    assert len(mem.of("serve_prefill")) == 3
    assert len(mem.of("serve_done")) == 3
    assert mem.of("serve_decode")          # at least one batched decode step
    done = {e["request_id"]: e["n_tokens"] for e in mem.of("serve_done")}
    assert done == {i: len(plain[i]) for i in plain}


# ------------------------------------------------------ launch smoke (slow)
@pytest.mark.slow
def test_launch_train_track_smoke(tmp_path):
    """`--track --verify` end to end: the tracker JSONL carries per-step
    throughput, the live fingerprint stream, and the cache/run summaries."""
    track = tmp_path / "run.jsonl"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "stablelm-1.6b",
         "--reduced", "--steps", "3", "--batch", "2", "--seq", "128",
         "--track", str(track), "--verify",
         "--verify-out", str(tmp_path / "digest_chain.json")],
        capture_output=True, text=True, timeout=600, cwd=REPO_ROOT,
        env={**os.environ, "PYTHONPATH": "src"})
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"

    events = [json.loads(l) for l in open(track)]
    kinds = {e["event"] for e in events}
    assert {"run_config", "step", "fingerprint", "cache_info",
            "run_summary"} <= kinds
    steps = [e for e in events if e["event"] == "step"]
    assert [e["step"] for e in steps] == [1, 2, 3]
    for e in steps:
        assert e["tokens_per_s"] > 0
        assert "utilization_vs_modeled" not in e
        assert "loss" in e and "grad_norm" in e
    # the fingerprint chain stayed clean
    assert not [e for e in events if e["event"] == "fingerprint_divergence"]
    summary = next(e for e in events if e["event"] == "run_summary")
    assert summary["fingerprint_ok"] is True
