"""repro.obs.prof / span / export / report + benchmarks/watchdog.

The load-bearing contracts of the profiling layer:

  * **deterministic identity** — span ids are pure functions of
    ``(run_id, scope, phase)``; with an injected fake clock two traced runs
    produce byte-identical span streams;
  * **disarmed is a bitwise no-op** — a profiler over a ``NoopTracker``
    never reads the clock, and attaching a real tracker to the serving
    engine changes no token and no logprob on the plain, speculative
    (``spec_k>0``), or TP-sharded paths;
  * **exact percentiles** — ``quantile_lower`` is the order statistic
    ``sorted(v)[floor(q*(n-1))]``, property-tested against
    ``numpy.quantile(method="lower")``;
  * **crash-safe JSONL** — ``read_jsonl`` recovers every complete record
    from a stream whose final line was torn mid-write;
  * **triage, not vibes** — ``diff_runs`` names the first diverging step
    AND the leaf paths that changed, and is clean on identical runs;
  * **the watchdog gates** — a regression beyond tolerance fails the check,
    an explicit allow-regress entry passes it.
"""
import json
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest
import jax
from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.models import transformer as T
from repro.obs import (JsonlTracker, MemoryTracker, NoopTracker, Profiler,
                       RunReport, diff_runs, quantile_lower, read_jsonl,
                       record_state_digests, span_id)
from repro.obs import export as EX
from repro.obs.metrics import Histogram
from repro.serve.engine import ContinuousEngine, SampleConfig

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ span ids
def test_span_id_deterministic_and_distinct():
    a = span_id("run", "req:3", "prefill")
    assert a == span_id("run", "req:3", "prefill")        # pure function
    assert len(a) == 16 and int(a, 16) >= 0               # 16 hex chars
    # any coordinate change moves the id
    assert a != span_id("run2", "req:3", "prefill")
    assert a != span_id("run", "req:4", "prefill")
    assert a != span_id("run", "req:3", "decode")


def _fake_clock(start=100.0, tick=0.25):
    state = {"t": start}

    def clock():
        state["t"] += tick
        return state["t"]

    return clock


def test_span_stream_byte_reproducible_with_fake_clock(tmp_path):
    """Deterministic ids + injected clock ⇒ the span stream is a pure
    function of the program: two runs write byte-identical JSONL."""
    paths = [str(tmp_path / f"r{i}.jsonl") for i in (0, 1)]
    for p in paths:
        with JsonlTracker(p, timestamps=False) as tr:
            prof = Profiler(tr, run_id="demo", clock=_fake_clock())
            with prof.span("request", "req:0", lane="req0") as req:
                with prof.span("prefill", "req:0", parent=req, step=0):
                    pass
                prof.end(prof.begin("decode", "step:1", step=1), committed=2)
            prof.mark("serve_preempt", {"request_id": 0}, step=2)
    assert open(paths[0], "rb").read() == open(paths[1], "rb").read()
    recs = read_jsonl(paths[0], event="span")
    assert [r["phase"] for r in recs] == ["prefill", "decode", "request"]
    assert recs[0]["parent_id"] == recs[2]["span_id"]
    assert all(r["dur_s"] > 0 for r in recs)


def test_disarmed_tracer_never_reads_clock():
    def bomb():
        raise AssertionError("disarmed tracer read the clock")

    prof = Profiler(NoopTracker(), clock=bomb)
    assert not prof.armed and prof.now() == 0.0
    assert prof.begin("decode", "step:0") is None
    prof.end(None, committed=1)                      # no-op, no raise
    with prof.span("prefill", "req:0") as s:
        assert s is None
    prof.mark("serve_preempt", {"request_id": 0})
    # armed tracer over the same API does emit
    mem = MemoryTracker()
    armed = Profiler(mem, clock=_fake_clock())
    assert armed.armed
    armed.end(armed.begin("decode", "step:0", step=0))
    assert mem.of("span")[0]["phase"] == "decode"


# ----------------------------------------------------------- torn-line JSONL
def test_read_jsonl_recovers_torn_final_line(tmp_path):
    path = str(tmp_path / "torn.jsonl")
    with JsonlTracker(path, timestamps=False) as tr:
        for s in range(3):
            tr.log("step", {"loss": 1.0 / (s + 1)}, step=s)
    whole = open(path).read()
    # simulate a crash mid-write: the final record is half a line
    open(path, "w").write(whole[: len(whole) - 17])
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        recs = read_jsonl(path)
    assert [r["step"] for r in recs] == [0, 1]       # complete records survive
    assert any(issubclass(w.category, RuntimeWarning) for w in caught)
    with pytest.raises(json.JSONDecodeError):
        read_jsonl(path, strict=True)                # strict mode still raises


def test_read_jsonl_interior_corruption_still_raises(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        f.write('{"event": "a", "seq": 0}\n')
        f.write("NOT JSON\n")
        f.write('{"event": "b", "seq": 2}\n')
    with pytest.raises(json.JSONDecodeError):
        read_jsonl(path)     # torn-tail tolerance must not mask real damage


def test_jsonl_tracker_flushes_every_event(tmp_path):
    """Crash-safety precondition: each record is on disk before the next —
    a reader sees every completed event without close()."""
    path = str(tmp_path / "live.jsonl")
    tr = JsonlTracker(path, timestamps=False)
    try:
        tr.log("a", {"v": 1})
        tr.log("b", {"v": 2})
        assert [r["event"] for r in read_jsonl(path)] == ["a", "b"]
    finally:
        tr.close()


# ------------------------------------------------------------ exact quantiles
@settings(max_examples=60, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       n=st.integers(min_value=1, max_value=200),
       qi=st.integers(min_value=0, max_value=100))
def test_quantile_lower_matches_numpy(seed, n, qi):
    rng = np.random.RandomState(seed)
    # duplicates on purpose: the tie-break contract must match numpy's
    vals = rng.randint(0, max(1, n // 3) + 1, size=n).astype(np.float64)
    vals += rng.rand(n).round(1)
    q = qi / 100.0
    got = quantile_lower(vals.tolist(), q)
    want = float(np.quantile(vals, q, method="lower"))
    assert got == want, (n, q)


def test_quantile_lower_contract_pinned():
    # lowest order statistic semantics, explicitly
    assert quantile_lower([3.0, 1.0, 2.0], 0.0) == 1.0
    assert quantile_lower([3.0, 1.0, 2.0], 0.5) == 2.0
    assert quantile_lower([3.0, 1.0, 2.0], 1.0) == 3.0
    assert quantile_lower([1.0, 2.0], 0.49) == 1.0   # floor, never interpolate
    assert quantile_lower([7.0], 0.99) == 7.0
    with pytest.raises(ValueError):
        quantile_lower([], 0.5)
    with pytest.raises(ValueError):
        quantile_lower([1.0], 1.5)


def test_histogram_percentile_exact():
    h = Histogram("lat", boundaries=[1.0])
    data = [5.0, 1.0, 9.0, 1.0, 3.0]
    for v in data:
        h.observe(v)
    for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0):
        assert h.percentile(q) == float(np.quantile(data, q, method="lower"))
    snap = h.snapshot()
    assert snap["lat_p50"] == 3.0 and snap["lat_p99"] == 5.0


# ----------------------------------------- profiler ⊥ computation (serve)
@pytest.fixture(scope="module")
def serve_setup():
    cfg = registry.get("stablelm-1.6b").reduced()
    params = T.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = {i: rng.randint(1, cfg.vocab, size=n).tolist()
               for i, n in enumerate([5, 13, 7])}
    return cfg, params, prompts


def _serve(serve_setup, tracker, **kw):
    cfg, params, prompts = serve_setup
    eng = ContinuousEngine(cfg, params, n_slots=2, max_seq=64, page_size=8,
                           prefill_chunk=16,
                           scfg=SampleConfig(temperature=0.7, seed=3),
                           tracker=tracker, **kw)
    for i, toks in prompts.items():
        eng.submit(toks, req_id=i, max_new_tokens=6)
    return eng.run(), eng.result_logprobs


def test_profiler_spans_cover_request_lifecycle(serve_setup):
    mem = MemoryTracker()
    _serve(serve_setup, mem)
    spans = mem.of("span")
    phases = {s["phase"] for s in spans}
    assert {"request", "queue", "prefill", "prefill_chunk",
            "decode"} <= phases
    queue = [s for s in spans if s["phase"] == "queue"]
    assert all("queued_steps" in s and "slot" in s for s in queue)
    prefill = [s for s in spans if s["phase"] == "prefill"]
    assert all(s["ttft_s"] >= 0.0 for s in prefill)
    reqs = {s["scope"]: s for s in spans if s["phase"] == "request"}
    assert set(reqs) == {"req:0", "req:1", "req:2"}
    assert all("n_tokens" in s for s in reqs.values())
    # parentage: each queue span hangs off its request span
    by_id = {s["span_id"]: s for s in spans}
    for s in queue:
        assert by_id[s["parent_id"]]["phase"] == "request"


def test_armed_profiler_bitwise_noop_spec_path(serve_setup):
    """spec_k>0 (self-draft): tracked vs untracked engines emit identical
    tokens AND logprobs, and the tracked stream carries spec_round spans."""
    mem = MemoryTracker()
    tracked_tok, tracked_lp = _serve(serve_setup, mem, spec_k=2)
    plain_tok, plain_lp = _serve(serve_setup, None, spec_k=2)
    base_tok, base_lp = _serve(serve_setup, None)           # non-spec oracle
    for i in plain_tok:
        np.testing.assert_array_equal(tracked_tok[i], plain_tok[i])
        np.testing.assert_array_equal(tracked_lp[i], plain_lp[i])
        np.testing.assert_array_equal(tracked_tok[i], base_tok[i])
        np.testing.assert_array_equal(tracked_lp[i], base_lp[i])
    rounds = [s for s in mem.of("span") if s["phase"] == "spec_round"]
    assert rounds and all("live_slots" in s for s in rounds)


SHARDED_PROF_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.obs import MemoryTracker
    from repro.serve.engine import ContinuousEngine, SampleConfig

    cfg = registry.get("stablelm-1.6b").reduced()
    params = T.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab, size=n).tolist() for n in (5, 13, 7)]
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("model",))

    def run(tracker):
        eng = ContinuousEngine(cfg, params, n_slots=2, max_seq=64,
                               page_size=8, prefill_chunk=16, mesh=mesh,
                               scfg=SampleConfig(temperature=0.7, seed=3),
                               tracker=tracker)
        for i, p in enumerate(prompts):
            eng.submit(p, req_id=i, max_new_tokens=6)
        return eng.run(), eng.result_logprobs

    mem = MemoryTracker()
    t_tok, t_lp = run(mem)
    p_tok, p_lp = run(None)
    for i in p_tok:
        assert np.array_equal(t_tok[i], p_tok[i]), i
        assert np.array_equal(t_lp[i], p_lp[i]), i
    spans = mem.of("span")
    builds = [s for s in spans if s["phase"] == "sharded_build"]
    assert builds and builds[0]["tp"] == 2, builds
    assert {"request", "queue", "prefill", "decode"} <= {
        s["phase"] for s in spans}
    print("sharded profiler bitwise no-op OK")
""")


def test_armed_profiler_bitwise_noop_sharded_tp():
    """TP-sharded engine (subprocess, 4 forced CPU devices): tracked vs
    untracked tokens + logprobs bitwise, sharded_build span recorded."""
    r = subprocess.run([sys.executable, "-c", SHARDED_PROF_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": "src"}, cwd=REPO_ROOT)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "sharded profiler bitwise no-op OK" in r.stdout


# ------------------------------------------------------------ Perfetto export
def test_schedule_timeline_validates_with_both_lanes():
    events = EX.attention_timeline(128, 32, causal=True, measure=False)
    # modeled lane always present; synthesize the achieved lane
    from repro.core.schedules import cached_schedule
    from repro.tune.model import task_costs
    n = 128 // 64
    sched = cached_schedule("symmetric_shift", n, 1, True, n)
    c, r = task_costs(64, 64, 32)
    events = EX.schedule_to_trace(sched, c, r, achieved_s=1e-3)
    probs = EX.validate_trace(
        EX.make_trace(events),
        require_processes=(EX.PROCESS_MODELED, EX.PROCESS_ACHIEVED))
    assert probs == [], probs
    # the achieved lane is the modeled layout under a uniform stretch
    xs = [e for e in events if e.get("ph") == "X"]
    modeled = sorted(e["ts"] for e in xs if e["pid"] == EX.PID_MODELED)
    achieved = sorted(e["ts"] for e in xs if e["pid"] == EX.PID_ACHIEVED)
    stretch = [a / m for a, m in zip(achieved, modeled) if m > 0]
    assert all(abs(s - stretch[0]) < 1e-9 for s in stretch)


def test_validate_trace_rejects_garbage():
    assert EX.validate_trace({"traceEvents": []})          # empty
    assert EX.validate_trace({"traceEvents": [{"ph": "X", "name": "a",
                                               "pid": 1, "tid": 1,
                                               "ts": -5, "dur": 1}]})
    assert EX.validate_trace({"traceEvents": [{"ph": "?", "ts": 0}]})
    good = EX.make_trace(EX.attention_timeline(128, 32, measure=False))
    assert EX.validate_trace(good) == []
    assert EX.validate_trace(good, require_processes=("no-such-process",))


def test_spans_to_trace_roundtrip(tmp_path, serve_setup):
    mem = MemoryTracker()
    _serve(serve_setup, mem)
    events = EX.spans_to_trace(mem.events, process_name="serve-test")
    path = str(tmp_path / "trace.json")
    EX.write_trace(path, events)
    obj = json.load(open(path))
    assert EX.validate_trace(obj, require_processes=("serve-test",)) == []
    names = {e["name"] for e in obj["traceEvents"] if e.get("ph") == "X"}
    assert any(n.startswith("decode") for n in names)
    assert any(n.startswith("request") for n in names)


# ------------------------------------------------------------------ RunReport
def test_run_report_percentiles_and_counters(serve_setup):
    mem = MemoryTracker()
    _serve(serve_setup, mem)
    rep = RunReport.from_events(mem.events)
    assert rep.counters["serve_done"] == 3
    assert rep.counters["span"] == len(mem.of("span"))
    for key in ("ttft_s", "queue_wait_s", "queue_wait_steps",
                "per_token_s", "decode_step_s"):
        d = rep.latency[key]
        assert d["p50"] <= d["p90"] <= d["p99"] <= d["max"]
        assert d["n"] > 0
    assert rep.throughput["completed_tokens"] == 18.0      # 3 reqs x 6
    assert rep.throughput["decode_tokens_per_s"] > 0
    # report serialization is deterministic
    assert rep.to_json() == RunReport.from_events(mem.events).to_json()


# ------------------------------------------------------- divergence triage
def _mini_train(det_embed_grad, steps=2):
    """A tiny train loop over a tiny data vocab (heavy token collisions so
    the two embedding-backward realizations differ bitwise)."""
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.train import optimizer as O
    from repro.train import step as S

    cfg = registry.get("stablelm-1.6b").reduced(
        det_embed_grad=det_embed_grad)
    tcfg = S.TrainConfig(opt=O.OptConfig(total_steps=steps))
    state = S.init_state(cfg, tcfg, jax.random.PRNGKey(0))
    data = SyntheticLM(DataConfig(seed=0, batch=2, seq=64, vocab=8))
    step_fn = jax.jit(S.make_train_step(cfg, tcfg))
    mem = MemoryTracker()
    for s in range(steps):
        state, _ = step_fn(state, data.batch(s))
        record_state_digests(state, s + 1, tracker=mem)
    return RunReport.from_events(mem.events)


def test_diff_runs_clean_on_identical_runs():
    a, b = _mini_train(True), _mini_train(True)
    diff = diff_runs(a, b)
    assert diff.clean and diff.via == "digest_chain"
    assert "clean" in str(diff)


def test_diff_runs_names_step_and_leaf_path():
    """The acceptance probe: a deliberately-diverged run (the nondeterministic
    embedding backward) is pinned to its first step and leaf paths."""
    diff = diff_runs(_mini_train(True), _mini_train(False))
    assert not diff.clean and diff.via == "digest_chain"
    assert diff.first_step == 1
    assert diff.leaf_paths and any("embed" in p for p in diff.leaf_paths)
    assert f"step {diff.first_step}" in str(diff)


def test_diff_runs_fingerprint_fallback():
    a = RunReport(fingerprints={1: 10, 2: 20, 3: 30})
    b = RunReport(fingerprints={1: 10, 2: 21, 3: 30})
    diff = diff_runs(a, b)
    assert not diff.clean and diff.via == "fingerprint"
    assert diff.first_step == 2 and diff.leaf_paths == ()
    assert diff_runs(a, a).clean
    assert diff_runs(RunReport(), RunReport()).via == "none"


def test_record_state_digests_feeds_chain_and_tracker():
    from repro.verify.digest import DigestChain
    state = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
             "b": np.zeros(3, np.float32)}
    mem, chain = MemoryTracker(), DigestChain()
    tree = record_state_digests(state, 4, tracker=mem, chain=chain)
    assert chain.records == [(4, tree)]
    rec = mem.of("leaf_digests")[0]
    assert rec["tree_digest"] == tree and rec["step"] == 4
    assert set(rec["leaves"]) == {"b", "w"}
    assert all(len(v) == 16 for v in rec["leaves"].values())
    # disarmed: chain still fed, nothing logged, same digest
    chain2 = DigestChain()
    assert record_state_digests(state, 4, tracker=NoopTracker(),
                                chain=chain2) == tree
    assert chain2.records == chain.records


# ------------------------------------------------------------------ watchdog
def _summary(**over):
    serve = {"suite": "serve", "value": 4.5, "decode_tps": 700.0,
             "spec_speedup_k4": 2.9, "spec_accept_rate": 1.0}
    kb = {"suite": "kernel_bwd", "value": 64.0, "modeled_utilization": 1.0,
          "modeled_makespan": 184.0}
    for row in (serve, kb):
        for k in list(over):
            if k in row:
                row[k] = over.pop(k)
    return {"suites": [serve, kb]}


def test_watchdog_flatten_and_roundtrip(tmp_path):
    from benchmarks import watchdog as W
    flat = W.flatten_summary(_summary())
    assert flat["serve.decode_tps"] == 700.0
    assert flat["kernel_bwd.modeled_makespan"] == 184.0
    assert "serve.suite" not in flat            # only watched numeric fields
    base_path = str(tmp_path / "BASELINES.json")
    W.record(_summary(), base_path)
    baselines = json.load(open(base_path))
    failures, _ = W.check(_summary(), baselines)
    assert failures == []


def test_watchdog_fails_on_regression(tmp_path):
    from benchmarks import watchdog as W
    baselines = W.record(_summary(), str(tmp_path / "b.json"))
    # decode_tps halves: beyond the 0.5 tolerance -> regression
    failures, lines = W.check(_summary(decode_tps=300.0), baselines)
    assert any("serve.decode_tps" in f for f in failures)
    # "lower is better": makespan growing beyond tolerance also fails
    failures, _ = W.check(_summary(modeled_makespan=200.0), baselines)
    assert any("kernel_bwd.modeled_makespan" in f for f in failures)
    # improvements never fail (and are labelled)
    failures, lines = W.check(_summary(decode_tps=1400.0), baselines)
    assert failures == []
    assert any(line.startswith("  IMPROVED") for line in lines)
    # a watched metric disappearing is a failure
    gutted = {"suites": [r for r in _summary()["suites"]
                         if r["suite"] != "serve"]}
    failures, _ = W.check(gutted, baselines)
    assert any("disappeared" in f for f in failures)


def test_watchdog_allow_regress_is_explicit(tmp_path):
    from benchmarks import watchdog as W
    baselines = W.record(_summary(), str(tmp_path / "b.json"))
    bad = _summary(decode_tps=300.0)
    failures, _ = W.check(bad, baselines)
    assert failures
    failures, lines = W.check(bad, baselines,
                              allow_regress=["serve.decode_tps"])
    assert failures == []
    assert any(line.startswith("  ALLOWED") for line in lines)


def test_watchdog_cli_gate(tmp_path):
    from benchmarks import watchdog as W
    summary_path = str(tmp_path / "s.json")
    base_path = str(tmp_path / "b.json")
    json.dump(_summary(), open(summary_path, "w"))
    assert W.main(["--summary", summary_path, "--baselines", base_path,
                   "--record", "--check"]) == 0
    json.dump(_summary(decode_tps=300.0), open(summary_path, "w"))
    assert W.main(["--summary", summary_path, "--baselines", base_path,
                   "--check"]) == 1
    assert W.main(["--summary", summary_path, "--baselines", base_path,
                   "--check", "--allow-regress", "serve.decode_tps"]) == 0


def test_committed_baselines_match_committed_summary():
    """The repo's own BASELINES.json gates the repo's own BENCH_summary.json
    cleanly — the invariant the obs-trace CI job enforces."""
    from benchmarks import watchdog as W
    summary = json.load(open(os.path.join(REPO_ROOT, "benchmarks",
                                          "BENCH_summary.json")))
    baselines = json.load(open(W.BASELINES_PATH))
    failures, _ = W.check(summary, baselines)
    assert failures == [], failures
