"""repro.tune — deterministic schedule autotuner.

The contracts under test (ISSUE/ROADMAP item 5):

  * enumeration is *legal by construction*: blocks tile the sequences, VMEM
    footprints fit, families respect mask compatibility, worker-parallel is
    only offered where it is bitwise-equal to serialized;
  * sim-mode ranking is a pure function of the candidate set — stable across
    passes, enumeration orders, and **processes** (subprocess test);
  * the cache round-trips through JSON, addresses itself, and makes
    decisions sticky; a bumped tuner version invalidates entries;
  * measure mode's tie-break never lets clock jitter choose between
    near-equal candidates;
  * ``dash_attention(tune=True)`` is **bitwise identical** (outputs and
    gradients) to the hand-configured call with the same resolved knobs, for
    the attention geometries of three registry configs;
  * the cost calibration matches ``benchmarks/bench_schedule_sim.rc_ratio``.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.kernels.ops import dash_attention
from repro.masks import Document, PrefixLM, SlidingWindow, cache_info
from repro.masks.schedule import cached_block_schedule
from repro.obs import MemoryTracker
from repro.tune import (Candidate, TuneCache, TUNER_VERSION,
                        enumerate_candidates, legal_blocks, make_key,
                        measure_topk, modeled_costs, pick_placement,
                        rank_candidates, tune_attention)
from repro.tune.model import task_costs

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------- space
def test_legal_blocks_tile_and_fit():
    assert legal_blocks(1024, 1024, 128) == (256, 128)
    assert legal_blocks(384, 384, 128) == (128,)        # 256 doesn't tile 384
    assert legal_blocks(512, 1024, 128) == (256, 128)
    # a starved VMEM budget removes every block
    assert legal_blocks(1024, 1024, 128, vmem_budget=1e-5) == ()


def test_enumeration_legality():
    cands = enumerate_candidates(seq_q=1024, head_dim=128, causal=True)
    assert cands, "causal 1024 must have candidates"
    for c in cands:
        assert 1024 % c.block_q == 0 and 1024 % c.block_k == 0
        assert c.schedule in ("symmetric_shift", "descending", "fa3")
        assert c.n_workers >= 1
    # both realizations offered exactly where the worker grid is bitwise-safe
    from repro.tune.space import _realizations, build_schedule
    by_key = {}
    for c in cands:
        by_key.setdefault((c.schedule, c.block_q), set()).add(c.worker_parallel)
    for (name, bq), offered in by_key.items():
        sch = build_schedule(Candidate(name, bq, bq, False, 0),
                             1024, 1024, True)
        assert offered == set(_realizations(sch)), (name, bq)


def test_enumeration_mask_axis():
    mask = SlidingWindow(512)
    cands = enumerate_candidates(seq_q=2048, head_dim=128, mask=mask)
    assert {c.schedule for c in cands} <= {"shift", "fa3"}
    with pytest.raises(AssertionError):
        enumerate_candidates(seq_q=2048, head_dim=128, causal=True, mask=mask)
    with pytest.raises(AssertionError):   # no block tiles a 100-token seq
        enumerate_candidates(seq_q=100, head_dim=128)


def test_candidate_roundtrip_and_key():
    c = Candidate("shift", 128, 128, True, 8)
    assert Candidate.from_dict(json.loads(json.dumps(c.to_dict()))) == c
    assert c.key() == "shift|bq128|bk128|par|w8"


# ------------------------------------------------------------------- model
def test_rank_determinism_and_set_purity():
    kw = dict(seq_q=2048, head_dim=64, causal=True)
    a = rank_candidates(enumerate_candidates(**kw), **kw)
    b = rank_candidates(enumerate_candidates(**kw), **kw)
    assert [r["candidate"] for r in a] == [r["candidate"] for r in b]
    rev = rank_candidates(tuple(reversed(enumerate_candidates(**kw))), **kw)
    assert [r["candidate"] for r in a] == [r["candidate"] for r in rev]
    # makespans ascend
    ms = [r["modeled_makespan_s"] for r in a]
    assert ms == sorted(ms)


def test_rank_winner_families():
    full = rank_candidates(enumerate_candidates(seq_q=1024, head_dim=128),
                           seq_q=1024, head_dim=128)
    assert full[0]["candidate"].schedule == "shift"
    assert full[0]["candidate"].worker_parallel
    causal = rank_candidates(
        enumerate_candidates(seq_q=1024, head_dim=128, causal=True),
        seq_q=1024, head_dim=128, causal=True)
    assert causal[0]["candidate"].schedule == "symmetric_shift"


def test_serialized_modeled_slower_than_parallel():
    par = Candidate("shift", 128, 128, True, 8)
    ser = Candidate("shift", 128, 128, False, 8)
    mp = modeled_costs(par, seq_q=1024, head_dim=128)
    ms = modeled_costs(ser, seq_q=1024, head_dim=128)
    assert mp["modeled_makespan_s"] < ms["modeled_makespan_s"]
    assert ms["modeled_utilization"] == pytest.approx(1 / 8)


def test_calibration_matches_bench_schedule_sim():
    sys.path.insert(0, REPO_ROOT)
    try:
        from benchmarks.bench_schedule_sim import rc_ratio
    finally:
        sys.path.remove(REPO_ROOT)
    for d in (64, 128):
        c, r = task_costs(128, 128, d)
        assert r / c == pytest.approx(rc_ratio(d, 128))


# ------------------------------------------------------------------- cache
def test_cache_roundtrip_and_self_addressing(tmp_path):
    cache = TuneCache(root=str(tmp_path))
    key = make_key(mask_key="causal", seq_q=1024, seq_kv=1024, head_dim=128,
                   n_heads=8, n_kv_heads=8, dtype="bfloat16",
                   backend="pallas-tpu")
    assert key.startswith(f"tuner-v{TUNER_VERSION}|")
    assert cache.get(key) is None
    cand = Candidate("symmetric_shift", 128, 128, True, 8)
    cache.put(key, cand, {"modeled_makespan_s": 1e-6})
    rec = cache.get(key)
    assert TuneCache.candidate_of(rec) == cand
    assert rec["modeled_makespan_s"] == 1e-6
    assert cache.cache_info() == {"hits": 1, "misses": 1, "entries": 1}
    # a record that no longer addresses itself (hand-edited key) is a miss
    with open(cache.path(key)) as f:
        broken = json.load(f)
    broken["key"] = "something-else"
    with open(cache.path(key), "w") as f:
        json.dump(broken, f)
    assert cache.get(key) is None
    # stale tuner version is a miss too
    broken["key"], broken["tuner_version"] = key, TUNER_VERSION + 1
    with open(cache.path(key), "w") as f:
        json.dump(broken, f)
    assert cache.get(key) is None


def test_cache_emits_tracker_events(tmp_path):
    mem = MemoryTracker()
    cache = TuneCache(root=str(tmp_path), tracker=mem)
    res1 = tune_attention(seq=512, head_dim=64, causal=True, cache=cache)
    res2 = tune_attention(seq=512, head_dim=64, causal=True, cache=cache)
    assert res1.candidate == res2.candidate
    assert (res1.source, res2.source) == ("sim", "cache")
    assert [e["result"] for e in mem.of("tune_cache")] == ["miss", "hit"]


# --------------------------------------------------------------------- api
def test_tune_attention_key_separates_geometries(tmp_path):
    cache = TuneCache(root=str(tmp_path))
    a = tune_attention(seq=1024, head_dim=128, causal=True, cache=cache)
    b = tune_attention(seq=1024, head_dim=128, causal=False, cache=cache)
    c = tune_attention(seq=1024, head_dim=128, causal=True, cache=cache,
                       dtype="float32")
    assert len({a.key, b.key, c.key}) == 3
    assert a.candidate.schedule == "symmetric_shift"
    assert b.candidate.schedule == "shift"


def test_measure_without_runner_refuses_on_cache_miss(tmp_path):
    """mode="measure" with no runner never ranks by simulation in its place:
    a cache miss raises, and nothing is written to the cache."""
    cache = TuneCache(root=str(tmp_path))
    with pytest.raises(ValueError, match="runner"):
        tune_attention(seq=1024, head_dim=128, causal=True, cache=cache,
                       mode="measure")
    sim = tune_attention(seq=1024, head_dim=128, causal=True, cache=cache)
    assert sim.source == "sim"


def test_tune_attention_normalizes_paper_masks(tmp_path):
    """Full()/Causal() specs share keys (and decisions) with the flag form."""
    from repro.masks import Causal, Full
    cache = TuneCache(root=str(tmp_path))
    flag = tune_attention(seq=1024, head_dim=128, causal=True, cache=cache)
    spec = tune_attention(seq=1024, head_dim=128, mask=Causal(), cache=cache)
    assert spec.key == flag.key and spec.source == "cache"
    full = tune_attention(seq=1024, head_dim=128, mask=Full(), cache=cache)
    assert full.candidate.schedule == "shift"


def test_measure_tie_break_deterministic(tmp_path):
    """Within rel_tol, jitter cannot reorder; outside it, faster wins."""
    kw = dict(seq_q=1024, head_dim=128, causal=True)
    ranked = rank_candidates(enumerate_candidates(**kw), **kw)

    def jitter_clock():
        calls = {"n": 0}

        def clock():
            calls["n"] += 1
            return calls["n"] * 1e-9      # monotone jitter, negligible scale
        return clock

    def noop_runner(cand):
        pass

    # near-equal measurements (all within tolerance): the modeled order
    # decides — run twice, same winner
    t1 = measure_topk(ranked, noop_runner, k=3, clock=jitter_clock())
    t2 = measure_topk(ranked, noop_runner, k=3, clock=jitter_clock())
    assert t1[0]["candidate"] == t2[0]["candidate"] == ranked[0]["candidate"]

    # a decisively slower candidate drops behind regardless of model order
    slow = {ranked[0]["candidate"].key()}

    class FakeClock:
        def __init__(self):
            self.t = 0.0
            self.pending = 0.0

        def __call__(self):
            self.t += self.pending
            self.pending = 0.0
            return self.t

    clk = FakeClock()

    def runner2(cand):
        # charge 10s to the modeled winner, 1s to everyone else
        clk.pending += 10.0 if cand.key() in slow else 1.0

    t3 = measure_topk(ranked, runner2, k=3, clock=clk)
    assert t3[0]["candidate"] != ranked[0]["candidate"]
    assert t3[0]["measured_s"] == pytest.approx(1.0)


def test_pick_placement_and_tuned_block_schedule():
    for mask, n in [(SlidingWindow(512), 16),
                    (Document.from_lengths((512, 1024, 512)), 16),
                    (PrefixLM(512), 16)]:
        assert pick_placement(mask, n, n) == "shift"
        tuned = cached_block_schedule(mask, n, n, tune=True)
        hand = cached_block_schedule(mask, n, n, placement="shift")
        assert tuned is hand        # same memoized instance — sticky choice


def test_masks_cache_info_exposed():
    info = cache_info()
    assert set(info) == {"cached_schedule", "cached_block_schedule",
                         "block_map"}
    for stats in info.values():
        assert {"hits", "misses", "maxsize", "currsize"} <= set(stats)
        assert stats["maxsize"] is not None      # explicit bound, never inf


# ------------------------------------------------- cross-process determinism
_SUBPROC = r"""
import json, sys
from repro.tune import TuneCache, tune_attention
cache = TuneCache(root=sys.argv[1])
res = tune_attention(seq=2048, head_dim=64, causal=True, cache=cache)
print(json.dumps({"key": res.key, "candidate": res.candidate.key(),
                  "source": res.source}))
"""


@pytest.mark.slow
def test_subprocess_same_key_same_choice(tmp_path):
    """Two processes with one cache key pick one candidate (ISSUE acceptance).

    Run 1 (cold shared cache) decides by sim ranking; run 2 hits the cache;
    run 3 (its own empty cache) re-derives the same choice from scratch —
    the ranking itself, not the store, is what carries the determinism."""
    def run(root):
        r = subprocess.run(
            [sys.executable, "-c", _SUBPROC, str(root)], capture_output=True,
            text=True, timeout=300, cwd=REPO_ROOT,
            env={**os.environ, "PYTHONPATH": "src"})
        assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
        return json.loads(r.stdout.strip().splitlines()[-1])

    shared = tmp_path / "shared"
    a = run(shared)
    b = run(shared)
    c = run(tmp_path / "fresh")
    assert a["key"] == b["key"] == c["key"]
    assert a["candidate"] == b["candidate"] == c["candidate"]
    assert (a["source"], b["source"], c["source"]) == ("sim", "cache", "sim")


# --------------------------------------- tuned ≡ hand-configured (bitwise)
GEOMETRIES = [
    # three registry configs' attention geometries (reduced): MHA + GQA
    pytest.param("stablelm-1.6b", False, id="stablelm-full"),
    pytest.param("qwen1.5-110b", True, id="qwen-causal"),
    pytest.param("mistral-nemo-12b", True, id="mistral-causal"),
]


@pytest.mark.parametrize("arch,causal", GEOMETRIES)
def test_tuned_bitwise_equals_handpicked(arch, causal, tmp_path):
    cfg = registry.get(arch).reduced()
    B, S = 1, 256
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, cfg.n_heads, S, cfg.head_dim),
                          jnp.float32)
    k = jax.random.normal(ks[1], (B, cfg.n_kv_heads, S, cfg.head_dim),
                          jnp.float32)
    v = jax.random.normal(ks[2], (B, cfg.n_kv_heads, S, cfg.head_dim),
                          jnp.float32)
    cache = TuneCache(root=str(tmp_path))
    res = tune_attention(seq=S, head_dim=cfg.head_dim, dtype=q.dtype,
                         causal=causal, n_heads=cfg.n_heads,
                         n_kv_heads=cfg.n_kv_heads, cache=cache)
    cand = res.candidate

    def tuned(q, k, v):
        return dash_attention(q, k, v, causal=causal, interpret=True,
                              tune=True).astype(jnp.float32).sum()

    def hand(q, k, v):
        return dash_attention(q, k, v, causal=causal, interpret=True,
                              schedule=cand.schedule, block=cand.block_q,
                              worker_parallel=cand.worker_parallel
                              ).astype(jnp.float32).sum()

    gt = jax.grad(tuned, argnums=(0, 1, 2))(q, k, v)
    gh = jax.grad(hand, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gt, gh, "qkv"):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"d{name} ({arch})")


# ------------------------------------------------------ launch smoke (slow)
@pytest.mark.slow
def test_launch_train_tune_track_smoke(tmp_path):
    """`--tune sim --track --verify` end to end: the tracker JSONL carries the
    tuner decision, per-step throughput + utilization-vs-modeled, the live
    fingerprint stream, and the cache/run summaries (ISSUE acceptance)."""
    track = tmp_path / "run.jsonl"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "stablelm-1.6b",
         "--reduced", "--steps", "3", "--batch", "2", "--seq", "128",
         "--tune", "sim", "--track", str(track), "--verify",
         "--verify-out", str(tmp_path / "digest_chain.json")],
        capture_output=True, text=True, timeout=600, cwd=REPO_ROOT,
        env={**os.environ, "PYTHONPATH": "src",
             "REPRO_TUNE_CACHE": str(tmp_path / "tune")})
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "[tune]" in r.stdout

    events = [json.loads(l) for l in open(track)]
    kinds = {e["event"] for e in events}
    assert {"run_config", "tune_choice", "tune_cache", "step", "fingerprint",
            "cache_info", "run_summary"} <= kinds
    steps = [e for e in events if e["event"] == "step"]
    assert [e["step"] for e in steps] == [1, 2, 3]
    for e in steps:
        assert e["tokens_per_s"] > 0
        assert 0 <= e["utilization_vs_modeled"]
        assert "loss" in e and "grad_norm" in e
    # the tuner decision is recorded and the fingerprint chain stayed clean
    choice = next(e for e in events if e["event"] == "tune_choice")
    assert choice["candidate"] and choice["source"] in ("sim", "cache")
    assert not [e for e in events if e["event"] == "fingerprint_divergence"]
    summary = next(e for e in events if e["event"] == "run_summary")
    assert summary["fingerprint_ok"] is True
