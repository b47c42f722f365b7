"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b --reduced \
        --steps 200 --batch 8 --seq 256 [--resume] [--ckpt-dir DIR]

Runs a real training loop (synthetic or memmap data) with periodic async
checkpointing and exact resume (stateless data sampler + full optimizer state)
on one device. On CPU this trains the reduced configs (~100M-class models at
--reduced-large); ``chip_smoke.py`` drives the same functions at published
widths on a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.ckpt import checkpoint as C
from repro.configs import registry
from repro.data.pipeline import DataConfig, make_source
from repro.launch.compile_cache import use_compile_cache
from repro.train import optimizer as O
from repro.train import step as S


def build(cfg, tcfg):
    return S.jit(cfg, S.make_train_step(cfg, tcfg), donate_argnums=(0,))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--reduced-large", action="store_true",
                    help="~100M-param reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", default=None, choices=[None, "int8"])
    ap.add_argument("--opt", default="adamw", choices=["adamw", "adafactor"])
    ap.add_argument("--data", default=None, help="memmap token file (else synthetic)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--die-at-step", type=int, default=None,
                    help="simulate a hard failure (fault-tolerance demo)")
    ap.add_argument("--verify", action="store_true",
                    help="audit the lowered step for nondeterminism-prone "
                         "primitives, record a per-step state digest chain, "
                         "and ship a live uint32 fingerprint in metrics")
    ap.add_argument("--verify-every", type=int, default=1,
                    help="digest the state every N steps (digesting gathers "
                         "the full state to host)")
    ap.add_argument("--verify-out", default=None,
                    help="write the digest-chain JSON here (default: "
                         "<ckpt-dir>/digest_chain.json or ./digest_chain.json)")
    ap.add_argument("--heartbeat", action="store_true",
                    help="enable straggler/hang monitor (launch/heartbeat.py)")
    ap.add_argument("--tune", default="off", choices=["off", "sim"],
                    help="resolve the attention schedule knobs with "
                         "repro.tune before training: 'sim' ranks by modeled "
                         "makespan (pure, reproducible). The choice is "
                         "logged.")
    ap.add_argument("--track", default=None, metavar="JSONL",
                    help="write a repro.obs event stream here: per-step "
                         "throughput, fingerprint + divergence events (with "
                         "--verify), tuner decisions, attention tilings")
    ap.add_argument("--track-reference", default=None, metavar="JSONL",
                    help="a previous run's --track file; with --verify, the "
                         "live fingerprint stream is compared against it and "
                         "the first mismatch fires a fingerprint_divergence "
                         "event")
    ap.add_argument("--trace-out", default=None, metavar="TRACE.json",
                    help="write a Perfetto/Chrome-trace JSON of the run: "
                         "per-step phase spans (data/step/digest/ckpt) plus "
                         "the attention schedule's modeled per-worker lanes "
                         "(repro.obs.export); works with or without --track. "
                         "Device time comes from a profiler trace, in which "
                         "the same spans appear as repro.<phase>")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="arm a seeded repro.faults checkpoint-IO plan: "
                         "saves at random --ckpt-every multiples fail their "
                         "first 1..IO_RETRIES write attempts and are absorbed "
                         "by the writer's bounded deterministic retry — the "
                         "run's loss/digests are unchanged (README "
                         "§Robustness)")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = registry.get(args.arch)
    if args.reduced_large:
        cfg = cfg.reduced(d_model=768, n_heads=12, n_kv_heads=12, head_dim_=64,
                          d_ff=3072, vocab=32_000, vocab_pad=512,
                          n_layers=12 * len(cfg.block_pattern))
    elif args.reduced:
        cfg = cfg.reduced()

    from repro.obs import (CompositeTracker, DivergenceAlarm, MemoryTracker,
                           Profiler, StepMeter, open_tracker,
                           record_state_digests)
    tracker = open_tracker(args.track)
    trace_mem = None
    if args.trace_out is not None:
        # --trace-out needs the span stream even without --track: tee into an
        # in-memory tracker and export at the end
        trace_mem = MemoryTracker()
        tracker = CompositeTracker([tracker, trace_mem])
    run_id = f"train-{args.arch}-s{args.seed}"
    prof = Profiler(tracker, run_id=run_id)
    tracker.log("run_config", {
        "arch": args.arch, "steps": args.steps, "batch": args.batch,
        "seq": args.seq, "microbatches": args.microbatches, "run_id": run_id,
        "seed": args.seed, "tune": args.tune, "verify": bool(args.verify)})

    if args.tune != "off":
        from repro.tune import tune_attention
        tres = tune_attention(seq=args.seq, head_dim=cfg.head_dim,
                              dtype=cfg.dtype_name, causal=True,
                              n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                              mode=args.tune, tracker=tracker)
        print(f"[tune] {tres.candidate.key()} source={tres.source} "
              f"modeled_makespan={tres.modeled_makespan_s:.3e}s", flush=True)

    tcfg = S.TrainConfig(
        opt=O.OptConfig(name=args.opt, lr=args.lr, total_steps=args.steps),
        microbatches=args.microbatches, remat=True,
        grad_compression=args.grad_compression, seed=args.seed,
        digest_metrics=args.verify)

    data = make_source(DataConfig(seed=args.seed, batch=args.batch,
                                  seq=args.seq, vocab=cfg.vocab,
                                  path=args.data))
    state = S.init_state(cfg, tcfg, jax.random.PRNGKey(args.seed))
    start = 0
    if args.resume and args.ckpt_dir and C.latest_step(args.ckpt_dir) is not None:
        start = C.latest_step(args.ckpt_dir)
        state = C.restore(args.ckpt_dir, start, state)
        print(f"resumed from step {start}")

    step_fn = build(cfg, tcfg)
    chain, chain_path = None, None
    if args.verify:
        from repro.verify import trace as VT
        from repro.verify.digest import DigestChain

        # audit the jitted step's own trace — no second model trace
        findings = VT.audit_jaxpr(step_fn.trace(state, data.batch(start)).jaxpr)
        if findings:
            for f in findings:
                print(f"[verify] {f}", flush=True)
            raise SystemExit(3)
        print("[verify] train step jaxpr clean", flush=True)
        chain_path = args.verify_out or (
            os.path.join(args.ckpt_dir, "digest_chain.json")
            if args.ckpt_dir else "digest_chain.json")
        chain = DigestChain()
        if start > 0 and os.path.exists(chain_path):
            # resume the chain at the restored step: keep the records up to
            # `start` so the resumed run's head stays comparable to a
            # straight run's (crash/resume ≡ straight, the repo contract)
            with open(chain_path) as f:
                prior = DigestChain.from_json(f.read())
            chain = DigestChain(
                records=[(s, d) for s, d in prior.records if s <= start])
            print(f"[verify] resumed digest chain at step {start} "
                  f"({len(chain)} records)", flush=True)

    def _persist_chain():
        parent = os.path.dirname(chain_path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(chain_path, "w") as f:
            f.write(chain.to_json())

    alarm = None
    if args.verify:
        alarm = (DivergenceAlarm.from_jsonl(args.track_reference,
                                            tracker=tracker)
                 if args.track_reference else DivergenceAlarm(tracker=tracker))

    monitor = None
    if args.heartbeat:
        from repro.launch.heartbeat import Monitor
        monitor = Monitor(on_hang=lambda: os._exit(42))
        monitor.start_watchdog()

    injector = None
    if args.chaos is not None:
        from repro.faults import FaultPlan, Injector
        plan = FaultPlan.seeded_ckpt(args.chaos, steps=args.steps,
                                     every=args.ckpt_every, rate=0.5,
                                     max_failures=C.IO_RETRIES,
                                     name=f"train-chaos-{args.chaos}")
        injector = Injector(plan, tracker=tracker)
        print(f"[chaos] armed {plan.key()} ({len(plan)} flaky saves; all "
              "within the writer's retry budget)", flush=True)

    meter = StepMeter()
    # --trace-out implies per-step sync + events too: span durations must
    # time real step work, not dispatch
    tracking = args.track is not None or args.trace_out is not None
    tokens_per_step = args.batch * args.seq
    from repro.faults import armed_checkpoint
    from repro.kernels.ops import log_tilings
    pending = None
    t0 = time.time()
    # armed_checkpoint(None) is a no-op; when --chaos armed an injector, the
    # hook must stay installed through the *final* async save's join — the
    # writer thread consults it mid-write. The first step traces the model:
    # each attention call's tiling lands in the tracker as "attn_tiling".
    with armed_checkpoint(injector), log_tilings(tracker):
        for step in range(start, args.steps):
            if args.die_at_step is not None and step == args.die_at_step:
                print(f"simulated failure at step {step}", flush=True)
                os._exit(17)
            with prof.span("train_data", scope=f"step:{step + 1}",
                           lane="host", step=step + 1):
                batch = data.batch(step)
            ts = time.time()
            step_span = prof.begin("train_step", scope=f"step:{step + 1}",
                                   lane="device", step=step + 1)
            state, metrics = step_fn(state, batch)
            if tracking:
                jax.block_until_ready(metrics["loss"])
            prof.end(step_span)
            if chain is not None and (step + 1) % args.verify_every == 0:
                with prof.span("train_digest", scope=f"step:{step + 1}",
                               lane="host", step=step + 1):
                    # one hashing pass feeds the chain AND (when tracking)
                    # the per-leaf digest record diff_runs triages with
                    record_state_digests(state, step + 1, tracker=tracker,
                                         chain=chain)
            if monitor is not None:
                jax.block_until_ready(metrics["loss"])
                if monitor.step(time.time() - ts) == "straggler":
                    print(f"[heartbeat] straggler step {step} "
                          f"({time.time() - ts:.2f}s vs baseline "
                          f"{monitor.baseline:.2f}s)", flush=True)
            if tracking:
                # block before reading the clock: the event times real step
                # work, not dispatch. The sync only happens when --track
                # asked for it.
                jax.block_until_ready(metrics["loss"])
                payload = meter.update(tokens_per_step, time.time() - ts)
                payload.update(S.step_event(metrics))
                tracker.log("step", payload, step=step + 1)
            if alarm is not None and "state_fingerprint" in metrics:
                if alarm.observe(step + 1, metrics["state_fingerprint"]):
                    print(f"[verify] fingerprint divergence at step "
                          f"{step + 1} (see tracker)", flush=True)
            if (step + 1) % args.log_every == 0 or step == start:
                m = {k: float(v) for k, v in metrics.items()}
                dt = (time.time() - t0) / max(1, step + 1 - start)
                print(f"step {step + 1} loss={m['loss']:.4f} "
                      f"gnorm={m['grad_norm']:.3f} lr={m['lr']:.2e} "
                      f"({dt * 1e3:.0f} ms/step)", flush=True)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                with prof.span("train_ckpt", scope=f"step:{step + 1}",
                               lane="host", step=step + 1):
                    if pending is not None:
                        pending.join()
                    pending = C.save(args.ckpt_dir, step + 1, state,
                                     async_=True)
                    if chain is not None:   # chain survives a crash post-save
                        _persist_chain()
        if pending is not None:
            pending.join()
    if monitor is not None:
        monitor.stop()
    final_loss = float(metrics["loss"])
    summary = {"final_step": args.steps, "final_loss": final_loss}
    if injector is not None:
        summary["chaos_plan"] = injector.plan.key()
        summary["chaos_faults_landed"] = len(injector.history)
        summary["chaos_landing_digest"] = injector.history_digest()
        print(f"[chaos] {len(injector.history)} injected IO failures "
              f"absorbed by retry; landing digest "
              f"{injector.history_digest()[:16]}", flush=True)
    if chain is not None:
        _persist_chain()
        print(f"[verify] digest chain head {chain.head} "
              f"({len(chain)} records) -> {chain_path}", flush=True)
        summary["digest_chain_head"] = chain.head
    if alarm is not None:
        summary["fingerprint_ok"] = alarm.ok
    if tracking:
        from repro.masks import cache_info
        tracker.log("cache_info", cache_info())
        tracker.log("run_summary", dict(summary,
                                        tokens_per_s_avg=meter.event()
                                        .get("tokens_per_s_avg", 0.0)))
    if args.trace_out is not None:
        from repro.obs import export as EX
        events = EX.spans_to_trace(trace_mem.events, process_name=run_id)
        events += EX.attention_timeline(args.seq, cfg.head_dim, causal=True)
        EX.write_trace(args.trace_out, events)
        print(f"[trace] {len(events)} events -> {args.trace_out}", flush=True)
    tracker.close()
    print(json.dumps(summary))
    return final_loss


if __name__ == "__main__":
    main()
