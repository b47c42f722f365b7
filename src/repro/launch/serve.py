"""Serving driver: static batch or continuous batching over paged KV slots.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --reduced \
        --batch 4 --prompt-len 64 --gen 32
    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --reduced \
        --engine continuous --requests 8 --slots 4 --gen 32

The static path exercises the same prefill/decode step functions the dry-run
cells lower at 32k/500k scale; the continuous path drives the batch-invariant
deterministic engine (``repro.serve.ContinuousEngine`` — README §Serving):
chunked prefill + in-flight batched decode over paged KV cache slots, with
per-request tokens that are bitwise independent of co-batching.

``--tp N`` shards the continuous engine over an N-way model-parallel mesh
(``repro.serve.sharded``); ``--mesh RxC`` uses an (R, C) ``(data, model)``
mesh instead.  Tokens are bitwise identical for every choice — the
topology-invariance contract (README §Serving) — so these flags are pure
throughput/capacity knobs.  On CPU, force devices first, e.g.::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
        --reduced --engine continuous --tp 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.launch.specs import make_batch
from repro.configs.base import InputShape
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T
from repro.serve.engine import ContinuousEngine, SampleConfig


def _static(cfg, params, args, key):
    shape = InputShape("serve", "prefill", args.prompt_len, args.batch)
    data = make_batch(cfg, shape, key)
    max_seq = args.prompt_len + args.gen

    prefill = jax.jit(lambda p, b: T.prefill_step(p, b, cfg, max_seq=max_seq))
    decode = jax.jit(lambda p, c, t, pos, cx: T.decode_step(p, c, t, pos, cfg,
                                                            cross_x=cx))
    t0 = time.time()
    logits, caches, cross_x = prefill(params, data["batch"])
    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    t1 = time.time()
    out_tokens = [tok]
    pos = args.prompt_len + (cfg.frontend_len if cfg.frontend == "vision" else 0)
    for i in range(args.gen - 1):
        logits, caches = decode(params, caches, tok, jnp.asarray(pos + i), cross_x)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t2 = time.time()
    gen = jnp.concatenate(out_tokens, axis=1)
    tps = args.batch * (args.gen - 1) / max(1e-9, t2 - t1)
    print(f"prefill {args.batch}x{args.prompt_len} in {t1 - t0:.2f}s; "
          f"decode {args.gen - 1} steps at {tps:.1f} tok/s")
    print("sample tokens[0,:16]:", gen[0, :16].tolist())
    return gen


def _mesh_from_args(args):
    """None (single device), ``--tp N`` → an (N,) "model" mesh, or
    ``--mesh RxC`` → an (R, C) ("data", "model") mesh."""
    if args.mesh:
        shape = tuple(int(v) for v in args.mesh.lower().split("x"))
        if len(shape) != 2:
            raise SystemExit(f"--mesh wants RxC (e.g. 2x2), got {args.mesh!r}")
        names = ("data", "model")
    elif args.tp > 1:
        shape, names = (args.tp,), ("model",)
    else:
        return None
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < need:
        raise SystemExit(
            f"mesh {shape} needs {need} devices, have {len(devs)} "
            f"(on CPU: XLA_FLAGS=--xla_force_host_platform_device_count={need})")
    return jax.sharding.Mesh(np.array(devs[:need]).reshape(shape), names)


def _spec_kwargs(args):
    """--spec-k/--draft-model -> ContinuousEngine speculation kwargs.

    ``--draft-model self`` (the default) self-drafts; ``--draft-model auto``
    takes the registry pairing (:data:`repro.configs.registry.DRAFTERS`);
    any other value names a drafter arch.  Tokens are bitwise identical to
    ``--spec-k 0`` in every case (README §Serving)."""
    if not args.spec_k:
        return {}
    kw = {"spec_k": args.spec_k}
    draft = args.draft_model
    if draft == "auto":
        draft = registry.drafter_for(args.arch) or "self"
    if draft != "self":
        dcfg = registry.get(draft)
        if args.reduced:
            dcfg = dcfg.reduced()
        kw["draft_cfg"] = dcfg
        kw["draft_params"] = T.init(dcfg, jax.random.PRNGKey(args.seed + 1))
        print(f"drafter: {draft} (exact acceptance; tokens bitwise equal "
              "to --spec-k 0)")
    return kw


def _continuous(cfg, params, args):
    from repro.obs import CompositeTracker, MemoryTracker, open_tracker
    page = 16
    mesh = _mesh_from_args(args)
    tracker = open_tracker(args.track)
    trace_mem = None
    if args.trace_out is not None:
        trace_mem = MemoryTracker()
        tracker = CompositeTracker([tracker, trace_mem])
    run_id = f"serve-{args.arch}-s{args.seed}"
    if mesh is not None:
        print(f"mesh: {dict(mesh.shape)} over {mesh.size} devices "
              f"(tokens bitwise identical to single-device)")
    injector = None
    if args.chaos is not None:
        from repro.faults import FaultPlan, Injector
        plan = FaultPlan.seeded(args.chaos, steps=16 * args.gen, rate=0.2,
                                name=f"serve-chaos-{args.chaos}")
        injector = Injector(plan)
        print(f"chaos armed: {plan.key()} ({len(plan)} scheduled faults; "
              "tokens stay bitwise identical — README §Robustness)")
    max_seq = -(-(args.prompt_len + args.gen) // page) * page
    eng = ContinuousEngine(cfg, params, n_slots=args.slots, max_seq=max_seq,
                           page_size=page, prefill_chunk=min(32, args.prompt_len),
                           scfg=SampleConfig(seed=args.seed), mesh=mesh,
                           faults=injector, tracker=tracker, run_id=run_id,
                           **_spec_kwargs(args))
    rng = np.random.RandomState(args.seed)
    for i in range(args.requests):
        plen = rng.randint(max(1, args.prompt_len // 2), args.prompt_len + 1)
        eng.submit(rng.randint(1, cfg.vocab, size=plen).tolist(),
                   req_id=i, max_new_tokens=args.gen)
    t0 = time.time()
    out = eng.run()
    dt = time.time() - t0
    total = sum(len(v) for v in out.values())
    print(f"continuous: {args.requests} requests / {args.slots} slots, "
          f"{total} tokens in {dt:.2f}s ({total / max(1e-9, dt):.1f} tok/s, "
          f"{eng.decode_steps} decode steps)")
    if eng.spec is not None:
        print(f"speculation: k={eng.spec.k} "
              f"{'self-draft' if eng.spec.self_draft else 'separate drafter'}, "
              f"{eng.spec.rounds} rounds, acceptance "
              f"{eng.spec.acceptance_rate():.3f} "
              f"({eng.spec.accepted}/{eng.spec.drafted - eng.spec.truncated} "
              "evaluated drafts)")
    if injector is not None:
        print(f"chaos: {len(injector.history)} faults landed, "
              f"{eng.preemptions} preemptions, landing digest "
              f"{injector.history_digest()[:16]}")
    if args.trace_out is not None:
        from repro.obs import export as EX
        events = EX.spans_to_trace(trace_mem.events, process_name=run_id)
        events += EX.attention_timeline(max_seq, cfg.head_dim, causal=True,
                                        measure=True)
        EX.write_trace(args.trace_out, events)
        print(f"[trace] {len(events)} events -> {args.trace_out}", flush=True)
    tracker.close()
    print("request 0 tokens:", out[0][:16].tolist())
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--engine", choices=("static", "continuous"),
                    default="static")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--greedy", action="store_true", default=True)
    ap.add_argument("--tp", type=int, default=1,
                    help="model-parallel degree for --engine continuous "
                         "(tokens are bitwise invariant to this)")
    ap.add_argument("--mesh", default=None,
                    help='mesh shape "RxC" as (data, model), e.g. 2x2; '
                         "overrides --tp")
    ap.add_argument("--spec-k", type=int, default=0, metavar="K",
                    help="speculative decoding: draft K tokens per round "
                         "(--engine continuous); acceptance is exact, so "
                         "tokens/logprobs are bitwise equal to --spec-k 0 "
                         "(README §Serving)")
    ap.add_argument("--draft-model", default="self",
                    help='drafter for --spec-k: "self" (default, acceptance '
                         '1.0 by construction), "auto" (registry pairing), '
                         "or a registry arch name")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="arm a seeded repro.faults plan (pool exhaustion, "
                         "slot revocation, decode stalls) against the "
                         "continuous engine; tokens are bitwise invariant "
                         "to it (README §Robustness)")
    ap.add_argument("--track", default=None, metavar="JSONL",
                    help="write the engine's repro.obs event stream here "
                         "(serve_* events + profiler spans; --engine "
                         "continuous). Tokens are bitwise invariant to "
                         "tracking (tests/test_obs_prof.py)")
    ap.add_argument("--trace-out", default=None, metavar="TRACE.json",
                    help="write a Perfetto/Chrome-trace JSON: request/queue/"
                         "prefill/decode spans plus the attention schedule "
                         "timeline with modeled and achieved lanes "
                         "(repro.obs.export); works with or without --track")
    args = ap.parse_args(argv)

    if (args.tp > 1 or args.mesh) and args.engine != "continuous":
        ap.error("--tp/--mesh apply to --engine continuous")
    if args.chaos is not None and args.engine != "continuous":
        ap.error("--chaos applies to --engine continuous")
    if args.spec_k and args.engine != "continuous":
        ap.error("--spec-k applies to --engine continuous")
    if args.spec_k < 0:
        ap.error("--spec-k must be >= 0")
    if (args.track or args.trace_out) and args.engine != "continuous":
        ap.error("--track/--trace-out apply to --engine continuous")
    use_compile_cache()

    cfg = registry.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(args.seed)
    params = T.init(cfg, key)
    if args.engine == "continuous":
        return _continuous(cfg, params, args)
    return _static(cfg, params, args, key)


if __name__ == "__main__":
    main()
