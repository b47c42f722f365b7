"""Smoke run of the DASH train and serve paths on a TPU.

    python chip_smoke.py                # phases 1-4 on one chip
    python chip_smoke.py --four-chips   # TP-sharded serving on four chips

One process drives every phase, through the functions the entry points use
(``launch/train.py``'s ``build``, ``train.step.init_state``,
``data.pipeline.make_source`` and ``serve.ContinuousEngine``), at the
published widths of ``stablelm-1.6b`` with its depth cut to 4 of 24 layers
(the whole model does not fit one 16 GB chip for training). Weights are random
and made from ``--seed``.

1. Device: the first device must be a TPU; there is no CPU fallback.
2. DASH kernels: ``dash_attention`` forward and gradients at B 1, H 32,
   S 4096, D 64, bf16, causal, worker-parallel and serialized, against the
   f32 reference in ``kernels/ref.py``; the two realizations agree bitwise and
   a second call repeats the bits.
3. Train: 3 AdamW steps at batch 2 x 4096 for ``attention_impl`` "xla" and
   "pallas"; finite losses starting near ln(vocab), and two runs from the same
   seed end in the same state digest.
4. Serve: ``ContinuousEngine`` with 4 slots and 4 requests of 512-1024 prompt
   tokens and 32 new tokens; finite logits and logprobs, and request 0 served
   alone gives the same tokens as in the co-batch. Then a printed smoke timing
   of the paged decode step compiled with and without XLA's excess precision.

``--four-chips`` runs only the sharded engine at tp=4 against the
single-device engine: tokens and sampled logprobs must be bitwise equal.

Any failed check exits non-zero. The last line of a passing run is one JSON
object naming the device. Times printed here are smoke timings, not
benchmark numbers.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# stablelm-1.6b at published widths, cut to 4 layers
ARCH, N_LAYERS = "stablelm-1.6b", 4
# phase 2 shape (one stablelm attention layer at batch 1)
KB, KH, KS, KD = 1, 32, 4096, 64
# |kernel - f32 reference| / max|reference| bounds. Outputs and gradients
# leave the kernels in bf16 (relative rounding 2**-8 ~ 3.9e-3), and their
# f32 tiles run on the MXU, whose default precision rounds f32 operands to
# bf16; over S = 4096 terms of mixed sign those roundings stay well under
# 2e-2 of the largest element.
TOL = {"out": 2e-2, "dq": 2e-2, "dk": 2e-2, "dv": 2e-2}
# phase 3: random init spreads the logits (std ~0.9 at width 2048), which
# adds about var/2 to the uniform-prediction loss ln(vocab)
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, LOSS0_BAND = 2, 4096, 3, 1.0
# phase 4
N_SLOTS, N_REQUESTS, PROMPT_MIN, PROMPT_MAX, GEN = 4, 4, 512, 1024, 32
PAGE, PREFILL_CHUNK = 16, 256
DECODE_ITERS = 50


class SmokeFailure(RuntimeError):
    """A check of this smoke run failed."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def same_bits(a, b) -> bool:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and \
        a.tobytes() == b.tobytes()


def phase_device(n_chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    print(f"[device] platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    check(d0.platform == "tpu",
          f"found platform {d0.platform!r} ({d0.device_kind}); this smoke "
          "run needs a TPU and has no CPU fallback")
    check(len(devs) >= n_chips, f"needs {n_chips} chips, found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def phase_kernels(b=KB, h=KH, s=KS, d=KD, seed=0):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.kernels.ops import dash_attention

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, do = (jax.random.normal(kk, (b, h, s, d), jnp.float32)
                   .astype(jnp.bfloat16) for kk in ks)

    def fwd_bwd(worker_parallel):
        def f(q, k, v, do):
            out, pull = jax.vjp(functools.partial(
                dash_attention, causal=True, worker_parallel=worker_parallel),
                q, k, v)
            return (out,) + pull(do)
        return jax.jit(f)

    def reference(q, k, v, do):
        def one_head(x):
            qh, kh, vh, doh = (t[None].astype(jnp.float32) for t in x)
            out, lse = ref.mha_fwd(qh, kh, vh, causal=True)
            dq, dk, dv = ref.mha_bwd(qh, kh, vh, out, lse, doh, causal=True)
            return out[0], dq[0], dk[0], dv[0]
        flat = tuple(t.reshape(b * h, s, d) for t in (q, k, v, do))
        return tuple(r.reshape(b, h, s, d)
                     for r in jax.lax.map(one_head, flat))

    t0 = time.perf_counter()
    par = jax.block_until_ready(fwd_bwd(True)(q, k, v, do))
    t1 = time.perf_counter()
    ser = jax.block_until_ready(fwd_bwd(False)(q, k, v, do))
    again = jax.block_until_ready(fwd_bwd(True)(q, k, v, do))
    print(f"[kernels] B{b} H{h} S{s} D{d} bf16 causal: first call "
          f"(compile + run) {t1 - t0:.1f}s (smoke timing)", flush=True)
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(jax.jit(reference)(q, k, v, do))
    for name, got, ref_x in zip(("out", "dq", "dk", "dv"), par, want):
        got = np.asarray(got.astype(jnp.float32))
        ref_x = np.asarray(ref_x)
        check(np.isfinite(got).all(), f"kernel {name} has non-finite values")
        err = float(np.abs(got - ref_x).max() / np.abs(ref_x).max())
        print(f"[kernels] {name}: max|kernel - f32 ref| / max|ref| = {err!r} "
              f"(bound {TOL[name]})", flush=True)
        check(err <= TOL[name], f"{name} off the f32 reference: {err!r}")
    for name, a, c, r in zip(("out", "dq", "dk", "dv"), par, ser, again):
        check(same_bits(a, c),
              f"{name}: worker-parallel and serialized differ in bits")
        check(same_bits(a, r), f"{name}: a second call changed the bits")
    print("[kernels] worker-parallel == serialized bitwise; repeat call "
          "bitwise equal", flush=True)


def model_config(impl="xla"):
    from repro.configs import registry
    return registry.get(ARCH).replace(n_layers=N_LAYERS, attention_impl=impl)


def phase_train(cfg, batch=TRAIN_BATCH, seq=TRAIN_SEQ, steps=TRAIN_STEPS,
                seed=0):
    import jax
    from repro.data.pipeline import DataConfig, make_source
    from repro.launch.train import build
    from repro.train import optimizer as O
    from repro.train import step as S
    from repro.verify.digest import tree_digest

    tcfg = S.TrainConfig(opt=O.OptConfig(name="adamw", total_steps=steps),
                         remat=True, seed=seed)
    data = make_source(DataConfig(seed=seed, batch=batch, seq=seq,
                                  vocab=cfg.vocab))
    step_fn = build(cfg, tcfg)
    tag = f"[train {cfg.attention_impl}]"
    digests = []
    for run in range(2):
        state = S.init_state(cfg, tcfg, jax.random.PRNGKey(seed))
        if run == 0:
            t0 = time.perf_counter()
            step_fn = step_fn.lower(state, data.batch(0)).compile()
            print(f"{tag} compile {time.perf_counter() - t0:.1f}s "
                  f"(smoke timing)", flush=True)
        losses, times = [], []
        for i in range(steps):
            batch_i = data.batch(i)
            t0 = time.perf_counter()
            state, metrics = step_fn(state, batch_i)
            losses.append(float(jax.block_until_ready(metrics["loss"])))
            times.append(time.perf_counter() - t0)
        digests.append(tree_digest(state))
        del state
        check(all(math.isfinite(x) for x in losses),
              f"{tag} non-finite loss: {losses}")
        print(f"{tag} run {run}: losses {losses} step wall "
              f"{[round(t, 3) for t in times]}s (smoke timing, "
              f"{batch}x{seq} tokens/step)", flush=True)
        if run == 0:
            ln_v = math.log(cfg.vocab)
            check(abs(losses[0] - ln_v) <= LOSS0_BAND,
                  f"{tag} first loss {losses[0]} not within {LOSS0_BAND} of "
                  f"ln(vocab) = {ln_v:.3f}")
    print(f"{tag} state digest {digests[0][:16]}... run 1 "
          f"{'==' if digests[0] == digests[1] else '!='} run 0", flush=True)
    check(digests[0] == digests[1],
          f"{tag} two same-seed runs ended in different state digests")


def _requests(cfg, seed):
    import numpy as np
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab,
                        size=rng.randint(PROMPT_MIN, PROMPT_MAX + 1)).tolist()
            for _ in range(N_REQUESTS)]


def _engine(cfg, params, seed, mesh=None, capture=False):
    from repro.serve.engine import ContinuousEngine, SampleConfig
    max_seq = -(-(PROMPT_MAX + GEN) // PAGE) * PAGE
    return ContinuousEngine(cfg, params, n_slots=N_SLOTS, max_seq=max_seq,
                            page_size=PAGE, prefill_chunk=PREFILL_CHUNK,
                            scfg=SampleConfig(temperature=1.0, top_k=64,
                                              seed=seed),
                            mesh=mesh, capture_prefill_logits=capture)


def _serve(eng, prompts):
    for i, p in enumerate(prompts):
        eng.submit(p, req_id=i, max_new_tokens=GEN)
    t0 = time.perf_counter()
    out = eng.run()
    return out, time.perf_counter() - t0


def phase_serve(cfg, seed=0):
    import jax
    import numpy as np
    from repro.models import transformer as T

    params = T.init(cfg, jax.random.PRNGKey(seed))
    prompts = _requests(cfg, seed)
    eng = _engine(cfg, params, seed, capture=True)
    out, dt = _serve(eng, prompts)
    n_tok = sum(len(t) for t in out.values())
    print(f"[serve] {N_REQUESTS} requests (prompts "
          f"{[len(p) for p in prompts]}) / {N_SLOTS} slots: {n_tok} tokens "
          f"in {dt:.1f}s incl. compile (smoke timing)", flush=True)
    for rid in range(N_REQUESTS):
        toks = out.get(rid)
        check(toks is not None and len(toks) == GEN,
              f"request {rid} returned {None if toks is None else len(toks)} "
              f"tokens, want {GEN}")
        check(((toks >= 0) & (toks < cfg.padded_vocab)).all(),
              f"request {rid} tokens out of range")
        check(np.isfinite(eng.result_logprobs[rid]).all(),
              f"request {rid} logprobs not finite")
        check(np.isfinite(eng.prefill_logits[rid].astype(np.float32)).all(),
              f"request {rid} prefill logits not finite")
    del eng
    alone = _engine(cfg, params, seed)
    out0, _ = _serve(alone, prompts[:1])
    check(same_bits(out0[0], out[0]),
          "request 0 alone != request 0 in the co-batch")
    print(f"[serve] request 0 alone == in co-batch bitwise "
          f"({out[0][:8].tolist()}...)", flush=True)
    decode_precision_cost(alone)


def decode_precision_cost(eng, iters=DECODE_ITERS):
    """Decode-shaped calls ((n_slots, 1) tokens at position PROMPT_MAX) of
    the engine's paged step, compiled with ``fold.exact_jit``, against the
    same step under plain ``jax.jit``; both warm, timed in the order
    exact, plain, plain, exact. Printed only: a smoke timing of what turning
    off XLA's excess precision costs the decode step, not a check."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as T

    lay = eng.cache.layout
    n = lay.n_slots
    args = (jnp.zeros((n, 1), jnp.int32),
            jnp.full((n, 1), PROMPT_MAX, jnp.int32),
            eng.cache.device_page_table(),
            jnp.full((n,), lay.trash_page, jnp.int32),
            jnp.arange(n, dtype=jnp.int32) % lay.page_size)
    steps = {"exact": eng._step,
             "plain": jax.jit(functools.partial(T.paged_step, cfg=eng.cfg))}
    first = {name: jax.block_until_ready(
        fn(eng.params, eng.cache.pools, *args)) for name, fn in steps.items()}
    pools = first["exact"][1]
    rates = {name: [] for name in steps}
    for name in ("exact", "plain", "plain", "exact"):
        t0 = time.perf_counter()
        for _ in range(iters):
            logits, pools = steps[name](eng.params, pools, *args)
        jax.block_until_ready(logits)
        rates[name].append(n * iters / (time.perf_counter() - t0))
    print(f"[serve] paged decode step, {n} rows x {iters} calls, warm: "
          f"exact_jit {rates['exact']} tokens/s, jax.jit {rates['plain']} "
          f"tokens/s; logits bitwise equal: "
          f"{same_bits(first['exact'][0], first['plain'][0])} (smoke timing)",
          flush=True)


def bytes_in_use(devs):
    return [d.memory_stats()["bytes_in_use"] for d in devs]


def phase_four_chips(cfg, seed=0):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.models import transformer as T

    params = T.init(cfg, jax.random.PRNGKey(seed))
    prompts = _requests(cfg, seed)
    one = _engine(cfg, params, seed)
    out1, dt1 = _serve(one, prompts)
    lp1 = dict(one.result_logprobs)
    del one
    print(f"[four-chips] single device: {sum(map(len, out1.values()))} tokens "
          f"in {dt1:.1f}s incl. compile (smoke timing)", flush=True)
    devs = jax.devices()[:4]
    before = bytes_in_use(devs)
    mesh = Mesh(np.array(devs).reshape(4), ("model",))
    tp = _engine(cfg, params, seed, mesh=mesh)
    out4, dt4 = _serve(tp, prompts)
    after = bytes_in_use(devs)
    print(f"[four-chips] tp=4: {sum(map(len, out4.values()))} tokens in "
          f"{dt4:.1f}s incl. compile (smoke timing)", flush=True)
    for i, (b0, a0) in enumerate(zip(before, after)):
        print(f"[four-chips] device {i} bytes_in_use {a0} "
              f"(+{a0 - b0} for the tp=4 engine)", flush=True)
    for rid in range(N_REQUESTS):
        check(same_bits(out4[rid], out1[rid]),
              f"request {rid}: tp=4 tokens != single-device tokens")
        check(same_bits(tp.result_logprobs[rid], lp1[rid]),
              f"request {rid}: tp=4 logprobs != single-device logprobs")
    print("[four-chips] tp=4 tokens and sampled logprobs == single device "
          "bitwise", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only TP-sharded serving at tp=4 against the "
                         "single-device engine")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        device = phase_device(4 if args.four_chips else 1)
        from repro.launch.compile_cache import use_compile_cache
        print(f"[device] compile cache: {use_compile_cache()}", flush=True)
        if args.four_chips:
            phase_four_chips(model_config(), args.seed)
        else:
            phase_kernels(seed=args.seed)
            for impl in ("xla", "pallas"):
                phase_train(model_config(impl), seed=args.seed)
            phase_serve(model_config(), args.seed)
    except SmokeFailure as e:
        print(f"FAIL: {e}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
