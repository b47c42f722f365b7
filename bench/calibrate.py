"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --what program,control,half_batch [--seconds 10] [--out FILE]

* ``program``: the cell's own runs (a short window, then the same check as
  a benchmark run), one per seed, all in this one process;
* ``control``: the reference put in the program's place, computed in the
  precision below the configuration's (float8 e4m3 with one scale per
  tensor, for bfloat16): its three steps against the float32 reference's;
* ``half_batch``: a planted fault, the reference with half of each batch
  left out and the mean taken over the rest;
* ``lr_102``, ``lr_bf16``: a wrong update, the reference with the learning
  rate 2 % high or rounded to bfloat16.

A state left unchanged reads 1 by the training measure and needs no run.
Prints one JSON line per reading and writes them all to ``--out``. The
benchmark's own runs never run any of this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    what = args.what.split(",")

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import harness

    spec = harness.load_spec(args.workload, ROOT)
    try:
        harness.device_info(spec.workload["chips"])
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    drv, ref_mod = harness.kind(spec), harness.model(spec)
    readings = []

    def emit(rec):
        rec["t"] = round(time.perf_counter() - T_START, 3)
        readings.append(rec)
        print(json.dumps(rec), flush=True)

    for seed in seeds:
        if "program" in what:
            out = drv.run(spec, seed=seed, seconds=args.seconds,
                          tracer=harness.Tracer(False),
                          t_start=time.perf_counter())
            correct, _ = harness.judge(spec.limits, out)
            emit({"seed": seed, "what": "program", "correct": correct,
                  "checks": out.checks, "metrics": out.e2e,
                  "memory_peak_bytes": out.memory_peak_bytes,
                  "notes": out.notes})
            gc.collect()
        if set(what) - {"program"}:
            tr = spec.traffic
            feed = drv.batch_fn(spec.cfg["vocab_size"], tr["batch"], tr["seq"],
                                ref_mod.root_key)
            words = jax.numpy.asarray(ref_mod.seed_words(seed))
            batches = [(feed(words, i)["tokens"], feed(words, i)["labels"])
                       for i in range(tr["check_steps"])]
            ref = ref_mod.train_reference(spec.cfg, tr["optimizer"], seed,
                                          batches)
            planted = {}
            if "control" in what:
                planted["control"] = lambda: ref_mod.train_reference(
                    spec.cfg, tr["optimizer"], seed, batches, precision="fp8")
            if "half_batch" in what:
                half = tr["batch"] // 2
                planted["half_batch"] = lambda: ref_mod.train_reference(
                    spec.cfg, tr["optimizer"], seed,
                    [(t[:half], lb[:half]) for t, lb in batches])
            lr = tr["optimizer"]["lr"]
            for name, scaled in (("lr_102", lr * 1.02), ("lr_bf16", float(
                    jax.numpy.asarray(lr, jax.numpy.bfloat16)))):
                if name in what:
                    planted[name] = functools.partial(
                        ref_mod.train_reference, spec.cfg,
                        dict(tr["optimizer"], lr=scaled), seed, batches)
            for name, make in planted.items():
                got = make()
                del got["grad_elems"], got["p0"]
                c = drv.compare(got, ref, tr["leaf_rule"])
                del got
                emit({"seed": seed, "what": name,
                      "checks": {k: c[k] for k in
                                 ("loss_gap", "grad_gap", "change_gap")},
                      "worst_grad_leaf": c["worst_grad_leaf"],
                      "worst_change_leaf": c["worst_change_leaf"]})
            del ref
        gc.collect()
    if args.out:
        with open(args.out, "w") as f:
            json.dump(readings, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
