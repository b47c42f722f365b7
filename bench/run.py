"""Chip benchmark of the DASH train and serve paths.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run. It sets up the cell named in ``BENCHMARK.json`` (weights
and data from ``--seed``, every program compiled or read from the
compilation cache in ``<checkout>/.jax_cache``), measures for ``--seconds``,
checks what the timed path produced against the plain reference, and prints
one JSON line last on standard output. With ``--trace 0`` its metrics are
the cell's end-to-end metrics; with ``--trace 1`` a profiler trace of the
window is reduced to the cell's per-layer metrics. The numbers compared for
``correct`` are printed, each with its limit, as the last lines on standard
error and under ``checks`` in the result line.

It needs a TPU: on any other platform, or with fewer chips than the cell
asks for, it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    # The compilation cache lives at a fixed path in the checkout, so that
    # only the first run of a cell there compiles.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from bench import harness

    spec = harness.load_spec(args.workload, ROOT)
    try:
        device, peaks = harness.device_info(spec.workload["chips"])
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                         device=device, peaks=peaks, t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
