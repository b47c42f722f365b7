import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# NOTE: the two lines above MUST run before any other import (jax locks the
# device count at first init). Everything else follows.

"""Multi-pod dry-run (deliverable e): lower + compile every (arch × shape) cell
on the production meshes and record memory/cost/collective analyses.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-110b \
        --shape train_4k [--multi-pod] [--all] [--force]

Artifacts land in experiments/dryrun/<arch>__<shape>__<mesh>.json and feed
benchmarks/bench_roofline.py and EXPERIMENTS.md §Dry-run/§Roofline.
"""
import argparse
import functools
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.configs.base import SHAPES, shape_applicable
from repro.dist.sharding import (RULE_SETS, logical_to_spec, sanitize_pspecs,
                                 use_rules)
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.models import transformer as T
from repro.train import optimizer as O
from repro.train import step as S

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# archs whose parameter+optimizer footprint needs ZeRO-3 over the data axis
BIG = {"qwen1.5-110b", "nemotron-4-15b", "mistral-nemo-12b",
       "phi3.5-moe-42b-a6.6b", "llama4-scout-17b-a16e", "jamba-1.5-large-398b"}


def pick_tcfg(arch: str, multi_pod: bool = False) -> S.TrainConfig:
    # jamba-398B: bf16 m/v halves optimizer HBM — required for single-pod fit
    state_dtype = "bfloat16" if arch == "jamba-1.5-large-398b" else "float32"
    # multi-pod: per-device batch halves → the 'names' selective-remat policy
    # (+9% roofline frac on qwen, EXPERIMENTS §Perf h2) fits the HBM budget
    policy = "names" if multi_pod else "none"
    return S.TrainConfig(opt=O.OptConfig(state_dtype=state_dtype), remat=True,
                         remat_policy=policy)


def pick_rules(arch: str, multi_pod: bool):
    name = "fsdp_tp" if arch in BIG else "tp"
    return name, RULE_SETS[name](multi_pod)


# ----------------------------------------------------------- HLO collectives
_COLL = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")
_DT_BYTES = {"f64": 8, "f32": 4, "u64": 8, "s64": 8, "u32": 4, "s32": 4,
             "bf16": 2, "f16": 2, "u16": 2, "s16": 2, "u8": 1, "s8": 1,
             "pred": 1}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(txt: str) -> int:
    """Bytes of the first (possibly tuple) result shape in an HLO line."""
    total = 0
    for m in _SHAPE_RE.finditer(txt):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DT_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return m.group(1).count(",") + 1
    return default


_OP_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|[\w\[\],{}]+)\s+(?P<op>[a-z0-9-]+)\(")


def collective_bytes(hlo_text: str, n_devices: int):
    """Per-device wire bytes per collective kind (post-SPMD shapes are
    per-partition). Ring-bandwidth model: all-reduce≈2·S·(n-1)/n, all-gather /
    all-to-all≈out·(n-1)/n, reduce-scatter≈out·(n-1), permute≈S."""
    totals = {k: 0.0 for k in _COLL}
    counts = {k: 0 for k in _COLL}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        raw_op = m.group("op")
        base = raw_op.replace("-start", "")
        if base not in _COLL or raw_op.endswith("-done"):
            continue
        op = base
        size = _shape_bytes(m.group("shape"))
        if raw_op.endswith("-start"):
            size //= 2  # tuple of (aliased input, output)
        n_g = max(2, _group_size(line, n_devices))
        if op == "all-reduce":
            wire = 2.0 * size * (n_g - 1) / n_g
        elif op == "reduce-scatter":
            wire = float(size) * (n_g - 1)
        elif op in ("all-gather", "all-to-all"):
            wire = float(size) * (n_g - 1) / n_g
        else:  # collective-permute
            wire = float(size)
        totals[op] += wire
        counts[op] += 1
    return totals, counts


def _lower_compile(cfg, shape, rules, tcfg, mesh):
    """Build + lower + compile the cell's step function. Returns compiled."""
    with jax.set_mesh(mesh), use_rules(rules, mesh):
        specs = input_specs(cfg, shape)
        if shape.kind == "train":
            step = S.make_train_step(cfg, tcfg)
            state_sds = jax.eval_shape(
                functools.partial(S.init_state, cfg, tcfg), jax.random.PRNGKey(0))
            st_specs = S.state_pspecs(cfg, tcfg, rules)
            b_specs = S.batch_pspecs(cfg, rules)
            jitted = jax.jit(step, in_shardings=(st_specs, b_specs),
                             out_shardings=(st_specs, None), donate_argnums=(0,))
            return jitted.lower(state_sds, specs["batch"]).compile()
        pspecs = jax.tree.map(
            lambda a: logical_to_spec(a, rules), T.specs(cfg),
            is_leaf=lambda x: isinstance(x, tuple) and all(
                e is None or isinstance(e, str) for e in x))
        params_sds = jax.eval_shape(
            functools.partial(T.init, cfg), jax.random.PRNGKey(0))
        if shape.kind == "prefill":
            step = S.make_prefill_step(cfg, max_seq=shape.seq)
            b_specs = {k: v for k, v in S.batch_pspecs(cfg, rules).items()
                       if k != "labels"}
            jitted = jax.jit(step, in_shardings=(pspecs, b_specs))
            return jitted.lower(params_sds, specs["batch"]).compile()
        # decode
        step = S.make_serve_step(cfg)
        c_specs = S.cache_pspecs(cfg, shape, rules,
                                 shard_seq=(shape.name == "long_500k"))
        c_specs = sanitize_pspecs(c_specs, specs["caches"], mesh)
        batch_ax = logical_to_spec(("batch",), rules)[0]
        b_specs = sanitize_pspecs({"tokens": P(batch_ax, None)},
                                  specs["batch"], mesh)
        in_sh = [pspecs, c_specs, b_specs, P()]
        args = [params_sds, specs["caches"], specs["batch"], specs["cache_pos"]]
        if cfg.encoder is not None:
            in_sh.append(sanitize_pspecs(P(batch_ax, None, None),
                                         specs["cross_x"], mesh))
            args.append(specs["cross_x"])
        jitted = jax.jit(step, in_shardings=tuple(in_sh),
                         out_shardings=(None, c_specs), donate_argnums=(1,))
        return jitted.lower(*args).compile()


def _measures(compiled, n_dev):
    cost = compiled.cost_analysis() or {}
    coll, counts = collective_bytes(compiled.as_text(), n_dev)
    return {"flops": cost.get("flops", 0.0),
            "bytes_accessed": cost.get("bytes accessed", 0.0),
            "collective_bytes": coll, "collective_counts": counts}


def _scale_layers(cfg, n_rep: int):
    """cfg with n_rep pattern repeats, layer scan unrolled so cost_analysis sees
    every repeat (encoder scaled identically)."""
    kw = {"n_layers": n_rep * len(cfg.block_pattern), "scan_unroll": True}
    if cfg.encoder is not None:
        kw["encoder"] = _scale_layers(cfg.encoder, n_rep)
    return cfg.replace(**kw)


# ----------------------------------------------------------------- one cell
def run_cell(arch: str, shape_name: str, multi_pod: bool, force: bool = False,
             rules_name: str = None, tag: str = "", overrides: dict = None,
             tracker=None):
    mesh_name = "2x16x16" if multi_pod else "16x16"
    os.makedirs(ART_DIR, exist_ok=True)
    art_path = os.path.join(
        ART_DIR, f"{arch}__{shape_name}__{mesh_name}{tag}.json")
    if os.path.exists(art_path) and not force:
        print(f"[skip] {art_path} exists")
        return json.load(open(art_path))

    cfg = registry.get(arch)
    tcfg_over = {}
    if overrides:
        model_over = {k: v for k, v in overrides.items()
                      if not k.startswith("tcfg_")}
        tcfg_over = {k[5:]: v for k, v in overrides.items()
                     if k.startswith("tcfg_")}
        cfg = cfg.replace(**model_over)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        print(f"[n/a] {arch} × {shape_name}: {why}")
        art = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "skipped": why}
        json.dump(art, open(art_path, "w"), indent=1)
        return art

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    if rules_name is None:
        rules_name, rules = pick_rules(arch, multi_pod)
    else:
        rules = RULE_SETS[rules_name](multi_pod)
    tcfg = pick_tcfg(arch, multi_pod)
    if tcfg_over:
        import dataclasses as _dc
        tcfg = _dc.replace(tcfg, **tcfg_over)
    t0 = time.time()

    compiled = _lower_compile(cfg, shape, rules, tcfg, mesh)
    full = _measures(compiled, n_dev)
    mem = compiled.memory_analysis()
    t1 = time.time()

    # --- while-loop trip-count correction: XLA cost_analysis counts a rolled
    # loop body once. Lower the same cell at 1 and 2 pattern repeats; the delta
    # is one repeat's body; corrected = full + (trips-1) · body.  (Inner scans —
    # mamba chunk scan, slstm time scan — remain counted once; their flops share
    # is <1% and is noted in EXPERIMENTS.md §Dry-run.)
    trips = cfg.n_layers // len(cfg.block_pattern)
    body = None
    if trips > 1:
        m1 = _measures(_lower_compile(_scale_layers(cfg, 1), shape, rules,
                                      tcfg, mesh), n_dev)
        m2 = _measures(_lower_compile(_scale_layers(cfg, 2), shape, rules,
                                      tcfg, mesh), n_dev)
        body = {
            "flops": m2["flops"] - m1["flops"],
            "bytes_accessed": m2["bytes_accessed"] - m1["bytes_accessed"],
            "collective_bytes": {k: m2["collective_bytes"][k]
                                 - m1["collective_bytes"][k]
                                 for k in m1["collective_bytes"]},
        }

    def corrected(metric):
        if body is None:
            return full[metric]
        if metric == "collective_bytes":
            return {k: full[metric][k] + (trips - 1) * max(0.0, body[metric][k])
                    for k in full[metric]}
        return full[metric] + (trips - 1) * max(0.0, body[metric])

    def _mem_field(f):
        return getattr(mem, f, None) if mem is not None else None

    art = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "rules": rules_name, "n_devices": n_dev,
        "kind": shape.kind, "seq": shape.seq, "batch": shape.batch,
        "compile_s": round(t1 - t0, 1), "trips": trips,
        "flops_raw": full["flops"], "flops": corrected("flops"),
        "bytes_accessed_raw": full["bytes_accessed"],
        "bytes_accessed": corrected("bytes_accessed"),
        "collective_bytes_raw": full["collective_bytes"],
        "collective_bytes": corrected("collective_bytes"),
        "collective_counts": full["collective_counts"],
        "memory": {f: _mem_field(f) for f in
                   ("argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes")},
        "opt_state_dtype": tcfg.opt.state_dtype,
    }
    json.dump(art, open(art_path, "w"), indent=1)
    if tracker is not None:
        tracker.log("dryrun_cell", {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "rules": rules_name, "compile_s": art["compile_s"],
            "flops": art["flops"], "bytes_accessed": art["bytes_accessed"]})
    print(f"[ok] {arch} × {shape_name} × {mesh_name} rules={rules_name} "
          f"compile={art['compile_s']}s flops={art['flops']:.3e} "
          f"coll={sum(art['collective_bytes'].values()):.3e}B")
    if mem is not None:
        print("  memory_analysis:", {k: v for k, v in art["memory"].items()})
    return art


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--rules", default=None,
                    choices=[None, "tp", "fsdp_tp", "zero3_pod", "cp"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--set", action="append", default=[], dest="overrides",
                    help="ModelConfig override key=value (hillclimb experiments)")
    ap.add_argument("--track", default=None, metavar="JSONL",
                    help="log one repro.obs 'dryrun_cell' event per compiled "
                         "cell (compile time + cost analysis headline)")
    args = ap.parse_args()

    from repro.obs import open_tracker
    tracker = open_tracker(args.track) if args.track else None

    overrides = {}
    for kv in args.overrides:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v in ("True", "False"):
            v = v == "True"
        overrides[k] = v

    archs = registry.ARCHS if (args.all or not args.arch) else [
        registry.ALIASES.get(args.arch, args.arch)]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    failures = []
    for arch_mod in archs:
        arch = registry.get(arch_mod).name
        for shape_name in shapes:
            for mp in meshes:
                try:
                    run_cell(arch, shape_name, mp, force=args.force,
                             rules_name=args.rules, tag=args.tag,
                             overrides=overrides, tracker=tracker)
                except Exception as e:  # noqa: BLE001 — record and continue
                    traceback.print_exc()
                    failures.append((arch, shape_name, mp, str(e)[:200]))
    if tracker is not None:
        tracker.close()
    if failures:
        print("\nFAILURES:")
        for f in failures:
            print(" ", f)
        sys.exit(1)
    print("\nall requested dry-run cells passed")


if __name__ == "__main__":
    main()
