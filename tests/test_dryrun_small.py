"""Dry-run machinery integration test on a small forced-device mesh (subprocess,
so the main process keeps 1 device): proves the lowering path of launch/dryrun.py
works end to end for a train cell and a decode cell without the 512-device cost."""
import json
import os
import subprocess
import sys
import textwrap

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import functools
    import jax
    from repro.configs import registry
    from repro.configs.base import InputShape
    from repro.dist.sharding import RULE_SETS, use_rules, logical_to_spec, \\
        sanitize_pspecs
    from repro.launch.dryrun import _measures, collective_bytes
    from repro.launch.mesh import auto_mesh
    from repro.launch.specs import input_specs
    from repro.models import transformer as T
    from repro.train import step as S
    from jax.sharding import PartitionSpec as P

    mesh = auto_mesh((2, 4), ("data", "model"))
    # rules reference only data/model axes on this mesh
    rules = {k: (tuple(a for a in v if a in ("data", "model")) or None)
             if v else v for k, v in RULE_SETS["fsdp_tp"](False).items()}

    cfg = registry.get("stablelm-1.6b").reduced(
        d_model=256, n_heads=8, n_kv_heads=8, head_dim_=32, d_ff=512,
        vocab=2048, vocab_pad=256, n_layers=2)
    shape = InputShape("t", "train", 256, 8)
    tcfg = S.TrainConfig()
    with jax.set_mesh(mesh), use_rules(rules, mesh):
        specs = input_specs(cfg, shape)
        step = S.make_train_step(cfg, tcfg)
        state_sds = jax.eval_shape(functools.partial(S.init_state, cfg, tcfg),
                                   jax.random.PRNGKey(0))
        st = S.state_pspecs(cfg, tcfg, rules)
        jitted = jax.jit(step, in_shardings=(st, S.batch_pspecs(cfg, rules)),
                         out_shardings=(st, None))
        compiled = jitted.lower(state_sds, specs["batch"]).compile()
    m = _measures(compiled, 8)
    assert m["flops"] > 0 and m["bytes_accessed"] > 0
    assert sum(m["collective_bytes"].values()) > 0, "expected TP/FSDP collectives"
    print("train cell lowered:", {k: round(v) for k, v in m.items()
                                  if not isinstance(v, dict)})

    # decode cell
    shape_d = InputShape("d", "decode", 256, 8)
    with jax.set_mesh(mesh), use_rules(rules, mesh):
        specs = input_specs(cfg, shape_d)
        serve = S.make_serve_step(cfg)
        params_sds = jax.eval_shape(functools.partial(T.init, cfg),
                                    jax.random.PRNGKey(0))
        pspecs = jax.tree.map(lambda a: logical_to_spec(a, rules), T.specs(cfg),
                              is_leaf=lambda x: isinstance(x, tuple) and all(
                                  e is None or isinstance(e, str) for e in x))
        c_specs = sanitize_pspecs(S.cache_pspecs(cfg, shape_d, rules),
                                  specs["caches"], mesh)
        b_specs = {"tokens": P("data", None)}
        jitted = jax.jit(serve, in_shardings=(pspecs, c_specs, b_specs, P()),
                         out_shardings=(None, c_specs))
        compiled = jitted.lower(params_sds, specs["caches"], specs["batch"],
                                specs["cache_pos"]).compile()
    print("decode cell lowered ok")
""")


def test_dryrun_lowering_small_mesh():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=1200,
                       env={**os.environ, "PYTHONPATH": "src"}, cwd=REPO_ROOT)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "train cell lowered" in r.stdout
    assert "decode cell lowered ok" in r.stdout


def test_collective_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
  %all-reduce.1 = f32[16,128]{1,0} all-reduce(%x), replica_groups=[16,16]<=[256], to_apply=%add
  %ag = bf16[4,256]{1,0} all-gather(%y), replica_groups={{0,1,2,3}}, dimensions={1}
  %cp = f32[8,8]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
  %notacoll = f32[2,2]{1,0} add(%a, %b)
"""
    totals, counts = collective_bytes(hlo, 256)
    assert counts["all-reduce"] == 1 and counts["all-gather"] == 1
    assert counts["collective-permute"] == 1
    ar = 16 * 128 * 4
    assert totals["all-reduce"] == 2.0 * ar * 15 / 16
    ag = 4 * 256 * 2
    assert totals["all-gather"] == ag * 3 / 4
    assert totals["collective-permute"] == 8 * 8 * 4


def test_artifacts_complete_if_present():
    """If the sweep has produced artifacts, the 40-cell × 2-mesh inventory must
    be complete and structurally sound (spec deliverable e)."""
    art = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")
    if not os.path.isdir(art):
        import pytest
        pytest.skip("dry-run artifacts not generated yet")
    files = [f for f in os.listdir(art) if f.endswith(".json")
             and f.count("__") == 2]
    assert len(files) >= 80
    for f in files:
        a = json.load(open(os.path.join(art, f)))
        assert a.get("skipped") or (a["flops"] > 0 and "memory" in a)
