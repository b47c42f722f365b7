"""The harness finds cells, configurations, traffic mixes and per-layer
metrics by name, and refuses to report anything without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def test_every_cell_resolves_to_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for wl in bench["workloads"]:
        spec = harness.load_spec(wl["name"], ROOT)
        assert harness.kind(spec).run
        assert harness.model(spec).dims(spec.cfg)
        assert set(spec.limits)
    for m in bench["per_layer"]:
        spec = harness.load_spec(m["workloads"][0], ROOT)
        assert harness.reader(spec, m["name"]).read


@pytest.fixture
def dummy_root(tmp_path):
    """A checkout with one more cell, configuration, traffic mix and
    per-layer metric, each added as a new file and a new entry only."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    shutil.copy(os.path.join(DATA, "tiny-stablelm.json"),
                root / "bench" / "configs" / "tiny-dummy.json")
    shutil.copy(os.path.join(DATA, "tiny-train.json"),
                root / "bench" / "traffic" / "dummy-mix.json")
    (root / "bench" / "limits" / "dummy-cell.json").write_text(json.dumps(
        {"loss_gap": {"limit": 0.01}, "grad_gap": {"limit": 0.1},
         "change_gap": {"limit": 0.1}}))
    (root / "bench" / "metrics" / "dummy_steps.py").write_text(
        "def read(ctx):\n    return float(ctx.counts['steps'])\n")
    bench["configs"].append({"name": "tiny-dummy", "source": "test",
                             "file": "bench/configs/tiny-dummy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy-cell", "config": "tiny-dummy",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "dummy_tokens_per_s", "unit": "x",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["dummy-cell"]})
    bench["per_layer"].append({"name": "dummy_steps", "unit": "steps",
                               "better": "higher", "source": "program_counter",
                               "layer": "test", "moves": "setup_s",
                               "workloads": ["dummy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_new_files_are_found_by_name(dummy_root):
    spec = harness.load_spec("dummy-cell", dummy_root)
    assert spec.cfg["name"] == "tiny-dummy" and spec.traffic["kind"] == "train"
    assert spec.bench_dir == os.path.join(dummy_root, "bench")
    res = harness.run(spec, seed=5, seconds=0.5, traced=True, device=CPU,
                      peaks={"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    assert res["correct"], res["checks"]
    # the new reader ran; the shipped ones found no device ops and said so
    assert res["metrics"]["dummy_steps"]["value"] == res["attempted"] > 0
    assert "device_idle.train" not in res["metrics"]
    assert list(res)[-1] == "checks"


def _run_py(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = _run_py(["--workload", "train-stablelm-4k", "--seed", "1",
                 "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no CPU fallback" in p.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run_py(["--workload", "train-stablelm-4k", "--seed", "1",
                 "--seconds", "1", "--trace", "0"], str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
