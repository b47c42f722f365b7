"""The benchmark harness: finds a cell's files by name, runs it, reports.

Everything that belongs to one configuration, traffic mix, cell or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* configuration ``<c>``: the file its ``configs`` entry names (sizes as run,
  with ``model``: the family, which names ``models/<model>.py``, the plain
  reference, and ``adapters/<model>.py``, the interface to the program);
* traffic mix ``<t>``: ``traffic/<t>.json``, whose ``kind`` names the
  generator and runner ``kinds/<kind>.py``;
* cell ``<w>``: ``limits/<w>.json``, the limits that decide ``correct``;
* per-layer metric ``<m>``: ``metrics/<m>.py``, a reader with ``read(ctx)``.

A later change adds a cell, a configuration, a mix or a metric by adding
files, and edits none.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
from typing import Any, Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
TRACE_DIR = os.path.join(BENCH_DIR, ".trace")


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a device the peaks table lacks."""


@dataclasses.dataclass
class Spec:
    workload: dict
    cfg: dict           # the configuration file, with "name"
    traffic: dict       # the traffic file, with "name"
    limits: dict        # name -> {"limit": x, ...}
    bench: dict         # all of BENCHMARK.json
    bench_dir: str = BENCH_DIR   # where the files named above live


@dataclasses.dataclass
class Outcome:
    """What a traffic kind's runner returns."""
    attempted: int
    failed: int
    e2e: Dict[str, float]
    checks: Dict[str, float]                 # compared numbers
    memory_peak_bytes: int
    program_bytes: Dict[str, int] = dataclasses.field(default_factory=dict)
    counts: Dict[str, Any] = dataclasses.field(default_factory=dict)
    notes: List[str] = dataclasses.field(default_factory=list)


# --------------------------------------------------------------------------- #
# files by name
# --------------------------------------------------------------------------- #
def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec(workload: str, root: str = ROOT) -> Spec:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``, with its files
    from ``<root>/bench``."""
    bench_dir = os.path.join(root, "bench")
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    wl = [w for w in bench["workloads"] if w["name"] == workload]
    if not wl:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    wl = wl[0]
    centry = [c for c in bench["configs"] if c["name"] == wl["config"]][0]
    cfg = dict(_json(os.path.join(root, centry["file"])), name=centry["name"])
    traffic = dict(_json(os.path.join(bench_dir, "traffic",
                                      f"{wl['traffic']}.json")),
                   name=wl["traffic"])
    limits = _json(os.path.join(bench_dir, "limits", f"{workload}.json"))
    return Spec(workload=wl, cfg=cfg, traffic=traffic, limits=limits,
                bench=bench, bench_dir=bench_dir)


def _named(spec: Spec, group: str, name: str):
    return load_module(os.path.join(spec.bench_dir, group, f"{name}.py"),
                       f"bench_{group}_{name}".replace("-", "_")
                       .replace(".", "_"))


def kind(spec: Spec):
    """The generator and runner of the cell's traffic mix."""
    return _named(spec, "kinds", spec.traffic["kind"])


def model(spec: Spec):
    """The plain reference of the cell's configuration."""
    return _named(spec, "models", spec.cfg["model"])


def adapter(spec: Spec):
    """The interface to the program for the cell's configuration."""
    return _named(spec, "adapters", spec.cfg["model"])


def reader(spec: Spec, name: str):
    """The reader of per-layer metric ``name``."""
    return _named(spec, "metrics", name)


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


# --------------------------------------------------------------------------- #
# device
# --------------------------------------------------------------------------- #
def device_info(chips: int) -> Tuple[dict, dict]:
    """(device record, peaks) of the machine; raises :class:`NoChip`."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoChip(f"found platform {d0.platform!r} ({d0.device_kind}); "
                     "the benchmark measures a TPU and has no CPU fallback")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devs)}")
    peaks = _json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if d0.device_kind not in peaks:
        raise NoChip(f"no peaks for device kind {d0.device_kind!r} in "
                     "bench/peaks.json")
    return ({"platform": d0.platform, "kind": d0.device_kind,
             "count": len(devs)}, peaks[d0.device_kind])


class Compiles:
    """Programs JAX lowers and compiles while counting: a window that warm-up
    covered lowers none. Read through ``jax.monitoring``; one listener per
    process."""
    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.counting = False
        self.lowered = self.compiled = 0
        self._listening = False

    def _event(self, event, duration, **kwargs):
        if self.counting:
            self.lowered += event == self._LOWER
            self.compiled += event == self._COMPILE

    def start(self) -> None:
        if not self._listening:
            import jax
            jax.monitoring.register_event_duration_secs_listener(self._event)
            self._listening = True
        self.lowered = self.compiled = 0
        self.counting = True

    def stop(self) -> str:
        self.counting = False
        return (f"programs lowered in the window {self.lowered}, compiled "
                f"{self.compiled}")


COMPILES = Compiles()


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# --------------------------------------------------------------------------- #
# tracing
# --------------------------------------------------------------------------- #
class Tracer:
    """The traced window of a ``--trace 1`` run, and the benchmark's own
    host spans (``bench.*``), which land on the profiler's clock."""

    def __init__(self, enabled: bool, directory: str = TRACE_DIR):
        self.enabled = enabled
        self.directory = directory
        self._window = None
        self.path: Optional[str] = None

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def begin(self) -> None:
        if not self.enabled or self._window is not None:
            return
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        jax.profiler.start_trace(self.directory)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()

    def end(self) -> None:
        if self._window is None:
            return
        import jax
        from bench import trace as TR
        self._window.__exit__(None, None, None)
        self._window = None
        jax.profiler.stop_trace()
        self.path = TR.find_xplane(self.directory)

    def cleanup(self) -> None:
        shutil.rmtree(self.directory, ignore_errors=True)


@dataclasses.dataclass
class ReadContext:
    """What a per-layer metric reader sees."""
    trace: Any                 # bench.trace.Trace
    cfg: dict
    traffic: dict
    counts: Dict[str, Any]
    peaks: dict
    workload: str


# --------------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------------- #
def judge(limits: dict, out: Outcome) -> Tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number the cell's limits
    name was read, is finite and is within its limit, and nothing failed."""
    checks = {}
    correct = out.failed == 0
    for cname, lim in limits.items():
        value = out.checks.get(cname)
        ok = (value is not None and math.isfinite(value)
              and value <= lim["limit"])
        correct = correct and ok
        checks[cname] = {"value": value, "limit": lim["limit"]}
    return correct, checks


def run(spec: Spec, seed: int, seconds: float, traced: bool,
        device: Optional[dict] = None, peaks: Optional[dict] = None,
        t_start: Optional[float] = None) -> dict:
    """Run a cell once and return the result record (the last line).

    ``device``/``peaks`` come from :func:`device_info`; tests pass their
    own to drive a run on the CPU, where no device metric is reported.
    """
    import time
    tracer = Tracer(traced)
    name = spec.workload["name"]
    drv = kind(spec)
    try:
        out: Outcome = drv.run(spec, seed=seed, seconds=seconds,
                               tracer=tracer,
                               t_start=time.perf_counter() if t_start is None
                               else t_start)
    finally:
        if tracer._window is not None:
            tracer.end()
    correct, checks = judge(spec.limits, out)
    result: Dict[str, Any] = {"correct": correct,
                              "attempted": out.attempted,
                              "failed": out.failed}
    metrics: Dict[str, dict] = {}
    dev = dict(device or {})
    dev["memory_peak_bytes"] = out.memory_peak_bytes
    breakdown = None
    if traced:
        from bench import trace as TR
        if tracer.path is None:
            raise RuntimeError("a traced run ended without a trace")
        tr = TR.read(tracer.path)
        busy_s, window_s = TR.device_busy(tr)
        dev["busy_s"], dev["window_s"] = busy_s, window_s
        ctx = ReadContext(trace=tr, cfg=spec.cfg, traffic=spec.traffic,
                          counts=out.counts, peaks=peaks or {}, workload=name)
        for m in spec.bench["per_layer"]:
            if not applies(m, name):
                continue
            value = reader(spec, m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": [list(x) for x in TR.top_ops(tr)],
                     "idle_gaps": [list(x) for x in TR.longest_gaps(tr)]}
        tracer.cleanup()
    else:
        for m in spec.bench["end_to_end"]:
            if applies(m, name) and m["name"] in out.e2e:
                metrics[m["name"]] = {"value": out.e2e[m["name"]],
                                      "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = dev
    if breakdown is not None:
        result["breakdown"] = breakdown
    if out.program_bytes:
        result["program_bytes"] = out.program_bytes
    result["checks"] = checks
    for note in out.notes:
        print(note, file=sys.stderr)
    return result
