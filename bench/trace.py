"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

A traced run writes one ``.xplane.pb`` (``jax.profiler.start_trace``).
:func:`read` keeps three things of it, all on the profiler's one clock, in
nanoseconds:

* the device operations of each TPU (plane ``/device:TPU:<n>``, line
  ``XLA Ops``), as (name, start, end);
* the XLA module executions of each TPU (line ``XLA Modules``);
* the benchmark's own host spans (``jax.profiler.TraceAnnotation`` names
  starting with ``bench.``), as (name, start, end).

The traced window is the benchmark's ``bench.window`` span. Everything below
is plain interval arithmetic on those lists, so it is tested on the CPU on a
small recorded trace (``bench/tests``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]      # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Interval]]          # device id -> op events, by start
    modules: Dict[int, List[Interval]]      # device id -> module executions
    host: List[Interval]                    # bench.* host spans, by start
    window: Tuple[float, float]             # the traced window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def clipped_ops(self, device: int) -> List[Interval]:
        return clip(self.ops.get(device, []), self.window)


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``start_trace`` directory."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def read(path: str) -> Trace:
    """Read one ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[int, List[Interval]] = {}
    modules: Dict[int, List[Interval]] = {}
    host: List[Interval] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = [(e.name, e.start_ns, e.end_ns) for e in line.events]
                target = ops if line.name == OPS_LINE else modules
                target.setdefault(dev, []).extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    for d in (ops, modules):
        for evs in d.values():
            evs.sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    wins = [h for h in host if h[0] == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace has no {WINDOW_SPAN} host span")
    return Trace(ops=ops, modules=modules, host=host,
                 window=(wins[0][1], wins[0][2]))


# --------------------------------------------------------------------------- #
# interval arithmetic
# --------------------------------------------------------------------------- #
def clip(events: Iterable[Interval], window: Tuple[float, float]
         ) -> List[Interval]:
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(events: Sequence[Interval]) -> List[Tuple[float, float]]:
    """Merged (start, end) intervals covered by any event."""
    out: List[List[float]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Sequence[Interval]) -> float:
    return sum(e - s for s, e in union(events))


def gaps(events: Sequence[Interval], window: Tuple[float, float]
         ) -> List[Tuple[float, float]]:
    """Idle (start, end) stretches of the window that no event covers."""
    out, t = [], window[0]
    for s, e in union(clip(events, window)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


_INSTR = re.compile(r"^%([\w.\-]+) = ")
CONTAINERS = ("while", "conditional", "call")


def short_name(name: str) -> str:
    """The HLO instruction name of a TPU op event (``%fusion.12 = f32[...]
    fusion(...)`` -> ``fusion.12``); other names unchanged."""
    m = _INSTR.match(name)
    return m.group(1) if m else name


def is_container(name: str) -> bool:
    """A ``while``, ``conditional`` or ``call`` op: its event spans the ops
    of its body, which have events of their own."""
    return short_name(name).split(".")[0] in CONTAINERS


def time_matching(events: Sequence[Interval], patterns: Sequence[str]
                  ) -> Tuple[float, int]:
    """Summed duration (ns) and count of events whose short name
    (:func:`short_name`) matches any of the regular expressions
    ``patterns`` (``re.search``)."""
    rx = [re.compile(p) for p in patterns]
    total, n = 0.0, 0
    for name, s, e in events:
        sn = short_name(name)
        if any(r.search(sn) for r in rx):
            total += e - s
            n += 1
    return total, n


def host_span_at(host: Sequence[Interval], t: float) -> str:
    """Name of the innermost ``bench.`` host span covering time ``t``."""
    best: Optional[Interval] = None
    for span in host:
        if span[0] == WINDOW_SPAN:
            continue
        if span[1] <= t <= span[2] and (best is None or span[1] >= best[1]):
            best = span
    return best[0] if best else "(no benchmark span)"


# --------------------------------------------------------------------------- #
# what a traced run reports
# --------------------------------------------------------------------------- #
def device_busy(tr: Trace) -> Tuple[float, float]:
    """(busy_s averaged over the traced devices, window_s)."""
    devs = sorted(tr.ops) or [0]
    busy = [busy_ns(tr.clipped_ops(d)) for d in devs]
    return sum(busy) / len(busy) * 1e-9, tr.window_s


def top_ops(tr: Trace, n: int = 10, device: int = 0
            ) -> List[Tuple[str, float]]:
    """The ``n`` operations (by instruction name, containers left out) that
    took the most device time, with their summed seconds, on one device."""
    tot: Dict[str, float] = {}
    for name, s, e in tr.clipped_ops(device):
        if is_container(name):
            continue
        sn = short_name(name)
        tot[sn] = tot.get(sn, 0.0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns * 1e-9) for name, ns in ranked]


def longest_gaps(tr: Trace, n: int = 10, device: int = 0
                 ) -> List[Tuple[str, float]]:
    """The ``n`` longest idle stretches of one device, each named by the
    benchmark span the host was in at its midpoint."""
    gs = sorted(gaps(tr.ops.get(device, []), tr.window),
                key=lambda g: g[0] - g[1])[:n]
    return [(host_span_at(tr.host, (s + e) / 2), (e - s) * 1e-9)
            for s, e in gs]
