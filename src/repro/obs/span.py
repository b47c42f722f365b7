"""Deterministic span tracing over the :mod:`repro.obs.tracker` protocol.

A span is a named interval of host work (a prefill, a decode step, a train
step phase).  The design constraint that keeps spans compatible with the
repo's bitwise story:

  * **identity is deterministic** — ``span_id`` is a sha256 of
    ``(run_id, scope, phase)``, never a clock, counter race, or object id.
    Two runs of the same program emit the same span ids in the same order,
    so span streams from byte-reproducible runs diff clean and
    ``diff_runs`` can join spans across runs by id;
  * **time is payload, not identity** — wall-clock fields (``begin_s``,
    ``dur_s``, relative to the tracer's first observation) are observations
    *about* the run, carried in the event data, and are the only
    nondeterministic fields in a span record;
  * **on the profiler's clock** — each span also opens a
    ``jax.profiler.TraceAnnotation`` named ``repro.<phase>`` when the
    tracker is armed or a profiler trace is active, so a device trace shows
    the host's phases beside the kernels. Each annotation records its own
    interval, so spans that overlap without nesting (a request and its
    queue wait) land as they happened;
  * **disarmed is free** — against a :class:`~repro.obs.tracker.NoopTracker`
    and with no profiler trace active the tracer never reads the clock and
    never allocates a ``Span`` or an annotation, so an untracked run does not
    even perturb host timing, let alone a token bit (tests/test_obs_prof.py
    proves bitwise invariance on the spec and sharded serve paths).

Span event record (one ``"span"`` event per *completed* span)::

    {"event": "span", "phase": <str>, "scope": <str>, "span_id": <16 hex>,
     "parent_id": <16 hex|null>, "lane": <str|absent>,
     "begin_s": <float>, "dur_s": <float>, "step": <int|absent>,
     ...attrs from begin() and end()...}

``lane`` groups spans into horizontal tracks for the Perfetto export
(:mod:`repro.obs.export`); ``scope`` is the deterministic instance key
(``"req:3"``, ``"step:17"``) that, hashed with the phase, yields the id.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Optional

from jax._src.lib import _profiler
from jax.profiler import TraceAnnotation

from repro.obs.tracker import NoopTracker, Tracker

ANNOTATION_PREFIX = "repro."


def profiling() -> bool:
    """True while a profiler trace is being recorded in this process."""
    return _profiler.TraceMe.is_enabled()


def span_id(run_id: str, scope: str, phase: str) -> str:
    """Deterministic 16-hex span identity: sha256 of ``run_id|scope|phase``.

    Pure function of its arguments — no clock, no sequence number — so the
    same logical span gets the same id in every run of the same program.
    """
    h = hashlib.sha256(f"{run_id}|{scope}|{phase}".encode()).hexdigest()
    return h[:16]


@dataclasses.dataclass
class Span:
    """An open span handle; pass back to :meth:`SpanTracer.end` to emit."""

    id: str
    phase: str
    scope: str
    begin_s: float
    parent_id: Optional[str] = None
    lane: Optional[str] = None
    step: Optional[int] = None
    attrs: Dict = dataclasses.field(default_factory=dict)
    annotation: Any = None     # the open ``repro.<phase>`` profiler event


class SpanTracer:
    """Emit deterministic-identity spans into a tracker.

    ``clock`` is injectable (tests pass a fake counter to get byte-identical
    span streams); the default is ``time.perf_counter`` re-based to the first
    observation so ``begin_s`` values are small run-relative floats.
    """

    def __init__(self, tracker: Optional[Tracker] = None, run_id: str = "run",
                 clock: Callable[[], float] = time.perf_counter):
        self.tracker = tracker if tracker is not None else NoopTracker()
        self.run_id = run_id
        self._clock = clock
        self._epoch: Optional[float] = None

    @property
    def armed(self) -> bool:
        """False against a NoopTracker — every tracer call short-circuits."""
        return not isinstance(self.tracker, NoopTracker)

    def now(self) -> float:
        """Run-relative wall time (0.0 at the tracer's first observation)."""
        if not self.armed:
            return 0.0
        t = self._clock()
        if self._epoch is None:
            self._epoch = t
        return t - self._epoch

    def begin(self, phase: str, scope: str, *, parent: Optional[Span] = None,
              lane: Optional[str] = None, step: Optional[int] = None,
              **attrs) -> Optional[Span]:
        """Open a span; returns ``None`` when disarmed and no profiler trace
        is active (``end(None)`` no-ops)."""
        if not self.armed and not profiling():
            return None
        annotation = TraceAnnotation(ANNOTATION_PREFIX + phase)
        annotation.__enter__()
        return Span(id=span_id(self.run_id, scope, phase), phase=phase,
                    scope=scope, begin_s=self.now(),
                    parent_id=parent.id if parent is not None else None,
                    lane=lane, step=step, attrs=dict(attrs),
                    annotation=annotation)

    def end(self, span: Optional[Span], **attrs) -> None:
        """Close a span's profiler event and, when armed, emit the
        ``"span"`` event (no-op on ``None``)."""
        if span is None:
            return
        span.annotation.__exit__(None, None, None)
        if not self.armed:
            return
        data: Dict = {"phase": span.phase, "scope": span.scope,
                      "span_id": span.id, "parent_id": span.parent_id,
                      "begin_s": round(span.begin_s, 9),
                      "dur_s": round(self.now() - span.begin_s, 9)}
        if span.lane is not None:
            data["lane"] = span.lane
        data.update(span.attrs)
        data.update(attrs)
        self.tracker.log("span", data, step=span.step)

    @contextmanager
    def span(self, phase: str, scope: str, *, parent: Optional[Span] = None,
             lane: Optional[str] = None, step: Optional[int] = None, **attrs):
        """``with tracer.span("decode", "step:7"): ...`` — begin/end pair."""
        s = self.begin(phase, scope, parent=parent, lane=lane, step=step,
                       **attrs)
        try:
            yield s
        finally:
            self.end(s)

    def mark(self, name: str, data: Optional[Dict] = None,
             step: Optional[int] = None) -> None:
        """Zero-duration instant event (``at_s`` payload) — e.g. a preempt."""
        if not self.armed:
            return
        rec = {"at_s": round(self.now(), 9)}
        rec.update(data or {})
        self.tracker.log(name, rec, step=step)
