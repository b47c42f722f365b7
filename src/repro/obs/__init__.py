"""repro.obs — run-wide metrics and tracing (ROADMAP item 5, Levanter
``tracker/`` style).

A heavy-traffic deterministic deployment needs live signals from every
train/serve run: throughput (tokens/s), where the time goes (host spans on
the profiler's clock, device ops named by layer), and digest divergence (did
two runs that must be bitwise equal stop being so — HEAL's instability
failure mode, caught while the run is live).

  :mod:`repro.obs.tracker`   the event sink protocol + ``JsonlTracker`` /
                             ``NoopTracker`` / ``CompositeTracker``;
  :mod:`repro.obs.metrics`   counters / timers / histograms and the
                             ``StepMeter`` throughput aggregator;
  :mod:`repro.obs.alarm`     ``DivergenceAlarm`` — compares the live uint32
                             ``verify.digest.tree_fingerprint`` stream against
                             a reference run and fires a tracker event at the
                             first diverging step;
  :mod:`repro.obs.span`      deterministic-identity spans (ids are sha256 of
                             ``(run_id, scope, phase)``, never clocks), each
                             also a ``repro.<phase>`` profiler annotation;
  :mod:`repro.obs.layers`    the ``layer.<name>`` scopes of the compiled
                             steps and ``op_layers``, the map from a compiled
                             program's instructions back to them;
  :mod:`repro.obs.prof`      the ``Profiler`` facade the serve engine and
                             train loop thread, + ``record_state_digests``;
  :mod:`repro.obs.export`    Perfetto/Chrome-trace JSON artifacts: modeled
                             schedule lanes + span timelines;
  :mod:`repro.obs.report`    ``RunReport`` percentiles/counters and the
                             ``diff_runs`` divergence triage (first step +
                             leaf path).

Event stream format: JSON Lines, one object per event, sorted keys, with a
monotone ``seq`` number — see README §Observability for the schema.  Trackers
are host-side only and must never appear inside jitted code; producers hand
them already-materialized scalars.
"""
from repro.obs.alarm import DivergenceAlarm
from repro.obs.metrics import (Counter, Histogram, StepMeter, Timer,
                               quantile_lower)
from repro.obs.prof import Profiler, open_profiler, record_state_digests
from repro.obs.report import RunDiff, RunReport, diff_runs
from repro.obs.span import Span, SpanTracer, span_id
from repro.obs.tracker import (CompositeTracker, JsonlTracker, MemoryTracker,
                               NoopTracker, Tracker, open_tracker, read_jsonl)

__all__ = [
    "Tracker", "JsonlTracker", "NoopTracker", "CompositeTracker",
    "MemoryTracker", "open_tracker", "read_jsonl",
    "Counter", "Timer", "Histogram", "StepMeter", "quantile_lower",
    "DivergenceAlarm",
    "Span", "SpanTracer", "span_id",
    "Profiler", "open_profiler", "record_state_digests",
    "RunReport", "RunDiff", "diff_runs",
]
