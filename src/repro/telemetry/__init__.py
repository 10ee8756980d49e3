"""``repro.telemetry`` — structured events, metrics, and trace spans for
training under churn.

One process-wide :class:`Recorder` (disabled by default — every helper
below is a cheap no-op until :func:`configure` installs one) collects:

* **structured events** — schema-versioned JSONL records for step
  windows, failures, recoveries, snapshot saves/restores, simulated node
  churn, truncation (:mod:`repro.telemetry.events`);
* **counters** — :func:`inc`;
* **trace spans** — host-side timings around the hot-path boundaries
  (window dispatch/drain, the window boundary's bookkeeping, failures and
  their recovery phases, snapshot writes, restores), each with its parent
  span, exported as Chrome ``trace_event`` JSON for Perfetto
  (:mod:`repro.telemetry.trace`);
* **anchors** — :func:`anchor` writes the recorder's clock into the JAX
  profiler's trace once per window, so spans map onto device time;
* **derived run metrics** — goodput, per-strategy recovery breakdown,
  per-tier snapshot bytes, straggler stretch
  (:mod:`repro.telemetry.metrics`), rendered by
  ``python -m repro.telemetry.report`` (:mod:`repro.telemetry.report`).

See ``docs/observability.md`` for the event schema, span taxonomy, and
the overhead contract (disabled telemetry must cost <2% fused-window
throughput and stay sync-free).
"""
from repro.telemetry.events import (EVENT_KINDS, SCHEMA_VERSION,
                                    validate_events, validate_record)
from repro.telemetry.log import log, set_verbosity, verbosity
from repro.telemetry.metrics import compute_metrics, render_text
from repro.telemetry.recorder import (Recorder, anchor, clock, complete,
                                      configure, emit, enabled, get_recorder,
                                      inc, set_recorder, span)
from repro.telemetry.trace import chrome_trace, load_chrome_trace

__all__ = [
    "EVENT_KINDS", "SCHEMA_VERSION", "Recorder", "anchor",
    "chrome_trace", "clock", "complete", "compute_metrics", "configure",
    "emit", "enabled", "get_recorder", "inc", "load_chrome_trace",
    "log", "render_text", "set_recorder", "set_verbosity",
    "span", "validate_events", "validate_record", "verbosity",
]
