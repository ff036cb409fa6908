"""telemetry/ — ONE observability layer across both engines (DESIGN.md §10).

The paper's §3 "Tools" pitch — live system status, utilization
monitoring, simulator-performance tracking — is honored by BOTH engines
through a single schema:

* the host :class:`~repro.core.monitors.UtilizationMonitor` accumulates
  telemetry-schema sample rows per observed event;
* the compiled fleet engine writes the same rows into a fixed-capacity
  device buffer *inside* its jitted ``lax.while_loop`` (``SimState.tele_buf``),
  plus per-phase profile counters accumulated in-carry;
* both decode into :class:`TelemetryTrace` — a downsampled sample matrix
  ``[S, 5 + R]`` + phase-counter totals — with one JSONL structured-trace
  format (:meth:`TelemetryTrace.write_jsonl` / ``read_jsonl``) consumed
  by the metrics/plots pipeline and the benchmark profiler.

Parity contract (pinned by ``tests/test_telemetry.py``): same workload +
same stride ⇒ bit-identical sample matrices and phase-counter totals
from either engine.

Where the time goes is the other half: :class:`SpanRecorder` records the
fleet path's host spans (``experiment.run`` → ``fleet.build`` /
``fleet.launch`` / ``results.write`` and their children), each also a
profiler annotation, and the compiled loop names its phases with
``jax.named_scope`` so a device trace attributes device time per phase.
"""
from .spans import Span, SpanRecorder
from .trace import (BASE_COLUMNS, PHASE_KEYS, TelemetryTrace,
                    telemetry_columns)

__all__ = ["BASE_COLUMNS", "PHASE_KEYS", "Span", "SpanRecorder",
           "TelemetryTrace", "telemetry_columns"]
