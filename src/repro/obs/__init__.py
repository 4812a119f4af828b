"""Fleet observability: pluggable trackers, spans, metrics, dashboards.

The paper's peers certify a *global* threshold decision from purely
*local* state; operating a fleet of them inverts the problem — the only
way to see the deployment's health is through aggregate observables
(convergence fraction, msgs/link, stopping-rule violations).  This
package is the one interface those observables flow through:

* :mod:`.metrics` — counter / gauge / histogram registry with label
  sets and Prometheus text exposition.
* :mod:`.tracker` — the pluggable :class:`Tracker` protocol
  (``log_record`` / ``log_metrics`` / ``span`` / registry) with
  :class:`NoopTracker`, :class:`InMemoryTracker`, :class:`JsonlTracker`
  (bitwise-compatible with the legacy sink's JSONL) and
  :class:`PrometheusTextTracker` backends.  Spans carry
  ``span_id``/``parent_id``/tenant ``trace`` ids and emit
  ``kind="span"`` records, so the stream is causally reconstructible.
* :mod:`.push` — :class:`PushTracker`, wandb-style step-stamped payload
  buffering flushed to a user callback.
* :mod:`.flight` — :class:`FlightRecorder`, a tee backend keeping a
  bounded ring of the last N records for post-mortem JSONL dumps.
* :mod:`.trace` — :func:`assemble` span records into per-tenant causal
  trees (:class:`TraceForest` / :class:`TenantTrace`).
* :mod:`.alerts` — :class:`AlertRule` / :class:`AlertEngine`, sustained
  metric predicates emitting ``kind="alert"`` records.
* :mod:`.schema` — the golden record schema + validators.
* :mod:`.audit` — the audit plane: online monitors for the paper's
  algebraic invariants (conservation, edge symmetry, stopping
  soundness, async seq monotonicity) over device-side reductions,
  ``kind="audit"`` records, and the :class:`AuditFaults` injection
  harness the monitors are proven against.
* :mod:`.forensics` — first-violation provenance: join audit records
  with the trace forest (``python -m repro.obs.forensics dump.jsonl``).
* :mod:`.dashboard` — per-tenant / fleet text dashboards over a record
  stream, histogram bars, audit summaries (:func:`render_audits`), and
  the causal :func:`trace_view`.

Everything is host-side code: trackers never touch device arrays, so
instrumenting the service adds no transfers — the numbers all come from
the one batched observe round-trip it already makes.  Every span also
opens a ``jax.profiler.TraceAnnotation`` named ``repro.<span>``, so under
``jax.profiler.trace`` the service's scopes land on the profiler's host
timeline, on the same clock as the device's ``XLA Ops``.
"""

from .metrics import (Counter, DEFAULT_COUNT_BUCKETS, DEFAULT_TIME_BUCKETS,
                      Gauge, Histogram, MetricsRegistry)
from .schema import (ALERT_OPTIONAL, ALERT_REQUIRED, AUDIT_OPTIONAL,
                     AUDIT_REQUIRED, CONTROL_OPTIONAL,
                     CONTROL_REQUIRED, FLIGHT_OPTIONAL, FLIGHT_REQUIRED,
                     PER_QUERY_OPTIONAL, PER_QUERY_REQUIRED, SPAN_OPTIONAL,
                     SPAN_REQUIRED, validate_record, validate_stream)
from .tracker import (InMemoryTracker, JsonlTracker, NoopTracker,
                      PrometheusTextTracker, Span, Tracker, jit_cache_size)
from .alerts import AlertEngine, AlertRule
from .audit import AuditFaults, AuditReport
from .flight import FlightRecorder
from .push import PushTracker
from .trace import SpanNode, TenantTrace, TraceForest, assemble
from .dashboard import (render_audits, render_controls, render_dashboard,
                        render_fleet_header, render_histogram, sparkline,
                        trace_view)

__all__ = [
    "ALERT_OPTIONAL",
    "ALERT_REQUIRED",
    "AUDIT_OPTIONAL",
    "AUDIT_REQUIRED",
    "AlertEngine",
    "AlertRule",
    "AuditFaults",
    "AuditReport",
    "CONTROL_OPTIONAL",
    "CONTROL_REQUIRED",
    "Counter",
    "DEFAULT_COUNT_BUCKETS",
    "DEFAULT_TIME_BUCKETS",
    "FLIGHT_OPTIONAL",
    "FLIGHT_REQUIRED",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "InMemoryTracker",
    "JsonlTracker",
    "MetricsRegistry",
    "NoopTracker",
    "PER_QUERY_OPTIONAL",
    "PER_QUERY_REQUIRED",
    "PrometheusTextTracker",
    "PushTracker",
    "SPAN_OPTIONAL",
    "SPAN_REQUIRED",
    "Span",
    "SpanNode",
    "TenantTrace",
    "TraceForest",
    "Tracker",
    "assemble",
    "jit_cache_size",
    "render_audits",
    "render_controls",
    "render_dashboard",
    "render_fleet_header",
    "render_histogram",
    "sparkline",
    "trace_view",
    "validate_record",
    "validate_stream",
]
