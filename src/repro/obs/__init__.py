"""Span-level tracing + metrics for the execution stack (observability).

One schema, three timelines: the emulated backend's virtual-clock spans, the
local backend's wall-clock spans, and ``simulate_funcpipe``'s *predicted*
spans — exported as a Perfetto-loadable Chrome trace, summarized into
pipeline-health metrics, and differenced into a predicted-vs-observed gap
attribution.  Front doors: ``run_plan(..., trace=True)`` /
``Session.emulate(trace=True)`` / ``repro emulate --trace out.json`` /
``repro inspect out.json``.

PR 9 closes the loop: ``repro.obs.calibrate`` folds a traced run back into a
*measured* ``ModelProfile`` and re-plans on it — ``Session.emulate(...)
.calibrate().plan()`` or ``repro calibrate trace.json``.
"""
from repro.obs.attribution import ELAPSED, GapRow, gap_attribution
from repro.obs.calibrate import (
    Calibration,
    PerfModelWarning,
    ReplanReport,
    StageObservation,
    calibrate_profile,
    calibrate_trace,
    observe_stages,
    replan,
    stage_prediction_errors,
)
from repro.obs.metrics import pipeline_health
from repro.obs.schema import (
    HOST_OPS,
    OPS,
    PHASES,
    RESOURCE_OF,
    Span,
    SpanRecorder,
    Trace,
    TraceValidationError,
    WorkerTracer,
    open_span,
    validate_trace,
)

__all__ = [
    "ELAPSED", "GapRow", "gap_attribution", "pipeline_health",
    "HOST_OPS", "OPS", "PHASES", "RESOURCE_OF", "Span", "SpanRecorder",
    "Trace", "TraceValidationError", "WorkerTracer", "open_span",
    "validate_trace",
    "Calibration", "PerfModelWarning", "ReplanReport", "StageObservation",
    "calibrate_profile", "calibrate_trace", "observe_stages", "replan",
    "stage_prediction_errors",
]
