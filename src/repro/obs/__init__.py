"""repro.obs: the observability layer of the scheduling stack.

Typed, sim-timestamped scheduling traces (``TraceRecorder``), the
per-round phase decomposition that replaces the untyped
``HistoryPoint.events`` scraping (``RoundDecomposition``), RB
utilization timelines, JSONL + Perfetto exporters (``repro.obs.export``)
and the CLI reporter (``python -m repro.obs.report``).

Enable per run with ``SimConfig(trace=True)``; tracing is
zero-interference — a traced run is bit-identical to an untraced one.

``span`` (``repro.obs.spans``) is the other clock: wall-time spans of
the round's host work, stamped by the JAX profiler while one runs.
"""
from repro.obs.decomposition import (
    GroupDecomposition,
    RoundDecomposition,
    decompose_group_plan,
    mean_phase_seconds,
    round_decomposition,
)
from repro.obs.trace import (
    NULL_RECORDER,
    TRACE_SCHEMA_VERSION,
    TraceEvent,
    TraceRecorder,
    format_round_line,
    round_log_record,
)
from repro.obs.spans import span
from repro.obs.utilization import ledger_rb_utilization

__all__ = [
    "GroupDecomposition",
    "RoundDecomposition",
    "decompose_group_plan",
    "mean_phase_seconds",
    "round_decomposition",
    "NULL_RECORDER",
    "TRACE_SCHEMA_VERSION",
    "TraceEvent",
    "TraceRecorder",
    "format_round_line",
    "round_log_record",
    "ledger_rb_utilization",
    "span",
]
