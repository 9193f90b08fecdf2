"""Unified observability plane: metrics, spans, SLE rollups, export.

See DESIGN.md "Observability plane". The registry is always on (it is
where the legacy ad-hoc counters now live); span tracing is gated
`REPRO_OBS=off|on` (off default) and is passive either way — every
trace golden replays byte-identical with obs on.
"""
from repro.obs.export import (OBS_SCHEMA, check_run, diff_runs,
                              export_run, export_scenario, flatten,
                              load, render_dryrun_table, summarize,
                              to_json, write_json, write_spans_jsonl)
from repro.obs.registry import (Counter, Gauge, Histogram,
                                MetricsRegistry, Series)
from repro.obs.sle import (SLE_BAND, accuracy_sle, capacity_sle, fault_sle,
                           fleet_monitoring_usd, fleet_sle, jain_index,
                           responsiveness_steps, scenario_monitoring_usd,
                           scenario_sle)
from repro.obs.spans import (NULL_TRACER, OBS_MODES, NullTracer,
                             SpanTracer, obs_mode)

__all__ = [
    "OBS_SCHEMA", "OBS_MODES", "SLE_BAND", "NULL_TRACER",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Series",
    "NullTracer", "SpanTracer", "obs_mode",
    "accuracy_sle", "capacity_sle", "fault_sle", "jain_index",
    "responsiveness_steps", "scenario_monitoring_usd",
    "fleet_monitoring_usd", "scenario_sle", "fleet_sle",
    "export_run", "export_scenario", "to_json", "write_json",
    "write_spans_jsonl", "load", "flatten", "diff_runs", "check_run",
    "summarize", "render_dryrun_table",
]
