"""repro.obs — unified observability: timelines, energy attribution,
sweep telemetry, and serve metrics behind one zero-overhead core.

Jax-free and observational-only by contract: nothing here may alter a
:class:`~repro.core.report.CostReport` or enter an explore cache key
(machine-enforced by ``repro.analysis`` — import-boundary protects this
package, CIM205 keeps cache keys obs-free, and the determinism pass
waives its wall-clock rule here and only here).

See ``docs/observability.md`` for the trace schema and workflows.
"""
from .core import (OBS_SCHEMA, Heartbeat, Observer, counter, disable,
                   enable, enabled, event, get_observer, heartbeat,
                   is_enabled, profiled_spans, read_events, read_manifest,
                   set_annotator, span)
from .energy import (component_group, component_rows, energy_table,
                     write_energy_csv, write_energy_json)
from .metrics import ServeMetrics, StreamingHistogram
from .timeline import check_chrome_trace, chrome_trace, write_chrome_trace

__all__ = [
    "OBS_SCHEMA", "Observer", "Heartbeat",
    "enable", "disable", "enabled", "is_enabled", "get_observer",
    "span", "counter", "event", "heartbeat",
    "read_events", "read_manifest", "set_annotator", "profiled_spans",
    "chrome_trace", "write_chrome_trace", "check_chrome_trace",
    "component_group", "component_rows", "energy_table",
    "write_energy_csv", "write_energy_json",
    "ServeMetrics", "StreamingHistogram",
]
