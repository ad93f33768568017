"""``repro.obs`` — serving observability: event-level traces + exporters.

The tracing counterpart to ``serve/metrics.py``'s aggregates: where
``ServeMetrics`` says *what* regressed (TTFT p95, tokens/s), a
:class:`Tracer` threaded through the engine says *which tick, which
request, which phase* — and ``export`` renders it as Chrome trace-event
JSON (Perfetto / ``chrome://tracing``) or a per-request timeline table.
See ``docs/observability.md``.
"""
from repro.obs.export import (format_timeline, save_chrome, timeline,
                              to_chrome, validate_chrome)
from repro.obs.trace import (BUILDS, ENGINE_TRACKS, NULL, ORIGIN,
                             SCHEMA_VERSION, NullTracer, Tracer, activate,
                             get_active, install_build_listener,
                             record_kernel_config, replay_builds, req_track,
                             set_active)

__all__ = [
    "BUILDS", "ENGINE_TRACKS", "NULL", "ORIGIN", "SCHEMA_VERSION",
    "NullTracer", "Tracer", "activate", "format_timeline", "get_active",
    "install_build_listener", "record_kernel_config", "replay_builds",
    "req_track", "save_chrome", "set_active", "timeline", "to_chrome",
    "validate_chrome",
]
