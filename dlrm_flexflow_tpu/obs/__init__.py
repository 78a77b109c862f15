"""Unified observability layer: metrics registry, structured tracing,
and the live predicted-vs-measured drift monitor.

Three modules, one switch:

- :mod:`.metrics` — process-wide Counter/Gauge/Histogram registry with
  labels, bounded-reservoir percentiles, and Prometheus-text exposition
  (``GET /metrics`` in serve_dlrm.py). The serving stack's ``stats()``
  dicts keep their shapes; their latency windows and hot counters are
  now backed by registry instruments.
- :mod:`.trace` — named spans in a bounded in-memory ring, tagged with
  the emitting ``ff-*`` thread, exported as Chrome-trace/Perfetto JSON:
  prefetch → superstep dispatch on the training side, enqueue →
  batch-form → dispatch → swap on the serving side, publish →
  watcher-apply → swap for freshness.
- :mod:`.drift` — the runtime twin of shardcheck FLX513: measured step
  wall time and lowered-HLO collective bytes compared online against
  the simulator's predictions, with gauges and a loud (debounced)
  structured warning when measured/predicted exceeds the threshold.

Everything is OFF by default and free when off (no-op singletons, type
identity pinned like ``make_lock``); the one thing that is always there
is that a span is a profiler annotation (``trace.span``), inert without
a profiler session. Turn the rest on with ``--obs on``
(plus ``--obs-trace-dir DIR`` to export traces) or programmatically via
:func:`configure` / the per-module ``override`` context managers.
Configure BEFORE building engines/fleets — instruments resolve at
creation time.
"""

from __future__ import annotations

from . import metrics, trace


def configure(cfg) -> bool:
    """Apply an FFConfig's ``--obs`` flags process-wide. Returns True
    when observability ended up enabled. Idempotent; never turns obs
    OFF (a second model with the default config must not disable the
    first one's instruments mid-run)."""
    if str(getattr(cfg, "obs", "off")) != "on":
        return metrics.enabled()
    metrics.set_enabled(True)
    trace.set_enabled(True)
    d = str(getattr(cfg, "obs_trace_dir", "") or "")
    if d:
        trace.set_trace_dir(d)
    return True


__all__ = ["metrics", "trace", "configure"]
