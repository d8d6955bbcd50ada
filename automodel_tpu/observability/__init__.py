"""Unified observability for the serving stack — host-side, off by default.

- metrics.py:  typed central registry (counters / gauges / fixed-bucket
               histograms) + Prometheus text snapshot; every serve stat
               lands here.
- trace.py:    span/event tracer, dual step-clock + wall-clock stamps,
               Chrome-trace (Perfetto) + JSONL export, deterministic
               lifecycle digest, flight-recorder ring.
- timeline.py: per-request phase timelines → TTFT/ITL attribution
               (queue vs prefill vs transfer vs step vs backpressure).
- profiler.py: `jax.profiler` windowed capture for train + serve paths.

The `Observability` bundle is what the engines thread through: metrics
are ALWAYS live (plain float adds, negligible), and so are the spans'
profiler annotations (trace.py: whenever a profiler session runs, the
program's spans are in its trace); the tracer's in-memory buffer, the
profiler captures and the flight recorder only when
`ObservabilityConfig.enabled`. Nothing in this
package may be referenced from jit-reachable code — the tracer records
host wall clocks and the registry mutates Python floats, either of which
inside a jitted function is a tracing-time no-op at best and a host-sync
hazard at worst. Lint rule AM106 (analysis/lint.py) enforces the fence.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import weakref
from typing import Optional

import jax

from automodel_tpu.observability.metrics import (
    LATENCY_MS_BUCKETS,
    METRIC_CATALOG,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
)
from automodel_tpu.observability.timeline import (
    RequestTimeline,
    attribute_itl,
    attribute_ttft,
    attribution_summary,
    build_timelines,
)
from automodel_tpu.observability.trace import (
    NULL_TRACER,
    NullTracer,
    TraceEvent,
    Tracer,
    validate_chrome_trace,
)
from automodel_tpu.observability.profiler import (
    Profiler,
    ProfilingConfig,
    ServeProfiler,
)

logger = logging.getLogger(__name__)

#: the registries of the live bundles: the process's ONE `jax.monitoring`
#: listener (it cannot be taken off again) ticks the compile counters of each
_COMPILE_WATCHERS: "weakref.WeakSet[MetricsRegistry]" = weakref.WeakSet()


def _on_jax_duration(event: str, seconds: float, **_kw) -> None:
    if not event.endswith("backend_compile_duration"):
        return
    for reg in list(_COMPILE_WATCHERS):
        reg.counter("jax_backend_compiles_total").inc()
        reg.counter("jax_backend_compile_seconds_total").inc(seconds)


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


@dataclasses.dataclass(frozen=True)
class ObservabilityConfig:
    """`serving.observability` YAML section. Everything defaults off;
    with `enabled: false` the tracer keeps no buffer (its spans still
    reach a running profiler session) and the jitted step is the same
    program either way."""

    enabled: bool = False
    #: trace export prefix: writes <trace_path>.trace.json (Perfetto) and
    #: <trace_path>.trace.jsonl at the end of the run
    trace_path: Optional[str] = None
    #: bounded ring of recent events dumped on crash/stall/SIGTERM
    flight_recorder_len: int = 256
    flight_recorder_path: Optional[str] = None
    #: [start_step, num_steps] window for a serve-path jax.profiler capture
    profile_window: Optional[tuple] = None
    #: alternatively: capture when a step exceeds this many ms
    itl_spike_ms: Optional[float] = None
    profile_dir: Optional[str] = None
    #: serve a tiny HTTP /metrics + /healthz endpoint from the online frontend
    #: (0 picks an ephemeral port; None disables)
    http_port: Optional[int] = None


class Observability:
    """The per-engine (or per-router, shared) observability bundle.

    `registry` is always a real `MetricsRegistry` — counters cost one
    float add, so they stay on unconditionally and offline/online stats
    mirror onto them. `tracer` is the null tracer unless enabled: no
    buffer, spans are profiler annotations only.
    """

    def __init__(self, cfg: ObservabilityConfig | None = None, *,
                 registry: MetricsRegistry | None = None):
        self.cfg = cfg or ObservabilityConfig()
        self.enabled = bool(self.cfg.enabled)
        self.registry = registry if registry is not None else MetricsRegistry()
        # a serving process's contract is one step program: a compilation
        # after warm-up belongs on an operator's /metrics
        _COMPILE_WATCHERS.add(self.registry)
        self.tracer = (
            Tracer(ring_len=self.cfg.flight_recorder_len)
            if self.enabled else NULL_TRACER
        )
        self.profiler: ServeProfiler | None = None
        if self.enabled and self.cfg.profile_dir and (
            self.cfg.profile_window or self.cfg.itl_spike_ms is not None
        ):
            self.profiler = ServeProfiler(
                self.cfg.profile_dir,
                window=self.cfg.profile_window,
                itl_spike_ms=self.cfg.itl_spike_ms,
            )

    @classmethod
    def build(cls, cfg: ObservabilityConfig | None) -> "Observability":
        return cls(cfg)

    # -- step hook --------------------------------------------------------

    def observe_step(self, step_idx: int, step_ms: float) -> None:
        self.registry.histogram(
            "serve_step_ms", "device step wall time (ms)"
        ).observe(step_ms)
        if self.profiler is not None:
            self.profiler.observe(step_idx, step_ms)

    # -- exports ----------------------------------------------------------

    def export(self, prefix: Optional[str] = None) -> dict:
        """Write the Chrome + JSONL trace exports; returns written paths."""
        prefix = prefix or self.cfg.trace_path
        if not self.enabled or not prefix or not self.tracer.events:
            return {}
        d = os.path.dirname(prefix)
        if d:
            os.makedirs(d, exist_ok=True)
        chrome, jsonl = prefix + ".trace.json", prefix + ".trace.jsonl"
        self.tracer.export_chrome(chrome)
        self.tracer.export_jsonl(jsonl)
        return {"chrome": chrome, "jsonl": jsonl}

    def flight_dump(self, reason: str, path: Optional[str] = None) -> Optional[str]:
        """Dump the flight-recorder ring (crash / stall / SIGTERM). Safe
        to call from except/finally blocks — never raises."""
        if not self.enabled:
            return None
        try:
            path = path or self.cfg.flight_recorder_path
            if path is None:
                base = self.cfg.trace_path or "flight"
                path = f"{base}.flight.{reason}.jsonl"
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            n = self.tracer.dump_ring(path, reason=reason)
            self.registry.counter(
                "flight_recorder_dumps_total",
                "flight-recorder dumps written (labeled by reason)",
                reason=reason,
            ).inc()
            logger.warning("flight recorder: %d events → %s (%s)",
                           n, path, reason)
            return path
        except Exception:  # pragma: no cover - last-resort path
            logger.exception("flight recorder dump failed")
            return None

    def close(self) -> None:
        if self.profiler is not None:
            self.profiler.close()
        self.export()


#: Shared do-nothing bundle for code paths that never configured one.
#: Its registry is real (process-global default), its tracer is null.
NULL_OBSERVABILITY = Observability(None, registry=default_registry())

__all__ = [
    "LATENCY_MS_BUCKETS",
    "METRIC_CATALOG",
    "NULL_OBSERVABILITY",
    "NULL_TRACER",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NullTracer",
    "Observability",
    "ObservabilityConfig",
    "Profiler",
    "ProfilingConfig",
    "RequestTimeline",
    "ServeProfiler",
    "TraceEvent",
    "Tracer",
    "attribute_itl",
    "attribute_ttft",
    "attribution_summary",
    "build_timelines",
    "default_registry",
    "validate_chrome_trace",
]
