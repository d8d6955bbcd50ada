"""Profiling — step-windowed `jax.profiler` capture.

- `Profiler` — the train path's capture of a window of steps.
- `ServeProfiler` — windowed `jax.profiler` capture for the serve loop,
  triggered either by a fixed step range (`profile_window: [start, n]` in
  the `serving.observability` config) or by a latency-spike predicate
  (`itl_spike_ms`): the first step whose measured device time crosses the
  threshold starts the capture, so the trace you get is the trace of the
  anomaly, not of a lucky warm step. A capture holds the program's own
  spans (`serve.step.upload`, `serve.frontend.intake`, ...: trace.py
  mirrors every span into a running profiler session) beside the device's
  ops, and each device op carries its `serve.*` named scope.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Optional

import jax

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class ProfilingConfig:
    trace_dir: Optional[str] = None
    start_step: int = 5     # skip compile + warmup steps
    num_steps: int = 3

    def build(self) -> "Profiler":
        return Profiler(self)


class Profiler:
    """Step-windowed trace capture; call `step(n)` once per train step."""

    def __init__(self, config: ProfilingConfig):
        self.config = config
        self._active = False
        self.done = False

    def step(self, step_num: int) -> None:
        c = self.config
        if c.trace_dir is None or self.done:
            return
        if not self._active and step_num >= c.start_step:
            jax.profiler.start_trace(c.trace_dir)
            self._active = True
            logger.info("profiler trace started (step %d) → %s", step_num, c.trace_dir)
        elif self._active and step_num >= c.start_step + c.num_steps:
            jax.profiler.stop_trace()
            self._active = False
            self.done = True
            logger.info("profiler trace written to %s", c.trace_dir)

    def close(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self.done = True


class ServeProfiler:
    """Serving-path windowed capture. One capture per run: either the
    fixed `window = (start_step, num_steps)` or the first step whose
    measured time exceeds `itl_spike_ms` (then `spike_steps` more)."""

    def __init__(self, trace_dir: str, *, window=None,
                 itl_spike_ms: float | None = None, spike_steps: int = 3):
        self.trace_dir = trace_dir
        self.window = tuple(window) if window else None
        self.itl_spike_ms = itl_spike_ms
        self.spike_steps = spike_steps
        self._active = False
        self._stop_at: int | None = None
        self.done = False
        self.triggered_by: str | None = None

    def observe(self, step_idx: int, step_ms: float | None = None) -> None:
        """Call once per serve step with the step's measured wall ms."""
        if self.done or self.trace_dir is None:
            return
        if not self._active:
            if self.window and self.window[0] <= step_idx:
                self._start(step_idx, step_idx + self.window[1], "window")
            elif (self.itl_spike_ms is not None and step_ms is not None
                  and step_ms > self.itl_spike_ms):
                self._start(step_idx, step_idx + self.spike_steps, "spike")
        elif self._stop_at is not None and step_idx >= self._stop_at:
            self._stop()

    def _start(self, step_idx: int, stop_at: int, why: str) -> None:
        jax.profiler.start_trace(self.trace_dir)
        self._active = True
        self._stop_at = stop_at
        self.triggered_by = why
        logger.info("serve profiler started (%s, step %d) → %s",
                    why, step_idx, self.trace_dir)

    def _stop(self) -> None:
        jax.profiler.stop_trace()
        self._active = False
        self.done = True
        logger.info("serve profiler trace written to %s", self.trace_dir)

    def close(self) -> None:
        if self._active:
            self._stop()
