"""Profiling — step-windowed `jax.profiler` capture + compiled cost analysis.

Home of the former `utils/profiling.py` (train-path `Profiler`, kept
API-compatible; `utils.profiling` remains as a deprecation shim) plus the
serving-path additions:

- `ServeProfiler` — windowed `jax.profiler` capture for the serve loop,
  triggered either by a fixed step range (`profile_window: [start, n]` in
  the `serving.observability` config) or by a latency-spike predicate
  (`itl_spike_ms`): the first step whose measured device time crosses the
  threshold starts the capture, so the trace you get is the trace of the
  anomaly, not of a lucky warm step.
- `serve_step_cost` / `step_efficiency` — `compiled.cost_analysis()`
  FLOPs/bytes for the engine's jitted step via AOT lowering (does NOT
  touch the jit call cache, so compile-once assertions still hold),
  joined with measured step wall time into achieved-FLOP/s and
  bandwidth figures, and MFU / bandwidth-utilization when hardware peaks
  are known.
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


annotate = jax.named_scope  # the NVTX-range analog for model code


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


def serve_step_cost(engine) -> dict | None:
    """FLOPs/bytes of the engine's compiled serve step via AOT
    `lower().compile().cost_analysis()`. AOT compilation is cached
    separately from the jit call cache, so `step_cache_size()` (the
    compile-once counter) is unaffected. Returns None when the backend
    does not expose a cost model."""
    try:
        plan = engine.empty_plan()
        lowered = engine.lower_step(plan)
        cost = lowered.compile().cost_analysis()
        if not cost:
            return None
        return {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        }
    except Exception as e:  # pragma: no cover - backend-dependent
        logger.debug("serve step cost analysis unavailable: %s", e)
        return None


def step_efficiency(cost: dict | None, step_s: float, *,
                    peak_flops: float | None = None,
                    peak_bytes_per_s: float | None = None) -> dict:
    """Join static cost with one measured step time. Achieved rates are
    always reported; MFU / bandwidth-utilization only when the hardware
    peaks are known (None on CPU fallback runs)."""
    out = {"step_ms": step_s * 1e3}
    if not cost or step_s <= 0:
        return out
    gflops_s = cost["flops"] / step_s / 1e9
    gbytes_s = cost["bytes_accessed"] / step_s / 1e9
    out.update({
        "flops_per_step": cost["flops"],
        "bytes_per_step": cost["bytes_accessed"],
        "achieved_gflops_per_s": gflops_s,
        "achieved_gbytes_per_s": gbytes_s,
    })
    if peak_flops:
        out["mfu"] = cost["flops"] / step_s / peak_flops
    if peak_bytes_per_s:
        out["bw_util"] = cost["bytes_accessed"] / step_s / peak_bytes_per_s
    return out
