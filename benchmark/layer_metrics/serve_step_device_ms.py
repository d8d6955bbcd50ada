"""Median device duration of the jitted serve step's program, from the
profiler trace ("XLA Modules" line of the chip's plane)."""

import statistics

from benchmark import trace_reduce

STEP_PROGRAM = r"_step_impl"


def read(ctx):
    if ctx["trace"] is None:
        return None
    runs = trace_reduce.program_runs(ctx["trace"].devices[0], STEP_PROGRAM)
    return statistics.median(d for _, d in runs) * 1e3 if runs else None
