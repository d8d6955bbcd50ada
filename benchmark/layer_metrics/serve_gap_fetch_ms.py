"""Median duration of `serve.step.fetch`, the second half of
`serve.step.readback`: the copies of the step's outputs to numpy once the
device is done. Notes the share of `serve.step.readback` that
`serve.step.wait` and `serve.step.fetch` cover together, over the same
traced steps. None, with the note `spans_missing`, where the program has no
`step.fetch` span (older than PR 38)."""

import statistics

from benchmark import program_trace


def _dur(span):
    return span.end - span.start


def read(ctx):
    if ctx["trace"] is None:
        return None
    steps = [s for s in program_trace.steps_of(program_trace.of(ctx))
             if "step.wait" in s.spans and "step.fetch" in s.spans]
    if not steps:
        ctx["note"](serve_gap_fetch_ms=None, why="spans_missing")
        return None
    covered = sum(_dur(s.one("step.wait")) + _dur(s.one("step.fetch"))
                  for s in steps)
    ctx["note"](readback_covered_share=covered / sum(
        _dur(s.one("step.readback")) for s in steps), fetch_steps=len(steps))
    return statistics.median(_dur(s.one("step.fetch")) for s in steps) * 1e3
