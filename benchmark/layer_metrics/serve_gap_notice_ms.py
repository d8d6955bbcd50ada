"""From the end of a step's device run to the end of its `serve.step.wait`
(`jax.block_until_ready` on the step's outputs, the first half of
`serve.step.readback`): how late the host learns that the device is done.
Median over the traced steps, the device's times taken
`program_trace.device_clock_shift` later (the least shift that puts every
run after its dispatch's start: a lower bound). The note `clock_bracket_ms`
gives that shift beside the largest the trace allows, `[lo, hi]` with `hi`
the least over the steps of (end of `step.wait` - end of the run): no run
ends after the host learned it had. `hi < lo` is a contradiction of the two
clocks and is noted as one. None, with the note `spans_missing`, where the
program has no `step.wait` span (older than PR 38)."""

import statistics

from benchmark import program_trace


def read(ctx):
    if ctx["trace"] is None:
        return None
    steps = [s for s in program_trace.steps_of(program_trace.of(ctx))
             if "step.wait" in s.spans]
    if not steps:
        ctx["note"](serve_gap_notice_ms=None, why="spans_missing")
        return None
    lo = program_trace.device_clock_shift(steps)
    late = [s.one("step.wait").end - s.run[1] for s in steps]
    hi = min(late)
    ctx["note"](clock_bracket_ms=[lo * 1e3, hi * 1e3],
                clock_contradiction=hi < lo, notice_steps=len(steps))
    return statistics.median(t - lo for t in late) * 1e3
