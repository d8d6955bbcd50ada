"""The whole serve step's share of the chip's bf16 peak: model FLOPs of the
rows the traced steps really held (the architecture's own counts, found by
benchmark/flops.py `counts_for`: `serve_step_flops` from each step's rows,
contexts and sampled rows) over the device-busy seconds of the traced slice
times the peak. Steps are matched to the trace by count: mean FLOPs of the
harness's traced steps times the number of step programs in the trace."""

from benchmark import flops, trace_reduce

STEP_PROGRAM = r"_step_impl"


def read(ctx):
    if ctx["trace"] is None:
        return None
    chip = ctx["trace"].devices[0]
    steps = trace_reduce.traced(ctx["steps"], ctx["window"])
    runs = trace_reduce.program_runs(chip, STEP_PROGRAM)
    busy = trace_reduce.busy_seconds(chip)
    if not steps or not runs or busy <= 0:
        return None
    counts = flops.counts_for(ctx)
    mean = sum(counts.serve_step_flops(ctx["config"], s.rows, s.context_tokens,
                                       s.samples) for s in steps) / len(steps)
    return 100.0 * mean * len(runs) / (busy * ctx["peaks"]["bf16_flops_per_s"])
