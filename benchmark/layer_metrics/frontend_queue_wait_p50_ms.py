"""Median wait from `submit` to the first engine step whose plan held a row
of the request, over the requests whose first step began inside the window.
Harness clock on both ends (the step wrapper's own record)."""

import statistics


def read(ctx):
    t0, t1 = ctx["window"]["t0"], ctx["window"]["t1"]
    first = ctx["first_step_t"]
    waits = [(first[r.rid] - r.submit_t) * 1e3 for r in ctx["recs"]
             if r.rid in first and t0 <= first[r.rid] < t1]
    return statistics.median(waits) if waits else None
