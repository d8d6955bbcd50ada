"""The selective scan's share of its roofline: the least time the chip needs
for the recurrence of the rows and runs the traced steps held (`ssm_scan_call`
of the architecture's counts, found by benchmark/flops.py `counts_for`, once
a state-space layer: each run's state read and written once, each row's
inputs read and its output written) over the device time under
`serve.ssm.scan` and `serve.ssm.state` in one run of the step program (median
over the traced runs; union of the ops' intervals). Of the work, whatever
implements it: an XLA scan over the rows or a kernel read the same here,
because the time is taken by scope. Rows and runs are the integer args `rows`
and `state_runs` of the program's `serve.step.plan` spans. Notes which peak
bounds it. None where the counts have no such call, the trace no such scope,
or the spans no such arg (a program without state-space layers)."""

import statistics

from benchmark import flops, program_trace


def classify(op):
    if "serve.ssm.scan" in op.scope or "serve.ssm.state" in op.scope:
        return ("scan",)
    return ()


def read(ctx):
    if ctx["trace"] is None:
        return None
    counts = flops.counts_for(ctx)
    if not hasattr(counts, "ssm_scan_call"):
        return None
    turns = [s.stats for s in program_trace.of(ctx).spans
             if s.name == "step.plan" and "state_runs" in s.stats
             and "rows" in s.stats]
    if not turns:
        ctx["note"](serve_ssm_scan_roofline=None,
                    why="no state_runs on step.plan")
        return None
    ms = program_trace.by_scope_of(ctx, "serve_ssm_scan_roofline", classify)
    if ms is None or not ms.get("scan"):
        return None
    cfg = ctx["config"]
    least, bounds = [], {"compute": 0, "memory": 0}
    for t in turns:
        need = counts.ssm_scan_call(cfg, int(t["rows"]), int(t["state_runs"]))
        r = flops.roofline(need["flops"], need["bytes"], ctx["peaks"])
        least.append(r["least_s"] * counts.ssm_layers(cfg))
        bounds[r["bound"]] += 1
    ctx["note"](serve_ssm_scan_roofline_bound=max(bounds, key=bounds.get),
                scan_turns=len(turns), scan_ms_per_step=ms["scan"],
                scan_least_ms_per_step=1e3 * statistics.median(least),
                state_runs_p50=statistics.median(
                    int(t["state_runs"]) for t in turns))
    return 100.0 * 1e3 * statistics.median(least) / ms["scan"]
