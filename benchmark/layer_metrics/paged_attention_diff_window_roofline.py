"""The differential calls of the layers with a window as a share of their
roofline: the least time the chip needs for them (`paged_attention_diff_call`
of the architecture's counts, found by benchmark/flops.py `counts_for`: the
keys and values of the LIVE IN-WINDOW blocks read once, q in, out;
`window_calls_per_step` calls a step) over the summed device time of the
kernel's events, named `paged_attention_diff_window_gqa`. The blocks and rows
are the integer args `window_blocks` and `rows` of the program's
`serve.step.plan` spans; a row's keys are counted as the window, a bound from
above on the operations. Notes the calls, ms a call and which peak bounds
it. None, and the metric left out, where the counts have no such call, the
trace no such kernel, or the spans no such arg."""

from benchmark import flops, program_trace, trace_reduce

KERNEL = r"paged_attention_diff_window"


def read(ctx):
    if ctx["trace"] is None:
        return None
    counts = flops.counts_for(ctx)
    if not hasattr(counts, "paged_attention_diff_call"):
        return None
    cfg = ctx["config"]
    per_step = counts.window_calls_per_step(cfg)
    turns = [s.stats for s in program_trace.of(ctx).spans
             if s.name == "step.plan" and "window_blocks" in s.stats
             and "rows" in s.stats]
    seconds, calls = trace_reduce.name_seconds(ctx["trace"].devices[0], KERNEL)
    if not per_step or not turns or not calls:
        ctx["note"](paged_attention_diff_window_roofline=None,
                    why="no window_blocks on step.plan" if not turns
                    else "no paged_attention_diff_window kernel in the trace")
        return None
    window, page = int(cfg["sliding_window"]), int(cfg["serving"]["page_size"])
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for t in turns:
        rows, blocks = int(t["rows"]), int(t["window_blocks"])
        need = counts.paged_attention_diff_call(
            cfg, rows, rows * window, blocks * page)
        r = flops.roofline(need["flops"], need["bytes"], ctx["peaks"])
        least += r["least_s"] * per_step
        bounds[r["bound"]] += 1
    least *= calls / (len(turns) * per_step)
    ctx["note"](
        paged_attention_diff_window_roofline_bound=max(bounds, key=bounds.get),
        diff_window_kernel_calls=calls, diff_window_kernel_seconds=seconds,
        diff_window_kernel_ms_per_call=1e3 * seconds / calls)
    return 100.0 * least / seconds
