"""The window layers' paged attention as a share of its roofline: the least
time the chip needs for the window calls of the traced turns
(`paged_attention_gqa_call(..., window=...)` of the architecture's counts,
found by benchmark/flops.py `counts_for`: the keys and values of the LIVE
IN-WINDOW blocks read once, q in, out; `window_calls_per_step` calls a step)
over the summed device time of the kernel's events. A window call runs the
GQA kernel's body under the name `paged_attention_window_gqa`, so this reader
and `paged_attention_gqa_roofline` (the full layers' calls) each see their
own. The blocks and rows are the integer args `window_blocks` and `rows` of
the program's `serve.step.plan` spans. Notes which peak bounds it, and the
full layers' live blocks beside the window's. None, and the metric left
out, where the counts have no window call, the trace no such kernel, or the
spans no such arg (a program without window layers)."""

from benchmark import flops, program_trace, trace_reduce

KERNEL = r"paged_attention_window"


def read(ctx):
    if ctx["trace"] is None:
        return None
    counts = flops.counts_for(ctx)
    if not hasattr(counts, "window_calls_per_step"):
        return None
    cfg = ctx["config"]
    turns = [s.stats for s in program_trace.of(ctx).spans
             if s.name == "step.plan" and "window_blocks" in s.stats
             and "rows" in s.stats]
    seconds, calls = trace_reduce.name_seconds(ctx["trace"].devices[0], KERNEL)
    if not turns or not calls:
        ctx["note"](paged_attention_window_roofline=None,
                    why="no window_blocks on step.plan" if not turns
                    else "no paged_attention_window kernel in the trace")
        return None
    window, page = int(cfg["sliding_window"]), int(cfg["serving"]["page_size"])
    per_step = counts.window_calls_per_step(cfg)
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for t in turns:
        rows, blocks = int(t["rows"]), int(t["window_blocks"])
        need = counts.paged_attention_gqa_call(
            cfg, rows, rows * window, blocks * page, window=window)
        r = flops.roofline(need["flops"], need["bytes"], ctx["peaks"])
        least += r["least_s"] * per_step
        bounds[r["bound"]] += 1
    # scale the traced turns to the calls the trace holds
    least *= calls / (len(turns) * per_step)
    mean = lambda key: sum(int(t.get(key, 0)) for t in turns) / len(turns)  # noqa: E731
    ctx["note"](
        paged_attention_window_roofline_bound=max(bounds, key=bounds.get),
        window_kernel_calls=calls, window_kernel_seconds=seconds,
        window_kernel_ms_per_call=1e3 * seconds / calls,
        window_blocks_per_turn=mean("window_blocks"),
        full_blocks_per_turn=mean("full_blocks"))
    return 100.0 * least / seconds
