"""The paged attention calls WITH SINKS as a share of their roofline: the
least time the chip needs for the sink calls of the traced turns (the
window layers' calls, `paged_attention_gqa_call(..., window=...)` of the
architecture's counts, found by benchmark/flops.py `counts_for`: the keys and
values of the LIVE IN-WINDOW blocks read once at the window layers' geometry,
q in, out; `sink_calls_per_step` calls a step) over the summed device time of
the kernel's events. A call with sinks runs the GQA kernel's body under the
name `paged_attention_sink_gqa`, so this reader, `paged_attention_gqa_roofline`
(full layers without sinks) and `paged_attention_window_roofline` (window
layers without sinks) each see their own. The blocks and rows are the integer
args `window_blocks` and `rows` of the program's `serve.step.plan` spans.
Notes the calls, ms a call and which peak bounds it. None, and the metric
left out, where the counts have no sink call or put sinks on a full layer,
the trace holds no such kernel, or the spans no such arg."""

from benchmark import flops, program_trace, trace_reduce

KERNEL = r"paged_attention_sink"


def read(ctx):
    if ctx["trace"] is None:
        return None
    counts = flops.counts_for(ctx)
    cfg = ctx["config"]
    if not hasattr(counts, "sink_calls_per_step"):
        return None
    per_step = counts.sink_calls_per_step(cfg)
    if not per_step or per_step != counts.window_calls_per_step(cfg):
        return None
    turns = [s.stats for s in program_trace.of(ctx).spans
             if s.name == "step.plan" and "window_blocks" in s.stats
             and "rows" in s.stats]
    seconds, calls = trace_reduce.name_seconds(ctx["trace"].devices[0], KERNEL)
    if not turns or not calls:
        ctx["note"](paged_attention_sink_roofline=None,
                    why="no window_blocks on step.plan" if not turns
                    else "no paged_attention_sink kernel in the trace")
        return None
    window, page = int(cfg["sliding_window"]), int(cfg["serving"]["page_size"])
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
    ctx["note"](
        paged_attention_sink_roofline_bound=max(bounds, key=bounds.get),
        sink_kernel_calls=calls, sink_kernel_seconds=seconds,
        sink_kernel_ms_per_call=1e3 * seconds / calls,
        window_blocks_per_turn=sum(int(t["window_blocks"]) for t in turns)
        / len(turns))
    return 100.0 * least / seconds
