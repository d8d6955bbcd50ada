"""The differential calls that read the ONE shared cache of a
decoder-hybrid-decoder as a share of their roofline: the least time the chip
needs for them over their summed device time. They are the full-attention
layer's call over every row of a step and one call a cross-attention layer
over the step's sample rows, all named `paged_attention_diff_gqa` (a window
layer's call is `paged_attention_diff_window_gqa`, its reader
`paged_attention_diff_window_roofline`). Each call's own reads count
(`paged_attention_diff_call` of the architecture's counts, found by
benchmark/flops.py `counts_for`: the keys and values of its live blocks read
once, q in, out): the full layer's `rows` and `full_blocks` (the live blocks
of its list), a cross layer's `cross_rows` and `cross_blocks`, the integer
args of the program's `serve.step.plan` spans; `shared_kv_calls` gives the
calls of each kind a step. The keys a row scores are counted as the tokens of
its blocks, a bound from above on the operations; the bytes bound the calls
(noted). None, and the metric left out, where the counts have no such call,
the trace no such kernel, or the spans no such arg."""

from benchmark import flops, program_trace, trace_reduce

KERNEL = r"paged_attention_diff_gqa"


def read(ctx):
    if ctx["trace"] is None:
        return None
    counts = flops.counts_for(ctx)
    if not hasattr(counts, "shared_kv_calls"):
        return None
    cfg = ctx["config"]
    full, cross = counts.shared_kv_calls(cfg)
    turns = [s.stats for s in program_trace.of(ctx).spans
             if s.name == "step.plan" and "cross_blocks" in s.stats
             and "attn_live_blocks" in s.stats]
    seconds, calls = trace_reduce.name_seconds(ctx["trace"].devices[0], KERNEL)
    if not turns or not calls:
        ctx["note"](paged_attention_shared_kv_roofline=None,
                    why="no cross_blocks on step.plan" if not turns
                    else "no paged_attention_diff_gqa kernel in the trace")
        return None
    page = int(cfg["serving"]["page_size"])
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for t in turns:
        for n, rows, blocks in ((full, int(t["rows"]), int(t["attn_live_blocks"])),
                                (cross, int(t["cross_rows"]), int(t["cross_blocks"]))):
            need = counts.paged_attention_diff_call(
                cfg, rows, blocks * page, blocks * page)
            r = flops.roofline(need["flops"], need["bytes"], ctx["peaks"])
            least += r["least_s"] * n
            bounds[r["bound"]] += n
    # scale the traced turns to the calls the trace holds
    least *= calls / (len(turns) * (full + cross))
    ctx["note"](
        paged_attention_shared_kv_roofline_bound=max(bounds, key=bounds.get),
        shared_kv_kernel_calls=calls, shared_kv_kernel_seconds=seconds,
        shared_kv_kernel_ms_per_call=1e3 * seconds / calls,
        cross_blocks_per_turn=sum(int(t["cross_blocks"]) for t in turns)
        / len(turns))
    return 100.0 * least / seconds
