"""`paged_attention_gqa`'s share of its roofline: the least time the chip
needs for the paged attention of the rows and contexts the traced steps held
(`paged_attention_gqa_call` of the architecture's counts, found by
benchmark/flops.py `counts_for`; `calls_per_step` calls a step, one a layer a
pass; not the padded rows x pages grid) over the summed device time of the
kernel's events. Notes which peak bounds it. None, and the metric left out,
where the architecture's counts have no such call or the trace no such
kernel."""

from benchmark import flops, trace_reduce

KERNEL = r"paged_attention_gqa"


def read(ctx):
    if ctx["trace"] is None:
        return None
    cfg = ctx["config"]
    steps = trace_reduce.traced(ctx["steps"], ctx["window"])
    seconds, calls = trace_reduce.name_seconds(ctx["trace"].devices[0], KERNEL)
    counts = flops.counts_for(ctx)
    if not steps or not calls or not hasattr(counts, "paged_attention_gqa_call"):
        return None
    per_step = counts.calls_per_step(cfg)
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for s in steps:
        need = counts.paged_attention_gqa_call(
            cfg, s.rows, s.context_tokens, s.sequence_tokens)
        r = flops.roofline(need["flops"], need["bytes"], ctx["peaks"])
        least += r["least_s"] * per_step
        bounds[r["bound"]] += 1
    # scale the harness's steps to the calls the trace holds
    least *= calls / (len(steps) * per_step)
    ctx["note"](paged_attention_gqa_roofline_bound=max(bounds, key=bounds.get),
                kernel_calls=calls, kernel_seconds=seconds,
                kernel_ms_per_call=1e3 * seconds / calls)
    return 100.0 * least / seconds
