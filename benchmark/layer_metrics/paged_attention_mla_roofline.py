"""`paged_attention_mla`'s share of its roofline: the least time the chip
needs for the latent-space attention of the rows and contexts the traced
steps held (`paged_mla_call` of the architecture's counts, found by
benchmark/flops.py `counts_for`; one call a layer; not the padded rows x pages
grid) over the summed device time of the kernel's events.
Notes which peak bounds it."""

from benchmark import flops, trace_reduce

KERNEL = r"paged_attention_mla"


def read(ctx):
    if ctx["trace"] is None:
        return None
    cfg = ctx["config"]
    steps = trace_reduce.traced(ctx["steps"], ctx["window"])
    seconds, calls = trace_reduce.name_seconds(ctx["trace"].devices[0], KERNEL)
    if not steps or not calls:
        return None
    counts = flops.counts_for(ctx)
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for s in steps:
        need = counts.paged_mla_call(cfg, s.rows, s.context_tokens, s.sequence_tokens)
        r = flops.roofline(need["flops"], need["bytes"], ctx["peaks"])
        least += r["least_s"] * cfg["num_hidden_layers"]
        bounds[r["bound"]] += 1
    # one call a layer a step: scale the harness's steps to the trace's calls
    least *= calls / (len(steps) * cfg["num_hidden_layers"])
    ctx["note"](paged_attention_mla_roofline_bound=max(bounds, key=bounds.get),
                kernel_calls=calls, kernel_seconds=seconds)
    return 100.0 * least / seconds
