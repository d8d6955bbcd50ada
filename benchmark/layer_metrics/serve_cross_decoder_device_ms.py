"""Device time of the ops under the `serve.cross` named scope in one run of
the step program (median over the traced runs; union of the ops'
intervals): a decoder-hybrid-decoder's cross-decoder, its gated memory units,
cross-attention layers and their MLPs, which run over the step's SAMPLED rows
alone. Notes its parts apart (`gmu`: under `serve.gmu`; `attn`: under
`serve.attn.cross`, and of it `attn_kernel`, the paged calls; `mlp`: under
`serve.mlp`), and the median `cross_rows` (the sample rows) against the
median `rows` of the program's `serve.step.plan` spans. None, and the metric
left out, on a program without the scope (a model without a cross-decoder,
or the parent of the PR that brought it)."""

import re
import statistics

from benchmark import program_trace

KERNEL = re.compile(r"paged_attention")


def classify(op):
    if "serve.cross" not in op.scope:
        return ()
    out = ["cross"]
    if "serve.gmu" in op.scope:
        out.append("gmu")
    elif "serve.attn.cross" in op.scope:
        out.append("attn")
        if KERNEL.search(op.name):
            out.append("attn_kernel")
    elif "serve.mlp" in op.scope:
        out.append("mlp")
    return tuple(out)


def read(ctx):
    ms = program_trace.by_scope_of(ctx, "serve_cross_decoder_device_ms", classify)
    if ms is None or not ms.get("cross"):
        return None
    turns = [s.stats for s in program_trace.of(ctx).spans
             if s.name == "step.plan" and "cross_rows" in s.stats]
    ctx["note"](serve_cross_decoder_device_ms={
        k: ms[k] for k in ("gmu", "attn", "attn_kernel", "mlp") if k in ms},
        cross_rows_p50=statistics.median(int(t["cross_rows"]) for t in turns)
        if turns else None,
        rows_p50=statistics.median(int(t["rows"]) for t in turns)
        if turns else None)
    return ms["cross"]
