"""Device time of the ops under the `serve.ssm` named scope (the state-space
mixers of a step) in one run of the step program (median over the traced
runs; union of the ops' intervals). Notes its four parts apart: `proj` (the
mixer's matrix products, norms and gates), `conv` (the causal convolution over
the rows), `scan` (the recurrence) and `state` (the read and write of the
slots' carried arrays). An op under `serve.ssm.state` counts as `state`
wherever else it lies. Notes too the step beside it, by outermost sublayer
(`step_parts_ms`: `ssm`, `attn` and of it the paged `attn_kernel`, `mlp`,
`head`, `cow`, `embed`, `unscoped`; `step_ms`, the union of them all);
`scoped_share`, the part of all device-busy time that lies under any `serve.*`
scope; and `unscoped_ms`, the five ops with most time under no scope at all.
None where the trace holds no such scope (a program without state-space
layers)."""

import re

from benchmark import program_trace

PARTS = ("state", "scan", "conv", "proj")
SUBLAYERS = ("ssm", "attn", "mlp", "moe", "head", "cow", "embed")
KERNEL = re.compile(r"paged_attention")


def classify(op):
    if not op.scope:
        return ("step", "step/unscoped", "unscoped/" + op.name)
    top = next((s for s in SUBLAYERS if f"serve.{s}" in op.scope), "rest")
    classes = ["step", "step/" + top]
    if top == "attn" and KERNEL.search(op.name):
        classes.append("step/attn_kernel")
    if top == "ssm":
        part = next((p for p in PARTS if f"serve.ssm.{p}" in op.scope), "rest")
        classes += ["ssm", part]
    return tuple(classes)


def read(ctx):
    ms = program_trace.by_scope_of(ctx, "serve_ssm_device_ms", classify)
    if ms is None:
        return None
    if "ssm" not in ms:
        ctx["note"](serve_ssm_device_ms=None, why="no serve.ssm scope")
        return None
    unscoped = sorted(((v, k.split("/", 1)[1]) for k, v in ms.items()
                       if k.startswith("unscoped/")), reverse=True)[:5]
    share = program_trace.scoped_share(program_trace.of(ctx))
    ctx["note"](
        serve_ssm_device_ms={k: ms[k] for k in (*PARTS, "rest") if k in ms},
        step_ms=ms["step"],
        step_parts_ms={k.split("/", 1)[1]: v for k, v in ms.items()
                       if k.startswith("step/")},
        scoped_share=share["scoped"], unscoped_ms={k: v for v, k in unscoped})
    return ms["ssm"]
