"""Device time of the ops under the `serve.attn.window` named scope in one
run of the step program (median over the traced runs; union of the ops'
intervals): the attention sublayers of the layers with a sliding window,
whose keys and values live in a ring per slot. Notes the paged kernel, the
ring write (`serve.ring_write`) and the rest (projections, the per-head
norms, rope) apart, the FULL layers' attention (`serve.attn.full`) beside
it with its kernel and pool write, and the whole step by sublayer
(`step_parts_ms`: a cell that reports no tail lists neither
`serve_attn_device_ms` nor `serve_moe_device_ms`, which move one). None, and
the metric left out, on a program without the scope (no window layer, or the
parent of the PR that brought it)."""

import re

from benchmark import program_trace

KERNEL = re.compile(r"paged_attention")
STEP_PARTS = ("serve.attn.window", "serve.attn.full", "serve.moe", "serve.mlp",
              "serve.head", "serve.embed", "serve.cow")


def classify(op):
    out = []
    part = next((p for p in STEP_PARTS if p in op.scope), None)
    out.append("step/" + (part or ("attn_other" if "serve.attn" in op.scope
                                   else "unscoped" if not op.scope else "rest")))
    for kind in ("window", "full"):
        if f"serve.attn.{kind}" not in op.scope:
            continue
        if KERNEL.search(op.name):
            what = "kernel"
        elif "serve.ring_write" in op.scope or "serve.pool_write" in op.scope:
            what = "write"
        else:
            what = "rest"
        out += [kind, f"{kind}/{what}"]
    return tuple(out)


def read(ctx):
    ms = program_trace.by_scope_of(ctx, "serve_window_attn_device_ms", classify)
    if ms is None or not ms.get("window"):
        return None
    ctx["note"](serve_window_attn_device_ms={
        "window": {k.split("/")[1]: v for k, v in ms.items()
                   if k.startswith("window/")},
        "full_ms": ms.get("full"),
        "full": {k.split("/")[1]: v for k, v in ms.items()
                 if k.startswith("full/")},
        "step_parts_ms": {k.split("/")[1]: v for k, v in ms.items()
                          if k.startswith("step/")}})
    return ms["window"]
