"""Device time of the ops under the `serve.attn` named scope in one run of
the step program (median over the traced runs; union of the ops' intervals).
Notes the paged-attention kernel, the pool write (`serve.pool_write`) and the
rest (projections, norms, rope, whatever copies of the pool land here) apart."""

import re

from benchmark import program_trace

KERNEL = re.compile(r"paged_attention")


def classify(op):
    if "serve.attn" not in op.scope:
        return ()
    if KERNEL.search(op.name):
        return ("attn", "kernel")
    return ("attn", "pool_write" if "serve.pool_write" in op.scope else "rest")


def read(ctx):
    ms = program_trace.by_scope_of(ctx, "serve_attn_device_ms", classify)
    if ms is None:
        return None
    ctx["note"](serve_attn_device_ms={k: v for k, v in ms.items() if k != "attn"})
    return ms.get("attn")
