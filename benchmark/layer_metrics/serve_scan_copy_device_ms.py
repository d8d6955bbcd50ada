"""Device time of the ops under `serve.layers` and under none of its
sublayers (`serve.attn`, `serve.mlp`, `serve.moe`) in one run of the step
program: the layer scan's own slicing of the stacked operands and its
write-back (median over the traced runs; union of the ops' intervals). A note
cross-checks against the ops the compiler NAMES as such slices
(`dynamic-slice_bitcast_fusion`, `bitcast_dynamic-update-slice_fusion`),
whatever their scope."""

import re

from benchmark import program_trace

BY_NAME = re.compile(program_trace.SCAN_COPY_NAMES)


def classify(op):
    out = []
    if "serve.layers" in op.scope and not any(
            s in op.scope for s in program_trace.SUBLAYERS):
        out.append("scan_copy")
    if BY_NAME.match(op.name):
        out.append("by_name")
    return out


def read(ctx):
    ms = program_trace.by_scope_of(ctx, "serve_scan_copy_device_ms", classify)
    if ms is None:
        return None
    ctx["note"](serve_scan_copy_device_ms={"by_name": ms.get("by_name")})
    return ms.get("scan_copy")
