"""Device time of the ops under the `serve.moe` named scope in one run of
the step program (median over the traced runs; union of the ops' intervals).
Notes route / experts / shared / the rest (the block's norm and residual)
apart; `scoped_share`, the part of all device-busy time that lies under any
`serve.*` scope, with `named_share`, the share (a part of it) whose scope comes
from the op's name because the compiler dropped its `op_name` (the ragged dots:
program_trace.SCOPE_BY_NAME); and `unscoped_ms`, the five ops with most time
under no scope at all (copies the compiler puts in on its own)."""

from benchmark import program_trace

PARTS = {"serve.moe.route": "route", "serve.moe.experts": "experts",
         "serve.moe.shared": "shared"}


def classify(op):
    if not op.scope:
        return ("unscoped/" + op.name,)
    if "serve.moe" not in op.scope:
        return ()
    part = next((PARTS[s] for s in op.scope if s in PARTS), "rest")
    return ("moe", part)


def read(ctx):
    ms = program_trace.by_scope_of(ctx, "serve_moe_device_ms", classify)
    if ms is None:
        return None
    unscoped = sorted(((v, k.split("/", 1)[1]) for k, v in ms.items()
                       if k.startswith("unscoped/")), reverse=True)[:5]
    share = program_trace.scoped_share(program_trace.of(ctx))
    ctx["note"](
        serve_moe_device_ms={k: ms[k] for k in ("route", "experts", "shared", "rest")
                             if k in ms},
        scoped_share=share["scoped"], named_share=share["named"],
        unscoped_ms={k: v for v, k in unscoped})
    return ms.get("moe")
