"""Device time of ONE pass of a looped decoder's serve step: the ops under a
`serve.pass<t>` named scope in one run of the step program (union of the
ops' intervals, median over the traced runs), then the median over the
passes. Notes each pass apart; inside a pass `serve.attn` against `serve.mlp`
(the weight-read floor of a looped dense model) and the rest (the norm
between passes); and what of the step lies under no pass (`serve.cow`,
`serve.embed`, `serve.head`, unscoped ops). None where the trace holds no
such scope (a program without passes)."""

import re
import statistics

from benchmark import program_trace

PASS = re.compile(r"^serve\.pass(\d+)$")


def classify(op):
    found = next((PASS.match(s) for s in op.scope if PASS.match(s)), None)
    if found is None:
        part = next((s for s in ("serve.cow", "serve.embed", "serve.head")
                     if s in op.scope), "unscoped" if not op.scope else "other")
        return ("outside", "outside/" + part)
    t = found.group(1)
    sub = ("attn" if "serve.attn" in op.scope
           else "mlp" if "serve.mlp" in op.scope or "serve.moe" in op.scope
           else "rest")
    return (f"pass{t}", f"pass{t}/{sub}")


def read(ctx):
    ms = program_trace.by_scope_of(ctx, "serve_pass_device_ms", classify)
    if ms is None:
        return None
    passes = sorted(k for k in ms if re.fullmatch(r"pass\d+", k))
    if not passes:
        ctx["note"](serve_pass_device_ms=None, why="no serve.pass<t> scope")
        return None
    ctx["note"](
        serve_pass_device_ms={p: ms[p] for p in passes},
        serve_pass_parts_ms={k: v for k, v in ms.items()
                             if "/" in k and k.startswith("pass")},
        serve_outside_passes_ms={k.split("/", 1)[1]: v for k, v in ms.items()
                                 if k.startswith("outside/")},
        serve_outside_passes_total_ms=ms.get("outside"))
    return statistics.median(ms[p] for p in passes)
