"""From the start of `serve.step.upload` to the start of that step's device
run: the plan's uploads, the dispatch of the jitted step and the launch. Median
over the traced steps; a note gives the three apart (benchmark/program_trace.py
`gaps`)."""

from benchmark import program_trace


def read(ctx):
    g = program_trace.gaps_of(ctx, "serve_gap_submit_ms")
    if g is None:
        return None
    ctx["note"](serve_gap_submit_ms={k: g[k] for k in ("upload", "dispatch", "launch")})
    return g["submit"]
