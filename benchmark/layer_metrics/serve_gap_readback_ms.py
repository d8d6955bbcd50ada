"""From the end of a step's device run to the end of its
`serve.step.readback`: how long the blocking read-back holds the executor
thread after the device is done. Median over the traced steps
(benchmark/program_trace.py `gaps`)."""

from benchmark import program_trace


def read(ctx):
    g = program_trace.gaps_of(ctx, "serve_gap_readback_ms")
    return None if g is None else g["readback"]
