"""From the end of `serve.step.readback` of step n to the start of
`serve.step.upload` of step n+1: the frontend's turn on the event loop and
the two hand-offs between the loop and the executor thread. Median over the
traced steps; a note gives its parts, the whole gap between two device
runs beside the three gap metrics, which must add up to it, the longest gap
of the slice with every part of it (where a stall sat), and by how much the
device's clock was shifted to lie beside the host's
(benchmark/program_trace.py `gaps`, `device_clock_shift`)."""

from benchmark import program_trace

PARTS = ("wake_up", "absorb", "emit", "intake", "plan", "hand_off", "rest")


def read(ctx):
    g = program_trace.gaps_of(ctx, "serve_gap_frontend_ms")
    if g is None:
        return None
    ctx["note"](serve_gap_frontend_ms={k: g[k] for k in PARTS},
                device_gap_ms=g["device_gap"], unattributed_ms=g["unattributed"],
                step_pairs=g["pairs"], device_clock_shift_ms=g["device_clock_shift"],
                longest_gap_ms=g["longest"])
    return g["frontend"]
