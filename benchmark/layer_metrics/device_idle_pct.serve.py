"""Share of the traced slice in which no operation ran on the chip: 1 minus
the union of the device-op intervals over first-op-start to last-op-end."""

from benchmark import trace_reduce


def read(ctx):
    if ctx["trace"] is None:
        return None
    lo, hi = trace_reduce.window_of(ctx["trace"])
    busy = trace_reduce.busy_seconds(ctx["trace"].devices[0])
    return 100.0 * (1.0 - busy / (hi - lo))
