"""Mean real rows (decode rows + prefill-chunk rows) of the steps that ended
inside the window, of the step's `token_budget`."""


def read(ctx):
    t0, t1 = ctx["window"]["t0"], ctx["window"]["t1"]
    rows = [s.rows for s in ctx["steps"] if t0 <= s.t1 < t1]
    return sum(rows) / len(rows) if rows else None
