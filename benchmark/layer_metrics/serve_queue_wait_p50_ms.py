"""Median wait from a request's entry into the program (`FrontendBase.submit`)
to the first plan that held a row of it: `since_submit_us` of the program's
`serve.request.first_row` markers in the traced slice, the program's own
clock on both ends. The counterpart of `frontend_queue_wait_p50_ms`, which
the harness's wrapper times over the whole window. Notes the `requests`
counted and the median wait up to the first admission to a slot
(`serve.request.admit`) beside it: the rest of the wait is for a row of a
step's token budget. None, with the note `spans_missing`, where the program
writes no such marker (older than PR 38)."""

import statistics

from benchmark import program_trace


def _waits_ms(pt, name):
    return [s.stats["since_submit_us"] / 1e3 for s in pt.spans
            if s.name == name and "since_submit_us" in s.stats]


def read(ctx):
    if ctx["trace"] is None:
        return None
    pt = program_trace.of(ctx)
    waits = _waits_ms(pt, "request.first_row")
    if not waits:
        ctx["note"](serve_queue_wait_p50_ms=None, why="spans_missing")
        return None
    admits = _waits_ms(pt, "request.admit")
    ctx["note"](queue_wait_requests=len(waits),
                admit_wait_p50_ms=statistics.median(admits) if admits else None,
                admit_requests=len(admits))
    return statistics.median(waits)
