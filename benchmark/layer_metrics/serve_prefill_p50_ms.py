"""Median time from the first plan that held a row of a request to its first
committed token: `since_first_row_us` of the program's
`serve.request.first_token` markers in the traced slice, that is chunked
prefill and the step that committed the token. Notes the `requests`
counted. None, with the note `spans_missing`, where the program writes no
such marker (older than PR 38)."""

import statistics

from benchmark import program_trace


def read(ctx):
    if ctx["trace"] is None:
        return None
    waits = [s.stats["since_first_row_us"] / 1e3
             for s in program_trace.of(ctx).spans
             if s.name == "request.first_token"
             and "since_first_row_us" in s.stats]
    if not waits:
        ctx["note"](serve_prefill_p50_ms=None, why="spans_missing")
        return None
    ctx["note"](prefill_requests=len(waits))
    return statistics.median(waits)
