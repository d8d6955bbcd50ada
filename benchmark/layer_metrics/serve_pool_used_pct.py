"""Share of the page pool that is held, as the scheduler left it each turn:
the median over the traced turns of 1 - free_pages / num_pages, from the
`free_pages` arg of the program's `serve.step.plan` spans
(`Scheduler.turn_stats`) and the configuration's `serving.num_pages`. Notes
the median `resident` (requests holding a slot) beside the configuration's
`max_slots`, and the `preempted` sum over the traced turns: where the pool,
not the slots, bounds admission, `resident` stays under `max_slots` while this
reads high. None where the spans carry no such arg (a program older than it)."""

import statistics

from benchmark import program_trace


def read(ctx):
    if ctx["trace"] is None:
        return None
    turns = [s.stats for s in program_trace.of(ctx).spans
             if s.name == "step.plan" and "free_pages" in s.stats]
    if not turns:
        ctx["note"](serve_pool_used_pct=None, why="no free_pages on step.plan")
        return None
    serving = ctx["config"]["serving"]
    ctx["note"](
        pool_turns=len(turns), max_slots=serving["max_slots"],
        resident_p50=statistics.median(int(t["resident"]) for t in turns),
        resident_min=min(int(t["resident"]) for t in turns),
        resident_max=max(int(t["resident"]) for t in turns),
        free_pages_p50=statistics.median(int(t["free_pages"]) for t in turns),
        preempted=sum(int(t["preempted"]) for t in turns))
    return 100.0 * statistics.median(
        1.0 - int(t["free_pages"]) / serving["num_pages"] for t in turns)
