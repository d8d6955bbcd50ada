"""Span/event tracer for the serving stack — host-side, dual-clock.

One tracer, two sinks. A span ALWAYS opens a `jax.profiler.TraceAnnotation`
named `serve.<name>` for its lifetime (stats: `engine_step` and the span's
int args), so whenever a profiler session is running — the benchmark's
`--trace 1`, `ServeProfiler`'s window or spike capture, a hand-started
trace — the program's spans lie in the `/host:CPU` plane on the profiler's
own clock, beside the device's ops. `ObservabilityConfig.enabled` only
decides whether the second sink, the in-memory buffer below (exports,
flight recorder, timelines), is kept. Of the instants only the request
markers of `PROFILED_INSTANTS` are mirrored, as zero-length
`serve.request.*` annotations (`request.commit` fires dozens of times a
step): their stats are `rid` and the wall-clock args the caller measured
(`wall=`), which go to the profiler alone, so the buffer's args, and
`digest()`, stay deterministic.

Every in-memory event carries BOTH clocks: wall time (`time.perf_counter`,
exported in microseconds for Perfetto) and a step clock (`step`), because
serving questions come in both flavors — "how many milliseconds did the
KV handoff take" and "how many steps did this request wait in the
admission queue". Spans (`ph == "X"`) time engine phases (intake, plan,
upload, dispatch, read-back, absorb, emit, KV transfers) and are stamped
with the ENGINE's step (`engine.steps_run`), so one turn's spans join on
it; instants (`ph == "i"`) mark request lifecycle transitions (submit →
admit → first_token → commit → done/shed/cancel/expire) on their track's
own clock.

Three export faces:

- `export_chrome(path)` — Chrome trace-event JSON, loadable in Perfetto
  (`ui.perfetto.dev`) or `chrome://tracing`; tracks become named threads.
- `export_jsonl(path)`  — one event object per line, greppable.
- `digest()`            — sha1 over the DETERMINISTIC projection of each
  request's lifecycle (event names + integer payloads, never wall times
  or step indices), so two identical runs produce identical digests even
  though the online loop's idle turns make absolute timing nondeterministic.

The flight recorder is a bounded ring of the most recent events,
maintained alongside the full buffer; `Observability.flight_dump` writes
it on crash / stall / SIGTERM so the last moments before a failure are
always on disk, next to the resilience layer's emergency checkpoint.

Everything here is plain host Python. Calling any of it from
jit-reachable code is a host-sync hazard — lint rule AM106 flags it.
"""

from __future__ import annotations

import hashlib
import json
import time

from jax.profiler import TraceAnnotation

#: prefix of every span's name in the profiler's trace
PROFILER_PREFIX = "serve."
#: the lifecycle instants that also go to the profiler's trace: first
#: admission to a slot, the first plan that holds a row of the request, its
#: first committed token (no `engine_step` stat: the scheduler's clock is
#: the frontend's turn, not the engine's step)
PROFILED_INSTANTS = ("request.admit", "request.first_row", "request.first_token")


class TraceEvent:
    __slots__ = ("name", "ph", "ts", "dur", "step", "track", "rid", "args")

    def __init__(self, name, ph, ts, dur, step, track, rid, args):
        self.name = name
        self.ph = ph          # "X" complete span | "i" instant
        self.ts = ts          # wall seconds (perf_counter epoch)
        self.dur = dur        # span duration, seconds (0.0 for instants)
        self.step = step      # engine step clock (-1 = not step-aligned)
        self.track = track    # logical thread: engine / replica0 / prefill1 ...
        self.rid = rid        # request id (-1 = not request-scoped)
        self.args = args      # small dict of ints/strs; deterministic values only

    def to_dict(self) -> dict:
        d = {
            "name": self.name, "ph": self.ph,
            "ts_us": round(self.ts * 1e6, 1), "step": self.step,
            "track": self.track, "rid": self.rid,
        }
        if self.ph == "X":
            d["dur_us"] = round(self.dur * 1e6, 1)
        if self.args:
            d["args"] = self.args
        return d


def _annotation(name, step, args):
    """The profiler-side sink of one span (span args are ints)."""
    return TraceAnnotation(PROFILER_PREFIX + name, engine_step=step, **args)


def _marker(name, rid, wall):
    """The profiler-side sink of one instant: a zero-length annotation for
    a name of `PROFILED_INSTANTS` whose caller measured its `wall` args
    (a dict of ints, perhaps empty; None: this occurrence stays in memory,
    as a re-admission after a preemption does)."""
    if wall is not None and name in PROFILED_INSTANTS:
        with TraceAnnotation(PROFILER_PREFIX + name, rid=rid, **wall):
            pass


class _SpanCtx:
    """One span into both sinks: the profiler's annotation for its
    lifetime, one X event in the tracer's buffer on exit."""

    __slots__ = ("_tr", "_name", "_track", "_step", "_rid", "_args", "_t0",
                 "_ann")

    def __init__(self, tr, name, track, step, rid, args):
        self._tr = tr
        self._name = name
        self._track = track
        self._step = step
        self._rid = rid
        self._args = args
        self._t0 = 0.0
        self._ann = _annotation(name, step, args)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set_metadata(self, **args):
        """Args known only once the span's work is done (the rows of the
        plan `step.plan` returned); same name as `TraceAnnotation`'s."""
        self._args.update(args)
        self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._tr._record(TraceEvent(
            self._name, "X", self._t0, t1 - self._t0,
            self._step, self._track, self._rid, self._args,
        ))
        return self._ann.__exit__(*exc)


class NullTracer:
    """The tracer without a buffer: an instant is the profiler's marker
    alone where it is one of `PROFILED_INSTANTS` (a no-op otherwise) and a
    span the profiler's annotation alone (well under a microsecond each
    when no profiler session is running), so the instrumented hot loops
    cost a few microseconds a step when tracing is off and nothing
    device-side ever depends on tracing either way."""

    enabled = False
    events = ()

    def instant(self, name, *, track="engine", step=-1, rid=-1, wall=None,
                **args):
        _marker(name, rid, wall)

    def span(self, name, *, track="engine", step=-1, rid=-1, **args):
        return _annotation(name, step, args)


NULL_TRACER = NullTracer()


class Tracer:
    """Recording tracer: appends to one unbounded buffer. The
    flight-recorder "ring" is virtual — the last `ring_len` events of the
    buffer, materialized only at dump time — so the hot record path is a
    single list.append (atomic under the online frontend's threading
    model: event loop + one executor thread)."""

    enabled = True

    def __init__(self, *, ring_len: int = 256):
        self.events: list[TraceEvent] = []
        self.ring_len = max(1, int(ring_len))

    @property
    def ring(self) -> list:
        return self.events[-self.ring_len:]

    # -- recording --------------------------------------------------------

    def _record(self, ev: TraceEvent) -> None:
        self.events.append(ev)

    def instant(self, name, *, track="engine", step=-1, rid=-1, wall=None,
                **args):
        _marker(name, rid, wall)
        self._record(TraceEvent(
            name, "i", time.perf_counter(), 0.0, step, track, rid, args
        ))

    def span(self, name, *, track="engine", step=-1, rid=-1, **args):
        return _SpanCtx(self, name, track, step, rid, args)

    # -- export -----------------------------------------------------------

    def _chrome_events(self) -> list[dict]:
        tids = {}
        out = []
        for t in sorted({e.track for e in self.events}):
            tids[t] = len(tids)
            out.append({
                "name": "thread_name", "ph": "M", "pid": 0, "tid": tids[t],
                "args": {"name": t},
            })
        for e in self.events:
            d = {
                "name": e.name, "ph": e.ph, "pid": 0, "tid": tids[e.track],
                "ts": round(e.ts * 1e6, 1),
                "args": {"step": e.step, "rid": e.rid, **e.args},
            }
            if e.ph == "X":
                d["dur"] = round(e.dur * 1e6, 1)
            else:
                d["s"] = "t"
            out.append(d)
        return out

    def export_chrome(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({"traceEvents": self._chrome_events(),
                       "displayTimeUnit": "ms"}, f)

    def export_jsonl(self, path: str) -> None:
        with open(path, "w") as f:
            for e in self.events:
                f.write(json.dumps(e.to_dict()) + "\n")

    def dump_ring(self, path: str, *, reason: str = "") -> int:
        """Write the flight-recorder ring as JSONL; returns event count."""
        evs = list(self.ring)
        with open(path, "w") as f:
            f.write(json.dumps({"flight_recorder": True, "reason": reason,
                                "events": len(evs)}) + "\n")
            for e in evs:
                f.write(json.dumps(e.to_dict()) + "\n")
        return len(evs)

    # -- determinism ------------------------------------------------------

    def digest(self) -> str:
        """sha1 over each request's lifecycle projected onto deterministic
        fields only: per-rid ordered (name, sorted int/str args), rids
        sorted. Wall clocks, durations, and step indices are excluded —
        idle turns in the online loop shift those between otherwise
        identical runs — and so are `stream.*` backpressure edges, which
        depend on consumer read timing rather than the request's
        lifecycle."""
        by_rid: dict[int, list] = {}
        for e in self.events:
            if e.rid < 0 or e.name.startswith("stream."):
                continue
            by_rid.setdefault(e.rid, []).append(
                (e.name, tuple(sorted(e.args.items())))
            )
        h = hashlib.sha1()
        for rid in sorted(by_rid):
            h.update(repr((rid, by_rid[rid])).encode())
        return h.hexdigest()


def validate_chrome_trace(path: str) -> dict:
    """CI helper: parse a Chrome trace export and check per-track span
    sanity — spans sorted by start must properly nest (every span that
    starts inside an open span must also end inside it) and instants must
    carry timestamps. Returns summary stats; raises on violation."""
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    spans: dict[int, list] = {}
    n_spans = n_instants = 0
    for e in evs:
        if e.get("ph") == "X":
            n_spans += 1
            spans.setdefault(e["tid"], []).append(
                (float(e["ts"]), float(e["ts"]) + float(e["dur"]))
            )
        elif e.get("ph") == "i":
            n_instants += 1
            if "ts" not in e:
                raise ValueError(f"instant without ts: {e}")
    for tid, ss in spans.items():
        ss.sort()
        stack: list[float] = []
        for t0, t1 in ss:
            while stack and stack[-1] <= t0:
                stack.pop()
            if stack and t1 > stack[-1] + 1e-6:
                raise ValueError(
                    f"tid {tid}: span [{t0}, {t1}] overlaps enclosing span "
                    f"ending at {stack[-1]} without nesting"
                )
            stack.append(t1)
    return {"events": len(evs), "spans": n_spans, "instants": n_instants,
            "tracks": len(spans)}
