"""The program's own spans and named scopes, read from a profiler trace.

`trace_reduce` reads a trace for what every program has: device ops by name,
busy intervals, program runs. This module reads the same `.xplane.pb` once more
for what the serve program writes into it (docs/OBSERVABILITY.md):

- host spans `serve.<name>` in the "/host:CPU" plane, one line per thread,
  each with an `engine_step` stat: `frontend.intake`, `step.plan`, `step.run`
  and its children `step.upload` / `step.dispatch` / `step.readback`,
  `step.absorb`, `frontend.emit`;
- the `jax.named_scope` path of every device op (`serve.layers`, `serve.attn`,
  `serve.pool_write`, `serve.moe`, `serve.moe.experts`, ...), which the TPU
  profiler keeps in the `tf_op` stat (the HLO `op_name`) of the METADATA of an
  "XLA Ops" event, where `jax.profiler.ProfileData` does not reach: `op_names`
  reads that table from the file's bytes.

Both are on the trace's one clock, so a span can be laid beside a device run:
a run of the step program belongs to the `serve.step.dispatch` that began
last before it. A program without the spans or the scopes (this repo before
PR 26; a step served from a compile cache that left the scopes out) gives
empty lists, and every reader then returns `None` with a note, never 0.

All times are seconds on the trace's clock; the readers report milliseconds.
"""

from __future__ import annotations

import bisect
import dataclasses
import os
import re
import statistics

from benchmark import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIX = "serve."
DEVICE_PLANE = "/device:TPU:0"   # one-chip cells: chip 0 is the chip
STEP_PROGRAM = r"_step_impl"
#: ops whose `op_name` the compiler drops: the TPU compiler rewrites a
#: `ragged_dot` into kernels of its own ("ragged-dot-none", tf_op
#: "ragged-dot-none:"), and the step has ragged dots in its routed experts
#: only. They get their scope here, by name, and `named_share` says how much
#: of the device's time that is.
SCOPE_BY_NAME = ((re.compile(r"^ragged-dot"),
                  ("serve.layers", "serve.moe", "serve.moe.experts")),)


@dataclasses.dataclass
class Span:
    name: str        # without the "serve." prefix: "step.upload"
    line: int        # the thread's line in the host plane
    start: float
    end: float
    stats: dict      # {"engine_step": 7, "rows": 256, ...}


@dataclasses.dataclass
class Op:
    name: str        # "fusion.12"
    start: float
    end: float
    scope: tuple     # ("serve.layers", "serve.moe", "serve.moe.experts") or ()
    by_name: bool = False   # the scope is SCOPE_BY_NAME's, not the trace's


@dataclasses.dataclass
class ProgramTrace:
    spans: list      # [Span], sorted by start
    ops: list        # [Op] of chip 0, enclosing ops (`while`) left out
    runs: list       # [(start, end)] of the step program on chip 0


def scope_of(op_name: str) -> tuple:
    """The `serve.*` components of an HLO `op_name` path
    ("jit(_step_impl)/serve.layers/while/body/serve.moe/serve.moe.route/dot"),
    outermost first."""
    return tuple(p for p in op_name.split("/") if p.startswith(PREFIX))


# -- the one thing `ProfileData` does not hand out -------------------------------
# An event's own stats are there (`event.stats`); the stats of its METADATA,
# where the profiler keeps what is the same for every run of an instruction
# (its HLO category, its source line, and `tf_op`, the HLO `op_name` that holds
# the named-scope path), are not. So the planes' `event_metadata` tables are
# read from the file's bytes: protobuf wire format, the field numbers of
# tsl/profiler/protobuf/xplane.proto.

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf: bytes):
    """(field number, value) of each field of one message: an int for a
    varint, the bytes for a length-delimited or a fixed-width field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire}")
            value = buf[i:i + size]
            i += size
        yield key >> 3, value


def op_names(xspace: bytes) -> dict:
    """{instruction name: its HLO `op_name`} from the `tf_op` stat of chip 0's
    event metadata (XSpace.planes=1; XPlane.name=2, .event_metadata=4,
    .stat_metadata=5; map entry .value=2; XEventMetadata.name=2, .stats=5;
    XStatMetadata.id=1, .name=2; XStat.metadata_id=1, .str_value=5,
    .ref_value=7, a reference to a stat metadata's name)."""
    out: dict = {}
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = v.decode()
            elif pf == 4:
                events.append(dict(_fields(v))[2])
            elif pf == 5:
                md = dict(_fields(dict(_fields(v))[2]))
                stat_names[md.get(1, 0)] = md.get(2, b"").decode()
        if name != DEVICE_PLANE:
            continue
        for ev in events:
            ev_name, tf_op = "", None
            for ef, v in _fields(ev):
                if ef == 2:
                    ev_name = v.decode()
                elif ef == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) == "tf_op":
                        tf_op = (st[5].decode() if 5 in st
                                 else stat_names.get(st.get(7), ""))
            if tf_op is not None:
                out[trace_reduce.short_name(ev_name)] = tf_op
    return out


def load(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    scopes = {name: scope_of(op) for name, op in op_names(raw).items()}
    spans, ops, runs = [], [], []
    n_line = 0
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name == DEVICE_PLANE:
            for ln in plane.lines:
                if ln.name == trace_reduce.OPS_LINE:
                    for e in ln.events:
                        name = trace_reduce.short_name(e.name)
                        if trace_reduce.ENCLOSING.match(name):
                            continue
                        s = e.start_ns * 1e-9
                        scope, by_name = scopes.get(name, ()), False
                        if not scope:
                            scope = next((sc for rx, sc in SCOPE_BY_NAME
                                          if rx.match(name)), ())
                            by_name = bool(scope)
                        ops.append(Op(name, s, s + e.duration_ns * 1e-9,
                                      scope, by_name))
                elif ln.name == trace_reduce.MODULES_LINE:
                    for e in ln.events:
                        if re.search(STEP_PROGRAM, e.name):
                            s = e.start_ns * 1e-9
                            runs.append((s, s + e.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                n_line += 1
                for e in ln.events:
                    if e.name.startswith(PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append(Span(
                            e.name[len(PREFIX):], n_line, s,
                            s + e.duration_ns * 1e-9, dict(e.stats)))
    return ProgramTrace(sorted(spans, key=lambda s: s.start),
                        sorted(ops, key=lambda o: o.start), sorted(runs))


def of(ctx) -> ProgramTrace:
    """The run's trace, read once and kept in `ctx` for the other readers
    (`run.finish` removes the trace's directory only after the readers)."""
    if "program_trace" not in ctx:
        ctx["program_trace"] = load(trace_reduce.find_xplane(
            os.path.join(ROOT, ".bench_runs", "trace")))
    return ctx["program_trace"]


# -- host: the gap between two steps ------------------------------------------

@dataclasses.dataclass
class Step:
    """One engine step whose spans and device run are whole in the trace."""
    engine_step: int
    spans: dict      # name -> [Span] with this engine_step
    run: tuple       # (start, end) of its device run

    def one(self, name: str) -> Span:
        return self.spans[name][0]


def steps_of(pt: ProgramTrace) -> list:
    """The traced steps in order, the slice's edge steps dropped. Spans join
    on `engine_step`; a device run belongs to the step whose `step.run` span
    covers most of it: matched by time, never by count. (Not "to the dispatch
    that began last before it": the profiler lays the device's clock beside
    the host's to within a millisecond or so, and a run that reads 0.7 ms
    early would go to the step before.)"""
    by_step: dict = {}
    for s in pt.spans:
        if "engine_step" in s.stats:
            by_step.setdefault(s.stats["engine_step"], {}).setdefault(
                s.name, []).append(s)
    whole = ("step.run", "step.upload", "step.dispatch", "step.readback")
    cand = sorted((sp["step.run"][0].start, sp["step.run"][0].end, n)
                  for n, sp in by_step.items() if all(k in sp for k in whole))
    starts = [c[0] for c in cand]
    run_of: dict = {}
    for run in pt.runs:
        i = bisect.bisect_right(starts, run[0])
        best = max(cand[max(i - 1, 0):i + 1], default=None,
                   key=lambda c: min(c[1], run[1]) - max(c[0], run[0]))
        if best and min(best[1], run[1]) > max(best[0], run[0]):
            run_of.setdefault(best[2], run)
    steps = [Step(n, by_step[n], run_of[n]) for _, _, n in cand if n in run_of]
    return steps[1:-1]


def device_clock_shift(steps: list) -> float:
    """Seconds to add to the device's times: the least shift that puts every
    run's start no earlier than the start of the `step.dispatch` that
    enqueued it, which is what really happened. 0 where the clocks agree."""
    return max([s.one("step.dispatch").start - s.run[0] for s in steps] + [0.0])


#: the event loop's spans between two steps: (name in the notes, span, of
#: which step of the pair)
LOOP_SPANS = (("absorb", "step.absorb", 0), ("emit", "frontend.emit", 0),
              ("intake", "frontend.intake", 1), ("plan", "step.plan", 1))


def gaps(pt: ProgramTrace) -> dict | None:
    """The gap between the device runs of consecutive steps, split where the
    program's spans split it. Medians, in ms, over the pairs (n, n+1):

    device_gap  end of run n .. start of run n+1
    readback    end of run n .. end of `step.readback` n
    frontend    .. start of `step.upload` n+1, and its parts: `wake_up` (to
                the loop's first span), `absorb`, `emit`, `intake`, `plan`,
                `hand_off` (end of the last `step.plan` to the upload) and
                `rest` (the loop's own lines between the spans)
    submit      .. start of run n+1, and its parts `upload`, `dispatch` (to the
                run's start, or the span's end if that comes first), `launch`
                (from the span's end to the run's start, if that comes later)

    `unattributed` = device_gap - (readback + frontend + submit) of the
    medians. The device's times are taken `device_clock_shift` later than the
    trace has them (see there). `longest` is the pair with the longest device
    gap, every part of it and the engine step it follows: where a stall sat,
    if the slice caught one. None where the trace holds no such pair."""
    steps = steps_of(pt)
    shift = device_clock_shift(steps)
    rows: dict = {}
    after: list = []     # the engine step each pair's gap follows
    for a, b in zip(steps, steps[1:]):
        if b.engine_step != a.engine_step + 1:
            continue
        a_end, b_start = a.run[1] + shift, b.run[0] + shift
        rb_end, up = a.one("step.readback").end, b.one("step.upload")
        dispatch_end = b.one("step.dispatch").end
        loop = [(key, s) for key, name, of_b in LOOP_SPANS
                for s in (a, b)[of_b].spans.get(name, ())
                if s.start >= rb_end and s.end <= up.start]
        parts = {key: 0.0 for key, _, _ in LOOP_SPANS}
        for key, s in loop:
            parts[key] += s.end - s.start
        parts["wake_up"] = min(s.start for _, s in loop) - rb_end if loop else 0.0
        parts["hand_off"] = up.start - max(s.end for _, s in loop) if loop else 0.0
        frontend = up.start - rb_end
        row = {
            "device_gap": b_start - a_end,
            "readback": rb_end - a_end,
            "frontend": frontend,
            "submit": b_start - up.start,
            "upload": up.end - up.start,
            "dispatch": min(b_start, dispatch_end) - up.end,
            "launch": max(b_start - dispatch_end, 0.0),
            **parts, "rest": frontend - sum(parts.values()),
        }
        after.append(a.engine_step)
        for k, v in row.items():
            rows.setdefault(k, []).append(v * 1e3)
    if not rows:
        return None
    out = {k: statistics.median(v) for k, v in rows.items()}
    out["unattributed"] = out["device_gap"] - (
        out["readback"] + out["frontend"] + out["submit"])
    out["pairs"] = len(rows["device_gap"])
    out["device_clock_shift"] = shift * 1e3
    # where a stall sat, if the slice caught one: the longest gap, whole
    worst = max(range(out["pairs"]), key=lambda i: rows["device_gap"][i])
    out["longest"] = {"after_engine_step": after[worst],
                      **{k: v[worst] for k, v in rows.items()}}
    return out


def gaps_of(ctx, metric: str) -> dict | None:
    """`gaps` of the run's trace for the reader of `metric`, worked once for
    the three of them. None without a trace (a rehearsal off the chip), and
    None with the note `spans_missing` where the trace holds no pair."""
    if ctx["trace"] is None:
        return None
    if "program_gaps" not in ctx:
        ctx["program_gaps"] = gaps(of(ctx))
    if ctx["program_gaps"] is None:
        ctx["note"](**{metric: None, "why": "spans_missing"})
    return ctx["program_gaps"]


# -- device: the step by sublayer ------------------------------------------------

def _union_ms(ops: list) -> float:
    return trace_reduce.total(trace_reduce.union(
        [(o.start, o.end) for o in ops])) * 1e3


def scoped_share(pt: ProgramTrace) -> dict | None:
    """Share of device-busy time under any `serve.*` scope (`scoped`), and the
    share whose scope is SCOPE_BY_NAME's (`named`, a part of `scoped`)."""
    busy = _union_ms(pt.ops)
    if not busy:
        return None
    return {"scoped": _union_ms([o for o in pt.ops if o.scope]) / busy,
            "named": _union_ms([o for o in pt.ops if o.by_name]) / busy}


def by_scope(pt: ProgramTrace, classify) -> dict | None:
    """Median over the whole runs of the step program of the device time
    (union of intervals, ms) of each class of op: `classify(op)` yields the
    classes an op counts for. None, never 0, where the trace holds no
    `serve.*` scope at all: the program is older than the scopes, or its step
    came from a compile cache that left them out."""
    if not pt.runs or not any(o.scope and not o.by_name for o in pt.ops):
        return None
    runs = pt.runs[1:-1] if len(pt.runs) > 2 else pt.runs
    starts = [o.start for o in pt.ops]
    per_run = []
    for r0, r1 in runs:
        classes: dict = {}
        for o in pt.ops[bisect.bisect_left(starts, r0):
                        bisect.bisect_left(starts, r1)]:
            for c in classify(o):
                classes.setdefault(c, []).append(o)
        per_run.append({c: _union_ms(ops) for c, ops in classes.items()})
    return {c: statistics.median(r.get(c, 0.0) for r in per_run)
            for c in sorted({c for r in per_run for c in r})}


def by_scope_of(ctx, metric: str, classify) -> dict | None:
    """`by_scope` of the run's trace for the reader of `metric`. None without
    a trace, and None with the note `scopes_missing` where it holds no scope."""
    if ctx["trace"] is None:
        return None
    ms = by_scope(of(ctx), classify)
    if ms is None:
        ctx["note"](**{metric: None, "why": "scopes_missing"})
    return ms
