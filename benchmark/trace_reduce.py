"""From a profiler trace (`.xplane.pb`) to the few quantities the per-layer
readers need. Reads with `jax.profiler.ProfileData` and nothing else.

A TPU trace has one plane per chip, "/device:TPU:<n>", whose line "XLA Ops"
holds one event per executed HLO op (fusions, custom calls such as Pallas
kernels, collectives) and whose line "XLA Modules" holds one event per run of
a compiled program; host threads are lines of "/host:CPU". All times below
are seconds from the trace's own clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|ragged-all-to-all|"
    r"collective-permute)")


@dataclasses.dataclass
class DeviceTrace:
    chip: int
    ops: list        # [(name, start_s, dur_s)] of the "XLA Ops" line
    modules: list    # [(name, start_s, dur_s)] of the "XLA Modules" line


@dataclasses.dataclass
class Trace:
    devices: list            # [DeviceTrace]
    host: list               # [(name, start_s, dur_s)] host annotations


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """An op event's name is its whole HLO line ("%fusion.3 = bf16[...]
    fusion(...)"); keep the result's name: "fusion.3"."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line) -> list:
    return [(short_name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for e in line.events]


def load(path: str, host_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(DeviceTrace(
                chip=int(m.group(1)),
                ops=_events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                modules=(_events(lines[MODULES_LINE])
                         if MODULES_LINE in lines else []),
            ))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [ev for ev in _events(ln)
                         if ev[0].startswith(host_prefix)]
    devices.sort(key=lambda d: d.chip)
    return Trace(devices=devices, host=sorted(host, key=lambda e: e[1]))


def union(intervals: list) -> list:
    """Merged, sorted [(start, end)] of [(start, end)]."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def busy_intervals(dev: DeviceTrace) -> list:
    return union([(s, s + d) for _, s, d in dev.ops])


def window_of(trace: Trace) -> tuple[float, float]:
    """First start to last end of any device op, over all chips."""
    starts = [s for d in trace.devices for _, s, _ in d.ops]
    ends = [s + t for d in trace.devices for _, s, t in d.ops]
    return min(starts), max(ends)


def busy_seconds(dev: DeviceTrace) -> float:
    return total(busy_intervals(dev))


def name_seconds(dev: DeviceTrace, pattern: str) -> tuple[float, int]:
    """Summed duration and count of the ops whose name matches `pattern`."""
    rx = re.compile(pattern)
    hit = [d for n, _, d in dev.ops if rx.search(n)]
    return sum(hit), len(hit)


#: ops that only enclose others (a scan's `while` holds its body's ops, which
#: the line lists too): left out of a ranking, or they would top every one
ENCLOSING = re.compile(r"^(while|conditional|call)\b")


def top_ops(dev: DeviceTrace, k: int = 10) -> list:
    """[[name, seconds]] of the k op names with most summed time; a run of
    digits in a name is folded ("fusion.12" and "fusion.7" are "fusion.N")."""
    acc: dict = {}
    for n, _, d in dev.ops:
        if ENCLOSING.match(n):
            continue
        key = re.sub(r"\d+", "N", n)
        acc[key] = acc.get(key, 0.0) + d
    return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(dev: DeviceTrace, host: list, k: int = 10) -> list:
    """[[what the host was doing, seconds]] of the k longest gaps between
    device ops, each named by the host annotation that covers most of it;
    host time under no annotation is "outside <the annotations' prefix>*"."""
    spans = union([(s, s + d) for _, s, d in host])
    if host:
        prefix = host[0][0].split(".")[0]
        host = host + [(f"outside {prefix}.*", a[1], b[0] - a[1])
                       for a, b in zip(spans, spans[1:])]
    busy = busy_intervals(dev)
    gaps = [(b[1], n[0]) for b, n in zip(busy, busy[1:])]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    out = []
    for s, e in gaps:
        best, cover = "unattributed", 0.0
        for name, hs, hd in host:
            c = min(e, hs + hd) - max(s, hs)
            if c > cover:
                best, cover = name, c
        out.append([best, e - s])
    return out


def exposed_collective_seconds(dev: DeviceTrace) -> float:
    """Seconds in which a collective runs on this chip and no other op does."""
    coll = union([(s, s + d) for n, s, d in dev.ops if COLLECTIVE.match(n)])
    comp = union([(s, s + d) for n, s, d in dev.ops if not COLLECTIVE.match(n)])
    hidden, j = 0.0, 0
    for cs, ce in coll:
        while j < len(comp) and comp[j][1] <= cs:
            j += 1
        i = j
        while i < len(comp) and comp[i][0] < ce:
            hidden += min(ce, comp[i][1]) - max(cs, comp[i][0])
            i += 1
    return total(coll) - hidden


def program_runs(dev: DeviceTrace, pattern: str) -> list:
    """[(start_s, dur_s)] of the runs of the compiled programs whose name
    matches `pattern` ("XLA Modules" line)."""
    rx = re.compile(pattern)
    return [(s, d) for n, s, d in dev.modules if rx.search(n)]


def traced(steps: list, window: dict) -> list:
    """The harness's step records (anything with host-clock .t0 and .t1) that
    ran wholly while the profiler was on."""
    on, off = window["trace_on"], window["trace_off"]
    return [s for s in steps if s.t0 >= on and s.t1 <= off]


def describe(path: str, top: int = 25) -> None:
    """Print what a trace holds: planes, their lines, and each line's names
    with most time. For looking at a trace by hand before reading it."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for ln in plane.lines:
            acc: dict = {}
            n = 0
            for e in ln.events:
                n += 1
                a = acc.setdefault(e.name, [0, 0.0])
                a[0] += 1
                a[1] += e.duration_ns * 1e-9
            print(f"  line {ln.name!r}: {n} events, {len(acc)} names")
            for name, (c, s) in sorted(acc.items(), key=lambda kv: -kv[1][1])[:top]:
                print(f"    {s:10.6f} s  x{c:<6d} {name[:150]}")
