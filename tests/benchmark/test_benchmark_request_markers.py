"""The four readers of PR 38 on hand-built `ProgramTrace`s, against sums
worked by hand, and the program's new spans and request markers read back
from a profiler trace recorded here (CPU: host spans, no device plane)."""

import asyncio
import importlib.util
import os

import pytest

from benchmark import program_trace as pt

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MS = 1e-3
READERS = ("serve_gap_notice_ms", "serve_gap_fetch_ms",
           "serve_queue_wait_p50_ms", "serve_prefill_p50_ms")


def span(name, line, start_ms, dur_ms, **stats):
    return pt.Span(name, line, start_ms * MS, (start_ms + dur_ms) * MS, stats)


def step(n, t, split=True):
    """The executor's spans of engine step `n` from `t` ms: upload 4.0,
    dispatch 1.0, read-back t+7.1 .. t+98.0 and, with `split`, inside it
    wait .. t+97.9 and fetch t+97.9 .. t+98.0. The device run is t+7.5 ..
    t+97.5 on the host's clock."""
    out = [span("step.run", 2, t + 2.0, 96.0, engine_step=n, rows=8),
           span("step.upload", 2, t + 2.0, 4.0, engine_step=n),
           span("step.dispatch", 2, t + 6.0, 1.0, engine_step=n),
           span("step.readback", 2, t + 7.1, 90.9, engine_step=n)]
    if split:
        out += [span("step.wait", 2, t + 7.1, 90.8, engine_step=n),
                span("step.fetch", 2, t + 97.9, 0.1, engine_step=n)]
    return out


def steps_trace(device_late_ms=0.0, split=True, n_steps=6):
    """`device_late_ms`: the device's clock reads that much late (negative:
    early, as the chip's did by 0.7 ms in PR 26's first trace)."""
    spans, runs = [], []
    for n in range(n_steps):
        t = 100.0 * n
        spans += step(40 + n, t, split)
        runs.append(((t + 7.5 + device_late_ms) * MS,
                     (t + 97.5 + device_late_ms) * MS))
    return pt.ProgramTrace(sorted(spans, key=lambda s: s.start), [], runs)


def marker(name, t_ms, **stats):
    return span("request." + name, 1, t_ms, 0.0, **stats)


def requests_trace():
    """Five requests: admitted after 100, 200, 300, 400, 500 ms, given a row
    120 ms after that, their first token 40, 30, 20, 50, 10 ms after the row;
    rid 9 was admitted and given its row before the slice, so only its first
    token is in it."""
    spans = []
    for i in range(5):
        admit = 100.0 * (i + 1)
        spans += [marker("admit", 1000 + i, rid=i, since_submit_us=int(admit * 1e3)),
                  marker("first_row", 1001 + i, rid=i,
                         since_submit_us=int((admit + 120) * 1e3)),
                  marker("first_token", 1100 + i, rid=i,
                         since_submit_us=int((admit + 120 + (40, 30, 20, 50, 10)[i]) * 1e3),
                         since_first_row_us=int((40, 30, 20, 50, 10)[i] * 1e3))]
    spans.append(marker("first_token", 999, rid=9, since_submit_us=2_000_000,
                        since_first_row_us=60_000))
    return pt.ProgramTrace(sorted(spans, key=lambda s: s.start), [], [])


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name,
        os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(name, ptrace):
    notes = []
    ctx = {"trace": object(), "program_trace": ptrace,
           "note": lambda **kw: notes.append(kw)}
    return reader(name).read(ctx), notes


def test_notice_is_run_end_to_wait_end_and_the_clock_is_bracketed():
    """Runs end at t+97.5, `step.wait` at t+97.9: the host learns 0.4 ms
    late; the device's clock may be shifted by 0 (no run before its
    dispatch) up to 0.4 (no run ending after the host learned it had)."""
    value, notes = read("serve_gap_notice_ms", steps_trace())
    assert value == pytest.approx(0.4)
    (note,) = notes
    assert note["clock_bracket_ms"] == pytest.approx([0.0, 0.4])
    assert note["clock_contradiction"] is False and note["notice_steps"] == 4


def test_a_device_clock_that_reads_early_narrows_the_bracket_from_below():
    """Every run 2 ms early: it starts 0.5 ms before its dispatch (t+6.0),
    so the lower bound is 0.5; it ends 2.4 ms before the wait's end. At the
    lower bound the notice reads 1.9: the notice and the fetch still add up
    to `serve_gap_readback_ms`, which takes the same shift."""
    t = steps_trace(device_late_ms=-2.0)
    value, notes = read("serve_gap_notice_ms", t)
    assert value == pytest.approx(1.9)
    assert notes[0]["clock_bracket_ms"] == pytest.approx([0.5, 2.4])
    fetch, _ = read("serve_gap_fetch_ms", t)
    assert value + fetch == pytest.approx(pt.gaps(t)["readback"])


def test_crossed_bounds_are_a_contradiction_of_the_clocks():
    """A device clock 1 ms late has every run end 0.6 ms AFTER the host
    learned it had: no shift satisfies both bounds."""
    value, notes = read("serve_gap_notice_ms", steps_trace(device_late_ms=1.0))
    assert notes[0]["clock_bracket_ms"] == pytest.approx([0.0, -0.6])
    assert notes[0]["clock_contradiction"] is True
    assert value == pytest.approx(-0.6)


def test_fetch_is_the_median_copy_and_notes_what_the_halves_cover():
    value, notes = read("serve_gap_fetch_ms", steps_trace())
    assert value == pytest.approx(0.1)
    # wait 90.8 + fetch 0.1 of a 90.9 ms read-back, on each of 4 steps
    assert notes[0]["readback_covered_share"] == pytest.approx(1.0)
    assert notes[0]["fetch_steps"] == 4


def test_queue_wait_is_the_median_first_row_and_notes_the_admission():
    value, notes = read("serve_queue_wait_p50_ms", requests_trace())
    # first rows at 220, 320, 420, 520, 620 ms after submit
    assert value == pytest.approx(420.0)
    assert notes[0]["queue_wait_requests"] == 5
    assert notes[0]["admit_wait_p50_ms"] == pytest.approx(300.0)
    assert notes[0]["admit_requests"] == 5


def test_prefill_is_the_median_first_row_to_first_token():
    value, notes = read("serve_prefill_p50_ms", requests_trace())
    # 40, 30, 20, 50, 10 and rid 9's 60: median of six
    assert value == pytest.approx(35.0)
    assert notes[0]["prefill_requests"] == 6


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_new_spans_reads_none_and_says_why(name):
    """The parent's program: the read-back whole, no request marker. Every
    reader gives None with the note `spans_missing`, never 0, and a
    rehearsal off the chip (no trace) reads nothing."""
    value, notes = read(name, steps_trace(split=False))
    assert value is None
    assert notes == [{name: None, "why": "spans_missing"}]
    assert reader(name).read({"trace": None}) is None


def test_the_markers_and_the_split_read_back_reach_a_profiler_trace(tmp_path):
    """The online loop under a profiler session on the CPU, the in-memory
    tracer off: `program_trace.load` finds `step.wait` and `step.fetch`
    inside each `step.readback` on its line with the engine's step, and the
    three request markers once a request with their wall-clock args, and
    the request readers read them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from automodel_tpu.models.llm import decoder
    from automodel_tpu.models.llm.decoder import TransformerConfig
    from automodel_tpu.serving import Request, ServingConfig, ServingEngine
    from automodel_tpu.serving.frontend import FrontendConfig, OnlineFrontend
    from benchmark import trace_reduce

    cfg = TransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=2,
        num_heads=4, num_kv_heads=2, dtype=jnp.float32, remat_policy="none")
    eng = ServingEngine(
        decoder.init(cfg, jax.random.key(0)), cfg,
        ServingConfig(page_size=4, num_pages=32, max_slots=2, pages_per_slot=6,
                      token_budget=8, prefill_chunk=4))
    lens = (5, 9, 3, 7)

    async def main():
        fe = OnlineFrontend(eng, FrontendConfig(idle_sleep_s=0.0002))
        fe.start()
        reqs = [Request(prompt=[int(t) for t in np.random.default_rng(i)
                                .integers(1, 64, (n,))], max_new_tokens=4)
                for i, n in enumerate(lens)]
        await asyncio.gather(*[fe.submit(r).collect() for r in reqs])
        await fe.close()

    jax.profiler.start_trace(str(tmp_path))
    asyncio.run(main())
    jax.profiler.stop_trace()
    t = pt.load(trace_reduce.find_xplane(str(tmp_path)))
    by_name = {}
    for s in t.spans:
        by_name.setdefault(s.name, []).append(s)
    readbacks = {s.stats["engine_step"]: s for s in by_name["step.readback"]}
    assert sorted(readbacks) == list(range(eng.steps_run))
    for half in ("step.wait", "step.fetch"):
        assert sorted(s.stats["engine_step"] for s in by_name[half]) == sorted(readbacks)
        for s in by_name[half]:
            rb = readbacks[s.stats["engine_step"]]
            assert s.line == rb.line and rb.start <= s.start and s.end <= rb.end
    for name in ("admit", "first_row", "first_token"):
        marks = by_name["request." + name]
        assert sorted(m.stats["rid"] for m in marks) == list(range(len(lens))), name
        assert all("engine_step" not in m.stats for m in marks)
        assert all(m.stats["since_submit_us"] >= 0 for m in marks)
    waits = {name: {m.stats["rid"]: m.stats["since_submit_us"]
                    for m in by_name["request." + name]}
             for name in ("admit", "first_row", "first_token")}
    for rid in range(len(lens)):
        assert waits["admit"][rid] <= waits["first_row"][rid] <= waits["first_token"][rid]
    assert all(0 <= m.stats["since_first_row_us"] <= m.stats["since_submit_us"]
               for m in by_name["request.first_token"])
    # two slots for four requests: two of them waited for a slot
    value, notes = read("serve_queue_wait_p50_ms", t)
    assert value > 0 and notes[0]["queue_wait_requests"] == len(lens)
    value, notes = read("serve_prefill_p50_ms", t)
    assert value > 0 and notes[0]["prefill_requests"] == len(lens)
