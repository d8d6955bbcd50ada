"""program_trace on a small synthetic trace, against sums worked by hand, and
on a trace recorded here (CPU: host spans, no device plane, so no scopes)."""

import importlib.util
import os

import pytest

from benchmark import program_trace as pt

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MS = 1e-3


def span(name, line, start_ms, dur_ms, step, **stats):
    return pt.Span(name, line, start_ms * MS, (start_ms + dur_ms) * MS,
                   {"engine_step": step, **stats})


def step_spans(n, t, idle_turn=False):
    """One turn of the online loop from `t` ms: the loop's spans on line 1,
    the executor's on line 2; the device run starts at t + 7.5 and is 90 long.

    t+0.0 intake 0.1 | t+0.2 plan 1.5 | t+2.0 run [upload 4.0 | t+6.0
    dispatch 1.0 | t+7.1 readback 90.9 -> t+98.0] | t+98.3 absorb 0.3 |
    t+98.7 emit 0.5 -> t+99.2; the next turn starts at t+100."""
    out = [
        span("frontend.intake", 1, t, 0.1, n),
        span("step.plan", 1, t + 0.2, 1.5, n, rows=256, samples=50),
        span("step.run", 2, t + 2.0, 96.0, n, rows=256, samples=50),
        span("step.upload", 2, t + 2.0, 4.0, n),
        span("step.dispatch", 2, t + 6.0, 1.0, n),
        span("step.readback", 2, t + 7.1, 90.9, n),
        span("step.absorb", 1, t + 98.3, 0.3, n),
        span("frontend.emit", 1, t + 98.7, 0.5, n),
    ]
    if idle_turn:  # a turn that planned nothing, before this one: same step
        out += [span("frontend.intake", 1, t - 0.5, 0.1, n),
                span("step.plan", 1, t - 0.3, 0.2, n)]
    return out


def run_of(t):
    return ((t + 7.5) * MS, (t + 97.5) * MS)


def op(name, start_ms, dur_ms, *scope):
    return pt.Op(name, start_ms * MS, (start_ms + dur_ms) * MS, tuple(scope))


def layer_ops(t):
    """The ops of one run that starts at `t` ms (90 ms long)."""
    L, A, M = "serve.layers", "serve.attn", "serve.moe"
    return [
        op("copy.1", t, 5.0, "serve.cow"),
        op("dynamic-slice_bitcast_fusion.2", t + 5, 30.0, L),
        op("fusion.3", t + 35, 1.0, L, A),
        op("fusion.4", t + 36, 0.5, L, A, "serve.pool_write"),
        op("paged_attention_mla.5", t + 36.5, 8.0, L, A),
        op("fusion.6", t + 44.5, 1.5, L, M, "serve.moe.route"),
        op("ragged-dot-none.7", t + 46, 20.0, L, M, "serve.moe.experts"),
        # overlaps the ragged dot by 2 ms: the union counts it once
        op("ragged-dot-none.8", t + 64, 22.0, L, M, "serve.moe.experts"),
        op("fusion.9", t + 86, 0.5, L, M, "serve.moe.shared"),
        op("fusion.10", t + 86.5, 0.25, L, M),
        # the write-back: named as a scan copy, but fused under the MoE scope
        op("bitcast_dynamic-update-slice_fusion.11", t + 86.75, 0.25, L, M),
        op("fusion.12", t + 87, 3.0, "serve.head"),
    ]


def trace(n_steps=6, scoped=True, idle_turn_before=None, device_early_ms=0.0):
    """`device_early_ms`: the device's clock reads that much early, as the
    chip's did (0.7 ms) in the first trace this was written against."""
    spans, runs, ops = [], [], []
    for n in range(n_steps):
        t = 100.0 * n
        spans += step_spans(40 + n, t, idle_turn=(n == idle_turn_before))
        a, b = run_of(t - device_early_ms)
        # the launch is not the same every step: 0.5 ms, on odd steps 0.6
        runs.append((a + (n % 2) * 0.1 * MS, b))
        ops += layer_ops(t + 7.5 - device_early_ms)
    if not scoped:
        ops = [pt.Op(o.name, o.start, o.end, ()) for o in ops]
    return pt.ProgramTrace(sorted(spans, key=lambda s: s.start),
                           sorted(ops, key=lambda o: o.start), runs)


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


def test_scope_of_keeps_the_serve_components_in_order():
    name = ("jit(_step_impl)/jit(main)/serve.layers/while/body/serve.moe/"
            "serve.moe.experts/ragged_dot")
    assert pt.scope_of(name) == ("serve.layers", "serve.moe", "serve.moe.experts")
    assert pt.scope_of("jit(_step_impl)/jit(main)/while/body/dot_general") == ()
    assert pt.scope_of("") == ()


def test_steps_join_on_engine_step_and_drop_the_edges():
    steps = pt.steps_of(trace())
    assert [s.engine_step for s in steps] == [41, 42, 43, 44]
    assert steps[1].run == run_of(200.0)
    # a run belongs to the step.run span that covers most of it, not to a
    # count: with the first run missing from the trace the others keep their
    # steps
    t = trace()
    t.runs = t.runs[1:]
    assert [(s.engine_step, s.run) for s in pt.steps_of(t)][0] == (42, run_of(200.0))
    # and a device clock that reads early moves no run to the step before
    early = pt.steps_of(trace(device_early_ms=1.2))
    assert [s.engine_step for s in early] == [41, 42, 43, 44]
    assert early[1].run == run_of(200.0 - 1.2)


def test_the_three_gaps_add_up_to_the_device_gap():
    g = pt.gaps(trace())
    assert g["pairs"] == 3 and g["device_clock_shift"] == 0.0
    # run n ends at t+97.5, run n+1 starts at t+107.5 (odd steps: 107.6); the
    # pairs are (41,42) (42,43) (43,44): medians over 10.0, 10.1, 10.0
    assert g["device_gap"] == pytest.approx(10.0)
    assert g["readback"] == pytest.approx(0.5)      # 97.5 -> 98.0
    assert g["frontend"] == pytest.approx(4.0)      # 98.0 -> 102.0
    assert g["submit"] == pytest.approx(5.5)        # 102.0 -> 107.5
    assert g["readback"] + g["frontend"] + g["submit"] == pytest.approx(g["device_gap"])
    assert g["unattributed"] == pytest.approx(0.0, abs=1e-9)
    # submit apart: upload 4.0, dispatch to the span's end 1.0, launch 0.5
    assert (g["upload"], g["dispatch"], g["launch"]) == pytest.approx((4.0, 1.0, 0.5))
    # frontend apart: 98.0 | absorb 98.3-98.6 | emit 98.7-99.2 | intake
    # 100.0-100.1 | plan 100.2-101.7 | upload at 102.0
    assert g["wake_up"] == pytest.approx(0.3)
    assert (g["absorb"], g["emit"], g["intake"], g["plan"]) == pytest.approx(
        (0.3, 0.5, 0.1, 1.5))
    assert g["hand_off"] == pytest.approx(0.3)
    assert g["rest"] == pytest.approx(4.0 - 0.3 - 0.3 - 0.5 - 0.1 - 1.5 - 0.3)


def test_a_device_clock_that_reads_early_is_shifted_by_the_least_that_fits():
    """The trace has every run 2 ms early: its start lies before the
    dispatch that enqueued it (t+6.0) by 0.5 ms on even steps and 0.4 on odd.
    The least shift that puts every run after its dispatch's start is 0.5: the
    frontend's gap and the device gap are untouched, submit reads low and
    readback high by what the launch really took, which no trace can tell."""
    g = pt.gaps(trace(device_early_ms=2.0))
    assert g["device_clock_shift"] == pytest.approx(0.5)
    assert g["device_gap"] == pytest.approx(10.0) and g["frontend"] == pytest.approx(4.0)
    assert g["submit"] == pytest.approx(4.0)      # upload start -> dispatch start
    assert g["readback"] == pytest.approx(2.0)
    assert g["readback"] + g["frontend"] + g["submit"] == pytest.approx(g["device_gap"])
    assert (g["upload"], g["dispatch"], g["launch"]) == pytest.approx((4.0, 0.0, 0.0))


def test_the_longest_gap_is_noted_whole():
    """A stall in one read-back (step 42's ends 50 ms late, and everything
    after it): the medians hardly move, the longest gap names the place."""
    t = trace()
    for s in t.spans:
        late = s.stats["engine_step"] > 42 or (
            s.stats["engine_step"] == 42 and s.name in ("step.absorb", "frontend.emit"))
        if late:
            s.start, s.end = s.start + 50 * MS, s.end + 50 * MS
        elif s.stats["engine_step"] == 42 and s.name in ("step.run", "step.readback"):
            s.end += 50 * MS
    t.runs = [(a + 50 * MS, b + 50 * MS) if i > 2 else (a, b)
              for i, (a, b) in enumerate(t.runs)]
    g = pt.gaps(t)
    assert g["device_gap"] == pytest.approx(10.0) and g["readback"] == pytest.approx(0.5)
    worst = g["longest"]
    assert worst["after_engine_step"] == 42
    assert worst["device_gap"] == pytest.approx(60.1)
    assert worst["readback"] == pytest.approx(50.5)
    assert worst["frontend"] == pytest.approx(4.0) and worst["submit"] == pytest.approx(5.6)


def test_an_idle_turn_between_two_steps_counts_for_the_frontend():
    g = pt.gaps(trace(n_steps=4, idle_turn_before=2))
    # one pair, (41, 42): the idle turn's intake and plan carry step 42 too
    assert g["pairs"] == 1
    assert g["intake"] == pytest.approx(0.2) and g["plan"] == pytest.approx(1.7)
    assert g["frontend"] == pytest.approx(4.0)


def test_gap_readers_report_the_medians_and_note_the_parts():
    t = trace()
    value, notes = read("serve_gap_submit_ms", t)
    assert value == pytest.approx(5.5)
    assert notes[0]["serve_gap_submit_ms"] == pytest.approx(
        {"upload": 4.0, "dispatch": 1.0, "launch": 0.5})
    value, _ = read("serve_gap_readback_ms", t)
    assert value == pytest.approx(0.5)
    value, notes = read("serve_gap_frontend_ms", t)
    assert value == pytest.approx(4.0)
    assert notes[0]["device_gap_ms"] == pytest.approx(10.0)
    assert notes[0]["unattributed_ms"] == pytest.approx(0.0, abs=1e-9)
    assert set(notes[0]["serve_gap_frontend_ms"]) == {
        "wake_up", "absorb", "emit", "intake", "plan", "hand_off", "rest"}
    assert notes[0]["longest_gap_ms"]["device_gap"] == pytest.approx(10.1)


def test_an_op_under_the_moe_scope_counts_for_moe_and_an_attention_op_for_attn():
    t = trace()
    moe, notes = read("serve_moe_device_ms", t)
    # route 1.5 + experts 46..86 = 40 (two ragged dots, 2 ms shared) + shared
    # 0.5 + the block's own 0.25 + the write-back fused under it 0.25
    assert moe == pytest.approx(42.5)
    assert notes[0]["serve_moe_device_ms"] == pytest.approx(
        {"route": 1.5, "experts": 40.0, "shared": 0.5, "rest": 0.5})
    # every op of the synthetic step is under some serve.* scope
    assert notes[0]["scoped_share"] == pytest.approx(1.0)
    assert notes[0]["named_share"] == 0.0 and notes[0]["unscoped_ms"] == {}
    attn, notes = read("serve_attn_device_ms", t)
    assert attn == pytest.approx(9.5)
    assert notes[0]["serve_attn_device_ms"] == pytest.approx(
        {"kernel": 8.0, "pool_write": 0.5, "rest": 1.0})
    # what stands under `serve.layers` and no sublayer (the synthetic 30 ms
    # slice) is neither's: no column reads it since PR 28
    assert moe + attn <= 90.0 - 30.0


def test_no_scope_at_all_gives_none_and_the_note_never_zero():
    t = trace(scoped=False)
    for name in ("serve_moe_device_ms", "serve_attn_device_ms"):
        value, notes = read(name, t)
        assert value is None
        assert notes == [{name: None, "why": "scopes_missing"}]
    assert pt.scoped_share(t) == {"scoped": 0.0, "named": 0.0}
    # the host spans are still there: the gap metrics read as before
    assert read("serve_gap_frontend_ms", t)[0] == pytest.approx(4.0)


def test_no_span_at_all_gives_none_and_the_note():
    t = trace()
    t.spans = []
    for name in ("serve_gap_submit_ms", "serve_gap_readback_ms",
                 "serve_gap_frontend_ms"):
        value, notes = read(name, t)
        assert value is None
        assert notes == [{name: None, "why": "spans_missing"}]
    # and a rehearsal off the chip, which has no trace, reads nothing
    assert reader("serve_gap_submit_ms").read({"trace": None}) is None


def test_load_reads_a_recorded_trace(tmp_path):
    """A trace recorded here (CPU) yields the program's spans from the host
    plane, one line a thread, with their stats; no device plane, so no op,
    no run, and every reader says so instead of reading 0."""
    import threading

    import jax
    import jax.numpy as jnp

    from automodel_tpu.observability import NULL_TRACER
    from benchmark import trace_reduce

    def executor():
        with NULL_TRACER.span("step.run", step=7, rows=8, samples=2):
            with NULL_TRACER.span("step.upload", step=7):
                jnp.ones((8, 8)).sum().block_until_ready()

    jax.profiler.start_trace(str(tmp_path))
    with NULL_TRACER.span("step.plan", step=7) as plan:
        plan.set_metadata(rows=8, samples=2)
    with jax.profiler.TraceAnnotation("bench.not_the_programs"):
        worker = threading.Thread(target=executor)
        worker.start()
        worker.join()
    jax.profiler.stop_trace()
    t = pt.load(trace_reduce.find_xplane(str(tmp_path)))
    assert [(s.name, s.stats) for s in t.spans] == [
        ("step.plan", {"engine_step": 7, "rows": 8, "samples": 2}),
        ("step.run", {"engine_step": 7, "rows": 8, "samples": 2}),
        ("step.upload", {"engine_step": 7}),
    ]
    plan, run, upload = t.spans
    assert run.line == upload.line != plan.line
    assert run.start <= upload.start and upload.end <= run.end
    assert t.ops == [] and t.runs == []
    assert pt.gaps(t) is None and pt.by_scope(t, lambda o: ("x",)) is None
    assert pt.scoped_share(t) is None


# -- a device plane, built by hand ---------------------------------------------

def varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def f_int(num, value):
    return varint(num << 3) + varint(value)


def f_bytes(num, payload):
    payload = payload.encode() if isinstance(payload, str) else payload
    return varint(num << 3 | 2) + varint(len(payload)) + payload


TF_OP, SOURCE = 7, 8   # stat metadata ids


def event_metadata(mid, hlo_line, tf_op=None):
    stats = f_bytes(5, f_int(1, SOURCE) + f_bytes(5, "engine.py:1"))
    if tf_op is not None:
        stats += f_bytes(5, f_int(1, TF_OP) + f_bytes(5, tf_op))
    value = f_int(1, mid) + f_bytes(2, hlo_line) + stats
    return f_bytes(4, f_int(1, mid) + f_bytes(2, value))        # map entry


def stat_metadata(mid, name):
    return f_bytes(5, f_int(1, mid) + f_bytes(2, f_int(1, mid) + f_bytes(2, name)))


def line(lid, name, events):
    body = f_int(1, lid) + f_bytes(2, name) + f_int(3, 1000)     # timestamp_ns
    for mid, start_us, dur_us in events:
        body += f_bytes(4, f_int(1, mid) + f_int(2, start_us * 10**6)
                        + f_int(3, dur_us * 10**6))              # picoseconds
    return f_bytes(3, body)


def device_xspace(scoped=True):
    """One chip; one run of the step program, 100 us long, with a scan copy,
    a ragged dot whose op_name the compiler dropped, a kernel, and the `while`
    that encloses them."""
    path = "jit(_step_impl)/serve.layers/while/body/closed_call/"
    meta = [
        (1, "%while.1 = (s32[]) while((s32[]) %tuple.9)", "jit(_step_impl)/serve.layers/while:"),
        (2, "%dynamic-slice_bitcast_fusion.2 = bf16[8] fusion(bf16[8] %p)",
         "jit(_step_impl)/serve.layers/while/body/dynamic_slice:"),
        (3, "%ragged-dot-none.3 = bf16[8] custom-call(bf16[8] %p)", "ragged-dot-none:"),
        (4, "%paged_attention_mla.4 = bf16[8] custom-call(bf16[8] %p)",
         path + "serve.attn/paged_attention_mla/pallas_call:"),
        (5, "%copy.5 = bf16[8] copy(bf16[8] %p)", None),
        (6, "jit__step_impl(123)", None),
    ]
    if not scoped:
        meta = [(i, n, t and t.replace("serve.layers/", "").replace("serve.attn/", ""))
                for i, n, t in meta]
    plane = f_int(1, 1) + f_bytes(2, "/device:TPU:0")
    plane += line(1, "XLA Ops", [(1, 0, 90), (2, 0, 30), (3, 30, 40), (4, 70, 20),
                                 (5, 90, 10)])
    plane += line(2, "XLA Modules", [(6, 0, 100)])
    for mid, name, tf_op in meta:
        plane += event_metadata(mid, name, tf_op)
    plane += stat_metadata(TF_OP, "tf_op") + stat_metadata(SOURCE, "source")
    return f_bytes(1, plane)


def test_op_names_reads_the_event_metadata_that_profiledata_hides(tmp_path):
    raw = device_xspace()
    names = pt.op_names(raw)
    assert names["while.1"] == "jit(_step_impl)/serve.layers/while:"
    assert names["ragged-dot-none.3"] == "ragged-dot-none:"
    assert pt.scope_of(names["paged_attention_mla.4"]) == ("serve.layers", "serve.attn")
    assert "copy.5" not in names          # a copy the compiler put in: no tf_op
    # another plane's table is not read: only chip 0's ops are
    assert pt.op_names(raw.replace(b"/device:TPU:0", b"/device:TPU:1")) == {}

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(raw)
    t = pt.load(str(path))
    assert t.runs == [(pytest.approx(1e-6), pytest.approx(101e-6))]
    # the enclosing `while` is left out; the ragged dot is scoped by its name
    assert [(o.name, o.scope, o.by_name) for o in t.ops] == [
        ("dynamic-slice_bitcast_fusion.2", ("serve.layers",), False),
        ("ragged-dot-none.3", ("serve.layers", "serve.moe", "serve.moe.experts"), True),
        ("paged_attention_mla.4", ("serve.layers", "serve.attn"), False),
        ("copy.5", (), False),
    ]
    assert pt.scoped_share(t) == {"scoped": pytest.approx(0.9), "named": pytest.approx(0.4)}
    moe, notes = read("serve_moe_device_ms", t)
    assert moe == pytest.approx(0.040)
    assert notes[0]["unscoped_ms"] == {"copy.5": pytest.approx(0.010)}
    assert read("serve_attn_device_ms", t)[0] == pytest.approx(0.020)


def test_a_step_from_a_cache_without_scopes_reads_none_not_the_named_ops(tmp_path):
    """The compile-cache trap: the same program, its scopes gone. The ragged
    dot would still get a scope from its name; that alone must not count."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(device_xspace(scoped=False))
    t = pt.load(str(path))
    assert [o.by_name for o in t.ops] == [False, True, False, False]
    for name in ("serve_moe_device_ms", "serve_attn_device_ms"):
        value, notes = read(name, t)
        assert value is None and notes == [{name: None, "why": "scopes_missing"}]
