"""The looped decoder in the benchmark, off the chip: `tiny_ouro` (an
Ouro-shaped toy, 3 layers walked 4 times, in a pool so small that requests
are preempted) driven end to end through a manifest of its own
(tests/benchmark/tiny_ouro/BENCHMARK.json: new files only), the operation
counts of `counts/ouro.py` against a count by hand, and the three readers
this architecture brought against small synthetic traces."""

import contextlib
import io
import json
import os

import pytest

from benchmark import load_module, peaks, trace_reduce
from benchmark import program_trace as pt
from benchmark import run as bench_run
from benchmark.drivers.closed_loop_serve import Step

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(HERE, "tiny_ouro", "BENCHMARK.json")
CELL = "tiny_ouro.tiny_c4"
MS = 1e-3


def drive(*extra, seed=3_000_000_019):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = bench_run.main(
            ["--workload", CELL, "--seed", str(seed), "--seconds", "1",
             "--trace", "0", *extra], manifest_path=MANIFEST, on_chip=False)
    return rc, out.getvalue().splitlines(), err.getvalue().splitlines()


@pytest.fixture(scope="module")
def rehearsal():
    return drive()


def notes_of(lines):
    return [json.loads(ln[len("note: "):]) for ln in lines[:-1]
            if ln.startswith("note: ")]


def test_the_looped_decoder_is_correct_through_preemptions(rehearsal):
    rc, lines, err = rehearsal
    assert rc == 0
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"] == {}           # off the chip: counts only
    assert set(line["compared"]) == {"gap_max", "logprob_err_max"}
    assert err[-1] == "compared: correct = True"
    notes = notes_of(lines)
    before = next(n for n in notes if "step_cache_size" in n)
    assert before["step_cache_size"] == 1 and before["attention_fallbacks"] == {}
    assert next(n for n in notes if "step_kernels" in n)["step_kernels"] == {
        "paged_attention_gqa": 0}          # looked for on the chip only
    counts = next(n for n in notes if "stats" in n)
    # 12 pages of 8 tokens under 4 clients whose contexts reach 52: the pool
    # binds, and what was preempted and requeued still matched the reference
    assert counts["stats"]["preemptions"] >= 1
    assert counts["stats"]["compiled_signatures"] == 1
    seen = next(n for n in notes if "observed" in n)["observed"]
    assert seen["requests_followed"] == 4 and seen["delivery_mismatch"] == 0


@pytest.mark.parametrize("control", ["fp8", "program_int8"])
def test_the_control_comes_out_not_correct(control):
    rc, lines, err = drive("--control", control, seed=5)
    line = json.loads(lines[-1])
    assert rc == 0 and line["correct"] is False
    number = line["compared"]["logprob_err_max"]
    assert number["value"] > 10 * number["limit"]
    assert err[-1] == "compared: correct = False"


# -- counts --------------------------------------------------------------------
def published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro_2_6b_serve_v5e1.json")) as f:
        return json.load(f)


def test_counts_against_a_count_by_hand():
    counts = load_module(ROOT, ["benchmark"], "counts", "ouro")
    cfg = published()
    # one layer, one pass, one token: q, k, v, o 2048 x 2048 each, three
    # matrices 2048 x 5632; a multiply-add is two operations
    layer = 2 * (4 * 2048 * 2048 + 3 * 2048 * 5632)
    assert counts.layer_linear_flops_per_token(cfg) == layer == 102_760_448
    assert counts.calls_per_step(cfg) == 192
    # 44 rows attending to 9,000 keys in all, 18 of them sampled: 192 layer
    # applications, scores and values over 16 heads of 128, the head once
    scores = 2 * 2 * 16 * 128 * 9000
    want = 192 * (44 * layer + scores) + 18 * 2 * 2048 * 49152
    assert counts.serve_step_flops(cfg, 44, 9000, 18) == want
    # one call: the keys and values of 3,800 cached tokens once (16 heads x
    # 128 x 2 B each, twice), q in and out for 44 rows
    call = counts.paged_attention_gqa_call(cfg, 44, 9000, 3800)
    assert call["flops"] == scores
    assert call["bytes"] == 2 * (3800 * 2 * 16 * 128 + 2 * 44 * 16 * 128)
    # a token's cache over every pass and layer: 1.5 MiB
    assert 192 * 2 * 16 * 128 * 2 == 1536 * 1024


# -- readers -------------------------------------------------------------------
def read(name, ctx):
    notes = []
    ctx = {"note": lambda **kw: notes.append(kw), "root": ROOT,
           "paths": ["benchmark", "tests/benchmark"], **ctx}
    mod = load_module(ROOT, ["benchmark"], "layer_metrics", name)
    return mod.read(ctx), notes


def op(name, start_ms, dur_ms, *scope):
    return pt.Op(name, start_ms * MS, (start_ms + dur_ms) * MS, tuple(scope))


def pass_ops(t):
    """One 100 ms run from `t` ms: cow 4, embed 1, four passes of 22 (attn
    12 of which the kernel 9, mlp 9, the norm between passes 1; the last
    pass has none: 21), head 2, one unscoped copy 1."""
    L = "serve.layers"
    out = [op("fusion.1", t, 4.0, "serve.cow"), op("fusion.2", t + 4, 1.0, "serve.embed")]
    at = t + 5.0
    for p in range(4):
        P = f"serve.pass{p}"
        out += [op("fusion.3", at, 3.0, L, P, "serve.attn"),
                op("paged_attention_gqa.4", at + 3, 9.0, L, P, "serve.attn"),
                op("fusion.5", at + 12, 9.0, L, P, "serve.mlp")]
        at += 21.0
        if p < 3:
            out.append(op("fusion.6", at, 1.0, L, P))
            at += 1.0
    return out + [op("fusion.7", at, 2.0, "serve.head"), op("copy.8", at + 2, 1.0)]


def looped_trace(scoped=True):
    ops, runs = [], []
    for n in range(5):
        t = 110.0 * n
        ops += pass_ops(t)
        runs.append((t * MS, (t + 100.0) * MS))
    if not scoped:   # a program without passes: every other scope is there
        ops = [pt.Op(o.name, o.start, o.end,
                     tuple(s for s in o.scope if not s.startswith("serve.pass")))
               for o in ops]
    return pt.ProgramTrace([], sorted(ops, key=lambda o: o.start), runs)


def test_serve_pass_device_ms_reads_each_pass_and_what_lies_outside():
    value, notes = read("serve_pass_device_ms",
                        {"trace": object(), "program_trace": looped_trace()})
    assert value == pytest.approx(22.0)     # median of 22, 22, 22, 21
    note = notes[0]
    assert note["serve_pass_device_ms"] == pytest.approx(
        {"pass0": 22.0, "pass1": 22.0, "pass2": 22.0, "pass3": 21.0})
    assert note["serve_pass_parts_ms"]["pass2/attn"] == pytest.approx(12.0)
    assert note["serve_pass_parts_ms"]["pass2/mlp"] == pytest.approx(9.0)
    assert note["serve_pass_parts_ms"]["pass2/rest"] == pytest.approx(1.0)
    assert "pass3/rest" not in note["serve_pass_parts_ms"]
    assert note["serve_outside_passes_ms"] == pytest.approx(
        {"serve.cow": 4.0, "serve.embed": 1.0, "serve.head": 2.0, "unscoped": 1.0})
    assert note["serve_outside_passes_total_ms"] == pytest.approx(8.0)


def test_serve_pass_device_ms_is_silent_on_a_program_without_passes():
    value, notes = read("serve_pass_device_ms", {
        "trace": object(), "program_trace": looped_trace(scoped=False)})
    assert value is None and notes[0]["serve_pass_device_ms"] is None
    assert read("serve_pass_device_ms", {"trace": None})[0] is None


def plan_span(step, start_ms, **stats):
    return pt.Span("step.plan", 1, start_ms * MS, (start_ms + 1) * MS,
                   {"engine_step": step, **stats})


def test_serve_pool_used_pct_reads_the_plan_spans_args():
    spans = [plan_span(n, 100.0 * n, rows=44, samples=18, free_pages=f,
                       resident=r, preempted=p)
             for n, (f, r, p) in enumerate(
                 [(4, 20, 0), (0, 21, 1), (2, 20, 0), (9, 19, 0), (1, 21, 2)])]
    ctx = {"trace": object(), "program_trace": pt.ProgramTrace(spans, [], []),
           "config": {"serving": {"num_pages": 84, "max_slots": 24}}}
    value, notes = read("serve_pool_used_pct", ctx)
    assert value == pytest.approx(100.0 * (1 - 2 / 84))   # median free: 2
    assert notes[0]["resident_p50"] == 20 and notes[0]["resident_max"] == 21
    assert notes[0]["preempted"] == 3 and notes[0]["max_slots"] == 24
    # a program older than the args (the parent commit): silent, no 0
    old = [plan_span(n, 100.0 * n, rows=44, samples=18) for n in range(3)]
    ctx["program_trace"] = pt.ProgramTrace(old, [], [])
    value, notes = read("serve_pool_used_pct", ctx)
    assert value is None and notes[0]["why"] == "no free_pages on step.plan"
    assert read("serve_pool_used_pct", {"trace": None})[0] is None


def test_paged_attention_gqa_roofline_counts_the_work_not_the_grid():
    cfg = published()
    # two traced steps, 192 calls each of 0.5 ms; a third step outside the slice
    ops = [("paged_attention_gqa.4", 0.001 * i, 0.0005) for i in range(384)]
    trace = trace_reduce.Trace(
        [trace_reduce.DeviceTrace(0, ops + [("fusion.1", 0.5, 0.01)], [])], [])
    steps = [Step(1.0, 1.2, 44, 18, 9000, 3800), Step(1.2, 1.4, 44, 18, 9000, 3800),
             Step(0.5, 0.7, 48, 20, 99999, 9999)]
    ctx = {"trace": trace, "config": cfg, "steps": steps,
           "window": {"trace_on": 0.9, "trace_off": 1.5},
           "peaks": peaks.peaks_for("TPU v5 lite")}
    value, notes = read("paged_attention_gqa_roofline", ctx)
    nbytes = 2 * (3800 * 2 * 16 * 128 + 2 * 44 * 16 * 128)
    assert value == pytest.approx(100.0 * (nbytes / 819e9) / 0.0005)
    assert value < 10.0   # 31.5 MB of a 252 MB grid: the share says so
    assert notes[0]["paged_attention_gqa_roofline_bound"] == "memory"
    assert notes[0]["kernel_calls"] == 384
    assert notes[0]["kernel_ms_per_call"] == pytest.approx(0.5)
    # another architecture's trace holds no such kernel: silent
    trace.devices[0].ops = [("paged_attention_mla.5", 0.0, 0.001)]
    assert read("paged_attention_gqa_roofline", ctx)[0] is None
