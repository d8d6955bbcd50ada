"""Window and full attention mixed, and one chip's share of the experts, in
the benchmark, off the chip: `tiny_exaone_moe` (a K-EXAONE-shaped toy: LLLG x
2 with a window of 8, rope on the window layers alone, experts 2 and 3 of 8
held behind the whole router) driven end to end through a manifest of its own
(tests/benchmark/tiny_exaone_moe/BENCHMARK.json: new files only) and the new
reference, counts and limits; the float8 control, a step that IGNORES THE
WINDOW and a step that reads a RECYCLED RING PAGE all come out not `correct`;
`counts/exaone_moe.py` against a count by hand; the two readers this
architecture brought against small synthetic traces."""

import contextlib
import io
import json
import os

import pytest

from benchmark import load_module, peaks
from benchmark import program_trace as pt
from benchmark import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(HERE, "tiny_exaone_moe", "BENCHMARK.json")
CELL = "tiny_exaone_moe.tiny_c4"
MS = 1e-3


def drive(*extra, seed=3_600_000_019):
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


def test_window_layers_and_a_share_are_correct_through_the_whole_command(rehearsal):
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
    counts = next(n for n in notes if "stats" in n)
    assert counts["stats"]["compiled_signatures"] == 1
    seen = next(n for n in notes if "observed" in n)["observed"]
    assert seen["requests_followed"] == 4 and seen["delivery_mismatch"] == 0
    # contexts past the ring's 24 tokens: every ring page recycled at least once
    assert seen["longest_followed"] > 24


def test_the_float8_control_comes_out_not_correct():
    rc, lines, err = drive("--control", "fp8", seed=5)
    line = json.loads(lines[-1])
    assert rc == 0 and line["correct"] is False
    number = line["compared"]["logprob_err_max"]
    assert number["value"] > 10 * number["limit"]
    assert err[-1] == "compared: correct = False"


def test_a_step_that_ignores_the_window_comes_out_not_correct(monkeypatch):
    """The window layers' calls made without their window (in the mask; the
    ring is read through its table as before): rows attend to keys the
    reference's window leaves out, and past the ring's reach to whatever a
    recycled page holds."""
    import automodel_tpu.serving.engine as engine

    inner = engine.ragged_paged_attention
    monkeypatch.setattr(
        engine, "ragged_paged_attention",
        lambda *a, window=None, **kw: inner(*a, window=None, **kw))
    rc, lines, err = drive(seed=7)
    line = json.loads(lines[-1])
    assert rc == 0 and line["correct"] is False
    number = line["compared"]["logprob_err_max"]
    assert number["value"] > 10 * number["limit"]


def test_a_step_that_reads_a_recycled_ring_page_comes_out_not_correct(monkeypatch):
    """A ring two pages too short: a chunk's last rows overwrite pages that
    its first rows' windows still reach into."""
    import automodel_tpu.serving.engine as engine

    inner = engine.ring_pages
    monkeypatch.setattr(engine, "ring_pages", lambda *a: inner(*a) - 3)
    rc, lines, err = drive(seed=9)
    line = json.loads(lines[-1])
    assert rc == 0 and line["correct"] is False
    number = line["compared"]["logprob_err_max"]
    assert number["value"] > 10 * number["limit"]


# -- counts --------------------------------------------------------------------
def published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "k_exaone_236b_a23b_serve_v5e1.json")) as f:
        return json.load(f)


def test_counts_against_a_count_by_hand():
    counts = load_module(ROOT, ["benchmark"], "counts", "exaone_moe")
    cfg = published()
    assert (counts.full_layers(cfg), counts.window_layers(cfg)) == (2, 6)
    assert (counts.calls_per_step(cfg), counts.window_calls_per_step(cfg)) == (2, 6)
    assert counts.dense_layers(cfg) == 1 and counts.held_share(cfg) == 8 / 128
    attn = 2 * (2 * 6144 * 64 * 128 + 2 * 6144 * 8 * 128)
    assert counts.attn_linear_flops_per_token(cfg) == attn == 2 * 113_246_208
    dense = 2 * 3 * 6144 * 18432
    # top-8 of 128 with 8 held: half an expert a row on this chip; the shared
    # expert; the router at its published width
    expert = 2 * 3 * 6144 * 2048
    sparse = 8 * (8 / 128) * expert + expert + 2 * 6144 * 128
    assert counts.mlp_flops_per_token(cfg, False) == dense
    assert counts.mlp_flops_per_token(cfg, True) == sparse
    per_row = 8 * attn + dense + 7 * sparse
    assert counts.layers_linear_flops_per_token(cfg) == per_row
    # 500 rows attending to 1,500,000 keys in all in a full layer and to no
    # more than 128 each in a window layer; 30 of them sampled
    scores = 2 * 2 * 64 * 128
    want = (500 * per_row + 2 * scores * 1_500_000 + 6 * scores * 500 * 128
            + 30 * 2 * 6144 * 19200)
    assert counts.serve_step_flops(cfg, 500, 1_500_000, 30) == want
    # short contexts: a window layer's rows attend to what there is
    assert counts.window_context_tokens(cfg, 10, 700) == 700
    # a full layer's call: 8 key/value heads' keys and values of 200,000
    # cached tokens once, q in and out for 500 rows of 64 heads
    call = counts.paged_attention_gqa_call(cfg, 500, 1_500_000, 200_000)
    assert call["flops"] == scores * 1_500_000
    assert call["bytes"] == 2 * (200_000 * 2 * 8 * 128 + 2 * 500 * 64 * 128)
    # a window layer's: its in-window blocks alone, 120 pages of 128 tokens
    win = counts.paged_attention_gqa_call(
        cfg, 500, 500 * 128, 120 * 128, window=128)
    assert win["flops"] == scores * 500 * 128
    assert win["bytes"] == 2 * (120 * 128 * 2 * 8 * 128 + 2 * 500 * 64 * 128)
    assert win["bytes"] < call["bytes"] / 10


# -- readers -------------------------------------------------------------------
def read(name, ctx):
    notes = []
    ctx = {"note": lambda **kw: notes.append(kw), "root": ROOT,
           "paths": ["benchmark", "tests/benchmark"], **ctx}
    mod = load_module(ROOT, ["benchmark"], "layer_metrics", name)
    return mod.read(ctx), notes


def op(name, start_ms, dur_ms, *scope):
    return pt.Op(name, start_ms * MS, (start_ms + dur_ms) * MS, tuple(scope))


def step_ops(t):
    """One 30 ms run from `t` ms: embed 1; three window layers' attention of
    4 each (projections 1.5, ring write 0.5, kernel 2); a full layer's of 6
    (projections 1.5, pool write 0.5, kernel 4); the experts 2 a layer; head 3."""
    L, P, A = "serve.layers", "serve.pass0", "serve.attn"
    out = [op("fusion.1", t, 1.0, "serve.embed")]
    at = t + 1.0
    for _ in range(3):
        W = (L, P, A, "serve.attn.window")
        out += [op("fusion.2", at, 1.5, *W),
                op("scatter.3", at + 1.5, 0.5, *W, "serve.ring_write"),
                op("paged_attention_window_gqa.4", at + 2, 2.0, *W),
                op("fusion.5", at + 4, 2.0, L, P, "serve.moe")]
        at += 6.0
    F = (L, P, A, "serve.attn.full")
    out += [op("fusion.6", at, 1.5, *F),
            op("scatter.7", at + 1.5, 0.5, *F, "serve.pool_write"),
            op("paged_attention_gqa.8", at + 2, 4.0, *F),
            op("fusion.9", at + 6, 2.0, L, P, "serve.moe"),
            op("fusion.10", at + 8, 3.0, "serve.head")]
    return out


def plan_span(step, start_ms, **stats):
    return pt.Span("step.plan", 1, start_ms * MS, (start_ms + 1) * MS,
                   {"engine_step": step, **stats})


class Dev:
    """What `trace_reduce.name_seconds` reads: (name, start, duration)."""

    def __init__(self, ops):
        self.ops = [(o.name, o.start, o.end - o.start) for o in ops]


class Trace:
    def __init__(self, ops):
        self.devices = [Dev(ops)]


def window_trace(scoped=True, blocks_arg=True):
    ops, runs, spans = [], [], []
    for n in range(5):
        t = 40.0 * n
        ops += step_ops(t)
        runs.append((t * MS, (t + 30.0) * MS))
        stats = dict(rows=500, samples=30)
        if blocks_arg:
            stats.update(window_blocks=120, full_blocks=1600)
        spans.append(plan_span(n, t - 5, **stats))
    if not scoped:   # a program without window layers
        ops = [pt.Op(o.name.replace("_window", ""), o.start, o.end, tuple(
            s for s in o.scope if s not in ("serve.attn.window",
                                            "serve.attn.full", "serve.ring_write")))
               for o in ops]
    ops = sorted(ops, key=lambda o: o.start)
    return pt.ProgramTrace(spans, ops, runs), Trace(ops)


def test_serve_window_attn_device_ms_reads_both_kinds():
    program, _ = window_trace()
    value, notes = read("serve_window_attn_device_ms",
                        {"trace": object(), "program_trace": program})
    assert value == pytest.approx(12.0)
    note = notes[0]["serve_window_attn_device_ms"]
    assert note["window"] == pytest.approx(
        {"kernel": 6.0, "write": 1.5, "rest": 4.5})
    assert note["full_ms"] == pytest.approx(6.0)
    assert note["full"] == pytest.approx({"kernel": 4.0, "write": 0.5, "rest": 1.5})
    assert note["step_parts_ms"] == pytest.approx(
        {"serve.embed": 1.0, "serve.attn.window": 12.0, "serve.attn.full": 6.0,
         "serve.moe": 8.0, "serve.head": 3.0})
    # a program without window layers (the parent commit, the other cells)
    program, _ = window_trace(scoped=False)
    value, notes = read("serve_window_attn_device_ms",
                        {"trace": object(), "program_trace": program})
    assert value is None
    assert read("serve_window_attn_device_ms", {"trace": None})[0] is None


def test_paged_attention_window_roofline_counts_the_in_window_blocks():
    cfg = published()
    program, trace = window_trace()
    ctx = {"trace": trace, "program_trace": program, "config": cfg,
           "peaks": peaks.peaks_for("TPU v5 lite")}
    value, notes = read("paged_attention_window_roofline", ctx)
    # 120 blocks of 128 tokens x (k, v) x 8 heads x 128 x 2 B, q in and out
    nbytes = 2 * (120 * 128 * 2 * 8 * 128 + 2 * 500 * 64 * 128)
    nflops = 2 * 2 * 64 * 128 * 500 * 128
    least_ms = max(nbytes / 819e9, nflops / 197e12) * 1e3
    # 15 window kernel events of 2 ms in the trace, 5 turns x 6 calls counted
    # and scaled to the 15: a call's least over a call's 2 ms
    assert value == pytest.approx(100.0 * least_ms / 2.0)
    assert 0 < value < 100
    assert notes[-1]["window_kernel_calls"] == 15
    assert notes[-1]["window_kernel_ms_per_call"] == pytest.approx(2.0)
    assert notes[-1]["full_blocks_per_turn"] == 1600
    # the full layers' reader sees its own calls alone, by the kernel's name
    from benchmark import trace_reduce

    assert trace_reduce.name_seconds(trace.devices[0], r"paged_attention_gqa")[1] == 5
    # spans without the arg (a program older than it): silent, no 0
    program, trace = window_trace(blocks_arg=False)
    ctx.update(trace=trace, program_trace=program)
    value, notes = read("paged_attention_window_roofline", ctx)
    assert value is None and notes[0]["why"] == "no window_blocks on step.plan"
    # a program without the kernel: silent too
    program, trace = window_trace(scoped=False)
    ctx.update(trace=trace, program_trace=program)
    value, notes = read("paged_attention_window_roofline", ctx)
    assert value is None and "no paged_attention_window" in notes[0]["why"]
    # another architecture's counts have no window call: silent
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro_2_6b_serve_v5e1.json")) as f:
        ctx["config"] = json.load(f)
    assert read("paged_attention_window_roofline", ctx)[0] is None
    assert read("paged_attention_window_roofline", {"trace": None})[0] is None
