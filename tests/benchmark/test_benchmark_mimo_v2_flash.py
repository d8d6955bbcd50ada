"""Window layers with key/value heads and sinks of their own, keys wider than
values, and one chip's share of the experts, in the benchmark, off the chip:
`tiny_mimo_v2_flash` (a MiMo-V2-Flash-shaped toy: full, window x 2, full,
window x 2 with a window of 8; experts 2 and 3 of 8 held behind the whole
router) driven end to end through a manifest of its own
(tests/benchmark/tiny_mimo_v2_flash/BENCHMARK.json: new files only) and the
new reference, counts and limits; the float8 control, a step that DROPS THE
SINKS and a step that DROPS THE VALUE SCALE all come out not `correct`;
`counts/mimo_v2_flash.py` against a count by hand; the reader this
architecture brought against small synthetic traces."""

import contextlib
import dataclasses
import io
import json
import os

import pytest

from benchmark import load_module, peaks
from benchmark import program_trace as pt
from benchmark import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(HERE, "tiny_mimo_v2_flash", "BENCHMARK.json")
CELL = "tiny_mimo_v2_flash.tiny_c4"
MS = 1e-3


def drive(*extra, seed=4_000_000_019):
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


def test_unlike_window_layers_and_a_share_are_correct_through_the_whole_command(
        rehearsal):
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


def test_a_step_that_drops_the_sinks_comes_out_not_correct(monkeypatch):
    """The window layers' calls made without their sink logits: every row's
    softmax loses the term the reference keeps in its denominator."""
    import automodel_tpu.serving.engine as engine

    inner = engine.ragged_paged_attention
    monkeypatch.setattr(
        engine, "ragged_paged_attention",
        lambda *a, sinks=None, **kw: inner(*a, sinks=None, **kw))
    rc, lines, err = drive(seed=7)
    line = json.loads(lines[-1])
    assert rc == 0 and line["correct"] is False
    number = line["compared"]["logprob_err_max"]
    assert number["value"] > 10 * number["limit"]


def test_a_step_that_drops_the_value_scale_comes_out_not_correct(monkeypatch):
    """Every layer's values as projected, not scaled by 0.707: what lands in
    the pool and the rings, and what attention sums, is another function."""
    import automodel_tpu.serving.engine as engine

    inner = engine.kind_config
    monkeypatch.setattr(
        engine, "kind_config", lambda cfg, kind: dataclasses.replace(
            inner(cfg, kind), attention_value_scale=1.0))
    rc, lines, err = drive(seed=9)
    line = json.loads(lines[-1])
    assert rc == 0 and line["correct"] is False
    number = line["compared"]["logprob_err_max"]
    assert number["value"] > 10 * number["limit"]


# -- counts --------------------------------------------------------------------
def published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo_v2_flash_serve_v5e1.json")) as f:
        return json.load(f)


def test_counts_against_a_count_by_hand():
    counts = load_module(ROOT, ["benchmark"], "counts", "mimo_v2_flash")
    cfg = published()
    assert (counts.full_layers(cfg), counts.window_layers(cfg)) == (2, 9)
    assert (counts.calls_per_step(cfg), counts.window_calls_per_step(cfg),
            counts.sink_calls_per_step(cfg)) == (2, 9, 9)
    assert counts.dense_layers(cfg) == 1 and counts.held_share(cfg) == 16 / 256
    H = 4096
    full = 2 * (H * 64 * 192 + H * 4 * 192 + H * 4 * 128 + 64 * 128 * H)
    window = 2 * (H * 64 * 192 + H * 8 * 192 + H * 8 * 128 + 64 * 128 * H)
    # the parameters of a full layer's attention and a window layer's less
    # its sinks, twice over
    assert (full, window) == (2 * 89_128_960, 2 * (94_371_904 - 64))
    assert counts.attn_linear_flops_per_token(cfg, False) == full
    assert counts.attn_linear_flops_per_token(cfg, True) == window
    dense = 2 * 3 * H * 16384
    # top-8 of 256 with 16 held: half an expert a row on this chip; the
    # router at its published width; no shared expert
    expert = 2 * 3 * H * 2048
    sparse = 8 * (16 / 256) * expert + 2 * H * 256
    assert counts.mlp_flops_per_token(cfg, False) == dense
    assert counts.mlp_flops_per_token(cfg, True) == sparse
    per_row = 2 * full + 9 * window + dense + 10 * sparse
    assert counts.layers_linear_flops_per_token(cfg) == per_row
    # 150 rows attending to 300,000 keys in all in a full layer and to no
    # more than 128 each in a window layer; 70 of them sampled
    scores = 2 * 64 * (192 + 128)
    want = (150 * per_row + 2 * scores * 300_000 + 9 * scores * 150 * 128
            + 70 * 2 * H * 19072)
    assert counts.serve_step_flops(cfg, 150, 300_000, 70) == want
    assert counts.window_context_tokens(cfg, 10, 700) == 700
    # a full layer's call: 4 key/value heads' keys (192) and values (128)
    # of 120,000 cached tokens once, q in and out for 150 rows of 64 heads
    call = counts.paged_attention_gqa_call(cfg, 150, 300_000, 120_000)
    assert call["flops"] == scores * 300_000
    assert call["bytes"] == 2 * (120_000 * 4 * 320 + 150 * 64 * 320)
    # a window layer's: 8 key/value heads, its in-window blocks alone
    win = counts.paged_attention_gqa_call(cfg, 150, 150 * 128, 200 * 128,
                                          window=128)
    assert win["flops"] == scores * 150 * 128
    assert win["bytes"] == 2 * (200 * 128 * 8 * 320 + 150 * 64 * 320)


# -- the reader ------------------------------------------------------------------
def read(name, ctx):
    notes = []
    ctx = {"note": lambda **kw: notes.append(kw), "root": ROOT,
           "paths": ["benchmark", "tests/benchmark"], **ctx}
    mod = load_module(ROOT, ["benchmark"], "layer_metrics", name)
    return mod.read(ctx), notes


def op(name, start_ms, dur_ms, *scope):
    return pt.Op(name, start_ms * MS, (start_ms + dur_ms) * MS, tuple(scope))


def step_ops(t, sink_name="paged_attention_sink_gqa"):
    """One 30 ms run from `t` ms: embed 1; three window layers' attention of
    4 each (kernel 2); a full layer's of 6 (kernel 4); the experts 2 a
    layer; head 3."""
    L, P, A = "serve.layers", "serve.pass0", "serve.attn"
    out = [op("fusion.1", t, 1.0, "serve.embed")]
    at = t + 1.0
    for _ in range(3):
        W = (L, P, A, "serve.attn.window")
        out += [op("fusion.2", at, 1.5, *W),
                op("scatter.3", at + 1.5, 0.5, *W, "serve.ring_write"),
                op(f"{sink_name}.4", at + 2, 2.0, *W),
                op("fusion.5", at + 4, 2.0, L, P, "serve.moe")]
        at += 6.0
    F = (L, P, A, "serve.attn.full")
    out += [op("fusion.6", at, 1.5, *F),
            op("scatter.7", at + 1.5, 0.5, *F, "serve.pool_write"),
            op("paged_attention_gqa.8", at + 2, 4.0, *F),
            op("fusion.9", at + 6, 2.0, L, P, "serve.moe"),
            op("fusion.10", at + 8, 3.0, "serve.head")]
    return out


class Dev:
    """What `trace_reduce.name_seconds` reads: (name, start, duration)."""

    def __init__(self, ops):
        self.ops = [(o.name, o.start, o.end - o.start) for o in ops]


class Trace:
    def __init__(self, ops):
        self.devices = [Dev(ops)]


def sink_trace(blocks_arg=True, sink_name="paged_attention_sink_gqa"):
    ops, runs, spans = [], [], []
    for n in range(5):
        t = 40.0 * n
        ops += step_ops(t, sink_name)
        runs.append((t * MS, (t + 30.0) * MS))
        stats = dict(rows=150, samples=70)
        if blocks_arg:
            stats.update(window_blocks=200, full_blocks=900)
        spans.append(pt.Span("step.plan", 1, (t - 5) * MS, (t - 4) * MS,
                             {"engine_step": n, **stats}))
    ops = sorted(ops, key=lambda o: o.start)
    return pt.ProgramTrace(spans, ops, runs), Trace(ops)


def test_paged_attention_sink_roofline_counts_the_window_layers_blocks():
    cfg = published()
    program, trace = sink_trace()
    ctx = {"trace": trace, "program_trace": program, "config": cfg,
           "peaks": peaks.peaks_for("TPU v5 lite")}
    value, notes = read("paged_attention_sink_roofline", ctx)
    # 200 blocks of 128 tokens x 8 heads x (192 + 128) x 2 B, q in and out
    nbytes = 2 * (200 * 128 * 8 * 320 + 150 * 64 * 320)
    nflops = 2 * 64 * 320 * 150 * 128
    least_ms = max(nbytes / 819e9, nflops / 197e12) * 1e3
    # 15 sink kernel events of 2 ms in the trace, 5 turns x 9 calls counted
    # and scaled to the 15: a call's least over a call's 2 ms
    assert value == pytest.approx(100.0 * least_ms / 2.0)
    assert 0 < value < 100
    assert notes[-1]["sink_kernel_calls"] == 15
    assert notes[-1]["sink_kernel_ms_per_call"] == pytest.approx(2.0)
    assert notes[-1]["paged_attention_sink_roofline_bound"] == "memory"
    # each reader sees its own calls alone, by the kernel's name
    from benchmark import trace_reduce

    dev = trace.devices[0]
    assert trace_reduce.name_seconds(dev, r"paged_attention_gqa")[1] == 5
    assert trace_reduce.name_seconds(dev, r"paged_attention_window")[1] == 0
    # spans without the arg: silent, no 0
    program, trace = sink_trace(blocks_arg=False)
    ctx.update(trace=trace, program_trace=program)
    value, notes = read("paged_attention_sink_roofline", ctx)
    assert value is None and notes[0]["why"] == "no window_blocks on step.plan"
    # a program whose window calls carry no sinks (the parent's name): silent
    program, trace = sink_trace(sink_name="paged_attention_window_gqa")
    ctx.update(trace=trace, program_trace=program)
    value, notes = read("paged_attention_sink_roofline", ctx)
    assert value is None and "no paged_attention_sink" in notes[0]["why"]
    # another architecture's counts have no sink call: silent
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "k_exaone_236b_a23b_serve_v5e1.json")) as f:
        ctx["config"] = json.load(f)
    assert read("paged_attention_sink_roofline", ctx)[0] is None
    assert read("paged_attention_sink_roofline", {"trace": None})[0] is None
