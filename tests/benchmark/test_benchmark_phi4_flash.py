"""A decoder-hybrid-decoder in the benchmark, off the chip: `tiny_phi4_flash`
(a Phi-4-mini-flash-shaped toy: Mamba and window layers, the memory layer,
the full layer whose cache the cross layers read, a GMU and a cross layer;
a window of 8) driven end to end through a manifest of its own
(tests/benchmark/tiny_phi4_flash/BENCHMARK.json: new files only) and the new
reference, counts and limits; the float8 control and a step whose GMUs lose
the memory come out not `correct`; `counts/phi4_flash.py` against a count by
hand; the three readers this architecture brought against small synthetic
traces."""

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
MANIFEST = os.path.join(HERE, "tiny_phi4_flash", "BENCHMARK.json")
CELL = "tiny_phi4_flash.tiny_c4"
MS = 1e-3


def drive(*extra, seed=4_200_000_019):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = bench_run.main(
            ["--workload", CELL, "--seed", str(seed), "--seconds", "1",
             "--trace", "0", *extra], manifest_path=MANIFEST, on_chip=False)
    return rc, out.getvalue().splitlines(), err.getvalue().splitlines()


def notes_of(lines):
    return [json.loads(ln[len("note: "):]) for ln in lines[:-1]
            if ln.startswith("note: ")]


def test_a_decoder_hybrid_decoder_is_correct_through_the_whole_command():
    rc, lines, err = drive()
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
    # contexts past the window and the ring: every ring page recycled
    assert seen["longest_followed"] > 24


def test_the_float8_control_comes_out_not_correct():
    rc, lines, err = drive("--control", "fp8", seed=5)
    line = json.loads(lines[-1])
    assert rc == 0 and line["correct"] is False
    number = line["compared"]["logprob_err_max"]
    assert number["value"] > 10 * number["limit"]
    assert err[-1] == "compared: correct = False"


def test_a_step_whose_gmus_lose_the_memory_comes_out_not_correct(monkeypatch):
    import jax.numpy as jnp

    from automodel_tpu.serving import ServingEngine

    inner = ServingEngine._gmu
    monkeypatch.setattr(ServingEngine, "_gmu", lambda self, h, lp, m: inner(
        self, h, lp, jnp.zeros_like(m)))
    rc, lines, err = drive(seed=7)
    line = json.loads(lines[-1])
    assert rc == 0 and line["correct"] is False
    number = line["compared"]["logprob_err_max"]
    assert number["value"] > 10 * number["limit"]


# -- counts --------------------------------------------------------------------
def published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi4_mini_flash_serve_v5e1.json")) as f:
        return json.load(f)


def test_counts_against_a_count_by_hand():
    counts = load_module(ROOT, ["benchmark"], "counts", "phi4_flash")
    cfg = published()
    assert [counts.count(cfg, k) for k in ("mamba", "window", "full", "gmu", "cross")] == [9, 8, 1, 7, 7]
    assert counts.self_layers(cfg) == 18 and counts.shared_kv_calls(cfg) == (1, 7)
    H, I, C = 2560, 10240, 5120
    mlp = 2 * 3 * H * I
    attn = 2 * (2 * H * 2560 + 2 * H * 1280)
    cross = 2 * 2 * H * 2560
    mamba = 2 * (H * 2 * C + C * (160 + 32) + 160 * C + C * H) + 9 * C * 16
    gmu = 2 * 2 * H * C
    head = 2 * H * 200064
    every = 18 * mlp + 9 * attn + 9 * mamba
    sampled = 14 * mlp + 7 * cross + 7 * gmu + head
    # 150 rows attending to 300,000 keys in all, 70 of them sampled: the
    # cross layers' keys are the rows' mean context times the sampled rows
    score = 2 * 40 * (64 + 128)
    want = (150 * every + 70 * sampled + score * 300_000
            + 8 * score * 150 * 512 + 7 * score * (300_000 * 70 // 150))
    assert counts.serve_step_flops(cfg, 150, 300_000, 70) == want
    # a differential call: 20 key/value heads' keys and values (64 each) of
    # the live blocks' tokens once, q (64 a head) in and out (128 a head)
    call = counts.paged_attention_diff_call(cfg, 64, 200_000, 204_800)
    assert call["flops"] == score * 200_000
    assert call["bytes"] == 2 * (204_800 * 2 * 20 * 64 + 64 * 40 * 192)


# -- the readers ------------------------------------------------------------------
def read(name, ctx):
    notes = []
    ctx = {"note": lambda **kw: notes.append(kw), "root": ROOT,
           "paths": ["benchmark", "tests/benchmark"], **ctx}
    mod = load_module(ROOT, ["benchmark"], "layer_metrics", name)
    return mod.read(ctx), notes


def op(name, start_ms, dur_ms, *scope):
    return pt.Op(name, start_ms * MS, (start_ms + dur_ms) * MS, tuple(scope))


def step_ops(t, diff=True):
    """One 40 ms run from `t` ms: embed 1; two window layers' attention of 3
    (kernel 1.5); the full layer's of 4 (kernel 2); an MLP of 2 after each;
    then the cross-decoder: a GMU of 1, a cross layer of 3 (kernel 2), an
    MLP of 1 after each; head 3."""
    L, P, A = "serve.layers", "serve.pass0", "serve.attn"
    prefix = "paged_attention_diff" if diff else "paged_attention"
    out = [op("fusion.1", t, 1.0, "serve.embed")]
    at = t + 1.0
    for _ in range(2):
        W = (L, P, A, "serve.attn.window")
        out += [op("fusion.2", at, 1.5, *W),
                op(f"{prefix}_window_gqa.3", at + 1.5, 1.5, *W),
                op("fusion.4", at + 3, 2.0, L, P, "serve.mlp")]
        at += 5.0
    F = (L, P, A, "serve.attn.full")
    out += [op("fusion.5", at, 2.0, *F),
            op(f"{prefix}_gqa.6", at + 2, 2.0, *F),
            op("fusion.7", at + 4, 2.0, L, P, "serve.mlp")]
    at += 6.0
    if diff:
        X = (L, P, "serve.cross")
        out += [op("fusion.8", at, 1.0, *X, "serve.gmu"),
                op("fusion.9", at + 1, 1.0, *X, "serve.mlp"),
                op("fusion.10", at + 2, 1.0, *X, A, "serve.attn.cross"),
                op(f"{prefix}_gqa.11", at + 3, 2.0, *X, A, "serve.attn.cross"),
                op("fusion.12", at + 5, 1.0, *X, "serve.mlp")]
    out.append(op("fusion.13", at + 6, 3.0, "serve.head"))
    return out


class Dev:
    """What `trace_reduce.name_seconds` reads: (name, start, duration)."""

    def __init__(self, ops):
        self.ops = [(o.name, o.start, o.end - o.start) for o in ops]


class Trace:
    def __init__(self, ops):
        self.devices = [Dev(ops)]


def a_trace(args=True, diff=True):
    ops, runs, spans = [], [], []
    for n in range(5):
        t = 50.0 * n
        ops += step_ops(t, diff)
        runs.append((t * MS, (t + 40.0) * MS))
        stats = dict(rows=150, samples=64, attn_live_blocks=900,
                     full_blocks=900, window_blocks=200)
        if args:
            stats.update(cross_rows=64, cross_blocks=1900)
        spans.append(pt.Span("step.plan", 1, (t - 5) * MS, (t - 4) * MS,
                             {"engine_step": n, **stats}))
    ops = sorted(ops, key=lambda o: o.start)
    return pt.ProgramTrace(spans, ops, runs), Trace(ops)


def context(program, trace, config=None):
    return {"trace": trace, "program_trace": program,
            "config": config or published(), "peaks": peaks.peaks_for("TPU v5 lite")}


def test_serve_cross_decoder_device_ms_reads_the_cross_scope():
    value, notes = read("serve_cross_decoder_device_ms", context(*a_trace()))
    assert value == pytest.approx(6.0)
    seen = notes[-1]["serve_cross_decoder_device_ms"]
    assert seen == pytest.approx({"gmu": 1.0, "attn": 3.0, "attn_kernel": 2.0,
                                  "mlp": 2.0})
    assert notes[-1]["cross_rows_p50"] == 64 and notes[-1]["rows_p50"] == 150
    # a program without a cross-decoder: silent
    assert read("serve_cross_decoder_device_ms",
                context(*a_trace(diff=False)))[0] is None


def test_paged_attention_shared_kv_roofline_counts_each_calls_reads():
    value, notes = read("paged_attention_shared_kv_roofline",
                        context(*a_trace()))
    page = 128

    def least(rows, blocks):
        nbytes = 2 * (blocks * page * 2 * 20 * 64 + rows * 40 * 192)
        nflops = 2 * 40 * 192 * blocks * page
        return max(nbytes / 819e9, nflops / 197e12)

    # a turn: the full layer's call over 150 rows and 900 blocks, 7 cross
    # calls over 64 rows and 1,900 blocks; 10 events of 2 ms in the trace
    # (2 a run), scaled from 5 turns x 8 calls to the 10
    per_turn = least(150, 900) + 7 * least(64, 1900)
    want = 100.0 * 5 * per_turn * 10 / (5 * 8) / (10 * 2.0 * MS)
    assert value == pytest.approx(want)
    assert 0 < value < 100
    assert notes[-1]["shared_kv_kernel_calls"] == 10
    assert notes[-1]["paged_attention_shared_kv_roofline_bound"] == "memory"
    # spans without the args: silent, no 0
    value, notes = read("paged_attention_shared_kv_roofline",
                        context(*a_trace(args=False)))
    assert value is None and notes[0]["why"] == "no cross_blocks on step.plan"
    # another architecture's counts: silent
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "jamba2_3b_serve_v5e1.json")) as f:
        assert read("paged_attention_shared_kv_roofline",
                    context(*a_trace(), config=json.load(f)))[0] is None
    assert read("paged_attention_shared_kv_roofline", {"trace": None})[0] is None


def test_paged_attention_diff_window_roofline_counts_the_window_blocks():
    value, notes = read("paged_attention_diff_window_roofline",
                        context(*a_trace()))
    nbytes = 2 * (200 * 128 * 2 * 20 * 64 + 150 * 40 * 192)
    nflops = 2 * 40 * 192 * 150 * 512
    least_ms = max(nbytes / 819e9, nflops / 197e12) * 1e3
    # 10 window kernel events of 1.5 ms; 5 turns x 8 calls scaled to the 10
    assert value == pytest.approx(100.0 * least_ms / 1.5)
    assert 0 < value < 100
    assert notes[-1]["diff_window_kernel_calls"] == 10
    # the parent's window calls carry another name: silent
    value, notes = read("paged_attention_diff_window_roofline",
                        context(*a_trace(diff=False)))
    assert value is None and "no paged_attention_diff_window" in notes[0]["why"]
