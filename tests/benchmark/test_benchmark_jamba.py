"""State-space layers in the benchmark, off the chip: `tiny_jamba` (a
Jamba-shaped toy, its head untied like the cell's: 6 layers, attention at 1 and 4 over one
key/value head, Mamba-1 mixers elsewhere) driven end to end through a
manifest of its own (tests/benchmark/tiny_jamba/BENCHMARK.json: new files
only) and the new reference, counts and limits; the float8 control and a step
whose carried state is DROPPED both come out not `correct`; `counts/jamba.py`
against a count by hand; the two readers this architecture brought against
small synthetic traces."""

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
MANIFEST = os.path.join(HERE, "tiny_jamba", "BENCHMARK.json")
CELL = "tiny_jamba.tiny_c4"
MS = 1e-3


def drive(*extra, seed=3_400_000_019):
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


def test_state_space_layers_are_correct_through_the_whole_command(rehearsal):
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
    # prompts of up to 40 tokens through chunks of 12: the state crosses steps
    assert seen["longest_followed"] > 16


def test_the_float8_control_comes_out_not_correct():
    rc, lines, err = drive("--control", "fp8", seed=5)
    line = json.loads(lines[-1])
    assert rc == 0 and line["correct"] is False
    number = line["compared"]["logprob_err_max"]
    assert number["value"] > 10 * number["limit"]
    assert err[-1] == "compared: correct = False"


def test_a_dropped_carried_state_comes_out_not_correct(monkeypatch):
    """Every run made to start from zeros (the recurrent state a slot
    carried in is never read): what `dt_bias`'s draw guards. With a bias
    drawn near 0 the state would forget in two tokens and this would pass."""
    import automodel_tpu.serving.engine as engine

    inner = engine.step_runs

    def forgetful(slot, pos, trash):
        runs = inner(slot, pos, trash)
        return {**runs, "first_pos": runs["first_pos"] * 0}

    monkeypatch.setattr(engine, "step_runs", forgetful)
    rc, lines, err = drive(seed=7)
    line = json.loads(lines[-1])
    assert rc == 0 and line["correct"] is False
    number = line["compared"]["logprob_err_max"]
    assert number["value"] > 10 * number["limit"]


# -- counts --------------------------------------------------------------------
def published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "jamba2_3b_serve_v5e1.json")) as f:
        return json.load(f)


def test_counts_against_a_count_by_hand():
    counts = load_module(ROOT, ["benchmark"], "counts", "jamba")
    cfg = published()
    assert (counts.attn_layers(cfg), counts.ssm_layers(cfg)) == (2, 26)
    assert counts.calls_per_step(cfg) == 2
    mlp = 2 * 3 * 2560 * 8192
    attn = 2 * (2 * 2560 * 20 * 128 + 2 * 2560 * 1 * 128)
    mixer = 2 * (2560 * 10240 + 5120 * (160 + 32) + 160 * 5120 + 5120 * 2560)
    scan = 9 * 5120 * 16
    assert counts.mlp_flops_per_token(cfg) == mlp
    assert counts.attn_linear_flops_per_token(cfg) == attn
    assert counts.ssm_linear_flops_per_token(cfg) == mixer
    assert counts.ssm_scan_flops_per_token(cfg) == scan == 737_280
    # 200 rows attending to 60,000 keys in all, 128 of them sampled
    scores = 2 * 2 * 20 * 128 * 60000
    want = (200 * (28 * mlp + 2 * attn + 26 * (mixer + scan)) + 2 * scores
            + 128 * 2 * 2560 * 65536)
    assert counts.serve_step_flops(cfg, 200, 60000, 128) == want
    # one paged call: ONE key/value head's keys and values of 40,000 cached
    # tokens once, q in and out for 200 rows of 20 heads
    call = counts.paged_attention_gqa_call(cfg, 200, 60000, 40000)
    assert call["flops"] == scores
    assert call["bytes"] == 2 * (40000 * 2 * 1 * 128 + 2 * 200 * 20 * 128)
    # a slot's state in one layer, and over the 26: 9.32 MB
    assert counts.state_bytes_per_slot_layer(cfg) == 358_400
    assert 26 * 358_400 == 9_318_400
    # one layer's scan: 130 runs read and write their slot's state once; each
    # of 200 rows reads u, z (bf16), delta (f32), B, C (f32) and writes y (f32)
    scan_call = counts.ssm_scan_call(cfg, 200, 130)
    assert scan_call["flops"] == 200 * scan
    assert scan_call["bytes"] == (130 * 2 * 358_400
                                  + 200 * (2 * 5120 * 2 + 2 * 5120 * 4 + 2 * 16 * 4))


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
    """One 60 ms run from `t` ms: embed 1; an attention layer 3; two mixers
    of 20 each (proj 4, conv 2, scan 10 of which the slot's read and write 3,
    proj 4 again); their MLPs 4 each; head 4."""
    L, P, M = "serve.layers", "serve.pass0", "serve.ssm"
    out = [op("fusion.1", t, 1.0, "serve.embed"),
           op("fusion.2", t + 1, 3.0, L, P, "serve.attn")]
    at = t + 4.0
    for _ in range(2):
        out += [
            op("fusion.3", at, 4.0, L, P, M, "serve.ssm.proj"),
            op("fusion.4", at + 4, 2.0, L, P, M, "serve.ssm.conv"),
            op("fusion.5", at + 6, 7.0, L, P, M, "serve.ssm.scan"),
            op("dynamic-slice.6", at + 13, 3.0, L, P, M, "serve.ssm.scan",
               "serve.ssm.state"),
            op("fusion.7", at + 16, 4.0, L, P, M, "serve.ssm.proj"),
            op("fusion.8", at + 20, 4.0, L, P, "serve.mlp")]
        at += 24.0
    return out + [op("fusion.9", at, 4.0, "serve.head")]


def plan_span(step, start_ms, **stats):
    return pt.Span("step.plan", 1, start_ms * MS, (start_ms + 1) * MS,
                   {"engine_step": step, **stats})


def ssm_trace(scoped=True, runs_arg=True):
    ops, runs, spans = [], [], []
    for n in range(5):
        t = 70.0 * n
        ops += step_ops(t)
        runs.append((t * MS, (t + 56.0) * MS))
        stats = dict(rows=200, samples=128)
        if runs_arg:
            stats["state_runs"] = 130
        spans.append(plan_span(n, t - 5, **stats))
    if not scoped:   # a program without state-space layers
        ops = [pt.Op(o.name, o.start, o.end,
                     tuple(s for s in o.scope if not s.startswith("serve.ssm")))
               for o in ops]
    return pt.ProgramTrace(spans, sorted(ops, key=lambda o: o.start), runs)


def test_serve_ssm_device_ms_reads_the_four_parts():
    value, notes = read("serve_ssm_device_ms",
                        {"trace": object(), "program_trace": ssm_trace()})
    assert value == pytest.approx(40.0)
    assert notes[0]["serve_ssm_device_ms"] == pytest.approx(
        {"proj": 16.0, "conv": 4.0, "scan": 14.0, "state": 6.0})
    assert notes[0]["scoped_share"] == pytest.approx(1.0)
    assert notes[0]["unscoped_ms"] == {}
    # the step beside it, by outermost sublayer: embed 1, attention 3, two
    # mixers of 20, their MLPs 4 each, head 4: 56 in all
    assert notes[0]["step_ms"] == pytest.approx(56.0)
    assert notes[0]["step_parts_ms"] == pytest.approx(
        {"embed": 1.0, "attn": 3.0, "ssm": 40.0, "mlp": 8.0, "head": 4.0})
    # a program without such layers (the parent commit, the other cells)
    value, notes = read("serve_ssm_device_ms", {
        "trace": object(), "program_trace": ssm_trace(scoped=False)})
    assert value is None and notes[0]["why"] == "no serve.ssm scope"
    assert read("serve_ssm_device_ms", {"trace": None})[0] is None


def test_serve_ssm_scan_roofline_counts_the_work_under_the_scopes():
    cfg = published()
    ctx = {"trace": object(), "program_trace": ssm_trace(), "config": cfg,
           "peaks": peaks.peaks_for("TPU v5 lite")}
    value, notes = read("serve_ssm_scan_roofline", ctx)
    nbytes = 130 * 2 * 358_400 + 200 * (2 * 5120 * 2 + 2 * 5120 * 4 + 2 * 16 * 4)
    least_ms = 26 * nbytes / 819e9 * 1e3
    # scan + state of the two toy mixers of a run: 20 ms
    assert value == pytest.approx(100.0 * least_ms / 20.0)
    assert 0 < value < 100
    assert notes[-1]["serve_ssm_scan_roofline_bound"] == "memory"
    assert notes[-1]["state_runs_p50"] == 130
    assert notes[-1]["scan_ms_per_step"] == pytest.approx(20.0)
    # spans without the arg (a program older than it): silent, no 0
    ctx["program_trace"] = ssm_trace(runs_arg=False)
    value, notes = read("serve_ssm_scan_roofline", ctx)
    assert value is None and notes[0]["why"] == "no state_runs on step.plan"
    # another architecture's counts have no such call: silent
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro_2_6b_serve_v5e1.json")) as f:
        ctx["config"] = json.load(f)
    ctx["program_trace"] = ssm_trace()
    assert read("serve_ssm_scan_roofline", ctx)[0] is None
    assert read("serve_ssm_scan_roofline", {"trace": None})[0] is None
