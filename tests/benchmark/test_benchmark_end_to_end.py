"""The command driven end to end on the CPU through two tiny configurations
(a DeepseekV3 with MLA and experts; `tiny_dense`, a Mistral-shaped decoder
with qkv biases that entered the tests' manifest as new files and entries
alone): everything of a run except the look for a chip. It prints counts only
and no number under a device metric's name; `correct` is true for the program
as it is, and false for each control (the reference in int8 or fp8 put in the
program's place; the program's own int8 path) and for a token altered where
it is produced."""

import contextlib
import io
import json
import os

import numpy as np
import pytest

from benchmark import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny", "BENCHMARK.json")


#: each cell of the tests' manifest, and the kernel its configuration's
#: `step_kernels` names (looked for on the chip only: 0 in a rehearsal)
CELLS = {"tiny.tiny_c4": "paged_attention_mla",
         "tiny_dense.tiny_c4": "paged_attention_gqa"}


def drive(*extra, seed=3_000_000_019, trace=0, cell="tiny.tiny_c4"):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = bench_run.main(
            ["--workload", cell, "--seed", str(seed), "--seconds", "1",
             "--trace", str(trace), *extra],
            manifest_path=TINY, on_chip=False)
    lines = out.getvalue().splitlines()
    return rc, lines, err.getvalue().splitlines()


@pytest.fixture(scope="module", params=list(CELLS))
def rehearsal(request):
    return drive(cell=request.param)


def test_last_line_is_the_contracts_object(rehearsal):
    rc, lines, _ = rehearsal
    assert rc == 0
    line = json.loads(lines[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"


def test_a_run_off_the_chip_prints_counts_and_no_device_metric(rehearsal):
    _, lines, _ = rehearsal
    assert json.loads(lines[-1])["metrics"] == {}
    notes = [json.loads(ln[len("note: "):]) for ln in lines[:-1]]
    counts = next(n for n in notes if "tokens_in_window" in n)
    assert counts["tokens_in_window"] > 0 and counts["steps_in_window"] > 0
    assert counts["first_tokens_in_window"] > 0 and counts["gaps_in_window"] > 0
    before = next(n for n in notes if "step_cache_size" in n)
    assert before["step_cache_size"] == 1 and before["attention_fallbacks"] == {}
    cell = next(n for n in notes if "workload" in n)["workload"]
    kernels = next(n for n in notes if "step_kernels" in n)["step_kernels"]
    assert kernels == {CELLS[cell]: 0}
    last = next(n for n in notes if "compilations_inside_window" in n)
    assert last["compilations_inside_window"] == 0


def test_each_number_compared_is_printed_beside_its_limit(rehearsal):
    _, lines, err = rehearsal
    compared = json.loads(lines[-1])["compared"]
    assert set(compared) == {"gap_max", "logprob_err_max"}
    for name, n in compared.items():
        assert n["value"] <= n["limit"]
        assert f"compared: {name} = {n['value']} (limit {n['limit']})" in err
    assert err[-1] == "compared: correct = True"


def test_the_check_follows_the_longest_finished_request(rehearsal):
    _, lines, _ = rehearsal
    notes = [json.loads(ln[len("note: "):]) for ln in lines[:-1]]
    seen = next(n for n in notes if "observed" in n)["observed"]
    assert seen["requests_followed"] == 4 and seen["tokens_followed"] >= 8
    assert seen["finished_in_window"] > seen["requests_followed"]


@pytest.mark.parametrize("cell", list(CELLS))
@pytest.mark.parametrize("control", ["fp8", "program_int8"])
def test_the_control_comes_out_not_correct(control, cell):
    rc, lines, err = drive("--control", control, seed=5, cell=cell)
    assert rc == 0
    line = json.loads(lines[-1])
    assert line["correct"] is False
    assert line["compared"]["logprob_err_max"]["value"] > \
        line["compared"]["logprob_err_max"]["limit"]
    assert err[-1] == "compared: correct = False"


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from automodel_tpu.serving import ServingEngine

    sound = ServingEngine.run_step

    def altered(self, plan):
        tokens, logprobs = sound(self, plan)
        return (np.asarray(tokens) + 1) % self.cfg.vocab_size, logprobs

    monkeypatch.setattr(ServingEngine, "run_step", altered)
    rc, lines, _ = drive(seed=6)
    line = json.loads(lines[-1])
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["gap_max"]["value"] > 1.0


def test_no_chip_no_result(capsys):
    rc = bench_run.main(["--workload", "tiny.tiny_c4", "--seed", "1",
                         "--seconds", "1", "--trace", "0"], manifest_path=TINY)
    captured = capsys.readouterr()
    assert rc == 2 and captured.out == ""
    assert "does not fall back" in captured.err


def test_unknown_workload_is_refused(capsys):
    rc = bench_run.main(["--workload", "nothing.here", "--seed", "1",
                         "--seconds", "1", "--trace", "0"], manifest_path=TINY)
    assert rc == 2 and capsys.readouterr().out == ""
