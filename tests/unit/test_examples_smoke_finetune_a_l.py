"""Hermetic example smokes, group `finetune_a_l`: `examples/llm_finetune`, names before m
(tests/examples_smoke.py; the parse tier is test_examples.py)."""

import pytest

from tests.examples_smoke import example_id, run_smoke, smokes


@pytest.mark.recipe
@pytest.mark.parametrize("path", smokes("finetune_a_l"), ids=example_id)
def test_example_smoke_trains(path, tmp_path):
    run_smoke(path, tmp_path)
