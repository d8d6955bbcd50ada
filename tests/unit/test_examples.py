"""Example-YAML surface tests (reference discipline: tests/ci_tests/ —
generated per-recipe configs, every one exercised).

Fast tier, here: every example parses, its recipe class resolves, and (when
it carries a tiny hf_config) the model spec + config builder accept it.
Recipe tier, in `test_examples_smoke_*.py` (one file per group of
tests/examples_smoke.py, so that xdist's `--dist loadfile` spreads the
trainings): every HERMETIC smoke (mock dataset + /tmp run_dir) actually
trains end-to-end in-process.
"""

import pytest

from automodel_tpu.cli.app import resolve_recipe_class
from tests.examples_smoke import EXAMPLES, _load, example_id


@pytest.mark.parametrize("path", EXAMPLES, ids=example_id)
def test_example_parses_and_resolves(path):
    cfg = _load(path)
    cls = resolve_recipe_class(cfg)
    assert cls is not None
    mcfg = cfg.get("model")
    hf = mcfg.get("hf_config") if mcfg is not None else None
    if hf is not None and "architectures" in hf:
        from automodel_tpu.models.registry import get_model_spec

        hf_d = hf.to_dict() if hasattr(hf, "to_dict") else dict(hf)
        spec = get_model_spec(hf_d)
        # the config builder must accept the YAML's tiny config
        spec.config_from_hf(hf_d, remat_policy="none")
