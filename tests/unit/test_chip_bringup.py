"""What bringing the program up on the chip changed, pinned at tiny size on
the CPU: the smoke refuses to run without a TPU, no device gets an invented
peak, the compile cache has one fixed home, and llm_serve holds the weights
once."""

import gc
import os
import pathlib
import subprocess
import sys
import weakref

import jax
import jax.numpy as jnp
import pytest

from automodel_tpu.cli.app import resolve_recipe_class
from automodel_tpu.config.loader import load_yaml

REPO = pathlib.Path(__file__).parent.parent.parent


def test_chip_smoke_refuses_to_run_without_a_tpu():
    r = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout == ""  # no result line, no fallback


def test_unknown_device_has_no_peak():
    from automodel_tpu.utils.flops import (
        PEAK_TFLOPS,
        MFUCalculator,
        device_peak_tflops,
    )

    class Device:
        device_kind = "Mystery Accelerator 9"

    with pytest.raises(KeyError, match="Mystery Accelerator 9"):
        device_peak_tflops(Device())
    assert "cpu" not in PEAK_TFLOPS
    Device.device_kind = "TPU v5 lite"
    assert device_peak_tflops(Device()) == 197.0
    # on the CPU the calculator reports rates and no utilization
    perf = MFUCalculator(flops_per_token=1e9).metrics(1000, 1.0)
    assert perf["tps"] == 1000 and perf["mfu_pct"] is None


def test_compile_cache_has_one_fixed_home(monkeypatch):
    from automodel_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() is None  # tests run on the CPU
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.setattr(compile_cache, "_pinned_to_cpu", lambda: False)
    try:
        # set in the environment: JAX reads it itself, the code sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert compile_cache.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before
        # unset: one fixed directory inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_recipe_holds_the_weights_once(tmp_path):
    cfg = load_yaml(str(REPO / "examples/llm_serve/llama_serve_smoke.yaml"))
    cfg.set("run_dir", str(tmp_path))
    cfg.set("model.dtype", "bfloat16")
    recipe = resolve_recipe_class(cfg)(cfg)
    recipe.setup()
    # no optimizer, no train step, no fp32 masters beside the serve copy
    assert recipe.train_state is None
    assert not hasattr(recipe, "tx") and not hasattr(recipe, "_train_step")
    weights = jax.tree.leaves(recipe.params)
    assert {w.dtype for w in weights} == {jnp.dtype(jnp.bfloat16)}
    n_elements = sum(w.size for w in weights)
    chassis_copy = [weakref.ref(w) for w in weights]
    del weights
    recipe.run_train_validation_loop()
    # the engine took them (onto its own device here: the chassis shards
    # over all 8): the recipe keeps no reference, so the chassis' copy is
    # gone and the engine's is the only one
    assert recipe.params is None
    gc.collect()
    assert all(ref() is None for ref in chassis_copy)
    # (one tree per layer now: more leaves, the same elements)
    held = jax.tree.leaves(recipe.server.params)
    assert sum(h.size for h in held) == n_elements
    assert all(h.dtype == jnp.bfloat16 for h in held)
