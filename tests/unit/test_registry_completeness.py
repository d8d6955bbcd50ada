"""Registry-completeness guard: every model family the registry exposes
must be backed by verification — a committed golden pin
(tests/golden_values/model_pins/, exercised by test_model_pins.py) or an
HF-parity/golden test — or be EXPLICITLY allowlisted as a known gap.

The allowlist is the contract: it may only SHRINK. Adding a family to the
registry without a pin or parity test fails here (extend coverage or
consciously allowlist it in review); landing coverage for an allowlisted
family also fails until the entry is removed (the list can't silently
absorb stale entries)."""

import pathlib

import pytest

from automodel_tpu.models.registry import MODEL_ARCH_MAPPING

TESTS_DIR = pathlib.Path(__file__).parent
PIN_DIR = TESTS_DIR.parent / "golden_values" / "model_pins"

#: family -> (test file, test name) of the HF-parity/golden-logit test that
#: verifies it. Pointers are checked against the file's source so a renamed
#: or deleted test fails here instead of silently dropping coverage.
PARITY_TESTS = {
    "llama": ("test_hf_parity.py", "test_llama_logits_match_hf"),
    "qwen2": ("test_hf_parity.py", "test_qwen2_logits_match_hf"),
    "mixtral": ("test_hf_parity.py", "test_mixtral_logits_match_hf"),
    "qwen3_next": ("test_hf_parity.py", "test_qwen3_next_logits_match_hf"),
    "glm4": ("test_hf_parity.py", "test_glm4_logits_match_hf"),
    "glm4_moe": ("test_hf_parity.py", "test_glm4_moe_logits_match_hf"),
    "ernie4_5": ("test_hf_parity.py", "test_ernie4_5_logits_match_hf"),
    "ernie4_5_moe": ("test_hf_parity.py", "test_ernie4_5_moe_logits_match_hf"),
    "gemma3": ("test_hf_parity.py", "test_gemma3_logits_match_hf"),
    "hunyuan_dense": ("test_hf_parity.py", "test_hunyuan_dense_logits_match_hf"),
    "hunyuan_moe": ("test_hf_parity.py", "test_hunyuan_moe_logits_match_hf"),
    "minimax_m2": ("test_hf_parity.py", "test_minimax_m2_adapter_roundtrip"),
    "llama_bidirectional": (
        "test_hf_parity.py", "test_llama_bidirectional_loads_and_attends_both_ways"
    ),
    "mamba2": ("test_hf_parity.py", "test_mamba2_logits_match_hf"),
    # logits and per-pass gates against the plain float32 reference the
    # benchmark decides `correct` with (benchmark/reference/ouro.py)
    "ouro": ("test_ouro_model.py",
             "test_forward_matches_the_reference_logits_and_gates"),
    # against the family's published modelling code as the plain reference
    # writes it out (benchmark/reference/jamba.py), the head tied
    "jamba": ("test_jamba.py",
              "test_forward_matches_reference_with_the_tied_head"),
    # against the layer equations the plain reference writes out
    # (benchmark/reference/exaone_moe.py), every expert held
    "exaone_moe": ("test_exaone_moe.py",
                   "test_forward_matches_reference_with_all_experts_held"),
}

#: Known gaps — families with functional tests (adapter roundtrips, recipe
#: smoke, component parity) but NO pinned logits and NO torch/HF-oracle
#: parity test yet. Remove an entry when its pin or parity test lands; do
#: not add entries outside review.
ALLOWLIST_KNOWN_GAPS = {
    "deepseek_v3",    # exercised via test_moe.py registry/forward only
    "deepseek_v32",   # DSA variant of v3; component parity in test_dsa.py
    "deepseek_v4",    # test_dsa.py recipe smoke; no pinned logits
    "gemma2",         # test_decoder/test_generate functional only
    "glm4_moe_lite",  # test_model_tail roundtrip only
    "gpt_oss",        # test_moe.py (swigluoai/bias experts) only
    "hy_mt2",         # test_model_tail roundtrip only
    "kimi_k2",        # covered indirectly via kimi_vl text backbone
    "kimi_k25_vl",    # test_kimi_vl variant test; no pin
    "llava",          # test_vlm hf-roundtrip (weights), no logits oracle
    "llava_onevision",  # shares the llava module; no dedicated test
    "ministral3",     # test_model_tail forward only
    "ministral_bidirectional",  # test_model_tail bidirectional check only
    "mistral",        # adapter shared with llama; no dedicated parity
    "mistral4",       # test_model_tail QPE scaling only
    "nemotron_h",     # test_nemotron_h structural/causality tests
    "omni",           # test_omni forward/roundtrip only
    "qwen3",          # test_model_pins uses it as a backbone, no own pin
    "qwen3_moe",      # structural tests via test_moe only
}


def _registry_families() -> set:
    return {spec.name for spec in MODEL_ARCH_MAPPING.values()}


def _pinned_families() -> set:
    return {p.stem for p in PIN_DIR.glob("*.json")}


def test_every_family_verified_or_allowlisted():
    families = _registry_families()
    covered = _pinned_families() | set(PARITY_TESTS)
    missing = families - covered - ALLOWLIST_KNOWN_GAPS
    assert not missing, (
        f"registry families with no golden pin, no HF-parity test, and no "
        f"allowlist entry: {sorted(missing)} — add a pin "
        "(AM_WRITE_PINS=1 pytest tests/unit/test_model_pins.py) or a parity "
        "test, or (review-gated) extend ALLOWLIST_KNOWN_GAPS"
    )


def test_allowlist_only_shrinks():
    """An allowlisted family that GAINS coverage must leave the list, and
    entries must name real registry families (no zombie entries)."""
    families = _registry_families()
    covered = _pinned_families() | set(PARITY_TESTS)
    stale = ALLOWLIST_KNOWN_GAPS & covered
    assert not stale, (
        f"allowlisted families now have coverage: {sorted(stale)} — remove "
        "them from ALLOWLIST_KNOWN_GAPS (the list only shrinks)"
    )
    zombie = ALLOWLIST_KNOWN_GAPS - families
    assert not zombie, f"allowlist names unknown families: {sorted(zombie)}"


def test_parity_pointers_resolve():
    for fam, (fname, tname) in PARITY_TESTS.items():
        path = TESTS_DIR / fname
        assert path.exists(), f"{fam}: {fname} missing"
        assert f"def {tname}(" in path.read_text(), (
            f"{fam}: {fname} no longer defines {tname} — update PARITY_TESTS"
        )


def test_pins_on_disk_are_exercised():
    """Every committed pin file corresponds to a FAMILIES entry in
    test_model_pins.py (orphan pins = dead weight that looks like
    coverage), and vice versa every FAMILIES entry has its pin committed."""
    import ast

    src = (TESTS_DIR / "test_model_pins.py").read_text()
    for node in ast.walk(ast.parse(src)):
        if (
            isinstance(node, ast.Assign)
            and getattr(node.targets[0], "id", "") == "FAMILIES"
        ):
            exercised = {k.value for k in node.value.keys}
            break
    else:  # pragma: no cover
        pytest.fail("FAMILIES dict not found in test_model_pins.py")
    pins = _pinned_families()
    assert pins == exercised, (
        f"orphan pins: {sorted(pins - exercised)}; "
        f"missing pins: {sorted(exercised - pins)}"
    )
