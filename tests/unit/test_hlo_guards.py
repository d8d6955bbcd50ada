"""HLO baseline guards: CPU-verifiable perf regression fences.

Tier-1 runs with no accelerator in the loop, so these guards pin the
COMPILED structure of the headline parallel programs:
`jit(...).lower().compile()` on a virtual CPU mesh emits the same logical
collectives GSPMD/shard_map would emit for TPU, and a change that, say,
re-gathers expert weights per microbatch or breaks the manual-A2A EP
dispatch shows up as baseline drift here — failing tier-1 with no
accelerator in the loop.

This file used to hand-count `compiled.as_text()` ops with five copies of
a regex; it is now a thin shell over `automodel_tpu.analysis`: one builder
per jitted entry point (analysis/entrypoints.py), one structured report
per compiled program (analysis/hlo.py), and one checked-in JSON baseline
per entry (analysis/baselines/*.json). The ratchet is two-sided: a
regression that GROWS a collective fails, and an optimization that LOWERS
a count also fails until the baseline is consciously re-pinned with

    python -m automodel_tpu.analysis --update-baselines

which replaces hand-editing counts in five tests. The same comparisons run
in CI via `python -m automodel_tpu.analysis`; keeping them as individual
tier-1 tests too gives per-entry failure granularity and rides the
existing pytest budget."""

import os

import pytest

import automodel_tpu.analysis
from automodel_tpu.analysis import compare_report, load_baseline
from automodel_tpu.analysis.entrypoints import (
    ENTRY_POINTS,
    STRUCTURAL_INVARIANTS,
    build_report,
    check_invariants,
)

# the SAME directory `python -m automodel_tpu.analysis` gates
BASELINES = os.path.join(
    os.path.dirname(os.path.abspath(automodel_tpu.analysis.__file__)),
    "baselines",
)


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_hlo_baseline(entry):
    report = build_report(entry)
    baseline = load_baseline(BASELINES, entry)
    assert baseline is not None, (
        f"no baseline for {entry!r} in {BASELINES} — run "
        "`python -m automodel_tpu.analysis --update-baselines`"
    )
    drifts = compare_report(report, baseline)
    assert not drifts, (
        "compiled program drifted from its baseline; if intentional, "
        "re-pin with `python -m automodel_tpu.analysis --update-baselines` "
        "and justify in the PR:\n" + "\n".join(drifts)
    )
    # structural invariants (floors / zero-ceilings / op floors) live next
    # to the entry-point registry so the CLI gate enforces the SAME tables
    # — and --update-baselines refuses to pin a program that violates them
    assert check_invariants(report) == []
    assert entry in STRUCTURAL_INVARIANTS  # registry/invariants stay in sync


@pytest.mark.parametrize("entry", [
    "paged_serve_step", "spec_serve_step", "prefill_step", "kv_transfer",
])
def test_serve_step_donation_pinned(entry):
    """The serve step's pool donation is part of the compiled contract:
    losing it silently doubles pool memory — in the plain, speculative
    draft-then-verify, and prefill-class step programs alike, and in the
    handoff's fused page-copy program (whose destination pool is donated
    so a transfer never double-buffers). The aliasing table in
    the baseline must stay non-empty (belt to the baseline's suspenders —
    this asserts the INVARIANT, not a count that drifts)."""
    baseline = load_baseline(BASELINES, entry)
    assert baseline is not None
    assert baseline.donation, (
        f"{entry} baseline has an empty input_output_alias table — "
        "the pool donation was lost"
    )
