"""Compiled-program (HLO) analyzer: structured reports + baseline ratchet.

Generalizes the hand-rolled ``compiled.as_text()`` counting that used to
live in five copies inside ``tests/unit/test_hlo_guards.py`` into one
library. ``analyze_compiled`` parses optimized HLO into an
:class:`HLOReport` —

- collectives by kind (``all-gather`` … ``ragged-all-to-all``), each with a
  breakdown by replica-group shape (``"4x2"`` = 4 groups of 2), annotated
  with the mesh axes that could produce that group size when the caller
  passes ``mesh_axes``;
- data-movement op counts: ``gather`` / ``dynamic-slice`` /
  ``dynamic-update-slice`` (the paged-KV access structure);
- bf16→f32 ``convert`` upcasts (a precision regression silently doubles
  matmul input bytes);
- ``custom-call`` targets and host callbacks (a host callback inside a hot
  step is a device→host sync per step);
- the input→output donation/aliasing table from the module header;
- ``memory_analysis()`` peak bytes (argument/output/temp/alias).

Counts reflect compiled program STRUCTURE: scan bodies compile once, so a
count is independent of trip counts and batch traffic.

Baselines are JSON snapshots of the report per jitted entry point
(:mod:`automodel_tpu.analysis.entrypoints`), checked in under
``analysis/baselines/``. ``compare_report`` is the ratchet: any drift in
either direction — a regression that grows a collective OR an optimization
that removes one — fails until the baseline is consciously regenerated
with ``python -m automodel_tpu.analysis --update-baselines``. Memory bytes
compare within a relative tolerance (layout noise); every count compares
exactly.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re

COLLECTIVE_KINDS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
    "ragged-all-to-all",
)
DATA_OPS = ("gather", "dynamic-slice", "dynamic-update-slice")

# "= f32[8]{1,0} all-gather(" — the char class has no hyphen, so "gather"
# cannot also match inside "all-gather" (idiom proven in the old guards);
# parens admit tuple-typed ops ("= (f32[..], f32[..]) all-to-all("), and
# the missing "%" keeps operand references from ever starting a match
_OP_RE = r"= (?:[\w\[\],<>:{{}}() ]+ )?{op}(?:-start)?\("
# two forms: explicit {{0,1},{2,3}} and iota-v2 [n,m]<=[dims](T(perm))? —
# the source dims may be multi-dimensional with a transpose suffix
# ([2,4]<=[4,2]T(1,0)), which changes WHICH devices group together but not
# the n-groups-of-m shape the signature reports
_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[\d,{} ]*\}\}|\[[\d,]*\]<=\[[\d,]*\](?:T\([\d,]*\))?|\{\})"
)
# collective-permute carries source_target_pairs instead of replica_groups
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_CUSTOM_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d, ]*)\}:\s*\((\d+),\s*\{([\d, ]*)\},\s*([\w-]+)\)"
)
# `%y = f32[..] convert(%x)`: jaxlib 0.9.0 prints operands by name only, so
# the operand's dtype comes from its own definition (names are unique in a
# module); older text carried it inline (`convert(bf16[..] %x)`)
_DEF_RE = re.compile(r"%([\w.\-]+) = (\w+)\[")
_F32_CONVERT_RE = re.compile(
    r"= f32\[[^\]]*\]\S* convert\((?:(\w+)\[[^\]]*\]\S* )?%([\w.\-]+)\)"
)


def _count_bf16_upcasts(txt: str) -> int:
    defs = dict(_DEF_RE.findall(txt))
    return sum(
        (inline or defs.get(name)) == "bf16"
        for inline, name in _F32_CONVERT_RE.findall(txt)
    )


@dataclasses.dataclass
class HLOReport:
    """Structured summary of one compiled program (see module docstring)."""

    entry: str
    collectives: dict          # kind -> count (0s included: absence is pinned)
    collective_groups: dict    # kind -> {group signature -> count}
    ops: dict                  # gather/dynamic-slice/DUS -> count
    convert_upcasts: int       # bf16 -> f32 converts
    custom_calls: dict         # custom_call_target -> count
    host_callbacks: int        # callback-flavored custom calls
    donation: list             # sorted "output{idx} <- param N{idx} (kind)"
    memory: dict               # memory_analysis() bytes (may be {})

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "HLOReport":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)})


def _count(txt: str, op: str) -> int:
    return len(re.findall(_OP_RE.format(op=re.escape(op)), txt))


def _brace_slice(txt: str, marker: str) -> str:
    """The brace-balanced `{...}` slice following `marker` ('' if absent)."""
    start = txt.find(marker)
    if start < 0:
        return ""
    i = txt.index("{", start + len(marker))
    depth = 0
    for j in range(i, len(txt)):
        depth += (txt[j] == "{") - (txt[j] == "}")
        if depth == 0:
            return txt[i: j + 1]
    return ""


def _group_signature(raw: str, mesh_axes: dict | None) -> str:
    """Normalize a replica_groups attribute to "<n>x<size>" (n groups of
    size), annotated with candidate mesh axes of that size."""
    if raw in ("{}", "{{}}"):
        return "flat"
    if raw.startswith("{{"):
        groups = [g for g in raw[2:-2].split("},{") if g]
        n, size = len(groups), len(groups[0].split(",")) if groups else 0
    else:  # iota v2: [n,size]<=[dims...](T(perm))?
        dims = raw[1: raw.index("]")].split(",")
        n, size = int(dims[0]), int(dims[1]) if len(dims) > 1 else 1
    sig = f"{n}x{size}"
    if mesh_axes:
        axes = sorted(a for a, s in mesh_axes.items() if s == size and s > 1)
        if axes:
            sig += f" (axis~{','.join(axes)})"
    return sig


def analyze_compiled(compiled, entry: str = "", mesh_axes: dict | None = None) -> HLOReport:
    """Parse one jitted-and-compiled program into an :class:`HLOReport`.

    `compiled` is the result of ``jax.jit(f).lower(...).compile()``.
    `mesh_axes` (axis name -> size) annotates replica-group signatures with
    the axes that could have produced them (sizes are ambiguous when two
    axes share a size — both are listed).
    """
    txt = compiled.as_text()
    collectives = {k: _count(txt, k) for k in COLLECTIVE_KINDS}

    # one instruction per line; the op regex's char class excludes hyphens,
    # so "all-to-all" cannot also match inside "ragged-all-to-all" (same
    # argument as gather vs all-gather)
    groups: dict = {k: {} for k in COLLECTIVE_KINDS if collectives[k]}
    for line in txt.splitlines():
        for kind in COLLECTIVE_KINDS:
            if collectives[kind] and re.search(
                _OP_RE.format(op=re.escape(kind)), line
            ):
                m = _GROUPS_RE.search(line)
                if m:
                    sig = _group_signature(m.group(1), mesh_axes)
                else:
                    p = _PAIRS_RE.search(line)
                    sig = (
                        f"{p.group(1).count('{')} pairs" if p else "unspecified"
                    )
                groups[kind][sig] = groups[kind].get(sig, 0) + 1
                break

    ops = {k: _count(txt, k) for k in DATA_OPS}

    custom_calls: dict = {}
    for line in txt.splitlines():
        if re.search(_OP_RE.format(op="custom-call"), line):
            m = _CUSTOM_TARGET_RE.search(line)
            target = m.group(1) if m else "<unknown>"
            custom_calls[target] = custom_calls.get(target, 0) + 1
    host_callbacks = sum(
        n for t, n in custom_calls.items() if "callback" in t.lower()
    )

    donation = []
    table = _brace_slice(txt, "input_output_alias=")
    if table:
        for out_idx, param, param_idx, kind in _ALIAS_ENTRY_RE.findall(table):
            donation.append(
                f"output{{{out_idx.strip()}}} <- param {param}"
                f"{{{param_idx.strip()}}} ({kind})"
            )
    donation.sort()

    memory: dict = {}
    try:
        ma = compiled.memory_analysis()
        memory = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes": int(
                ma.argument_size_in_bytes
                + ma.output_size_in_bytes
                + ma.temp_size_in_bytes
                - ma.alias_size_in_bytes
            ),
        }
    except (AttributeError, NotImplementedError, RuntimeError):
        pass  # backend without memory stats: report without the section

    return HLOReport(
        entry=entry,
        collectives=collectives,
        collective_groups=groups,
        ops=ops,
        convert_upcasts=_count_bf16_upcasts(txt),
        custom_calls=custom_calls,
        host_callbacks=host_callbacks,
        donation=donation,
        memory=memory,
    )


# -- baseline ratchet ---------------------------------------------------------


def baseline_path(baselines_dir: str, entry: str) -> str:
    return os.path.join(baselines_dir, f"{entry}.json")


def save_baseline(report: HLOReport, baselines_dir: str, meta: dict | None = None) -> str:
    os.makedirs(baselines_dir, exist_ok=True)
    path = baseline_path(baselines_dir, report.entry)
    payload = {"report": report.to_json(), "meta": dict(meta or {})}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def load_baseline(baselines_dir: str, entry: str) -> HLOReport | None:
    path = baseline_path(baselines_dir, entry)
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as f:
        return HLOReport.from_json(json.load(f)["report"])


def compare_report(
    report: HLOReport,
    baseline: HLOReport,
    *,
    mem_rtol: float = 0.02,
) -> list[str]:
    """Diff a fresh report against its baseline. Returns human-readable
    drift messages (empty = match). Counts are exact in BOTH directions —
    an improvement fails too, until the baseline is consciously re-pinned
    (`--update-baselines`); memory compares within `mem_rtol`."""
    drifts: list[str] = []

    def _cmp(field: str, got, want) -> None:
        if got != want:
            drifts.append(
                f"{report.entry}: {field} drifted — baseline {want!r}, "
                f"compiled program has {got!r}"
            )

    _cmp("collectives", report.collectives, baseline.collectives)
    _cmp("collective_groups", report.collective_groups, baseline.collective_groups)
    _cmp("ops", report.ops, baseline.ops)
    _cmp("convert_upcasts", report.convert_upcasts, baseline.convert_upcasts)
    _cmp("custom_calls", report.custom_calls, baseline.custom_calls)
    _cmp("host_callbacks", report.host_callbacks, baseline.host_callbacks)
    _cmp("donation", report.donation, baseline.donation)
    if report.memory and baseline.memory:
        for key, want in baseline.memory.items():
            got = report.memory.get(key, 0)
            denom = max(abs(want), 1)
            if abs(got - want) / denom > mem_rtol:
                drifts.append(
                    f"{report.entry}: memory[{key}] drifted beyond "
                    f"rtol={mem_rtol} — baseline {want}, got {got}"
                )
    return drifts
