"""Operations and bytes that the ALGORITHM needs, from shapes alone. These are
the numerators of every `*_mfu*` and `*_roofline` metric; they never look at
what the program's kernels pad, recompute or read twice. A multiply-add counts
as 2 operations. `cfg` is a configuration file's dict (Hugging Face keys).

What depends on the architecture lives in `counts/<architecture>.py`, found
under the manifest's `paths` by the configuration's `reference` name, like a
reader: `serve_step_flops(cfg, rows, context_tokens, sampled_rows)` and one
`<kernel>_call(cfg, ...) -> {"flops", "bytes"}` for each kernel it has a
roofline of. Here is what every architecture shares.
"""

from __future__ import annotations

from benchmark import load_module


def counts_for(ctx: dict):
    """The operation counts of the architecture of a reader's cell. A
    configuration whose architecture has no `counts/<name>.py` fails here,
    loudly: a share of a peak is never made from another model's counts."""
    return load_module(ctx["root"], ctx["paths"], "counts",
                       ctx["config"]["reference"])


def head_flops_per_row(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def roofline(flops: float, bytes_: float, peaks: dict) -> dict:
    """Least seconds the chip could take, and which peak bounds it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return {"least_s": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}

