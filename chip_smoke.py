"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two hot paths of automodel_tpu through the entry points a user
calls (`automodel_tpu.cli.app.run_recipe`, what `python -m automodel_tpu
<yaml>` runs) at the published widths of Moonlight-16B-A3B, depth cut,
seeded random weights, mock data, and checks what comes out:

  kernels  one chip    every `pl.pallas_call` under ops/pallas/ compiled
                       (never interpreted) at one production shape and
                       compared with its XLA oracle evaluated in float32
                       under `jax.default_matmul_precision("highest")`
  serve    one chip    examples/llm_serve/moonlight_16b_a3b_v5e1.yaml
  train4   four chips  examples/llm_pretrain/moonlight_16b_a3b_v5e4.yaml

    python chip_smoke.py [--phases kernels,serve,train4]

Without --phases it runs every phase the device count allows. It needs a
TPU: anywhere else it exits non-zero at once and prints no result. ONE
process runs the phases in turn and drops each phase's buffers before the
next (a chip belongs to one process; a child of a process that has touched
JAX cannot have it). Any failed check raises: the exit status is 0 only
when every phase that ran passed, and the last line of standard output is
then `{"ok": true, "device": {...}}`.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import math
import os
import re
import shutil
import sys
import time

import jax

ROOT = os.path.dirname(os.path.abspath(__file__))
PHASES = ("kernels", "serve", "train4")
SERVE_YAML = "examples/llm_serve/moonlight_16b_a3b_v5e1.yaml"
TRAIN_YAML = "examples/llm_pretrain/moonlight_16b_a3b_v5e4.yaml"

# Production shapes of the kernels phase. Flash: one 4096-token sequence of
# Moonlight's MLA heads (16 heads, qk 128 + 64, v 128), the train phase's
# per-chip shape. Paged: the serve YAML's step (256 rows, 26 pages of 64
# tokens a slot) over Moonlight's latent pool (latent 512, rope 64) and
# over a GQA pool of 32 query heads on 8 kv heads of 128 (the Llama-3-8B /
# Qwen3-8B head layout; a smaller pool keeps the float32 oracle's gather of
# every row's pages small).
FLASH_SHAPE = dict(B=1, S=4096, H=16, D_qk=192, D_v=128)
PAGED_STEP = dict(T=256, P=26, ps=64)
MLA_POOL = dict(heads=16, latent=512, rope=64, nope=128, num_pages=2049)
GQA_POOL = dict(q_heads=32, kv_heads=8, head_dim=128, num_pages=513)
# Grouped matmul: the serve step's gate / up product of one expert layer
# (256 rows x top-6 sorted over Moonlight's 64 experts of 2048 -> 1408), the
# last 36 rows past the last group as a masked token's are.
EXPERTS_CALL = dict(m=1536, k=2048, n=1408, E=64, masked_rows=36)
# Selective scan: one state-space layer of AI21-Jamba2-3B over a 256-row
# step (5120 channels, 16 states, 128 slots): 100 decode rows, a chunk of 60
# continuing its slot, a chunk of 40 from position 0, 56 pad rows.
SCAN_CALL = dict(T=256, C=5120, N=16, slots=128, decode=100, chunks=(60, 40))

# bf16's unit roundoff is 2^-9. A kernel rounds its softmax weights and its
# output to bf16 and accumulates in float32, so against a float32 oracle on
# the same inputs an element is off by a few roundoffs of the tensor's
# largest magnitude: 2^-6 allows eight. A wrong mask, scale, page or block
# index moves the result by its own magnitude and fails by a wide margin.
KERNEL_TOL = 2.0 ** -6

_compile_seconds = 0.0


def _on_compile(event: str, seconds: float, **_kw) -> None:
    global _compile_seconds
    if event.endswith("backend_compile_duration"):
        _compile_seconds += seconds


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: FAILED: {what}")
    print(f"  ok: {what}", flush=True)


def hbm(device) -> dict:
    stats = device.memory_stats()
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use")}


def gib(n: float) -> str:
    return f"{n / 2**30:.2f} GiB"


def program_memory(compiled) -> str:
    """What the compiler reserved for one call of a compiled program."""
    m = compiled.memory_analysis()
    return (f"arguments {gib(m.argument_size_in_bytes)} (donated "
            f"{gib(m.alias_size_in_bytes)}), temporaries "
            f"{gib(m.temp_size_in_bytes)}")


def rel_err(got, want) -> float:
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def mosaic_calls(hlo_text: str, kernel: str) -> list[list[tuple]]:
    """Operand shapes of each Mosaic custom call of the Pallas kernel
    named `kernel` (the `name=` of its pallas_call) in a compiled module."""
    out = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        # op_name="jit(f)/.../flash_attention_fwd/pallas_call", or under
        # autodiff ".../transpose(jvp(flash_attention_dq))/pallas_call"
        if not re.search(rf'[/(]{kernel}\)*/pallas_call"', line):
            continue
        # "operand_layout_constraints={s32[256,26]{1,0}, bf16[...]{2,1,0}}"
        start = line.index("operand_layout_constraints={")
        operands = line[start : line.index("}}", start)]
        shapes = re.findall(r"\w+\[([\d,]*)\]", operands)
        out.append([tuple(int(d) for d in s.split(",") if d) for s in shapes])
    return out


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------
def _paged_batch(rng, *, T, P, ps, num_pages):
    """A mixed step's rows: decode rows of long contexts, two prefill
    chunks, pad rows; per-row page tables are dense prefixes of distinct
    pages, padded with the trash page (the last one), as the scheduler
    emits them. Returns (tables, positions, each row's slot)."""
    import numpy as np

    trash = num_pages - 1
    perm = rng.permutation(trash)
    tables = np.full((T, P), trash, np.int32)
    pos = np.full((T,), -1, np.int32)
    slot = np.full((T,), -1, np.int32)
    row, used, slots = 0, 0, 0

    def context(length, rows_pos):
        nonlocal row, used, slots
        n = -(-length // ps)
        pages = perm[used : used + n]
        used += n
        for p in rows_pos:
            tables[row, :n] = pages
            pos[row], slot[row] = p, slots
            row += 1
        slots += 1

    for _ in range(12):  # decode rows, one per running request
        length = int(rng.integers(ps, P * ps))
        context(length, [length - 1])
    for chunk in (96, 64):  # prefill chunks mid-prompt
        length = int(rng.integers(chunk, P * ps))
        context(length, range(length - chunk, length))
    assert row <= T - 8 and used <= trash  # the rest stay pad rows
    return tables, pos, slot


def phase_kernels() -> None:
    import jax.numpy as jnp
    import numpy as np

    from automodel_tpu.ops.attention import make_attention_mask, xla_attention
    from automodel_tpu.ops.paged_attention import (
        RowSegments,
        max_row_segments,
        ragged_paged_attention_xla,
        ragged_paged_mla_attention_xla,
        row_segments,
        row_tile,
    )
    from automodel_tpu.ops.pallas import flash_attention as fa
    from automodel_tpu.ops.pallas import ragged_paged_attention as rpa
    from automodel_tpu.ops.quant import quantize_kv_rows

    check(not fa._interpret() and not rpa._interpret(),
          "Pallas kernels compile for the chip (interpret mode is off)")
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa: E731

    # -- flash forward, dq, dkv
    B, S, H = (FLASH_SHAPE[k] for k in "BSH")
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, FLASH_SHAPE["D_qk"]), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, FLASH_SHAPE["D_qk"]), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, FLASH_SHAPE["D_v"]), jnp.bfloat16)
    do = jax.random.normal(ks[3], (B, S, H, FLASH_SHAPE["D_v"]), jnp.bfloat16)
    mask = make_attention_mask(S, S, causal=True)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True)

    def oracle(q, k, v):
        return xla_attention(q, k, v, mask=mask)

    flash_fwd_bwd = jax.jit(lambda q, k, v, do: jax.vjp(flash, q, k, v)[1](do)
                            + (flash(q, k, v),))
    got = flash_fwd_bwd(q, k, v, do)
    txt = flash_fwd_bwd.lower(q, k, v, do).compile().as_text()
    for name in ("flash_attention_fwd", "flash_attention_dq",
                 "flash_attention_dkv"):
        check(len(mosaic_calls(txt, name)) >= 1, f"{name} is a Mosaic call")
    with jax.default_matmul_precision("highest"):
        want = jax.jit(
            lambda q, k, v, do: jax.vjp(oracle, q, k, v)[1](do)
            + (oracle(q, k, v),)
        )(*f32((q, k, v, do)))
    for name, g, w in zip(("dq", "dk", "dv", "out"), got, want):
        err = rel_err(g, w)
        check(err < KERNEL_TOL, f"flash {name} vs float32 oracle: "
              f"max|err|/max|ref| = {err:.2e} < {KERNEL_TOL:.2e}")
    del q, k, v, do, got, want

    # -- the four paged kernels
    T, P, ps = (PAGED_STEP[k] for k in ("T", "P", "ps"))
    rng = np.random.default_rng(0)

    def paged_case(name, kernel, oracle, q_shapes, page_shapes, num_pages,
                   quant):
        """`kernel` over the step's rows grouped as the engine groups
        them (a decode row a segment of its own, a chunk cut at the
        tile), against `oracle` reading every row's own table."""
        tables, pos, slot = _paged_batch(
            rng, T=T, P=P, ps=ps, num_pages=num_pages)
        # a row's queries and its output (as wide as the first of them)
        tile = row_tile(T, sum(h * w for h, w in q_shapes + q_shapes[:1]))
        _tile, *segments = row_segments(
            jnp.asarray(slot), jnp.asarray(pos), jnp.asarray(tables),
            page_size=ps, tile=tile,
            max_segments=max_row_segments(T, 14, tile),
        )
        kk = jax.random.split(jax.random.key(len(name)), 4)
        qs = [jax.random.normal(a, (T, *s), jnp.bfloat16)
              for a, s in zip(kk[:2], q_shapes)]
        pages = [jax.random.normal(a, (num_pages, ps, *s), jnp.bfloat16)
                 for a, s in zip(kk[2:], page_shapes)]
        scales = []
        if quant:  # one scale per page slot, as the engine writes them
            pairs = [
                quantize_kv_rows(p.reshape(num_pages * ps, *p.shape[2:]))
                for p in pages
            ]
            pages = [q8.reshape(p.shape) for (q8, _), p in zip(pairs, pages)]
            scales = [sc.reshape(num_pages, ps) for _, sc in pairs]
        tables, pos = jnp.asarray(tables), jnp.asarray(pos)
        fn = jax.jit(lambda blocks, count, *a: kernel(
            *a, segments=RowSegments(tile, blocks, count)))
        got = fn(*segments, *qs, *pages, *scales, tables, pos)
        txt = fn.lower(
            *segments, *qs, *pages, *scales, tables, pos).compile().as_text()
        check(len(mosaic_calls(txt, name)) == 1, f"{name} is a Mosaic call")
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*f32(qs), *pages, *scales, tables, pos)
        err = rel_err(got, want)
        check(err < KERNEL_TOL, f"{name} vs float32 oracle: "
              f"max|err|/max|ref| = {err:.2e} < {KERNEL_TOL:.2e}")
        check(float(jnp.max(jnp.abs(got[-1].astype(jnp.float32)))) == 0.0,
              f"{name}: pad rows come out zero")

    m = MLA_POOL
    mla_scale = (m["nope"] + m["rope"]) ** -0.5
    mla_q = [(m["heads"], m["latent"]), (m["heads"], m["rope"])]
    mla_pages = [(m["latent"],), (m["rope"],)]
    paged_case(
        "paged_attention_mla",
        functools.partial(rpa.paged_mla_attention_kernel, scale=mla_scale),
        lambda qa, qr, c, kr, pt, pos: ragged_paged_mla_attention_xla(
            qa, qr, f32(c), f32(kr), pt, pos, scale=mla_scale),
        mla_q, mla_pages, m["num_pages"], quant=False,
    )
    paged_case(
        "paged_attention_mla_int8",
        functools.partial(
            rpa.paged_mla_attention_quant_kernel, scale=mla_scale),
        lambda qa, qr, c, kr, cs, krs, pt, pos: ragged_paged_mla_attention_xla(
            qa, qr, c, kr, pt, pos, scale=mla_scale,
            c_scales=cs, kr_scales=krs),
        mla_q, mla_pages, m["num_pages"], quant=True,
    )
    g = GQA_POOL
    gqa_scale = g["head_dim"] ** -0.5
    gqa_q = [(g["q_heads"], g["head_dim"])]
    gqa_pages = [(g["kv_heads"], g["head_dim"])] * 2
    paged_case(
        "paged_attention_gqa",
        functools.partial(rpa.paged_attention_kernel, scale=gqa_scale),
        lambda q, k, v, pt, pos: ragged_paged_attention_xla(
            q, f32(k), f32(v), pt, pos, scale=gqa_scale),
        gqa_q, gqa_pages, g["num_pages"], quant=False,
    )
    paged_case(
        "paged_attention_gqa_int8",
        functools.partial(rpa.paged_attention_quant_kernel, scale=gqa_scale),
        lambda q, k, v, ks_, vs_, pt, pos: ragged_paged_attention_xla(
            q, k, v, pt, pos, scale=gqa_scale, k_scales=ks_, v_scales=vs_),
        gqa_q, gqa_pages, g["num_pages"], quant=True,
    )

    # -- the routed experts' grouped matmul
    from automodel_tpu.ops import grouped_matmul as gmm
    from automodel_tpu.ops.pallas import grouped_matmul as gmm_kernel

    e = EXPERTS_CALL
    check(not gmm_kernel._interpret(), "grouped_matmul compiles for the chip")
    kk = jax.random.split(jax.random.key(32), 2)
    lhs = jax.random.normal(kk[0], (e["m"], e["k"]), jnp.bfloat16)
    rhs = jax.random.normal(
        kk[1], (e["E"], e["k"], e["n"]), jnp.bfloat16) * e["k"] ** -0.5
    routed = e["m"] - e["masked_rows"]
    sizes = jnp.asarray(
        rng.multinomial(routed, np.ones(e["E"] - 4) / (e["E"] - 4)).tolist()
        + [0] * 4, jnp.int32)  # four experts idle
    fn = jax.jit(functools.partial(gmm.grouped_matmul, impl="pallas"))
    got = fn(lhs, rhs, sizes)
    txt = fn.lower(lhs, rhs, sizes).compile().as_text()
    check(len(mosaic_calls(txt, "grouped_matmul")) == 1,
          "grouped_matmul is a Mosaic call")
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.lax.ragged_dot)(*f32((lhs, rhs)), sizes)
    err = rel_err(got[:routed], want[:routed])
    check(err < KERNEL_TOL, f"grouped_matmul vs float32 lax.ragged_dot: "
          f"max|err|/max|ref| = {err:.2e} < {KERNEL_TOL:.2e}")
    check(float(jnp.max(jnp.abs(got[routed:].astype(jnp.float32)))) == 0.0,
          "grouped_matmul: rows past the last group come out zero")

    # -- a serve step's ragged selective scan
    from automodel_tpu.ops import selective_scan as scan
    from automodel_tpu.ops.pallas import selective_scan as scan_kernel

    c = SCAN_CALL
    check(not scan_kernel._interpret(), "selective_scan compiles for the chip")
    T, C, N, S = c["T"], c["C"], c["N"], c["slots"]
    runs_of = [(s, 200 + s, 1) for s in range(c["decode"])] + [
        (c["decode"] + i, 64 * (1 - i), n) for i, n in enumerate(c["chunks"])]
    rows = [(s, p) for s, p0, n in runs_of for p in range(p0, p0 + n)]
    slot = np.full(T, -1, np.int32)
    pos = np.full(T, -1, np.int32)
    slot[:len(rows)], pos[:len(rows)] = np.asarray(rows, np.int32).T
    runs = scan.step_runs(jnp.asarray(slot), jnp.asarray(pos), trash=S)
    operands = (
        jnp.asarray(rng.normal(size=(T, C)), jnp.bfloat16),
        jnp.asarray(np.exp(rng.normal(-4, 1, (T, C))), jnp.float32),
        -jnp.exp(jnp.asarray(rng.normal(1.5, 0.7, (N, C)), jnp.float32)),
        jnp.asarray(rng.normal(size=(T, N)), jnp.float32),
        jnp.asarray(rng.normal(size=(T, N)), jnp.float32),
        jnp.asarray(rng.normal(size=(S + 1, N, C)), jnp.float32),
    )
    fn = jax.jit(functools.partial(
        scan.ragged_selective_scan, runs=runs, impl="pallas"))
    txt = fn.lower(*operands).compile().as_text()
    check(len(mosaic_calls(txt, "selective_scan")) == 1,
          "selective_scan is a Mosaic call")
    y, state = fn(*operands)
    y_ref, state_ref = jax.jit(functools.partial(
        scan.ragged_selective_scan, runs=runs, impl="xla"))(*operands)
    err = max(rel_err(y[:len(rows)], y_ref[:len(rows)]),
              rel_err(state[:S], state_ref[:S]))
    check(err < 2.0 ** -20, f"selective_scan vs the float32 lax.scan: "
          f"max|err|/max|ref| = {err:.2e} < {2.0 ** -20:.2e}")
    absent = np.arange(c["decode"] + len(c["chunks"]), S)
    check(bool(jnp.array_equal(state[absent], operands[5][absent])),
          "selective_scan: a slot that is not in the step is not touched")


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------
def _load(yaml_path: str):
    """The example's config, with its run_dir made absolute and emptied
    (the loggers append: an earlier run's records must not be read back)."""
    from automodel_tpu.config.loader import load_yaml

    cfg = load_yaml(os.path.join(ROOT, yaml_path))
    run_dir = os.path.join(ROOT, cfg.get("run_dir"))
    shutil.rmtree(run_dir, ignore_errors=True)
    cfg.set("run_dir", run_dir)
    return cfg


def _mixed_steps(trace_path: str) -> int:
    """Engine steps in which one request decoded while another prefilled,
    from the per-request admit / first-token / done steps in the trace."""
    admit, first, done = {}, {}, {}
    with open(trace_path) as f:
        for line in f:
            ev = json.loads(line)
            table = {"request.admit": admit, "request.first_token": first,
                     "request.done": done}.get(ev["name"])
            if table is not None:
                table.setdefault(ev["rid"], ev["step"])
    decoding, prefilling = set(), set()
    for rid in done:
        decoding.update(range(first[rid] + 1, done[rid] + 1))
        prefilling.update(range(admit[rid], first[rid] + 1))
    # a request's own prefill and decode steps are disjoint, so a step in
    # both sets belongs to two requests
    return len(decoding & prefilling)


def phase_serve() -> None:
    import jax.numpy as jnp
    import numpy as np

    from automodel_tpu.cli.app import run_recipe
    from automodel_tpu.models.llm.decoder import unembed
    from automodel_tpu.observability.metrics import default_registry

    cfg = _load(SERVE_YAML)
    run_dir = cfg.get("run_dir")
    n_req = int(cfg.get("max_requests"))
    n_new = int(cfg.get("serving.max_new_tokens"))
    n_layers = int(cfg.get("model.hf_config.num_hidden_layers"))

    recipe = run_recipe(cfg)
    engine = recipe.server

    check(not hasattr(recipe, "tx") and recipe.train_state is None
          and recipe.params is None,
          "llm_serve built no optimizer and kept no second copy of the weights")
    dtypes = {str(p.dtype) for p in jax.tree.leaves(engine.params)}
    check(dtypes == {"bfloat16"}, f"the engine holds bf16 weights ({dtypes})")
    n_params = sum(p.size for p in jax.tree.leaves(engine.params))
    print(f"  depth {n_layers} (1 dense + {n_layers - 1} expert layers), "
          f"{n_params / 1e9:.2f} B parameters, "
          f"{gib(2 * n_params)} of bf16 weights", flush=True)

    with open(os.path.join(run_dir, "generations.jsonl")) as f:
        gens = [json.loads(line) for line in f]
    check(len(gens) == n_req and all(
        len(g["generated_ids"]) == n_new and g["finish_reason"] == "length"
        for g in gens
    ), f"all {n_req} requests finished with the {n_new} tokens asked for")
    check(engine.step_cache_size() == 1,
          "the serve step compiled once (step_cache_size() == 1)")
    fallbacks = {
        k: v for k, v in default_registry().snapshot().items()
        if k.startswith("attention_reference_fallbacks_total")
    }
    check(not fallbacks, f"no attention call fell back to XLA ({fallbacks})")
    step = engine.lower_step().compile()
    print(f"  compiled serve step: {program_memory(step)}", flush=True)
    txt = step.as_text()
    calls = mosaic_calls(txt, "paged_attention_mla")
    check(len(calls) >= 1,
          f"the compiled serve step calls the Mosaic MLA paged kernel "
          f"({len(calls)} call sites, q_abs {calls and calls[0][2]})")
    calls = mosaic_calls(txt, "grouped_matmul")
    check(len(calls) >= 3 and "ragged-dot" not in txt,
          f"the experts' products are the Mosaic grouped matmul "
          f"({len(calls)} call sites, no ragged-dot left)")
    mixed = _mixed_steps(os.path.join(run_dir, "serve.trace.jsonl"))
    check(mixed >= 1, f"{mixed} steps mixed prefill and decode rows")

    # Agreement with a reference on a small input: the TRAINING forward
    # (flash attention over un-absorbed MLA heads, no cache, no pages) is
    # teacher-forced with a request's prompt and its generated tokens; a
    # token the engine chose greedily — through chunked prefill, the paged
    # latent cache and the absorbed-MLA kernel — agrees when it is the
    # reference's best token or within LOGIT_TOL of it.
    # Logits here have a standard deviation of 1 (unit-RMS hidden states,
    # head columns of norm 1). Where both paths route a token alike, bf16
    # moves a logit by hundredths, and a token read from a wrong page, mask
    # or position sits about 4 below the best of 163840. But random weights
    # make top-6-of-64 routing brittle: a rounding difference flips one
    # expert for some tokens, and a flipped token's logits move by tenths
    # or more (the training forward in bf16 against ITSELF in float32
    # disagrees on the best token at a tenth of the positions at small
    # width on the CPU; at these widths a float32 CPU reference put a third
    # of the engine's tokens off the best, a few by more than 1). So the
    # check is on the majority, request by request: an error in the serve
    # path moves every token, a routing flip moves its own.
    LOGIT_TOL, MIN_AGREE = 0.25, 0.5
    from automodel_tpu.models.moe_lm import decoder as moe_decoder

    n_prompt = len(gens[0]["prompt_ids"])
    check(all(len(g["prompt_ids"]) == n_prompt for g in gens),
          f"every prompt has {n_prompt} tokens")

    def restack(params):
        """The engine's per-layer trees back on a leading layer axis, which
        the training forward scans. Leaf by leaf and through host memory:
        beside the weights and the pool the chip has no room for one more
        stacked expert leaf (2.75 GiB asked, 1.60 free: PR 27), so each
        layer's buffer is read back and given up before its stack goes in
        (the engine is done with them)."""
        from automodel_tpu.serving.engine import LAYER_STACKS

        out = dict(params)
        for key in LAYER_STACKS:
            layers = params.get(key)
            if layers is None:
                continue
            treedef = jax.tree.structure(layers[0])
            stacked = []
            for parts in zip(*(jax.tree.leaves(layer) for layer in layers)):
                on_host = np.stack([np.asarray(part) for part in parts])
                for part in parts:
                    part.delete()
                stacked.append(jnp.asarray(on_host))
            out[key] = treedef.unflatten(stacked)
        return out

    @jax.jit
    def ref_logits(params, ids):
        hidden, _aux = moe_decoder.forward(
            params, recipe.model_cfg, ids, return_hidden=True
        )
        # position p predicts token p + 1
        rows = hidden[:, n_prompt - 1 : n_prompt - 1 + n_new]
        return unembed(params, recipe.model_cfg, rows)[0]

    stacked_params = restack(engine.params)
    del engine.params
    for g in (gens[0], gens[len(gens) // 2], gens[-1]):
        ids = np.asarray(g["prompt_ids"] + g["generated_ids"], np.int32)
        padded = np.zeros((1, -(-len(ids) // 128) * 128), np.int32)
        padded[0, : len(ids)] = ids  # flash needs a multiple of 128
        logits = ref_logits(stacked_params, jnp.asarray(padded))
        chosen = jnp.asarray(ids[n_prompt:])
        gap = logits.max(-1) - jnp.take_along_axis(
            logits, chosen[:, None], -1)[:, 0]
        check(bool(jnp.all(jnp.isfinite(logits))),
              f"request {g['rid']}: reference logits are finite")
        agree = float((gap <= LOGIT_TOL).mean())
        check(agree >= MIN_AGREE,
              f"request {g['rid']}: {agree:.0%} of the {n_new} served tokens "
              f"are within {LOGIT_TOL} of the training forward's best logit "
              f"(>= {MIN_AGREE:.0%} asked; {int((gap == 0).sum())} are the "
              f"best, largest gap {float(gap.max()):.2f}, logit std "
              f"{float(logits.std()):.2f})")

    peaks = [hbm(d) for d in jax.devices()]
    print(f"  serve: peak HBM on chip 0 {gib(peaks[0]['peak_bytes_in_use'])} "
          "(live buffers; a program's temporaries are not in memory_stats)",
          flush=True)
    # a 1x1x1 serving mesh on a larger host: the chassis shards the weights
    # over every chip, the engine moves them to its own, and the rest must
    # end up empty again
    for i, p in enumerate(peaks[1:], 1):
        check(p["bytes_in_use"] < 2**20,
              f"chip {i} holds nothing after the engine took the weights "
              f"({p['bytes_in_use']} bytes in use)")


# ---------------------------------------------------------------------------
# phase: train4
# ---------------------------------------------------------------------------
def phase_train4() -> None:
    import jax.numpy as jnp
    import numpy as np

    from automodel_tpu.cli.app import run_recipe

    cfg = _load(TRAIN_YAML)
    run_dir = cfg.get("run_dir")
    n_layers = int(cfg.get("model.hf_config.num_hidden_layers"))
    vocab = int(cfg.get("model.hf_config.vocab_size"))
    batch = int(cfg.get("dataloader.microbatch_size"))
    seq = int(cfg.get("dataset.seq_len"))

    recipe = run_recipe(cfg)
    ctx = recipe.mesh_ctx

    want_mesh = {"pp": 1, "dp_replicate": 1, "dp_shard": 2, "ep": 2,
                 "cp": 1, "tp": 1}
    check(ctx.sizes == want_mesh and
          list(ctx.mesh.devices.flat) == jax.devices(),
          f"the mesh is what the YAML asked for: {ctx.sizes}")
    params = recipe.train_state.params
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"  depth {n_layers} (1 dense + {n_layers - 1} expert layers), "
          f"{n_params / 1e9:.2f} B parameters, seq {seq}", flush=True)

    with open(os.path.join(run_dir, "training.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    recs = [r for r in recs if "loss" in r]
    check(len(recs) >= 5, f"{len(recs)} train steps ran")
    print("  loss by step:", [round(r["loss"], 4) for r in recs],
          " grad_norm:", [round(r["grad_norm"], 4) for r in recs], flush=True)
    # untrained weights predict about uniformly: ln(vocab) = 12.0 here
    uniform = math.log(vocab)
    check(abs(recs[0]["loss"] - uniform) < 0.05 * uniform,
          f"step-1 loss {recs[0]['loss']:.4f} is within 5% of "
          f"ln({vocab}) = {uniform:.4f}")
    check(all(np.isfinite(r["loss"]) for r in recs), "loss finite on every step")
    check(all(np.isfinite(r["grad_norm"]) and r["grad_norm"] > 0 for r in recs),
          "gradient norm finite and non-zero on every step")

    leaves = jax.tree_util.tree_leaves_with_path(params)
    check(all(len(p.sharding.device_set) == 4 for _, p in leaves),
          f"all {len(leaves)} parameter leaves span four devices")
    replicated = [
        f"{jax.tree_util.keystr(path)} {p.nbytes / 2**20:.0f} MiB"
        for path, p in leaves
        if p.nbytes > 2**20 and p.sharding.shard_shape(p.shape) == p.shape
    ]
    print(f"  replicated on all four (above 1 MiB): {replicated}", flush=True)
    mem = [hbm(d) for d in jax.devices()]
    in_use = [m["bytes_in_use"] for m in mem]
    check(max(in_use) < 2 * min(in_use),
          f"bytes_in_use of the four chips within a factor of two: "
          f"{[gib(b) for b in in_use]}")
    print("  train4: peak HBM by chip "
          f"{[gib(m['peak_bytes_in_use']) for m in mem]} (live buffers; a "
          "program's temporaries are not in memory_stats, and chip 0's peak "
          "also covers any earlier phase of this process)", flush=True)

    tokens = jax.ShapeDtypeStruct(
        (1, batch, seq), jnp.int32,
        sharding=ctx.sharding(*recipe._batch_spec()),
    )
    step = recipe._train_step.lower(
        recipe.train_state, {"input_ids": tokens, "labels": tokens},
        recipe.rng.next_key(),
    ).compile()
    print(f"  compiled train step, per chip: {program_memory(step)}",
          flush=True)
    txt = step.as_text()
    n_ragged = len(re.findall(r" ragged-all-to-all\(", txt))
    check(n_ragged >= 2,
          f"the compiled step contains ragged-all-to-all ({n_ragged})")
    local_batch = batch // (ctx.sizes["dp_shard"] * ctx.sizes["ep"])
    for name, q_index in (("flash_attention_fwd", 5),
                          ("flash_attention_dq", 5),
                          ("flash_attention_dkv", 5)):
        calls = mosaic_calls(txt, name)
        check(len(calls) >= 1 and all(
            c[q_index][0] == local_batch for c in calls
        ), f"{name}: {len(calls)} Mosaic calls, q operand "
           f"{calls and calls[0][q_index]} has the per-chip batch "
           f"{local_batch}, not the global {batch}")


# ---------------------------------------------------------------------------
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--phases", default=None,
        help=f"comma-separated subset of {','.join(PHASES)}; a phase named "
        "here that cannot run is an error",
    )
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform!r} "
              "devices); this script does not fall back", file=sys.stderr)
        raise SystemExit(2)

    from importlib.metadata import version

    import jaxlib

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"chip_smoke: {device} jax {jax.__version__} "
          f"jaxlib {jaxlib.__version__} libtpu {version('libtpu')}",
          flush=True)

    from automodel_tpu.utils.compile_cache import enable_compile_cache

    print(f"chip_smoke: compile cache at {enable_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_compile)

    runnable = {"kernels": 1, "serve": 1, "train4": 4}
    if args.phases is None:
        phases = [p for p in PHASES if len(devices) >= runnable[p]]
    else:
        phases = args.phases.split(",")
        for p in phases:
            if p not in PHASES:
                raise SystemExit(f"chip_smoke: unknown phase {p!r}")
            if len(devices) < runnable[p]:
                raise SystemExit(
                    f"chip_smoke: {p} needs {runnable[p]} devices, "
                    f"found {len(devices)}"
                )

    run = {"kernels": phase_kernels, "serve": phase_serve,
           "train4": phase_train4}
    for p in PHASES:
        if p not in phases:
            why = (f"needs {runnable[p]} devices, found {len(devices)}"
                   if len(devices) < runnable[p] else "not asked for")
            print(f"{p}: not_run ({why})", flush=True)
            continue
        print(f"{p}: start", flush=True)
        global _compile_seconds
        _compile_seconds = 0.0
        t0 = time.perf_counter()
        run[p]()
        # drop the phase's buffers before the next one takes the chip
        gc.collect()
        jax.clear_caches()
        gc.collect()
        print(f"{p}: passed in {time.perf_counter() - t0:.1f} s "
              f"(compile {_compile_seconds:.1f} s); HBM in use after "
              f"{[gib(hbm(d)['bytes_in_use']) for d in devices]}, peak "
              f"{[gib(hbm(d)['peak_bytes_in_use']) for d in devices]}",
              flush=True)

    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
