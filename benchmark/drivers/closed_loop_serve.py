"""Traffic kind `closed_loop_serve`: N clients, each submitting its next
request the moment its last one ends, against `OnlineFrontend` over one
`ServingEngine`. One process, one event loop; the engine's jitted step runs in
the frontend's own worker thread.

Set-up: weights on the device from the seed (benchmark/weights.py), the engine
built from the configuration's `serving` group, then the ramp: the clients run
until `ramp_requests` requests have finished, which also compiles and warms
the engine's one step program. Then the window opens. At its end the requests
in flight are cancelled; nothing is censored, because every first token and
every gap that lands inside the window counts, whenever its request began.
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import time

import numpy as np

from benchmark import served_check, traffic_gen, weights


@dataclasses.dataclass
class Rec:
    """One request as its client saw it (host clock, perf_counter)."""
    prompt: list
    n_out: int
    submit_t: float
    rid: int = -1
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)
    reason: str | None = None
    end_t: float = -1.0
    error: str | None = None


@dataclasses.dataclass
class Step:
    """One `engine.run_step` as the harness saw it."""
    t0: float
    t1: float
    rows: int             # real rows of the plan (of token_budget)
    samples: int          # rows that sampled a token
    context_tokens: int   # sum over rows of the row's context length
    sequence_tokens: int  # sum over the step's sequences of cached tokens


def step_kernel_counts(config: dict, lowered_text: str, on_chip: bool) -> dict:
    """{kernel name: times it stands in the lowered step}, for the names the
    configuration's `step_kernels` lists. On the chip a configuration that
    lists none is refused (a step is never passed unlooked-at), and so is a
    step that lacks one it lists."""
    names = list(config.get("step_kernels") or ())
    counts = {name: lowered_text.count(name) for name in names}
    if on_chip and not names:
        raise RuntimeError(
            "the configuration's file names no `step_kernels`: list the "
            "kernels its lowered serve step must contain")
    absent = [name for name, n in counts.items() if not n]
    if on_chip and absent:
        raise RuntimeError(f"no {', '.join(absent)} kernel in the step")
    return counts


def build_engine(run, ref):
    """The program's engine at the configuration's geometry, holding the
    benchmark's weights, drawn as the configuration's reference module `ref`
    says. Returns (engine, the parameter tree's shapes)."""
    import jax
    import jax.numpy as jnp

    from automodel_tpu.models.registry import get_model_spec
    from automodel_tpu.serving import ServingConfig, ServingEngine
    from automodel_tpu.serving.router import ServeMeshConfig

    cfg = run.config
    hf = {k: v for k, v in cfg.items() if k not in run.NOT_HF_KEYS}
    hf["architectures"] = cfg["architectures"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["serve_dtype"]]
    spec = get_model_spec(hf)
    model_cfg = spec.config_from_hf(
        hf, dtype=dtype, remat_policy="none", attn_impl=cfg.get("attn_impl", "auto"))
    shapes = jax.eval_shape(lambda: spec.module.init(model_cfg, jax.random.key(0)))
    params = weights.make_params(
        run.seed, shapes, dtype, weights.draw_for(ref, cfg))
    serving = dict(cfg["serving"])
    if run.control == "program_int8":
        # the program's own lower-precision path, switched on
        serving["serve_precision"] = "int8"
    engine = ServingEngine(
        params, model_cfg, ServingConfig(**serving),
        mesh_ctx=ServeMeshConfig().build_contexts(jax.devices()[:1])[0],
    )
    return engine, shapes


def instrument(engine, sched, steps: list, first_step_t: dict, logprobs: dict,
               annotate):
    """Wrap `engine.run_step`: a record per step, the wall time of the first
    step that held a row of each request, and each sampled token's
    log-probability as the step returned it (the frontend drops it)."""
    inner = engine.run_step
    n_slots = engine.serve_cfg.max_slots

    def run_step(plan):
        t0 = time.perf_counter()
        with annotate("bench.run_step"):
            out = inner(plan)
        t1 = time.perf_counter()
        valid = plan.pos >= 0
        ctx = np.zeros(n_slots, np.int64)
        np.maximum.at(ctx, plan.slot[valid], plan.pos[valid] + 1)
        steps.append(Step(t0, t1, int(valid.sum()), plan.n_samples,
                          int((plan.pos[valid] + 1).sum()), int(ctx.sum())))
        toks, lps = out[0], out[1]
        for slot, _c, samples in plan.scheduled:
            rid = sched.running[slot].rid
            if rid not in first_step_t:
                first_step_t[rid] = t0
            if samples:
                logprobs.setdefault(rid, []).append(
                    (int(toks[slot]), float(lps[slot])))
        return out

    engine.run_step = run_step


async def drive(run, engine, frontend, source, recs, window, before_window):
    """Ramp, window, close. Fills `recs`; sets window['t0'], window['t1']."""
    from automodel_tpu.serving import Request

    mix = run.mix
    state = {"stop": False, "finished": 0}
    ramp_done = asyncio.Event()
    live: dict = {}

    async def client():
        while not state["stop"]:
            prompt, n_out = source.next()
            req = Request(prompt=prompt, max_new_tokens=n_out,
                          temperature=float(mix["temperature"]),
                          eos_token_id=None, seed=0)
            rec = Rec(prompt, n_out, time.perf_counter())
            stream = frontend.submit(req)
            rec.rid = stream.rid
            recs.append(rec)
            live[rec.rid] = rec
            try:
                async for tok in stream:
                    rec.times.append(time.perf_counter())
                    rec.tokens.append(tok)
                rec.reason = stream.finish_reason
            except Exception as e:  # a failed request, not a failed run
                rec.reason, rec.error = "error", repr(e)
            rec.end_t = time.perf_counter()
            live.pop(rec.rid, None)
            state["finished"] += 1
            if state["finished"] >= int(mix["ramp_requests"]):
                ramp_done.set()

    frontend.start()
    clients = [asyncio.ensure_future(client()) for _ in range(int(mix["clients"]))]
    await ramp_done.wait()
    before_window()
    run.window_open = True
    window["t0"] = t0 = time.perf_counter()
    if run.trace:
        await asyncio.sleep(max(run.seconds - run.trace_slice_s, 0.0))
        run.start_trace()
        window["trace_on"] = time.perf_counter()
    await asyncio.sleep(max(t0 + run.seconds - time.perf_counter(), 0.0))
    window["t1"] = time.perf_counter()
    run.window_open = False
    state["stop"] = True
    if run.trace:
        window["trace_off"] = time.perf_counter()
        run.stop_trace()
    for rid in list(live):
        frontend.cancel(rid)
    await frontend.close()
    await asyncio.gather(*clients)


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


QUANTILES = (5, 25, 50, 75, 90, 93, 94, 95, 96, 97, 99, 99.9)


def quantile_table(values) -> dict:
    """A few quantiles of `values`, to show where a tail statistic sits."""
    v = np.asarray(values, np.float64)
    return {str(q): float(np.percentile(v, q)) for q in QUANTILES}


def end_to_end(recs, window, seconds_asked: float) -> tuple[dict, dict]:
    """(metrics, counts) over everything that landed inside the window."""
    t0, t1 = window["t0"], window["t1"]
    span = t1 - t0
    firsts, gaps, n_tokens = [], [], 0
    for r in recs:
        ts = r.times
        n_tokens += sum(1 for t in ts if t0 <= t < t1)
        if ts and t0 <= ts[0] < t1:
            firsts.append((ts[0] - r.submit_t) * 1e3)
        gaps += [(b - a) * 1e3 for a, b in zip(ts, ts[1:]) if t0 <= b < t1]
    inside = [r for r in recs if t0 <= r.submit_t < t1]
    bad = ("shed", "rejected", "timed_out", "error")
    metrics = {
        "serve_out_tokens_per_s": (n_tokens / span, "tokens/s"),
        "ttft_p95_ms": (percentile(firsts, 95), "ms"),
        "itl_p95_ms": (percentile(gaps, 95), "ms"),
    }
    counts = {
        "window_s": span, "window_s_asked": seconds_asked,
        "tokens_in_window": n_tokens, "first_tokens_in_window": len(firsts),
        "gaps_in_window": len(gaps),
        "ttft_p50_ms": percentile(firsts, 50), "itl_p50_ms": percentile(gaps, 50),
        # how steeply the gaps rise around the 95th percentile
        "itl_quantiles_ms": quantile_table(gaps),
        "attempted": len(inside),
        # a request that the close itself sheds or cancels has not failed
        "failed": sum(1 for r in inside if r.reason in bad and r.end_t < t1),
        "finished_in_window": sum(
            1 for r in recs if r.reason == "length" and t0 <= r.times[-1] < t1),
    }
    return metrics, counts


def run(run) -> dict:
    import jax

    from automodel_tpu.observability.metrics import default_registry
    from automodel_tpu.serving.frontend import FrontendConfig, OnlineFrontend

    mix, cfg = run.mix, run.config
    t_build = time.perf_counter()
    ref = served_check.load_reference(cfg, run.root, run.manifest["paths"])
    engine, shapes = build_engine(run, ref)
    jax.block_until_ready(engine.params)
    run.note(engine_built_s=time.perf_counter() - t_build)
    frontend = OnlineFrontend(engine, FrontendConfig(drain=False))
    steps, first_step_t, logprobs, recs, window = [], {}, {}, [], {}
    instrument(engine, frontend.sched, steps, first_step_t, logprobs,
               run.annotate)
    source = traffic_gen.RequestSource(mix, run.seed, cfg["vocab_size"])

    def before_window():
        """What must hold for the window to be the cell: one compiled step,
        no XLA fallback of the attention op, and in the lowered step every
        kernel the configuration's `step_kernels` names."""
        fallbacks = {k: v for k, v in default_registry().snapshot().items()
                     if k.startswith("attention_reference_fallbacks_total")}
        text = engine.lower_step().as_text() if run.on_chip else ""
        run.note(
            step_cache_size=engine.step_cache_size(),
            attention_fallbacks=fallbacks,
            ramp_steps=len(steps), ramp_requests=len(recs),
        )
        if engine.step_cache_size() != 1 or fallbacks:
            raise RuntimeError("the serve step is not the cell's: "
                               f"{engine.step_cache_size()} programs, "
                               f"fallbacks {fallbacks}")
        run.note(step_kernels=step_kernel_counts(cfg, text, run.on_chip))

    asyncio.run(drive(run, engine, frontend, source, recs, window,
                      before_window))

    metrics, counts = end_to_end(recs, window, run.seconds)
    inside = [s for s in steps if window["t0"] <= s.t1 < window["t1"]]
    counts["steps_in_window"] = len(inside)
    # where a stall sat, if there was one: the longest steps and the longest
    # host turns between two steps, each with its second of the window
    t0 = window["t0"]
    counts["longest_steps_s"] = sorted(
        ((s.t1 - s.t0, s.t0 - t0) for s in inside), reverse=True)[:3]
    counts["longest_turns_s"] = sorted(
        ((b.t0 - a.t1, a.t1 - t0) for a, b in zip(inside, inside[1:])),
        reverse=True)[:3]
    counts["step_call_quantiles_ms"] = quantile_table(
        [(s.t1 - s.t0) * 1e3 for s in inside])
    counts["step_period_quantiles_ms"] = quantile_table(
        [(b.t1 - a.t1) * 1e3 for a, b in zip(inside, inside[1:])])
    counts["turn_quantiles_ms"] = quantile_table(
        [(b.t0 - a.t1) * 1e3 for a, b in zip(inside, inside[1:])])
    counts["stats"] = {k: v for k, v in frontend.stats().items()
                       if k in ("steps", "submitted", "finished", "shed",
                                "rejected", "timed_out", "preemptions",
                                "cancelled", "compiled_signatures")}
    run.note(**counts)
    result = {
        "metrics": metrics, "attempted": counts["attempted"],
        "failed": counts["failed"], "steps": steps, "recs": recs,
        "window": window, "first_step_t": first_step_t,
    }
    run.read_memory_peak()

    # free the program's state, then follow the served tokens
    engine.run_step = None
    del engine.params, engine.pool
    del engine, frontend
    gc.collect()
    jax.clear_caches()
    gc.collect()
    t_check = time.perf_counter()
    result["compared"] = served_check.check(
        ref, cfg, mix, run.seed, recs, logprobs, window, shapes,
        served_check.load_limits(run.workload, run.root, run.manifest["paths"]),
        control=None if run.control == "program_int8" else run.control)
    run.note(check_s=time.perf_counter() - t_check)
    return result
