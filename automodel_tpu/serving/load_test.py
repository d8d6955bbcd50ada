"""Sustained-load harness for the online serving frontend.

Drives an `OnlineFrontend` (or `OnlineRouter` / `DisaggOnlineFrontend` —
anything with submit/wait_step/close) with a deterministic synthetic
arrival trace: ragged prompt lengths and interarrival gaps drawn from a
seeded rng, submissions paced against the loop's OWN step counter
(`wait_step`), one consumer coroutine per stream timestamping every
token as it arrives. That yields the numbers an offline `serve_batch`
run structurally cannot: wall-clock TTFT and inter-token gaps under
concurrent consumption, shed/reject rates under overload, and goodput
(deadline-respecting completions per second).

Pacing by step index — not wall time — is what makes traces replayable:
the same config produces the same (arrival step, prompt, deadline)
sequence, so admission and shedding decisions (both pure step
arithmetic) are reproducible run to run even though the wall-clock
latencies are not.

`parity_check=N` re-serves the first N prompts through the SAME engine's
offline `serve_batch` and asserts token-for-token greedy equality — the
live loop's admission churn, pausing, and preemption must be invisible
in the sampled tokens.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time

import numpy as np

from automodel_tpu.serving.scheduler import Request


@dataclasses.dataclass(frozen=True)
class LoadTestConfig:
    """One synthetic arrival trace (fully determined by `seed`)."""

    num_requests: int = 1000
    #: [lo, hi] prompt length range (uniform)
    prompt_len: tuple = (3, 12)
    max_new_tokens: int = 8
    #: mean engine steps between arrivals (geometric); 0 → all at step 0
    mean_interarrival_steps: float = 0.25
    #: deadline (steps from admission) carried by `deadline_fraction` of
    #: requests; None → no deadlines in the trace
    deadline_in: int | None = None
    deadline_fraction: float = 0.0
    vocab: int = 64
    seed: int = 0
    #: re-serve the first N prompts offline and assert greedy parity
    parity_check: int = 0

    def __post_init__(self):
        if self.num_requests < 1:
            raise ValueError("num_requests must be >= 1")
        if not (0.0 <= self.deadline_fraction <= 1.0):
            raise ValueError("deadline_fraction must be in [0, 1]")


def make_trace(cfg: LoadTestConfig) -> list:
    """[(arrival_step, prompt, deadline_in)] — sorted, deterministic."""
    rng = np.random.default_rng(cfg.seed)
    lo, hi = cfg.prompt_len
    trace = []
    step = 0
    for i in range(cfg.num_requests):
        n = int(rng.integers(lo, hi + 1))
        prompt = [int(t) for t in rng.integers(1, cfg.vocab, (n,))]
        dl = None
        if cfg.deadline_in is not None and (
            rng.random() < cfg.deadline_fraction
        ):
            dl = cfg.deadline_in
        trace.append((step, prompt, dl))
        if cfg.mean_interarrival_steps > 0:
            step += int(rng.geometric(
                1.0 / (1.0 + cfg.mean_interarrival_steps)
            )) - 1
    return trace


async def _consume(stream, records: dict) -> None:
    stamps = []
    toks = []
    async for tok in stream:
        stamps.append(time.perf_counter())
        toks.append(tok)
    records[stream.rid] = (toks, stamps, stream.finish_reason)


async def drive_load(frontend, cfg: LoadTestConfig) -> dict:
    """Submit the trace paced by the loop's step counter; consume every
    stream concurrently; return the latency/goodput report (frontend is
    closed on return)."""
    trace = make_trace(cfg)
    records: dict = {}
    consumers = []
    submitted = []
    t0 = time.perf_counter()
    frontend.start()
    for arrival, prompt, dl in trace:
        if arrival > 0:
            await frontend.wait_step(arrival)
        req = Request(prompt=prompt, max_new_tokens=cfg.max_new_tokens)
        stream = frontend.submit(req, deadline_in=dl)
        submitted.append(req)
        consumers.append(asyncio.ensure_future(_consume(stream, records)))
    await asyncio.gather(*consumers)
    stats = await frontend.close()
    elapsed = time.perf_counter() - t0

    ok = [
        r for r in submitted
        if r.finish_reason in ("eos", "length")
    ]
    shed = [r for r in submitted if r.finish_reason in ("shed", "rejected")]
    recovered = [r for r in submitted if r.recovered > 0]
    reasons: dict = {}
    for r in submitted:
        reasons[r.finish_reason] = reasons.get(r.finish_reason, 0) + 1
    ttft = [r.ttft_s * 1e3 for r in ok if r.ttft_s >= 0]
    gaps = []
    for toks, stamps, _reason in records.values():
        gaps += [
            (b - a) * 1e3 for a, b in zip(stamps[:-1], stamps[1:])
        ]
    new_tokens = sum(len(toks) for toks, _s, _r in records.values())

    def pct(xs, q):
        return round(float(np.percentile(xs, q)), 4) if xs else None

    report = {
        "requests": len(submitted),
        "completed": len(ok),
        "shed": len(shed),
        "shed_rate": round(len(shed) / max(len(submitted), 1), 4),
        # terminal status per stream (the TokenStream finish_reason
        # classes) + how many streams survived a replica death
        "finish_reasons": reasons,
        "recovered": len(recovered),
        # recovered-request TTFT penalty: how much the re-prefill detour
        # costs the affected streams vs the undisturbed population
        "ttft_p50_recovered_ms": pct(
            [r.ttft_s * 1e3 for r in recovered
             if r.finish_reason in ("eos", "length") and r.ttft_s >= 0], 50
        ),
        "new_tokens": new_tokens,
        "elapsed_s": round(elapsed, 4),
        # deadline-respecting completions per second: the serving number
        # that overload actually moves (throughput of work that still
        # mattered when it finished)
        "goodput_rps": round(len(ok) / max(elapsed, 1e-9), 2),
        "tokens_per_sec": round(new_tokens / max(elapsed, 1e-9), 2),
        "ttft_p50_ms": pct(ttft, 50),
        "ttft_p95_ms": pct(ttft, 95),
        "ttft_p99_ms": pct(ttft, 99),
        "itl_p50_ms": pct(gaps, 50),
        "itl_p95_ms": pct(gaps, 95),
        "itl_p99_ms": pct(gaps, 99),
        "frontend": stats,
    }
    if cfg.parity_check:
        report["parity"] = {
            "records": records,
            "trace": trace[: cfg.parity_check],
        }
    return report


def run_load_test(engine, cfg: LoadTestConfig,
                  frontend_cfg=None) -> dict:
    """Blocking entry point: build an `OnlineFrontend` on `engine`, drive
    the trace, optionally verify greedy parity against the same engine's
    offline `serve_batch`. Returns the report (parity scaffolding
    resolved to a pass/fail count)."""
    from automodel_tpu.serving.frontend import FrontendConfig, OnlineFrontend

    frontend = OnlineFrontend(engine, frontend_cfg or FrontendConfig())
    report = asyncio.run(drive_load(frontend, cfg))
    if cfg.parity_check:
        scaffold = report.pop("parity")
        records = scaffold["records"]
        prompts = [p for _a, p, _d in scaffold["trace"]]
        offline = engine.serve_batch([
            Request(prompt=list(p), max_new_tokens=cfg.max_new_tokens)
            for p in prompts
        ])
        checked = 0
        for rid, want in enumerate(offline["outputs"]):
            got = records.get(rid)
            if got is None or got[2] not in ("eos", "length"):
                continue  # shed/cancelled streams have no parity claim
            if got[0] != want:
                raise AssertionError(
                    f"online stream rid={rid} diverged from offline "
                    f"serve_batch: {got[0]} vs {want}"
                )
            checked += 1
        report["parity_checked"] = checked
    return report
