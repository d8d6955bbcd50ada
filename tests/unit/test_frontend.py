"""Online serving frontend: the live-traffic acceptance contract.

- STREAMS: tokens arrive per request in commit order, and the async
  loop's admission churn is invisible — greedy outputs are token-for-
  token identical to the offline `serve_batch` / `generate()` paths,
  with the step still compiling ONCE.
- BACKPRESSURE: a consumer that stops reading pauses only its own slot
  (bounded stream queue); everyone else keeps streaming.
- SHEDDING: deadline-aware admission control is pure step arithmetic —
  identical traces shed identical request sets.
- CANCELLATION: cancel storms mid-flight leak nothing — the allocator
  identity free + prefix-cached == total holds afterwards, including
  the disaggregated in-flight-handoff pin path.
- ADAPTIVE SPECULATION: per-request acceptance EWMA collapses the draft
  length to plain decode under zero acceptance, without touching parity.
- AUTOSCALER: the queue-imbalance policy fires with hysteresis and the
  router's borrow/return bookkeeping respects min_decode.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.inference.generate import GenerateConfig, generate
from automodel_tpu.models.llm import decoder
from automodel_tpu.models.llm.decoder import TransformerConfig
from automodel_tpu.observability import ObservabilityConfig
from automodel_tpu.serving import (
    AutoscaleConfig,
    DisaggConfig,
    DisaggOnlineFrontend,
    DisaggRouter,
    FrontendConfig,
    OnlineFrontend,
    PrefixCacheConfig,
    QueueAutoscaler,
    Request,
    ServingConfig,
    ServingEngine,
    SpeculativeConfig,
)
from automodel_tpu.serving.load_test import LoadTestConfig, run_load_test
from automodel_tpu.speculative.serve_draft import DraftSource
from tests.serving_params import own

CFG = TransformerConfig(
    vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=2,
    num_heads=4, num_kv_heads=2, qk_norm=True, dtype=jnp.float32,
    remat_policy="none",
)
FAST = FrontendConfig(idle_sleep_s=0.0002)


def _params():
    return decoder.init(CFG, jax.random.key(0))


def _serve_cfg(**geo):
    base = dict(page_size=4, num_pages=24, max_slots=3, pages_per_slot=6,
                token_budget=8, prefill_chunk=4)
    base.update(geo)
    return ServingConfig(**base)


def _engine(params, **geo):
    return ServingEngine(own(params), CFG, _serve_cfg(**geo))


def _frontend(kind, params, cfg=FAST, **geo):
    """The client side is one class under both frontends: "online" is an
    `OnlineFrontend` over one engine, "disagg" a `DisaggOnlineFrontend`
    over a prefill and a decode replica of the same geometry."""
    if kind == "online":
        return OnlineFrontend(_engine(params, **geo), cfg)
    return DisaggOnlineFrontend(
        DisaggRouter(
            own(params), CFG, _serve_cfg(**geo),
            DisaggConfig(enabled=True, prefill_replicas=1, decode_replicas=1),
        ),
        cfg,
    )


def _scheds(fe):
    if isinstance(fe, OnlineFrontend):
        return [fe.sched]
    return fe.p_scheds + fe.d_scheds


def _shed_count(fe, why):
    return fe.obs.registry.snapshot().get(
        f'frontend_shed_total{{reason="{why}"}}', 0)


BOTH_FRONTENDS = pytest.mark.parametrize("kind", ["online", "disagg"])


def _prompts(lens, vocab=64, seed0=0):
    return [
        [int(t) for t in np.random.default_rng(seed0 + i).integers(
            1, vocab, (l,))]
        for i, l in enumerate(lens)
    ]


def _ref(params, prompt, max_new):
    out = generate(
        params, CFG, jnp.asarray([prompt], jnp.int32), jax.random.key(0),
        GenerateConfig(max_new_tokens=max_new),
    )
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


# ---------------------------------------------------------------------------
# streaming: ordering + parity + compile-once
# ---------------------------------------------------------------------------

def test_streams_match_generate_and_compile_once():
    """Staggered live submissions through the async loop: every stream
    yields exactly the greedy `generate()` continuation, in order, and
    the engine step compiled once despite mid-flight admission."""
    params = _params()
    engine = _engine(params)
    prompts = _prompts([5, 9, 3, 7, 11])

    async def run():
        fe = OnlineFrontend(engine, FAST).start()
        streams = []
        for i, p in enumerate(prompts):
            if i >= 2:
                await fe.wait_step(i + 2)  # genuinely mid-flight
            streams.append(fe.submit(Request(prompt=list(p),
                                             max_new_tokens=6)))
        outs = await asyncio.gather(*(s.collect() for s in streams))
        stats = await fe.close()
        return outs, stats, streams

    outs, stats, streams = asyncio.run(run())
    for p, out in zip(prompts, outs):
        assert out == _ref(params, p, 6)
    assert all(s.finish_reason == "length" for s in streams)
    assert stats["compiled_signatures"] == 1
    assert stats["finished"] == 5 and stats["shed"] == 0


def test_load_test_harness_parity_under_sustained_load():
    """The load harness end to end on one replica: a paced many-request
    trace, all streams consumed concurrently, greedy parity re-checked
    offline, latency percentiles populated."""
    params = _params()
    engine = _engine(params, num_pages=96, max_slots=8, token_budget=16,
                     prefill_chunk=8)
    rep = run_load_test(
        engine,
        LoadTestConfig(num_requests=60, parity_check=20,
                       mean_interarrival_steps=0.3, seed=3),
        FAST,
    )
    assert rep["completed"] == 60 and rep["shed"] == 0
    assert rep["parity_checked"] == 20
    assert rep["ttft_p99_ms"] is not None and rep["itl_p99_ms"] is not None
    assert rep["frontend"]["compiled_signatures"] == 1


# ---------------------------------------------------------------------------
# backpressure
# ---------------------------------------------------------------------------

@BOTH_FRONTENDS
def test_slow_consumer_pauses_only_its_own_stream(kind):
    """One consumer stops reading: its stream queue stays bounded by
    stream_buffer (the slot is withheld from plans), the OTHER requests
    run to completion meanwhile, and once the stalled consumer resumes
    it still receives its full, correct continuation."""
    params = _params()
    cfg = dataclasses.replace(FAST, stream_buffer=4)
    prompts = _prompts([4, 6, 5])

    async def run():
        fe = _frontend(kind, params, cfg, num_pages=48, max_slots=3,
                       pages_per_slot=12).start()
        slow = fe.submit(Request(prompt=list(prompts[0]),
                                 max_new_tokens=24))
        fast = [
            fe.submit(Request(prompt=list(p), max_new_tokens=24))
            for p in prompts[1:]
        ]
        # consume only the fast streams; the slow one is never read
        fast_outs = await asyncio.gather(*(s.collect() for s in fast))
        lag_while_stalled = slow._lag()
        paused = set().union(*(sched.paused for sched in _scheds(fe)))
        # resume the stalled consumer: it must still get everything
        slow_out = await slow.collect()
        await fe.close()
        return fast_outs, slow_out, lag_while_stalled, paused

    fast_outs, slow_out, lag, paused = asyncio.run(run())
    for p, out in zip(prompts[1:], fast_outs):
        assert out == _ref(params, p, 24)  # fast streams never stalled
    assert slow_out == _ref(params, prompts[0], 24)
    # bounded: buffer + at most one worst-case commit was ever queued
    assert lag <= 4
    assert paused, "the unread stream's slot should have been withheld"


# ---------------------------------------------------------------------------
# load shedding
# ---------------------------------------------------------------------------

def _shed_trace(kind, params):
    """Overload a tiny engine with tight-deadline arrivals; return the
    per-rid finish reasons."""
    prompts = _prompts([8, 8, 8, 8, 8, 8], seed0=11)

    async def run():
        fe = _frontend(kind, params, num_pages=16, max_slots=2,
                       pages_per_slot=8, token_budget=4,
                       prefill_chunk=4).start()
        streams = [
            fe.submit(Request(prompt=list(p), max_new_tokens=4),
                      deadline_in=9)
            for p in prompts
        ]
        await asyncio.gather(*(s.collect() for s in streams))
        stats = await fe.close()
        return {s.rid: s.finish_reason for s in streams}, stats, fe

    return asyncio.run(run())


@BOTH_FRONTENDS
def test_deadline_shedding_is_deterministic(kind):
    """Six 8-token prompts with a 9-step deadline through a 4-token/step
    engine: the backlog makes the tail provably unreachable, so it sheds
    AT ADMISSION — and because the decision is pure step arithmetic, an
    identical trace sheds the identical rid set."""
    params = _params()
    reasons_a, stats_a, fe = _shed_trace(kind, params)
    reasons_b, stats_b, _fe = _shed_trace(kind, params)
    assert reasons_a == reasons_b  # deterministic across runs
    shed = {r for r, why in reasons_a.items() if why == "shed"}
    done = {r for r, why in reasons_a.items() if why in ("eos", "length")}
    assert shed and done, f"want a mix under overload, got {reasons_a}"
    assert stats_a["shed"] == len(shed) == stats_b["shed"]
    assert _shed_count(fe, "deadline") == len(shed)
    # shed requests never occupied pool pages
    assert all(sched.alloc.num_free == 16 for sched in _scheds(fe))


@BOTH_FRONTENDS
def test_full_queue_sheds_at_the_door(kind):
    """`max_waiting` caps the queue an arrival would join: five arrivals
    in one turn against a cap of two admit two and shed three as
    "queue_full", whatever their deadlines; the admitted keep parity."""
    params = _params()
    cfg = dataclasses.replace(FAST, max_waiting=2)
    prompts = _prompts([5, 6, 4, 7, 5], seed0=17)

    async def run():
        fe = _frontend(kind, params, cfg).start()
        streams = [fe.submit(Request(prompt=list(p), max_new_tokens=3))
                   for p in prompts]
        outs = await asyncio.gather(*(s.collect() for s in streams))
        return fe, streams, outs, await fe.close()

    fe, streams, outs, stats = asyncio.run(run())
    assert [s.finish_reason for s in streams] == ["length"] * 2 + ["shed"] * 3
    assert outs[2:] == [[], [], []]
    for p, out in zip(prompts[:2], outs):
        assert out == _ref(params, p, 3)
    assert stats["shed"] == 3 == _shed_count(fe, "queue_full")
    assert stats["finished"] == 5 and stats["finish_reasons"]["shed"] == 3


@BOTH_FRONTENDS
def test_closed_frontend_sheds_what_it_had_not_admitted(kind):
    """An arrival still in the queue when `close()` is called was never
    admitted: it sheds as "closed" on the loop's last turn (its stream
    ends, nothing hangs), and a later `submit` raises."""
    params = _params()

    async def run():
        fe = _frontend(kind, params).start()
        stream = fe.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
        stats = await fe.close()  # before the loop's first turn
        with pytest.raises(RuntimeError, match="closed"):
            fe.submit(Request(prompt=[4, 5], max_new_tokens=1))
        return fe, stream, await stream.collect(), stats

    fe, stream, out, stats = asyncio.run(run())
    assert stream.finish_reason == "shed" and out == []
    assert stats["shed"] == 1 == _shed_count(fe, "closed")
    assert stats["steps"] == 0 and stats["running"] == stats["waiting"] == 0


def test_no_deadline_means_no_shedding():
    params = _params()
    engine = _engine(params, num_pages=16, max_slots=2, pages_per_slot=8,
                     token_budget=4, prefill_chunk=4)
    prompts = _prompts([8, 8, 8, 8], seed0=5)

    async def run():
        async with OnlineFrontend(engine, FAST) as fe:
            streams = [
                fe.submit(Request(prompt=list(p), max_new_tokens=3))
                for p in prompts
            ]
            outs = await asyncio.gather(*(s.collect() for s in streams))
        return outs

    outs = asyncio.run(run())
    for p, out in zip(prompts, outs):
        assert out == _ref(params, p, 3)


@BOTH_FRONTENDS
def test_itl_is_the_period_between_committing_turns(kind):
    """`request_itl_ms` observes once a turn that committed tokens the wall
    time since the last such turn, whatever the tokens a turn commits (not
    a step's time over its tokens), and an idle turn starts the count anew."""
    import time

    params = _params()
    fe = _frontend(kind, params)

    async def run():
        fe.start()
        out = await fe.submit(Request(prompt=[1, 2, 3], max_new_tokens=6)).collect()
        await fe.close()
        return out

    assert len(asyncio.run(run())) == 6
    itl = fe.obs.registry.snapshot()["request_itl_ms"]
    # six tokens, one a turn: five periods between them on one engine; the
    # disaggregated loop has a hand-off turn that commits nothing
    assert itl["count"] == 5 if kind == "online" else 1 <= itl["count"] <= 5
    assert "itl_ewma_ms" not in fe.stats()
    before = itl["count"], itl["sum"]
    fe._note_itl(3)
    time.sleep(0.02)
    fe._note_itl(3)
    fe._note_itl(0)             # a turn that committed nothing: no period
    asyncio.run(fe._idle_turn())
    fe._note_itl(1)             # the first after an idle turn: no period
    itl = fe.obs.registry.snapshot()["request_itl_ms"]
    assert itl["count"] == before[0] + 1
    assert itl["sum"] - before[1] >= 20.0


# ---------------------------------------------------------------------------
# /metrics endpoint
# ---------------------------------------------------------------------------

@BOTH_FRONTENDS
def test_metrics_endpoint_serves_the_registry(kind):
    """`observability.http_port` (0: any free port) puts /metrics and
    /healthz on the serve loop of either frontend: the registry's
    Prometheus text with the frontend's gauges refreshed, and liveness."""
    params = _params()

    async def get(addr, path):
        reader, writer = await asyncio.open_connection(*addr)
        writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
        await writer.drain()
        reply = await reader.read()
        writer.close()
        head, _, body = reply.partition(b"\r\n\r\n")
        return head.split(b"\r\n")[0], body.decode()

    async def run():
        fe = _frontend(
            kind, params,
            observability=ObservabilityConfig(http_port=0)).start()
        addr = await fe.http_address()
        out = await fe.submit(
            Request(prompt=[1, 2, 3], max_new_tokens=3)).collect()
        replies = [await get(addr, p)
                   for p in ("/metrics", "/healthz", "/nowhere")]
        await fe.close()
        return out, replies

    out, (metrics, health, nowhere) = asyncio.run(run())
    assert len(out) == 3
    assert metrics[0] == b"HTTP/1.1 200 OK"
    assert "frontend_submitted_total 1" in metrics[1]
    assert "frontend_running 0" in metrics[1]
    assert health == (b"HTTP/1.1 200 OK", "ok\n")
    assert nowhere[0] == b"HTTP/1.1 404 Not Found"


# ---------------------------------------------------------------------------
# cancellation
# ---------------------------------------------------------------------------

@BOTH_FRONTENDS
def test_cancel_storm_leaks_no_pages(kind):
    """Cancel most of a live wave mid-generation (running AND queued):
    every cancelled stream terminates with reason "cancelled", survivors
    finish with parity, and afterwards every page is either free or held
    by the prefix cache: free + cached == total."""
    params = _params()
    prompts = _prompts([6, 7, 5, 9, 4, 8, 6, 7], seed0=23)

    async def run():
        fe = _frontend(
            kind, params, num_pages=40, max_slots=3, pages_per_slot=8,
            prefix_cache=PrefixCacheConfig(enabled=True)).start()
        streams = [
            fe.submit(Request(prompt=list(p), max_new_tokens=20))
            for p in prompts
        ]
        await fe.wait_step(4)  # storm lands mid-generation
        for s in streams[2:]:
            fe.cancel(s.rid)
        keep = await asyncio.gather(*(s.collect() for s in streams[:2]))
        rest = await asyncio.gather(*(s.collect() for s in streams[2:]))
        stats = await fe.close()
        return fe, keep, rest, stats, streams

    fe, keep, rest, stats, streams = asyncio.run(run())
    for p, out in zip(prompts[:2], keep):
        assert out == _ref(params, p, 20)
    assert all(s.finish_reason == "cancelled" for s in streams[2:])
    assert stats["cancelled"] == 6
    for sched in _scheds(fe):
        assert sched.alloc.num_free + sched.prefix.cached_pages == 40
    assert {v for k, v in stats.items()
            if k.startswith("compiled_signatures")} == {1}


@BOTH_FRONTENDS
def test_cancel_unknown_rid_is_noop(kind):
    params = _params()

    async def run():
        async with _frontend(kind, params) as fe:
            s = fe.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
            fe.cancel(999)  # never submitted: must not disturb anything
            return await s.collect()

    assert len(asyncio.run(run())) == 2


def test_disagg_cancel_releases_inflight_handoff_pins():
    """THE regression: cancelling a request whose KV handoff is IN FLIGHT
    (extracted from prefill, not yet admitted by decode) must drop the
    prefill-side page pins the same turn. Starve the decode class so
    handoffs pile up in flight, cancel them there, then drain — every
    replica's pool must return to free + cached == total."""
    params = _params()
    router = DisaggRouter(
        own(params), CFG,
        ServingConfig(page_size=4, num_pages=16, max_slots=2,
                      pages_per_slot=4, token_budget=8, prefill_chunk=8),
        DisaggConfig(enabled=True, prefill_replicas=1, decode_replicas=1),
    )

    async def run():
        fe = DisaggOnlineFrontend(router, FAST).start()
        streams = [
            fe.submit(Request(prompt=list(p), max_new_tokens=8))
            for p in _prompts([6, 6, 6, 6, 5, 7], seed0=31)
        ]
        # wait for the decode class to saturate and handoffs to queue
        for _ in range(4000):
            if fe.inflight:
                break
            await asyncio.sleep(0.001)
        assert fe.inflight, "decode starvation should strand handoffs"
        stranded = [h.req.rid for h in fe.inflight]
        for rid in stranded:
            fe.cancel(rid)
        for s in streams:
            if s.rid not in stranded:
                fe.cancel(s.rid)
        await asyncio.gather(*(s.collect() for s in streams))
        stats = await fe.close()
        return fe, stats, streams

    fe, stats, streams = asyncio.run(run())
    assert stats["cancelled_inflight"] >= 1
    assert stats["inflight_handoffs"] == 0
    assert all(s.finish_reason == "cancelled" for s in streams)
    for sched in fe.p_scheds + fe.d_scheds:
        cached = sched.prefix.cached_pages if sched.prefix is not None else 0
        assert sched.alloc.num_free + cached == 16, (
            "handoff pins leaked pages"
        )


# ---------------------------------------------------------------------------
# adaptive speculative draft length
# ---------------------------------------------------------------------------

class _AlwaysWrongDraft(DraftSource):
    """Drafts a token guaranteed != the greedy target at every position
    (t -> (t % (V-1)) + 1 never maps to itself for t in [0, V-1]), by
    cheating from the precomputed reference continuation."""

    def __init__(self, refs: dict):
        self.refs = refs  # rid -> full greedy continuation

    def draft(self, req, k: int) -> list:
        ref = self.refs[req.rid]
        g = len(req.generated)
        out = []
        for i in range(k):
            t = ref[g + i] if g + i < len(ref) else 1
            out.append((t % (CFG.vocab_size - 1)) + 1)
        return out


def test_adaptive_draft_len_collapses_to_plain_decode():
    """Zero acceptance: the per-request EWMA (decay 0.5, threshold 0.5)
    walks 1.0 -> 0.5 -> 0.25 -> 0.125, capping K at 4, 4, 1, 0 — so a
    hopeless drafter costs exactly 9 drafted tokens per request and then
    the slot IS a plain decode slot (and parity is untouched). The fixed
    -K engine keeps paying for the full run."""
    params = _params()
    prompts = _prompts([5, 7], seed0=41)
    max_new = 16
    refs = {i: _ref(params, p, max_new) for i, p in enumerate(prompts)}
    # budget 16: both decode slots always fit a full K=4 block, so the
    # collapse arithmetic below is exact (a tighter budget would clip
    # blocks and merely slow the decay)
    geo = dict(page_size=4, num_pages=32, max_slots=2, pages_per_slot=8,
               token_budget=16, prefill_chunk=4)

    def serve(adaptive):
        spec = SpeculativeConfig(
            enabled=True, draft_len=4, adaptive=adaptive,
            adaptive_threshold=0.5, adaptive_decay=0.5,
        )
        engine = ServingEngine(
            own(params), CFG, ServingConfig(**geo, speculative=spec),
            draft_source=_AlwaysWrongDraft(refs),
        )
        reqs = [
            Request(prompt=list(p), max_new_tokens=max_new, rid=i)
            for i, p in enumerate(prompts)
        ]
        return engine.serve_batch(reqs)

    adap = serve(adaptive=True)
    fixed = serve(adaptive=False)
    for res in (adap, fixed):
        assert res["stats"]["accepted_tokens"] == 0
        for i, p in enumerate(prompts):
            assert res["outputs"][i] == refs[i]  # parity regardless
    # collapse: 4 + 4 + 1 drafted tokens per request, then plain decode
    assert adap["stats"]["drafted_tokens"] == 9 * len(prompts)
    assert fixed["stats"]["drafted_tokens"] > adap["stats"]["drafted_tokens"]
    for req in adap["requests"]:
        assert req.spec_ewma == pytest.approx(0.125)


# ---------------------------------------------------------------------------
# autoscaler
# ---------------------------------------------------------------------------

def test_queue_autoscaler_hysteresis():
    cfg = AutoscaleConfig(enabled=True, grow_ratio=4.0, shrink_ratio=1.0,
                          sustain=3, cooldown=10, min_decode=1)
    pol = QueueAutoscaler(cfg)
    # two imbalanced turns: below sustain, no action
    assert pol.observe(40, 2, 0) is None
    assert pol.observe(40, 2, 1) is None
    # third consecutive -> grow
    assert pol.observe(40, 2, 2) == "grow"
    # still imbalanced but inside cooldown -> quiet
    for t in range(3, 12):
        assert pol.observe(40, 2, t) is None
    # cooldown over and the streak held -> grow again
    assert pol.observe(40, 2, 12) == "grow"
    # a single balanced turn resets the shrink streak too
    assert pol.observe(1, 5, 23) is None
    assert pol.observe(30, 2, 24) is None  # grow streak restarted at 1
    # sustained balance -> shrink (after its own sustain + cooldown)
    for t in range(25, 27):
        assert pol.observe(0, 5, t) is None
    assert pol.observe(0, 5, 27) == "shrink"


class _FakeAlloc:
    def __init__(self, free):
        self.num_free = free


class _FakeSched:
    def __init__(self, waiting=0, running=0, free=10):
        self.waiting = [None] * waiting
        self.running = {i: None for i in range(running)}
        self.alloc = _FakeAlloc(free)


def test_disagg_router_borrow_and_return_bookkeeping():
    """autoscale_tick on a shell router: sustained prefill overload
    borrows the freest decode replica (never below min_decode dedicated),
    sustained balance returns the most recent borrow."""
    router = object.__new__(DisaggRouter)
    router.disagg = DisaggConfig(
        enabled=True, prefill_replicas=1, decode_replicas=3,
        autoscale=AutoscaleConfig(enabled=True, sustain=2, cooldown=0,
                                  min_decode=2),
    )
    router.autoscaler = QueueAutoscaler(router.disagg.autoscale)
    router.borrowed = set()
    router.decode = [None] * 3
    router.n_borrows = router.n_returns = 0

    p = [_FakeSched(waiting=30)]
    d = [_FakeSched(free=4), _FakeSched(free=9), _FakeSched(free=6)]
    assert router.autoscale_tick(p, d, 0) is None
    assert router.autoscale_tick(p, d, 1) == "grow"
    assert router.borrowed == {1}  # the freest decode replica
    # next grow would dip below min_decode=2 dedicated -> refused
    assert router.autoscale_tick(p, d, 2) is None
    assert router.autoscale_tick(p, d, 3) is None
    assert router.borrowed == {1} and router.n_borrows == 1
    # balance restored -> the borrow comes back
    q = [_FakeSched(waiting=0)]
    assert router.autoscale_tick(q, d, 4) is None
    assert router.autoscale_tick(q, d, 5) == "shrink"
    assert router.borrowed == set() and router.n_returns == 1


def test_disagg_autoscale_borrowed_replica_serves_prefill():
    """End to end with engines: force a borrow, then verify arrivals
    routed to the borrowed decode replica prefill there, hand off with
    the rids guard (its own decode work untouched), and parity holds."""
    params = _params()
    router = DisaggRouter(
        own(params), CFG,
        ServingConfig(page_size=4, num_pages=32, max_slots=2,
                      pages_per_slot=6, token_budget=8, prefill_chunk=4),
        DisaggConfig(
            enabled=True, prefill_replicas=1, decode_replicas=2,
            autoscale=AutoscaleConfig(enabled=True, grow_ratio=2.0,
                                      sustain=1, cooldown=0, min_decode=1),
        ),
    )
    prompts = _prompts([5, 6, 4, 7, 5, 6, 4, 5], seed0=53)

    async def run():
        fe = DisaggOnlineFrontend(router, FAST).start()
        # first wave overloads the single prefill replica -> borrow fires
        streams = [
            fe.submit(Request(prompt=list(p), max_new_tokens=4))
            for p in prompts[:6]
        ]
        await fe.wait_step(3)
        # second wave arrives while borrowed: routes to the (empty)
        # borrowed decode replica, prefills there, hands off under the
        # rids guard
        streams += [
            fe.submit(Request(prompt=list(p), max_new_tokens=4))
            for p in prompts[6:]
        ]
        outs = await asyncio.gather(*(s.collect() for s in streams))
        stats = await fe.close()
        return outs, stats

    outs, stats = asyncio.run(run())
    for p, out in zip(prompts, outs):
        assert out == _ref(params, p, 4)
    assert stats["autoscale_borrows"] >= 1
    # compile-once per replica class survives the routing-set change
    assert stats["compiled_signatures_prefill"] == 1
    assert stats["compiled_signatures_decode"] == 1
