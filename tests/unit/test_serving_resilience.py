"""Serving resilience: the degrade-don't-die acceptance contract.

- HEALTH MACHINE: healthy → degraded → draining → dead transitions are a
  pure function of the observation sequence; death counts land on the
  class-labeled failure counter exactly once.
- CHAOS PARITY: a deterministically injected replica death mid-stream is
  INVISIBLE in the tokens — every affected request recovers on a
  survivor token-for-token (greedy), the allocator identity holds on
  every surviving pool, compile-once survives recovery, and an identical
  chaos trace replays to the identical outcome.
- DEGRADED ROUTING: killing the entire prefill class collapses the
  disagg router to monolithic routing (zero wedged requests) and
  `restore()` flips it back.
- RETRY + ESCALATION: transient KV-transfer faults are absorbed by the
  deterministic-jitter retry budget; exhaustion escalates to the health
  board (re-prefill elsewhere), never into the serve loop.
- FOLLOWER LOSS: a plan-wire follower that stops reading surfaces as a
  NAMED `ReplicaFailure` within the bounded ack timeout.
- ROLLING RESTART: drain()/quiesce()/resume_admission() stop admission,
  flush residents, and reopen without dropping work.
"""

import asyncio
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.inference.generate import GenerateConfig, generate
from automodel_tpu.models.llm import decoder
from automodel_tpu.models.llm.decoder import TransformerConfig
from automodel_tpu.observability.metrics import MetricsRegistry
from automodel_tpu.resilience.faults import FaultError, FaultSpec, injected
from automodel_tpu.resilience.retry import RetryBudgetExhausted
from automodel_tpu.serving import (
    DisaggConfig,
    DisaggRouter,
    FrontendConfig,
    OnlineFrontend,
    OnlineRouter,
    PrefixCacheConfig,
    ReplicaFailure,
    ReplicaRouter,
    Request,
    ServeMeshConfig,
    ServeResilienceConfig,
    ServingConfig,
    ServingEngine,
)
from automodel_tpu.serving.plan_wire import KVStoreBroadcast
from automodel_tpu.serving.resilience import (
    DEAD,
    DEGRADED,
    DRAINING,
    HEALTHY,
    HealthBoard,
    ReplicaHealth,
    pool_identity_ok,
    transfer_with_retry,
)
from tests.serving_params import own

CFG = TransformerConfig(
    vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=2,
    num_heads=4, num_kv_heads=2, qk_norm=True, dtype=jnp.float32,
    remat_policy="none",
)
FAST = FrontendConfig(idle_sleep_s=0.0002)


@pytest.fixture(scope="module")
def params():
    return decoder.init(CFG, jax.random.key(0))


def _sc(**geo):
    base = dict(page_size=4, num_pages=24, max_slots=3, pages_per_slot=6,
                token_budget=8, prefill_chunk=4)
    base.update(geo)
    return ServingConfig(**base)


def _prompts(lens, vocab=64, seed0=0):
    return [
        [int(t) for t in np.random.default_rng(seed0 + i).integers(
            1, vocab, (l,))]
        for i, l in enumerate(lens)
    ]


def _reqs(prompts, max_new=6, arrivals=None):
    return [
        Request(prompt=list(p), max_new_tokens=max_new,
                arrival=(arrivals[i] if arrivals else 0))
        for i, p in enumerate(prompts)
    ]


def _ref(params, prompt, max_new):
    out = generate(
        params, CFG, jnp.asarray([prompt], jnp.int32), jax.random.key(0),
        GenerateConfig(max_new_tokens=max_new),
    )
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


# ---------------------------------------------------------------------------
# health state machine (pure, no engines)
# ---------------------------------------------------------------------------

def test_replica_health_transitions():
    h = ReplicaHealth("replica0", degraded_failures=2)
    assert h.state == HEALTHY and h.alive and h.admittable
    # exhaustion degrades first (still serving), then kills
    assert h.mark_exhausted(3, "transfer budget") == DEGRADED
    assert h.alive and h.admittable
    assert h.mark_exhausted(5, "transfer budget") == DEAD
    assert not h.alive and not h.admittable
    # dead is absorbing until restore
    assert h.mark_exhausted(6, "late") == DEAD
    assert h.restore() == HEALTHY and h.exhaustions == 0
    # rolling restart: draining is alive but not admittable
    assert h.mark_draining(7) == DRAINING
    assert h.alive and not h.admittable
    # a step error is one strike from any live state
    assert h.mark_dead(8, "step raised") == DEAD


def test_health_board_counts_each_death_once():
    reg = MetricsRegistry()
    board = HealthBoard(
        ["prefill0", "decode0", "decode1"],
        ServeResilienceConfig(degraded_failures=1), registry=reg,
    )
    assert board.snapshot() == {
        "prefill0": HEALTHY, "decode0": HEALTHY, "decode1": HEALTHY,
    }
    board.mark_dead("prefill0", 2, "boom")
    board.mark_dead("prefill0", 3, "boom again")  # already dead: no recount
    # degraded_failures=1 → a single exhaustion is also a death
    assert board.mark_exhausted("decode1", 4, "rotten link") == DEAD
    assert reg.counter(
        "serve_replica_failures_total", "", **{"class": "prefill"}
    ).value == 1.0
    assert reg.counter(
        "serve_replica_failures_total", "", **{"class": "decode"}
    ).value == 1.0
    assert board.n_dead() == 2 and board.alive("decode0")
    assert board.any_alive(["prefill0", "decode0"])


def test_transfer_retry_counts_attempts_and_exhausts_loudly():
    reg = MetricsRegistry()
    cfg = ServeResilienceConfig(
        transfer_retry_attempts=3,
        transfer_retry_base_delay_s=1e-4, transfer_retry_max_delay_s=1e-3,
    )
    calls = {"n": 0}

    def flaky(tag):
        calls["n"] += 1
        if calls["n"] < 3:
            raise FaultError(f"injected: {tag}")
        return tag

    assert transfer_with_retry(
        flaky, "ok", cfg=cfg, registry=reg, point="kv_transfer"
    ) == "ok"
    assert calls["n"] == 3
    retried = reg.counter(
        "serve_transfer_retries_total",
        "KV transfer / plan-wire send retry attempts",
    )
    assert retried.value == 2.0  # the two FAILED attempts

    def rotten():
        raise FaultError("injected: permanently down")

    with pytest.raises(RetryBudgetExhausted):
        transfer_with_retry(
            rotten, cfg=cfg, registry=reg, point="kv_transfer"
        )
    assert retried.value == 5.0


# ---------------------------------------------------------------------------
# offline chaos parity: replica death mid-batch
# ---------------------------------------------------------------------------

def _chaos_serve(params, prompts, arrivals, max_new):
    sc = _sc(prefix_cache=PrefixCacheConfig(enabled=True))
    router = ReplicaRouter(own(params), CFG, sc, ServeMeshConfig(replicas=2, tp=1))
    with injected(FaultSpec(point="serve_step_run.replica1", call=3)):
        res = router.serve_batch(_reqs(prompts, max_new, arrivals))
    return router, res


def test_replica_death_chaos_parity_offline(params):
    """Injected replica death mid-batch: every evacuated request requeues
    onto the survivor and finishes token-for-token identical to an
    undisturbed run; the surviving pool drains to the allocator identity
    and its step never recompiles. Replaying the identical chaos trace
    reproduces the identical outcome (deterministic recovery)."""
    prompts = _prompts([5, 9, 3, 7, 11, 4])
    arrivals = [0, 0, 1, 2, 3, 4]
    max_new = 6
    baseline = ServingEngine(own(params), CFG, _sc()).serve_batch(
        _reqs(prompts, max_new, arrivals)
    )

    router, res = _chaos_serve(params, prompts, arrivals, max_new)
    assert res["outputs"] == baseline["outputs"]
    assert all(r.finish_reason in ("eos", "length") for r in res["requests"])
    stats = res["stats"]
    assert stats["replica_health"]["replica1"] == DEAD
    assert stats["requests_recovered"] >= 1
    # compile-once on the survivor, through admission churn AND recovery
    assert stats["per_replica"][0]["compiled_signatures"] == 1
    # the class-labeled death counter fired exactly once
    assert router.obs.registry.counter(
        "serve_replica_failures_total", "", **{"class": "replica"}
    ).value == 1.0
    assert router.obs.registry.counter(
        "serve_requests_recovered_total", ""
    ).value == float(stats["requests_recovered"])

    # identical trace → identical recovery (fresh router, same fault)
    router2, res2 = _chaos_serve(params, prompts, arrivals, max_new)
    assert res2["outputs"] == res["outputs"]
    assert res2["stats"]["requests_recovered"] == stats["requests_recovered"]


def test_resilience_disabled_restores_fail_fast(params):
    router = ReplicaRouter(
        own(params), CFG, _sc(), ServeMeshConfig(replicas=2, tp=1),
        resilience=ServeResilienceConfig(enabled=False),
    )
    with injected(FaultSpec(point="serve_step_run.replica0", call=1)):
        with pytest.raises(FaultError):
            router.serve_batch(_reqs(_prompts([5, 7]), 4))


def test_last_replica_death_raises_named_failure(params):
    router = ReplicaRouter(own(params), CFG, _sc(), ServeMeshConfig(replicas=2,
                                                               tp=1))
    with injected(
        FaultSpec(point="serve_step_run.replica0", call=2),
        FaultSpec(point="serve_step_run.replica1", call=2),
    ):
        with pytest.raises(ReplicaFailure) as ei:
            router.serve_batch(_reqs(_prompts([5, 7, 6]), 6))
    assert ei.value.replica in ("replica0", "replica1")


# ---------------------------------------------------------------------------
# online chaos parity: live streams adopted across a death
# ---------------------------------------------------------------------------

def test_online_streams_survive_replica_death(params):
    """A replica death under LIVE streams: the dying frontend's residents
    are adopted by the survivor — the client keeps its TokenStream, the
    tokens are exactly the greedy continuation (never lost, never
    duplicated), and the stream ends with its NORMAL finish reason,
    `recovered` marking the detour."""
    sc = _sc(prefix_cache=PrefixCacheConfig(enabled=True))
    router = ReplicaRouter(own(params), CFG, sc, ServeMeshConfig(replicas=2,
                                                            tp=1))
    prompts = _prompts([5, 9, 3, 7])
    max_new = 8

    async def run():
        orouter = OnlineRouter(router, FAST).start()
        streams = []
        for p in prompts:
            s = orouter.submit(Request(prompt=list(p),
                                       max_new_tokens=max_new))
            streams.append(s)
            # let the chosen frontend pull the arrival into its scheduler
            # so the next route probes real occupancy (deterministic
            # spread over both replicas)
            fe = orouter.frontends[orouter._by_rid[s.rid]]
            while fe._arrivals.qsize():
                await asyncio.sleep(0)
        outs = await asyncio.gather(*(s.collect() for s in streams))
        stats = await orouter.close()
        return orouter, outs, stats, streams

    with injected(FaultSpec(point="serve_step_run.replica1", call=3)):
        orouter, outs, stats, streams = asyncio.run(run())

    for p, out in zip(prompts, outs):
        assert out == _ref(params, p, max_new)
    assert all(s.finish_reason == "length" for s in streams)
    assert stats["replica_health"]["replica1"] == DEAD
    assert stats["recovered"] >= 1
    assert sum(s.recovered for s in streams) >= 1
    assert stats["per_replica"][0]["compiled_signatures"] == 1
    # the survivor drained: every page free or prefix-cached
    assert pool_identity_ok(orouter.frontends[0].sched)


# ---------------------------------------------------------------------------
# disagg: degraded-mode routing + transfer retry escalation
# ---------------------------------------------------------------------------

def test_prefill_class_death_degrades_to_monolithic(params):
    """Killing the ENTIRE prefill class must not wedge the queue: the
    router collapses to monolithic routing (decode replicas take prefill
    chunks, requests complete in place), outputs stay token-identical,
    and restore() returns the router to disagg."""
    sc = _sc()
    prompts = _prompts([5, 9, 3, 7])
    max_new = 6
    baseline = ServingEngine(own(params), CFG, sc).serve_batch(
        _reqs(prompts, max_new)
    )
    router = DisaggRouter(
        own(params), CFG, sc,
        DisaggConfig(enabled=True, transfer_pages=4,
                     prefill_token_budget=16),
    )
    with injected(FaultSpec(point="serve_step_run.prefill0", call=1)):
        res = router.serve_batch(_reqs(prompts, max_new))
    assert res["outputs"] == baseline["outputs"]
    assert all(r.finish_reason in ("eos", "length") for r in res["requests"])
    stats = res["stats"]
    assert stats["degraded"] is True
    assert stats["replica_health"]["prefill0"] == DEAD
    assert stats["requests_recovered"] >= 1
    assert router.obs.registry.gauge(
        "serve_degraded_mode", ""
    ).value == 1.0
    # the slice came back: disagg routing resumes
    router.restore("prefill0")
    assert router.degraded is False
    res2 = router.serve_batch(_reqs(prompts, max_new))
    assert res2["outputs"] == baseline["outputs"]
    assert res2["stats"]["handoffs"] >= 1


def test_transfer_faults_absorbed_by_retry(params):
    """Two transient KV-transfer faults: the deterministic-jitter retry
    budget absorbs them (attempts counted), nothing escalates, parity
    holds."""
    sc = _sc()
    prompts = _prompts([5, 9, 3])
    max_new = 6
    baseline = ServingEngine(own(params), CFG, sc).serve_batch(
        _reqs(prompts, max_new)
    )
    router = DisaggRouter(
        own(params), CFG, sc,
        DisaggConfig(enabled=True, transfer_pages=4,
                     prefill_token_budget=16),
    )
    with injected(FaultSpec(point="kv_transfer", times=2)):
        res = router.serve_batch(_reqs(prompts, max_new))
    assert res["outputs"] == baseline["outputs"]
    assert res["stats"]["requests_recovered"] == 0
    assert res["stats"]["replica_health"] == {
        "prefill0": HEALTHY, "decode0": HEALTHY,
    }
    assert router.obs.registry.counter(
        "serve_transfer_retries_total", ""
    ).value >= 2.0


def test_transfer_exhaustion_escalates_to_reprefill(params):
    """Retry budget exhausted on a handoff: the decode replica degrades
    (not dead — its step is fine), the admission rolls back with pins
    dropped, and the request re-prefills from scratch — still finishing
    token-identical."""
    sc = _sc()
    prompts = _prompts([5, 9, 3])
    max_new = 6
    baseline = ServingEngine(own(params), CFG, sc).serve_batch(
        _reqs(prompts, max_new)
    )
    router = DisaggRouter(
        own(params), CFG, sc,
        DisaggConfig(enabled=True, transfer_pages=4,
                     prefill_token_budget=16),
        resilience=ServeResilienceConfig(
            transfer_retry_attempts=2,
            transfer_retry_base_delay_s=1e-4,
            transfer_retry_max_delay_s=1e-3,
        ),
    )
    # 3 faults / 2 attempts per budget: the first handoff exhausts its
    # budget (2 failures → escalate), the re-prefilled handoff eats the
    # third fault and succeeds on retry
    with injected(FaultSpec(point="kv_transfer", times=3)):
        res = router.serve_batch(_reqs(prompts, max_new))
    assert res["outputs"] == baseline["outputs"]
    stats = res["stats"]
    assert stats["requests_recovered"] >= 1
    assert stats["replica_health"]["decode0"] == DEGRADED
    assert stats["degraded"] is False  # prefill class is intact
    assert router.obs.registry.counter(
        "serve_requests_recovered_total", ""
    ).value >= 1.0


# ---------------------------------------------------------------------------
# rolling restart: drain / quiesce / resume
# ---------------------------------------------------------------------------

def test_drain_quiesce_resume_admission(params):
    """drain() stops ADMISSION while residents finish; quiesce() returns
    only once nothing is resident; resume_admission() reopens — no work
    dropped anywhere."""
    engine = ServingEngine(own(params), CFG, _sc())
    prompts = _prompts([5, 9, 4])

    async def run():
        fe = OnlineFrontend(engine, FAST).start()
        live = [fe.submit(Request(prompt=list(p), max_new_tokens=6))
                for p in prompts[:2]]
        consumers = [asyncio.ensure_future(s.collect()) for s in live]
        await fe.wait_step(2)
        fe.drain()
        shed = fe.submit(Request(prompt=list(prompts[2]), max_new_tokens=6))
        shed_out = await shed.collect()
        await fe.quiesce()
        assert not fe.sched.has_work
        fe.resume_admission()
        late = fe.submit(Request(prompt=list(prompts[2]), max_new_tokens=6))
        late_out = await late.collect()
        outs = [await c for c in consumers]
        stats = await fe.close()
        return fe, outs, shed, shed_out, late, late_out, stats

    fe, outs, shed, shed_out, late, late_out, stats = asyncio.run(run())
    for p, out in zip(prompts[:2], outs):
        assert out == _ref(params, p, 6)
    assert shed.finish_reason == "shed" and shed_out == []
    assert late.finish_reason == "length"
    assert late_out == _ref(params, prompts[2], 6)
    assert stats["finished"] == 2 + 1 + 1  # 2 drained + 1 shed + 1 late
    assert stats["finish_reasons"]["shed"] == 1
    assert stats["draining"] is False
    assert pool_identity_ok(fe.sched)


# ---------------------------------------------------------------------------
# mid-recovery shed arithmetic (the deadline-accounting bugfix)
# ---------------------------------------------------------------------------

def test_recovery_backlog_prices_reprefill_into_shedding(params):
    """An adopted-but-not-yet-queued request re-prefills its whole
    `known`; admission arithmetic must count that backlog. The old
    formula (device + waiting only) admitted deadline-doomed work
    mid-recovery — this pins the corrected term."""
    engine = ServingEngine(own(params), CFG, _sc())
    fe = OnlineFrontend(engine, FAST)  # never started: pure arithmetic
    big = Request(prompt=list(range(1, 41)), max_new_tokens=4)  # 40 to re-feed
    fe._adopted.append((big, None, 0))
    assert fe._recovery_backlog() == 40

    probe = Request(prompt=list(range(1, 9)), max_new_tokens=4)  # 8 pending
    probe.deadline = fe.step_idx + 4
    base = fe._backlog(fe.sched) + fe._waiting_backlog(fe.sched)
    # without the recovery term the request looks easily reachable...
    assert fe._reachable(probe, base, fe.sched) is True
    # ...but the 40-token re-prefill ahead of it makes the deadline
    # unreachable — the fixed formula sheds it at the door
    assert fe._reachable(
        probe, base + fe._recovery_backlog(), fe.sched) is False


# ---------------------------------------------------------------------------
# plan-wire follower loss: bounded-timeout acks
# ---------------------------------------------------------------------------

class _FakeCoordClient:
    """Hermetic stand-in for the jax.distributed coordination KV store:
    blocking gets honor the timeout against a condition variable."""

    def __init__(self):
        self._kv: dict = {}
        self._cond = threading.Condition()

    def key_value_set_bytes(self, k, b):
        with self._cond:
            self._kv[k] = bytes(b)
            self._cond.notify_all()

    def key_value_delete(self, k):
        with self._cond:
            self._kv.pop(k, None)

    def blocking_key_value_get_bytes(self, k, timeout_ms):
        deadline = time.monotonic() + timeout_ms / 1e3
        with self._cond:
            while k not in self._kv:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(f"no key {k} within {timeout_ms}ms")
                self._cond.wait(left)
            return self._kv[k]

    def keys(self):
        with self._cond:
            return set(self._kv)


def test_plan_wire_acks_roundtrip_with_live_follower():
    kv = _FakeCoordClient()
    lead = KVStoreBroadcast(6, True, client=kv, ack_every=2,
                            ack_timeout_ms=2_000, num_followers=1)
    follower = KVStoreBroadcast(6, False, client=kv, ack_every=2,
                                follower_id=1)
    bufs = [np.full(6, i, np.int32) for i in range(4)]
    got = []

    def consume():
        for _ in bufs:
            got.append(follower.recv())

    t = threading.Thread(target=consume)
    t.start()
    for b in bufs:  # acks due after seq 1 and seq 3; both arrive in time
        lead.send(b)
    t.join(timeout=10)
    assert not t.is_alive()
    assert [list(g) for g in got] == [list(b) for b in bufs]
    # the follower acked on receipt at every ack-due frame
    assert "planwire/ack/1/1" in kv.keys()
    assert "planwire/ack/1/3" in kv.keys()


def test_plan_wire_dead_follower_surfaces_as_named_failure():
    kv = _FakeCoordClient()
    lead = KVStoreBroadcast(6, True, client=kv, ack_every=2,
                            ack_timeout_ms=30, num_followers=1)
    lead.send(np.zeros(6, np.int32))  # seq 0: no ack due yet
    with pytest.raises(ReplicaFailure) as ei:
        lead.send(np.ones(6, np.int32))  # seq 1: ack due, nobody home
    assert ei.value.replica == "follower1"
    assert "seq 1" in ei.value.reason


def test_plan_wire_acks_disabled_never_blocks():
    kv = _FakeCoordClient()
    lead = KVStoreBroadcast(4, True, client=kv, ack_every=0,
                            num_followers=1)
    for i in range(6):
        lead.send(np.full(4, i, np.int32))
    assert not any(k.startswith("planwire/ack") for k in kv.keys())
