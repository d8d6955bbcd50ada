"""Data-parallel serving tier: N sharded engine replicas behind a router.

The pod-scale layer of the serving stack (the Gemma-on-TPU serving study,
PAPERS.md, is the comparison target): one `ServingEngine` shards its jitted
step over tp/ep inside a mesh SLICE, and the `ReplicaRouter` replicates
that engine across `replicas` disjoint slices — the same `llm_serve`
recipe scales from one chip to a pod by changing `serving.mesh` in YAML:

    serving:
      mesh: {replicas: 2, tp: 2, ep: 1}     # dp2 x tp2 over 4 chips

Routing is PER-REQUEST ADMISSION, decided once when a request arrives
(requests never migrate — their KV pages live on one slice's pool):

- sticky on prefix-cache affinity: each replica's scheduler is probed for
  the longest cached prefix of the request (`Scheduler.prefix_hit_tokens`);
  the best non-zero match wins, so agent loops and shared-system-prompt
  traffic keep landing where their pages already are instead of diluting
  the radix tree across replicas;
- otherwise least-loaded-by-free-pages: the replica whose pool has the
  most free pages (ties → fewest resident requests, then lowest index).
  Free pages are the honest load signal — they bound both admission and
  preemption churn, which is what actually moves tail latency.

The router owns NO device state: it holds one scheduler per replica and
drives them in lockstep engine steps (an offline analog of N independent
serve loops; an online frontend would run one thread per replica). Every
replica keeps its own compile-once contract — `serve_batch` reports the
jit cache-miss counter per replica plus balance stats (requests/tokens per
replica, per-replica p50/p95 ms per committed token).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from automodel_tpu.observability import Observability
from automodel_tpu.resilience.faults import FaultError
from automodel_tpu.serving.engine import (
    ServingConfig,
    ServingEngine,
    _percentiles_ms,
    _resolve_ttft,
    split_layer_stacks,
)
from automodel_tpu.serving.frontend import (
    FrontendConfig,
    OnlineFrontend,
    TokenStream,
)
from automodel_tpu.serving.kv_transfer import KVTransfer, refuse_state_handoff
from automodel_tpu.serving.resilience import (
    HealthBoard,
    ReplicaFailure,
    RetryBudgetExhausted,
    ServeResilienceConfig,
    pool_identity_ok,
    transfer_with_retry,
)
from automodel_tpu.serving.scheduler import Request


@dataclasses.dataclass(frozen=True)
class ServeMeshConfig:
    """Typed `serving.mesh` section: the pod topology of a serving run.

    `replicas` data-parallel engine replicas, each over a `tp * ep`-chip
    mesh slice (tp shards attention/MLP/pool heads, ep shards expert
    dispatch for MoE decoders). replicas=tp=ep=1 is the single-chip
    engine on a trivial 1x1 mesh — the SAME code path end to end."""

    replicas: int = 1
    tp: int = 1
    ep: int = 1

    def __post_init__(self):
        if self.replicas < 1 or self.tp < 1 or self.ep < 1:
            raise ValueError(f"mesh sizes must be >= 1: {self}")

    @property
    def chips_per_replica(self) -> int:
        return self.tp * self.ep

    @property
    def num_chips(self) -> int:
        return self.replicas * self.chips_per_replica

    def build_contexts(self, devices=None) -> list:
        """One MeshContext per replica over disjoint device slices."""
        import jax

        from automodel_tpu.distributed import MeshConfig

        devices = list(devices if devices is not None else jax.devices())
        if len(devices) < self.num_chips:
            raise ValueError(
                f"serving.mesh needs replicas*tp*ep = {self.num_chips} "
                f"devices, have {len(devices)}"
            )
        per = self.chips_per_replica
        return [
            MeshConfig(tp=self.tp, ep=self.ep, dp_shard=1).build(
                devices[i * per : (i + 1) * per]
            )
            for i in range(self.replicas)
        ]


def _mirror_router_stats(reg, stats: dict) -> None:
    """Mirror one router serve_batch call's outcome stats onto the central
    registry. The lockstep step/token counters are incremented inside
    `ServingEngine.run_step`; these are the per-call outcome counters only
    the driving loop knows."""
    for name, key, help_ in (
        ("serve_new_tokens_total", "new_tokens",
         "tokens committed to requests"),
        ("serve_requests_total", "requests",
         "requests finished by the engine"),
        ("serve_preemptions_total", "preemptions",
         "requests preempted and requeued"),
        ("serve_timed_out_total", "timed_out",
         "requests expired at their deadline"),
        ("serve_prefix_hits_total", "prefix_hits",
         "admissions that matched a cached prefix"),
        ("serve_prefill_skipped_tokens_total", "prefill_skipped_tokens",
         "prompt tokens skipped via prefix reuse"),
        ("serve_handoffs_total", "handoffs",
         "prefill→decode handoffs admitted"),
        ("serve_handoff_pages_moved_total", "handoff_pages_moved",
         "handoff pages moved between pools"),
        ("serve_handoff_pages_spliced_total", "handoff_pages_spliced",
         "handoff pages spliced via decode-side prefix match"),
        ("serve_handoff_expired_total", "handoff_expired",
         "handoffs expired before decode admission"),
        ("serve_spec_drafted_total", "drafted_tokens",
         "draft tokens proposed"),
        ("serve_spec_accepted_total", "accepted_tokens",
         "draft tokens accepted"),
    ):
        v = stats.get(key)
        if v:
            reg.counter(name, help_).inc(v)


class ReplicaRouter:
    """N data-parallel `ServingEngine` replicas + per-replica admission."""

    def __init__(
        self,
        params,
        cfg,
        serve_cfg: ServingConfig = ServingConfig(),
        mesh: ServeMeshConfig = ServeMeshConfig(),
        devices=None,
        draft_source_factory=None,
        resilience: ServeResilienceConfig | None = None,
    ):
        """`params` may carry any placement (chassis-sharded arrays flow
        straight in); each replica re-shards them onto its own slice. The
        router takes ownership of `params` as an engine does
        (`split_layer_stacks`): the layer stacks are split ONCE, here, and
        every replica is handed the same per-layer tree.
        `draft_source_factory()` builds one draft source per replica for
        the stateful EAGLE/DFlash speculation adapters (per-request state
        must live with the replica that serves the request)."""
        self.mesh = mesh
        ctxs = mesh.build_contexts(devices)
        params = split_layer_stacks(params, cfg.dtype)
        # ONE shared observability bundle: replicas interleave on a shared
        # registry/trace, distinguished by track name
        self.obs = Observability(serve_cfg.observability)
        self.engines = [
            ServingEngine(
                params, cfg, serve_cfg,
                draft_source=(
                    draft_source_factory() if draft_source_factory else None
                ),
                mesh_ctx=ctx,
                obs=self.obs, track=f"replica{r}",
            )
            for r, ctx in enumerate(ctxs)
        ]
        # per-replica health (serving/resilience.py): engine-lifetime like
        # the prefix cache — a replica that died stays dead across
        # serve_batch calls until restore()
        self.resilience = resilience or ServeResilienceConfig()
        self.health = HealthBoard(
            [e.track for e in self.engines], self.resilience,
            registry=self.obs.registry,
        )

    @property
    def num_replicas(self) -> int:
        return len(self.engines)

    def _admittable(self) -> list[int]:
        return [
            r for r, e in enumerate(self.engines)
            if self.health.admittable(e.track)
        ]

    def restore(self, replica: int) -> None:
        """Bring a dead/draining replica back into the routing set (the
        operator restarted or re-provisioned its slice)."""
        self.health.restore(self.engines[replica].track)

    # -- admission ----------------------------------------------------------
    def route(self, req: Request, schedulers, alive=None) -> tuple[int, bool]:
        """(replica index, sticky?) for one arriving request: best
        prefix-cache affinity first, else most-free-pages (ties → fewest
        resident requests, then lowest index). `alive` (optional) narrows
        the candidate indices — the health board's admittable set."""
        cand = list(alive) if alive is not None else range(len(schedulers))
        best_aff, best_r = 0, None
        for r in cand:
            aff = schedulers[r].prefix_hit_tokens(req.prompt)
            if aff > best_aff:
                best_aff, best_r = aff, r
        if best_r is not None:
            return best_r, True
        return max(
            cand,
            key=lambda r: (
                schedulers[r].alloc.num_free,
                -(len(schedulers[r].running) + len(schedulers[r].waiting)),
                -r,
            ),
        ), False

    # -- failure recovery ----------------------------------------------------
    def _recover_replica(self, r: int, scheds, exc, step_idx: int) -> int:
        """A replica's step raised: mark it dead, evacuate every resident
        and queued request, and requeue them onto surviving replicas with
        pages released and `fed` reset — re-prefill rides each survivor's
        prefix cache, so the cost is the divergence suffix. Raises the
        NAMED `ReplicaFailure` when no survivors remain. Returns the
        number of requests recovered."""
        name = self.engines[r].track
        self.health.mark_dead(name, step_idx, repr(exc))
        self.obs.tracer.instant(
            "replica.death", track=name, step=step_idx,
            reason=type(exc).__name__,
        )
        # reason-labeled post-mortem: ring buffers + registry snapshot
        self.obs.flight_dump("replica_death")
        evac = scheds[r].evacuate()
        alive = self._admittable()
        if not alive:
            raise ReplicaFailure(
                name, f"last replica died with {len(evac)} requests resident"
            ) from exc
        reg = self.obs.registry
        reg.counter(
            "serve_requests_recovered_total",
            "requests requeued onto survivors after a replica death",
        ).inc(len(evac))
        reg.counter(
            "serve_recovery_reprefill_tokens_total",
            "known tokens requeued for re-prefill by failure recovery",
        ).inc(sum(len(q.known) for q in evac))
        for q in evac:
            q.recovered += 1
            i, _ = self.route(q, scheds, alive=alive)
            scheds[i].submit(q)
        return len(evac)

    # -- offline drive ------------------------------------------------------
    def serve_batch(
        self,
        requests: list[Request],
        *,
        metric_logger=None,
        max_steps: int | None = None,
    ) -> dict:
        """Route + drive all replicas until every request finished. Returns
        {"outputs": per-request ids (submission order), "requests", "stats"}
        with the same top-level counters as `ServingEngine.serve_batch`
        plus `per_replica` and router balance stats."""
        for i, req in enumerate(requests):
            if req.rid < 0:
                req.rid = i  # global rids: replicas must never collide
        scheds = [eng.make_scheduler() for eng in self.engines]
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        n = self.num_replicas
        routed = [0] * n
        sticky_routed = 0
        decode_s = [0.0] * n
        n_sampled = [0] * n
        n_steps = [0] * n
        tokens_fed = [0] * n
        ms_per_tok: list[list[float]] = [[] for _ in range(n)]
        ttft_watch: list[Request] = []
        budget = max_steps if max_steps is not None else 10_000_000
        t_start = time.perf_counter()
        step_idx = 0
        while step_idx < budget and (
            pending or any(s.has_work for s in scheds)
        ):
            while pending and pending[0].arrival <= step_idx:
                req = pending.pop(0)
                req.arrived_t = time.perf_counter()
                ttft_watch.append(req)
                r, sticky = self.route(req, scheds, alive=self._admittable())
                scheds[r].submit(req)
                routed[r] += 1
                sticky_routed += int(sticky)
            progressed = False
            for r, (eng, sched) in enumerate(zip(self.engines, scheds)):
                if not self.health.alive(eng.track) or not sched.has_work:
                    continue
                plan = eng.plan_turn(sched, step_idx)
                if plan is None:
                    continue
                try:
                    n_new, dt = eng.run_and_absorb(sched, plan, step_idx)
                except RuntimeError as e:
                    # replica death (injected serve_step_run fault or a
                    # real step failure — FaultCrash, a BaseException,
                    # still propagates): recover onto survivors and keep
                    # serving. The failed step never rebound the pool, so
                    # survivors and the health board see a clean cut.
                    if not self.resilience.enabled:
                        raise
                    self._recover_replica(r, scheds, e, step_idx)
                    progressed = True
                    continue
                progressed = True
                n_steps[r] += 1
                tokens_fed[r] += plan.n_tokens
                if plan.n_samples:
                    decode_s[r] += dt
                    n_sampled[r] += n_new
                    if n_new:
                        ms_per_tok[r].append(dt * 1e3 / n_new)
            if ttft_watch:
                ttft_watch = _resolve_ttft(ttft_watch)
            if progressed:
                step_idx += 1
                continue
            # idle step on every replica: jump to the next event (arrival
            # or deadline eviction) instead of spinning — mirroring the
            # single-engine loop's fast-forward, incl. never jumping PAST
            # a servable arrival
            arrivals = [r.arrival for r in pending if r.arrival > step_idx]
            for s in scheds:
                arrivals += [
                    r.arrival for r in s.waiting if r.arrival > step_idx
                ]
            deadlines = [
                s.next_deadline for s in scheds
                if s.next_deadline is not None and s.next_deadline > step_idx
            ]
            if deadlines:
                step_idx = min(deadlines + arrivals)
                continue
            if not arrivals:
                if pending or any(s.has_work for s in scheds):
                    blocked = next(
                        (s.waiting[0] for s in scheds if s.waiting),
                        pending[0] if pending else None,
                    )
                    raise RuntimeError(
                        "routed serving stalled: request "
                        f"rid={getattr(blocked, 'rid', '?')} cannot make "
                        f"progress on any of {n} replicas (free pages: "
                        f"{[s.alloc.num_free for s in scheds]})"
                    )
                break
            step_idx = min(arrivals)
        elapsed = time.perf_counter() - t_start
        assert max_steps is not None or (
            not pending and not any(s.has_work for s in scheds)
        ), "routed serve stalled"
        if max_steps is None and self.health.n_dead():
            # post-recovery allocator identity on every SURVIVING pool:
            # drained means every page is free or prefix-cached — a leak
            # through evacuate/requeue would surface right here
            for r in self._admittable():
                assert pool_identity_ok(scheds[r]), (
                    f"allocator identity broken on replica{r} after "
                    f"recovery: free={scheds[r].alloc.num_free} "
                    f"pages={scheds[r].alloc.num_pages}"
                )

        finished = [r for s in scheds for r in s.finished]
        by_rid = sorted(finished, key=lambda r: r.rid)
        ttft_p50, ttft_p95 = _percentiles_ms(
            [r.ttft_s * 1e3 for r in by_rid if r.ttft_s >= 0]
        )
        itl_p50, itl_p95 = _percentiles_ms(
            [s for samples in ms_per_tok for s in samples]
        )
        per_replica = []
        for r, (eng, sched) in enumerate(zip(self.engines, scheds)):
            samples = ms_per_tok[r]
            per_replica.append({
                "requests": routed[r],
                "steps": n_steps[r],
                "new_tokens": n_sampled[r],
                "tokens_fed": tokens_fed[r],
                "decode_tokens_per_sec": round(
                    n_sampled[r] / max(decode_s[r], 1e-9), 2
                ),
                "p50_ms_per_token": round(
                    float(np.percentile(samples, 50)), 4
                ) if samples else None,
                "p95_ms_per_token": round(
                    float(np.percentile(samples, 95)), 4
                ) if samples else None,
                "preemptions": sched.n_preemptions,
                "free_pages": sched.alloc.num_free,
                "compiled_signatures": eng.step_cache_size(),
            })
        stats = {
            "replicas": n,
            "requests": len(by_rid),
            "new_tokens": sum(n_sampled),
            "tokens_fed": sum(tokens_fed),
            "steps": max(n_steps) if n_steps else 0,
            "elapsed_s": round(elapsed, 4),
            # pod throughput: each replica decodes on its own slice, so
            # aggregate tokens/s is the SUM of per-replica rates (the
            # offline loop time-slices them on one host; a pod runs them
            # concurrently)
            "decode_tokens_per_sec": round(sum(
                ns / max(ds, 1e-9) for ns, ds in zip(n_sampled, decode_s)
            ), 2),
            "ttft_p50_ms": ttft_p50,
            "ttft_p95_ms": ttft_p95,
            "itl_p50_ms": itl_p50,
            "itl_p95_ms": itl_p95,
            "timed_out": sum(s.n_timed_out for s in scheds),
            "preemptions": sum(s.n_preemptions for s in scheds),
            "compiled_signatures": max(
                pr["compiled_signatures"] for pr in per_replica
            ),
            "sticky_routed": sticky_routed,
            "requests_per_replica": routed,
            "tokens_per_replica": list(n_sampled),
            "balance": round(
                min(routed) / max(max(routed), 1), 4
            ),
            "per_replica": per_replica,
            "replica_health": self.health.snapshot(),
            "requests_recovered": sum(
                1 for r in by_rid if r.recovered > 0
            ),
        }
        if any(s.prefix is not None for s in scheds):
            stats["prefix_hits"] = sum(s.n_prefix_hits for s in scheds)
            stats["prefill_skipped_tokens"] = sum(
                s.prefill_skipped for s in scheds
            )
        if any(s.spec is not None for s in scheds):
            stats["drafted_tokens"] = sum(s.n_drafted for s in scheds)
            stats["accepted_tokens"] = sum(s.n_accepted for s in scheds)
        _mirror_router_stats(self.obs.registry, stats)
        if metric_logger is not None:
            metric_logger.log({
                f"route_{k}": v for k, v in stats.items() if k != "per_replica"
            })
        return {
            "outputs": [list(r.generated) for r in by_rid],
            "requests": by_rid,
            "stats": stats,
        }


# ---------------------------------------------------------------------------
# disaggregated prefill/decode serving
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AutoscaleConfig:
    """Typed `serving.disaggregation.autoscale` section: when the prefill
    queue outruns the decode class for long enough, the prefill ROUTING
    SET borrows a decode replica (and returns it when the imbalance
    clears). Membership is pure routing state — engines are never rebuilt
    or resharded, so every replica keeps its compile-once contract; a
    borrowed replica simply starts receiving prompt-phase requests, whose
    finished prefills hand off like any prefill replica's."""

    enabled: bool = False
    #: borrow when prefill queue depth >= grow_ratio * (decode depth + 1)
    grow_ratio: float = 4.0
    #: return a borrowed replica when depth <= shrink_ratio * (decode+1)
    shrink_ratio: float = 1.0
    #: consecutive turns the signal must hold before acting (hysteresis)
    sustain: int = 8
    #: turns after any action before the next may fire
    cooldown: int = 32
    #: decode replicas that must stay dedicated to decode
    min_decode: int = 1

    def __post_init__(self):
        if self.grow_ratio <= self.shrink_ratio:
            raise ValueError(
                "autoscale grow_ratio must exceed shrink_ratio "
                f"(got {self.grow_ratio} <= {self.shrink_ratio})"
            )
        if self.sustain < 1 or self.cooldown < 0 or self.min_decode < 1:
            raise ValueError(f"bad autoscale config: {self}")


class QueueAutoscaler:
    """The autoscale DECISION, isolated from the routing mutation: feed it
    (prefill queue depth, decode load, step) once per turn and it answers
    None / "grow" / "shrink" with sustain-and-cooldown hysteresis — a pure
    function of the observation sequence, so identical traces autoscale
    identically (and the policy unit-tests without any engines)."""

    def __init__(self, cfg: AutoscaleConfig):
        self.cfg = cfg
        self._grow_streak = 0
        self._shrink_streak = 0
        self._last_action: int | None = None

    def observe(self, prefill_depth: int, decode_depth: int,
                step_idx: int) -> str | None:
        c = self.cfg
        grow = prefill_depth >= c.grow_ratio * (decode_depth + 1)
        shrink = prefill_depth <= c.shrink_ratio * (decode_depth + 1)
        self._grow_streak = self._grow_streak + 1 if grow else 0
        self._shrink_streak = self._shrink_streak + 1 if shrink else 0
        if (
            self._last_action is not None
            and step_idx - self._last_action < c.cooldown
        ):
            return None
        if self._grow_streak >= c.sustain:
            self._last_action = step_idx
            self._grow_streak = 0
            return "grow"
        if self._shrink_streak >= c.sustain:
            self._last_action = step_idx
            self._shrink_streak = 0
            return "shrink"
        return None


@dataclasses.dataclass(frozen=True)
class DisaggConfig:
    """Typed `serving.disaggregation` section: split the replica set into a
    prefill class and a decode class (Mooncake/DistServe-style). Finished
    prefills hand off as page-granular KV transfers (kv_transfer.py); the
    two phases stop competing for the same step's token budget, which is
    what moves decode tail latency under mixed long-prompt + chat load."""

    enabled: bool = False
    prefill_replicas: int = 1
    decode_replicas: int = 1
    #: pages per issued transfer program (fixed-length, trash-padded)
    transfer_pages: int = 8
    #: token budget override for the prefill class (None → serve config's);
    #: prefill replicas usually want a LARGER budget — they never carry
    #: latency-critical decode rows, so wide chunks amortize step overhead
    prefill_token_budget: int | None = None
    #: elastic prefill routing set (see AutoscaleConfig); off by default
    autoscale: AutoscaleConfig = AutoscaleConfig()

    def __post_init__(self):
        if self.prefill_replicas < 1 or self.decode_replicas < 1:
            raise ValueError(f"replica counts must be >= 1: {self}")
        if self.transfer_pages < 1:
            raise ValueError("transfer_pages must be >= 1")
        if (
            self.prefill_token_budget is not None
            and self.prefill_token_budget < 1
        ):
            raise ValueError("prefill_token_budget must be >= 1 (or None)")


@dataclasses.dataclass
class _Handoff:
    """One finished prefill in flight to a decode replica. `src_pages` are
    pinned (incref'd) in the prefill allocator until admitted or expired."""

    req: Request
    n_tokens: int      # committed tokens whose KV the pages hold (= fed)
    src_pages: list    # page IDs in the PREFILL replica's pool
    src: int           # prefill replica index (owns the pins)


class DisaggRouter:
    """Prefill-class + decode-class `ServingEngine` replicas with
    page-granular KV handoff between them.

    The request lifecycle: arrivals route to a prefill replica (by queue
    depth x pending prompt tokens); the moment a request samples its first
    token there, the scheduler pins its committed pages and releases the
    slot (`extract_handoffs`); the router carries the pinned pages as an
    in-flight handoff until a decode replica admits it
    (`try_admit_handoff`: radix-splice pages the decode tree already
    holds, allocate the rest), the `KVTransfer` pair moves the remaining
    pages device-side, and the prefill pins drop. The request lands on the
    decode replica with `fed` already at the divergence point — its first
    step THERE is a decode row; no re-prefill, no cache-format conversion.

    Phases route independently: prefill by least (depth x pending prompt
    tokens), decode by free pages with sticky prefix affinity. Each class
    keeps its own compile-once contract (one step signature per class, one
    transfer signature per replica pair). `mesh=None` runs every replica
    meshless on the default device — same code path, fused same-device
    transfers — which is the hermetic test/smoke mode."""

    def __init__(
        self,
        params,
        cfg,
        serve_cfg: ServingConfig = ServingConfig(),
        disagg: DisaggConfig = DisaggConfig(),
        mesh: ServeMeshConfig | None = None,
        devices=None,
        draft_source_factory=None,
        resilience: ServeResilienceConfig | None = None,
    ):
        # before any engine is built: the hand-off moves pages, not state
        refuse_state_handoff(cfg)
        self.disagg = disagg
        self.resilience = resilience or ServeResilienceConfig()
        n_p, n_d = disagg.prefill_replicas, disagg.decode_replicas
        ptb = disagg.prefill_token_budget or serve_cfg.token_budget
        # prefill-class engines never speculate (nothing to speculate on:
        # every resident request is still feeding its prompt) — dropping
        # the speculative section keeps their step the plain program
        prefill_cfg = dataclasses.replace(
            serve_cfg,
            token_budget=ptb,
            prefill_chunk=min(serve_cfg.prefill_chunk or ptb, ptb),
            speculative=None,
        )
        if mesh is not None:
            if mesh.replicas not in (1, n_p + n_d):
                raise ValueError(
                    f"serving.mesh.replicas={mesh.replicas} must be 1 or "
                    f"prefill+decode={n_p + n_d} under disaggregation"
                )
            ctxs = ServeMeshConfig(
                replicas=n_p + n_d, tp=mesh.tp, ep=mesh.ep
            ).build_contexts(devices)
        else:
            ctxs = [None] * (n_p + n_d)
            # meshless engines pin no step shardings — if any input is
            # committed (chassis-sharded params), the donated pool comes
            # back committed after step 1 and re-cuts the jit cache.
            # Commit params to the default device up front (a
            # single-device engine needs them there anyway); the fresh
            # pools are committed alongside, below.
            params = jax.device_put(params, jax.devices()[0])
        # split the layer stacks once (the router owns `params` as an engine
        # does): both classes' engines share the one per-layer tree
        params = split_layer_stacks(params, cfg.dtype)
        # ONE shared observability bundle across both replica classes
        self.obs = Observability(serve_cfg.observability)
        self.prefill = [
            ServingEngine(
                params, cfg, prefill_cfg, mesh_ctx=ctxs[i],
                obs=self.obs, track=f"prefill{i}",
            )
            for i in range(n_p)
        ]
        self.decode = [
            ServingEngine(
                params, cfg, serve_cfg,
                draft_source=(
                    draft_source_factory() if draft_source_factory else None
                ),
                mesh_ctx=ctxs[n_p + i],
                obs=self.obs, track=f"decode{i}",
            )
            for i in range(n_d)
        ]
        if mesh is None:
            # commit the fresh (uncommitted) pools too: the jit cache
            # keys on committed-ness, so an uncommitted pool in step 1
            # vs the committed donated output in step 2 would cost one
            # recompile per engine
            for e in self.prefill + self.decode:
                e.pool = jax.device_put(e.pool, jax.devices()[0])
        self.transfers = {
            (i, j): KVTransfer(
                self.prefill[i], self.decode[j],
                batch_pages=disagg.transfer_pages,
            )
            for i in range(n_p)
            for j in range(n_d)
        }
        # elastic prefill routing set: decode replica indices currently
        # borrowed by the prefill class (routing state only — engines and
        # their compiled steps are untouched)
        self.borrowed: set[int] = set()
        self.autoscaler = (
            QueueAutoscaler(disagg.autoscale)
            if disagg.autoscale.enabled else None
        )
        self.n_borrows = 0
        self.n_returns = 0
        # KVTransfer counters are object-lifetime totals; remember what has
        # already been mirrored so repeated serve calls inc only deltas
        self._transfer_mirrored = {"chunks": 0, "pages": 0, "bytes": 0}
        # per-replica health across BOTH classes (engine-lifetime, like the
        # prefix cache); degraded mode is DERIVED state — no alive prefill
        # replica — so restore() flips the router back to disagg routing
        # with no further bookkeeping
        self.health = HealthBoard(
            [e.track for e in self.prefill + self.decode], self.resilience,
            registry=self.obs.registry,
        )
        self._was_degraded = False

    # -- health / degraded mode ----------------------------------------------
    def _admittable_prefill(self) -> list[int]:
        return [
            i for i, e in enumerate(self.prefill)
            if self.health.admittable(e.track)
        ]

    def _admittable_decode(self) -> list[int]:
        return [
            j for j, e in enumerate(self.decode)
            if self.health.admittable(e.track)
        ]

    @property
    def degraded(self) -> bool:
        """Monolithic-fallback routing is in force: the prefill class has
        no admittable replica left, so decode replicas accept prefill
        chunks again (requests complete in place, no handoff). Derived
        from the health board — `restore()` on any prefill replica exits
        degraded mode the same turn."""
        return (
            self.resilience.enabled
            and self.resilience.degrade
            and not self._admittable_prefill()
        )

    def restore(self, track: str) -> None:
        """Bring a named replica (e.g. 'prefill0') back into the routing
        set — exits degraded mode when it re-staffs the prefill class."""
        self.health.restore(track)
        self._tick_degraded_gauge(-1)

    def _tick_degraded_gauge(self, step_idx: int) -> None:
        d = self.degraded
        if d != self._was_degraded:
            self._was_degraded = d
            self.obs.registry.gauge(
                "serve_degraded_mode",
                "1 while disagg routing is collapsed to monolithic",
            ).set(1.0 if d else 0.0)
            self.obs.tracer.instant(
                "router.degraded" if d else "router.restored",
                track="router", step=step_idx,
            )

    def _mirror_transfers(self) -> None:
        chunks = sum(t.n_chunks for t in self.transfers.values())
        pages = sum(t.n_pages for t in self.transfers.values())
        nbytes = sum(t.n_bytes for t in self.transfers.values())
        reg = self.obs.registry
        reg.counter(
            "serve_kv_transfer_chunks_total",
            "fixed-size transfer chunks issued",
        ).inc(chunks - self._transfer_mirrored["chunks"])
        reg.counter(
            "serve_kv_transfer_pages_total",
            "KV pages shipped by transfers",
        ).inc(pages - self._transfer_mirrored["pages"])
        reg.counter(
            "serve_kv_transfer_bytes_total",
            "KV transfer wire bytes (quantized pools ship int8+scales)",
        ).inc(nbytes - self._transfer_mirrored["bytes"])
        self._transfer_mirrored = {"chunks": chunks, "pages": pages, "bytes": nbytes}

    # -- autoscaling ---------------------------------------------------------
    def autoscale_tick(self, p_scheds, d_scheds, step_idx) -> str | None:
        """Once per serve turn: observe the queue imbalance, mutate the
        borrowed set when the policy fires. Grow borrows the decode
        replica with the most free pages (never dipping below
        min_decode dedicated ones); shrink returns the most recent
        borrow. Returns the action taken (None almost always)."""
        if self.autoscaler is None:
            return None
        p_depth = sum(len(s.waiting) for s in p_scheds) + sum(
            len(d_scheds[j].waiting) for j in self.borrowed
        )
        d_depth = sum(
            len(s.running) + len(s.waiting)
            for j, s in enumerate(d_scheds)
            if j not in self.borrowed
        )
        action = self.autoscaler.observe(p_depth, d_depth, step_idx)
        if action == "grow":
            dedicated = [
                j for j in range(len(self.decode)) if j not in self.borrowed
            ]
            if len(dedicated) <= self.disagg.autoscale.min_decode:
                return None
            j = max(
                dedicated,
                key=lambda j: (
                    d_scheds[j].alloc.num_free,
                    -len(d_scheds[j].running),
                    -j,
                ),
            )
            self.borrowed.add(j)
            self.n_borrows += 1
            return "grow"
        if action == "shrink" and self.borrowed:
            self.borrowed.discard(max(self.borrowed))
            self.n_returns += 1
            return "shrink"
        return None

    def decode_transfer(self, src_j: int, dst_r: int) -> KVTransfer:
        """Transfer pair for a BORROWED replica's handoffs (decode pool →
        decode pool), built lazily on first use — one compiled copy
        program per pair, same as the static prefill→decode grid. The
        src_j == dst_r pair is legal (the borrowed replica adopts its own
        radix-donated pages, so the splice path makes it nearly free)."""
        key = ("d", src_j, dst_r)
        t = self.transfers.get(key)
        if t is None:
            t = self.transfers[key] = KVTransfer(
                self.decode[src_j], self.decode[dst_r],
                batch_pages=self.disagg.transfer_pages,
            )
        return t

    # -- routing -------------------------------------------------------------
    def route_prefill(self, req: Request, schedulers) -> int:
        """Least-loaded prefill replica by queue depth x pending prompt
        tokens (what actually bounds time-to-first-token: how many prompt
        tokens are ahead of you, weighted by how many queues they cross)."""
        def pending_tokens(s, extra) -> int:
            t = extra
            for r in s.waiting:
                t += max(len(r.prompt) - s.prefix_hit_tokens(r.prompt), 0)
            for r in s.running.values():
                t += max(len(r.known) - r.fed, 0)
            return t

        def score(r: int):
            s = schedulers[r]
            mine = max(
                len(req.prompt) - s.prefix_hit_tokens(req.prompt), 0
            )
            depth = len(s.waiting) + len(s.running) + 1
            return (
                depth * pending_tokens(s, mine),
                len(s.waiting) + len(s.running),
                r,
            )

        return min(range(len(schedulers)), key=score)

    def _decode_order(self, h: _Handoff, schedulers) -> list:
        """Decode replicas to try for a handoff, best first: sticky prefix
        affinity (the transferred prefix is already cached there → pages
        splice instead of moving), then most free pages. Returns
        [(replica, sticky?)] so a full sticky replica falls back."""
        aff = [
            s.prefix_hit_tokens(h.req.known[: h.n_tokens])
            for s in schedulers
        ]
        order = sorted(
            range(len(schedulers)),
            key=lambda r: (
                aff[r],
                schedulers[r].alloc.num_free,
                -(len(schedulers[r].running) + len(schedulers[r].waiting)),
                -r,
            ),
            reverse=True,
        )
        return [(r, aff[r] > 0) for r in order]

    # -- failure recovery ----------------------------------------------------
    def _route_arrival(self, req: Request, p_scheds, d_scheds,
                       routed_p, routed_d) -> tuple[str, int]:
        """Submit one prefill-phase request (fresh arrival or recovery
        requeue) to the CURRENT routing set: admittable prefill replicas
        normally; under degraded mode the admittable decode replicas take
        prefill chunks directly and the request completes in place (no
        handoff). Raises the named `ReplicaFailure` when neither class
        can take it (prefill gone and degradation off, or decode gone)."""
        alive_p = self._admittable_prefill()
        if alive_p:
            idx = self.route_prefill(req, [p_scheds[i] for i in alive_p])
            r = alive_p[idx]
            p_scheds[r].submit(req)
            routed_p[r] += 1
            return ("p", r)
        alive_d = self._admittable_decode()
        if self.degraded and alive_d:
            idx = self.route_prefill(req, [d_scheds[j] for j in alive_d])
            j = alive_d[idx]
            d_scheds[j].submit(req)
            routed_d[j] += 1
            return ("d", j)
        raise ReplicaFailure(
            "prefill" if alive_d else "decode",
            "no admittable replica can take prefill work "
            f"(degrade={self.resilience.degrade})",
        )

    def _transfer_move(self, t: KVTransfer, pairs) -> None:
        """KV page copy with retry-and-backoff (deterministic jitter);
        `RetryBudgetExhausted` escalates to the caller's health handling,
        never into the serve loop."""
        transfer_with_retry(
            t.move, pairs, cfg=self.resilience,
            registry=self.obs.registry, point="kv_transfer",
        )

    def _recover_disagg_replica(self, klass: str, r: int, p_scheds, d_scheds,
                                inflight, routed_p, routed_d, exc,
                                step_idx: int) -> int:
        """A replica of either class died: evacuate its scheduler, drop
        any in-flight handoff pinned on a dead prefill pool, and requeue
        everything for full re-prefill through the (possibly degraded)
        routing set. Decode-class extinction is unservable → the named
        `ReplicaFailure` propagates."""
        engines = self.prefill if klass == "p" else self.decode
        scheds = p_scheds if klass == "p" else d_scheds
        name = engines[r].track
        if self.health.alive(name):
            self.health.mark_dead(name, step_idx, repr(exc))
        self.obs.tracer.instant(
            "replica.death", track=name, step=step_idx,
            reason=type(exc).__name__,
        )
        self.obs.flight_dump("replica_death")
        evac = scheds[r].evacuate()
        if klass == "p":
            for h in list(inflight):
                if h.src == r:
                    inflight.remove(h)
                    scheds[r].release_handoff(h.src_pages)
                    h.req.fed = 0
                    h.req.donated_pages = 0
                    evac.append(h.req)
        self._tick_degraded_gauge(step_idx)
        if not self._admittable_decode():
            raise ReplicaFailure(
                "decode", "no decode-class replicas left alive"
            ) from exc
        reg = self.obs.registry
        reg.counter(
            "serve_requests_recovered_total",
            "requests requeued onto survivors after a replica death",
        ).inc(len(evac))
        reg.counter(
            "serve_recovery_reprefill_tokens_total",
            "known tokens requeued for re-prefill by failure recovery",
        ).inc(sum(len(q.known) for q in evac))
        for q in evac:
            q.recovered += 1
            self._route_arrival(q, p_scheds, d_scheds, routed_p, routed_d)
        return len(evac)

    # -- offline drive -------------------------------------------------------
    def serve_batch(
        self,
        requests: list[Request],
        *,
        metric_logger=None,
        max_steps: int | None = None,
    ) -> dict:
        """Route + drive both replica classes until every request finished.
        Same result contract as `ReplicaRouter.serve_batch`; stats add the
        handoff block (counts, pages moved vs spliced, transfer programs)
        and tag each per_replica entry with its class."""
        for i, req in enumerate(requests):
            if req.rid < 0:
                req.rid = i
        p_scheds = [eng.make_scheduler() for eng in self.prefill]
        d_scheds = [eng.make_scheduler() for eng in self.decode]
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        inflight: list[_Handoff] = []
        expired: list[Request] = []
        ttft_watch: list[Request] = []
        n_p, n_d = len(self.prefill), len(self.decode)
        routed_p = [0] * n_p
        routed_d = [0] * n_d
        sticky_routed = 0
        n_expired = 0
        p_steps, p_fed = [0] * n_p, [0] * n_p
        p_sampled, p_decode_s = [0] * n_p, [0.0] * n_p
        p_ms: list[list[float]] = [[] for _ in range(n_p)]
        d_steps, d_fed = [0] * n_d, [0] * n_d
        d_sampled, d_decode_s = [0] * n_d, [0.0] * n_d
        d_ms: list[list[float]] = [[] for _ in range(n_d)]
        budget = max_steps if max_steps is not None else 10_000_000

        def has_work() -> bool:
            return bool(pending or inflight) or any(
                s.has_work for s in p_scheds + d_scheds
            )

        t_start = time.perf_counter()
        step_idx = 0
        while step_idx < budget and has_work():
            while pending and pending[0].arrival <= step_idx:
                req = pending.pop(0)
                req.arrived_t = time.perf_counter()
                ttft_watch.append(req)
                self._route_arrival(req, p_scheds, d_scheds,
                                    routed_p, routed_d)
            # deadline-expire handoffs stuck in flight (decode side full):
            # the prefill pins drop and the request times out — the same
            # contract deadline eviction gives a queued request
            for h in list(inflight):
                if h.req.deadline is not None and step_idx >= h.req.deadline:
                    inflight.remove(h)
                    p_scheds[h.src].release_handoff(h.src_pages)
                    h.req.finish_reason = "timed_out"
                    h.req.finished_at = step_idx
                    expired.append(h.req)
                    n_expired += 1
                    self.obs.tracer.instant(
                        "request.expire", track=f"prefill{h.src}",
                        step=step_idx, rid=h.req.rid, inflight=1,
                    )
            # admit in-flight handoffs FIFO; on success move the non-spliced
            # pages device-side and drop the prefill-side pins
            for h in list(inflight):
                for r, sticky in self._decode_order(h, d_scheds):
                    if not self.health.admittable(self.decode[r].track):
                        continue
                    try:
                        pairs = d_scheds[r].try_admit_handoff(
                            h.req, h.n_tokens, h.src_pages, step_idx
                        )
                    except FaultError:
                        # injected handoff_admit fault: nothing mutated —
                        # leave the handoff in flight and retry next turn
                        pairs = None
                    if pairs is None:
                        continue
                    try:
                        with self.obs.tracer.span(
                            "kv_transfer", track=f"prefill{h.src}",
                            step=step_idx, rid=h.req.rid, pages=len(pairs),
                        ):
                            self._transfer_move(
                                self.transfers[(h.src, r)], pairs
                            )
                    except RetryBudgetExhausted as e:
                        # retry budget gone → the HEALTH machine, not the
                        # serve loop: roll the admission back (no donation
                        # — pages may be half-copied), drop the pins, and
                        # re-prefill from scratch on the routing set
                        state = self.health.mark_exhausted(
                            self.decode[r].track, step_idx, str(e)
                        )
                        d_scheds[r].evict_for_recovery(h.req.rid)
                        p_scheds[h.src].release_handoff(h.src_pages)
                        inflight.remove(h)
                        reg = self.obs.registry
                        reg.counter(
                            "serve_requests_recovered_total",
                            "requests requeued onto survivors after a "
                            "replica death",
                        ).inc()
                        reg.counter(
                            "serve_recovery_reprefill_tokens_total",
                            "known tokens requeued for re-prefill by "
                            "failure recovery",
                        ).inc(len(h.req.known))
                        h.req.recovered += 1
                        self._route_arrival(
                            h.req, p_scheds, d_scheds, routed_p, routed_d
                        )
                        if state == "dead":
                            self._recover_disagg_replica(
                                "d", r, p_scheds, d_scheds, inflight,
                                routed_p, routed_d, e, step_idx,
                            )
                        break
                    p_scheds[h.src].release_handoff(h.src_pages)
                    inflight.remove(h)
                    sticky_routed += int(sticky)
                    routed_d[r] += 1
                    break
            progressed = False
            for r, (eng, sched) in enumerate(zip(self.decode, d_scheds)):
                if not self.health.alive(eng.track) or not sched.has_work:
                    continue
                plan = eng.plan_turn(sched, step_idx)
                if plan is None:
                    continue
                try:
                    n_new, dt = eng.run_and_absorb(sched, plan, step_idx)
                except RuntimeError as e:
                    if not self.resilience.enabled:
                        raise
                    self._recover_disagg_replica(
                        "d", r, p_scheds, d_scheds, inflight,
                        routed_p, routed_d, e, step_idx,
                    )
                    progressed = True
                    continue
                progressed = True
                d_steps[r] += 1
                d_fed[r] += plan.n_tokens
                if plan.n_samples:
                    d_decode_s[r] += dt
                    d_sampled[r] += n_new
                    if n_new:
                        d_ms[r].append(dt * 1e3 / n_new)
            for r, (eng, sched) in enumerate(zip(self.prefill, p_scheds)):
                if not self.health.alive(eng.track) or not sched.has_work:
                    continue
                plan = eng.plan_turn(sched, step_idx)
                if plan is None:
                    continue
                try:
                    n_new, dt = eng.run_and_absorb(sched, plan, step_idx)
                except RuntimeError as e:
                    if not self.resilience.enabled:
                        raise
                    self._recover_disagg_replica(
                        "p", r, p_scheds, d_scheds, inflight,
                        routed_p, routed_d, e, step_idx,
                    )
                    progressed = True
                    continue
                progressed = True
                p_steps[r] += 1
                p_fed[r] += plan.n_tokens
                if plan.n_samples:
                    p_decode_s[r] += dt
                    p_sampled[r] += n_new
                    if n_new:
                        p_ms[r].append(dt * 1e3 / n_new)
                for req, n_tok, src in sched.extract_handoffs():
                    inflight.append(_Handoff(req, n_tok, src, r))
            if ttft_watch:
                ttft_watch = _resolve_ttft(ttft_watch)
            if progressed:
                step_idx += 1
                continue
            # idle fast-forward, mirroring ReplicaRouter — in-flight handoff
            # deadlines count as events too (expiry frees prefill pins)
            arrivals = [r.arrival for r in pending if r.arrival > step_idx]
            for s in p_scheds + d_scheds:
                arrivals += [
                    r.arrival for r in s.waiting if r.arrival > step_idx
                ]
            deadlines = [
                s.next_deadline for s in p_scheds + d_scheds
                if s.next_deadline is not None and s.next_deadline > step_idx
            ]
            deadlines += [
                h.req.deadline for h in inflight
                if h.req.deadline is not None and h.req.deadline > step_idx
            ]
            if deadlines:
                step_idx = min(deadlines + arrivals)
                continue
            if not arrivals:
                if has_work():
                    raise RuntimeError(
                        "disaggregated serving stalled: "
                        f"{len(inflight)} handoffs in flight, decode free "
                        f"pages {[s.alloc.num_free for s in d_scheds]}, "
                        f"prefill waiting "
                        f"{[len(s.waiting) for s in p_scheds]}"
                    )
                break
            step_idx = min(arrivals)
        elapsed = time.perf_counter() - t_start
        assert max_steps is not None or not has_work(), "disagg serve stalled"
        if max_steps is None and self.health.n_dead():
            # post-recovery allocator identity on every surviving pool of
            # BOTH classes (drained → free + prefix-cached == num_pages;
            # a leaked handoff pin or evacuation page shows up here)
            for engines, scheds in (
                (self.prefill, p_scheds), (self.decode, d_scheds)
            ):
                for eng, s in zip(engines, scheds):
                    if self.health.alive(eng.track):
                        assert pool_identity_ok(s), (
                            f"allocator identity broken on {eng.track} "
                            f"after recovery: free={s.alloc.num_free} "
                            f"pages={s.alloc.num_pages}"
                        )

        finished = [r for s in p_scheds + d_scheds for r in s.finished]
        finished += expired
        by_rid = sorted(finished, key=lambda r: r.rid)
        ttft_p50, ttft_p95 = _percentiles_ms(
            [r.ttft_s * 1e3 for r in by_rid if r.ttft_s >= 0]
        )
        # decode-class ITL only: that is the latency the phase split buys
        itl_p50, itl_p95 = _percentiles_ms(
            [s for samples in d_ms for s in samples]
        )
        per_replica = []
        for klass, engines, scheds, routed, steps, fed, sampled, dec_s, ms in (
            ("prefill", self.prefill, p_scheds, routed_p, p_steps, p_fed,
             p_sampled, p_decode_s, p_ms),
            ("decode", self.decode, d_scheds, routed_d, d_steps, d_fed,
             d_sampled, d_decode_s, d_ms),
        ):
            for r, (eng, sched) in enumerate(zip(engines, scheds)):
                p50, p95 = _percentiles_ms(ms[r])
                per_replica.append({
                    "class": klass,
                    "requests": routed[r],
                    "steps": steps[r],
                    "new_tokens": sampled[r],
                    "tokens_fed": fed[r],
                    "decode_tokens_per_sec": round(
                        sampled[r] / max(dec_s[r], 1e-9), 2
                    ),
                    "p50_ms_per_token": p50,
                    "p95_ms_per_token": p95,
                    "preemptions": sched.n_preemptions,
                    "free_pages": sched.alloc.num_free,
                    "compiled_signatures": eng.step_cache_size(),
                })
        stats = {
            "prefill_replicas": n_p,
            "decode_replicas": n_d,
            "requests": len(by_rid),
            "new_tokens": sum(p_sampled) + sum(d_sampled),
            "tokens_fed": sum(p_fed) + sum(d_fed),
            "steps": max(p_steps + d_steps) if (p_steps or d_steps) else 0,
            "elapsed_s": round(elapsed, 4),
            "decode_tokens_per_sec": round(sum(
                ns / max(ds, 1e-9)
                for ns, ds in zip(d_sampled, d_decode_s)
            ), 2),
            "ttft_p50_ms": ttft_p50,
            "ttft_p95_ms": ttft_p95,
            "itl_p50_ms": itl_p50,
            "itl_p95_ms": itl_p95,
            "handoffs": sum(s.n_handoffs_in for s in d_scheds),
            "handoff_pages_moved": sum(s.handoff_pages_in for s in d_scheds),
            "handoff_pages_spliced": sum(
                s.handoff_pages_spliced for s in d_scheds
            ),
            "handoff_expired": n_expired,
            "transfer_chunks": sum(t.n_chunks for t in self.transfers.values()),
            "timed_out": (
                sum(s.n_timed_out for s in p_scheds + d_scheds) + n_expired
            ),
            "preemptions": sum(s.n_preemptions for s in p_scheds + d_scheds),
            "compiled_signatures_prefill": max(
                eng.step_cache_size() for eng in self.prefill
            ),
            "compiled_signatures_decode": max(
                eng.step_cache_size() for eng in self.decode
            ),
            "sticky_routed": sticky_routed,
            "requests_per_prefill": routed_p,
            "requests_per_decode": routed_d,
            "per_replica": per_replica,
            "replica_health": self.health.snapshot(),
            "degraded": self.degraded,
            "requests_recovered": sum(
                1 for r in by_rid if r.recovered > 0
            ),
        }
        scheds_all = p_scheds + d_scheds
        if any(s.prefix is not None for s in scheds_all):
            stats["prefix_hits"] = sum(s.n_prefix_hits for s in scheds_all)
            stats["prefill_skipped_tokens"] = sum(
                s.prefill_skipped for s in scheds_all
            )
        if any(s.spec is not None for s in d_scheds):
            stats["drafted_tokens"] = sum(s.n_drafted for s in d_scheds)
            stats["accepted_tokens"] = sum(s.n_accepted for s in d_scheds)
        _mirror_router_stats(self.obs.registry, stats)
        self._mirror_transfers()
        if metric_logger is not None:
            metric_logger.log({
                f"disagg_{k}": v
                for k, v in stats.items() if k != "per_replica"
            })
        return {
            "outputs": [list(r.generated) for r in by_rid],
            "requests": by_rid,
            "stats": stats,
        }


# ---------------------------------------------------------------------------
# online data-parallel tier
# ---------------------------------------------------------------------------

class OnlineRouter:
    """Live-traffic front for the data-parallel tier: one `OnlineFrontend`
    drive task per replica, with per-request admission decided by the SAME
    `ReplicaRouter.route` policy the offline loop uses — probed against
    the frontends' LIVE schedulers, so sticky prefix affinity and
    free-page load reflect what is resident right now, not a plan.

    `submit()` assigns globally-unique rids (replica frontends must never
    collide), routes, and delegates — the returned `TokenStream` is the
    chosen replica's. Each frontend paces itself; there is no cross-
    replica barrier, which is exactly the pod behavior (replicas step
    concurrently on their own slices).

    Failure recovery rides the shared health board: a frontend whose
    step raises calls back into `_handle_failure`, which marks the
    replica dead, evacuates its scheduler, and re-ADOPTS every live
    stream onto a survivor (`OnlineFrontend.adopt`) — the client's
    `TokenStream` object never changes, and greedy recovery is
    token-exact. `drain(r)`/`quiesce(r)`/`restore(r)` are the rolling-
    restart API."""

    def __init__(self, router: ReplicaRouter,
                 cfg: FrontendConfig = FrontendConfig()):
        self.router = router
        self.frontends = [
            OnlineFrontend(eng, cfg, name=f"replica{r}")
            for r, eng in enumerate(router.engines)
        ]
        for fe in self.frontends:
            fe.on_failure = self._handle_failure
        self._by_rid: dict[int, int] = {}
        self._next_rid = 0
        self.sticky_routed = 0

    def start(self) -> "OnlineRouter":
        for fe in self.frontends:
            fe.start()
        return self

    def _admittable(self) -> list[int]:
        return [
            r for r, fe in enumerate(self.frontends)
            if self.router.health.admittable(fe.engine.track)
        ]

    def submit(self, req: Request, *, deadline_in: int | None = None
               ) -> TokenStream:
        if req.rid < 0:
            req.rid = self._next_rid
        self._next_rid = max(self._next_rid, req.rid + 1)
        alive = self._admittable()
        if not alive:
            raise ReplicaFailure(
                "replica", "no admittable replica to take a submission"
            )
        r, sticky = self.router.route(
            req, [fe.sched for fe in self.frontends], alive=alive
        )
        self.sticky_routed += int(sticky)
        self._by_rid[req.rid] = r
        return self.frontends[r].submit(req, deadline_in=deadline_in)

    def cancel(self, rid: int) -> None:
        r = self._by_rid.get(rid)
        if r is not None:
            self.frontends[r].cancel(rid)

    # -- failure recovery ----------------------------------------------------
    def _handle_failure(self, fe: OnlineFrontend, exc: BaseException) -> None:
        """Callback from a dying frontend's drive task (its step raised;
        the flight recorder already dumped): mark the replica dead,
        evacuate its scheduler, and re-adopt every live stream onto a
        survivor — clients keep their `TokenStream`, tokens are never
        lost or duplicated (greedy continuation depends only on `known`).
        No survivors → the loud, NAMED `ReplicaFailure`."""
        r = self.frontends.index(fe)
        name = fe.engine.track
        self.router.health.mark_dead(name, fe.step_idx, repr(exc))
        evac = fe.sched.evacuate()
        alive = self._admittable()
        if not alive:
            raise ReplicaFailure(
                name, f"last replica died with {len(evac)} live streams"
            ) from exc
        scheds = [f.sched for f in self.frontends]
        for req in evac:
            entry = fe._active.pop(req.rid, None)
            emitted = fe._emitted.pop(req.rid, 0)
            if entry is None:
                continue  # finished this very turn; stream already ended
            req.recovered += 1
            i, _ = self.router.route(req, scheds, alive=alive)
            self._by_rid[req.rid] = i
            self.frontends[i].adopt(req, entry[1], emitted)
        # anything still attached has no compute left anywhere — end it
        # so no client awaits a dead replica's stream forever
        for rid in list(fe._active):
            fe._active[rid][0].finish_reason = (
                fe._active[rid][0].finish_reason or "cancelled"
            )
            fe._finish_stream(rid)

    # -- rolling restart -----------------------------------------------------
    def drain(self, r: int) -> None:
        """Rolling restart, step 1 for replica `r`: health → draining (no
        new routing) and the frontend stops admitting."""
        self.router.health[self.frontends[r].engine.track].mark_draining(
            self.frontends[r].step_idx
        )
        self.frontends[r].drain()

    async def quiesce(self, r: int) -> None:
        """Step 2: wait until replica `r` holds no work (streams flushed)."""
        await self.frontends[r].quiesce()

    def restore(self, r: int) -> None:
        """Step 3: the slice is back — rejoin the routing set."""
        self.router.health.restore(self.frontends[r].engine.track)
        self.frontends[r].resume_admission()

    async def wait_step(self, n: int) -> None:
        """Until EVERY replica's loop has started turn `n`."""
        for fe in self.frontends:
            await fe.wait_step(n)

    async def close(self) -> dict:
        for fe in self.frontends:
            await fe.close()
        return self.stats()

    async def __aenter__(self) -> "OnlineRouter":
        return self.start()

    async def __aexit__(self, *exc) -> None:
        await self.close()

    def stats(self) -> dict:
        per = [fe.stats() for fe in self.frontends]
        routed = [p["submitted"] for p in per]
        agg = {
            "replicas": len(per),
            "steps": max(p["steps"] for p in per),
            "submitted": sum(routed),
            "finished": sum(p["finished"] for p in per),
            "shed": sum(p["shed"] for p in per),
            "rejected": sum(p["rejected"] for p in per),
            "cancelled": sum(p["cancelled"] for p in per),
            "timed_out": sum(p["timed_out"] for p in per),
            "preemptions": sum(p["preemptions"] for p in per),
            "recovered": sum(p["recovered"] for p in per),
            "replica_health": self.router.health.snapshot(),
            "sticky_routed": self.sticky_routed,
            "requests_per_replica": routed,
            "balance": round(min(routed) / max(max(routed), 1), 4),
            "compiled_signatures": max(
                p["compiled_signatures"] for p in per
            ),
            "per_replica": per,
        }
        return agg
