"""Online serving frontend: async streaming loop, live admission, shedding.

The live-traffic layer above the engine/router tiers (ROADMAP: "turn the
engine into a service"): everything below this file consumes a pre-sorted
offline request list in one python loop; this file is the real queue.

One asyncio drive task owns one engine's serve loop:

- **Continuous admission.** `submit()` is callable mid-flight from any
  coroutine and returns a per-request `TokenStream` immediately; the
  drive task drains the arrival queue at the top of every engine turn, so
  a request lands in the scheduler the step after it arrives — no
  arrival-sorted list, no `Request.arrival` gating (the scheduler runs
  with `arrival_gating=False`: presence in the queue IS arrival).

- **Streaming with per-stream backpressure.** Tokens are pushed to each
  request's stream as its slot commits them each step; delivery is
  decoupled from the jitted step by per-request queues bounded by the
  PAUSE POLICY: a slot whose consumer has fallen `stream_buffer` tokens
  behind is withheld from the next plan (`Scheduler.paused`) — its pages
  stay resident and its deadline keeps ticking, but it costs no step
  rows, so a stalled consumer back-pressures exactly its own stream and
  never the step loop or anyone else's tokens. (The queue object itself
  is unbounded: the bound is enforced BEFORE scheduling, which is what
  lets the end-of-stream frame always land without blocking the loop.)

- **Deadline-aware load shedding.** Admission control rejects a request
  whose `Request.deadline` (absolute engine step, PR 11's plumbing) is
  provably unreachable — the queued prefill backlog alone already eats
  the budget — and the same check early-expires WAITING requests every
  turn, so overload turns into fast "shed" rejections instead of
  requests silently queueing to timeout while holding their place. The
  decision is a pure function of (step index, queue state, request), so
  identical arrival traces shed identical sets; no wall clock enters
  the decision.

- **Cancellation.** `cancel(rid)` takes effect at the top of the next
  turn — before the next plan is built — releasing the slot's pages
  (`Scheduler.cancel`) and, in the disaggregated frontend, any in-flight
  handoff pins, the same turn. Deferred-to-turn-start is what makes it
  safe: a plan in flight still references the slot's pages.

- **Multi-host plan broadcast** (`plan_broadcast` given): the lead
  process packs every StepPlan to one flat int32 frame and broadcasts it
  (serving/plan_wire.py) before running its own step; follower processes
  run `PlanFollower` — recv → unpack → the SAME jitted step — so the
  allocator/scheduler/prefix cache stay single-brained on the lead and a
  replica's mesh slice can span hosts without the host state knowing.

- **Failure recovery** (serving/resilience.py): a replica whose jitted
  step raises RuntimeError mid-loop is marked dead on the router's
  health board and its live streams are ADOPTED by a survivor — the
  `TokenStream` object never changes hands from the client's view; only
  the compute moves (requeue with `fed = 0`, re-prefill riding the
  prefix cache). Greedy continuations depend only on `known`, so a
  recovered stream is token-for-token identical to an undisturbed run.
  `drain()`/`quiesce()` are the rolling-restart half: stop admitting,
  finish or hand off residents, flush streams, keep the loop alive.

The jitted step is the only blocking call and runs in a worker thread
(`run_in_executor`); every scheduler mutation happens on the event-loop
thread between steps, so the scheduler needs no locks.

Two frontends, one client side. `FrontendBase` holds what a client and a
stream see — the stream table, arrivals and cancels, the shedding rule,
back-pressure, token delivery, close, rolling restart and the /metrics
endpoint — over whatever schedulers its subclass names. `OnlineFrontend`
drives ONE engine (with `plan_broadcast` and `on_failure`);
`DisaggOnlineFrontend` drives a `DisaggRouter`'s replica classes: arrivals
route to prefill replicas, finished prefills migrate as page-granular KV
handoffs, decode replicas stream. Each says only what differs: where an
arrival lands, how a rid is cancelled, what counts as work left, how
recovered work comes back, and its `_drive`. Every loop plans through
`ServingEngine.plan_turn`, which owns the `step.plan` span.
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import hashlib
import time

import numpy as np

from automodel_tpu.observability import NULL_OBSERVABILITY
from automodel_tpu.resilience.faults import FaultError
from automodel_tpu.serving.plan_wire import pack_plan, pack_stop
from automodel_tpu.serving.resilience import (
    ReplicaFailure,
    RetryBudgetExhausted,
)
from automodel_tpu.serving.scheduler import Request, Scheduler


#: headroom factor on the steps-to-first-token estimate (shed when
#: step + safety * est_steps >= deadline); >1 would shed earlier
SHED_SAFETY = 1.0


@dataclasses.dataclass(frozen=True)
class FrontendConfig:
    """Typed `serving.online` section."""

    #: tokens a consumer may lag before its slot is withheld from plans
    stream_buffer: int = 32
    #: hard cap on queued (waiting) requests — beyond it new arrivals shed
    #: immediately regardless of deadline; None → deadline shedding only
    max_waiting: int | None = None
    #: deadline-aware admission control + waiting-queue early expiry
    shed_deadlines: bool = True
    #: event-loop sleep while nothing is runnable
    idle_sleep_s: float = 0.001
    #: close(): finish resident work (True) or cancel it (False)
    drain: bool = True

    def __post_init__(self):
        if self.stream_buffer < 1:
            raise ValueError("stream_buffer must be >= 1")
        if self.max_waiting is not None and self.max_waiting < 1:
            raise ValueError("max_waiting must be >= 1 (or None)")


class TokenStream:
    """Async iterator over one request's committed tokens, in commit
    order. Ends (StopAsyncIteration) when the request finishes for ANY
    reason — `finish_reason` then says which: "eos"/"length" (normal),
    "timed_out" (deadline eviction), "shed" (admission control — the
    shed counter's `reason` label subdivides: deadline / queue_full /
    draining / no_replica / closed), "cancelled" (client disconnect),
    "rejected" (invalid request). A stream that survived a replica death
    finishes with its NORMAL reason — `recovered` > 0 is the
    failed-and-recovered marker (tokens are never lost or duplicated)."""

    def __init__(self, req: Request):
        self.request = req
        self._q: asyncio.Queue = asyncio.Queue()
        self._done = False

    @property
    def rid(self) -> int:
        return self.request.rid

    @property
    def finish_reason(self):
        return self.request.finish_reason

    @property
    def recovered(self) -> int:
        """Times this stream's compute was evacuated off a dead replica
        and requeued onto a survivor (recovery is invisible to a greedy
        consumer except as latency)."""
        return self.request.recovered

    def __aiter__(self):
        return self

    async def __anext__(self) -> int:
        if self._done:
            raise StopAsyncIteration
        tok = await self._q.get()
        if tok is None:
            self._done = True
            raise StopAsyncIteration
        return tok

    async def collect(self) -> list:
        """Drain the stream to a plain token list (testing convenience)."""
        return [t async for t in self]

    # frontend-internal
    def _push(self, tok: int) -> None:
        self._q.put_nowait(tok)

    def _end(self) -> None:
        self._q.put_nowait(None)

    def _lag(self) -> int:
        """Tokens committed but not yet consumed."""
        return self._q.qsize()


def _trace_pause_edges(tracer, track: str, step: int,
                       prev: set, now: set) -> None:
    """Emit stream.pause / stream.resume instants only on EDGES of the
    per-turn paused set — the timeline layer pairs them into intervals
    to subtract consumer backpressure from TTFT/ITL attribution."""
    for rid in now - prev:
        tracer.instant("stream.pause", track=track, step=step, rid=rid)
    for rid in prev - now:
        tracer.instant("stream.resume", track=track, step=step, rid=rid)


async def _handle_metrics_http(frontend, reader, writer) -> None:
    """Minimal one-shot HTTP handler: GET /metrics serves the registry's
    Prometheus text exposition (gauges refreshed via stats() first) and
    GET /healthz reports liveness. Deliberately tiny — no routing library,
    no keep-alive — because it shares the serve event loop and must never
    be able to stall it."""
    try:
        request = await reader.readline()
        while True:  # drain headers; we never need them
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
        parts = request.split()
        path = parts[1].decode("ascii", "replace") if len(parts) > 1 else "/"
        if path == "/metrics":
            frontend.stats()  # refresh gauges before snapshotting
            body = frontend.obs.registry.snapshot_prometheus().encode()
            status, ctype = b"200 OK", b"text/plain; version=0.0.4"
        elif path == "/healthz":
            body = b"closed\n" if frontend._closed else b"ok\n"
            status, ctype = b"200 OK", b"text/plain"
        else:
            body, status, ctype = b"not found\n", b"404 Not Found", b"text/plain"
        writer.write(
            b"HTTP/1.1 " + status + b"\r\nContent-Type: " + ctype
            + b"\r\nContent-Length: " + str(len(body)).encode()
            + b"\r\nConnection: close\r\n\r\n" + body
        )
        await writer.drain()
    except Exception:  # pragma: no cover — a bad client must not kill serving
        pass
    finally:
        writer.close()


class FrontendBase:
    """The client side of an online serve loop, the same over one engine
    or many: the stream table, arrivals and cancels, the shedding rule,
    back-pressure, token delivery, close, rolling restart and the /metrics
    endpoint. It walks whatever schedulers its subclass names and never an
    engine.

    A subclass names its schedulers to the constructor:

    - `scheds`: every scheduler a turn walks;
    - `arrival_scheds`: those whose waiting queues hold fresh arrivals
      (early expiry walks them);
    - `sink`: the scheduler whose `finished` list files a request that no
      scheduler holds (shed at the door, cancelled or expired in between);

    and defines `_place` (where an arrival lands), `_cancel_now`,
    `_has_work`, `_drain_recovered` / `_recovery_backlog` (how work
    evacuated off a dead replica comes back), `_drive`, and what it adds
    to `_pending_deadlines`, `_after_close` and `stats`."""

    #: idle close-drain turns tolerated before stalled work is cancelled
    CLOSE_STALL_TURNS = 200

    def __init__(self, cfg: FrontendConfig, *, scheds: list,
                 arrival_scheds: list, sink: Scheduler, obs,
                 draft_len: int, name: str):
        self.cfg = cfg
        self.name = name
        self.obs = obs
        self._scheds = scheds
        self._arrival_scheds = arrival_scheds
        self._sink = sink
        self.step_idx = 0
        self.steps_run = 0
        self._draft_len = draft_len
        if cfg.stream_buffer <= draft_len:
            raise ValueError(
                f"stream_buffer={cfg.stream_buffer} must exceed the "
                f"speculative draft_len={draft_len} — a verify block "
                "commits up to draft_len+1 tokens at once"
            )
        #: rid → (Request, TokenStream) for every live (unfinished) request
        self._active: dict[int, tuple[Request, TokenStream]] = {}
        self._emitted: dict[int, int] = {}       # rid → tokens pushed
        self._arrivals: asyncio.Queue = asyncio.Queue()
        self._cancels: list[int] = []
        self._next_rid = 0
        self._closed = False
        self._draining = False                   # rolling-restart admission stop
        self._task: asyncio.Task | None = None
        self._step_waiter: asyncio.Event = asyncio.Event()
        self._idle_close = 0
        self._paused_rids: set = set()         # pause/resume edge detection
        self._http_server = None
        self._http_task: asyncio.Task | None = None
        self.http_port: int | None = None      # bound /metrics port, once up
        # counters / reporting
        self.n_submitted = 0
        self.n_shed = 0
        self.n_rejected = 0
        self.n_recovered = 0                     # requeued here after a death
        self._last_emit_t: float | None = None   # the last turn that committed

    # -- client API ---------------------------------------------------------
    def submit(self, req: Request, *, deadline_in: int | None = None
               ) -> TokenStream:
        """Enqueue one request mid-flight; returns its stream immediately.
        `deadline_in` (engine steps from ADMISSION) is the online-friendly
        way to set a deadline — absolute step indices are meaningless to a
        client that cannot see the loop's counter."""
        if self._closed:
            raise RuntimeError("frontend is closed")
        if req.rid < 0:
            req.rid = self._next_rid
        self._next_rid = max(self._next_rid, req.rid + 1)
        # its entry into the program: TTFT and the request markers'
        # `since_submit_us` count from here, as the client does
        req.arrived_t = time.perf_counter()
        stream = TokenStream(req)
        self.n_submitted += 1
        self.obs.registry.counter(
            "frontend_submitted_total", "requests submitted to the frontend"
        ).inc()
        self.obs.tracer.instant(
            "frontend.submit", track=self.name, step=self.step_idx,
            rid=req.rid, prompt_len=len(req.prompt),
            max_new=req.max_new_tokens,
        )
        self._arrivals.put_nowait((req, stream, deadline_in))
        return stream

    def cancel(self, rid: int) -> None:
        """Client disconnect: the request is evicted at the top of the
        next turn (before the next plan is built — a plan in flight still
        references its pages), freeing its slot pages the same turn."""
        self._cancels.append(rid)

    def start(self):
        if self._task is None:
            self._task = asyncio.ensure_future(self._drive())
            if self.obs.cfg.http_port is not None:
                self._http_task = asyncio.ensure_future(self._serve_http())
        return self

    async def close(self) -> dict:
        """Stop accepting work; drain (or cancel, per cfg.drain) what is
        resident; stop the drive task. Returns final stats."""
        self._closed = True
        if self._task is not None:
            await self._task
            self._task = None
        if self._http_task is not None:
            await self._http_task
            self._http_task = None
        if self._http_server is not None:
            self._http_server.close()
            await self._http_server.wait_closed()
            self._http_server = None
        self._after_close()
        return self.stats()

    def _after_close(self) -> None:
        """What a subclass sends once its drive task has ended."""

    async def __aenter__(self):
        return self.start()

    async def __aexit__(self, *exc) -> None:
        await self.close()

    async def wait_step(self, n: int) -> None:
        """Block until the loop has started turn `n` (trace pacing for
        tests/harnesses: submit exactly when the counter says so)."""
        while self.step_idx < n:
            await self._step_waiter.wait()

    # -- rolling restart -----------------------------------------------------
    def drain(self) -> None:
        """Stop ADMITTING (new arrivals shed as "draining") while the
        loop keeps running and resident requests (and in-flight handoffs)
        finish and flush their streams — the first half of a rolling
        restart. Unlike `close()`, the frontend stays alive;
        `resume_admission()` reopens it."""
        self._draining = True

    def resume_admission(self) -> None:
        self._draining = False

    async def quiesce(self) -> None:
        """`drain()` and block until nothing is resident (requests
        finished, handoffs landed, streams flushed, queues empty): the
        point where the process behind a replica can restart without
        dropping work."""
        self.drain()
        while self._has_work or not self._arrivals.empty():
            await self.wait_step(self.step_idx + 1)

    # -- the turn's shared halves -------------------------------------------
    def _intake(self) -> bool:
        """Top of a turn, once cancels are applied: recovered work, then
        fresh arrivals, then early expiry of what queued too long. False
        when the frontend is closed and nothing is left to serve."""
        self._drain_arrivals()
        self._shed_waiting()
        if self._closed:
            if not self.cfg.drain:
                self._abort_resident()
            if not self._has_work:
                return False
        return True

    async def _idle_turn(self) -> None:
        """A turn that planned nothing. Deadline expiry inside schedule()
        may still have finished work, so streams are served first."""
        self._last_emit_t = None
        self._emit()
        self._advance()
        if self._closed and self._has_work:
            # close-drain with nothing runnable: consumers that stopped
            # reading (paused slots) or a pool-blocked queue would hang
            # the drain forever — give them a grace window of idle turns,
            # then cancel stragglers (unless a pending deadline will
            # resolve it first)
            self._idle_close += 1
            if (
                self._idle_close > self.CLOSE_STALL_TURNS
                and not any(d is not None for d in self._pending_deadlines())
            ):
                self._abort_resident()
        await asyncio.sleep(self.cfg.idle_sleep_s)

    def _pending_deadlines(self) -> list:
        return [s.next_deadline for s in self._scheds]

    def _note_itl(self, n_new: int) -> None:
        """Once a turn that committed tokens: the wall time since the last
        such turn, the gap every decoding stream sees between two tokens
        (an idle turn starts the count anew)."""
        if not n_new:
            return
        now = time.perf_counter()
        if self._last_emit_t is not None:
            self.obs.registry.histogram(
                "request_itl_ms", "inter-token latency (ms)"
            ).observe((now - self._last_emit_t) * 1e3)
        self._last_emit_t = now

    def _advance(self) -> None:
        self.step_idx += 1
        waiter, self._step_waiter = self._step_waiter, asyncio.Event()
        waiter.set()

    # -- cancellation --------------------------------------------------------
    def _apply_cancels(self) -> None:
        cancels, self._cancels = self._cancels, []
        for rid in cancels:
            self._cancel_now(rid)

    def _abort_resident(self) -> None:
        for rid in list(self._active):
            self._cancel_now(rid)

    def _count_cancel(self) -> None:
        self.obs.registry.counter(
            "frontend_cancelled_total", "streams cancelled by the caller"
        ).inc()

    def _retire(self, req: Request, reason: str) -> None:
        """File a request that no scheduler holds under the sink's
        `finished` (with its counter, for the two reasons a scheduler
        counts) and end its stream."""
        req.finish_reason = reason
        req.finished_at = self.step_idx
        self._sink.finished.append(req)
        if reason == "cancelled":
            self._sink.n_cancelled += 1
            self._count_cancel()
        elif reason == "timed_out":
            self._sink.n_timed_out += 1
        self._finish_stream(req.rid)

    # -- admission / shedding ------------------------------------------------
    def _drain_arrivals(self) -> None:
        self._drain_recovered()
        while not self._arrivals.empty():
            req, stream, deadline_in = self._arrivals.get_nowait()
            self._active[req.rid] = (req, stream)
            self._emitted[req.rid] = 0
            if deadline_in is not None:
                req.deadline = self.step_idx + deadline_in
            if self._closed or self._draining:
                self._shed_one(
                    req, "shed",
                    why="closed" if self._closed else "draining",
                )
                continue
            self._place(req, fresh=True)

    def _admit(self, req: Request, sched: Scheduler, *, fresh: bool) -> bool:
        """Judge `req` against the scheduler it would land in and submit
        it there, or shed it at the door. A recovered request (`fresh`
        false) skips the queue cap — it was admitted once already — and
        is held to its deadline against the survivor's queues PLUS the
        recovery backlog still buffered behind it: it re-prefills its
        whole `known`."""
        if (
            fresh and self.cfg.max_waiting is not None
            and len(sched.waiting) >= self.cfg.max_waiting
        ):
            self._shed_one(req, "shed", why="queue_full")
            return False
        if self.cfg.shed_deadlines and not self._reachable(
            req,
            self._backlog(sched) + self._waiting_backlog(sched)
            + self._recovery_backlog(),
            sched,
        ):
            self._shed_one(req, "shed", why="deadline")
            return False
        try:
            sched.submit(req)
        except ValueError:
            # oversized/invalid request: surface as a rejected stream
            # instead of crashing the loop every other client shares
            self._shed_one(req, "rejected")
            return False
        return True

    def _note_recovered(self, req: Request, **args) -> None:
        self.n_recovered += 1
        self.obs.registry.counter(
            "serve_requests_recovered_total",
            "requests requeued onto survivors after a replica death",
        ).inc()
        self.obs.registry.counter(
            "serve_recovery_reprefill_tokens_total",
            "known tokens requeued for re-prefill by failure recovery",
        ).inc(req.known_len)
        self.obs.tracer.instant(
            "request.adopt", track=self.name, step=self.step_idx,
            rid=req.rid, known=req.known_len, **args,
        )

    def _shed_one(self, req: Request, reason: str,
                  why: str | None = None) -> None:
        if reason == "rejected":
            self.n_rejected += 1
            self.obs.registry.counter(
                "frontend_rejected_total", "submissions rejected at admission"
            ).inc()
        else:
            self.n_shed += 1
            self.obs.registry.counter(
                "frontend_shed_total", "requests shed (labeled by reason)",
                reason=why or reason,
            ).inc()
        self.obs.tracer.instant(
            "request.shed", track=self.name, step=self.step_idx,
            rid=req.rid, reason=why or reason,
        )
        self._retire(req, reason)

    @staticmethod
    def _backlog(sched: Scheduler) -> int:
        """Unfed tokens resident on device (running prefill remainder)."""
        return sum(
            max(r.known_len - r.fed, 0) for r in sched.running.values()
        )

    @staticmethod
    def _waiting_backlog(sched: Scheduler) -> int:
        return sum(r.known_len - r.fed for r in sched.waiting)

    def _reachable(self, req: Request, backlog: int,
                   sched: Scheduler) -> bool:
        """Can `req` plausibly commit even ONE token before its deadline?
        The queued prefill backlog plus its own prompt must flow through
        `sched`'s token budget first; a request that cannot clear that
        by its deadline would only occupy pool pages and die, so it sheds
        at the door. Pure step arithmetic — identical traces shed
        identical sets."""
        if req.deadline is None:
            return True
        pending = req.known_len - req.fed
        est = -(-(SHED_SAFETY * (backlog + pending)) // sched.token_budget)
        return self.step_idx + int(est) < req.deadline

    def _shed_waiting(self) -> None:
        """Early-expire waiting requests whose deadline became unreachable
        while they queued (load grew ahead of them) — the 'early-expire'
        half of shedding: they exit NOW as shed instead of burning pool
        time later as timed_out."""
        if not self.cfg.shed_deadlines:
            return
        for sched in self._arrival_scheds:
            backlog = self._backlog(sched) + self._recovery_backlog()
            for req in list(sched.waiting):
                if not self._reachable(req, backlog, sched):
                    sched.waiting.remove(req)
                    self._shed_one(req, "shed", why="deadline")
                else:
                    backlog += req.known_len - req.fed

    # -- streaming ----------------------------------------------------------
    def _apply_backpressure(self) -> None:
        """Withhold any slot whose consumer lacks room for this step's
        worst-case commit (1 token, +draft_len speculative): its stream
        queue never exceeds stream_buffer + one verify block, and the
        step loop never blocks on a slow reader."""
        room_needed = 1 + self._draft_len
        now_paused = set()
        for sched in self._scheds:
            sched.paused.clear()
            for slot, req in sched.running.items():
                entry = self._active.get(req.rid)
                if entry is None:
                    continue
                if entry[1]._lag() + room_needed > self.cfg.stream_buffer:
                    sched.paused.add(slot)
                    now_paused.add(req.rid)
        _trace_pause_edges(
            self.obs.tracer, self.name, self.step_idx,
            self._paused_rids, now_paused,
        )
        self._paused_rids = now_paused

    def _emit(self) -> None:
        """Push newly committed tokens to their streams, in commit order;
        end the stream of everything that finished this turn (a request
        mid-migration is neither running nor done: its stream ends only
        once a terminal finish_reason lands)."""
        for rid, (req, stream) in list(self._active.items()):
            sent = self._emitted[rid]
            new = req.generated[sent:]
            if new:
                if req.ttft_s < 0 and req.arrived_t >= 0:
                    req.ttft_s = time.perf_counter() - req.arrived_t
                    self.obs.registry.histogram(
                        "request_ttft_ms", "time to first token (ms)"
                    ).observe(req.ttft_s * 1e3)
                for tok in new:
                    stream._push(tok)
                self._emitted[rid] = sent + len(new)
            if req.done:
                self._finish_stream(rid)

    def _finish_stream(self, rid: int) -> None:
        entry = self._active.pop(rid, None)
        self._emitted.pop(rid, None)
        if entry is not None:
            entry[1]._end()
            self.obs.registry.counter(
                "frontend_finished_total", "streams finished (any reason)"
            ).inc()
            if rid in self._paused_rids:
                # close the open pause so the timeline's pause intervals pair
                self._paused_rids.discard(rid)
                self.obs.tracer.instant(
                    "stream.resume", track=self.name,
                    step=self.step_idx, rid=rid,
                )

    # -- metrics endpoint ----------------------------------------------------
    async def _serve_http(self) -> None:
        self._http_server = await asyncio.start_server(
            functools.partial(_handle_metrics_http, self), "127.0.0.1",
            self.obs.cfg.http_port,
        )
        self.http_port = self._http_server.sockets[0].getsockname()[1]

    async def http_address(self) -> tuple:
        """(host, port) of the /metrics endpoint, once it is listening."""
        if self._http_task is not None:
            await self._http_task
        if self.http_port is None:
            raise RuntimeError("observability.http_port is not configured")
        return ("127.0.0.1", self.http_port)

    # -- reporting ----------------------------------------------------------
    def stats(self) -> dict:
        """The keys both frontends report, summed over `_scheds`; also
        refreshes the frontend's gauges on /metrics."""
        scheds = self._scheds
        running = sum(len(s.running) for s in scheds)
        waiting = sum(len(s.waiting) for s in scheds)
        reg = self.obs.registry
        reg.gauge("frontend_running", "requests resident in slots"
                  ).set(running)
        reg.gauge("frontend_waiting", "requests queued for admission"
                  ).set(waiting)
        reg.gauge("frontend_paused", "slots paused for stream backpressure"
                  ).set(sum(len(s.paused) for s in scheds))
        reasons: dict = {}
        for s in scheds:
            for r in s.finished:
                reasons[r.finish_reason] = reasons.get(r.finish_reason, 0) + 1
        return {
            "steps": self.steps_run,
            "submitted": self.n_submitted,
            "finished": sum(len(s.finished) for s in scheds),
            "finish_reasons": reasons,
            "shed": self.n_shed,
            "rejected": self.n_rejected,
            "recovered": self.n_recovered,
            "draining": self._draining,
            "cancelled": sum(s.n_cancelled for s in scheds),
            "timed_out": sum(s.n_timed_out for s in scheds),
            "running": running,
            "waiting": waiting,
        }


class OnlineFrontend(FrontendBase):
    """Async streaming serve loop over ONE engine (single-chip or a
    tp/ep-sharded mesh slice). `start()` launches the drive task;
    `submit()` returns a live TokenStream; `close()` drains and stops.

    `plan_broadcast` (serving/plan_wire.py transport, lead side) turns
    this into the lead process of a multi-host replica: every plan is
    broadcast before it runs, and the stop frame is sent on close."""

    def __init__(
        self,
        engine,
        cfg: FrontendConfig = FrontendConfig(),
        *,
        plan_broadcast=None,
        name: str = "frontend",
    ):
        self.engine = engine
        self.sched: Scheduler = engine.make_scheduler(arrival_gating=False)
        super().__init__(
            cfg,
            scheds=[self.sched], arrival_scheds=[self.sched],
            sink=self.sched,
            # share the engine's bundle (same registry/tracer)
            obs=getattr(engine, "obs", None) or NULL_OBSERVABILITY,
            draft_len=(
                engine._spec.draft_len if engine._spec is not None else 0
            ),
            name=name,
        )
        self.plan_broadcast = plan_broadcast
        #: (req, stream, emitted) evacuated off a DEAD replica, buffered by
        #: `adopt()` until the top of the next turn (drained before fresh
        #: arrivals, in adoption order — deterministic requeue)
        self._adopted: list = []
        #: router-installed replica-death handler (serving/resilience.py):
        #: called with (self, exc) when the jitted step raises; None →
        #: the error propagates out of the drive task unchanged
        self.on_failure = None
        self._sha = hashlib.sha1()             # lockstep digest (broadcast)

    def _after_close(self) -> None:
        if self.plan_broadcast is not None:
            sc = self.engine.serve_cfg
            self.plan_broadcast.send(pack_stop(
                sc.token_budget, sc.max_slots, sc.pages_per_slot,
                self._draft_len or None,
            ))

    @property
    def digest(self) -> str:
        """sha1 over every step's sampled-token output — matches the
        followers' PlanFollower digest when the broadcast is lockstep."""
        return self._sha.hexdigest()

    @property
    def _has_work(self) -> bool:
        return self.sched.has_work or bool(self._adopted)

    # -- drive loop ---------------------------------------------------------
    async def _drive(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            # every span of a turn carries the number `engine.run_step`
            # will stamp on this turn's `step.run` (idle turns advance
            # `step_idx` but not it), and none is held across an `await`:
            # other coroutines run on this thread meanwhile
            span = functools.partial(
                self.obs.tracer.span, track=self.name,
                step=self.engine.steps_run,
            )
            with span("frontend.intake"):
                self._apply_cancels()
                if not self._intake():
                    break
                self._apply_backpressure()
            plan = self.engine.plan_turn(self.sched, self.step_idx)
            if plan is None:
                await self._idle_turn()
                continue
            self._idle_close = 0
            if self.plan_broadcast is not None:
                self.plan_broadcast.send(pack_plan(
                    plan,
                    pages_per_slot=self.engine.serve_cfg.pages_per_slot,
                    draft_len=self._draft_len or None,
                ))
            t0 = time.perf_counter()
            try:
                out = await loop.run_in_executor(
                    None, functools.partial(self.engine.run_step, plan)
                )
            except RuntimeError as e:
                # replica death (injected serve_step_run fault or a real
                # runtime failure; FaultCrash is a BaseException and still
                # propagates): dump the flight recorder and hand the wreck
                # to the router's handler, which evacuates this scheduler
                # and re-adopts the live streams onto survivors. This loop
                # is done either way.
                if self.on_failure is None:
                    raise
                self._closed = True
                self.obs.tracer.instant(
                    "replica.death", track=self.name, step=self.step_idx,
                    reason=type(e).__name__,
                )
                self.obs.flight_dump("replica_death")
                self.on_failure(self, e)
                return
            dt = time.perf_counter() - t0
            self.obs.observe_step(self.step_idx, dt * 1e3)
            self._sha.update(np.ascontiguousarray(out[0]).tobytes())
            with span("step.absorb"):
                n_new = self.engine.absorb_outputs(
                    self.sched, plan, out, self.step_idx
                )
            self.steps_run += 1
            self._note_itl(n_new)
            with span("frontend.emit"):
                self._emit()
                self._advance()

    def _place(self, req: Request, *, fresh: bool) -> bool:
        return self._admit(req, self.sched, fresh=fresh)

    def _cancel_now(self, rid: int) -> None:
        # adopted-but-not-yet-requeued (mid-recovery) cancels land here
        for entry in list(self._adopted):
            if entry[0].rid == rid:
                self._adopted.remove(entry)
                self._active.setdefault(rid, (entry[0], entry[1]))
                self._emitted.setdefault(rid, entry[2])
                self._retire(entry[0], "cancelled")
                return
        if self.sched.cancel(rid, self.step_idx):
            self._count_cancel()
            self._finish_stream(rid)

    # -- failure recovery ----------------------------------------------------
    def adopt(self, req: Request, stream: TokenStream, emitted: int) -> None:
        """Take over a live stream evacuated off a DEAD replica (router's
        failure handler): buffered, then requeued at the top of this
        loop's next turn — before fresh arrivals, in adoption order, so
        identical chaos traces build identical queues. `emitted` preserves
        the token count the dead frontend already pushed: re-prefill
        regenerates the full `known` sequence but the stream only ever
        sees the continuation."""
        self._adopted.append((req, stream, emitted))

    def _drain_recovered(self) -> None:
        while self._adopted:
            req, stream, emitted = self._adopted.pop(0)
            self._active[req.rid] = (req, stream)
            self._emitted[req.rid] = emitted
            self._next_rid = max(self._next_rid, req.rid + 1)
            if self._place(req, fresh=False):
                self._note_recovered(req, emitted=emitted)

    def _recovery_backlog(self) -> int:
        """Re-prefill tokens adopted but not yet queued anywhere — the
        term mid-recovery shed arithmetic must price in."""
        return sum(r.known_len - r.fed for r, _s, _e in self._adopted)

    # -- reporting ----------------------------------------------------------
    def stats(self) -> dict:
        s = self.sched
        return {
            **super().stats(),
            "preemptions": s.n_preemptions,
            "paused": len(s.paused),
            "free_pages": s.alloc.num_free,
            "compiled_signatures": self.engine.step_cache_size(),
        }


class DisaggOnlineFrontend(FrontendBase):
    """The live loop over a `DisaggRouter`'s replica classes: arrivals
    route to a prefill replica, finished prefills migrate to a decode
    replica as page-granular KV handoffs, decode replicas stream.

    One drive task owns every scheduler (the handoff dance needs a
    consistent view of both classes each turn); engine steps for all
    replicas of a turn run back-to-back in the worker thread. An arrival
    is judged against the LEAST-LOADED prefill replica's backlog (that is
    where it would land); cancellation and deadline expiry also release
    the page pins of a handoff in flight between the classes."""

    def __init__(self, router, cfg: FrontendConfig = FrontendConfig()):
        self.router = router
        self.p_scheds = [
            eng.make_scheduler(arrival_gating=False) for eng in router.prefill
        ]
        self.d_scheds = [
            eng.make_scheduler(arrival_gating=False) for eng in router.decode
        ]
        super().__init__(
            cfg,
            scheds=self.p_scheds + self.d_scheds,
            arrival_scheds=self.p_scheds, sink=self.d_scheds[0],
            # router-shared bundle when the router built one; else borrow
            # the first prefill engine's (every engine owns a null one)
            obs=(
                getattr(router, "obs", None)
                or getattr(router.prefill[0], "obs", None)
                or NULL_OBSERVABILITY
            ),
            draft_len=max(
                (e._spec.draft_len for e in router.decode
                 if e._spec is not None),
                default=0,
            ),
            name="frontend",
        )
        #: rids prefill-ROUTED to each borrowed decode replica (autoscale):
        #: the extract_handoffs(rids=...) guard — only these migrate out,
        #: the replica's resident decode work is never evacuated
        self._borrow_rids: dict[int, set] = {}
        self.inflight: list = []
        #: requests evacuated off a dead replica (or rolled back from an
        #: exhausted transfer), requeued at the top of the next turn —
        #: before fresh arrivals, in evacuation order (deterministic)
        self._requeued: list = []
        self.n_cancelled_inflight = 0

    @property
    def _has_work(self) -> bool:
        return bool(self.inflight) or bool(self._requeued) or any(
            s.has_work for s in self._scheds
        )

    def _pending_deadlines(self) -> list:
        return super()._pending_deadlines() + [
            h.req.deadline for h in self.inflight
        ]

    # -- drive --------------------------------------------------------------
    async def _drive(self) -> None:
        # runtime import: router imports this module at its top level
        from automodel_tpu.serving.router import _Handoff

        loop = asyncio.get_running_loop()
        while True:
            self._apply_cancels()
            self.router.autoscale_tick(
                self.p_scheds, self.d_scheds, self.step_idx
            )
            if not self._intake():
                break
            self._expire_inflight()
            self._admit_inflight()
            self._apply_backpressure()
            plans = []
            for sched, eng in zip(
                self.d_scheds + self.p_scheds,
                self.router.decode + self.router.prefill,
            ):
                if not self.router.health.alive(eng.track):
                    continue
                if not sched.has_work:
                    continue
                plan = eng.plan_turn(sched, self.step_idx)
                if plan is not None:
                    plans.append((eng, sched, plan))
            if not plans:
                await self._idle_turn()
                continue
            self._idle_close = 0
            t0 = time.perf_counter()
            outs = await loop.run_in_executor(
                None, functools.partial(self._run_plans, plans)
            )
            dt = time.perf_counter() - t0
            self.obs.observe_step(self.step_idx, dt * 1e3)
            n_new = 0
            for eng, sched, plan, out, exc in outs:
                if exc is None:
                    n_new += eng.absorb_outputs(
                        sched, plan, out, self.step_idx
                    )
            # replica deaths AFTER the survivors' outputs are absorbed
            # (their tokens this turn are real and must land)
            for eng, sched, plan, out, exc in outs:
                if exc is None:
                    continue
                if not self.router.resilience.enabled:
                    raise exc
                if sched in self.p_scheds:
                    self._recover_replica("p", self.p_scheds.index(sched), exc)
                else:
                    self._recover_replica("d", self.d_scheds.index(sched), exc)
            for r, sched in enumerate(self.p_scheds):
                for req, n_tok, src in sched.extract_handoffs():
                    self.inflight.append(_Handoff(req, n_tok, src, r))
            # borrowed replicas extract ONLY their prefill-routed rids
            for j, rids in self._borrow_rids.items():
                rids.intersection_update(self._active)  # drop finished
                if not rids:
                    continue
                for req, n_tok, src in self.d_scheds[j].extract_handoffs(
                    rids=rids
                ):
                    rids.discard(req.rid)
                    self.inflight.append(_Handoff(req, n_tok, src, ("d", j)))
            self.steps_run += 1
            self._note_itl(n_new)
            self._emit()
            self._advance()

    @staticmethod
    def _run_plans(plans):
        """Executor body: every replica's step back-to-back, capturing
        per-replica RuntimeErrors (injected `serve_step_run` deaths, real
        XLA failures) so one dead replica cannot mask the survivors'
        outputs for the turn. FaultCrash — a BaseException simulating the
        whole PROCESS dying — still propagates and kills the loop."""
        outs = []
        for eng, sched, plan in plans:
            try:
                outs.append((eng, sched, plan, eng.run_step(plan), None))
            except RuntimeError as e:
                outs.append((eng, sched, plan, None, e))
        return outs

    # -- admission ------------------------------------------------------------
    def _route_scheds(self):
        """The prefill ROUTING SET, health-aware: admittable prefill
        replicas plus any autoscaler-borrowed decode replicas — or, when
        the whole prefill class is gone and degradation is on, the
        admittable decode replicas taking prefill chunks directly
        (monolithic collapse: the request completes in place, no handoff
        and no borrow-rid registration, so nothing is extracted).
        Returns (schedulers, tag-per-entry) — tag None for a prefill
        replica, int j for borrowed decode j, "mono" for degraded — or
        None when nothing can admit."""
        h = self.router.health
        scheds: list = []
        tags: list = []
        for i, s in enumerate(self.p_scheds):
            if h.admittable(self.router.prefill[i].track):
                scheds.append(s)
                tags.append(None)
        for j in sorted(self.router.borrowed):
            if h.admittable(self.router.decode[j].track):
                scheds.append(self.d_scheds[j])
                tags.append(j)
        if scheds:
            return scheds, tags
        if not self.router.degraded:
            return None
        for j, s in enumerate(self.d_scheds):
            if h.admittable(self.router.decode[j].track):
                scheds.append(s)
                tags.append("mono")
        return (scheds, tags) if scheds else None

    def _place(self, req: Request, *, fresh: bool) -> bool:
        route = self._route_scheds()
        if route is None:
            # nothing can admit and degradation is off/exhausted —
            # shed loudly-labeled rather than queueing into a wedge
            self._shed_one(req, "shed", why="no_replica")
            return False
        route_scheds, tags = route
        r = self.router.route_prefill(req, route_scheds)
        if not self._admit(req, route_scheds[r], fresh=fresh):
            return False
        if isinstance(tags[r], int):
            self._borrow_rids.setdefault(tags[r], set()).add(req.rid)
        return True

    def _drain_recovered(self) -> None:
        while self._requeued:
            req = self._requeued.pop(0)
            if self._place(req, fresh=False):
                self._note_recovered(req)

    def _recovery_backlog(self) -> int:
        return sum(r.known_len - r.fed for r in self._requeued)

    # -- failure recovery ----------------------------------------------------
    def _recover_replica(self, klass: str, r: int, exc) -> None:
        """Replica death in the live loop: health-board death + flight
        dump, evacuate the scheduler, drop handoff pins rooted there, and
        requeue everything onto survivors at the top of the next turn.
        Streams stay attached throughout — a greedy client sees recovery
        only as latency. Decode extinction is the one unabsorbable loss
        and raises `ReplicaFailure` out of the drive task."""
        engines = self.router.prefill if klass == "p" else self.router.decode
        scheds = self.p_scheds if klass == "p" else self.d_scheds
        name = engines[r].track
        if self.router.health.alive(name):
            self.router.health.mark_dead(name, self.step_idx, repr(exc))
        self.obs.tracer.instant(
            "replica.death", track=name, step=self.step_idx,
            reason=type(exc).__name__,
        )
        self.obs.flight_dump("replica_death")
        evac = scheds[r].evacuate()
        src = r if klass == "p" else ("d", r)
        for h in list(self.inflight):
            if h.src == src:
                self._drop_inflight(h)
                h.req.fed = 0
                h.req.donated_pages = 0
                evac.append(h.req)
        if klass == "d":
            # a dead decode replica can no longer be a borrowed prefill
            self._borrow_rids.pop(r, None)
            self.router.borrowed.discard(r)
        self.router._tick_degraded_gauge(self.step_idx)
        if not any(
            self.router.health.admittable(e.track)
            for e in self.router.decode
        ):
            raise ReplicaFailure(
                "decode", "no decode-class replicas left alive"
            ) from exc
        for q in evac:
            q.recovered += 1
            self._requeued.append(q)

    def _transfer_exhausted(self, h, r: int, exc) -> None:
        """The retry budget around this handoff's KV page transfer ran
        dry: escalate to the health board (degraded, dead after
        `degraded_failures` strikes), roll the decode admission back
        WITHOUT donating (the pages may hold a partial copy), drop the
        source pins, and requeue for a full re-prefill."""
        name = self.router.decode[r].track
        state = self.router.health.mark_exhausted(
            name, self.step_idx, str(exc)
        )
        self.d_scheds[r].evict_for_recovery(h.req.rid)
        self._drop_inflight(h)
        h.req.recovered += 1
        self._requeued.append(h.req)
        self.obs.tracer.instant(
            "transfer.exhausted", track=name, step=self.step_idx,
            rid=h.req.rid, state=state,
        )
        if state == "dead":
            self._recover_replica("d", r, exc)

    # -- cancellation --------------------------------------------------------
    def _cancel_now(self, rid: int) -> None:
        # evacuated-but-not-yet-requeued (mid-recovery) cancels land here
        for q in list(self._requeued):
            if q.rid == rid:
                self._requeued.remove(q)
                self._retire(q, "cancelled")
                return
        # a handoff in flight: its prefill-side page pins drop THIS turn
        for h in list(self.inflight):
            if h.req.rid == rid:
                self._drop_inflight(h)
                self.n_cancelled_inflight += 1
                self.obs.tracer.instant(
                    "request.cancel", track=self.name, step=self.step_idx,
                    rid=rid, inflight=1,
                )
                self._retire(h.req, "cancelled")
                return
        for rids in self._borrow_rids.values():
            rids.discard(rid)
        for sched in self._scheds:
            if sched.cancel(rid, self.step_idx):
                self._count_cancel()
                self._finish_stream(rid)
                return

    # -- handoffs ------------------------------------------------------------
    def _src_sched(self, h):
        """Scheduler owning a handoff's page pins: a prefill replica, or a
        borrowed decode replica (src tagged ("d", j) by the autoscaler)."""
        if isinstance(h.src, tuple):
            return self.d_scheds[h.src[1]]
        return self.p_scheds[h.src]

    def _drop_inflight(self, h) -> None:
        """A handoff leaves flight (admitted, cancelled, expired, rolled
        back): its source-side page pins are released."""
        self.inflight.remove(h)
        self._src_sched(h).release_handoff(h.src_pages)

    def _transfer(self, h, r):
        if isinstance(h.src, tuple):
            return self.router.decode_transfer(h.src[1], r)
        return self.router.transfers[(h.src, r)]

    def _expire_inflight(self) -> None:
        for h in list(self.inflight):
            if (
                h.req.deadline is not None
                and self.step_idx >= h.req.deadline
            ):
                self._drop_inflight(h)
                self.obs.registry.counter(
                    "serve_handoff_expired_total",
                    "handoffs expired before decode admission",
                ).inc()
                self.obs.tracer.instant(
                    "request.expire", track=self.name, step=self.step_idx,
                    rid=h.req.rid, inflight=1,
                )
                self._retire(h.req, "timed_out")

    def _admit_inflight(self) -> None:
        for h in list(self.inflight):
            for r, _sticky in self.router._decode_order(h, self.d_scheds):
                if not self.router.health.admittable(
                    self.router.decode[r].track
                ):
                    continue
                try:
                    pairs = self.d_scheds[r].try_admit_handoff(
                        h.req, h.n_tokens, h.src_pages, self.step_idx
                    )
                except FaultError:
                    # injected admission fault fires BEFORE any state
                    # mutates — the handoff just waits one more turn
                    pairs = None
                if pairs is None:
                    continue
                try:
                    with self.obs.tracer.span(
                        "kv_transfer", track=self.name, step=self.step_idx,
                        rid=h.req.rid, pages=len(pairs),
                    ):
                        self.router._transfer_move(self._transfer(h, r), pairs)
                except RetryBudgetExhausted as e:
                    self._transfer_exhausted(h, r, e)
                    break
                self._drop_inflight(h)
                break

    # -- reporting ----------------------------------------------------------
    def stats(self) -> dict:
        self.router._mirror_transfers()
        return {
            **super().stats(),
            "replica_health": self.router.health.snapshot(),
            "degraded": self.router.degraded,
            "cancelled_inflight": self.n_cancelled_inflight,
            "inflight_handoffs": len(self.inflight),
            "handoffs": sum(s.n_handoffs_in for s in self.d_scheds),
            "borrowed": sorted(self.router.borrowed),
            "autoscale_borrows": self.router.n_borrows,
            "autoscale_returns": self.router.n_returns,
            "compiled_signatures_prefill": max(
                e.step_cache_size() for e in self.router.prefill
            ),
            "compiled_signatures_decode": max(
                e.step_cache_size() for e in self.router.decode
            ),
        }
