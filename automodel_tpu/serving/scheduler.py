"""Continuous-batching request scheduler (host side of the serving engine).

The engine-loop half of the throughput story (arXiv:2605.25645: the win
over batch-synchronous generate comes from the loop, not just the kernel).
Every engine step the scheduler packs ONE fixed-shape token batch — the
`token_budget` rows the jitted step consumes — from whatever work exists:

- admission by free pages: a waiting request is admitted only when a slot
  is free AND the pool can hold its whole known sequence plus one decode
  page of slack (so a fresh admit never immediately preempts itself);
- decode first: every running request with exactly one pending token (its
  last sampled one) gets a row — decode latency is the SLO currency;
- chunked prefill rides the leftover budget: prompt tokens are fed in
  chunks of at most `prefill_chunk`, interleaved with other requests'
  decode steps instead of head-of-line blocking them;
- preempt-and-requeue on pool exhaustion: when a growing request needs a
  page and none is free, the YOUNGEST running request is preempted
  recompute-style (vLLM's recompute policy): its pages are freed and it
  re-queues at the queue head with `known = prompt + generated so far`, so
  its re-prefill reproduces the exact cache state. Greedy decoding is
  bit-reproducible across preemption; sampled decoding is too, because the
  engine derives each token's key as fold_in(request seed, position).

The unifying invariant: a request is just a `known` token list and a `fed`
counter (tokens whose KV is written). Prefill, decode, and post-preemption
re-prefill are all "feed known[fed:fed+c]"; a step that feeds the LAST
known token samples the next one from its logits. No phase flags.

Prefix sharing (serving/prefix_cache.py, opt-in): admission walks a radix
tree over known tokens at page granularity; matched pages are adopted
straight into the new slot's table and `fed` starts past them, so prefill
begins at the divergence point (a full hit's first step is already a
decode row). Running requests donate each newly COMPLETED full page, so
even concurrent requests share; finished/preempted/expired ones donate on
release. A slot about to append into a still-shared page gets a
copy-on-write replacement (`StepPlan.cow_src/cow_dst` carries the one-page
device copy). Cached-but-unreferenced pages are reclaimed (LRU) strictly
behind the free list, so admission-by-free-pages and preempt-and-requeue
keep working. The radix match also enables the first non-FIFO admission
policy, `admission_policy="prefix-hit"`: when the pool is too tight for
the queue head, prefer the arrived waiter with the highest hit ratio —
it adds decode load with the least prefill work, protecting decode
latency (the SLO currency) while the pool is contended.

Speculative decoding (speculative/serve_draft.py, opt-in): a decode-class
slot (one pending token) additionally asks its draft source for up to K
provisional tokens and feeds them as extra rows of the SAME chunk —
positions fed+1..fed+K, appended into spare pages the slot allocates
opportunistically. The jitted step scores the whole block in one ragged
paged-attention pass and verifies it in-jit (acceptance.py); `update`
absorbs the accepted prefix (+1 bonus/corrected token), rolls `fed` back
past the rejected suffix, and truncates the page table's provisional
tail (`PageAllocator.truncate`) — rollback is integer bookkeeping, the
payoff of the no-phase-flags request model (rejected KV rows sit beyond
`fed` and are overwritten when those positions are legitimately fed).
Provisional pages are OPPORTUNISTIC: they are allocated with reclaim but
never preemption (a draft block shrinks — possibly to zero, degrading to
plain decode — before any running request is evicted for it), they never
count in admission (`_need` stays known+1), and they are released every
step, so deadline eviction, preempt-and-requeue, and prefix-cache
donation only ever see committed pages.

The scheduler owns request/page state only; it never touches device
memory — it emits a `StepPlan` of numpy arrays the engine uploads.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque

import numpy as np

from automodel_tpu.observability.trace import NULL_TRACER
from automodel_tpu.ops.paged_attention import segment_bounds
from automodel_tpu.resilience.faults import fault_hit
from automodel_tpu.serving.kv_pages import PageAllocator, pages_for
from automodel_tpu.serving.prefix_cache import (
    PrefixCache,
    PrefixCacheConfig,
    PrefixMatch,
)


@dataclasses.dataclass
class Request:
    """One generation request. `temperature <= 0` → greedy; sampling keys
    derive from `seed` (per token position, preemption-stable)."""

    prompt: list
    max_new_tokens: int = 64
    temperature: float = 0.0
    eos_token_id: int | None = None
    seed: int = 0
    arrival: int = 0       # earliest engine step at which it may be admitted
    # graceful degradation under overload: a request not finished by engine
    # step `deadline` is EVICTED (pages freed, finish_reason "timed_out")
    # instead of occupying pool pages forever; None → no deadline
    deadline: int | None = None
    rid: int = -1          # set by the scheduler (submission order)

    # runtime state (scheduler-owned)
    generated: list = dataclasses.field(default_factory=list)
    fed: int = 0           # tokens of `known` whose KV is written
    preemptions: int = 0
    admitted_at: int = -1
    finished_at: int = -1
    finish_reason: str | None = None
    prefix_hit_tokens: int = 0  # prefill tokens skipped via the radix cache
    donated_pages: int = 0      # full pages already offered to the tree
    prefix_cut: bool = False    # a radix hit was cut (the model holds state)
    # wall-clock latency stamps (serve-loop-owned — the loop is the only
    # layer that knows when a request entered it: `FrontendBase.submit`
    # online, the step its arrival window opened offline):
    # ttft_s stays -1 for requests that never committed a token
    arrived_t: float = -1.0     # wall time the request entered the program
    ttft_s: float = -1.0        # time to first committed token (seconds)
    # wall time of the first plan that held a row of it (schedule() stamps
    # it once: the end of its queue; -1 until then)
    first_row_t: float = -1.0
    # adaptive speculation: EWMA of per-block acceptance (accepted/drafted)
    # for THIS request; starts optimistic so the first blocks draft at full
    # K and the estimate is earned from real verifier feedback
    spec_ewma: float = 1.0
    # failure recovery (serving/resilience.py): times this request was
    # evacuated off a dead replica and requeued onto a survivor — lets
    # stream consumers distinguish failed-and-recovered from undisturbed
    recovered: int = 0

    @property
    def known(self) -> list:
        return self.prompt + self.generated

    @property
    def known_len(self) -> int:
        """`len(self.known)` without building the list: the serve loop asks
        for it several times a slot a turn, and a context is thousands long."""
        return len(self.prompt) + len(self.generated)

    def known_slice(self, start: int, stop: int) -> list:
        """`self.known[start:stop]`, 0 <= start <= stop, without building
        the list."""
        n = len(self.prompt)
        if stop <= n:
            return self.prompt[start:stop]
        if start >= n:
            return self.generated[start - n:stop - n]
        return self.prompt[start:] + self.generated[:stop - n]

    @property
    def done(self) -> bool:
        return self.finish_reason is not None


@dataclasses.dataclass
class StepPlan:
    """One fixed-shape engine-step input batch (numpy; engine uploads)."""

    tok: np.ndarray          # (T,) int32 token ids (0 on pad rows)
    slot: np.ndarray         # (T,) int32 owning slot, -1 pad
    pos: np.ndarray          # (T,) int32 sequence position, -1 pad
    page: np.ndarray         # (T,) int32 destination page (trash for pads)
    off: np.ndarray          # (T,) int32 destination in-page offset
    page_tables: np.ndarray  # (S, P) int32, padded entries → trash page
    sample_tok: np.ndarray   # (S,) int32 row to sample from, -1 = no sample
    temp: np.ndarray         # (S,) float32 per-slot temperature
    seed: np.ndarray         # (S,) int32 per-slot base seed
    # copy-on-write page copies (≤ 1 per slot per step), first entries in
    # the order the scheduler made them; trash→trash = no-op
    cow_src: np.ndarray = None  # (S,) int32 source page
    cow_dst: np.ndarray = None  # (S,) int32 destination page
    # speculative decoding (None unless the engine runs with it enabled):
    # verify_rows[s, j] = row feeding the j-th token of slot s's verify
    # block (row 0 = the pending known token, rows 1..k its drafts; padded
    # entries repeat the last valid row), spec_len[s] = drafted tokens
    verify_rows: np.ndarray = None  # (S, K+1) int32
    spec_len: np.ndarray = None     # (S,) int32
    scheduled: list = dataclasses.field(default_factory=list)
    # scheduled: [(slot, n_tokens, samples: bool)] — host bookkeeping
    # (a slot's drafted rows are NOT in n_tokens; see spec_len)

    @property
    def n_tokens(self) -> int:
        fed = sum(c for _, c, _ in self.scheduled)
        if self.spec_len is not None:
            fed += int(self.spec_len.sum())
        return fed

    @property
    def n_samples(self) -> int:
        return sum(1 for *_, s in self.scheduled if s)


def _since_us(t0: float, now: float, key: str) -> dict:
    """`{key: microseconds from t0 to now}` for a request marker's profiler
    args, or {} where the stamp was never set."""
    return {key: int((now - t0) * 1e6)} if t0 >= 0 else {}


class Scheduler:
    """Continuous-batching scheduler over `max_slots` engine slots."""

    def __init__(
        self,
        *,
        num_pages: int,
        page_size: int,
        max_slots: int,
        pages_per_slot: int,
        token_budget: int,
        prefill_chunk: int | None = None,
        prefix_cache: PrefixCacheConfig | None = None,
        admission_policy: str = "fifo",
        spec=None,               # SpeculativeConfig (enabled) or None
        draft_source=None,       # speculative.serve_draft.DraftSource
        alloc: PageAllocator | None = None,
        prefix: PrefixCache | None = None,
        arrival_gating: bool = True,
        tracer=None,             # observability.trace.Tracer (None → no-op)
        track: str = "engine",
        attn_row_tile: int | None = None,  # the paged kernels' q tile, rows
        # a request may begin at position 0 alone: some layer's state, or a
        # window layer's ring of keys, lives per slot
        carries_state: bool = False,
        # the window of the attention layers that keep a ring per slot
        attn_window: int | None = None,
    ):
        # lifecycle tracing (observability/trace.py): the null tracer makes
        # every emit a constant-time no-op, so the untraced hot path is
        # unchanged. `track` names this scheduler's engine in the exported
        # timeline (replica0 / prefill1 / decode0 / ...).
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.track = track
        # `alloc`/`prefix` injection is the ENGINE-LIFETIME cache hook:
        # ServingEngine owns one allocator + radix tree and threads them
        # through every scheduler it makes, so cached pages survive across
        # serve_batch calls. Standalone construction (tests, one-shot runs)
        # keeps building a private pair — per-call semantics unchanged.
        self.alloc = (
            alloc if alloc is not None else PageAllocator(num_pages, page_size)
        )
        self.page_size = page_size
        self.max_slots = max_slots
        self.pages_per_slot = pages_per_slot
        self.token_budget = token_budget
        self.prefill_chunk = prefill_chunk or token_budget
        self.trash_page = num_pages  # pool arrays carry num_pages + 1 pages
        # where the engine's attention kernels cut a slot's run of rows
        # (ops/paged_attention.row_tile); alone, a run is never cut
        self.attn_row_tile = attn_row_tile or token_budget
        self.attn_window = attn_window
        if admission_policy not in ("fifo", "prefix-hit"):
            raise ValueError(f"unknown admission_policy {admission_policy!r}")
        if admission_policy == "prefix-hit" and not (
            prefix_cache and prefix_cache.enabled
        ):
            raise ValueError("admission_policy='prefix-hit' needs the prefix cache")
        self.admission_policy = admission_policy
        if prefix is not None:
            self.prefix: PrefixCache | None = prefix
        else:
            self.prefix = (
                PrefixCache(self.alloc, page_size, prefix_cache)
                if prefix_cache is not None and prefix_cache.enabled
                else None
            )
        self.spec = spec if (spec is not None and spec.enabled) else None
        self.draft_source = draft_source if self.spec is not None else None
        if self.spec is not None and self.draft_source is None:
            raise ValueError("speculative scheduling needs a draft source")
        # arrival_gating=False is ONLINE admission: a request's presence in
        # the queue IS its arrival (the live frontend submits when traffic
        # actually lands, so `Request.arrival` stops gating and only serves
        # as trace metadata). The offline serve loops keep gating on.
        self.arrival_gating = arrival_gating
        # slots the serve loop has withheld this step (stream backpressure:
        # a slow consumer pauses ITS OWN slot's rows; pages stay resident,
        # deadlines keep ticking, nothing else stalls)
        self.paused: set[int] = set()
        self.waiting: deque[Request] = deque()
        self.running: dict[int, Request] = {}   # slot → request
        self._admit_order: list[int] = []       # slots, oldest admit first
        self.finished: list[Request] = []
        self._next_rid = 0
        self.n_preemptions = 0
        self.n_timed_out = 0
        self.n_cancelled = 0
        self.n_cow = 0
        self.n_prefix_hits = 0        # admissions that adopted cached pages
        # The model carries a per-slot recurrent state or a window layer's
        # ring of keys (serving/kv_pages.py init_state, init_rings). A request
        # may then begin only at position 0: adopted pages would skip rows
        # whose state, or whose window's keys, nobody kept, so every prefix
        # hit is CUT to none and counted here.
        self.carries_state = carries_state
        self.n_prefix_hits_cut = 0    # admissions whose radix hit was cut
        self.prefill_skipped = 0      # prompt tokens never re-prefilled
        # speculative-decoding counters
        self.n_drafted = 0            # provisional tokens fed for scoring
        self.n_accepted = 0           # drafts the verifier kept
        self.n_spec_steps = 0         # verify blocks with >= 1 draft
        # disaggregated-handoff counters (serving/router.py DisaggRouter)
        self.n_handoffs_out = 0       # requests extracted for migration
        self.n_handoffs_in = 0        # handoffs admitted as pre-filled
        self.handoff_pages_in = 0     # pages actually copied across pools
        self.handoff_pages_spliced = 0  # pages served by the local tree

    # -- request lifecycle --------------------------------------------------
    def submit(self, req: Request) -> None:
        # hard errors, not asserts: these guard user input and must survive
        # python -O (a request that slips through can stall the serve loop)
        if len(req.prompt) < 1:
            raise ValueError("empty prompt")
        total = len(req.prompt) + req.max_new_tokens
        max_tokens = self.pages_per_slot * self.page_size
        if total > max_tokens:
            raise ValueError(
                f"request needs {total} positions > pages_per_slot*page_size"
                f" = {max_tokens}"
            )
        if pages_for(total, self.page_size) > self.alloc.num_pages:
            raise ValueError(
                f"request needs {pages_for(total, self.page_size)} pages but "
                f"the whole pool holds {self.alloc.num_pages} — it could "
                "never finish even alone"
            )
        if req.rid < 0:
            req.rid = self._next_rid
        self._next_rid = max(self._next_rid, req.rid + 1)
        self.waiting.append(req)
        self.tracer.instant(
            "request.submit", track=self.track, rid=req.rid,
            prompt_len=len(req.prompt), max_new=req.max_new_tokens,
        )

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    def turn_stats(self, preemptions_before: int, plan=None) -> dict:
        """What `step.plan`'s span says after a `schedule()`. Of the pool:
        pages free, requests resident, and the requests this turn preempted
        (`preemptions_before`: `n_preemptions` as the turn began). Where a
        page is dear (a looped decoder's holds every pass) these say what
        the slots do not: whether requests wait for pages, and what growth
        costs in preempted work. Of the turn's `plan` (None: no step): the
        (segment, page) blocks each paged-attention call of its step
        walks, `attn_segments` runs of one slot's rows and
        `attn_live_blocks` pages they attend to in all; against rows x
        pages_per_slot, the share of a (row, page) grid that is left;
        `attn_one_row_blocks` of them belong to runs of ONE row (decode
        rows), whose block the GQA kernel scores in a body of its own
        (`paged_attention_one_row_body_total` says which). A model with
        window layers says both kinds' live blocks, `full_blocks` and
        `window_blocks`."""
        segments = live_blocks = one_row_blocks = window_blocks = 0
        if plan is not None:
            is_start, is_last = segment_bounds(
                np, plan.slot, plan.pos, self.attn_row_tile
            )
            segments = int(is_start.sum())
            last_page = plan.pos[is_last] // self.page_size
            live_blocks = int((last_page + 1).sum())
            # a run of ONE row ends where it starts
            one_row_blocks = int((last_page[is_start[is_last]] + 1).sum())
            if self.attn_window:
                # a window layer's list starts at the page of the segment's
                # first in-window key (ops/paged_attention.row_segments)
                first_page = np.maximum(
                    plan.pos[is_start] - self.attn_window + 1, 0
                ) // self.page_size
                window_blocks = int((last_page - first_page + 1).sum())
        stats = {
            "free_pages": self.alloc.num_free,
            "resident": len(self.running),
            "preempted": self.n_preemptions - preemptions_before,
            "attn_segments": segments,
            "attn_live_blocks": live_blocks,
            "attn_one_row_blocks": one_row_blocks,
        }
        if self.attn_window:
            # the live blocks of each kind's list: a full layer walks every
            # page up to the row, a window layer the few the window touches
            stats["full_blocks"] = live_blocks
            stats["window_blocks"] = window_blocks
        if self.carries_state:
            # runs of one slot's rows at consecutive positions: what each
            # state-space layer's scan starts or continues this turn
            stats["state_runs"] = 0 if plan is None else int(
                segment_bounds(np, plan.slot, plan.pos, len(plan.slot))[0].sum()
            )
        return stats

    def prefix_hit_tokens(self, tokens: list) -> int:
        """Radix-affinity probe: how many of `tokens` this scheduler's
        prefix cache could serve from shared pages (0 without a cache).
        The ReplicaRouter's sticky-routing signal — a request lands on the
        replica already holding its prefix, so the data-parallel tier
        never dilutes the cache. Strictly READ-ONLY (no LRU tick): every
        replica is probed per request, and warming the losers' trees
        would let probe-only pages outlive genuinely served ones."""
        if self.prefix is None or self.carries_state:
            return 0
        return self.prefix.peek_match_tokens(list(tokens))

    def _match(self, req: Request) -> PrefixMatch:
        none = PrefixMatch(pages=[], fed=0, matched_tokens=0, cow_pending=False)
        if self.prefix is None:
            return none
        if self.carries_state:
            # read-only probe: what a model without state would have adopted
            if not req.prefix_cut and self.prefix.peek_match_tokens(req.known):
                req.prefix_cut = True   # once a request, however often probed
                self.n_prefix_hits_cut += 1
            return none
        return self.prefix.lookup(req.known)

    def _need(self, req: Request, match: PrefixMatch) -> int:
        """Pages a fresh admit must still find: whole known sequence + 1
        decode page of slack, minus adopted pages, plus one for the pending
        copy-on-write split when the first write lands in a shared page."""
        return (
            pages_for(req.known_len + 1, self.page_size)
            - len(match.pages)
            + (1 if match.cow_pending else 0)
        )

    def _admit(self, step_idx: int) -> None:
        while self.waiting and len(self.running) < self.max_slots:
            picked = self._pick_admission(step_idx)
            if picked is None:
                break
            i, req, match = picked
            del self.waiting[i]
            slot = next(
                s for s in range(self.max_slots) if s not in self.running
            )
            self.running[slot] = req
            self._admit_order.append(slot)
            first = req.admitted_at < 0
            if first:
                req.admitted_at = step_idx
            if match.pages:
                # radix hit: the matched prefix's pages go straight into the
                # slot's table and `fed` advances past them — prefill starts
                # at the divergence point (full hit → first step is decode)
                self.alloc.adopt(slot, match.pages)
                req.fed = match.fed
                req.prefix_hit_tokens += match.fed
                req.donated_pages = (
                    len(match.pages) - (1 if match.cow_pending else 0)
                )
                self.prefill_skipped += match.fed
                self.n_prefix_hits += 1
            self.tracer.instant(
                "request.admit", track=self.track, step=step_idx,
                rid=req.rid, slot=slot, prefix_fed=match.fed,
                wall=_since_us(req.arrived_t, time.perf_counter(),
                               "since_submit_us") if first else None,
            )
        # FIFO admission (default): if the head doesn't fit, nothing behind
        # it jumps the queue (no starvation of long prompts). Under
        # "prefix-hit", a tight pool admits the best-hit-ratio waiter
        # instead — the head stays at the front and still goes first the
        # moment it fits.

    def _admissible(self, req: Request, avail: int) -> PrefixMatch | None:
        """The match to admit `req` with, or None if it cannot fit. Adopting
        a tree-only page PINS it — it stops counting as reclaimable — so the
        radix hit only stands when `need` fits what would remain available
        after adoption. When the warm admit does not fit but a cold one
        would (the tree itself is hogging the pool), fall back to a cold
        admission: the un-adopted cached pages stay evictable and the
        pressure ladder reclaims them during prefill."""
        match = self._match(req)
        if match.pages:
            pinned = sum(
                1 for p in match.pages if self.alloc.refcount(p) == 1
            )
            if self._need(req, match) + pinned <= avail:
                return match
            match = PrefixMatch(pages=[], fed=0, matched_tokens=0,
                                cow_pending=False)
        if self._need(req, match) <= avail:
            return match
        return None

    def _pick_admission(self, step_idx: int):
        """Choose the next waiter: queue index, request, radix match.
        FIFO fast path: the head, whenever it fits. The prefix-hit scan
        below only runs while the pool is too tight for the head, so its
        per-waiter radix walks stay off the uncontended hot path."""
        avail = self.alloc.num_free + (
            self.prefix.reclaimable() if self.prefix else 0
        )
        head = self.waiting[0]
        if not self.arrival_gating or head.arrival <= step_idx:
            match = self._admissible(head, avail)
            if match is not None:
                return 0, head, match
        if self.admission_policy == "fifo":
            return None
        # pool too tight for the head (or head not arrived): prefer the
        # arrived waiter with the highest hit ratio among those that fit
        best = None
        for i, req in enumerate(self.waiting):
            if self.arrival_gating and req.arrival > step_idx:
                continue
            match = self._admissible(req, avail)
            if match is None:
                continue
            ratio = match.fed / max(req.known_len, 1)
            key = (ratio, -i)  # tie → submission order
            if best is None or key > best[0]:
                best = (key, i, req, match)
        return best[1:] if best is not None else None

    def _donate(self, slot: int) -> None:
        """Offer a slot's newly completed full pages to the radix tree (the
        tree takes its own allocator reference, so the pages survive the
        slot). Runs after every feed and on release — content below `fed`
        is immutable, so a donated page can never change under the tree."""
        if self.prefix is None:
            return
        req = self.running[slot]
        full = req.fed // self.page_size
        if full <= req.donated_pages:
            return
        self.prefix.insert(
            req.known[: full * self.page_size],
            self.alloc.table(slot)[:full],
        )
        req.donated_pages = full

    def _release_slot(self, slot: int, donate: bool = True) -> Request:
        """Remove a running request from its slot: donate its full pages to
        the prefix tree (completion, preemption, and deadline eviction all
        seed future hits), then drop the slot's references — shared pages
        live on, exclusive ones return to the free list."""
        if donate:
            self._donate(slot)
        req = self.running.pop(slot)
        self._admit_order.remove(slot)
        self.paused.discard(slot)
        self.alloc.free_slot(slot)
        if self.draft_source is not None:
            self.draft_source.release(req)
        return req

    def cancel(self, rid: int, step_idx: int = -1) -> bool:
        """Evict one request by rid wherever it lives — the mid-stream
        client-disconnect path. A RUNNING request releases its slot and
        pages THE SAME CALL (donating completed full pages like any other
        release, so the allocator identity num_free + cached == num_pages
        holds the moment this returns); a WAITING one just leaves the
        queue. Returns False when the rid is unknown (already finished or
        never submitted) — cancellation of a done request is a no-op, not
        an error."""
        for slot, req in list(self.running.items()):
            if req.rid == rid:
                req.finish_reason = "cancelled"
                req.finished_at = step_idx
                self.finished.append(req)
                self._release_slot(slot)
                self.n_cancelled += 1
                self.tracer.instant(
                    "request.cancel", track=self.track, step=step_idx,
                    rid=rid, resident=1,
                )
                return True
        for req in self.waiting:
            if req.rid == rid:
                self.waiting.remove(req)
                req.finish_reason = "cancelled"
                req.finished_at = step_idx
                self.finished.append(req)
                self.n_cancelled += 1
                self.tracer.instant(
                    "request.cancel", track=self.track, step=step_idx,
                    rid=rid, resident=0,
                )
                return True
        return False

    # -- disaggregated prefill/decode handoff -------------------------------
    def extract_handoffs(self, rids=None) -> list:
        """Pop every running request whose prefill has finished (>= 1
        committed token — its next step would be a pure decode row) for
        migration to a decode-class peer. Returns [(request, n_tokens,
        src_pages)]: the first pages_for(n_tokens) table pages, each PINNED
        with an extra allocator reference so they outlive the slot release
        — the caller decrefs via `release_handoff` after the device copy
        (or on deadline expiry). The release donates full pages to the
        radix tree as usual, so later prompts on THIS replica still hit;
        the pin covers the partial tail page the tree never takes.

        `rids` (optional) restricts extraction to those request ids — the
        autoscaling router's guard: a decode-class replica temporarily
        serving prefill traffic must hand off ONLY the requests routed to
        it as prefills, never evacuate its resident decode work."""
        out = []
        for slot, req in list(self.running.items()):
            if not req.generated or req.done:
                continue
            if rids is not None and req.rid not in rids:
                continue
            n = req.fed
            src = list(self.alloc.table(slot))[: pages_for(n, self.page_size)]
            for p in src:
                self.alloc.incref(p)
            self._release_slot(slot)
            self.n_handoffs_out += 1
            self.tracer.instant(
                "request.handoff_extract", track=self.track, rid=req.rid,
                n_tokens=n, pages=len(src),
            )
            out.append((req, n, src))
        return out

    def release_handoff(self, src_pages: list) -> None:
        """Drop the extraction pins once a handoff's pages were copied out
        (or its request expired in flight)."""
        for p in src_pages:
            self.alloc.decref(p)

    def try_admit_handoff(self, req: Request, n_tokens: int, src_pages: list,
                          step_idx: int):
        """Admit a migrating request whose first `n_tokens` known tokens
        already have KV committed on another replica. The handoff arrives
        as PRE-FILLED pages: `fed` starts at the divergence point, so the
        request's first step here is already a decode row. Pages the local
        radix tree already holds are SPLICED (adopted, not copied — a
        prefill peer's earlier donations become transferable cache hits);
        the rest get freshly allocated destination pages. Returns the
        [(src_page, dst_page)] copy plan the caller must execute BEFORE the
        next engine step, or None when no slot/pages are available yet
        (the caller retries next step)."""
        # chaos hook for the disagg handoff path — probed BEFORE any state
        # mutates, so an injected admission fault just delays the handoff a
        # turn (the caller's retry-next-step path, same as a full pool)
        fault_hit("handoff_admit", step_idx)
        ps = self.page_size
        P = pages_for(n_tokens, ps)
        if len(src_pages) != P:
            raise ValueError(
                f"handoff carries {len(src_pages)} pages for {n_tokens} "
                f"tokens (need {P})"
            )
        if len(self.running) >= self.max_slots:
            return None
        matched = (
            self.prefix.match_pages(req.known[:n_tokens])
            if self.prefix is not None else []
        )
        k = min(len(matched), P)
        # same accounting as _admissible: whole sequence + 1 decode page of
        # slack, minus spliced pages — and splicing a tree-only page PINS
        # it, so the warm splice only stands when the remainder still fits;
        # otherwise fall back to a cold (full-copy) admit and leave the
        # cached pages evictable for the pressure ladder
        avail = self.alloc.num_free + (
            self.prefix.reclaimable() if self.prefix is not None else 0
        )
        need_total = pages_for(req.known_len + 1, ps)
        if k:
            pinned = sum(
                1 for p in matched[:k] if self.alloc.refcount(p) == 1
            )
            if need_total - k + pinned > avail:
                k = 0
        if k == 0 and need_total > avail:
            return None
        slot = next(s for s in range(self.max_slots) if s not in self.running)
        self.running[slot] = req
        self._admit_order.append(slot)
        if req.admitted_at < 0:
            req.admitted_at = step_idx
        if k:
            self.alloc.adopt(slot, matched[:k])
        if not self.alloc.ensure(slot, n_tokens, reclaim=self._reclaim):
            # belt over the availability check's suspenders: roll the
            # admission back cleanly and let the caller retry next step
            self.alloc.free_slot(slot)
            del self.running[slot]
            self._admit_order.remove(slot)
            return None
        req.fed = n_tokens
        # spliced pages are the only ones already in THIS replica's tree;
        # the next _donate offers the transferred full pages too, making
        # them local cache hits for future prompts (and re-admissions)
        req.donated_pages = k
        if k:
            req.prefix_hit_tokens += k * ps
            self.n_prefix_hits += 1
        self.n_handoffs_in += 1
        self.handoff_pages_spliced += k
        table = self.alloc.table(slot)
        pairs = list(zip(src_pages[k:], table[k:P]))
        self.handoff_pages_in += len(pairs)
        self.tracer.instant(
            "request.handoff_admit", track=self.track, step=step_idx,
            rid=req.rid, slot=slot, spliced=k, moved=len(pairs),
        )
        return pairs

    def evacuate(self) -> list:
        """Pop EVERY resident and queued request for requeue on another
        replica — the failure-recovery half of preempt-and-requeue
        (serving/resilience.py). Running requests release their slots
        WITHOUT donating (this pool is dead; seeding its radix tree would
        just hide leaks from the allocator identity), waiting ones leave
        the queue; every request resets to the preemption state (`fed = 0`,
        `donated_pages = 0`) so its re-prefill on a survivor rides THAT
        replica's prefix cache from the divergence point. Returns requests
        in deterministic order: residents oldest-admit-first, then the
        waiting queue — chaos traces requeue identically every run."""
        out = []
        for slot in list(self._admit_order):
            out.append(self._release_slot(slot, donate=False))
        out.extend(self.waiting)
        self.waiting.clear()
        for req in out:
            req.fed = 0
            req.donated_pages = 0
            self.tracer.instant(
                "request.evacuate", track=self.track, rid=req.rid,
                known=req.known_len,
            )
        return out

    def evict_for_recovery(self, rid: int):
        """Pull ONE request back out for requeue elsewhere — the failed-
        transfer path: its freshly admitted slot may hold a partial page
        copy, so the release must NOT donate (garbage pages in the radix
        tree would poison future admissions). Resets to the preemption
        state; returns the Request, or None when the rid is not here."""
        for slot, req in list(self.running.items()):
            if req.rid == rid:
                self._release_slot(slot, donate=False)
                req.fed = 0
                req.donated_pages = 0
                return req
        for req in list(self.waiting):
            if req.rid == rid:
                self.waiting.remove(req)
                req.fed = 0
                req.donated_pages = 0
                return req
        return None

    def _preempt_youngest(self, protected) -> bool:
        """Free the youngest running request whose slot is not `protected`
        (the requester and every slot with rows already planned this step —
        their pages must not be recycled mid-step); requeue it at the queue
        head, recompute-style. Returns False if no victim. With the prefix
        cache on, the victim's full pages were donated — its requeued
        "re-prefill" is mostly a radix hit that re-adopts its own pages."""
        for slot in reversed(self._admit_order):
            if slot in protected:
                continue
            victim = self._release_slot(slot)
            victim.fed = 0
            victim.donated_pages = 0
            victim.preemptions += 1
            self.n_preemptions += 1
            self.waiting.appendleft(victim)
            self.tracer.instant(
                "request.preempt", track=self.track, rid=victim.rid,
                preemptions=victim.preemptions,
            )
            return True
        return False

    def _reclaim(self, n: int) -> int:
        """Allocator reclaim hook: cached pages, strictly behind free ones."""
        return self.prefix.reclaim(n) if self.prefix is not None else 0

    def _ensure(self, slot: int, num_tokens: int, protected) -> bool:
        """ensure() + the pool-pressure ladder: free list first, then evict
        cached-but-unreferenced prefix pages (LRU), then preempt-and-requeue
        the youngest unprotected request. False → stall this slot a step."""
        while not self.alloc.ensure(slot, num_tokens, reclaim=self._reclaim):
            if not self._preempt_youngest(protected):
                return False
        return True

    def _free_page_for_cow(self, protected) -> bool:
        """One free page for a copy-on-write split, same pressure ladder."""
        while not (self.alloc.num_free >= 1 or self._reclaim(1) >= 1):
            if not self._preempt_youngest(protected):
                return False
        return True

    def _expire_deadlines(self, step_idx: int) -> None:
        """Evict requests whose deadline has passed — running requests free
        their slot and pages (relieving pool pressure under overload),
        waiting ones just leave the queue. Runs BETWEEN engine steps (at the
        top of schedule()), so no mid-step plan ever references recycled
        pages. The partial generation stays on the Request."""
        for slot, req in list(self.running.items()):
            if req.deadline is not None and step_idx >= req.deadline:
                req.finish_reason = "timed_out"
                req.finished_at = step_idx
                self.finished.append(req)
                self._release_slot(slot)
                self.n_timed_out += 1
                self.tracer.instant(
                    "request.expire", track=self.track, step=step_idx,
                    rid=req.rid, resident=1,
                )
        expired = [
            r for r in self.waiting
            if r.deadline is not None and step_idx >= r.deadline
        ]
        for req in expired:
            self.waiting.remove(req)
            req.finish_reason = "timed_out"
            req.finished_at = step_idx
            self.finished.append(req)
            self.n_timed_out += 1
            self.tracer.instant(
                "request.expire", track=self.track, step=step_idx,
                rid=req.rid, resident=0,
            )

    @property
    def next_deadline(self) -> int | None:
        """Earliest pending deadline across running+waiting (None if none) —
        lets the serve loop distinguish 'stalled forever' from 'stalled
        until an eviction frees pages'."""
        ds = [
            r.deadline
            for r in list(self.running.values()) + list(self.waiting)
            if r.deadline is not None
        ]
        return min(ds) if ds else None

    # -- step planning ------------------------------------------------------
    def schedule(self, step_idx: int) -> StepPlan | None:
        """Build the next step's token batch, or None when nothing runs this
        step (queue empty or all arrivals in the future)."""
        self._expire_deadlines(step_idx)
        self._admit(step_idx)
        T, S, P = self.token_budget, self.max_slots, self.pages_per_slot
        plan = StepPlan(
            tok=np.zeros(T, np.int32),
            slot=np.full(T, -1, np.int32),
            pos=np.full(T, -1, np.int32),
            page=np.full(T, self.trash_page, np.int32),
            off=np.zeros(T, np.int32),
            page_tables=np.full((S, P), self.trash_page, np.int32),
            sample_tok=np.full(S, -1, np.int32),
            temp=np.zeros(S, np.float32),
            seed=np.zeros(S, np.int32),
            cow_src=np.full(S, self.trash_page, np.int32),
            cow_dst=np.full(S, self.trash_page, np.int32),
        )
        if self.spec is not None:
            plan.verify_rows = np.zeros((S, self.spec.draft_len + 1), np.int32)
            plan.spec_len = np.zeros(S, np.int32)
        row = splits = 0
        planned = set()
        # decode rows first (pending == 1), then prefill chunks; within each
        # class oldest admit first. Paused slots (stream backpressure) get
        # NO rows this step — they stay resident (page tables below still
        # carry them) and deadlines keep ticking, but their generation
        # holds until the serve loop unpauses them.
        order = [s for s in self._admit_order if s not in self.paused]
        decode = [s for s in order if self.running[s].known_len - self.running[s].fed == 1]
        prefill = [s for s in order if s not in decode]
        # decode rows not yet handed out: an earlier slot's draft block may
        # never eat a later decode slot's ONE guaranteed row (stable order
        # would starve the same slot every step)
        decode_left = len(decode)
        for slot in decode + prefill:
            req = self.running.get(slot)
            is_decode = decode_left > 0  # decode slots run first
            if is_decode:
                decode_left -= 1
            if req is None or row >= T:
                continue
            pending = req.known_len - req.fed
            c = min(pending, T - row, self.prefill_chunk)
            if c <= 0:
                continue
            # pool exhausted → the pressure ladder (reclaim cached pages,
            # then preempt-and-requeue); stall this slot a step if dry
            if not self._ensure(slot, req.fed + c, planned | {slot}):
                continue
            # copy-on-write on divergence: the first write of this chunk
            # lands in a page another table or the radix tree still reads —
            # give the slot a private copy (one-page device copy in-plan)
            first_page = req.fed // self.page_size
            if self.alloc.refcount(self.alloc.table(slot)[first_page]) > 1:
                if not self._free_page_for_cow(planned | {slot}):
                    continue
                pair = self.alloc.cow(slot, first_page)
                if pair is not None:  # the ladder may have dropped the share
                    # entries in the order made: a page this split reads
                    # may be freed and handed to a later split as its copy
                    plan.cow_src[splits], plan.cow_dst[splits] = pair
                    splits += 1
                    self.n_cow += 1
            planned.add(slot)
            samples = req.fed + c == req.known_len
            # speculative block: a sampling (decode-class) slot extends its
            # chunk with up to K drafted rows. Pages for the drafts come
            # from the free list / prefix-cache reclaim only — NEVER
            # preemption — and the block shrinks to what fits, so
            # speculation degrades to plain decode under pool pressure
            # instead of evicting anyone.
            drafts: list = []
            if samples and self.spec is not None and (
                req.temperature <= 0.0 or self.spec.acceptance == "sampled"
            ):
                k_cap = min(
                    self.spec.draft_len,
                    # leave one row for every decode slot still waiting
                    T - row - c - decode_left,
                    req.max_new_tokens - len(req.generated) - 1,
                    self.pages_per_slot * self.page_size - (req.fed + c),
                )
                # adaptive draft length (policy-only; the step's fixed
                # (S, K+1) verify shape is untouched): once a request's
                # acceptance EWMA falls below the threshold, its block
                # shrinks proportionally — and collapses to ZERO (plain
                # decode, no probe blocks) when the estimate decays far
                # enough, so a hopeless drafter stops burning verify rows
                # on rollbacks. The collapse is deterministic in the
                # verifier feedback, so greedy streams stay token-exact.
                if (
                    self.spec.adaptive
                    and req.spec_ewma < self.spec.adaptive_threshold
                ):
                    k_cap = min(
                        k_cap, int(self.spec.draft_len * req.spec_ewma)
                    )
                if k_cap > 0:
                    drafts = list(self.draft_source.draft(req, k_cap))[:k_cap]
                while drafts and not self.alloc.ensure(
                    slot, req.fed + c + len(drafts), reclaim=self._reclaim
                ):
                    drafts.pop()
            k = len(drafts)
            # the slot's run of rows, c of its known tokens then k drafts. A
            # chunk is filled whole (a row at a time, each asking `req.known`
            # anew, cost 7 ms a 512-row plan at contexts of thousands of
            # tokens); a lone decode row is five stores
            table = self.alloc.table(slot)
            if c + k == 1:
                p = req.fed
                plan.tok[row] = req.known_slice(p, p + 1)[0]
                plan.slot[row] = slot
                plan.pos[row] = p
                plan.page[row] = table[p // self.page_size]
                plan.off[row] = p % self.page_size
            else:
                rows = slice(row, row + c + k)
                p = np.arange(req.fed, req.fed + c + k)
                plan.tok[rows] = req.known_slice(req.fed, req.fed + c) + drafts
                plan.slot[rows] = slot
                plan.pos[rows] = p
                plan.page[rows] = np.asarray(table, np.int32)[p // self.page_size]
                plan.off[rows] = p % self.page_size
            if samples:
                plan.sample_tok[slot] = row + c - 1
            if self.spec is not None and samples:
                plan.verify_rows[slot] = np.minimum(
                    row + c - 1 + np.arange(self.spec.draft_len + 1),
                    row + c - 1 + k,
                )
                plan.spec_len[slot] = k
            plan.temp[slot] = req.temperature
            plan.seed[slot] = req.seed
            plan.scheduled.append((slot, c, samples))
            row += c + k
            if req.first_row_t < 0:
                # the end of its queue, as the harness times it: the first
                # plan that holds a row of the request
                req.first_row_t = time.perf_counter()
                self.tracer.instant(
                    "request.first_row", track=self.track, step=step_idx,
                    rid=req.rid, wall=_since_us(
                        req.arrived_t, req.first_row_t, "since_submit_us"),
                )
        for slot, req in self.running.items():
            t = self.alloc.table(slot)
            plan.page_tables[slot, : len(t)] = t
        if not plan.scheduled:
            return None
        return plan

    def update(
        self,
        plan: StepPlan,
        sampled: np.ndarray,
        step_idx: int,
        accept: np.ndarray | None = None,
        frontier_hidden=None,
        row_hidden=None,
    ) -> int:
        """Absorb one engine step's sampled tokens; finish/free requests.

        Speculative steps (plan.spec_len set) pass `accept` (S,) from the
        in-jit verifier and `sampled` as the (S, K+1) committed-candidate
        block: the accepted prefix + bonus token is absorbed, `fed` rolls
        back past the rejected suffix, and the page table's provisional
        tail is truncated. Returns the number of tokens committed this
        step (== number of sampling slots when speculation is off)."""
        sampled = np.asarray(sampled)
        committed_total = 0
        for slot, c, samples in plan.scheduled:
            req = self.running[slot]
            k = int(plan.spec_len[slot]) if plan.spec_len is not None else 0
            a = max(0, min(int(accept[slot]), k)) if k > 0 else 0
            if samples:
                block = sampled[slot]
                candidates = (
                    [int(t) for t in block[: a + 1]]
                    if block.ndim else [int(block)]
                )
            else:
                candidates = []
            n_commit = 0
            for tok in candidates:
                req.generated.append(tok)
                n_commit += 1
                committed_total += 1
                if req.eos_token_id is not None and tok == req.eos_token_id:
                    req.finish_reason = "eos"
                elif len(req.generated) >= req.max_new_tokens:
                    req.finish_reason = "length"
                if req.done:
                    break
            if n_commit:
                if len(req.generated) == n_commit:
                    now = time.perf_counter()
                    self.tracer.instant(
                        "request.first_token", track=self.track,
                        step=step_idx, rid=req.rid, wall={
                            **_since_us(req.arrived_t, now, "since_submit_us"),
                            **_since_us(req.first_row_t, now,
                                        "since_first_row_us"),
                        },
                    )
                self.tracer.instant(
                    "request.commit", track=self.track, step=step_idx,
                    rid=req.rid, n=n_commit,
                )
            # KV is written for the fed chunk plus the accepted drafts that
            # were actually COMMITTED — an EOS/length cut inside the block
            # discards the tail, whose KV rows roll back with the rejected
            # suffix (keeps fed <= len(known) always, and the acceptance
            # stats honest); the bonus/corrected token is known-but-not-fed
            # (pending == 1, the plain decode invariant)
            a = min(a, n_commit)
            req.fed += c + a
            if k > 0:
                self.n_drafted += k
                self.n_accepted += a
                self.n_spec_steps += 1
                d = self.spec.adaptive_decay
                req.spec_ewma = d * req.spec_ewma + (1.0 - d) * (a / k)
            if self.draft_source is not None and not req.done:
                if frontier_hidden is not None and samples:
                    # the newest committed token + the hidden that produced
                    # it (position == req.fed: the pending token's position)
                    self.draft_source.observe(
                        req, req.known[-1], frontier_hidden[slot], req.fed
                    )
                if row_hidden is not None:
                    # every row this slot fed whose KV survived the rollback
                    # (positions < fed) — prefill chunks included, so block
                    # drafters see the whole committed context
                    rows = np.nonzero(plan.slot == slot)[0]
                    rows = rows[plan.pos[rows] < req.fed]
                    self.draft_source.observe_rows(
                        req,
                        [int(p) for p in plan.pos[rows]],
                        row_hidden[rows],
                    )
            if req.done:
                req.finished_at = step_idx
                self.finished.append(req)
                self._release_slot(slot)
                self.tracer.instant(
                    "request.done", track=self.track, step=step_idx,
                    rid=req.rid, reason=req.finish_reason,
                    n_generated=len(req.generated),
                )
                continue
            # donate every newly completed full page while still running, so
            # CONCURRENT requests with the same prefix share immediately
            self._donate(slot)
            if k > 0:
                # roll back the rejected suffix's provisional pages
                self.alloc.truncate(slot, pages_for(req.fed, self.page_size))
        return committed_total
