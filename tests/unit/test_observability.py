"""The unified observability layer's acceptance contract (docs/OBSERVABILITY.md):

- registry units: counter/gauge/histogram semantics, label series, the
  kind-conflict tripwire, and the Prometheus text round-trip of every
  cataloged metric (METRIC_CATALOG ↔ docs table ↔ snapshot_prometheus);
- tracer units: span nesting validated through the Chrome export, the
  bounded flight-recorder ring, and the deterministic lifecycle digest
  (wall clocks / step indices / stream backpressure edges excluded);
- serving integration: tracing ON changes neither the greedy token stream
  nor the compile count; two identical online load_test runs produce the
  SAME digest; disagg TTFT attribution components sum to the measured
  TTFT exactly; an injected serve-step crash dumps the flight recorder;
- the two sinks: under a profiler session the online loop's spans lie in
  the profiler's own trace whether or not the in-memory buffer is kept,
  joined per turn by `engine_step`; the jitted step names its sublayers
  (`serve.*` scopes) without one more instruction; the process's
  compilations are counted.
"""

import asyncio
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.llm import decoder
from automodel_tpu.models.llm.decoder import TransformerConfig
from automodel_tpu.observability import (
    METRIC_CATALOG,
    NULL_TRACER,
    MetricsRegistry,
    Observability,
    ObservabilityConfig,
    Tracer,
    attribute_ttft,
    attribution_summary,
    build_timelines,
    validate_chrome_trace,
)
from automodel_tpu.observability.metrics import Counter, Gauge, Histogram
from automodel_tpu.resilience.faults import FaultCrash, injected
from automodel_tpu.serving import (
    DisaggConfig,
    DisaggOnlineFrontend,
    DisaggRouter,
    ReplicaRouter,
    Request,
    ServeMeshConfig,
    ServingConfig,
    ServingEngine,
)
from automodel_tpu.serving.frontend import FrontendConfig, OnlineFrontend
from automodel_tpu.serving.load_test import LoadTestConfig, run_load_test
from tests.serving_params import own

CFG = TransformerConfig(
    vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=2,
    num_heads=4, num_kv_heads=2, qk_norm=True, dtype=jnp.float32,
    remat_policy="none",
)


@pytest.fixture(scope="module")
def params():
    return decoder.init(CFG, jax.random.key(0))


def _sc(**kw):
    return ServingConfig(
        page_size=4, num_pages=32, max_slots=3, pages_per_slot=6,
        token_budget=8, prefill_chunk=4, **kw,
    )


def _reqs(lens, seed0=0, max_new=6):
    return [
        Request(
            prompt=[int(t) for t in
                    np.random.default_rng(seed0 + i).integers(1, 64, (l,))],
            max_new_tokens=max_new,
        )
        for i, l in enumerate(lens)
    ]


# -- registry units ----------------------------------------------------------


def test_counter_gauge_histogram_semantics():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = Gauge()
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.value == 4.0
    h = Histogram(bounds=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    assert h.count == 4 and h.sum == 555.5
    snap = h.snapshot()
    assert snap["cumulative"] == [1, 2, 3]  # le-semantics; 500 overflows
    assert h.percentile(0.5) == 10.0
    assert h.percentile(1.0) == 100.0  # overflow reports the top bound
    with pytest.raises(ValueError):
        Histogram(bounds=(1.0, 1.0))  # must strictly increase


def test_registry_kind_conflict_and_label_series():
    reg = MetricsRegistry()
    reg.counter("x_total", "things").inc()
    with pytest.raises(TypeError):
        reg.gauge("x_total")
    reg.counter("shed_total", "sheds", reason="deadline").inc(2)
    reg.counter("shed_total", "sheds", reason="queue_full").inc()
    snap = reg.snapshot()
    assert snap['shed_total{reason="deadline"}'] == 2.0
    assert snap['shed_total{reason="queue_full"}'] == 1.0
    assert list(snap) == sorted(snap)  # deterministic key order


def test_prometheus_exposition_shape():
    reg = MetricsRegistry()
    reg.counter("a_total", "a counter").inc(3)
    reg.histogram("lat_ms", "latency", buckets=(1.0, 10.0)).observe(5.0)
    text = reg.snapshot_prometheus()
    assert "# HELP a_total a counter" in text
    assert "# TYPE a_total counter" in text
    assert "a_total 3" in text
    assert 'lat_ms_bucket{le="1"} 0' in text
    assert 'lat_ms_bucket{le="10"} 1' in text
    assert 'lat_ms_bucket{le="+Inf"} 1' in text
    assert "lat_ms_sum 5" in text and "lat_ms_count 1" in text


def test_metric_catalog_roundtrips_docs_and_prometheus():
    """Every cataloged metric appears in docs/OBSERVABILITY.md's catalog
    table AND in the Prometheus snapshot of a catalog-registered registry;
    the docs table carries no phantom metrics either."""
    reg = MetricsRegistry()
    reg.register_catalog()
    text = reg.snapshot_prometheus()
    for name, kind, _help in METRIC_CATALOG:
        assert f"# TYPE {name} {kind}" in text, name
    doc = os.path.join(os.path.dirname(__file__), "..", "..", "docs",
                       "OBSERVABILITY.md")
    with open(doc, encoding="utf-8") as f:
        rows = [ln for ln in f if ln.startswith("| `")]
    documented = {ln.split("`")[1] for ln in rows}
    assert documented == {name for name, _k, _h in METRIC_CATALOG}


# -- tracer units ------------------------------------------------------------


def test_tracer_span_nesting_and_exports(tmp_path):
    tr = Tracer(ring_len=4)
    with tr.span("step.run", track="engine", step=0):
        with tr.span("step.absorb", track="engine", step=0):
            tr.instant("request.commit", track="engine", step=0, rid=1, n=1)
    tr.instant("request.done", track="other", rid=1, reason="eos")
    chrome = tmp_path / "t.trace.json"
    tr.export_chrome(str(chrome))
    stats = validate_chrome_trace(str(chrome))
    assert stats == {"events": 6, "spans": 2, "instants": 2, "tracks": 1}
    jsonl = tmp_path / "t.trace.jsonl"
    tr.export_jsonl(str(jsonl))
    lines = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
    assert len(lines) == 4
    assert {ln["name"] for ln in lines} == {
        "step.run", "step.absorb", "request.commit", "request.done",
    }
    # the outer span closes after the inner: X events record on exit,
    # so the inner one appears first but nests by [ts, ts+dur]
    spans = {ln["name"]: ln for ln in lines if "dur_us" in ln}
    inner, outer = spans["step.absorb"], spans["step.run"]
    assert outer["ts_us"] <= inner["ts_us"]
    assert inner["ts_us"] + inner["dur_us"] <= outer["ts_us"] + outer["dur_us"]


def test_flight_ring_is_bounded():
    tr = Tracer(ring_len=8)
    for i in range(50):
        tr.instant("request.commit", rid=i)
    assert len(tr.events) == 50
    assert len(tr.ring) == 8
    assert [e.rid for e in tr.ring] == list(range(42, 50))


def test_null_tracer_is_inert():
    assert NULL_TRACER.events == ()
    NULL_TRACER.instant("request.submit", rid=0)
    with NULL_TRACER.span("step.run", step=3) as span:
        span.set_metadata(rows=8)  # the one span API, buffer or none
    assert NULL_TRACER.events == ()


def test_span_metadata_set_inside_lands_in_the_event():
    tr = Tracer()
    with tr.span("step.plan", step=4) as span:
        span.set_metadata(rows=7, samples=2)
    (ev,) = tr.events
    assert (ev.name, ev.step, ev.args) == ("step.plan", 4,
                                           {"rows": 7, "samples": 2})


def test_digest_excludes_timing_and_stream_edges():
    def fill(tr, *, shift, with_pause):
        tr.instant("request.submit", rid=0, step=1 + shift, prompt_len=4)
        if with_pause:
            tr.instant("stream.pause", rid=0, step=2 + shift)
            tr.instant("stream.resume", rid=0, step=3 + shift)
        tr.instant("request.done", rid=0, step=9 + shift, reason="eos")
        tr.instant("step.plan", rid=-1)  # rid-less events never count

    a, b = Tracer(), Tracer()
    fill(a, shift=0, with_pause=True)
    fill(b, shift=5, with_pause=False)
    assert a.digest() == b.digest()
    c = Tracer()
    c.instant("request.submit", rid=0, step=1, prompt_len=5)  # arg differs
    c.instant("request.done", rid=0, step=9, reason="eos")
    assert c.digest() != a.digest()


# -- serving integration -----------------------------------------------------


def test_tracing_on_off_parity_and_compile_once(params):
    """The observability contract's heart: switching tracing ON changes
    neither the greedy token stream nor the number of compiled step
    signatures, and the trace actually recorded the run."""
    reqs = lambda: _reqs([5, 9, 3], seed0=10)  # noqa: E731
    base_eng = ServingEngine(own(params), CFG, _sc())
    base = base_eng.serve_batch(reqs())
    sc = _sc(observability=ObservabilityConfig(enabled=True))
    eng = ServingEngine(own(params), CFG, sc)
    res = eng.serve_batch(reqs())
    assert res["outputs"] == base["outputs"]
    assert res["stats"]["compiled_signatures"] == 1
    assert base["stats"]["compiled_signatures"] == 1
    names = {e.name for e in eng.obs.tracer.events}
    assert {"step.plan", "step.run", "step.upload", "step.dispatch",
            "step.readback", "step.absorb", "request.submit",
            "request.admit", "request.first_token", "request.done"} <= names
    # one step clock: every span of a turn carries the number its
    # step.run carries, and step.run's children nest inside it
    spans = [e for e in eng.obs.tracer.events if e.ph == "X"]
    runs = {e.step: e for e in spans if e.name == "step.run"}
    assert sorted(runs) == list(range(eng.steps_run))
    for name in ("step.absorb", "step.upload", "step.dispatch",
                 "step.readback"):
        assert sorted(e.step for e in spans if e.name == name) == sorted(runs)
    # (a turn that could plan nothing has a step.plan without rows)
    assert sorted(e.step for e in spans if e.name == "step.plan"
                  and "rows" in e.args) == sorted(runs)
    for e in spans:
        if e.name in ("step.upload", "step.dispatch", "step.readback"):
            run = runs[e.step]
            assert run.ts <= e.ts and e.ts + e.dur <= run.ts + run.dur
    assert all(set(r.args) == {"rows", "samples"} for r in runs.values())
    # a planned turn says what grid its step's paged-attention calls walk:
    # at most a segment a real row, at least a live page a segment
    plans = [e.args for e in spans if e.name == "step.plan" and "rows" in e.args]
    assert all(1 <= p["attn_segments"] <= p["rows"] for p in plans)
    assert all(p["attn_live_blocks"] >= p["attn_segments"] for p in plans)
    assert all(0 <= p["attn_one_row_blocks"] <= p["attn_live_blocks"]
               for p in plans)
    # with neither sink nothing is recorded
    assert base_eng.obs.tracer is NULL_TRACER and NULL_TRACER.events == ()
    reg = eng.obs.registry.snapshot()
    assert reg["serve_attn_segments"] == plans[-1]["attn_segments"]
    assert reg["serve_attn_live_blocks"] == plans[-1]["attn_live_blocks"]
    assert reg["serve_steps_total"] > 0
    assert reg["serve_new_tokens_total"] == sum(
        len(o) for o in res["outputs"]
    )
    assert reg["serve_step_ms"]["count"] == reg["serve_steps_total"]


PLAN_ARGS = {"free_pages", "resident", "preempted", "attn_segments",
             "attn_live_blocks", "attn_one_row_blocks", "rows", "samples"}


def _stream_all(fe, reqs):
    """Submit `reqs` to a started frontend, read every stream, close."""

    async def main():
        fe.start()
        outs = await asyncio.gather(*[fe.submit(r).collect() for r in reqs])
        await fe.close()
        return outs

    return asyncio.run(main())


def _serve_loop(loop, params, sc, reqs):
    """Drive `reqs` through one of the five serve loops, tracer on;
    returns (the tracer, the engines whose steps it drove)."""
    dc = DisaggConfig(enabled=True, prefill_replicas=1, decode_replicas=1)
    if loop == "engine":
        eng = ServingEngine(own(params), CFG, sc)
        eng.serve_batch(reqs)
        return eng.obs.tracer, [eng]
    if loop == "replica_router":
        router = ReplicaRouter(own(params), CFG, sc, ServeMeshConfig(replicas=2))
        router.serve_batch(reqs)
        return router.obs.tracer, router.engines
    if loop == "disagg_router":
        router = DisaggRouter(own(params), CFG, sc, dc)
        router.serve_batch(reqs)
        return router.obs.tracer, router.prefill + router.decode
    if loop == "online":
        eng = ServingEngine(own(params), CFG, sc)
        _stream_all(OnlineFrontend(eng, FrontendConfig(idle_sleep_s=0.0002)),
                    reqs)
        return eng.obs.tracer, [eng]
    assert loop == "disagg_online"
    router = DisaggRouter(own(params), CFG, sc, dc)
    _stream_all(
        DisaggOnlineFrontend(router, FrontendConfig(idle_sleep_s=0.0002)),
        reqs)
    return router.obs.tracer, router.prefill + router.decode


@pytest.mark.parametrize("loop", ["engine", "replica_router", "disagg_router",
                                  "online", "disagg_online"])
def test_every_serve_loop_plans_under_the_step_plan_span(params, loop):
    """All five loops plan through `ServingEngine.plan_turn`: every step an
    engine ran has, under that engine's number for the step, a `step.plan`
    span saying what the turn did to the pool, what grid the
    step's attention walks and the rows it planned, closed before the
    step's `step.run` began."""
    sc = _sc(observability=ObservabilityConfig(enabled=True))
    tracer, engines = _serve_loop(loop, params, sc,
                                  _reqs([5, 9, 3, 7], seed0=40))
    spans = [e for e in tracer.events if e.ph == "X"]
    # several engines count their steps apart: the track tells them apart
    key = (lambda e: (e.track, e.step)) if len(engines) > 1 else (
        lambda e: (engines[0].track, e.step))
    runs = {key(e): e for e in spans if e.name == "step.run"}
    assert sorted(runs) == sorted(
        (eng.track, n) for eng in engines for n in range(eng.steps_run))
    assert all(eng.steps_run > 2 for eng in engines)
    plans = {key(e): e for e in spans
             if e.name == "step.plan" and "rows" in e.args}
    assert sorted(plans) == sorted(runs)
    for key, plan in plans.items():
        run = runs[key]
        assert set(plan.args) == PLAN_ARGS
        assert all(isinstance(v, int) for v in plan.args.values())
        assert (plan.args["rows"], plan.args["samples"]) == (
            run.args["rows"], run.args["samples"])
        assert 1 <= plan.args["attn_segments"] <= plan.args["rows"]
        assert plan.ts + plan.dur <= run.ts, "planned before it ran"
    # a turn that planned nothing says so too (the pool's args, no rows)
    assert all(set(e.args) >= PLAN_ARGS - {"rows", "samples"}
               for e in spans if e.name == "step.plan")


def test_digest_stable_across_identical_load_tests(params):
    """Two fresh engines driving the SAME deterministic online trace
    produce the same lifecycle digest even though wall-clock timings (and
    hence idle turns / pause edges) differ run to run."""
    lt = LoadTestConfig(
        num_requests=8, prompt_len=(3, 8), max_new_tokens=5,
        mean_interarrival_steps=0.5, seed=3,
    )
    fc = FrontendConfig(idle_sleep_s=0.0002, stream_buffer=64)
    digests = []
    for _ in range(2):
        eng = ServingEngine(
            own(params), CFG, _sc(observability=ObservabilityConfig(enabled=True)),
        )
        report = run_load_test(eng, lt, fc)
        assert report["completed"] == 8
        digests.append(eng.obs.tracer.digest())
    assert digests[0] == digests[1]


def test_disagg_timeline_phases_sum_to_ttft(params):
    """Disagg run with handoffs: every first-token request's attribution
    components (queue + prefill + transfer + step + backpressure) sum to
    its measured TTFT exactly, and the handoff made the transfer phase
    real (markers present, not zero-width by omission)."""
    sc = _sc(observability=ObservabilityConfig(enabled=True))
    dc = DisaggConfig(enabled=True, transfer_pages=4, prefill_token_budget=16)
    router = DisaggRouter(own(params), CFG, sc, dc)
    res = router.serve_batch(_reqs([5, 11, 3, 7], seed0=30))
    assert res["stats"]["handoffs"] == 4
    events = list(router.obs.tracer.events)
    assert any(e.name == "kv_transfer" and e.ph == "X" for e in events)
    tls = build_timelines(events)
    spans = sorted(
        (e.ts, e.ts + e.dur) for e in events
        if e.ph == "X" and e.name == "step.run"
    )
    checked = 0
    for tl in tls.values():
        att = attribute_ttft(tl, spans)
        if att is None:
            continue
        total = (att["queue_ms"] + att["prefill_ms"] + att["transfer_ms"]
                 + att["step_ms"] + att["backpressure_ms"])
        assert total == pytest.approx(att["ttft_ms"], abs=1e-6)
        assert tl.t_extract is not None and tl.t_handoff_admit is not None
        checked += 1
    assert checked == 4
    summary = attribution_summary(events)
    assert summary["with_first_token"] == 4
    assert summary["ttft_p50"]["transfer_ms"] >= 0.0


def test_flight_recorder_dumps_on_injected_crash(params, tmp_path):
    """An injected serve-step FaultCrash (a BaseException, like a real
    preemption) escapes serve_batch — but not before the flight recorder
    writes its ring of the last events before the failure."""
    dump = tmp_path / "flight.jsonl"
    sc = _sc(observability=ObservabilityConfig(
        enabled=True, flight_recorder_len=32,
        flight_recorder_path=str(dump),
    ))
    eng = ServingEngine(own(params), CFG, sc)
    with injected({"point": "serve_step", "mode": "crash", "step": 2}):
        with pytest.raises(FaultCrash):
            eng.serve_batch(_reqs([5, 7], seed0=50))
    assert dump.exists()
    lines = [json.loads(ln) for ln in dump.read_text().splitlines()]
    assert lines[0]["flight_recorder"] is True
    assert lines[0]["reason"] == "crash"
    assert lines[0]["events"] == len(lines) - 1 > 0
    assert {"step.plan", "step.run"} <= {ln["name"] for ln in lines[1:]}
    snap = eng.obs.registry.snapshot()
    assert snap['flight_recorder_dumps_total{reason="crash"}'] == 1.0


def test_observability_disabled_is_null_tracer(params):
    """Default config: the engine gets the null tracer (no events, no
    ring) while the registry still mirrors the run's stats."""
    eng = ServingEngine(own(params), CFG, _sc())
    res = eng.serve_batch(_reqs([4, 6], seed0=70))
    assert eng.obs.tracer is NULL_TRACER
    assert eng.obs.enabled is False
    assert eng.obs.registry.snapshot()["serve_new_tokens_total"] == sum(
        len(o) for o in res["outputs"]
    )


def test_observability_export_writes_both_faces(tmp_path):
    obs = Observability(ObservabilityConfig(
        enabled=True, trace_path=str(tmp_path / "run" / "serve"),
    ))
    with obs.tracer.span("step.run", step=0):
        obs.tracer.instant("request.commit", rid=0, step=0, n=1)
    paths = obs.export()
    assert set(paths) == {"chrome", "jsonl"}
    assert validate_chrome_trace(paths["chrome"])["spans"] == 1
    assert len(open(paths["jsonl"]).read().splitlines()) == 2
    # disabled bundles export nothing
    assert Observability(None).export(str(tmp_path / "x")) == {}


# -- the two sinks: profiler session and in-memory buffer --------------------

TURN_SPANS = ("frontend.intake", "step.plan", "step.absorb", "frontend.emit")
RUN_SPANS = ("step.upload", "step.dispatch", "step.readback")


def _online(eng, lens=(5, 9, 3), max_new=6):
    """Drive a tiny OnlineFrontend over `eng`; returns each request's tokens."""

    async def main():
        fe = OnlineFrontend(eng, FrontendConfig(idle_sleep_s=0.0002))
        fe.start()

        async def one(req):
            return [t async for t in fe.submit(req)]

        outs = await asyncio.gather(
            *[one(r) for r in _reqs(lens, seed0=90, max_new=max_new)])
        await fe.close()
        return outs

    return asyncio.run(main())


def _profiled(tmp_path, fn):
    """Run `fn` under a profiler session; returns (its result, the
    `serve.*` host events as (name, line, start_ns, end_ns, stats))."""
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    events, n_line = [], 0
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            n_line += 1
            events += [
                (e.name, n_line, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats))
                for e in ln.events if e.name.startswith("serve.")]
    return out, events


@pytest.fixture(scope="module")
def online_untraced(params, tmp_path_factory):
    """The online loop under a profiler session, observability disabled."""
    eng = ServingEngine(own(params), CFG, _sc())
    outs, events = _profiled(
        tmp_path_factory.mktemp("prof_off"), lambda: _online(eng))
    return eng, outs, events


def test_online_spans_reach_the_profiler_with_observability_off(online_untraced):
    """Per engine step: one serve.step.run with upload / dispatch / readback
    nested in it on the executor thread's line, and the frontend's four
    spans on the loop's line, all with that step's `engine_step`."""
    eng, _outs, events = online_untraced
    assert eng.obs.tracer is NULL_TRACER and eng.steps_run > 3
    by_step = {}
    for name, line, t0, t1, stats in events:
        by_step.setdefault(stats["engine_step"], []).append(
            (name[len("serve."):], line, t0, t1, stats))
    for step in range(eng.steps_run):
        evs = by_step[step]
        (run,) = [e for e in evs if e[0] == "step.run"]
        assert set(run[4]) == {"engine_step", "rows", "samples"}
        for name in RUN_SPANS:
            (child,) = [e for e in evs if e[0] == name]
            assert child[1] == run[1], "same thread line"
            assert run[2] <= child[2] and child[3] <= run[3], name
        # the turn that planned this step (idle turns plan nothing and
        # carry no rows), and the one absorb / emit after it
        loop_lines = set()
        for name in TURN_SPANS:
            mine = [e for e in evs if e[0] == name]
            assert mine, name
            loop_lines |= {e[1] for e in mine}
        assert len(loop_lines) == 1 and run[1] not in loop_lines
        (plan,) = [e for e in evs if e[0] == "step.plan" and "rows" in e[4]]
        assert plan[4]["rows"] == run[4]["rows"]
        assert plan[3] <= run[2], "planned before it ran"
        (absorb,) = [e for e in evs if e[0] == "step.absorb"]
        assert run[3] <= absorb[2], "absorbed after it ran"
    # no instant is mirrored: a commit a token would swamp the trace
    assert not [e for e in events if e[0].startswith("serve.request.")]


def test_online_spans_with_observability_on_match_and_validate(
        params, online_untraced, tmp_path):
    """The same run with the buffer kept: the same spans in Tracer.events
    AND in the profiler's trace, a valid Chrome export (no span is held
    across an await, so each track nests), and the same greedy tokens."""
    _eng, base_outs, base_events = online_untraced
    eng = ServingEngine(
        own(params), CFG, _sc(observability=ObservabilityConfig(enabled=True)))
    outs, events = _profiled(tmp_path / "prof", lambda: _online(eng))
    assert outs == base_outs
    assert eng.step_cache_size() == 1
    spans = [e for e in eng.obs.tracer.events if e.ph == "X"]
    in_memory = sorted((e.name, e.step) for e in spans)
    in_profile = sorted((n[len("serve."):], st["engine_step"])
                        for n, _l, _a, _b, st in events)
    assert in_memory == in_profile
    # the untraced run made the same spans, step for step (idle turns, whose
    # number the wall clock decides, add intake and plan spans only)
    per_step = {"serve." + n for n in
                RUN_SPANS + ("step.run", "step.absorb", "frontend.emit")}
    assert sorted((n, st["engine_step"]) for n, _l, _a, _b, st in base_events
                  if n in per_step) == sorted(
        (n, st["engine_step"]) for n, _l, _a, _b, st in events
        if n in per_step)
    for name in TURN_SPANS + RUN_SPANS + ("step.run",):
        assert any(e.name == name for e in spans), name
    chrome = tmp_path / "online.trace.json"
    eng.obs.tracer.export_chrome(str(chrome))
    stats = validate_chrome_trace(str(chrome))
    assert stats["spans"] == len(spans) and stats["tracks"] == 2


def test_serve_step_names_its_sublayers_and_keeps_its_instructions():
    """The compiled step carries the serve.* scopes as metadata only: the
    baseline's instruction counts hold with them in."""
    from automodel_tpu.analysis import compare_report, load_baseline
    from automodel_tpu.analysis.entrypoints import ENTRY_POINTS, _configs
    from automodel_tpu.analysis.hlo import analyze_compiled
    from automodel_tpu.models.moe_lm import decoder as moe_decoder

    compiled, mesh_axes = ENTRY_POINTS["paged_serve_step"]()
    text = compiled.as_text()
    for scope in ("serve.cow", "serve.embed", "serve.layers", "serve.attn",
                  "serve.pool_write", "serve.mlp", "serve.head"):
        assert f"/{scope}/" in text, scope
    baselines = os.path.join(
        os.path.dirname(__file__), "..", "..", "automodel_tpu", "analysis",
        "baselines")
    report = analyze_compiled(compiled, entry="paged_serve_step",
                              mesh_axes=mesh_axes)
    assert compare_report(
        report, load_baseline(baselines, "paged_serve_step")) == []

    _dense, moe_cfg = _configs()
    eng = ServingEngine(
        moe_decoder.init(moe_cfg, jax.random.key(0)), moe_cfg, _sc())
    text = eng.lower_step().compile().as_text()
    for scope in ("serve.layers", "serve.attn", "serve.moe/serve.moe.route",
                  "serve.moe/serve.moe.experts", "serve.moe/serve.moe.shared",
                  "serve.head"):
        assert f"/{scope}/" in text, scope
    assert "serve.mlp" not in text  # first_k_dense == 0: no dense stack


def test_compile_counter_rises_once_for_the_step(params):
    """`jax_backend_compiles_total` sees the step's one compilation over
    the first step and none between the second and the tenth."""
    eng = ServingEngine(own(params), CFG, _sc())
    sched = eng.make_scheduler(arrival_gating=False)
    for r in _reqs([6, 7, 5], seed0=110, max_new=12):
        sched.submit(r)
    compiles = lambda: eng.obs.registry.snapshot().get(  # noqa: E731
        "jax_backend_compiles_total", 0.0)
    before = compiles()
    eng.run_one_step(sched, 0)
    after_first = compiles()
    assert after_first >= before + 1
    seconds = eng.obs.registry.snapshot()["jax_backend_compile_seconds_total"]
    assert seconds > 0.0
    eng.run_one_step(sched, 1)
    after_second = compiles()
    for i in range(2, 10):
        plan, _n, _dt = eng.run_one_step(sched, i)
        assert plan is not None
    assert compiles() == after_second
    assert eng.step_cache_size() == 1
