"""trace_reduce on a small synthetic trace, against values worked by hand."""

import pytest

from benchmark import trace_reduce as tr

# one chip; seconds. Three fusions, one kernel twice, two collectives.
OPS = [
    ("fusion.1", 0.0, 1.0),
    ("paged_attention_mla.3", 1.0, 0.5),
    ("all-gather.2", 1.25, 1.0),        # 0.25 hidden under the kernel
    ("fusion.22", 3.0, 1.0),
    ("all-reduce.7", 3.5, 0.25),        # wholly hidden
    ("paged_attention_mla.9", 5.0, 0.5),
    ("fusion.1", 7.0, 1.0),
]
DEV = tr.DeviceTrace(chip=0, ops=OPS, modules=[
    ("jit__step_impl(123)", 0.0, 2.25), ("jit_other(5)", 3.0, 1.0),
    ("jit__step_impl(123)", 5.0, 3.0)])
HOST = [("bench.run_step", 0.0, 2.4), ("bench.deliver", 2.4, 0.5),
        ("bench.run_step", 4.9, 3.2)]
TRACE = tr.Trace(devices=[DEV], host=HOST)


def test_union_and_total():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.total([(0, 2.5), (3, 4)]) == 3.5


def test_busy_and_window():
    assert tr.busy_intervals(DEV) == [(0.0, 2.25), (3.0, 4.0), (5.0, 5.5), (7.0, 8.0)]
    assert tr.busy_seconds(DEV) == pytest.approx(4.75)
    assert tr.window_of(TRACE) == (0.0, 8.0)
    idle_share = 1 - tr.busy_seconds(DEV) / 8.0
    assert idle_share == pytest.approx(0.40625)


def test_per_name_sums():
    assert tr.name_seconds(DEV, r"paged_attention_mla") == (1.0, 2)
    assert tr.name_seconds(DEV, r"^fusion") == (3.0, 3)
    assert tr.name_seconds(DEV, r"nothing") == (0, 0)


def test_top_ops_fold_numbers():
    top = tr.top_ops(DEV, k=3)
    assert top[0] == ["fusion.N", 3.0]
    assert sorted(top[1:]) == [["all-gather.N", 1.0], ["paged_attention_mla.N", 1.0]]


def test_exposed_collective_time():
    # all-gather 1.25..2.25 overlaps the kernel until 1.5: 0.75 exposed;
    # all-reduce 3.5..3.75 lies inside fusion.22: 0 exposed
    assert tr.exposed_collective_seconds(DEV) == pytest.approx(0.75)


def test_idle_gaps_are_named_by_the_host_span_that_covers_them():
    gaps = tr.idle_gaps(DEV, HOST, k=2)
    # longest: 5.5..7.0 (1.5 s), inside the second run_step; then 4.0..5.0,
    # of which run_step covers 0.1 and nothing else any
    assert gaps[0] == ["bench.run_step", pytest.approx(1.5)]
    assert gaps[1][1] == pytest.approx(1.0)
    # 2.25..3.0: run_step covers 0.15, deliver 0.5
    assert tr.idle_gaps(DEV, HOST, k=3)[2] == ["bench.deliver", pytest.approx(0.75)]
    # the host under no annotation at all (2.9..4.9) covers most of 4.0..5.0
    assert gaps[1][0] == "outside bench.*"


def test_short_names_and_enclosing_ops():
    line = "%while.4 = (s32[], bf16[1,256,2048]{2,1,0}) while((s32[]) %tuple.9)"
    assert tr.short_name(line) == "while.4"
    assert tr.short_name("%paged_attention_mla.9 = bf16[256,16,512] custom-call(") \
        == "paged_attention_mla.9"
    dev = tr.DeviceTrace(0, [("while.4", 0.0, 5.0), ("fusion.1", 0.0, 2.0)], [])
    assert tr.top_ops(dev) == [["fusion.N", 2.0]]
    assert tr.busy_seconds(dev) == 5.0


def test_program_runs_and_traced_steps():
    assert tr.program_runs(DEV, r"_step_impl") == [(0.0, 2.25), (5.0, 3.0)]

    class S:
        def __init__(self, t0, t1):
            self.t0, self.t1 = t0, t1

    steps = [S(0, 1), S(1, 2), S(2, 3), S(3, 4)]
    kept = tr.traced(steps, {"trace_on": 0.5, "trace_off": 3.5})
    assert [(s.t0, s.t1) for s in kept] == [(1, 2), (2, 3)]


def test_load_reads_a_recorded_trace(tmp_path):
    """A trace recorded here (CPU: no device plane) still yields the
    harness's own annotations from the host plane."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.unit_test"):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    trace = tr.load(tr.find_xplane(str(tmp_path)))
    assert trace.devices == []
    assert [n for n, _, _ in trace.host] == ["bench.unit_test"]
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(str(tmp_path / "nothing"))
