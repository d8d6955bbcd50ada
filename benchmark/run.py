"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One new process per run. It finds the cell in BENCHMARK.json, its
configuration file and traffic mix by name, and the driver of the mix's kind
under benchmark/drivers/; needs the cell's chips (no TPU: exit 2, nothing on
standard output); warms up, measures, checks what the window produced against
the plain reference, and prints ONE JSON object as the last line of standard
output. Earlier lines, `note: {...}`, carry counts and medians. See
benchmark/README.md.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Run:
    """What a driver is handed: the cell, the run's arguments, and the few
    services every driver needs (notes, tracing, memory)."""

    #: keys of a configuration file that are the benchmark's, not the model's
    NOT_HF_KEYS = ("source", "reduced", "assumed", "published", "stands_for",
                   "reference", "serve_dtype", "serving", "attn_impl",
                   "architectures", "step_kernels")
    trace_slice_s = 3.0

    def __init__(self, manifest: dict, workload: str, seed: int,
                 seconds: float, trace: bool, control: str | None,
                 on_chip: bool, root: str):
        from benchmark import traffic_gen

        self.manifest, self.root = manifest, root
        self.cell = next(w for w in manifest["workloads"] if w["name"] == workload)
        entry = next(c for c in manifest["configs"]
                     if c["name"] == self.cell["config"])
        with open(os.path.join(root, entry["file"])) as f:
            self.config = json.load(f)
        self.mix = traffic_gen.load_mix(
            self.cell["traffic"], root, manifest["paths"])
        self.workload, self.seed, self.seconds = workload, int(seed), float(seconds)
        self.trace, self.control, self.on_chip = bool(trace), control, on_chip
        self.trace_dir = os.path.join(root, ".bench_runs", "trace")
        self.memory_peak_bytes = None
        self.compiles = []
        self.gc_pauses = []      # (generation, seconds) inside the window
        self.window_open = False

    # -- notes ------------------------------------------------------------
    def note(self, **kw) -> None:
        print("note: " + json.dumps(kw, default=str), flush=True)

    # -- tracing ----------------------------------------------------------
    def annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        # no Python-function events: they are most of a trace's cost on the
        # host, and the harness's own annotations name what it needs
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()

    # -- compilations inside the window -------------------------------------
    def watch_compiles(self) -> None:
        import jax

        def on_event(event: str, seconds: float, **_kw) -> None:
            if event.endswith("backend_compile_duration") and self.window_open:
                self.compiles.append(seconds)

        jax.monitoring.register_event_duration_secs_listener(on_event)

    # -- garbage-collector pauses inside the window --------------------------
    def watch_gc(self) -> None:
        """Time every collection that runs while the window is open: a
        stall of the host loop can then be told from one of the device."""
        import gc

        began = [0.0]

        def on_gc(phase: str, info: dict) -> None:
            if phase == "start":
                began[0] = time.perf_counter()
            elif self.window_open:
                self.gc_pauses.append(
                    (info["generation"], time.perf_counter() - began[0]))

        gc.callbacks.append(on_gc)

    # -- memory -------------------------------------------------------------
    def read_memory_peak(self) -> None:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()[: self.cell["chips"]]]
        self.memory_peak_bytes = int(max(peaks))


def metrics_of(manifest: dict, kind: str, workload: str, reported: set) -> list:
    """The manifest's metrics of `kind` that this cell is to report."""
    out = []
    for m in manifest[kind]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif kind == "per_layer":
            if m["moves"] in reported:
                out.append(m)
        else:
            out.append(m)
    return out


def main(argv=None, *, manifest_path=None, on_chip=True, root=ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None,
                    choices=("int8", "fp8", "program_int8"),
                    help="not for measured runs: put the control in the "
                    "program's place, to see `correct` fail. int8, fp8: the "
                    "reference in that precision; program_int8: the "
                    "program's own serve_precision path")
    args = ap.parse_args(argv)

    with open(manifest_path or os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    if args.workload not in {w["name"] for w in manifest["workloads"]}:
        print(f"benchmark: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    run = Run(manifest, args.workload, args.seed, args.seconds, args.trace,
              args.control, on_chip, root)

    import jax

    devices = jax.devices()
    if on_chip and (devices[0].platform != "tpu"
                    or len(devices) < run.cell["chips"]):
        print(f"benchmark: {args.workload} needs {run.cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform!r} "
              "device(s). This command does not fall back.", file=sys.stderr)
        return 2
    from automodel_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program of a run, however small, is in the cache after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    run.watch_compiles()
    run.watch_gc()
    run.note(workload=args.workload, seed=args.seed, seconds=args.seconds,
             trace=args.trace, control=args.control, compile_cache=cache_dir,
             jax=jax.__version__)

    driver = importlib.import_module(f"benchmark.drivers.{run.mix['kind']}")
    # the driver sets run.window_open while it measures: a compilation in
    # there is counted, and makes the run not correct
    result = driver.run(run)
    return finish(run, manifest, result, devices)


def finish(run: Run, manifest: dict, result: dict, devices) -> int:
    """Reduce, print the notes and the one result line."""
    from benchmark import load_module
    from benchmark import peaks as peaks_mod

    window = result["window"]
    # window['t0'] is perf_counter; the process's start is wall time
    setup_s = (time.time() - T_START) - (time.perf_counter() - window["t0"])
    measured = dict(result["metrics"])
    measured["setup_s"] = (setup_s, "s")
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": run.memory_peak_bytes,
    }
    out_metrics, breakdown = {}, None
    reported = set(measured)
    # a rehearsal off the chip prints counts only: no number under a device
    # metric's name
    if run.on_chip and not run.trace:
        for m in metrics_of(manifest, "end_to_end", run.workload, reported):
            value, unit = measured[m["name"]]
            assert unit == m["unit"], (m["name"], unit, m["unit"])
            out_metrics[m["name"]] = {"value": value, "unit": unit}
    elif run.on_chip:
        from benchmark import trace_reduce

        trace = trace_reduce.load(trace_reduce.find_xplane(run.trace_dir))
        if os.environ.get("BENCH_DESCRIBE_TRACE"):
            trace_reduce.describe(trace_reduce.find_xplane(run.trace_dir))
        lo, hi = trace_reduce.window_of(trace)
        busy = [trace_reduce.busy_seconds(d) for d in trace.devices]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = hi - lo
        worst = max(trace.devices, key=trace_reduce.busy_seconds)
        breakdown = {
            "device_ops": trace_reduce.top_ops(worst),
            "idle_gaps": trace_reduce.idle_gaps(worst, trace.host),
        }
        ctx = {
            "trace": trace, "config": run.config, "mix": run.mix,
            "cell": run.cell, "steps": result["steps"], "recs": result["recs"],
            "window": window, "first_step_t": result["first_step_t"],
            "peaks": peaks_mod.peaks_for(devices[0].device_kind),
            "note": run.note, "root": run.root, "paths": manifest["paths"],
        }
        for m in metrics_of(manifest, "per_layer", run.workload, reported):
            # the reader of a per-layer metric: `<path>/layer_metrics/<name>.py`
            value = load_module(run.root, manifest["paths"], "layer_metrics",
                                m["name"]).read(ctx)
            if value is not None:
                out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        shutil.rmtree(run.trace_dir, ignore_errors=True)

    compared = result["compared"]
    run.note(setup_s=setup_s, total_s=time.time() - T_START,
             compilations_inside_window=len(run.compiles),
             gc_in_window={
                 "collections": len(run.gc_pauses),
                 "seconds": sum(s for _, s in run.gc_pauses),
                 "longest": sorted(run.gc_pauses, key=lambda p: -p[1])[:3]},
             observed=compared["observed"])
    correct = bool(compared["correct"]) and not run.compiles
    line = {
        "correct": correct, "attempted": result["attempted"],
        "failed": result["failed"], "metrics": out_metrics, "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared["numbers"]
    sys.stdout.flush()
    for name, n in compared["numbers"].items():
        print(f"compared: {name} = {n['value']} (limit {n['limit']})",
              file=sys.stderr)
    print(f"compared: correct = {correct}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
