"""Run one cell of ``BENCHMARK.json`` on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything runs in this one process, which holds the chip. Without a TPU,
or with fewer chips than the cell asks for, it exits with code 2 and prints
no result; it never falls back to the CPU.

A run: set-up (operands or weights from ``--seed``, every program the cell's
traffic reaches compiled or loaded from the persistent cache and run once),
then ``--seconds`` of measurement with the profiler off (``--trace 0``,
end-to-end metrics), or a short traced sub-window (``--trace 1``, per-layer
metrics read from the device trace), then the correctness check of what the
timed path produced against the plain reference, once the program's state
is freed. ``setup_s`` runs from process start to the first timed call.

Standard error carries the dispatch decisions, the compilations counted
inside the window, per-layer notes and, as its last lines, each compared
number beside its limit. The last line of standard output is the result:
``{"correct", "attempted", "failed", "metrics", "device", ["breakdown"],
"checks"}``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts JAX's compile-path events (tracing, lowering, backend
    compile) through ``jax.monitoring``; a program loaded from the
    persistent cache still traces, so any new program counts."""

    def __init__(self):
        import jax

        self.n = 0
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.n += 1
            self.events.append(event)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_or_none(cell, chip: bool):
    """The first device, or None (after saying why) where the cell cannot
    run: no TPU, or fewer devices than the cell's chips."""
    import jax

    devs = jax.devices()
    if chip and devs[0].platform != "tpu":
        say(f"bench: no TPU: JAX's first device is {devs[0].platform!r} "
            f"({devs[0].device_kind}); the benchmark never falls back to "
            "the CPU")
        return None
    if len(devs) < cell.chips:
        say(f"bench: workload {cell.name} needs {cell.chips} chips, JAX "
            f"sees {len(devs)}")
        return None
    return devs[0]


def use_cache() -> str:
    """The program's persistent compilation cache (``$JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), keeping every program however fast it
    compiled, so a second run of a cell loads all of them."""
    import jax
    from repro.launch import use_compile_cache

    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def traced_run(system, cell, peaks):
    """The traced sub-window, reduced in process: (per-layer metrics,
    device busy and window seconds, breakdown, attempted, failed)."""
    import jax

    from benchmarks.chip import trace as tr

    options = jax.profiler.ProfileOptions()
    options.enable_hlo_proto = False  # the trace needs op names, not HLO
    # no event per Python call: the serving loop would make millions of
    # them, slowing the traced job and its reduction; the harness's spans
    # and the runtime's own host events still label the idle gaps
    options.python_tracer_level = 0
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tdir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("bench/window"):
                info = system.traced()
        finally:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        t1 = time.perf_counter()
        trace = tr.load(files[0], window_span="bench/window")
        say(f"trace: {os.path.getsize(files[0])} bytes written in "
            f"{t1 - t0:.3f} s, {len(trace.ops)} device ops read in "
            f"{time.perf_counter() - t1:.3f} s")
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    reading = types.SimpleNamespace(trace=trace, info=info, peaks=peaks,
                                    cell=cell, say=say)
    metrics = {}
    for m in cell.per_layer:
        value = cell.metric_reader(m)(reading)
        if value is None:
            say(f"bench: per-layer metric {m['name']} found nothing to read")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    breakdown = {"device_ops": trace.top_ops(10),
                 "idle_gaps": trace.idle_gaps(10)}
    return (metrics, trace.busy_s(), trace.window_s(), breakdown,
            info["attempted"], info["failed"])


def main(argv=None, *, root: str = ROOT, chip: bool = True) -> int:
    """One run. ``chip=False`` (the tests' CPU runs) skips the look for a
    TPU, the persistent cache and the peaks; nothing else changes."""
    args = parse(argv)
    for p in (os.path.join(root, "src"), root):
        if p not in sys.path:
            sys.path.insert(0, p)
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        say(f"bench: no program under {root}/src: run from a checkout of "
            "the repository")
        return 2
    from benchmarks.chip import spec
    from benchmarks.chip.peaks import peaks_for

    cell = spec.resolve(args.workload, root)
    dev = device_or_none(cell, chip)
    if dev is None:
        return 2
    import jax

    peaks = peaks_for(dev.device_kind) if chip else None
    cache = use_cache() if chip else "off"
    counter = CompileCounter()
    say(f"bench: {cell.name} on {cell.chips} x {dev.device_kind} "
        f"({dev.platform}); seed {args.seed}; compile cache at {cache}")

    system = cell.kind_module().System(cell, args.seed, say)
    with jax.profiler.TraceAnnotation("bench/warmup"):
        system.setup()
    setup_s = time.perf_counter() - T_START
    say(f"bench: set-up {setup_s:.3f} s, {counter.n} compile-path events")

    before = counter.n
    if args.trace:
        metrics, busy_s, window_s, breakdown, attempted, failed = \
            traced_run(system, cell, peaks)
    else:
        out = system.window(args.seconds)
        attempted, failed = out["attempted"], out["failed"]
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in cell.end_to_end:
            if m["name"] != "setup_s":
                metrics[m["name"]] = {"value": float(out["metrics"][m["name"]]),
                                      "unit": m["unit"]}
    in_window = counter.n - before
    say(f"bench: compilations inside the window: {in_window} "
        f"{sorted(set(counter.events[before:]))}")
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips,
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    if args.trace:
        device.update(busy_s=busy_s, window_s=window_s)

    system.release()
    checks = system.check()
    correct = all(math.isfinite(v) and v <= limit for _, v, limit in checks)
    for name, v, limit in checks:
        say(f"check {name}: {v!r} (limit {limit!r}) "
            f"{'ok' if math.isfinite(v) and v <= limit else 'FAIL'}")
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": v, "limit": limit}
                        for name, v, limit in checks}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
