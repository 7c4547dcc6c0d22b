"""The benchmark's one command (BENCHMARK.json `command`):

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell on the chip(s) this machine holds: set-up (everything up
to the first measured step or request, compile included), a window of
`--seconds`, a correctness check, and as the LAST line of stdout the result
object {correct, attempted, failed, metrics, device[, breakdown]}. Earlier
lines are commentary: set-up phases, the run's parameters, p50/p99.
`--trace 0` prints the cell's end-to-end metrics, `--trace 1` its per-layer
metrics from a traced stretch inside the window.

It measures on a TPU or exits non-zero with no result line. `--rehearsal`
(tests only) walks the same control flow on the CPU at the toy sizes the
configuration and traffic files carry under `rehearsal`; its line says
`"platform": "cpu"` and its numbers are not measurements.

A cell, a configuration, a traffic mix and a layer metric are files found by
the names in BENCHMARK.json (README.md): nothing here is edited to add one.
"""

from __future__ import annotations

import time

_T_HARNESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


class Context:
    """What a runner gets, and what the layer-metric readers read afterwards."""

    def __init__(self, args, cell, config, traffic, devices, phases, compile_log, registry):
        self.workload = cell["name"]
        self.chips = int(cell["chips"])
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace_on = bool(args.trace)
        self.rehearsal = args.rehearsal
        self.config = config
        self.traffic = traffic
        self.devices = devices
        self.phases = phases
        self.compile_log = compile_log
        self.spans = harness.Spans()
        self._registry = registry
        self.registry_before: dict = {}
        self.registry_after: dict = {}
        self.compile_setup: dict = {}
        self.compile_window: dict = {}
        self._mark = compile_log.mark()
        # the traced stretch: the window's last few seconds, so that stopping
        # the profiler (seconds of host time) falls after the window's clock
        self.trace_for_s = min(float(traffic.get("trace_for_s", 3.0)), 0.5 * args.seconds)
        self.trace_after_s = args.seconds - self.trace_for_s
        self.stretch = harness.TracedStretch() if self.trace_on else None
        self._trace_state = "off" if not self.trace_on else "waiting"
        self.trace = None  # trace_reduce.Trace once read
        self.result: dict = {}

    def window_opens(self) -> None:
        self.compile_setup = self.compile_log.since(self._mark)
        self._mark = self.compile_log.mark()
        self.spans.reset()
        self.registry_before = self._registry.snapshot()

    def window_closes(self) -> None:
        self.registry_after = self._registry.snapshot()
        self.compile_window = self.compile_log.since(self._mark)
        if self._trace_state == "tracing":
            self.stretch.stop()
            self._trace_state = "done"

    def tick(self, elapsed_s: float) -> None:
        """Called by the runner now and then inside the window."""
        if self._trace_state == "waiting" and elapsed_s >= self.trace_after_s:
            self.stretch.start()
            self._trace_state = "tracing"


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None, help="default: BENCHMARK.json run_seconds")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="tests only: toy sizes on the CPU; control flow, never a measurement")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    phases = harness.Phases(_T_HARNESS, harness.process_age_s() - (time.perf_counter() - _T_HARNESS))
    manifest = harness.load_json(harness.MANIFEST)
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    cell, config, traffic = harness.resolve_cell(manifest, args.workload)
    config = harness.with_rehearsal(config, args.rehearsal)
    traffic = harness.with_rehearsal(traffic, args.rehearsal)
    chips = int(cell["chips"])

    if args.rehearsal:
        # before jax is imported: the CPU backend, with as many devices as the cell has chips
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        os.environ["XLA_FLAGS"] = " ".join(flags + [f"--xla_force_host_platform_device_count={chips}"])

    import jax

    from yet_another_mobilenet_series_tpu.obs.registry import get_registry
    from yet_another_mobilenet_series_tpu.utils import compile_cache

    # first: every run after a checkout's first is served from <checkout>/.jax_cache
    # (or JAX_COMPILATION_CACHE_DIR); off for a process held to the CPU
    cache_dir = compile_cache.configure()
    compile_log = harness.CompileLog()
    phases.done("imports_and_manifest")

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearsal:
        harness.log(f"benchmark: no TPU: JAX reports platform {platform!r} ({devices[0].device_kind}). "
                    "This benchmark measures on the chip or fails.")
        return 3
    if len(devices) < chips:
        harness.log(f"benchmark: cell {args.workload} needs {chips} chips, JAX reports {len(devices)}")
        return 3
    devices = devices[:chips]
    phases.done("backend_init")
    # the first program a process runs pays the runtime's lazy start: named, so
    # that it is not read as the cost of whatever the runner happens to run first
    jax.block_until_ready(jax.jit(lambda x: x + 1)(0))
    phases.done("first_device_call")

    ctx = Context(args, cell, config, traffic, devices, phases, compile_log, get_registry())
    runner = importlib.import_module("benchmark.runners." + traffic["runner"])
    out = runner.run(ctx)
    ctx.result = out
    setup_s = phases.setup_s(out["t_window_start"])
    facts = out["facts"]
    device = harness.device_facts(devices, facts.get("program_temp_bytes", 0))
    facts["peak_hbm_bytes"] = device["memory_peak_bytes"]

    correct = bool(out["correct"])
    # nothing may compile inside the window, whatever the runner checked
    facts["checks"]["no_compile_in_window"] = ctx.compile_window["compiles"] == 0
    correct = correct and facts["checks"]["no_compile_in_window"]

    result = {"correct": correct, "attempted": int(out["attempted"]), "failed": int(out["failed"])}
    breakdown = None
    if ctx.trace_on:
        from benchmark import readers, trace_reduce

        if ctx.stretch.path:
            ctx.trace = trace_reduce.load(ctx.stretch.path)
            busy = trace_reduce.device_busy(ctx.trace)
            if busy is not None:
                device["busy_s"], device["window_s"] = busy
                breakdown = trace_reduce.breakdown(ctx.trace)
        ctx.stretch.cleanup()
        values = readers.read_all(ctx, harness.metrics_of(manifest, "per_layer", args.workload))
    else:
        values = {"setup_s": setup_s, **out["end_to_end"]}
        values = {m["name"]: values.get(m["name"])
                  for m in harness.metrics_of(manifest, "end_to_end", args.workload)}
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"] + manifest["per_layer"]}
    result["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in values.items() if v is not None}
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown

    harness.emit({"setup_phases": phases.times, "setup_s": setup_s, "cache_dir": cache_dir,
                  "compile_setup": ctx.compile_setup, "compile_window": ctx.compile_window,
                  # the imports that cost PR 23 half a minute of every run's set-up
                  "heavy_imports": [m for m in ("orbax", "tensorflow", "google.cloud.logging") if m in sys.modules]})
    harness.emit({"run": {"workload": args.workload, "seed": args.seed, "seconds": args.seconds,
                          "trace": args.trace, "rehearsal": args.rehearsal, **facts}})
    if args.rehearsal:
        harness.log("benchmark: REHEARSAL on the CPU at toy sizes: control flow only, no number "
                    "on the next line is a measurement")
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
