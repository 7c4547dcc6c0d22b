"""What every runner shares: the manifest, set-up phases, the compile log, the
benchmark's own host spans, device facts and the traced stretch.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: those are files found through BENCHMARK.json (README.md).
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
import shutil
import sys
import tempfile
import time

from benchmark import trace_reduce

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(obj: dict) -> None:
    """One JSON line on stdout. Every line but the last is commentary (set-up
    phases, parameters, p50/p99); the last is the contract's result object."""
    print(json.dumps(obj), flush=True)


def load_json(path: str) -> dict:
    with open(os.path.join(ROOT, path) if not os.path.isabs(path) else path) as f:
        return json.load(f)


def process_age_s() -> float:
    """Seconds since the kernel started this process: the interpreter's own
    start-up belongs to set-up too. 0.0 where /proc is not there."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


def resolve_cell(manifest: dict, workload: str) -> tuple[dict, dict, dict]:
    """(cell, configuration file, traffic file) for a workload name."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(configs[cell["config"]]["file"])
    traffic = load_json(os.path.join("benchmark", "traffic", cell["traffic"] + ".json"))
    return cell, config, traffic


def metrics_of(manifest: dict, group: str, workload: str) -> list[dict]:
    """The manifest's metrics of one group that this cell reports: a metric
    without a `workloads` key is every cell's."""
    return [m for m in manifest[group] if workload in m.get("workloads", [workload])]


def with_rehearsal(data: dict, rehearsal: bool) -> dict:
    """A configuration or traffic file may carry a `rehearsal` group: the toy
    values `--rehearsal` lays over the real ones (CPU control flow only)."""
    out = {k: v for k, v in data.items() if k != "rehearsal"}
    if rehearsal:
        out.update(data.get("rehearsal", {}))
    return out


class Phases:
    """Set-up split into named stretches, each ended by the next."""

    def __init__(self, t_harness: float, age_at_harness: float):
        self.times: dict[str, float] = {"process_start_to_harness": age_at_harness}
        self._t = t_harness
        self._t_harness = t_harness
        self._age = age_at_harness

    def done(self, name: str) -> None:
        now = time.perf_counter()
        self.times[name] = self.times.get(name, 0.0) + (now - self._t)
        self._t = now

    def setup_s(self, t_window_start: float) -> float:
        """Process start to the first measured step or request."""
        return self._age + (t_window_start - self._t_harness)


class CompileLog:
    """Every backend compile and persistent-cache hit or miss that JAX
    reports (jax.monitoring), so 'nothing compiles in the window' and 'the
    second run finds every program in the cache' are counts. The method is
    chip_smoke.py's CompileLog, without its ties to the program's tracer."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s: list[float] = []
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_):
        if name == self.COMPILE:
            self.compile_s.append(secs)

    def _on_event(self, name, **_):
        if name == self.HIT:
            self.hits += 1
        elif name == self.MISS:
            self.misses += 1

    def mark(self) -> tuple[int, int, int]:
        return len(self.compile_s), self.hits, self.misses

    def since(self, mark) -> dict:
        n0, h0, m0 = mark
        new = self.compile_s[n0:]
        return {"compiles": len(new), "compile_s": sum(new),
                "cache_hits": self.hits - h0, "cache_misses": self.misses - m0}


class Spans:
    """The benchmark's own host spans around its calls into each layer: the
    duration goes to `durations[name]` on the host's clock, and a
    `jax.profiler.TraceAnnotation` named bench/<name> puts the same stretch
    on the profiler's clock, where trace_reduce labels idle gaps with it."""

    def __init__(self):
        import jax  # not at module level: run.py sets the platform before jax is first imported

        self._annotation = jax.profiler.TraceAnnotation
        self.durations: dict[str, list[float]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with self._annotation("bench/" + name):
            yield
        self.durations.setdefault(name, []).append(time.perf_counter() - t0)

    def reset(self) -> None:
        self.durations.clear()


class TracedStretch:
    """A few seconds of the window under jax.profiler, written below TMPDIR
    and read back through trace_reduce. A trace_reduce.WINDOW_SPAN annotation
    marks the stretch on the profiler's own clock, so busy time is clipped to
    exactly the stretch that idle time is a share of."""

    def __init__(self):
        self.dir: str | None = None
        self.path: str | None = None
        self._annotation = None

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir)
        self._annotation = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._annotation.__enter__()

    def stop(self) -> None:
        import jax

        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        files = sorted(glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True),
                       key=os.path.getmtime)
        self.path = files[-1] if files else None

    def cleanup(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def device_facts(devices, program_temp_bytes: int = 0) -> dict:
    """The contract's `device` object, as JAX reports it. The peak is the
    fullest chip's `memory_stats()["peak_bytes_in_use"]` PLUS the largest
    compiled program's temporaries: on this runtime (libtpu 0.0.34) the
    allocator's statistics count arguments and results only (a 512-image train
    step reads 0.46 GiB), and the 4.85 GB of scratch that the step's
    `memory_analysis()` declares, and the chip really holds while it runs, are
    not in them (PERF.md, Findings). 0 where the backend keeps no statistics."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(max(peaks)) + int(program_temp_bytes)}


def seed_key(seed: int):
    """--seed as key data (hi, lo): any whole number up to 2**64, with no
    overflow where 32 signed bits end. Passed INTO the jitted initialisers, so
    one cached program serves every seed."""
    import numpy as np

    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def init_key(key_data):
    """Inside a jitted initialiser: the seed as an `rbg` key, whose draws are
    the chip's own bit generator. Weights and inputs need no particular
    stream, and threefry doubles the size of an init program (13.9 MB of code
    against 6.7 MB for MobileNetV3-Large's train state, compiled for a v5e)."""
    import jax
    import jax.numpy as jnp

    return jax.random.wrap_key_data(jnp.concatenate([key_data, key_data]), impl="rbg")


def load_app_config(app: str, overrides: dict):
    """The program's own Config for an app file as shipped, with the cell's
    dotted overrides laid over it exactly as `key=value` on the CLI would."""
    from yet_another_mobilenet_series_tpu.config import parse_cli

    # JSON is YAML, which is what parse_cli reads a value as
    return parse_cli([f"app:{os.path.join(ROOT, app)}"]
                     + [f"{k}={json.dumps(v)}" for k, v in overrides.items()])
