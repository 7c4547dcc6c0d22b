"""Device-side telemetry: compile/cost accounting, memory gauges, profiler
capture — the device twin of the host-side obs/ stack (docs/OBSERVABILITY.md
"Device telemetry").

Everything below the dispatch boundary used to be a black box: the serve
engine and the train step compile XLA executables whose FLOPs/bytes the
compiler KNOWS (``cost_analysis()``) but nothing recorded, device memory was
invisible until an OOM, and the only profiler window was the train-only
step-indexed one. Three surfaces, all wired through the existing registry so
they ride every snapshot, ``/metrics``, ``/varz``, ``obs_report`` and the
watchdog hang report for free:

- **compile telemetry** — :func:`timed_compile` wraps every
  ``lower().compile()`` (serve/engine.py ``_build``; cli/train.py records the
  train step via :func:`record_cost` on the already-traced ``Lowered``):
  per-key compile seconds land in the ``obs.compile_seconds`` histogram +
  ``obs.compiles`` counter, and the executable's ``cost_analysis()``
  flops/bytes land in per-key ``obs.cost_flops.<key>`` /
  ``obs.cost_bytes.<key>`` gauges plus the :func:`compile_report` table the
  hang report embeds. The engine feeds dispatched-executable flops into
  ``serve.dispatched_flops`` and the matching cost bytes into
  ``serve.dispatched_bytes`` (the transfer-side twin, via :func:`bytes_for`),
  and :func:`install_dispatch_efficiency_gauge`
  derives ``serve.achieved_flops_per_s`` = dispatched cost FLOPs ÷ measured
  ``serve.run_seconds`` — the "how much of the paper FLOPs did the wall
  clock actually deliver" number ROADMAP item 3's latency work keys on.
- **the compile watch** — :func:`install_compile_watch` is the program's ONE
  ``jax.monitoring`` listener (installed by ``utils/compile_cache.configure()``,
  so in every entry point and in the benchmark): every backend compile and
  persistent-cache hit or miss that JAX reports, whoever compiled — a jitted
  call, an AOT ``lower().compile()``, a helper program — lands in the
  ``jax.backend_compiles`` / ``jax.cache_hits`` / ``jax.cache_misses``
  counters and the ``jax.backend_compile_seconds`` histogram, and in a
  bounded list of events stamped with where the program was (the compiling
  thread's open host spans, ``train.step``, ``serve.requests``); with the span tracer on, each
  is also an instant ``compile/<fun_name>`` in ``obs_trace.json``. "Which step
  recompiled" is a count in ``/metrics`` and a mark in the trace.
  (``obs.compiles`` above counts only what goes through
  :func:`timed_compile`.)
- **memory telemetry** — :func:`install_memory_gauges` registers PULL gauges
  (read only at snapshot time — the existing log cadence — zero extra device
  syncs): per-device ``device.bytes_in_use.d<i>`` / peak / limit from
  ``device.memory_stats()`` (absent on backends that don't report, e.g. CPU),
  ``device.live_buffer_bytes`` from ``jax.live_arrays()``, and
  ``host.rss_bytes`` from ``/proc/self/statm``. Because they are registry
  gauges they are automatically dumped into ``hang_report.json`` and
  ``train_health.json`` (both embed full snapshots).
- **profiler capture** — :class:`ProfilerCapture` is the start/stop pair
  behind the serving frontend's ``POST /profile/start|stop`` endpoints
  (docs/SERVING.md): a lock-guarded ``jax.profiler`` window whose owner
  (cli/serve.py) guarantees ``stop_if_active()`` on every drain path, so an
  operator who never sends the stop request cannot leak a capture past
  shutdown. The train-loop window stays step-indexed in cli/train.py; lint
  rule YAMT013 pins the try/finally discipline for both.

Cost analysis is best-effort by design: backends disagree on the
``cost_analysis()`` return shape (dict vs list-of-dicts) and some refuse it
entirely — a telemetry miss must never take a compile down, so every reader
is wrapped and a miss records nothing.
"""

from __future__ import annotations

import collections
import gc
import os
import threading
import time

from .registry import MetricsRegistry, get_registry
from .trace import get_tracer

# per-key cost table: key -> {"flops", "bytes", "compile_seconds"} — the
# compile_report() section of hang reports and the engine's dispatched-flops
# lookup. Process-lifetime like the registry itself.
_COSTS: dict[str, dict] = {}
_COSTS_LOCK = threading.Lock()


def _extract_cost(raw) -> dict:
    """Normalize a ``cost_analysis()`` result (dict, or list of per-module
    dicts on some backends) to {"flops": float, "bytes": float}; {} when the
    backend reported nothing usable."""
    if raw is None:
        return {}
    if isinstance(raw, (list, tuple)):
        merged: dict[str, float] = {}
        for d in raw:
            if isinstance(d, dict):
                for k, v in d.items():
                    merged[k] = merged.get(k, 0.0) + float(v)
        raw = merged
    if not isinstance(raw, dict):
        return {}
    out = {}
    if "flops" in raw:
        out["flops"] = float(raw["flops"])
    if "bytes accessed" in raw:
        out["bytes"] = float(raw["bytes accessed"])
    return out


def record_cost(key: str, stage, *, compile_seconds: float | None = None,
                registry: MetricsRegistry | None = None) -> dict:
    """Record ``stage.cost_analysis()`` (a ``jax.stages.Lowered`` or
    ``Compiled``) for executable ``key``: per-key ``obs.cost_flops.<key>`` /
    ``obs.cost_bytes.<key>`` gauges + the :func:`compile_report` entry.
    Returns the extracted cost dict ({} when the backend reported nothing) —
    never raises on a cost-analysis miss."""
    reg = registry or get_registry()
    try:
        cost = _extract_cost(stage.cost_analysis())
    except Exception:  # noqa: BLE001 — telemetry must never fail a compile
        cost = {}
    entry = dict(cost)
    if compile_seconds is not None:
        entry["compile_seconds"] = round(float(compile_seconds), 6)
    with _COSTS_LOCK:
        _COSTS[key] = entry
    if "flops" in cost:
        reg.gauge(f"obs.cost_flops.{key}").set(cost["flops"])
    if "bytes" in cost:
        reg.gauge(f"obs.cost_bytes.{key}").set(cost["bytes"])
    return cost


def timed_compile(lowered, key: str, *, registry: MetricsRegistry | None = None):
    """``lowered.compile()`` with the device-compile telemetry attached:
    compile wall time into ``obs.compile_seconds`` (histogram) +
    ``obs.compiles`` (counter), and the executable's cost_analysis
    flops/bytes into the per-key gauges (:func:`record_cost`). This is THE
    wrapper every explicit AOT compile goes through (serve/engine.py)."""
    reg = registry or get_registry()
    t0 = time.perf_counter()
    compiled = lowered.compile()
    dt = time.perf_counter() - t0
    reg.histogram("obs.compile_seconds").observe(dt)
    reg.counter("obs.compiles").inc()
    record_cost(key, compiled, compile_seconds=dt, registry=reg)
    return compiled


def flops_for(key: str) -> float:
    """Recorded cost-analysis FLOPs of executable ``key`` (0.0 when the
    backend reported none) — the engine's per-dispatch accounting lookup."""
    with _COSTS_LOCK:
        return float(_COSTS.get(key, {}).get("flops", 0.0))


def bytes_for(key: str) -> float:
    """Recorded cost-analysis bytes-accessed of executable ``key`` (0.0 when
    the backend reported none) — the transfer-side twin of :func:`flops_for`:
    the engine joins it to every dispatch as ``serve.dispatched_bytes``, the
    number the staging-overlap win is read against (docs/SERVING.md)."""
    with _COSTS_LOCK:
        return float(_COSTS.get(key, {}).get("bytes", 0.0))


def compile_report() -> dict:
    """{key: {flops, bytes, compile_seconds}} for every recorded executable —
    embedded in the watchdog hang report and printable from obs_report."""
    with _COSTS_LOCK:
        return {k: dict(v) for k, v in sorted(_COSTS.items())}


# ---------------------------------------------------------------------------
# the compile watch (jax.monitoring + gc.callbacks: the host's stops, timed
# where they happen; costs nothing until something is traced, compiled or collected)
# ---------------------------------------------------------------------------


class _Here(threading.local):
    """What the watch knows of the thread it was called on (JAX's events fire
    on the thread that does the work). The class attributes are each thread's
    first values."""

    depth = 0  # trace, lowering and compile stretches open on this thread
    t0_ns = 0  # with the tracer on: where the outermost of them started...
    annotation = None  # ...and its TraceAnnotation, open until it ends
    program = None  # the program being made here, until its compile arrives: {fun, trace_s, lower_s}
    cache = "off"  # what the persistent cache answered for it: "hit" | "miss" | "off"
    cache_read_s = 0.0


class CompileWatch:
    """The host's stops, as JAX and the interpreter report them. A program's
    tracing, lowering, persistent-cache read and backend compile
    (``jax.monitoring``) land in the registry and in a bounded list of events,
    one a program, each stamped with where the program was: the host spans
    open on the compiling thread at that moment, the last logged train step
    and the number of serving requests accepted so far. The collector's
    pauses (``gc.callbacks``) land in plain fields that the registry reads
    through pull gauges. Registry and tracer are fetched by call: an entry
    point configures its tracer after the watch is installed. One per process
    (:func:`install_compile_watch`).

    **Outermost traces only.** A jitted function traced inside another's trace
    (jax's own ``jnp`` helpers: thousands in a token step) reports too, and
    its seconds lie inside the outer one's; so do the traces a lowering rule
    makes. JAX marks the START of each stretch with a scalar event
    (``LogElapsedTimeContextManager.__enter__``), which is what makes
    "outermost" decidable when a stretch ends: the watch keeps a depth a
    thread, and a trace that ends at depth 0 is counted. The sum of
    ``jax.trace_seconds`` is therefore wall time, and for a nested trace the
    listeners do a comparison and return. With the tracer on, the same start
    mark opens a ``jax.profiler.TraceAnnotation`` for the outermost stretch
    (``compile/trace:<fun>``, ``compile/lower:<fun>``, ``compile/<fun>``), so
    inside a profiler window a recompile lies over the idle gap it caused.

    **The collector's callback takes no lock and touches no registry
    object.** A collection can start wherever an object is allocated:
    inside ``Histogram.observe`` or ``MetricsRegistry._get`` while their lock
    is held, or in the middle of a span's ``_push``. A callback that asked for
    such a lock would hang the process. Collections never overlap (the
    interpreter runs one at a time), so the fields need no lock of their own."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"
    # the stretches whose start JAX marks, and what each is called where it is drawn
    _DRAWN = {TRACE: "compile/trace:", LOWER: "compile/lower:", COMPILE: "compile/"}
    GC_RING_FLOOR_S = 1e-3  # the ring keeps pauses longer than this

    def __init__(self, keep: int = 4096):
        import jax.monitoring as mon

        # (sequence number, event): the number survives the ring dropping old ones
        self._events: collections.deque = collections.deque(maxlen=keep)
        self._lock = threading.Lock()
        self._seq = 0
        self._here = _Here()
        # the collector, by generation; written by _on_gc alone
        self.gc_seconds = [0.0, 0.0, 0.0]
        self.gc_collections = [0, 0, 0]
        self.gc_max_pause = [0.0, 0.0, 0.0]
        self.gc_callback_errors = 0
        # (t, generation, seconds, collected) of the pauses over GC_RING_FLOOR_S;
        # t is time.perf_counter() where the collection started
        self.gc_pauses: collections.deque = collections.deque(maxlen=1024)
        self._gc_t0_ns = 0
        self._gc_annotation = None
        mon.register_scalar_listener(self._on_start)
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    # -- jax.monitoring -------------------------------------------------------

    def _on_start(self, name, value, fun_name="", **_):
        if name not in self._DRAWN:
            return
        here = self._here
        here.depth += 1
        if here.depth == 1:
            tracer = get_tracer()
            if tracer.enabled:
                # the outermost stretch is drawn, on the profiler's clock too
                here.t0_ns = time.perf_counter_ns()
                here.annotation = tracer.annotation(self._DRAWN[name] + fun_name)

    def _ended(self, here: _Here, name: str, secs: float, fun_name: str) -> None:
        """An outermost stretch that :meth:`_on_start` drew ends: its
        annotation closes, and the trace and the lowering become complete
        events in ``obs_trace.json`` (the compile is the instant that is there)."""
        annotation, here.annotation = here.annotation, None
        if annotation is not None:
            annotation.__exit__(None, None, None)
        t0_ns, here.t0_ns = here.t0_ns, 0
        if t0_ns and name != self.COMPILE:
            get_tracer().complete(self._DRAWN[name] + fun_name, "compile", t0_ns, int(secs * 1e9))

    def _on_duration(self, name, secs, fun_name="", **_):
        here = self._here
        if name == self.CACHE_READ:
            # fires on the compiling thread just before that program's COMPILE
            get_registry().histogram("jax.cache_read_seconds").observe(secs)
            here.cache_read_s = secs
            return
        if name not in self._DRAWN:
            return
        here.depth = max(here.depth - 1, 0)
        if not here.depth:
            self._ended(here, name, secs, fun_name)
        elif name == self.TRACE:
            return  # inside another trace or a lowering: its seconds are in that one's
        if name == self.TRACE:
            reg = get_registry()
            reg.counter("jax.traces").inc()
            reg.histogram("jax.trace_seconds").observe(secs)
            here.program = {"fun": fun_name, "trace_s": secs, "lower_s": 0.0}
        elif name == self.LOWER:
            get_registry().histogram("jax.lower_seconds").observe(secs)
            program = here.program
            # the trace says `f`, the lowering and the compile `jit(f)`; a trace
            # that JAX answered from memory reports nothing, and then the
            # program starts here
            if program is None or program["lower_s"] or program["fun"] not in fun_name:
                program = here.program = {"trace_s": 0.0}
            program.update(fun=fun_name, lower_s=secs)
        else:
            self._compiled(here, secs, fun_name)

    def _compiled(self, here: _Here, secs: float, fun_name: str) -> None:
        reg, tracer = get_registry(), get_tracer()
        reg.counter("jax.backend_compiles").inc()
        reg.histogram("jax.backend_compile_seconds").observe(secs)
        program, here.program = here.program, None
        if program is None or program["fun"] not in fun_name:
            program = {"trace_s": 0.0, "lower_s": 0.0}
        event = {
            "fun": fun_name,
            "trace_s": round(program["trace_s"], 6), "lower_s": round(program["lower_s"], 6),
            # contains the cache's read on a hit
            "compile_s": round(secs, 6), "cache": here.cache, "cache_read_s": round(here.cache_read_s, 6),
            "t": time.perf_counter(),
            # a jitted call compiles on the thread that made it, so this
            # thread's open spans are where the compile happened; another
            # thread's (a prefetch worker mid-fill) are not
            "open": [sp["name"] for sp in tracer.open_spans() if sp["tid"] == threading.get_ident()],
            "train_step": reg.gauge("train.step").value,
            "serve_requests": reg.counter("serve.requests").value,
        }
        here.cache, here.cache_read_s = "off", 0.0
        with self._lock:
            self._seq += 1
            self._events.append((self._seq, event))
        tracer.instant(f"compile/{fun_name}", "compile", **event)

    def _on_event(self, name, **_):
        if name == self.HIT:
            get_registry().counter("jax.cache_hits").inc()
            self._here.cache = "hit"
        elif name == self.MISS:
            get_registry().counter("jax.cache_misses").inc()
            self._here.cache = "miss"

    # -- gc.callbacks ---------------------------------------------------------

    def _on_gc(self, phase, info):
        """NO lock, NO registry object (class docstring), and it never raises."""
        try:
            if phase == "start":
                self._gc_t0_ns = time.perf_counter_ns()
                if info["generation"] == 2:
                    # a full collection is drawn, on the profiler's clock too;
                    # the younger ones (tens a second) are counted
                    self._gc_annotation = get_tracer().annotation("gc/full")
            elif self._gc_t0_ns:
                t0_ns, self._gc_t0_ns = self._gc_t0_ns, 0
                dur_ns = time.perf_counter_ns() - t0_ns
                annotation, self._gc_annotation = self._gc_annotation, None
                gen, secs = info["generation"], dur_ns / 1e9
                if annotation is not None:
                    annotation.__exit__(None, None, None)
                if gen == 2:
                    get_tracer().complete("gc/full", "gc", t0_ns, dur_ns, {"collected": info["collected"]})
                self.gc_seconds[gen] += secs
                self.gc_collections[gen] += 1
                if secs > self.gc_max_pause[gen]:
                    self.gc_max_pause[gen] = secs
                if secs > self.GC_RING_FLOOR_S:
                    self.gc_pauses.append((t0_ns / 1e9, gen, secs, info["collected"]))
        except Exception:  # noqa: BLE001 — telemetry inside the collector must never raise
            self.gc_callback_errors += 1

    def gc_pause_seconds(self) -> float:
        return float(sum(self.gc_seconds))

    def gc_max_pause_between(self, t0: float, t1: float) -> float:
        """The longest pause that started in [t0, t1] (``time.perf_counter()``
        seconds); 0.0 where none passed the ring's floor of a millisecond."""
        return max((secs for t, _, secs, _ in list(self.gc_pauses) if t0 <= t <= t1), default=0.0)

    # -- readout --------------------------------------------------------------

    def mark(self) -> tuple:
        reg = get_registry()
        with self._lock:
            seq = self._seq
        return (seq, reg.counter("jax.cache_hits").value, reg.counter("jax.cache_misses").value,
                self.gc_pause_seconds())

    def since(self, mark) -> dict:
        """What was traced, lowered and compiled, what the cache answered and
        what the collector took since :meth:`mark`: {compiles, trace_s,
        lower_s, compile_s, cache_read_s, cache_hits, cache_misses, gc_s,
        events}; an event is one program."""
        seq0, h0, m0, gc0 = mark
        seq, hits, misses, gc1 = self.mark()
        with self._lock:
            new = [e for n, e in self._events if n > seq0]
        total = {k: round(sum(e[k] for e in new), 2) for k in ("trace_s", "lower_s", "compile_s", "cache_read_s")}
        return {"compiles": seq - seq0, **total, "cache_hits": int(hits - h0), "cache_misses": int(misses - m0),
                "gc_s": round(gc1 - gc0, 3), "events": new}


_COMPILE_WATCH: CompileWatch | None = None
_COMPILE_WATCH_LOCK = threading.Lock()


def install_compile_watch() -> CompileWatch:
    """The process's compile watch, installed on first call (idempotent:
    ``utils/compile_cache.configure()`` calls this from every entry point):
    its three ``jax.monitoring`` listeners and its one ``gc.callbacks`` entry
    go in once; the collector's pull gauges are set on every call, so a
    registry that was reset gets them again."""
    global _COMPILE_WATCH
    with _COMPILE_WATCH_LOCK:
        if _COMPILE_WATCH is None:
            _COMPILE_WATCH = CompileWatch()
            gc.callbacks.append(_COMPILE_WATCH._on_gc)
        watch = _COMPILE_WATCH
    reg = get_registry()
    # there from the start, so that "nothing was read from the cache" is a 0 and not a gap
    reg.counter("jax.traces")
    for name in ("jax.trace_seconds", "jax.lower_seconds", "jax.cache_read_seconds"):
        reg.histogram(name)
    reg.gauge("host.gc_pause_seconds").set_fn(watch.gc_pause_seconds)
    reg.gauge("host.gc_collections").set_fn(lambda: sum(watch.gc_collections))
    reg.gauge("host.gc_full_collections").set_fn(lambda: watch.gc_collections[2])
    reg.gauge("host.gc_max_pause_seconds").set_fn(lambda: max(watch.gc_max_pause))
    return watch


# ---------------------------------------------------------------------------
# memory gauges (pull-based: zero cost until a snapshot reads them)
# ---------------------------------------------------------------------------

_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def _rss_bytes() -> float:
    with open("/proc/self/statm") as f:
        return float(int(f.read().split()[1]) * _PAGE_SIZE)


def _live_buffer_bytes() -> float:
    import jax

    return float(sum(getattr(a, "nbytes", 0) for a in jax.live_arrays()))


_MEM_INSTALLED = False
_MEM_LOCK = threading.Lock()


def install_memory_gauges(registry: MetricsRegistry | None = None) -> None:
    """Register the device/host memory PULL gauges (idempotent; both CLIs
    call this at startup). Each gauge's callback runs only when a snapshot is
    taken — the existing log cadence — and ``memory_stats()`` / ``statm``
    reads are host-side, so telemetry adds no device syncs. Backends without
    ``memory_stats()`` support (CPU) simply skip the per-device HBM gauges;
    RSS and live-buffer accounting still land."""
    global _MEM_INSTALLED
    with _MEM_LOCK:
        if _MEM_INSTALLED:
            return
        _MEM_INSTALLED = True
    import jax

    reg = registry or get_registry()
    reg.gauge("host.rss_bytes").set_fn(_rss_bytes)
    reg.gauge("device.live_buffer_bytes").set_fn(_live_buffer_bytes)
    for i, dev in enumerate(jax.devices()):
        try:
            stats = dev.memory_stats()
        except Exception:  # noqa: BLE001 — a backend without stats is not an error
            stats = None
        if not stats:
            continue

        def make_reader(d, field):
            return lambda: float((d.memory_stats() or {}).get(field, 0))

        for field, name in (
            ("bytes_in_use", "bytes_in_use"),
            ("peak_bytes_in_use", "peak_bytes_in_use"),
            ("bytes_limit", "bytes_limit"),
        ):
            if field in stats:
                reg.gauge(f"device.{name}.d{i}").set_fn(make_reader(dev, field))


def install_dispatch_efficiency_gauge(registry: MetricsRegistry | None = None) -> None:
    """``serve.achieved_flops_per_s`` pull gauge: cumulative cost-analysis
    FLOPs the engine dispatched (``serve.dispatched_flops``) divided by the
    cumulative measured wall time those requests took
    (``serve.run_seconds.sum``). Idempotent — the engine installs it once."""
    reg = registry or get_registry()
    flops = reg.counter("serve.dispatched_flops")
    run = reg.histogram("serve.run_seconds")

    def achieved() -> float:
        return flops.value / run.total if run.total > 0 else 0.0

    reg.gauge("serve.achieved_flops_per_s").set_fn(achieved)


# ---------------------------------------------------------------------------
# build info (the /metrics build_info family)
# ---------------------------------------------------------------------------


def _git_sha(repo_dir: str | None = None) -> str:
    """HEAD sha read straight from .git (no subprocess: serving startup must
    not fork a shell); "" when not a checkout."""
    d = repo_dir or os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    try:
        git = os.path.join(d, ".git")
        with open(os.path.join(git, "HEAD")) as f:
            head = f.read().strip()
        if not head.startswith("ref:"):
            return head[:40]
        ref = head.split(None, 1)[1]
        ref_path = os.path.join(git, *ref.split("/"))
        if os.path.exists(ref_path):
            with open(ref_path) as f:
                return f.read().strip()[:40]
        with open(os.path.join(git, "packed-refs")) as f:
            for line in f:
                parts = line.strip().split()
                if len(parts) == 2 and parts[1] == ref:
                    return parts[0][:40]
    except OSError:
        pass
    return ""


def build_info(*, device: bool = True) -> dict:
    """Version-attribution labels for the ``build_info`` metric family: git
    sha, jax/jaxlib versions, backend platform. A scraped fleet can group
    replicas by exactly what they run.

    Versions come from package metadata, not ``import jax``. The platform
    label is the one field that needs a live backend, and reading it
    INITIALISES that backend — on a TPU host the calling process then owns
    the chip. ``device=False`` is for supervisors (cli/fleet.py) whose
    children need that chip: they report versions and sha only."""
    from importlib import metadata

    info = {
        "git_sha": _git_sha() or "unknown",
        "jax_version": metadata.version("jax"),
        "jaxlib_version": metadata.version("jaxlib"),
    }
    if device:
        import jax

        info["platform"] = jax.default_backend()
    return info


# ---------------------------------------------------------------------------
# profiler capture (the serving frontend's /profile endpoints)
# ---------------------------------------------------------------------------


class ProfilerCapture:
    """Config/HTTP-triggered ``jax.profiler`` window for the SERVING path —
    the train-only step-indexed window generalized (docs/SERVING.md
    "Profiler capture"). ``start``/``stop`` arrive as separate requests, so a
    function-local try/finally cannot guard the pair; instead the capture is
    lock-guarded single-flight and its OWNER (cli/serve.py's drain path)
    calls :meth:`stop_if_active` on every shutdown, bounding a leaked window
    at process drain. The xplane dump lands under ``dir`` for
    scripts/trace_ops.py aggregation."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self._lock = threading.Lock()
        self._active_since: float | None = None

    @property
    def active(self) -> bool:
        return self._active_since is not None

    def start(self) -> dict:
        """Begin a capture; raises RuntimeError when one is already open."""
        import jax

        with self._lock:
            if self._active_since is not None:
                raise RuntimeError(
                    f"profiler capture already active for "
                    f"{time.perf_counter() - self._active_since:.1f}s"
                )
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)  # yamt-lint: disable=YAMT013 — stop arrives via /profile/stop; stop_if_active() guards every drain path
            self._active_since = time.perf_counter()
            get_registry().counter("obs.profiler_captures").inc()
        return {"trace_dir": self.trace_dir}

    def stop(self) -> dict:
        """End the capture; raises RuntimeError when none is open."""
        import jax

        with self._lock:
            if self._active_since is None:
                raise RuntimeError("no profiler capture active")
            t0 = self._active_since
            self._active_since = None
            jax.profiler.stop_trace()
        return {"trace_dir": self.trace_dir,
                "captured_s": round(time.perf_counter() - t0, 3)}

    def stop_if_active(self) -> None:
        """Drain-path guard: close a still-open window without raising —
        the shutdown equivalent of the train loop's finally."""
        try:
            self.stop()
        except RuntimeError:
            pass
        except Exception:  # noqa: BLE001 — a torn capture must not block drain
            get_registry().counter("obs.profiler_stop_errors").inc()
