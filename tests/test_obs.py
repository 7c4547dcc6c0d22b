"""obs/ subsystem tests: registry semantics, span tracer (nesting, ring
eviction, Chrome-trace schema), stall watchdog (fires on an injected stall,
silent on a healthy loop), Logger integration (TF-less degrade, registry
snapshots in scalars rows), the fake-data train smoke (trace + snapshot
artifacts), and scripts/obs_report.py."""

import importlib.util
import json
import os
import time

import pytest

from yet_another_mobilenet_series_tpu.cli import train as cli_train
from yet_another_mobilenet_series_tpu.config import config_from_dict
from yet_another_mobilenet_series_tpu.obs.registry import MetricsRegistry, get_registry
from yet_another_mobilenet_series_tpu.obs.trace import SpanTracer
from yet_another_mobilenet_series_tpu.obs import trace as obs_trace
from yet_another_mobilenet_series_tpu.obs.watchdog import StallWatchdog
from yet_another_mobilenet_series_tpu.utils import logging as logging_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("a.hits").inc()
    reg.counter("a.hits").inc(2)
    reg.gauge("a.level").set(7.5)
    h = reg.histogram("a.wait")
    h.observe(1.0)
    h.observe(3.0)
    snap = reg.snapshot()
    assert snap["a.hits"] == 3.0
    assert snap["a.level"] == 7.5
    assert snap["a.wait.count"] == 2.0
    assert snap["a.wait.sum"] == 4.0
    assert snap["a.wait.mean"] == 2.0
    assert snap["a.wait.max"] == 3.0
    # get-or-create returns the SAME metric object
    assert reg.counter("a.hits") is reg.counter("a.hits")


def test_registry_type_conflict_and_negative_inc():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")
    with pytest.raises(ValueError, match="cannot decrease"):
        reg.counter("x").inc(-1)


def test_registry_gauge_callback_and_fault_isolation():
    reg = MetricsRegistry()
    src = {"v": 5}
    g = reg.gauge("pull")
    g.set_fn(lambda: src["v"])
    assert reg.snapshot()["pull"] == 5.0
    src["v"] = 9
    assert reg.snapshot()["pull"] == 9.0
    # a dying producer keeps the last good reading, never raises
    g.set_fn(lambda: 1 / 0)
    assert reg.snapshot()["pull"] == 9.0


def test_registry_reset():
    reg = MetricsRegistry()
    reg.counter("x").inc()
    reg.reset()
    assert reg.snapshot() == {}


# ---------------------------------------------------------------------------
# bucketed histograms: quantiles, min, concurrency, Prometheus exposition
# ---------------------------------------------------------------------------


def test_histogram_min_reported():
    """The satellite fix: vmin was tracked under the lock but never
    reported — it must reach summary(), snapshot(), and stay correct."""
    reg = MetricsRegistry()
    h = reg.histogram("t.wait")
    for v in (0.2, 0.005, 0.07):
        h.observe(v)
    s = h.summary()
    assert s["min"] == 0.005 and s["max"] == 0.2
    snap = reg.snapshot()
    assert snap["t.wait.min"] == 0.005
    # empty histogram reports zeros, never inf
    assert reg.histogram("t.empty").summary()["min"] == 0.0


def test_histogram_bucketed_quantiles_vs_sorted_reference():
    """Bucketed p50/p95/p99 must land within one bucket width of the exact
    sorted-sample quantile (the estimator interpolates inside the bucket
    that crosses the target rank)."""
    import numpy as np

    rng = np.random.RandomState(7)
    samples = np.exp(rng.uniform(np.log(2e-4), np.log(20.0), 4000))
    h = MetricsRegistry().histogram("t.lat")
    for v in samples:
        h.observe(float(v))
    for q in (0.5, 0.95, 0.99):
        ref = float(np.quantile(samples, q))
        est = h.quantile(q)
        # one bucket on the default quarter-decade ladder is a 10**0.25
        # (~1.78x) span: the estimate must stay inside the ref's bucket
        assert ref / (10 ** 0.25) <= est <= ref * (10 ** 0.25), (q, ref, est)
    # quantiles are monotone and clamped to the observed range
    s = h.summary()
    assert s["min"] <= s["p50"] <= s["p95"] <= s["p99"] <= s["max"]


def test_histogram_concurrent_observe_consistent():
    import threading

    h = MetricsRegistry().histogram("t.conc")
    n_threads, per_thread = 8, 500

    def worker(i):
        for j in range(per_thread):
            h.observe(1e-3 * (1 + (i * per_thread + j) % 97))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == n_threads * per_thread
    assert sum(h.bucket_counts()) == h.count  # no lost bucket increments
    assert h.summary()["p50"] > 0


def test_histogram_custom_default_buckets():
    """set_default_buckets (the obs.histogram_buckets config knob) applies
    to histograms created AFTER the call; existing ladders are untouched."""
    reg = MetricsRegistry()
    before = reg.histogram("a")
    reg.set_default_buckets([0.1, 1.0, 10.0])
    after = reg.histogram("b")
    assert after.bounds == (0.1, 1.0, 10.0)
    assert before.bounds != after.bounds
    assert reg.histogram("a") is before  # get-or-create keeps the old ladder


def test_quantiles_from_counts_empty_and_zero_observations():
    """Edge cases the serving bench's delta math can hit: an all-zero count
    window (no observations between snapshots) and an empty-histogram
    summary must yield zeros, never a divide-by-zero or an inf clamp."""
    from yet_another_mobilenet_series_tpu.obs.registry import (
        DEFAULT_BUCKET_BOUNDS, quantiles_from_counts)

    counts = [0] * (len(DEFAULT_BUCKET_BOUNDS) + 1)
    assert quantiles_from_counts(DEFAULT_BUCKET_BOUNDS, counts, (0.5, 0.95, 0.99)) == [0.0, 0.0, 0.0]
    # vmin/vmax still at their empty sentinels (inf/-inf) must not leak out
    assert quantiles_from_counts(
        DEFAULT_BUCKET_BOUNDS, counts, (0.5,), vmin=float("inf"), vmax=float("-inf")) == [0.0]
    h = MetricsRegistry().histogram("t.never_observed")
    s = h.summary()
    assert s == {"count": 0.0, "sum": 0.0, "mean": 0.0, "min": 0.0, "max": 0.0,
                 "p50": 0.0, "p95": 0.0, "p99": 0.0}
    assert h.quantile(0.99) == 0.0


def test_render_prometheus_empty_histogram():
    """A histogram with no samples still renders a complete, finite family:
    zero cumulative buckets, zero sum/count, zero quantiles — a scraper must
    never see NaN/inf from a warmed-but-idle latency metric."""
    reg = MetricsRegistry()
    reg.histogram("serve.latency_seconds.batch", bounds=[0.01, 0.1])
    golden = "\n".join([
        '# TYPE serve_latency_seconds histogram',
        'serve_latency_seconds_bucket{class="batch",le="0.01"} 0',
        'serve_latency_seconds_bucket{class="batch",le="0.1"} 0',
        'serve_latency_seconds_bucket{class="batch",le="+Inf"} 0',
        'serve_latency_seconds_sum{class="batch"} 0',
        'serve_latency_seconds_count{class="batch"} 0',
        'serve_latency_seconds{class="batch",quantile="0.5"} 0',
        'serve_latency_seconds{class="batch",quantile="0.95"} 0',
        'serve_latency_seconds{class="batch",quantile="0.99"} 0',
    ]) + "\n"
    assert reg.render_prometheus() == golden
    for v in reg.snapshot().values():
        assert v == v and abs(v) != float("inf")  # finite, not NaN


def test_render_prometheus_golden():
    """Exposition golden: counter/gauge samples, a labeled per-class
    histogram with cumulative buckets + quantile lines, TYPE lines once per
    family — the exact text GET /metrics serves."""
    reg = MetricsRegistry()
    reg.counter("serve.requests").inc(5)
    reg.counter("serve.requests.interactive").inc(3)
    reg.gauge("serve.inflight").set(2)
    h = reg.histogram("serve.latency_seconds.interactive", bounds=[0.01, 0.1, 1.0])
    for v in (0.005, 0.05, 0.5):
        h.observe(v)
    golden = "\n".join([
        '# TYPE serve_inflight gauge',
        'serve_inflight 2',
        '# TYPE serve_latency_seconds histogram',
        'serve_latency_seconds_bucket{class="interactive",le="0.01"} 1',
        'serve_latency_seconds_bucket{class="interactive",le="0.1"} 2',
        'serve_latency_seconds_bucket{class="interactive",le="1"} 3',
        'serve_latency_seconds_bucket{class="interactive",le="+Inf"} 3',
        'serve_latency_seconds_sum{class="interactive"} 0.555',
        'serve_latency_seconds_count{class="interactive"} 3',
        'serve_latency_seconds{class="interactive",quantile="0.5"} 0.055',
        'serve_latency_seconds{class="interactive",quantile="0.95"} 0.44',
        'serve_latency_seconds{class="interactive",quantile="0.99"} 0.488',
        '# TYPE serve_requests counter',
        'serve_requests 5',
        'serve_requests{class="interactive"} 3',
    ]) + "\n"
    assert reg.render_prometheus() == golden


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def _x_events(tracer):
    return [e for e in tracer.to_chrome_trace()["traceEvents"] if e["ph"] == "X"]


def test_tracer_span_nesting_and_containment():
    tr = SpanTracer(ring_size=16)
    with tr.span("outer", "dispatch", steps=2):
        with tr.span("inner", "sync"):
            time.sleep(0.001)
    evts = _x_events(tr)
    # completion order: inner closes first
    assert [e["name"] for e in evts] == ["inner", "outer"]
    inner, outer = evts
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["args"] == {"steps": 2}


def test_tracer_ring_eviction():
    tr = SpanTracer(ring_size=4)
    for i in range(10):
        with tr.span(f"s{i}", "data"):
            pass
    evts = _x_events(tr)
    assert [e["name"] for e in evts] == ["s6", "s7", "s8", "s9"]


def test_tracer_chrome_trace_schema(tmp_path):
    tr = SpanTracer(ring_size=8)
    with tr.span("a", "data"):
        pass
    path = tr.write(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    assert isinstance(doc["traceEvents"], list)
    for e in doc["traceEvents"]:
        assert e["ph"] in ("X", "M")
        assert {"name", "ph", "pid", "tid", "ts"} <= set(e)
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
            assert isinstance(e["cat"], str)


def test_tracer_disabled_is_noop():
    tr = SpanTracer(ring_size=8, enabled=False)
    s1 = tr.span("a", "data")
    s2 = tr.span("b", "sync")
    assert s1 is s2  # the shared null span: zero allocation on the hot path
    with s1:
        pass
    assert _x_events(tr) == []


def test_tracer_open_spans_readout():
    tr = SpanTracer(ring_size=8)
    with tr.span("outer", "dispatch"):
        with tr.span("inner", "data"):
            open_now = tr.open_spans()
            assert [s["name"] for s in open_now] == ["outer", "inner"]
            assert all(s["open_for_s"] >= 0 for s in open_now)
    assert tr.open_spans() == []


def test_tracer_misnested_exit_recovered_and_counted():
    """The satellite fix: an out-of-order exit must remove the span by
    identity (not leave it stuck in _open polluting every later hang
    report) and count obs.misnested_spans."""
    reg = get_registry()
    base = reg.snapshot().get("obs.misnested_spans", 0)
    tr = SpanTracer(ring_size=16)
    outer = tr.span("outer", "serve")
    inner = tr.span("inner", "serve")
    outer.__enter__()
    inner.__enter__()
    outer.__exit__(None, None, None)  # parent closed before child: misnested
    assert reg.snapshot()["obs.misnested_spans"] == base + 1
    # the child is still tracked (it was not the misnested one)...
    assert [s["name"] for s in tr.open_spans()] == ["inner"]
    inner.__exit__(None, None, None)
    # ...and a clean close leaves nothing behind: no phantom open spans
    assert tr.open_spans() == []
    assert [e["name"] for e in _x_events(tr)] == ["outer", "inner"]
    assert reg.snapshot()["obs.misnested_spans"] == base + 1  # clean pop uncounted


def test_tracer_async_flow_events_and_thread_names():
    """Async (b/e) + flow (s/t/f) events carry the correlation id; registered
    worker threads get Perfetto thread_name metadata rows."""
    import threading

    tr = SpanTracer(ring_size=64)
    tr.async_begin("serve/request", 42, cls="interactive")
    tr.flow_start("serve/req", 42)

    def worker():
        tr.register_thread("serve-worker-x")
        tr.flow_step("serve/req", 42)
        tr.flow_end("serve/req", 42, outcome="completed")
        tr.async_end("serve/request", 42)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    doc = tr.to_chrome_trace()
    evts = doc["traceEvents"]
    corr = [e for e in evts if e.get("id") == 42]
    assert [e["ph"] for e in corr] == ["b", "s", "t", "f", "e"]
    assert len({e["tid"] for e in corr}) == 2  # two threads, one id
    flow_end = next(e for e in corr if e["ph"] == "f")
    assert flow_end["bp"] == "e" and flow_end["args"]["outcome"] == "completed"
    names = {e["args"]["name"] for e in evts if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "serve-worker-x" in names
    # disabled tracer: marks are no-ops
    off = SpanTracer(ring_size=4, enabled=False)
    off.async_begin("x", 1)
    off.register_thread("nope")
    assert [e for e in off.to_chrome_trace()["traceEvents"] if e.get("id")] == []


def test_tracer_module_singleton_configure():
    prev = obs_trace.get_tracer()
    try:
        tr = obs_trace.configure(enabled=True, ring_size=4)
        assert obs_trace.get_tracer() is tr
        with obs_trace.get_tracer().span("x", "data"):
            pass
        assert [e["name"] for e in _x_events(tr)] == ["x"]
    finally:
        obs_trace._TRACER = prev


def test_tracer_spans_are_profiler_annotations_on_their_own_threads_line(tmp_path):
    """Under a real jax.profiler trace (CPU), an enabled tracer's spans are
    in the profiler's own xplane file, on /host:CPU, named <cat>/<name>
    exactly, each on the line of the thread that ran it — the same file and
    clock a device's XLA Ops are on (scripts/trace_ops.py labels idle gaps
    with them)."""
    import glob
    import threading

    import jax
    from jax.profiler import ProfileData

    tr = SpanTracer(ring_size=16)
    assert tr._annotate is jax.profiler.TraceAnnotation

    def worker():
        with tr.span("serve/stage", "serve"):
            time.sleep(0.002)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("dispatch/train_step", "dispatch", steps=1):
            time.sleep(0.002)
            t = threading.Thread(target=worker)
            t.start()
            t.join()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = next(p for p in ProfileData.from_file(path).planes if p.name == "/host:CPU")
    where = {}
    for i, line in enumerate(host.lines):  # one line a thread (their names may both be the process's)
        for ev in line.events:
            if ev.name in ("dispatch/train_step", "serve/stage"):
                where[ev.name] = (i, ev.start_ns, ev.duration_ns)
    assert set(where) == {"dispatch/train_step", "serve/stage"}
    assert where["dispatch/train_step"][0] != where["serve/stage"][0]
    outer, inner = where["dispatch/train_step"], where["serve/stage"]
    assert inner[2] >= 2e6 and outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]
    # and the tracer's own record is what it was
    assert sorted(e["name"] for e in _x_events(tr)) == ["dispatch/train_step", "serve/stage"]


def test_tracer_disabled_creates_no_annotation(monkeypatch):
    """Off costs what it cost: span() hands back the shared null span itself,
    and no TraceAnnotation is ever constructed."""
    import jax

    made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", lambda name: made.append(name))
    off = SpanTracer(ring_size=4, enabled=False)
    assert off._annotate is None
    assert off.span("serve/stage", "serve") is obs_trace._NULL_SPAN
    with off.span("data/next", "data"):
        pass
    off.instant("compile/f", "compile")
    assert made == [] and off.to_chrome_trace()["traceEvents"][1:] == []


def test_compile_watch_counts_compiles_and_names_the_open_span():
    """The program's one jax.monitoring listener (obs/device.py, installed by
    utils/compile_cache.configure): a compile is a count, seconds in a
    histogram, an event stamped with the compiling thread's open spans, and
    an instant compile/<fun_name> in the trace; cache hits and misses count."""
    import threading

    import jax
    import jax.monitoring
    import jax.numpy as jnp

    from yet_another_mobilenet_series_tpu.obs.device import CompileWatch, install_compile_watch
    from yet_another_mobilenet_series_tpu.utils import compile_cache

    compile_cache.configure()  # every entry point's first call installs it
    watch = install_compile_watch()
    assert install_compile_watch() is watch  # one per process
    reg = get_registry()
    prev = obs_trace.get_tracer()
    tr = obs_trace.configure(enabled=True, ring_size=64)
    try:
        mark = watch.mark()
        before = reg.snapshot()
        elsewhere = threading.Event()

        def other_thread():  # a span open on ANOTHER thread is not where the compile happened
            with tr.span("data/prefetch_fill", "data"):
                elsewhere.wait(10)

        t = threading.Thread(target=other_thread)
        t.start()
        try:
            def only_compiled_here(x):
                return jnp.tanh(x) * 3.25 + 0.125

            with tr.span("dispatch/train_step", "dispatch"):
                jax.block_until_ready(jax.jit(only_compiled_here)(jnp.ones((3,))))
        finally:
            elsewhere.set()
            t.join()
        # the persistent cache is off on the CPU: its events, as JAX would record them
        jax.monitoring.record_event(CompileWatch.MISS)
        jax.monitoring.record_event(CompileWatch.HIT)
        got = watch.since(mark)
        after = reg.snapshot()
    finally:
        obs_trace._TRACER = prev
    mine = [e for e in got["events"] if "only_compiled_here" in e["fun"]]
    assert len(mine) == 1 and got["compiles"] >= 1
    assert mine[0]["open"] == ["dispatch/train_step"] and mine[0]["compile_s"] >= 0
    assert mine[0]["cache"] == "off" and mine[0]["trace_s"] > 0 and mine[0]["lower_s"] > 0  # the CPU has no cache
    assert {"train_step", "serve_requests"} <= set(mine[0])
    assert (got["cache_hits"], got["cache_misses"]) == (1, 1)
    assert after["jax.backend_compiles"] - before.get("jax.backend_compiles", 0) == got["compiles"]
    assert after["jax.cache_misses"] - before.get("jax.cache_misses", 0) == 1
    assert after["jax.cache_hits"] - before.get("jax.cache_hits", 0) == 1
    assert after["jax.backend_compile_seconds.count"] - before.get("jax.backend_compile_seconds.count", 0) \
        == got["compiles"]
    marks = [e for e in tr.to_chrome_trace()["traceEvents"] if e["name"] == "compile/jit(only_compiled_here)"]
    assert len(marks) == 1 and marks[0]["ph"] == "i" and marks[0]["cat"] == "compile"
    assert marks[0]["args"]["open"] == ["dispatch/train_step"]


@pytest.fixture
def watch():
    """The process's compile watch with an enabled tracer of the test's own,
    put back afterwards: (watch, tracer)."""
    from yet_another_mobilenet_series_tpu.obs.device import install_compile_watch
    from yet_another_mobilenet_series_tpu.utils import compile_cache

    compile_cache.configure()
    prev = obs_trace.get_tracer()
    tr = obs_trace.configure(enabled=True, ring_size=256)
    yield install_compile_watch(), tr
    obs_trace._TRACER = prev


def test_compile_watch_counts_outermost_traces_and_their_seconds_once(watch):
    """A jitted function traced inside another's trace reports too (jax's own
    jnp helpers among them), and so do the traces a lowering rule makes: ONE
    trace is counted for the program, and its seconds once, so that the
    histogram's sum is wall time."""
    import jax
    import jax.monitoring
    import jax.numpy as jnp

    watch, tr = watch
    reported = []
    listener = lambda name, secs, **kw: reported.append(kw.get("fun_name")) if name == watch.TRACE else None  # noqa: E731
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        @jax.jit
        def nested_inside(x):
            return jnp.tanh(x) * 1.75

        def traced_once_outermost(x):
            return nested_inside(x) + jnp.where(x > 0, x, 0.5) + jnp.cumsum(x)  # cumsum: its lowering traces again

        before = get_registry().snapshot()
        mark = watch.mark()
        t0 = time.perf_counter()
        jax.block_until_ready(jax.jit(traced_once_outermost)(jnp.ones((4,))))
        wall = time.perf_counter() - t0
        got, after = watch.since(mark), get_registry().snapshot()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    mine = [n for n in reported if n in ("nested_inside", "traced_once_outermost")]
    assert sorted(mine) == ["nested_inside", "traced_once_outermost"] and len(reported) > 4  # JAX reported the inner ones
    # ...and the watch counted the programs, not the helpers: eager jnp calls made for the
    # arguments are programs of their own, each with ONE trace
    assert after["jax.traces"] - before["jax.traces"] == got["compiles"] < len(reported)
    (event,) = [e for e in got["events"] if "traced_once_outermost" in e["fun"]]
    seconds = after["jax.trace_seconds.sum"] - before["jax.trace_seconds.sum"]
    assert 0 < event["trace_s"] <= seconds <= wall  # once: the nested traces' seconds are inside it
    assert seconds + after["jax.lower_seconds.sum"] - before["jax.lower_seconds.sum"] <= wall
    drawn = [e for e in tr.to_chrome_trace()["traceEvents"] if e["name"].startswith("compile/trace:")]
    assert "compile/trace:traced_once_outermost" in [e["name"] for e in drawn]
    assert not [e for e in drawn if "nested_inside" in e["name"]] and all(e["ph"] == "X" for e in drawn)
    assert [e for e in tr.to_chrome_trace()["traceEvents"] if e["name"] == "compile/lower:jit(traced_once_outermost)"]


def test_compile_watch_draws_the_outermost_stretches_as_profiler_annotations(watch):
    """With the tracer on, JAX's start mark opens a TraceAnnotation for the
    outermost trace, lowering and compile, closed where the stretch ends: one
    each a program, none for what is nested inside; none at all with it off."""
    import jax
    import jax.numpy as jnp

    watch, tr = watch
    log = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    tr._annotate = Annotation

    def annotated_program(x):
        return jnp.tanh(x) * 0.375 + jnp.cumsum(x)

    jax.block_until_ready(jax.jit(annotated_program)(jnp.ones((5,))))
    mine = [(what, name) for what, name in log if "annotated_program" in name]
    assert mine == [(what, name) for name in ("compile/trace:annotated_program", "compile/lower:jit(annotated_program)",
                                              "compile/jit(annotated_program)") for what in ("enter", "exit")]
    assert not [name for _, name in log if "tanh" in name or "cumsum" in name]  # nested: counted by nobody, drawn by nobody
    assert watch._here.depth == 0 and watch._here.annotation is None
    obs_trace.configure(enabled=False)
    del log[:]
    jax.block_until_ready(jax.jit(lambda x: annotated_program(x) * 2)(jnp.ones((5,))))
    assert log == []


def _as_jax_reports(kind, secs, fun_name, inside=()):
    """One stretch exactly as jax's LogElapsedTimeContextManager records it:
    a scalar where it starts, a duration and a time span where it ends."""
    import jax.monitoring as mon

    t = time.time()
    mon.record_scalar(kind, t, fun_name=fun_name)
    for args in inside:
        args()
    mon.record_event_duration_secs(kind, secs, fun_name=fun_name)
    mon.record_event_time_span(kind, t, t + secs, fun_name=fun_name)


def test_compile_watch_puts_lowering_and_cache_read_on_the_right_programs_event(watch):
    """The CPU has no persistent cache, so JAX's events are recorded by hand,
    as JAX orders them: two programs made at once on two threads, one read
    from the cache and one compiled anew; a nested trace inside the first."""
    import threading

    import jax.monitoring as mon

    watch, tr = watch
    before = get_registry().snapshot()
    mark = watch.mark()
    turn = [threading.Event(), threading.Event()]

    def other_thread():
        turn[0].wait(10)
        _as_jax_reports(watch.TRACE, 0.25, "cold_step")
        _as_jax_reports(watch.LOWER, 0.125, "jit(cold_step)")
        turn[1].set()
        turn[0].wait(10)
        _as_jax_reports(watch.COMPILE, 8.0, "jit(cold_step)", inside=[lambda: mon.record_event(watch.MISS)])

    t = threading.Thread(target=other_thread)
    t.start()
    _as_jax_reports(watch.TRACE, 2.0, "warm_step",
                    inside=[lambda: _as_jax_reports(watch.TRACE, 0.5, "_where")])
    turn[0].set()
    turn[1].wait(10)  # the other thread's trace and lowering land between this program's trace and lowering
    _as_jax_reports(watch.LOWER, 0.5, "jit(warm_step)",
                    inside=[lambda: _as_jax_reports(watch.TRACE, 0.0625, "_cumsum")])
    _as_jax_reports(watch.COMPILE, 1.5, "jit(warm_step)", inside=[
        lambda: mon.record_event(watch.HIT),
        lambda: mon.record_event_duration_secs(watch.CACHE_READ, 1.25)])
    turn[0].set()
    t.join(10)
    assert not t.is_alive()
    got, after = watch.since(mark), get_registry().snapshot()
    events = {e["fun"]: e for e in got["events"]}
    warm, cold = events["jit(warm_step)"], events["jit(cold_step)"]
    assert (warm["trace_s"], warm["lower_s"], warm["compile_s"], warm["cache"], warm["cache_read_s"]) \
        == (2.0, 0.5, 1.5, "hit", 1.25)
    assert (cold["trace_s"], cold["lower_s"], cold["compile_s"], cold["cache"], cold["cache_read_s"]) \
        == (0.25, 0.125, 8.0, "miss", 0.0)
    assert (got["compiles"], got["trace_s"], got["lower_s"], got["compile_s"], got["cache_read_s"]) \
        == (2, 2.25, 0.62, 9.5, 1.25)
    assert (got["cache_hits"], got["cache_misses"]) == (1, 1) and warm["t"] <= time.perf_counter()
    moved = lambda key: after[key] - before.get(key, 0.0)  # noqa: E731
    assert moved("jax.traces") == 2 and moved("jax.trace_seconds.sum") == 2.25  # `_where`, `_cumsum`: inside
    assert moved("jax.lower_seconds.sum") == 0.625 and moved("jax.cache_read_seconds.sum") == 1.25
    assert moved("jax.backend_compile_seconds.sum") == 9.5  # keeps its meaning: it contains the read
    drawn = {e["name"]: e for e in tr.to_chrome_trace()["traceEvents"] if e.get("cat") == "compile"}
    assert drawn["compile/trace:warm_step"]["dur"] == pytest.approx(2e6, rel=1e-3)
    assert drawn["compile/lower:jit(cold_step)"]["ph"] == "X" and "compile/trace:_where" not in drawn
    assert drawn["compile/jit(warm_step)"]["ph"] == "i" and drawn["compile/jit(warm_step)"]["args"]["cache"] == "hit"


def test_compile_watch_a_lowering_whose_trace_jax_answered_from_memory_starts_its_own_program(watch):
    """`jit(f).lower()` of a function traced before reports no trace; a trace
    that nothing compiled (`eval_shape`) must not be handed to the next program."""
    watch, _ = watch
    mark = watch.mark()
    _as_jax_reports(watch.TRACE, 4.0, "only_shapes_wanted")  # eval_shape: traced, never lowered
    _as_jax_reports(watch.LOWER, 0.25, "jit(step)")
    _as_jax_reports(watch.COMPILE, 0.5, "jit(step)")
    (event,) = watch.since(mark)["events"]
    assert (event["fun"], event["trace_s"], event["lower_s"], event["cache"]) == ("jit(step)", 0.0, 0.25, "off")


def test_compile_watch_a_forced_collection_is_one_full_collection_and_one_gc_full_span(watch):
    import gc

    watch, tr = watch
    reg = get_registry()
    before = reg.snapshot()
    t0 = time.perf_counter()
    gc.collect()
    t1 = time.perf_counter()
    after = reg.snapshot()
    assert after["host.gc_full_collections"] - before["host.gc_full_collections"] == 1
    assert after["host.gc_collections"] - before["host.gc_collections"] == 1
    pause = after["host.gc_pause_seconds"] - before["host.gc_pause_seconds"]
    assert 0 < pause <= t1 - t0 and after["host.gc_max_pause_seconds"] >= pause > 0
    spans = [e for e in tr.to_chrome_trace()["traceEvents"] if e["name"] == "gc/full"]
    assert len(spans) == 1 and spans[0]["ph"] == "X" and spans[0]["cat"] == "gc" and "gc" in obs_trace.SPAN_CATEGORIES
    assert spans[0]["dur"] == pytest.approx(pause * 1e6, rel=1e-6) and "collected" in spans[0]["args"]
    # the ring keeps pauses over a millisecond, stamped on perf_counter where they started
    if pause > watch.GC_RING_FLOOR_S:
        assert watch.gc_max_pause_between(t0, t1) == pytest.approx(pause)
    assert watch.gc_max_pause_between(t1, t1 + 1.0) == 0.0
    gc.collect(0)  # a young collection is counted, not drawn
    assert reg.gauge("host.gc_collections").value - after["host.gc_collections"] == 1
    assert reg.gauge("host.gc_full_collections").value == after["host.gc_full_collections"]
    assert len([e for e in tr.to_chrome_trace()["traceEvents"] if e["name"] == "gc/full"]) == 1


def test_compile_watch_a_collection_while_the_registrys_locks_are_held_does_not_hang(watch):
    """A collection can start inside Histogram.observe or MetricsRegistry._get
    while their lock is held: the collector's callback must not want any of
    them (nor the watch's own, nor the tracer's open-span stack). Here the
    locks are held by THIS thread while another one collects."""
    import gc
    import threading

    watch, tr = watch
    reg = get_registry()
    held = [reg._lock, watch._lock, reg.histogram("jax.trace_seconds")._lock, reg.counter("jax.traces")._lock,
            reg.counter("obs.misnested_spans")._lock]
    full = watch.gc_collections[2]
    done = threading.Event()

    def collect():
        gc.collect()
        done.set()

    t = threading.Thread(target=collect, daemon=True)
    for lock in held:
        lock.acquire()
    try:
        t.start()
        finished = done.wait(20)
    finally:
        for lock in held:
            lock.release()
    t.join(20)
    assert finished and not t.is_alive(), "the collector's callback waited for a lock that other code takes"
    assert watch.gc_collections[2] == full + 1 and watch.gc_callback_errors == 0
    assert [e for e in tr.to_chrome_trace()["traceEvents"] if e["name"] == "gc/full"]


def test_compile_watch_collector_callback_swallows_its_own_errors(watch):
    watch, _ = watch
    errors, seconds = watch.gc_callback_errors, list(watch.gc_seconds)
    watch._on_gc("start", {})  # no generation: not what the interpreter hands over
    watch._on_gc("start", {"generation": 0})
    watch._on_gc("stop", {"generation": 7, "collected": 0})  # no such generation
    assert watch.gc_callback_errors == errors + 2 and watch.gc_seconds == seconds
    watch._on_gc("stop", {"generation": 0, "collected": 0})  # a stop with no start is not a pause
    assert watch.gc_seconds == seconds


def test_install_compile_watch_twice_appends_one_callback_and_restores_the_gauges():
    import gc

    from yet_another_mobilenet_series_tpu.obs.device import CompileWatch, install_compile_watch

    first = install_compile_watch()
    assert install_compile_watch() is first
    mine = [cb for cb in gc.callbacks if getattr(cb, "__self__", None).__class__ is CompileWatch]
    assert len(mine) == 1 and mine[0].__self__ is first
    # a registry that was emptied (tests do it) gets the pull gauges and the zeroes back
    reg = get_registry()
    saved = dict(reg._metrics)
    try:
        reg.reset()
        install_compile_watch()
        snap = reg.snapshot()
    finally:
        with reg._lock:
            reg._metrics.update(saved)
    assert snap["host.gc_collections"] == sum(first.gc_collections) > 0
    assert snap["jax.cache_read_seconds.sum"] == 0.0 and snap["jax.traces"] == 0.0


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------


def test_watchdog_fires_on_injected_stall(tmp_path):
    """à la test_fault_injection: the loop stops beating mid-span, the
    watchdog must dump a hang report with open spans + registry snapshot."""
    tr = SpanTracer(ring_size=8)
    reg = MetricsRegistry()
    reg.counter("train.rebuilds").inc(3)
    wd = StallWatchdog(str(tmp_path), deadline_s=0.25, poll_s=0.05, tracer=tr, registry=reg)
    wd.start()
    span = tr.span("dispatch/train_step", "dispatch")
    span.__enter__()  # a dispatch that never returns
    wd.arm(step=7)
    deadline = time.time() + 10
    report_path = tmp_path / "hang_report.json"
    while time.time() < deadline and not report_path.exists():
        time.sleep(0.05)
    wd.stop()
    span.__exit__(None, None, None)
    assert report_path.exists(), "watchdog never fired on a stalled loop"
    assert wd.fired
    rep = json.loads(report_path.read_text())
    assert rep["last_step"] == 7
    assert rep["last_phase"] == "step"
    assert rep["seconds_since_last_beat"] >= 0.25
    assert any(s["name"] == "dispatch/train_step" for s in rep["open_spans"])
    assert rep["registry"]["train.rebuilds"] == 3.0
    assert rep["threads"], "thread stacks missing from hang report"
    assert any("MainThread" in name for name in rep["threads"])


def test_watchdog_silent_on_healthy_loop(tmp_path):
    wd = StallWatchdog(str(tmp_path), deadline_s=0.5, poll_s=0.05)
    wd.start()
    for step in range(12):  # ~0.6 s of healthy 50ms steps
        wd.arm(step)
        time.sleep(0.05)
    wd.stop()
    assert not (tmp_path / "hang_report.json").exists()
    assert not wd.fired


def test_watchdog_rejects_nonpositive_deadline(tmp_path):
    with pytest.raises(ValueError, match="deadline"):
        StallWatchdog(str(tmp_path), deadline_s=0.0)


def test_watchdog_info_providers_reach_hang_report(tmp_path):
    """The serving extension: registered info providers (batcher threads,
    in-flight window, breaker state — cli/serve.py wires the real ones)
    land in hang_report.json, and a provider that raises contributes its
    error string instead of killing the report."""
    wd = StallWatchdog(
        str(tmp_path), deadline_s=0.2, poll_s=0.05,
        info_providers={"serving": lambda: {
            "batcher_threads": [{"name": "serve-collect", "alive": True}],
            "inflight": 2,
            "admission": {"breaker": "open"},
        }},
    )

    def broken():
        raise RuntimeError("provider died")

    wd.register_info("broken", broken)
    wd.start()
    wd.arm(step=1, phase="serve")
    deadline = time.time() + 10
    report_path = tmp_path / "hang_report.json"
    while time.time() < deadline and not report_path.exists():
        time.sleep(0.05)
    wd.stop()
    assert report_path.exists()
    rep = json.loads(report_path.read_text())
    assert rep["last_phase"] == "serve"
    serving = rep["info"]["serving"]
    assert serving["inflight"] == 2
    assert serving["batcher_threads"][0]["name"] == "serve-collect"
    assert serving["admission"]["breaker"] == "open"
    assert "provider failed" in rep["info"]["broken"] and "provider died" in rep["info"]["broken"]


def test_watchdog_serving_report_from_live_batcher(tmp_path):
    """End-to-end serving hang report: a pipelined batcher wedged on a hung
    engine, the watchdog's serving section carries the real thread names,
    window occupancy, and breaker state."""
    import numpy as np

    from yet_another_mobilenet_series_tpu.cli.serve import _serving_info
    from yet_another_mobilenet_series_tpu.serve.admission import AdmissionController
    from yet_another_mobilenet_series_tpu.serve.faults import FaultyEngine
    from yet_another_mobilenet_series_tpu.serve.pipeline import PipelinedBatcher

    class _Echo:
        def predict_async(self, images):
            class _H:
                def result(_s):
                    return images[:, 0, 0, :1]
            return _H()

        def predict(self, images):
            return self.predict_async(images).result()

    eng = FaultyEngine(_Echo(), hang_at=0)
    b = PipelinedBatcher(eng, max_batch=1, max_wait_ms=0.0, drain_timeout_s=1.0).start()
    ac = AdmissionController(b)
    wd = StallWatchdog(str(tmp_path), deadline_s=0.2, poll_s=0.05)
    wd.register_info("serving", lambda: _serving_info(b, ac))
    wd.start()
    wd.arm(phase="serve")
    try:
        fut = ac.submit(np.zeros((4, 4, 3), np.float32))
        report_path = tmp_path / "hang_report.json"
        deadline = time.time() + 10
        while time.time() < deadline and not report_path.exists():
            time.sleep(0.05)
        assert report_path.exists()
        rep = json.loads(report_path.read_text())
        serving = rep["info"]["serving"]
        names = {t["name"] for t in serving["batcher_threads"]}
        assert names == {"serve-collect", "serve-complete"}
        assert serving["inflight"] >= 1  # the wedged batch occupies the window
        assert serving["admission"]["breaker"] == "closed"
        assert serving["admission"]["classes"]["interactive"]["in_queue"] >= 1
        # the report NAMES the wedged request: id, class, age, phase
        oldest = serving["oldest_request"]
        assert oldest is not None
        assert oldest["class"] == "interactive"
        assert oldest["age_s"] >= 0.0 and oldest["id"] >= 1
        assert oldest["phase"] in ("queued", "dispatched")
        # the wedged request is also visible in the dumped thread stacks
        assert any("serve-complete" in name for name in rep["threads"])
    finally:
        wd.stop()
        b.stop()  # drain-bounded: the hung engine cannot wedge teardown
        with pytest.raises(Exception):
            fut.result(timeout=1)


# ---------------------------------------------------------------------------
# Logger integration
# ---------------------------------------------------------------------------


def test_logger_degrades_without_tensorflow(tmp_path, monkeypatch, capsys):
    """The satellite fix: tensorboard=True on a TF-less box must warn once
    and keep jsonl logging, not crash the run."""
    monkeypatch.setitem(__import__("sys").modules, "tensorflow", None)
    monkeypatch.setattr(logging_lib, "_TB_WARNED", False)
    log = logging_lib.Logger(str(tmp_path), enabled=True, tensorboard=True)
    try:
        assert log._tb is None
        out = capsys.readouterr().out
        assert "tensorboard logging disabled" in out
        # warn once only
        log2 = logging_lib.Logger(str(tmp_path), enabled=True, tensorboard=True)
        log2.close()
        assert "tensorboard logging disabled" not in capsys.readouterr().out
        log.scalars(3, {"loss": 1.5}, "train/")
    finally:
        log.close()
    rows = [json.loads(line) for line in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert rows == [{"step": 3, "train/loss": 1.5}]


def test_logger_scalars_carry_registry_snapshot(tmp_path):
    reg = MetricsRegistry()
    reg.counter("data.decode_failures").inc(2)
    log = logging_lib.Logger(str(tmp_path), enabled=True, tensorboard=False)
    try:
        log.set_registry(reg)
        log.scalars(1, {"loss": 0.5}, "train/")
    finally:
        log.close()
    row = json.loads((tmp_path / "metrics.jsonl").read_text().splitlines()[0])
    assert row["train/loss"] == 0.5
    assert row["obs/data.decode_failures"] == 2.0


def test_emit_routes_through_active_logger(capsys):
    log = logging_lib.Logger(None, enabled=True)
    logging_lib.emit("hello from the pipeline")
    out = capsys.readouterr().out
    assert "] hello from the pipeline" in out  # Logger's [HH:MM:SS] prefix
    log.close()
    logging_lib.emit("after close")
    assert capsys.readouterr().out == "after close\n"  # bare fallback


# ---------------------------------------------------------------------------
# fake-data CPU train smoke: trace + snapshot artifacts
# ---------------------------------------------------------------------------


def _smoke_cfg(tmp_path):
    return config_from_dict({
        "name": "obs-smoke",
        "model": {
            "arch": "mobilenet_v2", "num_classes": 4, "dropout": 0.0,
            "block_specs": [{"t": 2, "c": 8, "n": 1, "s": 2}],
        },
        "data": {"dataset": "fake", "image_size": 24, "fake_train_size": 64, "fake_eval_size": 16},
        "optim": {"optimizer": "sgd", "momentum": 0.9, "weight_decay": 1e-5},
        "schedule": {"schedule": "constant", "base_lr": 0.01, "scale_by_batch": False, "warmup_epochs": 0.0},
        "ema": {"enable": True, "decay": 0.9, "warmup": False},
        "train": {
            "batch_size": 32, "eval_batch_size": 16, "epochs": 1, "log_every": 1,
            "compute_dtype": "float32", "log_dir": str(tmp_path),
        },
        # trace on; generous watchdog deadline proves it stays silent on a
        # healthy loop even with compiles in the gap
        "obs": {"trace": True, "watchdog_deadline_s": 300.0},
        "dist": {"num_devices": 8},
    })


def test_train_smoke_emits_trace_and_registry_snapshot(tmp_path):
    result = cli_train.run(_smoke_cfg(tmp_path))
    assert result["epoch"] == pytest.approx(1.0)

    # valid Chrome-trace JSON with spans from all five core categories
    doc = json.loads((tmp_path / "obs_trace.json").read_text())
    evts = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert evts, "no spans recorded"
    for e in evts:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] >= 0
    cats = {e["cat"] for e in evts}
    assert {"data", "dispatch", "sync", "eval", "ckpt"} <= cats, cats
    assert "dispatch/train_step" in {e["name"] for e in evts}

    # registry snapshot written at run end
    snap = json.loads((tmp_path / "obs_registry.json").read_text())
    assert snap.get("ckpt.saves", 0) >= 1
    assert snap.get("eval.passes", 0) >= 1
    assert "ckpt.wait_seconds.count" in snap

    # every scalars row carries the obs/ snapshot
    rows = [json.loads(line) for line in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert rows and all(any(k.startswith("obs/") for k in r) for r in rows)

    # healthy loop: armed watchdog stayed silent
    assert not (tmp_path / "hang_report.json").exists()


# ---------------------------------------------------------------------------
# scripts/obs_report.py
# ---------------------------------------------------------------------------


def _obs_report_mod():
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(REPO, "scripts", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_obs_report_renders_summary(tmp_path, capsys):
    (tmp_path / "metrics.jsonl").write_text(
        json.dumps({"step": 1, "train/loss": 2.0, "train/images_per_sec": 100.0,
                    "obs/ckpt.saves": 0.0}) + "\n"
        + json.dumps({"step": 2, "eval/top1": 0.75, "eval/loss": 1.1}) + "\n"
    )
    (tmp_path / "obs_registry.json").write_text(
        json.dumps({"ckpt.saves": 1.0, "train.rebuilds": 2.0}))
    (tmp_path / "hang_report.json").write_text(json.dumps({
        "seconds_since_last_beat": 12.5, "deadline_s": 5.0, "last_step": 42,
        "last_phase": "step",
        "open_spans": [{"name": "dispatch/train_step", "cat": "dispatch", "open_for_s": 12.0}],
        "registry": {}, "threads": {"MainThread-1": ["..."]},
    }))
    rc = _obs_report_mod().main([str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "last train/loss = 2" in out
    assert "best eval/top1 = 0.75" in out
    assert "ckpt.saves = 1" in out
    assert "HANG REPORT" in out
    assert "dispatch/train_step" in out


def test_obs_report_device_section(tmp_path, capsys):
    """The device-telemetry section: compile events, per-executable cost,
    dispatch efficiency, memory gauges (obs/device.py surfaces)."""
    (tmp_path / "obs_registry.json").write_text(json.dumps({
        "obs.compiles": 3.0, "obs.compile_seconds.p50": 1.5,
        "obs.compile_seconds.max": 2.0, "obs.compile_seconds.sum": 4.0,
        "obs.cost_flops.serve_b8_s224_k1": 1.2e9,
        "obs.cost_bytes.serve_b8_s224_k1": 3.4e8,
        "serve.achieved_flops_per_s": 2.5e9, "serve.run_seconds.count": 4.0,
        "host.rss_bytes": 5e8, "device.live_buffer_bytes": 1e7,
        "device.bytes_in_use.d0": 2e9, "device.peak_bytes_in_use.d0": 3e9,
    }))
    rc = _obs_report_mod().main([str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "## device (compile / cost / memory)" in out
    assert "compiles = 3" in out and "p50 1.50s" in out
    assert "[serve_b8_s224_k1] 1.200 GFLOP, 340.0 MB accessed" in out
    assert "dispatch efficiency: 2.50 achieved GFLOP/s" in out
    assert "host rss 500 MB" in out
    assert "d0 in-use 2000 MB (peak 3000)" in out


def test_obs_report_missing_dir(capsys):
    assert _obs_report_mod().main(["/definitely/not/a/dir"]) == 2


def test_obs_report_requests_waterfalls_and_quantiles(tmp_path, capsys):
    """--requests renders per-request waterfalls from the trace's async
    events and a per-phase quantile table from the registry snapshot."""
    us = 1000.0  # µs timestamps in the trace
    events = [
        # request 17: queued 2 ms, in-flight 3 ms, across two threads
        {"name": "serve/request", "ph": "b", "id": 17, "tid": 1, "ts": 0,
         "args": {"cls": "interactive", "deadline_ms": 50.0}},
        {"name": "serve/queued", "ph": "b", "id": 17, "tid": 1, "ts": 0},
        {"name": "serve/queued", "ph": "e", "id": 17, "tid": 2, "ts": 2 * us},
        {"name": "serve/inflight", "ph": "b", "id": 17, "tid": 2, "ts": 2 * us},
        {"name": "serve/inflight", "ph": "e", "id": 17, "tid": 3, "ts": 5 * us},
        {"name": "serve/request", "ph": "e", "id": 17, "tid": 3, "ts": 5.2 * us,
         "args": {"outcome": "completed"}},
        # a flow step rides along and must not confuse the waterfall parse
        {"name": "serve/req", "ph": "t", "id": 17, "tid": 2, "ts": 2 * us},
    ]
    (tmp_path / "obs_trace.json").write_text(json.dumps({"traceEvents": events}))
    (tmp_path / "obs_registry.json").write_text(json.dumps({
        "serve.queue_wait_seconds.count": 4.0,
        "serve.queue_wait_seconds.p50": 0.002, "serve.queue_wait_seconds.p95": 0.003,
        "serve.queue_wait_seconds.p99": 0.0031, "serve.queue_wait_seconds.min": 0.001,
        "serve.queue_wait_seconds.max": 0.0032,
        "serve.latency_seconds.interactive.count": 4.0,
        "serve.latency_seconds.interactive.p50": 0.005,
        "serve.latency_seconds.interactive.p99": 0.009,
    }))
    rc = _obs_report_mod().main([str(tmp_path), "--requests"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "#17" in out and "class=interactive" in out
    assert "total=5.20ms" in out and "queued=2.00ms" in out and "inflight=3.00ms" in out
    assert "[completed]" in out
    assert "queue wait" in out and "latency [interactive]" in out
    assert "p50_ms" in out and "p99_ms" in out


# ---------------------------------------------------------------------------
# fleet federation + flight recorder (obs/fleet.py) and trace merge
# (scripts/trace_merge.py) — ISSUE 17
# ---------------------------------------------------------------------------


class _StubVarz:
    """A backend whose /varz is a callable — the scrape loop's only client
    surface (ReplicaClient.varz -> (status, doc))."""

    def __init__(self, doc_fn, status=200):
        self._doc_fn = doc_fn
        self._status = status
        self.calls = 0

    def varz(self, timeout_s=2.0):
        self.calls += 1
        if isinstance(self._status, Exception):
            raise self._status
        return self._status, self._doc_fn()


def _replica_varz(reg, rid, build=None):
    """A /varz document shaped like serve/frontend.py's, from a registry."""
    return {
        "replica": {"replica_id": rid},
        "build_info": build or {},
        "metrics": reg.snapshot(),
        "histograms": reg.histograms_state(),
        "admission": {"queued_total": 0},
        "draining": False,
    }


def test_registry_histograms_state_is_raw_and_mergeable():
    """Histogram.state() ships RAW per-bucket counts (not cumulative) plus
    bounds/count/sum/min/max — the exact payload quantiles_from_counts
    consumes, so a scraper recomputes quantiles losslessly."""
    from yet_another_mobilenet_series_tpu.obs.registry import quantiles_from_counts

    reg = MetricsRegistry()
    h = reg.histogram("serve.latency_seconds.interactive", bounds=[0.01, 0.1])
    h.observe(0.005)
    h.observe(0.5)
    reg.counter("serve.completed.interactive").inc()  # not a histogram: excluded
    state = reg.histograms_state()
    assert set(state) == {"serve.latency_seconds.interactive"}
    st = state["serve.latency_seconds.interactive"]
    assert st["bounds"] == [0.01, 0.1]
    assert st["counts"] == [1, 0, 1]  # raw slots incl. overflow, NOT cumulative
    assert st["count"] == 2 and st["sum"] == 0.505
    assert st["min"] == 0.005 and st["max"] == 0.5
    (p50,) = quantiles_from_counts(st["bounds"], st["counts"], (0.5,),
                                   vmin=st["min"], vmax=st["max"])
    assert 0.005 <= p50 <= 0.5
    assert json.loads(json.dumps(st)) == st  # JSON-safe for /varz


def test_fleet_federation_p99_matches_pooled_reference():
    """The federation-correctness property (ISSUE 17 acceptance): the fleet
    windowed p99 computed from SUMMED per-replica bucket-count deltas must
    equal the quantile of one histogram fed every pooled observation —
    identical ladders make the merge exact, not an average of averages.
    Includes the edges: a replica with NO histograms at all, and an
    all-zero window (no traffic between scrapes) reading 0."""
    import numpy as np

    from yet_another_mobilenet_series_tpu.obs.fleet import FleetFederation
    from yet_another_mobilenet_series_tpu.obs.registry import quantiles_from_counts

    regs = [MetricsRegistry() for _ in range(3)]
    backends = [(f"127.0.0.1:900{i}",
                 _StubVarz(lambda i=i: _replica_varz(regs[i], f"r{i}")))
                for i in range(3)]
    fed = FleetFederation(lambda: backends)
    rng = np.random.RandomState(11)
    lat = "serve.latency_seconds.interactive"

    # pre-window history the baseline scrape must consume, NOT leak into
    # the first window
    for reg in regs[:2]:
        for v in np.exp(rng.uniform(np.log(1e-3), np.log(2.0), 50)):
            reg.histogram(lat).observe(float(v))
    fed.scrape_once()

    # the window: replicas 0 and 1 observe, replica 2 stays histogram-free
    window = []
    for reg in regs[:2]:
        vs = np.exp(rng.uniform(np.log(1e-3), np.log(2.0), 400))
        for v in vs:
            reg.histogram(lat).observe(float(v))
        window.extend(float(v) for v in vs)
    summary = fed.scrape_once()
    assert summary == {"scraped": 3, "errors": 0}
    assert get_registry().gauge("fleet.federated_replicas").value == 3

    ref = MetricsRegistry().histogram("ref")  # same default ladder
    for v in window:
        ref.observe(v)
    (ref_p99,) = quantiles_from_counts(
        list(ref.bounds), list(ref.bucket_counts()), (0.99,))
    fed_p99 = get_registry().gauge("fleet.window_p99_seconds.interactive").value
    assert fed_p99 == ref_p99  # exact, same interpolation over equal counts
    assert fed.snapshot()["window_p99_s"]["interactive"] == ref_p99

    # merged CUMULATIVE counts = element-wise sum of both lifetimes so far
    merged = fed.merged_counts()[lat]
    per_rep = [list(r.histogram(lat).bucket_counts()) for r in regs[:2]]
    assert merged["counts"] == [a + b for a, b in zip(*per_rep)]

    # all-zero window: no traffic between scrapes reads a 0 gauge, not NaN
    fed.scrape_once()
    assert get_registry().gauge("fleet.window_p99_seconds.interactive").value == 0.0


def test_fleet_federation_replica_restart_not_double_counted():
    """Counter-reset handling: a replica restart zeroes its histograms; the
    merged cumulative counts must carry BOTH lifetimes exactly once (a
    naive cumulative sum would lose the first or double the second)."""
    from yet_another_mobilenet_series_tpu.obs.fleet import FleetFederation

    lat = "serve.latency_seconds.interactive"
    holder = {"reg": MetricsRegistry()}
    backends = [("127.0.0.1:9000",
                 _StubVarz(lambda: _replica_varz(holder["reg"], "r0")))]
    fed = FleetFederation(lambda: backends)
    for _ in range(10):
        holder["reg"].histogram(lat).observe(0.01)
    fed.scrape_once()
    holder["reg"] = MetricsRegistry()  # kill -9 + respawn: fresh process
    for _ in range(4):
        holder["reg"].histogram(lat).observe(0.01)
    fed.scrape_once()
    assert sum(fed.merged_counts()[lat]["counts"]) == 14

    # a dead backend is a skipped scrape, never an exception out of the loop
    backends.append(("127.0.0.1:9001", _StubVarz(None, status=OSError("down"))))
    summary = fed.scrape_once()
    assert summary == {"scraped": 1, "errors": 1}


def test_fleet_federation_slo_feed_and_fast_burn_incident(tmp_path):
    """The scrape loop feeds summed completed/bad deltas into the SLO
    tracker; sustained burn over BOTH windows trips fast_burn, which arms
    the flight recorder and the dump names the reason."""
    from yet_another_mobilenet_series_tpu.obs.fleet import FleetFederation, FlightRecorder
    from yet_another_mobilenet_series_tpu.serve.signals import SLOTracker

    t = [0.0]
    slo = SLOTracker(error_budget=0.1, short_window_s=5.0, long_window_s=50.0,
                     fast_burn=2.0, clock=lambda: t[0])
    reg = MetricsRegistry()
    rec = FlightRecorder(str(tmp_path), min_interval_s=0.0)
    fed = FleetFederation(
        lambda: [("a", _StubVarz(lambda: _replica_varz(reg, "ra")))],
        slo=slo, recorder=rec)
    fed.scrape_once()  # baseline
    for _ in range(60):  # 50% bad at a 10% budget = 5x burn, both windows
        t[0] += 1.0
        reg.counter("serve.completed.interactive").inc(5)
        reg.counter("serve.rejected.interactive").inc(5)
        fed.scrape_once()
    assert slo.fast_burn
    assert get_registry().gauge("fleet.slo_burn_rate.short").value >= 2.0
    path = rec.maybe_dump(fed)
    assert path and os.path.basename(path) == "incident_slo_fast_burn.json"
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "slo_fast_burn"
    assert "fleet" in doc and "replica_varz" in doc
    assert doc["fleet"]["slo"]["fast_burn"] is True
    assert any(e["kind"] == "trigger" for e in doc["events"])


def test_fleet_render_prometheus_golden():
    """Replica-labeled exposition golden: every replica's histograms under
    the fleet_ namespace (cumulative buckets, le labels, per-family TYPE
    once), build_info from every replica under ONE family, deterministic
    ordering — the exact text the router frontend appends to /metrics."""
    from yet_another_mobilenet_series_tpu.obs.fleet import FleetFederation

    r0, r1 = MetricsRegistry(), MetricsRegistry()
    r0.histogram("serve.latency_seconds.interactive", bounds=[0.01, 0.1]).observe(0.005)
    r0.histogram("serve.queue_wait_seconds", bounds=[0.01]).observe(0.005)
    r1.histogram("serve.latency_seconds.interactive", bounds=[0.01, 0.1]).observe(0.5)
    backends = [
        ("127.0.0.1:9000", _StubVarz(lambda: _replica_varz(
            r0, "r0", build={"git_sha": "abc", "platform": "cpu"}))),
        ("127.0.0.1:9001", _StubVarz(lambda: _replica_varz(
            r1, "r1", build={"git_sha": "abc", "platform": "cpu"}))),
    ]
    fed = FleetFederation(lambda: backends)
    assert fed.render_prometheus() == ""  # nothing scraped yet
    fed.scrape_once()
    golden = "\n".join([
        '# TYPE fleet_build_info gauge',
        'fleet_build_info{replica="r0",git_sha="abc",platform="cpu"} 1',
        'fleet_build_info{replica="r1",git_sha="abc",platform="cpu"} 1',
        '# TYPE fleet_serve_latency_seconds histogram',
        'fleet_serve_latency_seconds_bucket{replica="r0",class="interactive",le="0.01"} 1',
        'fleet_serve_latency_seconds_bucket{replica="r0",class="interactive",le="0.1"} 1',
        'fleet_serve_latency_seconds_bucket{replica="r0",class="interactive",le="+Inf"} 1',
        'fleet_serve_latency_seconds_sum{replica="r0",class="interactive"} 0.005',
        'fleet_serve_latency_seconds_count{replica="r0",class="interactive"} 1',
        '# TYPE fleet_serve_queue_wait_seconds histogram',
        'fleet_serve_queue_wait_seconds_bucket{replica="r0",le="0.01"} 1',
        'fleet_serve_queue_wait_seconds_bucket{replica="r0",le="+Inf"} 1',
        'fleet_serve_queue_wait_seconds_sum{replica="r0"} 0.005',
        'fleet_serve_queue_wait_seconds_count{replica="r0"} 1',
        'fleet_serve_latency_seconds_bucket{replica="r1",class="interactive",le="0.01"} 0',
        'fleet_serve_latency_seconds_bucket{replica="r1",class="interactive",le="0.1"} 0',
        'fleet_serve_latency_seconds_bucket{replica="r1",class="interactive",le="+Inf"} 1',
        'fleet_serve_latency_seconds_sum{replica="r1",class="interactive"} 0.5',
        'fleet_serve_latency_seconds_count{replica="r1",class="interactive"} 1',
    ]) + "\n"
    assert fed.render_prometheus() == golden


def test_slo_tracker_two_window_gating_and_pruning():
    """Multi-window burn-rate semantics: a short error burst saturates the
    SHORT window but the long window's healthy history gates the alarm;
    sustained burn floods both and trips fast_burn. Ticks prune past the
    long window."""
    from yet_another_mobilenet_series_tpu.serve.signals import SLOTracker

    t = [0.0]
    s = SLOTracker(target_p99_ms=100.0, error_budget=0.01, short_window_s=10.0,
                   long_window_s=100.0, fast_burn=14.0, clock=lambda: t[0])
    for _ in range(90):
        t[0] += 1.0
        s.observe(100, 0, p99_s=0.05)
    assert s.burn_rate(10.0) == 0.0 and not s.fast_burn
    for _ in range(4):  # the burst: 50% errors at a 1% budget
        t[0] += 1.0
        s.observe(100, 50, p99_s=0.05)
    assert s.burn_rate(10.0) >= 14.0
    assert s.burn_rate(100.0) < 14.0
    assert not s.fast_burn  # gated by the long window
    for _ in range(100):  # sustained: both windows saturate
        t[0] += 1.0
        s.observe(100, 50, p99_s=0.05)
    assert s.fast_burn
    st = s.state()
    assert st["fast_burn"] and st["burn_short"] >= 14.0 and st["burn_long"] >= 14.0
    assert st["ticks"] <= 101  # pruned to the long window


def test_slo_tracker_latency_breach_burns_budget():
    """A p99 above target burns budget even with zero errors: the latency
    burn is the breached-tick fraction over the window / budget."""
    from yet_another_mobilenet_series_tpu.serve.signals import SLOTracker

    t = [0.0]
    s = SLOTracker(target_p99_ms=100.0, error_budget=0.1, short_window_s=10.0,
                   long_window_s=100.0, clock=lambda: t[0])
    for _ in range(10):
        t[0] += 1.0
        s.observe(100, 0, p99_s=0.5)  # 5x over target, no errors
    assert s.burn_rate(10.0) == 10.0  # every tick breached / 0.1 budget
    with pytest.raises(ValueError):
        SLOTracker(error_budget=0.0)
    with pytest.raises(ValueError):
        SLOTracker(short_window_s=60.0, long_window_s=30.0)


def test_flight_recorder_ring_triggers_and_rate_limit(tmp_path):
    """Ring semantics + arming: only trigger kinds arm a dump, the rate
    limiter keeps an armed trigger pending (never drops it), a dump
    disarms, and the ring is bounded."""
    from yet_another_mobilenet_series_tpu.obs.fleet import FlightRecorder

    rec = FlightRecorder(str(tmp_path), ring=8, min_interval_s=3600.0)
    assert rec.maybe_dump() is None  # nothing armed
    rec.record("hedge_outcome", winner="hedge")  # significant but not a trigger
    assert rec.maybe_dump() is None
    rec.record("ejection", replica="127.0.0.1:9000", consecutive_failures=2)
    p = rec.maybe_dump()
    assert p and os.path.basename(p) == "incident_ejection.json"
    with open(p) as f:
        doc = json.load(f)
    assert [e["kind"] for e in doc["events"]] == ["hedge_outcome", "ejection"]
    assert all("t_unix" in e for e in doc["events"])
    assert "registry" in doc and "fleet" not in doc  # no federation passed
    # rate-limited: the new trigger stays ARMED until the limiter reopens
    rec.record("lease_expired", replica="127.0.0.1:9001")
    assert rec.maybe_dump() is None
    rec.min_interval_s = 0.0
    p2 = rec.maybe_dump()
    assert p2 and os.path.basename(p2) == "incident_lease_expired.json"
    assert rec.maybe_dump() is None  # disarmed by the dump
    for i in range(50):
        rec.record("breaker_flip", state=i % 3)
    assert len(rec.events()) == 8  # bounded ring


def test_flight_recorder_brownout_arming(tmp_path):
    """The recorder is a brownout TARGET: transitions land in the ring, a
    climb to incident_level arms a dump, recovery back down does not."""
    from yet_another_mobilenet_series_tpu.obs.fleet import FlightRecorder

    class _Policy:
        def __init__(self, level):
            self.level = level
            self.shed_classes = {"batch"} if level >= 3 else set()
            self.hedging = level < 1

    rec = FlightRecorder(str(tmp_path), min_interval_s=0.0, incident_level=3)
    rec.apply_brownout(_Policy(1))
    rec.apply_brownout(_Policy(1))  # same level: no duplicate event
    assert rec.maybe_dump() is None  # below incident_level
    rec.apply_brownout(_Policy(3))
    p = rec.maybe_dump()
    assert p and os.path.basename(p) == "incident_brownout_l3.json"
    with open(p) as f:
        doc = json.load(f)
    trans = [e for e in doc["events"] if e["kind"] == "brownout_transition"]
    assert [e["level"] for e in trans] == [1, 3]
    assert trans[-1]["shed_classes"] == ["batch"]
    rec.apply_brownout(_Policy(4))
    assert rec.maybe_dump() is not None
    rec.apply_brownout(_Policy(3))  # recovery DOWN through the level
    assert rec.maybe_dump() is None


def _trace_merge_mod():
    spec = importlib.util.spec_from_file_location(
        "trace_merge", os.path.join(REPO, "scripts", "trace_merge.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_merge_aligns_clocks_and_scopes_ids():
    """The merge invariants: wall-origin offsets shift every non-metadata
    event onto the earliest process's timeline, colliding pids get their
    own lanes, per-process async/flow ids are remapped so equal request
    ids never fuse across processes — EXCEPT fleet/leg flows, whose ids
    are the cross-process arrow and must survive untouched."""
    tm = _trace_merge_mod()
    router = {
        "pid": 100, "process_name": "router", "origin_unix": 1000.0,
        "traceEvents": [
            {"ph": "M", "name": "process_name", "pid": 100, "tid": 0, "ts": 0},
            {"ph": "b", "cat": "serve", "name": "serve/request", "id": 5,
             "pid": 100, "tid": 1, "ts": 10.0},
            {"ph": "s", "cat": "serve", "name": "fleet/leg", "id": 80,
             "pid": 100, "tid": 1, "ts": 12.0, "args": {"trace": 5, "leg": "primary"}},
        ],
    }
    replica = {
        "pid": 100, "process_name": "r0", "origin_unix": 1000.5,  # pid collision
        "traceEvents": [
            {"ph": "b", "cat": "serve", "name": "serve/request", "id": 5,
             "pid": 100, "tid": 1, "ts": 3.0, "args": {"trace": 5}},
            {"ph": "f", "bp": "e", "cat": "serve", "name": "fleet/leg", "id": 80,
             "pid": 100, "tid": 1, "ts": 4.0},
        ],
    }
    merged = tm.merge([router, replica], sources=["router.json", "r0.json"])
    assert "warnings" not in merged
    procs = {p["process_name"]: p for p in merged["processes"]}
    assert procs["router"]["pid"] == 100
    assert procs["r0"]["pid"] != 100  # collision remapped to its own lane
    assert procs["router"]["offset_us"] == 0.0
    assert procs["r0"]["offset_us"] == 500000.0  # +0.5 s wall-origin gap
    ev = {(e["pid"], e["ph"], e["name"]): e for e in merged["traceEvents"]}
    rpid = procs["r0"]["pid"]
    assert ev[(rpid, "b", "serve/request")]["ts"] == 3.0 + 500000.0
    assert ev[(100, "M", "process_name")]["ts"] == 0  # metadata never shifts
    a = ev[(100, "b", "serve/request")]["id"]
    b = ev[(rpid, "b", "serve/request")]["id"]
    assert a != b  # raw id 5 no longer fuses across processes
    assert a % tm.ID_STRIDE == 5 and b % tm.ID_STRIDE == 5
    assert ev[(100, "s", "fleet/leg")]["id"] == 80
    assert ev[(rpid, "f", "fleet/leg")]["id"] == 80  # the arrow survives


def test_trace_merge_cli_discovers_writes_and_warns(tmp_path, capsys):
    """main(): discovers the fleet layout (router + r*/ sorted), writes
    merged_trace.json atomically, prints the process table, and a doc
    missing origin_unix degrades to a warning, never a crash."""
    tm = _trace_merge_mod()
    doc = {"pid": 1, "process_name": "router", "origin_unix": 5.0,
           "traceEvents": [{"ph": "X", "name": "x", "pid": 1, "tid": 1,
                            "ts": 1.0, "dur": 2.0}]}
    (tmp_path / "obs_trace.json").write_text(json.dumps(doc))
    for i, origin in enumerate((5.25, None)):
        d = dict(doc, pid=2 + i, process_name=f"r{i}")
        if origin is None:
            d.pop("origin_unix")
        else:
            d["origin_unix"] = origin
        (tmp_path / f"r{i}").mkdir()
        (tmp_path / f"r{i}" / "obs_trace.json").write_text(json.dumps(d))
    assert tm.main([str(tmp_path)]) == 0
    printed = capsys.readouterr()
    out = json.load(open(tmp_path / "merged_trace.json"))
    assert [p["process_name"] for p in out["processes"]] == ["router", "r0", "r1"]
    assert [p["offset_us"] for p in out["processes"]] == [0.0, 250000.0, 0.0]
    assert len(out["warnings"]) == 1 and "r1" in out["warnings"][0]
    assert "merged_trace.json" in printed.out
    # a dir with no traces is a clean usage error
    (tmp_path / "empty").mkdir()
    assert tm.main([str(tmp_path / "empty")]) == 2


def test_obs_report_fleet_section(tmp_path, capsys):
    """--fleet renders replica layout, the merged-trace pointer, and the
    incident artifact census (reason, event kinds, SLO state)."""
    (tmp_path / "r0").mkdir()
    (tmp_path / "r0" / "obs_trace.json").write_text(json.dumps({"traceEvents": []}))
    (tmp_path / "incident_ejection.json").write_text(json.dumps({
        "reason": "ejection", "t_unix": 1000.0, "brownout_level": 0,
        "events": [{"t_unix": 999.0, "kind": "ejection", "replica": "127.0.0.1:9001"}],
        "registry": {},
        "fleet": {"replicas": {"127.0.0.1:9000": {}}, "window_p99_s": {"interactive": 0.012},
                  "scrapes": 5, "scrape_errors": 0,
                  "slo": {"burn_short": 1.5, "burn_long": 0.2, "fast_burn": False,
                          "target_p99_ms": 250.0, "error_budget": 0.01}},
    }))
    rc = _obs_report_mod().main([str(tmp_path), "--fleet"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "## fleet" in out
    assert "replica slots: 1 (1 with traces)" in out
    assert "trace_merge.py" in out  # merged trace not built yet: the hint
    assert "incident_ejection.json" in out and "reason = ejection" in out
    assert "ejection x1" in out
    assert "window p99 [interactive] = 12.00 ms" in out
    assert "burn short 1.50" in out
