"""Per-layer metrics are read, not computed in the runners: each metric of
BENCHMARK.json's `per_layer` has a file `layer_metrics/<name>.json` that names
a reader below and its arguments. A reader that finds nothing to read returns
None and the metric is left out of the line.

    {"reader": "registry_mean", "histogram": "serve.run_seconds", "scale": 1000}
    {"reader": "registry_delta", "counter": "serve.h2d_bytes"}
    {"reader": "registry_ratio", "numerator": ["serve.padded_rows"],
     "denominator": ["serve.infer_images", "serve.padded_rows"], "scale": 100}
    {"reader": "compile_log", "phase": "setup", "field": "compile_s"}
    {"reader": "span_mean", "span": "dispatch", "scale": 1000}
    {"reader": "fact", "key": "peak_hbm_bytes", "scale": 9.313225746154785e-10}
    {"reader": "trace", "function": "module_median_ms", "args": {"device": 0}}
    {"reader": "python", "module": "step_mfu_train"}   # layer_metrics/step_mfu_train.py: read(ctx)

Registry readers take the difference of the program's metrics registry
(obs/registry.py snapshot) between the window's two ends, so a counter a later
PR adds to the program becomes a metric by adding one JSON file.
"""

from __future__ import annotations

import importlib.util
import json
import os

from benchmark import harness, trace_reduce

LAYER_DIR = os.path.join(harness.BENCH_DIR, "layer_metrics")


def _delta(ctx, key: str) -> float | None:
    if key not in ctx.registry_after:
        return None
    return ctx.registry_after[key] - ctx.registry_before.get(key, 0.0)


def registry_mean(ctx, histogram: str, scale: float = 1.0):
    total, count = _delta(ctx, histogram + ".sum"), _delta(ctx, histogram + ".count")
    return scale * total / count if total is not None and count else None


def registry_delta(ctx, counter: str, scale: float = 1.0):
    d = _delta(ctx, counter)
    return None if d is None else scale * d


def registry_ratio(ctx, numerator: list, denominator: list, scale: float = 1.0):
    # a counter that never fired is not in the registry yet: it counts 0
    num = sum(_delta(ctx, k) or 0.0 for k in numerator)
    den = sum(_delta(ctx, k) or 0.0 for k in denominator)
    return scale * num / den if den else None


def compile_log(ctx, phase: str, field: str, scale: float = 1.0):
    return scale * {"setup": ctx.compile_setup, "window": ctx.compile_window}[phase][field]


def span_mean(ctx, span: str, scale: float = 1.0):
    durations = ctx.spans.durations.get(span)
    return scale * sum(durations) / len(durations) if durations else None


def fact(ctx, key: str, scale: float = 1.0):
    value = ctx.result["facts"].get(key)
    return None if value is None else scale * value


def trace(ctx, function: str, args: dict | None = None):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return getattr(trace_reduce, function)(ctx.trace, **(args or {}))


def python(ctx, module: str):
    path = os.path.join(LAYER_DIR, module + ".py")
    spec = importlib.util.spec_from_file_location("benchmark.layer_metrics." + module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


READERS = {f.__name__: f for f in (registry_mean, registry_delta, registry_ratio, compile_log,
                                   span_mean, fact, trace, python)}


def read_all(ctx, metrics: list[dict]) -> dict:
    out = {}
    for m in metrics:
        with open(os.path.join(LAYER_DIR, m["name"] + ".json")) as f:
            how = json.load(f)
        kwargs = {k: v for k, v in how.items() if k not in ("reader", "note")}
        out[m["name"]] = READERS[how["reader"]](ctx, **kwargs)
    return out
