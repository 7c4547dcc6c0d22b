"""The layer metrics that read the program's compile watch from inside
(obs/device.py: a program's trace, lowering and cache read beside its compile,
and the collector's pauses). CPU only, nothing timed: each metric against its
`per_layer` entry, its file and its reader; the readers on hand-made registry
snapshots and a hand-made ring of pauses; None for a program without the
watch (the parent commit with these files laid over it); and a rehearsal run
that carries all seven on its line.
"""

from __future__ import annotations

import collections
import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import readers  # noqa: E402

LAYER_DIR = os.path.join(REPO, "benchmark", "layer_metrics")
TRAIN_CELLS = ["mbv3l_train_b512", "effnetb0_train_b512", "effnetb0_train_dp4", "glm47flash_train_2x8k",
               "kimilinear_train_1x16k", "ouro26b_train_1x8k"]
# metric -> (unit, layer, the end-to-end metric it moves, the cells it lists: None = every cell)
SETUP = ("entry points and compile cache", "setup_s", None)
WINDOW = ("host loop and dispatch", "train_images_per_s_per_chip", TRAIN_CELLS)
METRICS = {"entry.program_trace_s": ("s", *SETUP), "entry.program_lower_s": ("s", *SETUP),
           "entry.program_cache_read_s": ("s", *SETUP), "entry.program_gc_s": ("s", *SETUP),
           "entry.program_traces_in_window": ("count", *SETUP),
           "host.gc_pause_ms.train": ("ms", *WINDOW), "host.gc_max_pause_ms.train": ("ms", *WINDOW)}


def read(ctx, *names):
    return readers.read_all(ctx, [{"name": n} for n in names or METRICS])


@pytest.mark.parametrize("name", sorted(METRICS))
def test_each_metric_has_its_entry_its_file_and_its_reader(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    unit, layer, moves, cells = METRICS[name]
    want = {"name": name, "unit": unit, "better": "lower", "source": "program_counter", "layer": layer, "moves": moves}
    assert entry == (want if cells is None else {**want, "workloads": cells})
    assert not name.startswith("step.")  # the older cells' files take `step.*` for the CNN step's scope metrics
    # appended: after every metric the benchmark had, which a later PR may not move
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index(name) > names.index("loop.expected_exit_step.train")
    with open(os.path.join(LAYER_DIR, name + ".json")) as f:
        how = json.load(f)
    assert how["reader"] in readers.READERS and how["note"]
    if how["reader"] == "python":
        assert os.path.exists(os.path.join(LAYER_DIR, how["module"] + ".py"))


def a_window(**after):
    """Registry snapshots at the window's two ends, as the program's watch fills
    them: 9 s of tracing in 41 outermost traces and so on before the window."""
    before = {"jax.traces": 41.0, "jax.trace_seconds.sum": 9.0, "jax.trace_seconds.count": 41.0,
              "jax.lower_seconds.sum": 2.5, "jax.cache_read_seconds.sum": 6.75,
              "jax.backend_compile_seconds.sum": 7.0, "host.gc_pause_seconds": 1.25, "host.gc_collections": 300.0}
    return types.SimpleNamespace(registry_before=before, registry_after={**before, **after},
                                 result={"t_window_start": 100.0, "facts": {"window_s": 10.0}})


def test_the_set_up_metrics_read_the_snapshot_where_the_window_opens():
    ctx = a_window(**{"jax.trace_seconds.sum": 11.0, "host.gc_pause_seconds": 3.5})  # what the window adds is not set-up
    assert read(ctx, "entry.program_trace_s", "entry.program_lower_s", "entry.program_cache_read_s",
                "entry.program_gc_s") == {"entry.program_trace_s": 9.0, "entry.program_lower_s": 2.5,
                                          "entry.program_cache_read_s": 6.75, "entry.program_gc_s": 1.25}


def test_the_window_metrics_read_the_difference_across_the_window():
    quiet = a_window()
    assert read(quiet, "entry.program_traces_in_window", "host.gc_pause_ms.train") \
        == {"entry.program_traces_in_window": 0.0, "host.gc_pause_ms.train": 0.0}
    # a retrace that jax answers from memory compiles nothing and is still a count; the collector's 2.1 s are ms
    loud = a_window(**{"jax.traces": 43.0, "host.gc_pause_seconds": 3.35})
    assert read(loud, "entry.program_traces_in_window", "host.gc_pause_ms.train") \
        == {"entry.program_traces_in_window": 2.0, "host.gc_pause_ms.train": pytest.approx(2100.0)}


def test_the_longest_pause_is_the_rings_longest_that_started_inside_the_window(monkeypatch):
    from yet_another_mobilenet_series_tpu.obs import device

    watch = device.install_compile_watch()
    # (t, generation, seconds, collected): one full collection while the step was traced, two pauses in the
    # window, one after it
    ring = collections.deque([(62.0, 2, 1.9, 120_000), (100.5, 1, 0.004, 0), (107.25, 2, 2.2, 31), (110.5, 2, 0.3, 0)])
    monkeypatch.setattr(watch, "gc_pauses", ring)
    assert read(a_window(), "host.gc_max_pause_ms.train") == {"host.gc_max_pause_ms.train": pytest.approx(2200.0)}
    monkeypatch.setattr(watch, "gc_pauses", collections.deque(list(ring)[:1]))  # none over a millisecond in the window
    assert read(a_window(), "host.gc_max_pause_ms.train") == {"host.gc_max_pause_ms.train": 0.0}


def test_a_program_without_the_watch_puts_nothing_on_the_line(monkeypatch):
    """The parent commit with this PR's benchmark files laid over it: its
    registry holds none of the names, and its watch has no ring."""
    from yet_another_mobilenet_series_tpu.obs import device

    parents = {"jax.backend_compiles": 7.0, "jax.backend_compile_seconds.sum": 7.0, "host.rss_bytes": 1e9}
    ctx = types.SimpleNamespace(registry_before=parents, registry_after=dict(parents),
                                result={"t_window_start": 100.0, "facts": {"window_s": 10.0}})
    monkeypatch.setattr(device, "install_compile_watch", lambda: object())  # the parent's: no collector fields
    assert read(ctx) == dict.fromkeys(METRICS)
    # ...and a program from before the watch: the import finds nothing, the reader does not raise
    monkeypatch.delattr(device, "install_compile_watch")
    assert read(ctx, "host.gc_max_pause_ms.train") == {"host.gc_max_pause_ms.train": None}


def test_a_traced_rehearsal_of_a_train_cell_carries_the_seven_on_its_line():
    proc = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "mbv3l_train_b512", "--seed",
                           str(2**31 + 37), "--seconds", "1.5", "--trace", "1", "--rehearsal"],
                          cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["correct"] is True and last["device"]["platform"] == "cpu"
    got = {k: v for k, v in last["metrics"].items() if k in METRICS}
    assert {k: v["unit"] for k, v in got.items()} == {k: v[0] for k, v in METRICS.items()}
    value = {k: v["value"] for k, v in got.items()}
    # the step was traced and lowered before the window (seconds here are the CPU's: a count that they are there)
    assert value["entry.program_trace_s"] > 0 and value["entry.program_lower_s"] > 0
    assert value["entry.program_cache_read_s"] == 0.0  # the persistent cache is off on the CPU: a 0, not a gap
    assert value["entry.program_gc_s"] > 0  # tracing allocates: the collector ran during set-up
    assert value["entry.program_traces_in_window"] == 0 == last["metrics"]["entry.program_compiles_in_window"]["value"]
    # the snapshots are taken outside the two clock readings, so a pause inside them is inside the difference
    assert 0 <= value["host.gc_max_pause_ms.train"] <= value["host.gc_pause_ms.train"]
    # what is traced, lowered and compiled is no more than the phases it happens in
    phases = next(json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith('{"setup_phases"'))["setup_phases"]
    inside = value["entry.program_trace_s"] + value["entry.program_lower_s"] \
        + last["metrics"]["entry.program_compile_s"]["value"]
    assert inside <= sum(v for k, v in phases.items() if k not in ("process_start_to_harness", "imports_and_manifest"))
