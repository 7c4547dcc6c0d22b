"""The benchmark's own tier-1 tests (CPU only): the manifest keeps to the
contract, every name resolves to its file, cells / configurations / traffic
mixes / layer metrics can be ADDED as files, the result line has exactly the
contract's keys, and the yardstick (MAC count, trace reduction, load
generator, reference) computes what it says. No chip topology is described
here and nothing is timed: a CPU run says what the program counts, never how
fast it is.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import loadgen, macs, readers, reference, trace_reduce  # noqa: E402

PACKAGE = "yet_another_mobilenet_series_tpu"
FIXTURE = os.path.join(REPO, "benchmark", "fixtures", "toy_train_v5e_1chip.xplane.pb")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(root: str, *args: str) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, "benchmark/run.py", *args], cwd=root,
                          capture_output=True, text=True, timeout=300)


def json_lines(stdout: str) -> list[dict]:
    return [json.loads(ln) for ln in stdout.splitlines() if ln.startswith("{")]


# -- the manifest ------------------------------------------------------------


def test_manifest_has_exactly_the_contracts_keys():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert all(not w.startswith("/") and ".." not in w for w in m["command"])
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    for path in m["paths"]:
        assert os.path.isdir(os.path.join(REPO, path)), path


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_units_and_keys_keep_to_the_contract(group):
    m = manifest()
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves", "workloads"},
    }[group]
    names = [e["name"] for e in m[group]]
    assert len(names) == len(set(names))
    for e in m[group]:
        assert set(e) <= allowed, e
        assert NAME.fullmatch(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]
        if "unit" in e:
            assert UNIT.fullmatch(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
    if group == "end_to_end":
        assert "setup_s" in names
        assert all(0.01 <= e["bound"] <= 0.1 and e["source"] in ("host_clock", "device_trace") for e in m[group])
    if group == "workloads":
        assert all(NAME.fullmatch(w["traffic"]) and w["chips"] in (1, 4) for w in m[group])
        assert len({(w["config"], w["traffic"]) for w in m[group]}) == len(names)
        assert sum(w["chips"] == 4 for w in m[group]) <= max(1, len(names) // 4)


def test_every_name_resolves_to_its_file():
    m = manifest()
    configs = {c["name"]: c for c in m["configs"]}
    assert {w["config"] for w in m["workloads"]} == set(configs)  # each configuration used by some cell
    for c in m["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        with open(os.path.join(REPO, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    for w in m["workloads"]:
        with open(os.path.join(REPO, "benchmark", "traffic", w["traffic"] + ".json")) as f:
            runner = json.load(f)["runner"]
        assert os.path.exists(os.path.join(REPO, "benchmark", "runners", runner + ".py"))
    end_to_end = {e["name"]: e for e in m["end_to_end"]}
    cells = {w["name"] for w in m["workloads"]}
    for metric in m["per_layer"]:
        with open(os.path.join(readers.LAYER_DIR, metric["name"] + ".json")) as f:
            assert json.load(f)["reader"] in readers.READERS
        # a layer metric is reported only where the metric it moves is reported
        moved = end_to_end[metric["moves"]]
        assert set(metric.get("workloads", cells)) <= set(moved.get("workloads", cells))
    for w in cells:  # every cell: setup_s, another end-to-end metric, a layer metric
        assert len([e for e in m["end_to_end"] if w in e.get("workloads", [w])]) >= 2
        assert any(w in p.get("workloads", [w]) for p in m["per_layer"])


# -- the command -------------------------------------------------------------


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    proc = run_cell(REPO, "--workload", manifest()["workloads"][0]["name"], "--seed", "1", "--seconds", "1")
    assert proc.returncode != 0
    assert json_lines(proc.stdout) == []
    assert "no TPU" in proc.stderr


def test_in_a_bare_directory_the_command_fails_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_cell(str(tmp_path), "--workload", manifest()["workloads"][0]["name"], "--rehearsal")
    assert proc.returncode != 0
    assert json_lines(proc.stdout) == []


@pytest.fixture(scope="module")
def train_rehearsal():
    """One end-to-end (--trace 0) rehearsal of the first train cell, from the repo itself."""
    cell = next(w for w in manifest()["workloads"] if w["traffic"].startswith("train"))
    proc = run_cell(REPO, "--workload", cell["name"], "--seed", str(2**31 + 11), "--seconds", "1",
                    "--trace", "0", "--rehearsal")
    assert proc.returncode == 0, proc.stderr[-3000:]
    return cell, json_lines(proc.stdout)


def test_last_line_has_exactly_the_contracts_keys(train_rehearsal):
    cell, lines = train_rehearsal
    last = lines[-1]
    assert set(last) == RESULT_KEYS
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    wanted = {e["name"] for e in manifest()["end_to_end"] if cell["name"] in e.get("workloads", [cell["name"]])}
    assert set(last["metrics"]) == wanted and "setup_s" in wanted
    assert all(set(v) == {"value", "unit"} and v["value"] > 0 for v in last["metrics"].values())
    assert set(last["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert last["device"]["platform"] == "cpu"  # a rehearsal says what it is


def test_nothing_compiles_in_the_window_and_no_heavy_import(train_rehearsal):
    _, lines = train_rehearsal
    notes = next(ln for ln in lines if "setup_phases" in ln)
    assert notes["compile_window"]["compiles"] == 0
    assert notes["heavy_imports"] == []  # orbax, tensorflow, google.cloud.logging: 31 s of PR 23's set-up
    assert {"import_program", "init_state", "first_step"} <= set(notes["setup_phases"])


def test_cell_config_traffic_and_layer_metric_are_added_as_files(tmp_path):
    """A later PR adds a cell by adding files and manifest entries, editing no
    file that is there: here a new configuration, a new open-loop traffic mix,
    a new cell and a new registry-backed layer metric, run traced."""
    root = tmp_path
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, PACKAGE), root / PACKAGE)
    before = {p: os.path.getmtime(os.path.join(dp, p)) for dp, _, fs in os.walk(root / "benchmark") for p in fs}
    m = manifest()
    with open(os.path.join(REPO, "benchmark/configs/mobilenet_v3_large.json")) as f:
        config = json.load(f)
    config["name"] = "mbv3l_copy"
    (root / "benchmark/configs/mbv3l_copy.json").write_text(json.dumps(config))
    traffic = {"runner": "serve_loop", "loop": "open", "rate_per_s": 40, "arrivals": "poisson", "pool": 8,
               "warm_s": 0.2, "check_rows": 4, "trace_for_s": 0.3,
               # two resolutions mixed in one queue, both on the engine's warm ladder
               "image_sizes": [32, 24], "serve_overrides": {"serve.image_sizes": [24, 32]}}
    (root / "benchmark/traffic/open40_new.json").write_text(json.dumps({**traffic, "rehearsal": {}}))
    (root / "benchmark/layer_metrics/engine.h2d_mb.serve.json").write_text(json.dumps(
        {"reader": "registry_delta", "counter": "serve.h2d_bytes", "scale": 1e-6}))
    m["configs"].append({"name": "mbv3l_copy", "source": "test", "file": "benchmark/configs/mbv3l_copy.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "new_cell", "config": "mbv3l_copy", "traffic": "open40_new", "chips": 1,
                           "why": "test"})
    have = {e["name"]: e for e in m["end_to_end"]}
    for name, unit, better in (("serve_images_per_s", "images/s", "higher"), ("serve_p95_ms", "ms", "lower")):
        if name in have:  # a metric that is there gains the cell; one that is not is a new entry
            have[name]["workloads"].append("new_cell")
        else:
            m["end_to_end"].append({"name": name, "unit": unit, "better": better, "bound": 0.05,
                                    "source": "host_clock", "workloads": ["new_cell"]})
    m["per_layer"].append({"name": "engine.h2d_mb.serve", "unit": "MB", "better": "lower",
                           "source": "program_counter", "layer": "serving engine",
                           "moves": "serve_images_per_s", "workloads": ["new_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    proc = run_cell(str(root), "--workload", "new_cell", "--seed", "7", "--seconds", "1.5",
                    "--trace", "1", "--rehearsal")
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = json_lines(proc.stdout)
    last = lines[-1]
    assert set(last) == RESULT_KEYS and last["correct"] is True
    assert last["metrics"]["engine.h2d_mb.serve"]["value"] > 0  # the new file's metric, read from the registry
    assert "entry.cache_misses" in last["metrics"]  # a metric of every cell
    assert last["attempted"] == pytest.approx(60, abs=8) and last["failed"] == 0  # 40/s for 1.5 s
    run = next(ln["run"] for ln in lines if "run" in ln)
    assert run["loop"] == "open" and run["late_p95_ms"] is not None and run["image_sizes"] == [32, 24]
    assert run["reference"]["ok"] and run["reference"]["max_abs_logit"] > reference.MIN_MAX_ABS_LOGIT
    assert next(ln for ln in lines if "setup_phases" in ln)["heavy_imports"] == []
    after = {p: os.path.getmtime(os.path.join(dp, p)) for dp, _, fs in os.walk(root / "benchmark") for p in fs
             if "__pycache__" not in dp}
    assert {p: t for p, t in after.items() if p in before} == before  # no existing file was edited


# -- the yardstick -----------------------------------------------------------


@pytest.mark.parametrize("config_name", ["mobilenet_v3_large", "efficientnet_b0"])
def test_macs_from_shapes_equal_the_programs_profiler(config_name):
    from yet_another_mobilenet_series_tpu.config import load_config
    from yet_another_mobilenet_series_tpu.models import get_model
    from yet_another_mobilenet_series_tpu.models.serialize import network_to_dict
    from yet_another_mobilenet_series_tpu.utils.profiling import profile_network

    with open(os.path.join(REPO, "benchmark", "configs", config_name + ".json")) as f:
        config = json.load(f)
    net = get_model(load_config(os.path.join(REPO, config["train_app"])).model, config["image_size"])
    ours = macs.forward_macs(network_to_dict(net), config["image_size"])
    assert ours == profile_network(net, config["image_size"]).total_macs == config["macs_per_image"]


def test_trace_reduction_on_the_recorded_v5e_trace():
    """Three toy train steps recorded on a TPU v5 lite by PR 23's builder
    (per-op statistics and the HLO metadata plane stripped: 357 KB)."""
    t = trace_reduce.load(FIXTURE)
    assert list(t.devices) == [0] and len(t.devices[0]["XLA Ops"]) == 1944
    lo, hi = t.window  # no traced_window span in this recording: first to last op
    assert (lo, hi) == (46379690.0, 50120718.0)
    busy, window = trace_reduce.device_busy(t)
    assert busy == pytest.approx(353.948e-6, rel=1e-9) and window == pytest.approx(3741.028e-6, rel=1e-9)
    assert trace_reduce.idle_share_pct(t) == pytest.approx(100 * (1 - 353.948 / 3741.028), rel=1e-9)
    assert trace_reduce.module_median_ms(t) == pytest.approx(0.143533, rel=1e-6)  # the one whole execution
    assert trace_reduce.collective_ms_per_step(t) == 0.0  # one chip: no collective in the step
    b = trace_reduce.breakdown(t)
    assert b["device_ops"][0] == ["kind:multiply_add_fusion", pytest.approx(114.975e-6)]
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 5
    assert b["idle_gaps"][0] == ["dispatch_train_step", pytest.approx(1932.37e-6)]


def test_trace_reduction_on_hand_made_events():
    ops = [("%fusion.1 = f32[8]{0} fusion(...)", 0.0, 100.0),
           ("%all-reduce-start.3 = ...", 100.0, 5.0),       # async half: not occupancy of the core
           ("%fusion.2 = ...", 150.0, 100.0),
           ("%fusion.3 = ...", 200.0, 100.0),               # overlaps fusion.2: the union counts it once
           ("%all-reduce-done.3 = ...", 300.0, 40.0),       # the wait: exposed collective time
           ("%all-reduce.7 = ...", 400.0, 60.0),
           ("%reduce-scatter.1 = ...", 900.0, 50.0)]
    modules = [("jit_step(1)", 0.0, 500.0), ("jit_step(1)", 500.0, 450.0)]
    spans = [(trace_reduce.WINDOW_SPAN, 0.0, 1000.0), ("bench/sync", 455.0, 400.0), ("bench/dispatch", 110.0, 30.0)]
    t = trace_reduce.Trace(devices={0: {"XLA Ops": ops, "XLA Modules": modules}}, host_spans=spans)
    assert trace_reduce.op_kind(ops[0][0]) == "fusion" and trace_reduce.op_name(ops[4][0]) == "all-reduce-done.3"
    # busy: [0,105) + [150,340) + [400,460) + [900,950) = 105 + 190 + 60 + 50
    assert trace_reduce.device_busy(t) == (pytest.approx(405e-9), pytest.approx(1000e-9))
    assert trace_reduce.idle_share_pct(t) == pytest.approx(59.5)
    assert trace_reduce.module_median_ms(t) == pytest.approx(475e-6)
    assert trace_reduce.collective_ms_per_step(t) == pytest.approx((40 + 60 + 50) / 2 / 1e6)
    gaps = trace_reduce.idle_gaps(t, n=2)
    assert gaps[0] == ["sync", pytest.approx(440e-9)] and gaps[1] == [trace_reduce.NO_SPAN, pytest.approx(60e-9)]
    assert ["kind:all-reduce-start", pytest.approx(5e-9)] not in trace_reduce.top_ops(t)


class FakeEngine:
    """submit() -> Future, resolved `service_s` later by one worker thread,
    in order; `stall_at`/`stall_s` freeze that worker once; `refuse_every`
    makes submit raise."""

    def __init__(self, service_s=0.002, stall_at=None, stall_s=0.0, fail_every=0, refuse_every=0):
        self.service_s, self.stall_at, self.stall_s = service_s, stall_at, stall_s
        self.fail_every, self.refuse_every = fail_every, refuse_every
        self.n = 0
        self._q: list = []
        self._cv = threading.Condition()
        threading.Thread(target=self._work, daemon=True).start()

    def submit(self, image):
        self.n += 1
        if self.refuse_every and self.n % self.refuse_every == 0:
            raise RuntimeError("queue full")
        fut = Future()
        with self._cv:
            self._q.append((self.n, fut))
            self._cv.notify()
        return fut

    def _work(self):
        while True:
            with self._cv:
                while not self._q:
                    self._cv.wait()
                i, fut = self._q.pop(0)
            time.sleep(self.stall_s if i == self.stall_at else self.service_s)
            if self.fail_every and i % self.fail_every == 0:
                fut.set_exception(RuntimeError("engine failed"))
            else:
                fut.set_result(np.zeros(3))


def test_open_loop_latency_runs_from_the_due_time():
    """A stall delays later requests; their latency must show it even though
    each was answered quickly once the engine got to it."""
    images = [np.zeros((2, 2, 3), np.float32)] * 4
    engine = FakeEngine(service_s=0.001, stall_at=10, stall_s=0.3)
    gen = loadgen.LoadGen(engine.submit, images, {"loop": "open", "rate_per_s": 100, "arrivals": "uniform"},
                          seed=1, duration_s=1.0)
    gen.start()
    records = gen.join()
    w = records.window(0.0, 2.0)
    assert w["attempted"] == 100 and w["failed"] == 0
    lat = np.asarray(records.done) - np.asarray(records.due)
    assert lat[:8].max() < 0.05             # before the stall: quick
    assert lat[10] > 0.28                   # the stalled request
    assert lat[12] > 0.25 and lat[20] > 0.15  # behind it: charged from when they were DUE
    assert w["p95_ms"] > 200 and w["late_p95_ms"] < 20  # and the generator itself was on time


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_generators_count_attempted_and_failed(loop):
    images = [np.zeros((2, 2, 3), np.float32)] * 4
    engine = FakeEngine(service_s=0.001, fail_every=5, refuse_every=7)
    params = {"loop": loop, "clients": 4, "rate_per_s": 200, "arrivals": "poisson"}
    gen = loadgen.LoadGen(engine.submit, images, params, seed=3, duration_s=0.5)
    gen.start()
    records = gen.join()
    w = records.window(0.0, 5.0)
    n = len(records.due)
    refused = n // 7
    failed_by_engine = sum(1 for i in range(1, n + 1) if i % 7 and i % 5 == 0)
    assert w["attempted"] == n and w["failed"] == refused + failed_by_engine
    assert w["completed"] == n - w["failed"] and all(d is not None for d in records.done)
    if loop == "open":
        assert n == 100  # rate x duration, whatever the seed
    half = records.window(0.0, 0.25)  # a window counts only what resolved inside it
    assert 0 < half["attempted"] < n


def test_every_seed_gets_the_same_arrivals_in_another_order():
    a = loadgen.arrival_times(1200, 2.0, seed=1)
    b = loadgen.arrival_times(1200, 2.0, seed=2**31 + 5)
    assert len(a) == len(b) == 2400 and 0 <= a.min() and a.max() < 2.0
    gaps = lambda t: np.sort(np.diff(np.append(t, 2.0)))  # noqa: E731
    assert np.allclose(gaps(a), gaps(b)) and not np.allclose(a, b)
    bursty = loadgen.arrival_times(100, 4.0, seed=1, burst_on_s=0.5, burst_off_s=0.5)
    assert len(bursty) == 400 and np.all((bursty % 1.0) < 0.5)  # nothing is due in an off-stretch
    order = loadgen.image_order(8, 64, seed=3)
    assert np.array_equal(np.bincount(order), np.full(8, 8))  # each image as often as any other


def test_reference_agrees_with_the_program_at_a_toy_size_and_catches_bf16(tmp_path):
    """The plain float32 reference against the program's folded forward on the
    CPU, where both are true float32; a bf16 forward must NOT pass."""
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from benchmark.runners import serve_loop
    from yet_another_mobilenet_series_tpu.serve.export import apply_folded, export_bundle, load_bundle

    with open(os.path.join(REPO, "benchmark/configs/efficientnet_b0.json")) as f:
        config = harness.with_rehearsal(json.load(f), True)
    model_cfg = harness.load_app_config(config["train_app"], config["overrides"]).model
    net, make = serve_loop.seeded_serving_weights(model_cfg, config["image_size"], calib_rows=16)
    params, state = jax.device_get(make(harness.seed_key(2**31 + 3)))
    bundle_dir = export_bundle(net, params, state, str(tmp_path / "bundle"))
    spec, weights = reference.load_bundle_files(bundle_dir)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3)))
    ref = np.asarray(reference.forward(spec, weights, x))
    bundle = load_bundle(bundle_dir)
    got = np.asarray(apply_folded(bundle.net, bundle.params, x))
    verdict = reference.compare(got, ref)
    assert verdict["ok"] and verdict["rel_to_max_logit"] < 1e-4, verdict
    assert np.abs(ref[0] - ref[1]).max() > 0.05 * verdict["max_abs_logit"]  # the image matters
    wrong_rows = reference.compare(got[::-1], ref)
    assert not wrong_rows["ok"], wrong_rows  # another image's answer is not within tolerance
    assert not reference.compare(np.zeros_like(ref), ref)["ok"]
    assert not reference.compare(got * 1e-3, ref * 1e-3)["ok"]  # a network that answers ~0 proves nothing


def test_spread_is_the_interquartile_distance_over_the_median():
    """prove.py's spread is the contract's: statistics.quantiles(n=4), not numpy's."""
    import statistics

    from benchmark import prove

    values = [7481.3, 7482.5, 7481.9, 7482.1, 7481.7, 7482.4]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert prove.spread(values) == pytest.approx((q3 - q1) / statistics.median(values))
    assert prove.spread(values) > (np.percentile(values, 75) - np.percentile(values, 25)) / np.median(values)
