"""What the chip path promises, checked where a CPU can check it (PR 22):

- chip_smoke.py refuses a host without a TPU, and its explicit rehearsal
  drives the whole train -> export -> serve -> listen path green at toy size;
- the persistent compile cache is placed in exactly one way
  (utils/compile_cache.py);
- one process per chip: the fleet supervisor reaches its first spawn without
  a JAX backend, and refuses more replicas than the host has chips;
- nothing passes without the device: cli.serve fails when requests failed
  (that the benchmark without a chip exits 3 and an unknown device has no
  peak is tests/benchmark_tests/'s).

The file sorts last on purpose: the tier-1 gate counts passing dots inside a
time limit the suite already strains, so tests added with PR 22 run after
every test that was there before it and cannot push one off the end.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**over):
    """The session's environment (JAX_PLATFORMS=cpu from conftest), one host
    device: these subprocesses need no mesh, and compile faster without."""
    env = dict(os.environ, PYTHONPATH=REPO)
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    for k, v in over.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return env


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO, path))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses resolve annotations through it
    spec.loader.exec_module(mod)
    return mod


_CACHE_PROBE = (
    "import os, jax\n"
    "from yet_another_mobilenet_series_tpu.utils import compile_cache\n"
    "print(repr((compile_cache.configure(), jax.config.jax_compilation_cache_dir,"
    " jax.config.jax_persistent_cache_min_compile_time_secs)))\n"
)

_SUPERVISOR_PROBE = """
import json, sys
from yet_another_mobilenet_series_tpu.cli import fleet

seen = {}

def first_spawn(self, slot):
    seen["jax_imported"] = "jax" in sys.modules
    xb = sys.modules.get("jax._src.xla_bridge")
    seen["backend_initialised"] = bool(xb and xb.backends_are_initialized())
    raise fleet.FleetSpawnError("probe: stop at the first spawn")

fleet.FleetSupervisor._spawn_real = first_spawn
try:
    fleet.main(["serve.bundle=/nonexistent", "serve.fleet.replicas=1",
                "serve.fleet.obs.flight_recorder=false", "train.log_dir=" + sys.argv[1]])
except fleet.FleetSpawnError:
    pass
print(json.dumps(seen))
"""


@pytest.fixture(scope="module")
def probes(tmp_path_factory):
    """Every subprocess this module needs, started at once: they import jax
    for a couple of seconds each, and run while the in-process rehearsal
    below (the module's first test) compiles. name -> (rc, stdout, stderr)."""
    tmp = tmp_path_factory.mktemp("probes")
    (tmp / "elsewhere").mkdir()
    no_cache = dict(JAX_COMPILATION_CACHE_DIR=None)
    off_cpu = dict(JAX_PLATFORMS=None)  # config only: no backend is ever started
    py = sys.executable
    cmds = {
        "chip_smoke": ([py, os.path.join(REPO, "chip_smoke.py")], _env(), REPO),
        "supervisor": ([py, "-c", _SUPERVISOR_PROBE, str(tmp)], _env(), "/"),
        "cache_a": ([py, "-c", _CACHE_PROBE], _env(**off_cpu, **no_cache), "/"),
        "cache_b": ([py, "-c", _CACHE_PROBE], _env(**off_cpu, **no_cache), str(tmp / "elsewhere")),
        "cache_env": ([py, "-c", _CACHE_PROBE],
                      _env(**off_cpu, JAX_COMPILATION_CACHE_DIR=str(tmp / "mine")), "/"),
        "cache_cpu": ([py, "-c", _CACHE_PROBE], _env(**no_cache), "/"),
    }
    procs = {k: subprocess.Popen(cmd, env=env, cwd=cwd, stdout=subprocess.PIPE,
                                 stderr=subprocess.PIPE, text=True)
             for k, (cmd, env, cwd) in cmds.items()}
    done = {}

    def result(name):
        if name not in done:
            out, err = procs[name].communicate(timeout=180)
            done[name] = (procs[name].returncode, out, err)
        return done[name]

    yield result
    for p in procs.values():
        if p.poll() is None:
            p.kill()
            p.wait()


# -- chip_smoke.py -----------------------------------------------------------


def test_chip_smoke_rehearsal_runs_the_whole_path_green(probes, tmp_path, capsys):
    """In this process (jax and TensorFlow are already loaded: a subprocess
    would spend half its time importing them), so the rehearsal trains on
    the session's eight host devices and serves on one."""
    import jax

    from yet_another_mobilenet_series_tpu.obs.registry import get_registry

    # xdist hands this worker other files first, and a process that served
    # before comes here with its process-wide counters already moved: three
    # earlier drain timeouts were read as this run's and failed the driver's
    # run of PR 24's tree. The smoke's counts are its own run's, whatever the
    # process did before.
    get_registry().counter("serve.drain_timeouts").inc(3)
    smoke = _load("chip_smoke.py", "chip_smoke")
    out = tmp_path / "report.json"
    assert smoke.main(["--rehearsal", "--out", str(out)]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": jax.device_count()}}
    assert any("REHEARSAL" in l for l in lines)  # it says what it is
    report = json.loads(out.read_text())
    # every main-path phase ran and is reported; the kernel phase is the one
    # a CPU cannot run, and the rehearsal says so instead of interpreting it
    assert {"train", "export", "serve", "reference"} <= set(report) and "kernel" not in report
    assert any("kernel: NOT RUN" in l for l in lines)
    train = report["train"]
    assert train["step_compiles"] == 1 and train["compiles_in_steady_windows"] == 0
    assert set(train["batch_rows_per_device"].values()) == {4.0}  # on every device
    assert len(train["batch_rows_per_device"]) == jax.device_count()
    assert report["serve"]["completed"] == report["serve"]["requests"]
    assert report["serve"]["obs_compiles_after"] == report["serve"]["obs_compiles_warmup"] == 5
    assert report["reference"]["max_abs_diff"] <= 1e-3 * report["reference"]["max_abs_logit"]
    # held to the CPU, the run left no compile cache in the checkout
    assert report["compile_cache"]["enabled"] is False


def test_chip_smoke_refuses_a_host_without_a_tpu(probes):
    rc, out, err = probes("chip_smoke")
    assert rc != 0
    assert "no TPU" in err
    assert '"ok"' not in out  # no result line at all


# -- the compile cache -------------------------------------------------------


def test_compile_cache_is_placed_one_way(probes):
    """No variable: the same in-checkout directory from two processes with
    different cwd (and pid). JAX_COMPILATION_CACHE_DIR set: that directory,
    chosen by JAX itself. Held to the CPU: no cache. configure() is config
    only, so none of this needs (or starts) a backend."""
    got = {}
    for k in ("cache_a", "cache_b", "cache_env", "cache_cpu"):
        rc, out, err = probes(k)
        assert rc == 0, err[-1000:]
        got[k] = eval(out.strip().splitlines()[-1])  # noqa: S307 — our own repr
    fixed = os.path.join(REPO, ".jax_cache")
    assert got["cache_a"] == got["cache_b"] == (fixed, fixed, 0.0)
    env_dir = got["cache_env"][0]  # JAX read the variable; code set nothing else
    assert env_dir.endswith("/mine") and got["cache_env"] == (env_dir, env_dir, 0.0)
    assert got["cache_cpu"][:2] == (None, None)
    # set in exactly one place (the code git tracks, not stray copies)
    code = [os.path.join(REPO, d) for d in (
        "yet_another_mobilenet_series_tpu", "scripts", "chip_smoke.py",
        "__graft_entry__.py")]
    hits = subprocess.run(
        ["grep", "-rlE", r"jax_compilation_cache_dir\"|compilation_cache\.set_cache_dir",
         "--include=*.py", *code], capture_output=True, text=True).stdout.split()
    assert [os.path.relpath(h, REPO) for h in hits] == [
        "yet_another_mobilenet_series_tpu/utils/compile_cache.py"]


# -- one process per chip ----------------------------------------------------


def test_fleet_supervisor_reaches_its_first_spawn_without_a_jax_backend(probes):
    rc, out, err = probes("supervisor")
    assert rc == 0, err[-2000:]
    seen = json.loads(out.strip().splitlines()[-1])
    # stronger than "no backend": the supervisor never even imports jax
    assert seen == {"jax_imported": False, "backend_initialised": False}


def test_fleet_refuses_more_replicas_than_chips(tmp_path):
    from yet_another_mobilenet_series_tpu.cli import fleet

    class _Up:
        pid, addr = 1, {"host": "127.0.0.1", "port": 1}

        def alive(self):
            return True

        def drain(self, timeout_s):
            return True

    spawned = []

    def spawn(slot):
        spawned.append(slot)
        return _Up()

    assert fleet.host_tpu_chips() == 0  # this session is held to the CPU
    env = fleet.one_chip_env(2, base={"PATH": "/bin"})
    assert env["TPU_VISIBLE_CHIPS"] == "2" and env["TPU_PROCESS_BOUNDS"] == "1,1,1"

    too_many = fleet.FleetSupervisor(replica_argv=[], log_dir=str(tmp_path), replicas=2,
                                     chips=1, spawn_fn=spawn)
    with pytest.raises(fleet.FleetSpawnError, match="belongs to one process"):
        too_many.start()
    assert spawned == []  # refused at start-up, before anything was spawned

    fits = fleet.FleetSupervisor(replica_argv=[], log_dir=str(tmp_path), replicas=1,
                                 chips=1, spawn_fn=spawn).start()
    try:
        with pytest.raises(fleet.FleetSpawnError, match="1 TPU chip"):
            fits.scale_to(2)
        assert fits.n_replicas == 1
    finally:
        fits.stop()
    # a CPU host (chips=0) places any number
    fleet.FleetSupervisor(replica_argv=[], log_dir=str(tmp_path), replicas=5, chips=0,
                          spawn_fn=spawn).check_placeable(64)


# -- nothing passes without the device ---------------------------------------


def test_cli_serve_fails_when_the_engine_raises_on_every_request(tmp_path, monkeypatch):
    """A load in which every request failed on the engine used to count them
    as 'shed' and exit 0; it must fail, with the counts kept. The engine is
    a stub that raises (no bundle, no compile): the accounting is the test."""
    import numpy as np

    from yet_another_mobilenet_series_tpu.cli import serve as cli_serve

    class _BrokenEngine:
        quant_mode = "stub"
        wire_np_dtype = np.float32

        def __init__(self, bundle, **kw):
            pass

        def predict(self, x):
            raise RuntimeError("device fell over")

    monkeypatch.setattr(cli_serve, "load_bundle", lambda d: None)
    monkeypatch.setattr(cli_serve, "InferenceEngine", _BrokenEngine)
    with pytest.raises(cli_serve.LoadFailed) as exc:
        cli_serve.main(["serve.bundle=/stub", "data.image_size=8", "serve.warmup=false",
                        "serve.pipelined=false", "serve.requests=12", "serve.clients=3",
                        f"train.log_dir={tmp_path}"])
    s = exc.value.summary
    assert s["failed"] == 12 and s["completed"] == 0 and s["shed"] == 0
    assert s["first_failure"] == "RuntimeError: device fell over"
    # the post-mortem still landed (the healthy path, counts intact, is
    # tests/test_serve.py's round trip)
    assert os.path.exists(tmp_path / "obs_registry.json")
