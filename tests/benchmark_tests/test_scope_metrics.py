"""The layer metrics that read the program's own names (CPU only, nothing
timed): `layer_metrics/step_scopes_train.py` on hand-made device events and a
hand-made scope table (whole steps only, device 0, the unresolved share, None
without a device plane or without the program's scopes), the two `entry.*`
metrics of the program's compile watch, and every new `layer_metrics/*.json`
against its `per_layer` entry and its reader.
"""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import readers, trace_reduce  # noqa: E402
from benchmark.layer_metrics import step_scopes_train as sst  # noqa: E402

LAYER_DIR = os.path.join(REPO, "benchmark", "layer_metrics")
SCOPE_METRICS = sorted([*sst.METRICS, sst.UNSCOPED_SHARE])
ENTRY_METRICS = ["entry.program_compile_s", "entry.program_compiles_in_window"]
TRAIN_CELLS = ["mbv3l_train_b512", "effnetb0_train_b512", "effnetb0_train_dp4"]

# the step's compiled text, as far as the table needs it: two BN fusions, a
# depthwise and a 1x1 conv in both passes, the update, and a compiler-made
# copy that carries no name
HLO = """
ENTRY %main () -> f32[] {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kInput, calls=%fc.1, metadata={op_name="jit(shard_fn)/jvp(bn_stats)/reduce_sum"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.2, metadata={op_name="jit(shard_fn)/jvp(bn_apply)/mul"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kInput, calls=%fc.3, metadata={op_name="jit(shard_fn)/transpose(jvp(bn_apply))/reduce_sum"}
  %convolution.4 = f32[8]{0} convolution(%a, %b), metadata={op_name="jit(shard_fn)/jvp(conv_dw)/conv_general_dilated"}
  %fusion.5 = f32[8]{0} fusion(%a), kind=kOutput, calls=%fc.5, metadata={op_name="jit(shard_fn)/transpose(jvp(conv_dw))/conv_general_dilated"}
  %convolution.6 = f32[8]{0} convolution(%a, %b), metadata={op_name="jit(shard_fn)/jvp(conv_pw)/conv_general_dilated"}
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.7, metadata={op_name="jit(shard_fn)/optim/mul"}
  %fusion.8 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.8, metadata={op_name="jit(shard_fn)/ema/add"}
  %fusion.9 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.9, metadata={op_name="jit(shard_fn)/jvp(act)/max"}
  %copy-start.10 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%a)
  %copy-done.10 = f32[8]{0} copy-done(%copy-start.10)
}
"""
# one step's ops: (instruction, offset in the step, duration), ns
STEP = [("fusion.1", 0, 100), ("fusion.2", 100, 50), ("convolution.4", 150, 40), ("convolution.6", 190, 60),
        ("fusion.9", 250, 10), ("copy-start.10", 250, 500), ("copy-done.10", 260, 20), ("fusion.3", 280, 200),
        ("fusion.5", 480, 120), ("fusion.7", 600, 30), ("fusion.8", 630, 20)]
STEP_NS = 700.0


def hand_made_trace(steps_at=(1000.0, 2000.0, 3000.0), window=(1500.0, 4000.0)) -> trace_reduce.Trace:
    """Three executions of the step on device 0, the first cut by the traced
    window's start; device 1 carries other work that must not be counted."""
    ops, modules = [], []
    for t0 in steps_at:
        modules.append(("jit_shard_fn(1)", t0, STEP_NS))
        ops += [(f"%{name} = f32[8]{{0}} op(f32[8]{{0}} %a)", t0 + off, float(dur)) for name, off, dur in STEP]
    other = {"XLA Ops": [("%fusion.1 = f32[8]{0} fusion()", 2000.0, 9999.0)],
             "XLA Modules": [("jit_shard_fn(1)", 2000.0, 9999.0)]}
    return trace_reduce.Trace(devices={0: {"XLA Ops": ops, "XLA Modules": modules}, 1: other},
                              host_spans=[(trace_reduce.WINDOW_SPAN, window[0], window[1] - window[0])])


def fake_ctx(trace, monkeypatch, hlo=HLO):
    monkeypatch.setattr(sst, "compiled_step_text", lambda ctx: hlo)
    return types.SimpleNamespace(trace=trace, registry_before={}, registry_after={})


def test_step_ops_takes_whole_steps_of_device_0_only():
    ops, n_steps, busy_s = sst.step_ops(hand_made_trace())
    assert n_steps == 2  # the execution the window cuts is left out
    assert len(ops) == 2 * (len(STEP) - 1)  # the async copy-start window is not occupancy
    assert ("fusion.3", 200.0) in ops and not any(name == "copy-start.10" for name, _ in ops)
    assert busy_s == pytest.approx(2 * 650e-9)
    no_whole_step = hand_made_trace(steps_at=(1000.0,), window=(1500.0, 1600.0))
    assert sst.step_ops(no_whole_step) is None


def test_scope_metrics_on_hand_made_events(monkeypatch, capsys):
    ctx = fake_ctx(hand_made_trace(), monkeypatch)
    got = {name: sst.metric(ctx, name) for name in SCOPE_METRICS}
    assert got == {
        "step.bn_fwd_ms.train": pytest.approx(150e-6),       # fusion.1 + fusion.2
        "step.bn_bwd_ms.train": pytest.approx(200e-6),       # fusion.3
        "step.conv_dw_ms.train": pytest.approx(160e-6),      # convolution.4 + fusion.5, both passes
        "step.conv_mxu_ms.train": pytest.approx(60e-6),      # convolution.6
        "step.update_ms.train": pytest.approx(50e-6),        # optim + ema
        "step.unscoped_share.train": pytest.approx(100 * 20 / 650),  # copy-done.10 has no name
    }
    # computed once a run: one commentary line, with every scope x phase and what it adds up to
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert len(lines) == 1
    table = lines[0]["step_scopes"]
    assert table["whole_steps"] == 2 and table["device"] == 0
    assert table["ms_per_step"]["bn_apply.bwd"] == pytest.approx(200e-6)
    assert table["ms_per_step"]["act.fwd"] == pytest.approx(10e-6)
    assert sum(table["ms_per_step"].values()) == pytest.approx(table["op_ms_per_step"]) == pytest.approx(650e-6)
    assert table["busy_ms_per_step"] == pytest.approx(650e-6)
    assert sum(table["share_pct"].values()) == pytest.approx(100.0)
    # no fusion of this hand-made step holds another scope's reductions: containing == own
    assert table["containing_ms_per_step"]["bn_apply"] == pytest.approx(250e-6)
    assert sum(table["containing_ms_per_step"].values()) == pytest.approx(650e-6)


def test_an_executable_without_the_names_reads_as_unscoped_not_as_zero(monkeypatch, capsys):
    """The stale case: the compile cache handed back a program compiled
    before the scopes (no op_name resolves). The ms metrics read 0 and the
    unresolved share says why."""
    bare = "\n".join(ln.split(", metadata=")[0] for ln in HLO.splitlines())
    ctx = fake_ctx(hand_made_trace(), monkeypatch, hlo=bare)
    assert sst.metric(ctx, sst.UNSCOPED_SHARE) == pytest.approx(100.0)
    assert all(sst.metric(ctx, name) == 0 for name in sst.METRICS)
    capsys.readouterr()


def test_nothing_to_read_is_none_and_never_raises(monkeypatch, capsys):
    no_device_plane = fake_ctx(trace_reduce.Trace(), monkeypatch)  # a CPU rehearsal
    not_traced = fake_ctx(None, monkeypatch)
    for ctx in (no_device_plane, not_traced):
        assert all(sst.metric(ctx, name) is None for name in SCOPE_METRICS)
    # a program from before obs/scopes.py (the PR's parent commit): the import fails, the reader does not
    import yet_another_mobilenet_series_tpu.obs.scopes  # noqa: F401 — so that there is an attribute to take away

    monkeypatch.delattr(sys.modules["yet_another_mobilenet_series_tpu.obs"], "scopes")
    monkeypatch.setitem(sys.modules, "yet_another_mobilenet_series_tpu.obs.scopes", None)
    assert sst.metric(fake_ctx(hand_made_trace(), monkeypatch), sst.UNSCOPED_SHARE) is None
    assert capsys.readouterr().out == ""


def test_entry_metrics_read_the_programs_compile_watch():
    before = {"jax.backend_compile_seconds.sum": 12.5, "jax.backend_compile_seconds.count": 7.0,
              "jax.backend_compiles": 7.0}
    ctx = types.SimpleNamespace(registry_before=before, registry_after=dict(before))
    values = readers.read_all(ctx, [{"name": n} for n in ENTRY_METRICS])
    assert values == {"entry.program_compile_s": 12.5, "entry.program_compiles_in_window": 0.0}
    ctx.registry_after["jax.backend_compiles"] = 8.0  # a recompile inside the window is a count
    assert readers.read_all(ctx, [{"name": ENTRY_METRICS[1]}]) == {ENTRY_METRICS[1]: 1.0}
    # a program without the watch: nothing in the registry, nothing on the line
    empty = types.SimpleNamespace(registry_before={}, registry_after={})
    assert readers.read_all(empty, [{"name": n} for n in ENTRY_METRICS]) == dict.fromkeys(ENTRY_METRICS)


@pytest.mark.parametrize("name", SCOPE_METRICS + ENTRY_METRICS)
def test_each_new_metric_has_its_entry_its_file_and_its_reader(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    with open(os.path.join(LAYER_DIR, name + ".json")) as f:
        how = json.load(f)
    assert how["reader"] in readers.READERS
    if how["reader"] == "python":
        assert os.path.exists(os.path.join(LAYER_DIR, how["module"] + ".py"))
    if name.startswith("step."):
        assert entry["layer"] == "compiled train step" and entry["source"] == "device_trace"
        assert entry["moves"] == "train_images_per_s_per_chip" and entry["workloads"] == TRAIN_CELLS
        # each metric's own module asks the shared one for exactly its name
        seen = []
        ctx = types.SimpleNamespace(trace=None)
        orig, sst.metric = sst.metric, lambda c, n: seen.append(n)
        try:
            readers.python(ctx, how["module"])
        finally:
            sst.metric = orig
        assert seen == [name]
    else:
        assert entry["layer"] == "entry points and compile cache" and entry["source"] == "program_counter"
        assert entry["moves"] == "setup_s" and "workloads" not in entry
