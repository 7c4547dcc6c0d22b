"""scripts/trace_ops.py on a tiny checked-in xplane fixture (network-free):
the aggregation functions the profiler-capture endpoints feed, previously
untested — including the jaxlib-0.4.36 regression where the CPU-client
thunk line was named ``tf_XLATfrtCpuClient`` and the exact-name match
aggregated zero events."""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE_DIR = os.path.join(REPO, "tests", "fixtures", "xplane")

pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2",
                    reason="xplane proto unavailable")


def _mod():
    spec = importlib.util.spec_from_file_location(
        "trace_ops", os.path.join(REPO, "scripts", "trace_ops.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_op_kind_collapse():
    m = _mod()
    assert m.op_kind("fusion.123") == "fusion"
    assert m.op_kind("%dot.2") == "dot"
    assert m.op_kind("all-reduce-start") == "all-reduce-start"
    assert m.op_kind("tanh") == "tanh"


def test_fixture_host_aggregation_sees_cpu_client_thunks():
    """The fixture traces a jitted tanh(x @ x) on CPU: the host fallback must
    find the dot + tanh thunk events on the tf_XLATfrtCpuClient line (the
    old XLAEigen/PjRtCpuClient exact match returned zero events here)."""
    m = _mod()
    xs, path = m.load_xspace(FIXTURE_DIR)
    assert path.endswith("vm.xplane.pb")
    host = m.aggregate_host(xs)
    assert host["n_events"] > 0, "CPU-client thunk line not matched"
    kinds = set(host["per_cat"])
    assert "dot" in kinds and "tanh" in kinds
    assert host["total_ps"] == sum(host["per_cat"].values()) > 0
    # the fixture has no device plane — the TPU aggregator must say so, not
    # fabricate one
    assert not any(p.name.startswith("/device:TPU") for p in xs.planes)


def test_load_xspace_missing_dir():
    m = _mod()
    with pytest.raises(FileNotFoundError, match="no .xplane.pb"):
        m.load_xspace("/definitely/not/a/dir")


def test_main_renders_fallback_and_table_check(tmp_path, capsys):
    """End-to-end CLI pass over the fixture, including the latency-table
    cross-check (table total + trace total + the provenance warning)."""
    m = _mod()
    table = tmp_path / "LATENCY_TABLE_t.json"
    table.write_text(json.dumps({
        "entries": [
            {"key": "a", "alive_channels": [4, 8], "latency_s": [1e-4, 2e-4]},
            {"key": "b", "alive_channels": [8, 16], "latency_s": [3e-4, 5e-4]},
        ],
        "provenance": {"device_kind": "cpu", "cpu_rehearsal": True},
    }))
    rc = m.main([FIXTURE_DIR, "5", "--check-table", str(table)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "no /device:TPU plane" in out
    assert "/host:CPU" in out and "dot" in out
    assert "latency-table cross-check" in out
    # predicted total = sum of full-width points = 0.2 + 0.5 ms
    assert "0.700 ms/image" in out
    assert "cpu_rehearsal=True" in out


def test_table_prediction_full_width_points(tmp_path):
    m = _mod()
    table = tmp_path / "t.json"
    # unsorted ladder: the full-width point is the LARGEST channels entry,
    # not the last list element
    table.write_text(json.dumps({"entries": [
        {"key": "a", "alive_channels": [8, 4], "latency_s": [2e-4, 1e-4]}]}))
    pred = m.table_prediction(str(table))
    assert pred["entries"] == 1
    assert pred["blocks_total_ms"] == pytest.approx(0.2)


# -- by the program's own names (obs/scopes.py, obs/trace.py) -----------------

TPU_FIXTURE = os.path.join(REPO, "benchmark", "fixtures", "toy_train_v5e_1chip.xplane.pb")


@pytest.mark.parametrize("event_name, instruction, nbytes", [
    ("%convert_reduce_fusion.15 = f32[64]{0:T(128)} fusion(bf16[8,4,4,64]{3,2,1,0} %p), kind=kInput",
     "convert_reduce_fusion.15", 64 * 4 + 8 * 4 * 4 * 64 * 2),
    ("%copy-done.2 = bf16[512,14,14,480]{0,3,2,1:T(8,128)(2,1)S(1)} copy-done(%copy-start.2)",
     "copy-done.2", 512 * 14 * 14 * 480 * 2),
    ("%add.3 = s32[]{:T(128)} add(s32[] %a, s32[] %b)", "add.3", 12),  # a scalar is one element
    ("dot.2", "dot.2", 0),  # a CPU thunk's bare name: nothing to count
])
def test_instruction_name_and_shape_bytes(event_name, instruction, nbytes):
    m = _mod()
    assert m.instruction_name(event_name) == instruction
    assert m.shape_bytes(event_name) == nbytes


def test_idle_gaps_are_labelled_with_the_programs_spans():
    """The longest stretches with no op on the device, each with the host
    spans open at its middle, whichever thread they are on."""
    m = _mod()
    ops = [("%fusion.1 = f32[8]{0} fusion()", 0, 100), ("%fusion.2 = f32[8]{0} fusion()", 50, 100),
           ("%fusion.3 = f32[8]{0} fusion()", 1150, 50), ("%fusion.4 = f32[8]{0} fusion()", 1400, 10),
           ("%fusion.5 = f32[8]{0} fusion()", 1500, 10)]
    spans = [("serve/stage", 100, 600), ("serve/h2d", 300, 500), ("serve/complete", 1250, 100),
             ("data/next", 5000, 10)]
    gaps = m.idle_gaps(ops, spans, n=3)
    assert gaps == [(pytest.approx(1000 / 1e9), "serve/h2d+serve/stage"),
                    (pytest.approx(200 / 1e9), "serve/complete"),
                    (pytest.approx(90 / 1e9), m.NO_SPAN)]


def test_scope_rows_sum_by_scope_and_bound_the_roofline():
    m = _mod()
    table = {"fusion.1": ("bn_stats", "fwd"), "fusion.2": ("bn_stats", "fwd"), "fusion.3": ("conv_dw", "bwd")}
    ops = [("%fusion.1 = f32[1000]{0} fusion(f32[1000]{0} %a)", 0, 2_000_000),     # 8 kB in 2 us
           ("%fusion.2 = f32[1000]{0} fusion(f32[1000]{0} %a)", 0, 2_000_000),
           ("%fusion.3 = f32[250]{0} fusion(f32[250]{0} %a)", 0, 1_000_000),
           ("%copy-done.9 = f32[250]{0} copy-done(%c)", 0, 1_000_000)]
    rows = m.scope_rows(ops, table, hbm_bytes_per_s=1e9)
    assert [(r["scope"], r["phase"]) for r in rows] == [("bn_stats", "fwd"), ("conv_dw", "bwd"), ("unscoped", "-")]
    assert rows[0]["ms"] == pytest.approx(4e-3) and rows[0]["share_pct"] == pytest.approx(100 * 4 / 6)
    # 16 kB at 1 GB/s is 16 us of traffic against 4 us of time: a bound over 100% is a bound, not a share
    assert rows[0]["hbm_roofline_upper_pct"] == pytest.approx(400.0)
    assert rows[2]["share_pct"] == pytest.approx(100 / 6)


def test_main_prints_scopes_and_gaps_for_a_device_trace(tmp_path, capsys):
    """On a recorded v5e trace (the benchmark's fixture) with a scope table
    beside it, as cli.train leaves one: the table by scope and phase, and the
    idle gaps (this recording predates the spans: every gap says so)."""
    import shutil

    from yet_another_mobilenet_series_tpu.obs import scopes

    m = _mod()
    shutil.copy(TPU_FIXTURE, tmp_path / "vm.xplane.pb")
    assert m.main([str(tmp_path), "3"]) == 0
    out = capsys.readouterr().out
    assert f"no {scopes.SCOPE_TABLE_FILE}" in out and "by scope and phase" not in out
    xs, _ = m.load_xspace(str(tmp_path))
    plane = next(p for p in xs.planes if p.name.startswith("/device:TPU"))
    names = sorted({m.instruction_name(n) for n, _, _ in m.device_ops(plane)})
    hlo = "\n".join(f'  %{n} = f32[8]{{0}} fusion(%a), metadata={{op_name="jit(f)/jvp(bn_stats)/reduce_sum"}}'
                    for n in names if n.startswith("convert_reduce_fusion"))
    scopes.write_scope_table(str(tmp_path), hlo)
    assert m.main([str(tmp_path), "3"]) == 0
    out = capsys.readouterr().out
    assert "by scope and phase" in out and "CONTAIN a scope's reduction" in out
    rows = {ln.split()[0]: ln for ln in out.splitlines()
            if ln.startswith(("  bn_stats", "  unscoped")) and "roofline <=" in ln}
    assert "fwd" in rows["bn_stats"] and "unscoped" in rows
    assert out.count("\n  idle ") == 5 and m.NO_SPAN in out
