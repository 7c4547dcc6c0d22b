"""The artifact contract for the benchmark entry points: ONE parsed JSON line
on stdout, and NO result under a device metric's name without the device.

- scripts/serve_bench.py: the serving benchmark emits one artifact
  shape (BENCH_SERVE_*.json — p50/p99 latency + QPS per batch bucket) and
  is fast enough to stay in the tier-1 gate via its tiny preset. These runs
  are on the CPU, so they ask for it (`--cpu-rehearsal`): the counts they
  pin stand, and the rate headlines ride under `cpu_rehearsal_*` names.
- scripts/train_chaos.py: the TRAINING chaos round (seeded corrupt records
  + one injected NaN step + a mid-epoch SIGTERM, then a resume) emits the
  same artifact shape; the contract check here is the kill-and-resume
  acceptance for the survivable-training PR.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _assert_structural_sweep(sw, *, saturated=False, ring=False):
    """The structural-sweep contract (shared by the tiny fast run and the
    checked-in rehearsal artifacts): every serving structure present with
    sane instruments, bitwise parity across the whole ladder, the
    fused/overlapped modes halving dispatches/request vs chained, and — for
    the rehearsal artifacts (``saturated=True``) — the back-to-back claim:
    > 1 dispatch per completion wake-up on the saturated bucket, with the
    steady-state achieved-FLOPS window reported next to the single-dispatch
    reference. With ``ring=True`` (r12+ artifacts) the sweep also carries
    the ring arm: the deterministic one-dispatch window probe (R full slots
    == ONE serve.dispatch_seconds observation, bitwise, fill 1.0 >=
    min_fill), ring windows consumed under the driven burst, and the
    dispatches_per_wakeup [1, 2] per-batch bound deliberately NOT applied
    to the ring arm (a whole window is one engine piece — tests/
    test_overlap.py pins the histogram invariant). QPS magnitude is NOT
    asserted (1-core caveat, recorded)."""
    expect = {"sync", "pipelined", "fused", "overlapped"} | ({"ring"} if ring else set())
    assert set(sw["modes"]) == expect
    assert sw["bitwise_ok"], "structural ladder broke bitwise parity"
    assert sw["max_batch"] == 2 * sw["max_bucket"]
    assert sw["clients"] >= sw["max_batch"] and sw["requests_per_round"] >= sw["clients"]
    for mode, v in sw["modes"].items():
        assert v["qps"] > 0 and v["p99_ms"] > 0, (mode, v)
        assert len(v["qps_rounds"]) == sw["rounds"]
        assert v["p99_ms_registry"] >= v["p50_ms_registry"] > 0, (mode, v)
        assert v["dispatches_per_request"] > 0, (mode, v)
        # CPU XLA reports cost_analysis, so the efficiency window is real
        assert v["achieved_flops_per_s"] > 0 and v["dispatched_gflops"] > 0, (mode, v)
        assert v["dispatched_gbytes"] > 0, (mode, v)
    assert sw["modes"]["sync"]["dispatches_per_wakeup"] is None  # no completion thread
    for mode in ("pipelined", "fused"):
        # run_max=1: one handle per wake-up. The metric counts engine
        # dispatch PIECES, and a max_batch=2*cap coalesced batch decomposes
        # into at most 2 pieces (exactly 1 when the fused scan covers it),
        # so per-batch modes sit in [1, 2] — never the run depths back-to-
        # back produces
        assert 1.0 <= sw["modes"][mode]["dispatches_per_wakeup"] <= 2.0, mode
    # the structural dispatch claim: coalesced overflow rides the fused scan
    # (2 chunks -> 1 dispatch), halving dispatches/request vs chained. It is a
    # claim about FULL batches, and whether a live sweep's batches fill is the
    # scheduler's doing: a client thread not run within max_wait_ms leaves a
    # batch to flush short, and a short batch is one piece either way (under
    # six xdist workers the tiny sweep read fills of 0.75-0.86 and ratios of
    # 0.58-0.69, and failed here; PR 29). So the halving is held where the
    # artifact is a saturated rehearsal or the run itself shows full batches,
    # and in every run what no clock moves: engine pieces per coalesced
    # batch (dispatches/request x rows/batch) lie between one and two.
    def pieces_per_batch(mode):
        v = sw["modes"][mode]
        return v["dispatches_per_request"] * v["avg_fill"] * sw["max_batch"]

    for chained, fused in (("sync", "fused"), ("pipelined", "overlapped")):
        for mode in (chained, fused):
            assert 0.98 <= pieces_per_batch(mode) <= 2.02, (mode, sw["modes"][mode])
        if saturated or min(sw["modes"][m]["avg_fill"] for m in (chained, fused)) >= 0.99:
            assert sw["modes"][fused]["dispatches_per_request"] <= (
                0.55 * sw["modes"][chained]["dispatches_per_request"]), (chained, fused)
    dpw = sw["modes"]["overlapped"]["dispatches_per_wakeup"]
    assert dpw is not None and dpw >= 1.0
    if saturated:
        assert dpw > 1.0, "back-to-back never engaged on the saturated bucket"
        assert sw["single_dispatch_achieved_flops_per_s"] > 0
    if ring:
        assert sw["ring_slots"] >= 2 and 0 < sw["ring_min_fill"] <= 1.0
        probe = sw["ring_probe"]
        # the tentpole's headline, registry-delta counted: a saturated
        # R-slot window ran as exactly ONE dispatch, bitwise, fully filled
        assert probe["slots"] == sw["ring_slots"]
        assert probe["rows"] == sw["ring_slots"] * sw["max_bucket"]
        assert probe["dispatch_seconds_count_delta"] == 1, probe
        assert probe["ring_dispatches_delta"] == 1, probe
        assert probe["bitwise_ok"], "ring window broke bitwise parity"
        assert probe["fill"] == 1.0 and probe["fill"] >= sw["ring_min_fill"]
        rv = sw["modes"]["ring"]
        # dpw stays reported for the ring arm but is NOT bounded by the
        # per-batch [1, 2] contract: ring windows count as one piece each,
        # so values below the per-batch regime are the point, not a bug
        assert rv["dispatches_per_wakeup"] is None or rv["dispatches_per_wakeup"] >= 1.0
        for mode in ("sync", "pipelined", "fused", "overlapped"):
            assert sw["modes"][mode]["ring_windows"] == 0, mode
            assert sw["modes"][mode]["ring_slots_per_window"] is None, mode
        if saturated:
            # the driven burst really rode the ring, with real coalescing
            assert rv["ring_windows"] > 0
            assert rv["ring_slots_per_window"] >= 1.0
    assert "cpu_rehearsal" in sw["cpu_rehearsal_note"]  # the caveat is recorded


def _assert_fleet(fl, *, rehearsal=False, obs=True):
    """The --fleet contract (shared by the tiny fast run and the checked-in
    rehearsal artifacts): hedged-vs-unhedged on one seeded schedule with
    hedges fired and first-answer wins counted; a kill -9 round where
    completed + rejected accounts for EVERY submitted request (failed == 0,
    unresolved == 0 — no client ever hangs or sees the death) and the
    supervisor restarts the corpse; and an autoscaler trace bounded by
    [min, max] with cooldown respected. The rehearsal artifact additionally
    pins the diurnal shape — N rising under the peak and falling after —
    and the hedged tail beating the unhedged one. QPS magnitude is never
    asserted (1-core caveat, recorded in the artifact).

    ``obs`` gates the ISSUE-17 observability block (r10+; the archived r06
    artifact predates it): federated windowed p99 EXACTLY equal to the
    pooled per-replica reference, the scrape-overhead measurement, and the
    kill-chaos incident artifact."""
    assert fl["replicas"] >= 2
    assert fl["hedge_timer_ms"] is not None and fl["hedge_timer_ms"] > 0
    ab = fl["hedge_ab"]
    for mode in ("unhedged", "hedged"):
        r = ab[mode]
        assert r["unresolved"] == 0, f"{mode}: a client hung"
        assert r["submitted"] == r["completed"] + r["rejected"] + r["failed"], (mode, r)
        assert r["qps"] > 0 and r["p99_ms"] >= r["p50_ms"] > 0, (mode, r)
    assert ab["unhedged"]["hedges"] == 0  # the control arm really was a control
    assert ab["hedged"]["hedges"] >= 1, "the straggler never triggered a hedge"
    assert 1 <= ab["hedged"]["hedge_wins"] <= ab["hedged"]["hedges"]
    # first-answer-wins is idempotent: losers' late answers are dropped and
    # COUNTED, never double-delivered (>= because a loser still inside its
    # stall when the delta is read is not yet counted)
    assert ab["hedged"]["hedge_wasted"] >= 1
    k = fl["kill"]
    assert k["chaos_kills"] == 1
    assert k["unresolved"] == 0 and k["failed"] == 0, k
    assert k["submitted"] == k["completed"] + k["rejected"], k
    assert k["restarts"] >= 1 and k["replicas_after_restart"] == fl["replicas"]
    if obs:
        o = fl["obs"]
        r = o["round"]
        assert r["unresolved"] == 0, "obs round: a client hung"
        assert r["submitted"] == r["completed"] + r["rejected"] + r["failed"], r
        # the headline: the federated windowed p99 (summed per-replica
        # bucket deltas) EQUALS the pooled reference recomputed by the
        # bench with independent delta math — lossless federation, so
        # equality is exact, not approximate
        assert o["p99_match"] is True
        assert o["federated_p99_ms"] == o["pooled_p99_ms"]
        assert o["federated_p99_ms"] > 0, "obs round produced no latency signal"
        assert o["federated_replicas"] == fl["replicas"]
        slo = o["slo"]
        assert slo["target_p99_ms"] > 0 and 0 < slo["error_budget"] < 1
        assert slo["burn_short"] >= 0 and slo["burn_long"] >= 0
        assert slo["ticks"] >= 1, "the SLO tracker never saw a scrape tick"
        # overhead is MEASURED and recorded; the <1% bound is a docs claim
        # for uncontended hardware, not an assertion on this shared core
        assert o["submit_p50_ms"] > 0 and o["submit_p50_ms_under_scrape"] > 0
        assert isinstance(o["federation_overhead_pct"], (int, float))
        assert o["scrape_mean_ms"] > 0
        assert o["amortized_overhead_pct"] >= 0
        # the kill-chaos round always pins a self-contained incident
        assert o["incident"] is not None and o["incident"].startswith("incident_")
        assert o["incident"].endswith(".json")
        assert o["incident_events"] >= 1, "the flight-recorder ring was empty"
        assert o["incident_has_fleet_snapshot"] is True
    a = fl["autoscale"]
    assert a["min_replicas"] >= 1 and a["max_replicas"] > a["min_replicas"]
    assert a["trace"], "autoscaler never ticked"
    assert all(a["min_replicas"] <= r["n"] <= a["max_replicas"] for r in a["trace"])
    assert all(r["action"] == "hold" for r in a["trace"] if r["in_cooldown"])
    assert a["cooldown_respected"]
    for p in a["phases"]:
        assert p["unresolved"] == 0, (p["phase"], "a client hung")
        assert p["submitted"] == p["completed"] + p["rejected"] + p["failed"], p
    if rehearsal:
        assert ab["hedged_tail_speedup"] is not None and ab["hedged_tail_speedup"] > 1.0
        assert a["n_peak"] > a["n_start"], "N never rose under the diurnal peak"
        assert a["n_end"] < a["n_peak"], "N never fell after the peak"
        assert any(r["action"] == "up" for r in a["actions"])
        assert any(r["action"] == "down" for r in a["actions"])
    assert "cpu_rehearsal" in fl["cpu_rehearsal_note"]  # the caveat is recorded


def _assert_overload(ov, *, rehearsal=False):
    """The --overload contract (shared by the tiny fast run and the
    checked-in r08 rehearsal artifact): one seeded 3x-capacity open-loop
    storm played through both arms with per-class books balanced and ZERO
    unresolved futures (nobody ever hangs, storm or not); brownout-on beats
    brownout-off on interactive availability; the ladder steps up during
    the storm AND fully recovers to L0 after it, with door sheds counted;
    and the gray-failure round soft-ejects the latency-degraded (never
    crashing) replica within the window and shows the tail recovering
    after the ejection. Absolute capacity is never asserted (1-core
    caveat, recorded in the artifact)."""
    cap = ov["capacity"]
    assert cap["closed_loop_qps"] > 0 and cap["storm_qps"] > cap["closed_loop_qps"]
    assert cap["multiple"] >= 1.5 and cap["interactive_deadline_ms"] > 0
    storm = ov["storm"]
    for arm in ("off", "on"):
        rnd = storm[arm]
        assert rnd["unresolved"] == 0, f"{arm}: a client hung"
        for cls, s in rnd["classes"].items():
            assert s["submitted"] == s["completed"] + s["rejected"] + s["shed"] + s["failed"], (
                arm, cls, s)
            assert s["failed"] == 0, (arm, cls, s)  # overload is never an error
        assert sum(s["submitted"] for s in rnd["classes"].values()) == ov["requests"]
    # the headline: quality-for-goodput really bought interactive goodput
    assert storm["interactive_availability_on"] > storm["interactive_availability_off"]
    assert storm["off"]["shed_at_door_brownout"] == 0  # the control arm was a control
    assert storm["on"]["shed_at_door_brownout"] >= 1
    bo = storm["on"]["brownout"]
    assert bo["transitions_up"] >= 1, "the ladder never stepped up under the storm"
    assert 1 <= bo["peak_level"] <= 5
    assert bo["recovered_to_l0"] and bo["final_level"] == 0
    assert bo["transitions_up"] == bo["transitions_down"]  # every climb unwound
    assert bo["trace"], "controller never ticked"
    levels = [r["level"] for r in bo["trace"]]
    assert all(0 <= lv <= 5 for lv in levels)
    # one level per tick, up or down — the ladder is ordered, never a jump
    assert all(abs(b - a) <= 1 for a, b in zip(levels, levels[1:]))
    gray = ov["gray"]
    assert gray["replicas"] >= 2
    assert gray["unresolved"] == 0 and gray["failed"] == 0, gray
    assert gray["slow_ejections"] >= 1, "the gray replica was never soft-ejected"
    assert gray["time_to_eject_s"] is not None and 0 < gray["time_to_eject_s"] < 60
    assert gray["p99_ms_before_eject"] > 0 and gray["p99_ms_after_eject"] > 0
    if rehearsal:
        # the recovery claim with margin: post-eject tail well under the
        # straggler-poisoned one, and enough post-eject samples to mean it
        assert gray["tail_recovery"] is not None and gray["tail_recovery"] > 2.0
        assert gray["post_eject_samples"] >= 10
        assert gray["p99_ms_before_eject"] >= gray["straggler"]["latency_ms"]
    else:
        assert gray["tail_recovery"] is not None and gray["tail_recovery"] > 1.0
    assert "cpu_rehearsal" in ov["cpu_rehearsal_note"]  # the caveat is recorded


def _assert_partition(pt, *, rehearsal=False):
    """The --partition contract (shared by the tiny fast run and the
    checked-in r09 rehearsal artifact): four socket-level fault rounds
    (blackhole / reset / half-open / flap) each with ZERO client-visible
    failures and zero unresolved futures (transport retry absorbs every
    partition shape), detection of the hard faults within the POLL-budget
    bound — eject_failures x (poll interval + connect-bounded poll read) +
    slack — and provably under the read timeout (the 60 s class of hang
    this PR removes), every ejection readmitted after the heal (no
    permanent capacity loss from a transient fault, no flap ping-pong),
    and the TTL-lease membership round removing a silently-vanished leased
    backend within TTL + one poll sweep while traffic keeps answering."""
    cfg = pt["config"]
    assert cfg["poll_interval_s"] > 0 and cfg["eject_failures"] >= 1
    assert 0 < cfg["connect_timeout_s"] < cfg["read_timeout_s"]
    assert pt["detect_bound_s"] > 0
    assert set(pt["rounds"]) == {"blackhole", "reset", "half_open", "flap"}
    for name, r in pt["rounds"].items():
        assert r["unresolved"] == 0, f"{name}: a client hung"
        assert r["failed"] == 0, f"{name}: client-visible failures under partition"
        assert r["submitted"] == r["completed"] + r["rejected"], (name, r)
        assert r["qps"] > 0 and r["p99_ms"] >= r["p50_ms"] > 0, (name, r)
        # no permanent capacity loss from a transient fault: every ejection
        # the round caused was readmitted by round end
        assert r["routable_after"] == pt["replicas"], (name, r)
        assert r["ejections"] == r["readmissions"], (name, r)
    for shape in ("blackhole", "reset", "half_open"):
        r = pt["rounds"][shape]
        assert r["detection_s"] is not None and 0 < r["detection_s"] <= pt["detect_bound_s"], (
            shape, r["detection_s"], pt["detect_bound_s"])
        assert r["partition_ejections"] >= 1, f"{shape}: never attributed as a partition"
        assert r["recovery_s"] is not None and r["recovery_s"] < 30, (shape, r)
    # the headline claim: a blackholed replica ejects on the POLL budget,
    # not the read timeout (pre-split, detection == the read budget burn)
    assert pt["rounds"]["blackhole"]["detection_s"] < cfg["read_timeout_s"]
    # read-timeout-shaped legs (half-open) really re-routed instead of
    # 504ing: in-flight legs stall across the whole fault window, so at
    # least one retry is structural. (Reset legs can legitimately see zero
    # retries when poll-side detection ejects the victim before any pick
    # lands on it — its zero-failure book is the claim there.)
    assert pt["rounds"]["half_open"]["route_retries"] >= 1
    # flap must not permanently evict: bounded churn, full convergence
    # (routable_after + ejections == readmissions pinned above)
    m = pt["membership"]
    assert m["joined"], "the leased replica never joined via /register"
    assert m["unresolved"] == 0 and m["failed"] == 0, m
    assert m["registrations"] >= 1 and m["lease_renewals"] >= 1
    assert m["lease_expirations"] == 1, "the vanished lease never expired"
    assert m["removed_s"] is not None and 0 < m["removed_s"] <= m["removal_bound_s"], m
    assert m["total_after"] == pt["replicas"]
    if rehearsal:
        assert pt["replicas"] >= 3 and pt["requests_per_round"] >= 100
    assert "cpu_rehearsal" in pt["cpu_rehearsal_note"]  # the caveat is recorded


def _assert_zoo(z, *, rehearsal=False):
    """The --zoo contract (shared by the tiny fast run and the checked-in
    r11 rehearsal artifact): a 2-replica model-sharded fleet serving an
    int8 small tier and an f32 big tier, three arms on ONE seeded trace.
    Pinned: big-only answers bitwise-match the explicit-pin references;
    the sharded arm shows ZERO misroutes (per-replica
    serve.model_requests deltas) and zero 5xx; the cascade arm escalates
    AND answers small (> 0 each), every answer bitwise-matches exactly one
    per-image reference with escalated answers EQUAL to the big-only
    arm's, and its dispatched-FLOPs/request mean sits STRICTLY below the
    big-only arm's. Latency magnitude is never asserted (1-core caveat,
    recorded in the artifact)."""
    assert z["replicas"] == 2
    m = z["models"]
    assert m["small"]["weights"] == "int8" and m["big"]["weights"] == "float32"
    # the tiers are distinct stamped identities (satellite: bundle identity)
    assert m["small"]["digest"] and m["big"]["digest"]
    assert m["small"]["digest"] != m["big"]["digest"]
    assert 0 < m["small"]["int8_top1"] <= 1.0
    assert len(z["placement"]) == 2
    assert sorted(v for vals in z["placement"].values() for v in vals) == ["big", "small"]
    assert 0.0 <= z["threshold"] <= 1.0
    assert z["margins"]["min"] <= z["margins"]["median"] <= z["margins"]["max"]
    arms = z["arms"]
    assert set(arms) == {"big_only", "sharded", "cascade"}
    for name, r in arms.items():
        assert r["unresolved"] == 0, f"{name}: a client hung"
        assert r["submitted"] == z["requests"], (name, r)
        assert r["submitted"] == r["completed"] + r["rejected"] + r["failed"], (name, r)
        assert r["qps"] > 0 and r["p99_ms"] >= r["p50_ms"] > 0, (name, r)
        assert r["flops_per_request"] > 0, (name, r)
    assert arms["big_only"]["bitwise_match_big"] is True
    sh = arms["sharded"]
    # the headline placement claims: zero misroutes, zero 5xx, both tenants
    # exercised, every answer from the replica that serves its model
    assert sh["misroutes"] == 0
    assert sh["failed"] == 0 and sh["rejected"] == 0
    assert sh["mix"]["small"] >= 1 and sh["mix"]["big"] >= 1
    assert sh["mix"]["small"] + sh["mix"]["big"] == z["requests"]
    assert sh["bitwise_match"] is True
    assert set(sh["per_model"]) == {"small", "big"}
    for mdl, row in sh["per_model"].items():
        assert row["n"] == sh["mix"][mdl] and row["p99_ms"] >= row["p50_ms"] > 0
    ca = arms["cascade"]
    # the cascade split the trace: both outcomes populated, the counted
    # escalations equal the answers that bitwise-matched the big tier
    assert ca["escalations"] >= 1, "the cascade never escalated"
    assert ca["answered_small"] >= 1, "the cascade never answered small"
    assert ca["escalations"] + ca["answered_small"] == ca["completed"]
    assert 0.0 < ca["escalation_rate"] < 1.0
    assert ca["answer_mismatches"] == 0
    assert ca["escalated_bitwise_match_big_only"] is True
    assert ca["answers_big_bitwise"] + ca["answers_small_bitwise"] == ca["completed"]
    # the cost headline: the blended cascade cost beats all-big STRICTLY,
    # and the all-small shard mix is cheaper still (sanity on the proxy)
    cost = z["cost"]
    assert cost["cascade_flops_per_request"] < cost["big_only_flops_per_request"]
    assert cost["sharded_flops_per_request"] < cost["big_only_flops_per_request"]
    assert 0.0 < cost["cascade_vs_big_only"] < 1.0
    if rehearsal:
        # the checked-in artifact pins a real split (median-calibrated
        # threshold): a meaningful share of traffic stays on the small tier
        assert 0.2 <= ca["escalation_rate"] <= 0.8
        assert ca["deadline_skips"] == 0 and ca["escalation_failures"] == 0
    assert "cpu_rehearsal" in z["cpu_rehearsal_note"]  # the caveat is recorded


def _assert_quant_ab(q):
    """The --quant contract (shared by the tiny fast run and the checked-in
    r07 rehearsal artifact): the three precision modes present with their
    quant_mode labels, the uint8 wire moving >= 3.5x fewer transferred
    bytes per request than the f32 wire (registry math — exactly 4x modulo
    nothing, on ANY host), the zero-mean denorm pinned BITWISE, the
    mean/std wire delta inside the configured atol, the int8 export's
    top-1 agreement over its gate with the resident-byte shrink recorded,
    and the CPU caveat explaining why QPS magnitude is not asserted."""
    assert set(q["modes"]) == {"f32", "uint8_wire", "int8"}
    assert q["modes"]["f32"]["quant_mode"] == "wire=float32,weights=float32"
    assert q["modes"]["uint8_wire"]["quant_mode"] == "wire=uint8,weights=float32"
    assert q["modes"]["int8"]["quant_mode"] == "wire=uint8,weights=int8"
    for m, v in q["modes"].items():
        assert v["h2d_bytes_per_request"] > 0, m
        assert v["dispatched_bytes_per_request"] > 0, m  # CPU XLA reports cost
    # the headline byte claim: per-request transferred bytes quarter
    assert q["wire_bytes_ratio"] >= 3.5
    assert q["modes"]["uint8_wire"]["h2d_bytes_per_request"] == (
        q["modes"]["int8"]["h2d_bytes_per_request"])  # same u8 wire
    # wire bytes are exact registry math: cap * S * S * 3 * width
    f32_per_req = q["modes"]["f32"]["h2d_bytes_per_request"]
    assert f32_per_req == 4 * q["modes"]["uint8_wire"]["h2d_bytes_per_request"]
    p = q["parity"]
    assert p["identity_norm_bitwise"] is True  # the 'fold is exact' regime
    assert p["wire_parity_ok"] and p["wire_max_abs_logit_delta"] <= p["wire_atol"]
    assert p["int8_top1_agreement_calib"] >= p["int8_top1_min"]
    assert p["int8_top1_agreement_heldout"] >= p["int8_top1_min"]
    x = q["int8_export"]
    assert x["quantized_tensors"] >= 5
    assert x["resident_shrink"] > 2.0  # int8 weights + f32 biases/scales/SE
    assert x["bytes_int8"] < x["bytes_f32"]
    assert x["calib_images"] >= 16
    for row in q["per_bucket"]:
        for m in q["modes"]:
            assert row[f"qps_{m}"] > 0 and row[f"p99_ms_{m}"] >= row[f"p50_ms_{m}"] > 0, (m, row)
    assert "cpu_rehearsal" in q["cpu_rehearsal_note"]  # the caveat is recorded


def _assert_fused_ab(fz):
    """The chained-vs-fused A/B contract (shared by the tiny fast run and
    the checked-in r04 rehearsal artifact): one row per ladder K plus one
    off-ladder K, bitwise parity everywhere, and the STRUCTURAL claim —
    dispatches per request is exactly 1 for on-ladder K (vs K chained) and
    strictly fewer than chained for the off-ladder decomposition. Speedup
    magnitude is NOT asserted: on the 1-core rehearsal box it may be ~flat,
    and the artifact must record that caveat the way r02 did."""
    assert fz["ladder"] and fz["max_bucket"] >= 1
    assert fz["off_ladder_k"] not in fz["ladder"]
    assert [r["k"] for r in fz["per_k"]] == fz["ladder"] + [fz["off_ladder_k"]]
    for r in fz["per_k"]:
        assert r["bitwise_ok"], r
        assert r["rows"] == r["k"] * fz["max_bucket"]
        assert r["p99_ms_chained"] >= r["p50_ms_chained"] > 0
        assert r["p99_ms_fused"] >= r["p50_ms_fused"] > 0
        assert r["qps_chained"] > 0 and r["qps_fused"] > 0
        assert r["dispatches_per_request_chained"] == r["k"]
        if r["on_ladder"]:
            assert r["dispatches_per_request_fused"] == 1, r
        else:
            assert 1 <= r["dispatches_per_request_fused"] < r["dispatches_per_request_chained"], r
        assert r["fused_speedup"] == pytest.approx(r["qps_fused"] / r["qps_chained"], rel=1e-3)
    assert fz["peak_speedup"] == max(r["fused_speedup"] for r in fz["per_k"])
    assert "cpu_rehearsal" in fz["cpu_rehearsal_note"]  # the caveat is recorded


def test_serve_bench_emits_parsed_artifact(tmp_path):
    """scripts/serve_bench.py: exactly one JSON line, the artifact
    shape, p50/p99/QPS per (bucket, image_size) plus the sync-vs-pipelined
    and fp32-vs-bf16 A/B sections — the BENCH_SERVE_* contract."""
    out_path = tmp_path / "BENCH_SERVE_test.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "serve_bench.py"),
         "--arch", "tiny", "--image-sizes", "24,32", "--buckets", "2,4", "--iters", "3",
         "--concurrent-iters", "2", "--ab-iters", "2", "--fused", "--fused-iters", "3",
         "--structural", "--structural-rounds", "2",
         "--quant", "--quant-iters", "2", "--quant-rounds", "2",
         "--chaos-requests", "40", "--chaos-fault-rate", "0.3", "--cpu-rehearsal",
         "--out", str(out_path)],
        capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-1000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line, got {lines}"
    out = json.loads(lines[0])
    # a CPU run's rate never rides under the device metric's name or unit
    assert out["metric"] == "cpu_rehearsal_tiny_serve_images_per_sec"
    assert "error" not in out, out.get("error")
    assert out["value"] is not None and out["value"] > 0
    assert out["unit"].startswith("images/sec on XLA:CPU (rehearsal")
    assert out["vs_baseline"] is None  # no serving reference divisor exists
    assert out["platform"]
    # the shared provenance stamp (scripts/provenance.py): every bench artifact is
    # version/hardware attributable
    prov = out["provenance"]
    assert prov["jax_version"] and prov["jaxlib_version"] and prov["python"]
    assert prov["platform"] == out["platform"]
    assert prov["cpu_rehearsal"] == (out["platform"] == "cpu")
    assert out["image_sizes"] == [24, 32]
    # direct rows: one per (bucket, image_size), latency quantiles ordered
    assert [(r["batch"], r["image_size"]) for r in out["buckets"]] == [
        (2, 24), (4, 24), (2, 32), (4, 32)]
    for r in out["buckets"]:
        assert r["qps"] > 0 and r["p99_ms"] >= r["p50_ms"] > 0
        # the same window's quantiles from the registry's bucketed histogram
        # math (serve.run_seconds deltas) — the bench must report what
        # /metrics scrapes, not only its own percentile-of-a-list
        assert r["p99_ms_registry"] >= r["p95_ms_registry"] >= r["p50_ms_registry"] > 0
    # whole-run registry quantile snapshot: every serving histogram that saw
    # data carries the p50/p95/p99 columns obs_registry.json and /varz expose
    rq = out["registry_quantiles"]
    assert "serve.run_seconds" in rq and "serve.batch_size" in rq
    for name, v in rq.items():
        assert v["count"] > 0, name
        assert v["p99"] >= v["p95"] >= v["p50"] >= 0, (name, v)
    # concurrent-submit A/B: sync and pipelined QPS per (bucket, size); no
    # ordering assertion on magnitude — the tiny preset's sub-ms executables
    # are noise-dominated, the checked-in rehearsal artifact pins the win
    assert [(r["batch"], r["image_size"]) for r in out["concurrent"]] == [
        (2, 24), (4, 24), (2, 32), (4, 32)]
    for r in out["concurrent"]:
        assert r["qps_sync"] > 0 and r["qps_pipelined"] > 0
        assert r["requests"] >= r["clients"] >= 1
        assert r["pipelined_speedup"] == pytest.approx(r["qps_pipelined"] / r["qps_sync"], rel=1e-3)
    ab = out["ab"]["pipelined_vs_sync"]
    assert ab["peak_qps_pipelined"] == max(r["qps_pipelined"] for r in out["concurrent"])
    assert ab["peak_qps_sync"] == max(r["qps_sync"] for r in out["concurrent"])
    # fp32-vs-bf16 A/B: per-bucket QPS pairs + the measured parity delta
    # judged against the engine's pinned tolerance
    bf = out["ab"]["bf16_vs_fp32"]
    assert [r["batch"] for r in bf["buckets"]] == [2, 4]
    for r in bf["buckets"]:
        assert r["qps_bf16"] > 0 and r["qps_fp32"] > 0
    assert bf["peak_qps_bf16"] > 0 and bf["peak_qps_fp32"] > 0
    assert bf["max_abs_logit_delta"] >= 0
    assert bf["parity_ok"] and bf["max_abs_logit_delta"] <= bf["parity_atol"]
    _assert_fused_ab(out["ab"]["fused_vs_chained"])
    # quantized-serving A/B: the three precision modes with the exact
    # transferred-byte quartering and all parity verdicts (the r07 shape)
    _assert_quant_ab(out["ab"]["quant"])
    # structural sweep: the five serving structures interleaved; the tiny
    # preset pins structure + invariants only — including the deterministic
    # ring one-dispatch probe, which is NOT timing-dependent — while the
    # checked-in rehearsal artifacts pin the driven saturation claims
    # (dispatches_per_wakeup > 1 in r05, ring windows consumed in r12)
    _assert_structural_sweep(out["ab"]["structural_sweep"], ring=True)
    # chaos A/B: open-loop Poisson rounds with mixed priorities/sizes — the
    # books must balance per class and NOTHING may hang (unresolved == 0);
    # the healthy round must be failure-free (injected-fault counts are
    # dispatch-granular and timing-dependent under coalescing, so the tiny
    # preset pins structure + invariants; the checked-in r03 rehearsal pins
    # the measured retry/injection accounting)
    chaos = out["chaos"]
    assert chaos["requests"] == 40 and chaos["target_qps"] > 0
    assert set(chaos["class_mix"]) == {"interactive", "batch", "best_effort"}
    for round_name in ("healthy", "faulty"):
        rnd = chaos[round_name]
        assert rnd["unresolved"] == 0, f"{round_name}: a client hung"
        submitted = 0
        for cls, s in rnd["classes"].items():
            assert s["submitted"] == s["completed"] + s["rejected"] + s["shed"] + s["failed"], (
                round_name, cls, s)
            submitted += s["submitted"]
            if s["completed"]:
                assert s["p99_ms"] >= s["p50_ms"] > 0
                # per-class registry window quantiles ride every chaos row
                reg_q = s["registry_quantiles"]
                assert reg_q["count"] >= 1
                assert reg_q["p99_ms"] >= reg_q["p95_ms"] >= reg_q["p50_ms"] > 0
        assert submitted == chaos["requests"]
        assert rnd["qps"] > 0
    healthy = chaos["healthy"]
    assert healthy["injected_failures"] == 0 and healthy["breaker_opens"] == 0
    assert all(s["failed"] == 0 for s in healthy["classes"].values())
    faulty = chaos["faulty"]
    assert chaos["fault"]["failure_rate"] == 0.3
    # arrival-time rejection causes decompose the total
    for rnd in (healthy, faulty):
        assert rnd["rejected_total"] == (
            rnd["rejected_deadline"] + rnd["rejected_class_full"]
            + rnd["rejected_breaker"] + rnd["rejected_queue_full"])
    # the headline value is the overall peak across direct + concurrent
    assert out["value"] == out["peak_qps"] >= max(r["qps"] for r in out["buckets"])
    # --out writes the same artifact for the driver to collect
    assert json.loads(out_path.read_text()) == out


def test_serve_bench_fleet_emits_parsed_artifact(tmp_path):
    """scripts/serve_bench.py --fleet: a REAL 2-replica fleet (cli/serve.py
    subprocesses behind the router tier) driven through the hedge A/B, the
    kill -9 availability round, and the autoscaler's diurnal schedule —
    one JSON line in the bench artifact shape, the r06 contract."""
    out_path = tmp_path / "BENCH_SERVE_fleet_test.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "serve_bench.py"),
         "--fleet", "--arch", "tiny", "--image-sizes", "24", "--buckets", "1,4",
         "--fleet-requests", "24", "--fleet-phase-s", "3,10,7", "--cpu-rehearsal",
         "--out", str(out_path)],
        capture_output=True, text=True, timeout=540, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-1000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line, got {lines}"
    out = json.loads(lines[0])
    assert out["metric"] == "cpu_rehearsal_tiny_fleet_requests_per_sec"
    assert "error" not in out, out.get("error")
    assert out["unit"].startswith("requests/sec on XLA:CPU (rehearsal")
    assert out["vs_baseline"] is None
    prov = out["provenance"]
    assert prov["jax_version"] and prov["platform"] == out["platform"]
    # structure + invariants on the tiny run (the checked-in r06 rehearsal
    # additionally pins the diurnal rise/fall and the hedged-tail win)
    _assert_fleet(out["fleet"])
    assert out["value"] == out["fleet"]["hedge_ab"]["unhedged"]["qps"] > 0
    assert json.loads(out_path.read_text()) == out


def test_serve_bench_overload_emits_parsed_artifact(tmp_path):
    """scripts/serve_bench.py --overload: the brownout A/B on one seeded
    3x-capacity storm (paced engine, in-process) plus the gray-failure
    fleet round (real replica subprocesses, latency-based soft ejection) —
    one JSON line in the bench artifact shape, the r08 contract."""
    out_path = tmp_path / "BENCH_SERVE_overload_test.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "serve_bench.py"),
         "--overload", "--arch", "tiny", "--image-sizes", "24", "--buckets", "1,4",
         "--overload-storm-s", "3", "--overload-gray-requests", "48", "--cpu-rehearsal",
         "--out", str(out_path)],
        capture_output=True, text=True, timeout=540, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-1000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line, got {lines}"
    out = json.loads(lines[0])
    assert out["metric"] == "tiny_overload_interactive_availability"
    assert "error" not in out, out.get("error")
    assert out["unit"] == "completed/submitted" and out["vs_baseline"] is None
    prov = out["provenance"]
    assert prov["jax_version"] and prov["platform"] == out["platform"]
    _assert_overload(out["overload"])
    assert out["value"] == out["overload"]["storm"]["interactive_availability_on"] > 0
    assert json.loads(out_path.read_text()) == out


def test_serve_bench_partition_emits_parsed_artifact(tmp_path):
    """scripts/serve_bench.py --partition: seeded socket-level partition
    rounds (netchaos proxies between an in-process router and echo
    replicas — jax-free by design, the measurement is the TRANSPORT) plus
    the TTL-lease membership round — one JSON line in the bench artifact
    shape, the r09 contract."""
    out_path = tmp_path / "BENCH_SERVE_partition_test.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "serve_bench.py"),
         "--partition", "--partition-replicas", "2", "--partition-requests", "40",
         "--partition-qps", "20", "--out", str(out_path)],
        capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-1000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line, got {lines}"
    out = json.loads(lines[0])
    assert out["metric"] == "partition_blackhole_detect_seconds"
    assert "error" not in out, out.get("error")
    assert out["unit"] == "seconds" and out["vs_baseline"] is None
    # jax-free: provenance via importlib.metadata, cpu_rehearsal pinned by
    # the caller (no backend was ever touched)
    prov = out["provenance"]
    assert prov["jax_version"] and prov["cpu_rehearsal"] is True
    assert "platform" not in prov and out["platform"] == "cpu"
    _assert_partition(out["partition"])
    assert out["value"] == out["partition"]["rounds"]["blackhole"]["detection_s"] > 0
    assert json.loads(out_path.read_text()) == out


def test_serve_bench_zoo_emits_parsed_artifact(tmp_path):
    """scripts/serve_bench.py --zoo: a REAL 2-replica model-sharded fleet
    (slot 0 int8 small tier, slot 1 f32 big tier via per-slot
    serve.zoo.models assignments) driven through the big-only, sharded,
    and confidence-cascade arms on one seeded trace — one JSON line in
    the bench artifact shape, the r11 contract."""
    out_path = tmp_path / "BENCH_SERVE_zoo_test.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "serve_bench.py"),
         "--zoo", "--arch", "tiny", "--image-sizes", "24", "--buckets", "1",
         "--zoo-requests", "16", "--cpu-rehearsal", "--out", str(out_path)],
        capture_output=True, text=True, timeout=540, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-1000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line, got {lines}"
    out = json.loads(lines[0])
    assert out["metric"] == "tiny_zoo_cascade_flops_vs_big_only"
    assert "error" not in out, out.get("error")
    assert out["unit"] == "cascade/big_only dispatched-FLOPs per request"
    assert out["vs_baseline"] is None
    prov = out["provenance"]
    assert prov["jax_version"] and prov["platform"] == out["platform"]
    _assert_zoo(out["zoo"])
    assert out["value"] == out["zoo"]["cost"]["cascade_vs_big_only"]
    assert 0.0 < out["value"] < 1.0
    assert json.loads(out_path.read_text()) == out


def test_serve_bench_r11_zoo_rehearsal_artifact():
    """The r11 cpu_rehearsal artifact pins the multi-model zoo acceptance
    (ISSUE 18): on a live model-sharded fleet the sharded arm routes with
    ZERO misroutes and zero 5xx, the cascade escalates a real share of
    the trace (median-calibrated threshold) with every escalated answer
    bitwise-identical to the big-only arm's, and the cascade's
    dispatched-FLOPs/request mean sits strictly below big-only — the
    serving-cost claim the zoo exists for. Latency magnitude is the
    deferred accelerator measurement; the caveat is recorded in the
    artifact — r02..r10 discipline."""
    with open(os.path.join(REPO, "BENCH_SERVE_r11_cpu_rehearsal.json")) as f:
        out = json.load(f)
    assert out["platform"] == "cpu" and "error" not in out
    prov = out["provenance"]
    assert prov["cpu_rehearsal"] is True and prov["jax_version"]
    _assert_zoo(out["zoo"], rehearsal=True)
    assert out["value"] == out["zoo"]["cost"]["cascade_vs_big_only"]
    assert 0.0 < out["value"] < 1.0
    # the rehearsal trace is long enough for the split to be meaningful
    assert out["zoo"]["requests"] >= 32


def test_serve_bench_r09_partition_rehearsal_artifact():
    """The r09 cpu_rehearsal artifact pins the partition-containment
    acceptance (ISSUE 15): under a seeded blackhole through the netchaos
    proxy the router ejects the partitioned replica within the poll-budget
    bound (NOT the read timeout), with zero client-visible failures in
    every fault round (transport retry onto healthy replicas), full
    readmission after every heal, and lease expiry removing a silently-
    vanished backend within TTL + one poll sweep. Absolute rates are the
    deferred real-multi-host measurement; the caveat is recorded in the
    artifact — r02..r08 discipline."""
    with open(os.path.join(REPO, "BENCH_SERVE_r09_cpu_rehearsal.json")) as f:
        out = json.load(f)
    assert out["platform"] == "cpu" and "error" not in out
    assert out["value"] is not None and out["value"] > 0
    prov = out["provenance"]
    assert prov["cpu_rehearsal"] is True and prov["jax_version"]
    _assert_partition(out["partition"], rehearsal=True)
    # the rehearsal artifact additionally pins the margin: blackhole
    # detection at least 2x under the read timeout the split removes from
    # the failure path
    pt = out["partition"]
    assert pt["rounds"]["blackhole"]["detection_s"] <= 0.5 * pt["config"]["read_timeout_s"]


def test_serve_bench_r08_overload_rehearsal_artifact():
    """The r08 cpu_rehearsal artifact pins the brownout + gray-failure
    acceptance: under the SAME seeded 3x-capacity storm the ladder arm
    completes a strictly larger share of interactive traffic than the
    control arm (quality traded for goodput at the door, with Retry-After),
    the ladder climbs during the storm and walks all the way back to L0
    after it (up-count == down-count, one level per transition), zero
    futures unresolved in either arm, and the latency-degraded never-
    crashing replica is soft-ejected within the window with the fleet tail
    recovering afterwards. Absolute capacity is the deferred accelerator
    measurement; the caveat is recorded in the artifact — r02..r07
    discipline."""
    with open(os.path.join(REPO, "BENCH_SERVE_r08_cpu_rehearsal.json")) as f:
        out = json.load(f)
    assert out["platform"] == "cpu" and "error" not in out
    assert out["value"] is not None and out["value"] > 0
    prov = out["provenance"]
    assert prov["cpu_rehearsal"] is True and prov["jax_version"]
    _assert_overload(out["overload"], rehearsal=True)
    # the rehearsal artifact additionally pins a MATERIAL availability win,
    # not a statistical sliver
    storm = out["overload"]["storm"]
    assert storm["interactive_availability_on"] >= 2.0 * storm["interactive_availability_off"]


def test_serve_bench_r07_quant_rehearsal_artifact():
    """The r07 cpu_rehearsal artifact pins the quantized-serving acceptance:
    per-request serve.h2d_bytes on the uint8 wire >= 3.5x lower than the
    f32 wire (registry math, host-independent — measured exactly 4x), the
    zero-mean denorm BITWISE-identical to the f32 wire, the mean/std wire
    delta recorded under the configured atol, and the int8 export's top-1
    agreement over its gate with scales + calibration provenance
    accounted. QPS magnitude between modes is the deferred accelerator
    measurement; the caveat is recorded in the artifact — r02/r04/r05
    discipline."""
    with open(os.path.join(REPO, "BENCH_SERVE_r07_cpu_rehearsal.json")) as f:
        out = json.load(f)
    assert out["platform"] == "cpu" and "error" not in out
    assert out["value"] is not None and out["value"] > 0
    prov = out["provenance"]
    assert prov["cpu_rehearsal"] is True and prov["jax_version"]
    _assert_quant_ab(out["ab"]["quant"])
    # the rehearsal artifact additionally pins the exact quartering and a
    # realistic (224px-scale) per-request byte magnitude
    q = out["ab"]["quant"]
    assert q["wire_bytes_ratio"] == 4.0
    assert q["modes"]["f32"]["h2d_bytes_per_request"] >= 4 * q["image_size"] ** 2 * 3


def test_serve_bench_r06_fleet_rehearsal_artifact():
    """The r06 cpu_rehearsal artifact pins the fleet acceptance: the hedged
    round beats the unhedged tail on the shared seeded schedule (hedges
    fired at the measured p-quantile timer, first answer wins), the kill -9
    round accounts for every submitted request as completed+rejected with
    nothing hanging and the replica restarted, and the autoscaler trace
    rises and falls across the diurnal schedule with cooldown respected.
    Absolute throughput is the deferred accelerator measurement; the caveat
    is recorded in the artifact — r02/r04/r05 discipline."""
    with open(os.path.join(REPO, "BENCH_SERVE_r06_cpu_rehearsal.json")) as f:
        out = json.load(f)
    assert out["platform"] == "cpu" and "error" not in out
    assert out["value"] is not None and out["value"] > 0
    prov = out["provenance"]
    assert prov["cpu_rehearsal"] is True and prov["jax_version"]
    # archived artifact from before the observability round existed
    _assert_fleet(out["fleet"], rehearsal=True, obs=False)


def test_serve_bench_r10_fleet_obs_rehearsal_artifact():
    """The r10 cpu_rehearsal artifact pins the fleet-observability
    acceptance on top of the r06 fleet contract: the federated windowed
    p99 (per-replica histogram bucket deltas summed by obs/fleet.py)
    EXACTLY equals the pooled reference the bench recomputes with
    independent reset-aware delta math from the same scraped /varz
    documents; the scrape-under-load overhead measurement is recorded
    (magnitude is a docs claim — on this 1-core box scraper and submitter
    share the core, so the number is an upper bound); and the kill -9
    chaos round dumped a self-contained ``incident_*.json`` (event ring +
    federated fleet snapshot + last per-replica /varz)."""
    with open(os.path.join(REPO, "BENCH_SERVE_r10_cpu_rehearsal.json")) as f:
        out = json.load(f)
    assert out["platform"] == "cpu" and "error" not in out
    assert out["value"] is not None and out["value"] > 0
    prov = out["provenance"]
    assert prov["cpu_rehearsal"] is True and prov["jax_version"]
    _assert_fleet(out["fleet"], rehearsal=True)


def test_train_chaos_emits_parsed_artifact(tmp_path):
    """scripts/train_chaos.py: exactly one JSON line, bench artifact shape,
    and the survivable-training acceptance inside it — the chaos round
    skipped injected corrupt records and the NaN step (counted, bounded),
    the SIGTERM produced a clean exit with a synchronous checkpoint and a
    resume marker, and the resume round continued FROM THE KILLED STEP (no
    restart-from-zero) through to completion with a sane loss."""
    out_path = tmp_path / "TRAIN_CHAOS_test.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "train_chaos.py"),
         "--log-dir", str(tmp_path / "run"), "--out", str(out_path)],
        capture_output=True, text=True, timeout=540, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-1000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line, got {lines}"
    out = json.loads(lines[0])
    assert out["metric"] == "train_chaos_recovered_steps"
    assert "error" not in out, out.get("error")
    assert out["value"] is not None and out["value"] > 0
    assert out["unit"] == "steps" and out["vs_baseline"] is None
    # provenance stamped WITHOUT importing jax in the parent (versions via
    # importlib.metadata; cpu_rehearsal pinned by the caller)
    prov = out["provenance"]
    assert prov["jax_version"] and prov["cpu_rehearsal"] is True
    assert "platform" not in prov  # the parent never touched a backend

    chaos, resume = out["chaos"], out["resume"]
    # preemption: clean exit, marker written, one preemption counted
    assert chaos["exit_code"] == 0 and chaos["preemptions"] == 1
    assert chaos["killed_step"] > 0 and chaos["reason"] == "SIGTERM"
    # chaos bookkeeping: the injected corrupt records were skipped AND
    # counted by the resilience wrapper; the injected NaN step was skipped
    # AND counted by the guard — and neither exhausted its budget
    assert chaos["injected_corrupt"] >= 1
    assert chaos["corrupt_records"] >= chaos["injected_corrupt"]
    assert chaos["injected_nan_steps"] == 1
    assert chaos["skipped_steps"] >= 1 and chaos["nonfinite_events"] >= 1
    assert not chaos["health_abort"]
    # resume: continues from the preemption checkpoint, not from zero
    assert resume["exit_code"] == 0
    assert resume["resumed_step"] == chaos["killed_step"] > 0
    assert resume["marker_consumed"]
    assert resume["final_step"] > resume["resumed_step"]
    # loss trajectory continuity: the first post-resume loss stays in the
    # same regime as the pre-kill loss (no re-init cliff, no blowup)
    assert resume["loss_after_resume"] is not None and chaos["loss_before_kill"] is not None
    assert resume["loss_after_resume"] < 3.0 * max(chaos["loss_before_kill"], 0.1)
    # --out writes the same artifact for the driver to collect
    assert json.loads(out_path.read_text()) == out


def test_serve_bench_r03_chaos_rehearsal_artifact():
    """The r03 cpu_rehearsal artifact pins the chaos A/B acceptance: a
    healthy open-loop Poisson round and a seeded 5%-fault round over mixed
    priorities, per-class accounting balanced, nothing unresolved, retries
    absorbing injected failures, and the faulty round still serving (the
    resilience edge degrades gracefully instead of collapsing)."""
    with open(os.path.join(REPO, "BENCH_SERVE_r03_cpu_rehearsal.json")) as f:
        out = json.load(f)
    assert out["platform"] == "cpu" and "error" not in out
    chaos = out["chaos"]
    assert chaos["fault"]["failure_rate"] == 0.05
    for round_name in ("healthy", "faulty"):
        rnd = chaos[round_name]
        assert rnd["unresolved"] == 0, f"{round_name}: a request hung"
        submitted = 0
        for cls, s in rnd["classes"].items():
            assert s["submitted"] == s["completed"] + s["rejected"] + s["shed"] + s["failed"], (
                round_name, cls, s)
            submitted += s["submitted"]
        assert submitted == chaos["requests"]
        assert rnd["rejected_total"] == (
            rnd["rejected_deadline"] + rnd["rejected_class_full"]
            + rnd["rejected_breaker"] + rnd["rejected_queue_full"])
        assert rnd["qps"] > 0
    healthy, faulty = chaos["healthy"], chaos["faulty"]
    assert healthy["injected_failures"] == 0
    assert all(s["failed"] == 0 for s in healthy["classes"].values())
    # the faulty round really injected faults, and the edge responded:
    # every injected failure was retried or surfaced typed — and the
    # service kept serving a comparable share of the load
    assert faulty["injected_failures"] >= 1
    assert faulty["retries"] >= 1
    total_completed = {
        r: sum(s["completed"] for s in chaos[r]["classes"].values())
        for r in ("healthy", "faulty")
    }
    assert total_completed["faulty"] >= 0.5 * total_completed["healthy"]
    # per-class latency quantiles exist for every class that completed work
    for rnd in (healthy, faulty):
        for cls, s in rnd["classes"].items():
            if s["completed"]:
                assert s["p99_ms"] >= s["p50_ms"] > 0, (cls, s)


def test_serve_bench_r04_fused_rehearsal_artifact():
    """The r04 cpu_rehearsal artifact pins the fused-dispatch acceptance:
    whole requests of K max-bucket chunks served in ONE dispatch for
    on-ladder K (vs K chained dispatches), bitwise-identical logits, the
    off-ladder K decomposing into fewer dispatches than chained — and the
    1-core caveat recorded in the artifact (speedup may be ~flat there; the
    dispatch-count drop is the structural win, the throughput claim is the
    ROADMAP hardware rung), exactly the r02 caveat discipline."""
    with open(os.path.join(REPO, "BENCH_SERVE_r04_cpu_rehearsal.json")) as f:
        out = json.load(f)
    assert out["platform"] == "cpu" and "error" not in out
    assert out["value"] is not None and out["value"] > 0
    _assert_fused_ab(out["ab"]["fused_vs_chained"])


def test_serve_bench_r05_structural_rehearsal_artifact():
    """The r05 cpu_rehearsal artifact pins the overlapped-staging /
    device-resident acceptance: the four-structure interleaved sweep with
    bitwise parity across the whole ladder, fused/overlapped halving
    dispatches per request, back-to-back dispatch REALLY engaging on the
    saturated bucket (serve.dispatches_per_wakeup > 1 — the structural
    claim a 1-core box CAN pin), and the steady-state achieved-FLOPS window
    reported next to the single-dispatch reference. Throughput magnitude is
    the deferred accelerator measurement; the caveat is recorded in the
    artifact, r02/r04 discipline."""
    with open(os.path.join(REPO, "BENCH_SERVE_r05_cpu_rehearsal.json")) as f:
        out = json.load(f)
    assert out["platform"] == "cpu" and "error" not in out
    assert out["value"] is not None and out["value"] > 0
    prov = out["provenance"]
    assert prov["cpu_rehearsal"] is True and prov["jax_version"]
    _assert_structural_sweep(out["ab"]["structural_sweep"], saturated=True)
    # whole-run registry-math quantiles ride the artifact like every round
    rq = out["registry_quantiles"]
    assert "serve.run_seconds" in rq and "serve.h2d_seconds" in rq
    assert "serve.dispatches_per_wakeup" in rq


def test_serve_bench_r12_ring_rehearsal_artifact():
    """The r12 cpu_rehearsal artifact pins the device-resident request-ring
    acceptance: the five-structure interleaved sweep (r05's four + the ring
    arm) with bitwise parity everywhere, the deterministic one-dispatch
    probe — a saturated window of R full max-bucket slots registry-counted
    as exactly ONE serve.dispatch_seconds observation at fill 1.0 >=
    min_fill, bitwise vs the per-batch path — and ring windows REALLY
    consumed under the driven burst (serve.ring_dispatches > 0 with real
    slot coalescing). The per-batch dispatches_per_wakeup [1, 2] bound is
    deliberately not applied to the ring arm (one window == one piece).
    Throughput magnitude is the deferred accelerator measurement (ROADMAP
    item 2's hardware rung); the standing 1-core caveat is recorded in the
    artifact, r02/r04/r05 discipline."""
    with open(os.path.join(REPO, "BENCH_SERVE_r12_cpu_rehearsal.json")) as f:
        out = json.load(f)
    assert out["platform"] == "cpu" and "error" not in out
    assert out["value"] is not None and out["value"] > 0
    prov = out["provenance"]
    assert prov["cpu_rehearsal"] is True and prov["jax_version"]
    _assert_structural_sweep(out["ab"]["structural_sweep"], saturated=True, ring=True)
    rq = out["registry_quantiles"]
    assert "serve.run_seconds" in rq and "serve.h2d_seconds" in rq
    assert "serve.ring_slots_per_dispatch" in rq


def test_serve_bench_checked_in_rehearsal_artifact():
    """The r02 cpu_rehearsal artifact carries the acceptance deltas with
    per-round transparency. What it can honestly pin on THIS rehearsal box:
    the box is single-core, so host staging/collect work and XLA "device"
    compute share one core — overlap cannot add throughput there (a direct
    experiment measured ~5% cache/context interleave tax on overlapped
    staging), and a phase-clean sync cycle is work-conserving-optimal. The
    invariant pinned here is therefore NO REGRESSION: the pipelined path
    stays within the artifact's own recorded round spread of sync on every
    bucket, with full buckets (no padded-fill collapse) and a
    within-tolerance bf16 parity delta. The actual speedup claim is a
    hardware measurement (ROADMAP serving rung): on an accelerator the
    host work this PR moves off the critical path is pure win."""
    with open(os.path.join(REPO, "BENCH_SERVE_r02_cpu_rehearsal.json")) as f:
        out = json.load(f)
    assert out["platform"] == "cpu" and "error" not in out
    for r in out["concurrent"]:
        # within the observed per-round spread of the sync mode itself
        spread = (max(r["qps_rounds_sync"]) - min(r["qps_rounds_sync"])) / r["qps_sync"]
        floor = 1.0 - max(spread, 0.05)
        assert r["qps_pipelined"] >= floor * r["qps_sync"], (r, floor)
        # batching policy held: no partial-fill collapse in either mode
        assert r["avg_fill_sync"] >= 0.9 and r["avg_fill_pipelined"] >= 0.9, r
        assert len(r["qps_rounds_sync"]) == len(r["qps_rounds_pipelined"]) == r["rounds"]
    ab = out["ab"]["pipelined_vs_sync"]
    assert ab["peak_qps_pipelined"] >= 0.9 * ab["peak_qps_sync"]
    bf = out["ab"]["bf16_vs_fp32"]
    assert bf["parity_ok"] and bf["max_abs_logit_delta"] <= bf["parity_atol"]
    assert bf["mean_abs_logit"] > 0  # the parity probe wasn't degenerate
