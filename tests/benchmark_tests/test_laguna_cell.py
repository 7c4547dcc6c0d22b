"""The token training cell `lagunas21_train_1x8k` (CPU only, nothing timed):
its MAC count against the program's own `dot_general`s and the window's
pairs against the tiles the program meets, the configuration file against
the catalog's published config and its arithmetic, every new layer metric
against its entry, file and reader (the window kernels' roofline share under
100% by construction), its `--rehearsal` run, the benchmark's copy of the
reference (benchmark/reference_laguna.py) against the package's
(models/lm_reference.py `laguna_*`), the comparison's limits against a lower
precision, and the planted faults that `compare` must see.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, macs_laguna, readers, reference_laguna as bench_ref, roofline_window  # noqa: E402
from benchmark.layer_metrics import attn_window_mxu_roofline_pct_train as roofline  # noqa: E402
from benchmark.layer_metrics import step_scopes_lm  # noqa: E402
from benchmark.runners import train_tokens_resident_laguna as runner  # noqa: E402

CELL = "lagunas21_train_1x8k"
CONFIG = "laguna_s_2_1_ep32_share"
CATALOG = os.environ.get("MODEL_CATALOG", "")  # a JSON-lines catalog of published configs, if one is at hand
LAYER_DIR = os.path.join(REPO, "benchmark", "layer_metrics")
TIME = "lm.attn_window_ms.train"
ROOFLINE = "attn.window_mxu_roofline_pct.train"
JOINED = ["host.dispatch_ms.train", "step.device_ms.train", "step.mfu.train", "coll.ms_per_step.train",
          "device.idle_share.train", "device.peak_hbm_gib.train", "moe.assignments_per_expert.train",
          "moe.load_max_over_mean.train", *step_scopes_lm.METRICS, step_scopes_lm.UNSCOPED_SHARE,
          "host.gc_pause_ms.train", "host.gc_max_pause_ms.train"]
NOT_JOINED = ["lm.kda_core_ms.train", "lm.kda_proj_ms.train", "lm.kda_pointwise_ms.train", "lm.exit_gate_ms.train",
              "lm.norm_residual_ms.train", "loop.layer_applications.train", "loop.expected_exit_step.train",
              "lm.ssd_core_ms.train", "lm.ssd_proj_ms.train", "lm.ssd_pointwise_ms.train",
              "ssd.conv_hbm_roofline_pct.train"]


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def config_file() -> dict:
    with open(os.path.join(REPO, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def toy(seq_len: int, **more):
    """(TokenModel, LMConfig) at the configuration's own rehearsal sizes."""
    from yet_another_mobilenet_series_tpu.models import get_model

    config = harness.with_rehearsal(config_file(), True)
    cfg = harness.load_app_config(config["train_app"], {**config["overrides"], "model.lm.seq_len": seq_len, **more})
    return get_model(cfg.model), cfg.model.lm


# -- the yardstick -------------------------------------------------------------


def dot_macs(jaxpr, times: int = 1) -> tuple[int, int]:
    """(MACs of every dot_general, MACs of every ragged_dot) in a jaxpr,
    through scans, remats and calls, but NOT of what is traced under the
    `attn_window` scope: the window's pairs are counted apart."""
    plain = ragged = 0
    for eqn in jaxpr.eqns:
        if "attn_window" in str(eqn.source_info.name_stack):
            continue
        name = eqn.primitive.name
        if name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            plain += times * math.prod(eqn.outvars[0].aval.shape) * math.prod(
                eqn.invars[0].aval.shape[i] for i in contract)
        elif name.startswith("ragged_dot"):
            ragged += times * math.prod(eqn.outvars[0].aval.shape) * eqn.invars[0].aval.shape[-1]
        inner = times * eqn.params.get("length", 1) if name == "scan" else times
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    p, r = dot_macs(sub, inner)
                    plain, ragged = plain + p, ragged + r
    return plain, ragged


def unrolled(lower, upper, body, carry):
    """`lax.fori_loop` written out where its bounds are known while tracing; a loop where they are not."""
    import jax

    if isinstance(lower, jax.core.Tracer) or isinstance(upper, jax.core.Tracer):
        return jax.lax.fori_loop(lower, upper, body, carry)
    for i in range(int(lower), int(upper)):
        carry = body(i, carry)
    return carry


def test_macs_from_shapes_equal_the_programs_dot_generals(monkeypatch):
    """Everything but the routed experts and the window's core: the program's
    forward over one sequence of 16 (the full layers' attention in tiles of
    one row by one key, unrolled, so that exactly the causal pairs are
    `dot_general`s) against macs_laguna.py's count from the configuration's
    keys; the grouped matmuls traced over every assignment's row; and the
    windowed layers' core counted apart, by the tiles of one row by one key
    its loops meet: exactly the pairs the window of 12 admits, sum over p of
    min(p + 1, 12), where the causal bounds meet 136."""
    import jax
    import jax.numpy as jnp

    from yet_another_mobilenet_series_tpu.ops import lm as ops

    monkeypatch.setattr(ops, "ATTN_BLOCK", 1)
    monkeypatch.setattr(ops, "lax", types.SimpleNamespace(**{**vars(jax.lax), "fori_loop": unrolled}))
    net, lm = toy(16)
    params, state = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((1, lm.seq_len + 2), jnp.int32)
    plain, ragged = dot_macs(jax.make_jaxpr(lambda p, s, t: net.forward(p, s, t)[0])(params, state, tokens).jaxpr)
    keys = {k: getattr(lm, k) for k in (
        "hidden_size", "head_dim", "num_key_value_heads", "num_hidden_layers", "first_k_dense_replace",
        "moe_intermediate_size", "intermediate_size", "shared_expert_intermediate_size", "num_experts_per_tok",
        "sliding_window")}
    keys.update(layer_types=list(lm.layer_types), num_attention_heads_per_layer=list(lm.num_attention_heads_per_layer),
                n_routed_experts=net.experts_held, vocab_size=net.vocab)
    parts = macs_laguna.parts(keys, lm.seq_len, lm.n_routed_experts)
    routed, windowed = parts.pop("routed_experts_expected"), parts.pop("attn_core_window")
    assert plain == sum(parts.values())
    assert ragged == routed * lm.n_routed_experts // net.experts_held
    assert parts["attn_core_full"] == 2 * (16 * 17 // 2) * 4 * 2 * 16  # two full layers of 4 heads
    pairs = []
    real = ops._tile_scores
    monkeypatch.setattr(ops, "_tile_scores", lambda q, k, *rest: (pairs.append(1), real(q, k, *rest))[1])
    x = jnp.zeros((1, 1, 16, 8))
    ops.loops_fwd(x, x, x, 1.0, 1, window=12)
    assert len(pairs) == roofline_window.window_pairs(16, 12) == 12 * 13 // 2 + 4 * 12 == 126
    assert windowed == 3 * len(pairs) * 6 * 2 * 16  # three sliding layers of 6 heads
    assert macs_laguna.forward_macs(keys, lm.seq_len, lm.n_routed_experts) == plain + routed + windowed


def test_the_configuration_file_is_the_published_config_and_its_arithmetic():
    """Every key of the catalog's `config` is in the file under the same name
    with the same value, but those in `reduced`: the depth and the per-layer
    lists cut to layers 0-4, the experts held, the vocabulary slice; nested
    groups (`rope_parameters`) whole."""
    config = config_file()
    cut = ["num_hidden_layers", "layer_types", "mlp_layer_types", "gating_types", "num_attention_heads_per_layer",
           "num_experts", "vocab_size"]
    assert config["reduced"] == cut
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f) if r["name"] == "Laguna-S-2.1"]
        assert config["source"] == row["source_url"]
        differing = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differing == set(cut), differing
        assert all(config[k] == row["config"][k][:5] for k in cut if isinstance(config[k], list))
        assert row["config"]["num_hidden_layers"] == config["published"]["num_hidden_layers"] == 48
    assert config["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert config["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48] and config["mlp_only_layers"] == [0]
    assert config["published"]["n_routed_experts"] == 256 == config["num_experts"] * config["expert_shares"]
    assert config["n_routed_experts"] == config["num_experts"] == 8 and config["expert_shares"] == 32
    assert config["published"]["vocab_size"] == config["vocab_size"] * 8 == 100352
    assert (config["hidden_size"], config["head_dim"], config["num_key_value_heads"], config["sliding_window"],
            config["intermediate_size"], config["moe_intermediate_size"], config["shared_expert_intermediate_size"],
            config["num_experts_per_tok"], config["moe_routed_scaling_factor"], config["rms_norm_eps"]) == (
        3072, 128, 8, 512, 12288, 1024, 1024, 10, 2.5, 1e-06)
    assert config["routed_scaling_factor"] == config["moe_routed_scaling_factor"]  # the program's one scaling key
    full = config["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"], full["original_max_position_embeddings"], full["partial_rotary_factor"]) == (
        "yarn", 128, 8192, 0.5)
    parts = config["parameters_by_part"]
    expert_ffn = (parts["router"] + parts["shared_expert_with_its_gate"] + parts["routed_experts_held_a_layer"]
                  + parts["norms_a_layer"])
    assert parts["full_attention_mixer"] == 2 * 3072 * 6144 + 2 * 3072 * 1024 + 3072 * 48 == 44_187_648
    assert parts["sliding_attention_mixer"] == 2 * 3072 * 9216 + 2 * 3072 * 1024 + 3072 * 72 == 63_135_744
    assert parts["layer_0_full_dense"] == parts["full_attention_mixer"] + parts["dense_mlp"] + parts["norms_a_layer"]
    assert parts["expert_layer_with_sliding_here"] == parts["sliding_attention_mixer"] + expert_ffn
    assert parts["expert_layer_with_full_here"] == parts["full_attention_mixer"] + expert_ffn
    assert (parts["layer_0_full_dense"] + 3 * parts["expert_layer_with_sliding_here"] + parts["expert_layer_with_full_here"]
            + parts["embedding_and_head_held"] + parts["final_norm"]) == config["parameters_here"] == 811_029_504
    assert (parts["layer_0_full_dense"] + 36 * parts["published_expert_layer_with_sliding"]
            + 11 * parts["published_expert_layer_with_full"] + parts["published_embedding_head_final_norm"]
            ) == config["published"]["parameters"] == 117_562_097_664
    assert config["bytes"]["parameters_gradients_moments_gb"] == round(16 * 811_029_504 / 1e9, 2) == 12.98
    assert macs_laguna.forward_macs(config, 8192, 256) == 5_000_161_394_688  # one sequence: the cell's macs_per_image
    shares = {k: v / 5_000_161_394_688 for k, v in macs_laguna.parts(config, 8192, 256).items()}
    assert 0.66 < shares["attn_proj"] + shares["attn_core_full"] + shares["attn_core_window"] < 0.67
    assert 0.164 < shares["attn_core_full"] < 0.166 and 0.044 < shares["attn_core_window"] < 0.046
    assert any("softmax over all 256" in line for line in config["assumed"])
    (entry,) = [c for c in manifest()["configs"] if c["name"] == config["name"]]
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_the_cell_and_its_traffic_are_one_8k_document():
    (cell,) = [w for w in manifest()["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train_tokens_resident_laguna_1x8k", 1)
    assert len(cell["why"]) <= 200 and manifest()["workloads"][-1] == cell
    with open(os.path.join(REPO, "benchmark", "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["runner"], traffic["sequences_per_chip"], traffic["seq_len"], traffic["zipf_exponent"],
            traffic["warm_steps"], traffic["sync_every"], traffic["sync_lag"], traffic["trace_for_s"],
            traffic["reference_rows_at_once"], traffic["rehearsal"]["seq_len"]) == (
        "train_tokens_resident_laguna", 1, 8192, 1.0, 1, 1, 1, 4.0, 256, 32)
    assert config_file()["overrides"] == {"schedule.warmup_epochs": 0.0, "schedule.base_lr": 1e-6}
    assert sum(w["chips"] == 4 for w in manifest()["workloads"]) == 1  # the benchmark keeps its one four-chip cell
    assert len(manifest()["configs"]) == 7 and len(manifest()["workloads"]) == 8


# -- the layer metrics -----------------------------------------------------------


def test_the_window_time_has_its_entry_its_file_and_its_reader():
    """`attn_window` rows of the table step_scopes_lm.py made, every phase; None without a table or without such a row."""
    (entry,) = [m for m in manifest()["per_layer"] if m["name"] == TIME]
    assert entry == {"name": TIME, "unit": "ms", "better": "lower", "source": "device_trace",
                     "layer": "compiled train step", "moves": "train_images_per_s_per_chip", "workloads": [CELL]}
    with open(os.path.join(LAYER_DIR, TIME + ".json")) as f:
        how = json.load(f)
    assert (how["reader"], how["module"]) == ("python", "lm_attn_window_ms_train")
    assert readers.python(types.SimpleNamespace(trace=None), how["module"]) is None
    ctx = types.SimpleNamespace(step_scopes_lm={"metrics": {}, "table": {"ms_per_step": {"attn_core.fwd": 3.0}}})
    assert readers.python(ctx, how["module"]) is None
    rows = {"attn_window.fwd": 4.0, "attn_window.bwd": 11.5, "attn_window.-": 0.25, "attn_core.fwd": 7.0,
            "attn_gate.bwd": 1.0}
    ctx = types.SimpleNamespace(step_scopes_lm={"metrics": {}, "table": {"ms_per_step": rows}})
    assert readers.python(ctx, how["module"]) == 15.75
    from yet_another_mobilenet_series_tpu.obs import scopes

    assert {"attn_window", "attn_gate"} <= set(scopes.SCOPES)
    assert not {"attn_window", "attn_gate"} & {s for names in step_scopes_lm.METRICS.values() for s in names}


def test_the_roofline_share_has_its_entry_and_file_and_cannot_pass_100():
    """The window kernels' share of the MXU roofline: FLOPs of the admitted
    pairs alone (roofline_window.py) over the calls' device time x 197
    TFLOP/s. Calls that took exactly FLOPs / peak read 100; longer ones less;
    the causal kernels are not read; no kernel, no trace: None."""
    (entry,) = [m for m in manifest()["per_layer"] if m["name"] == ROOFLINE]
    assert entry == {"name": ROOFLINE, "unit": "%", "better": "higher", "source": "device_trace",
                     "layer": "compiled train step", "moves": "train_images_per_s_per_chip", "workloads": [CELL]}
    with open(os.path.join(LAYER_DIR, ROOFLINE + ".json")) as f:
        how = json.load(f)
    assert (how["reader"], how["module"]) == ("python", "attn_window_mxu_roofline_pct_train")
    pairs = roofline_window.window_pairs(8192, 512)
    assert pairs == 512 * 513 // 2 + (8192 - 512) * 512 and roofline_window.window_pairs(100, 512) == 100 * 101 // 2
    need = roofline_window.window_flops(1, 72, 8192, 128, 512)
    assert need == {"fwd": 4 * 128 * 72 * pairs, "bwd": 8 * 128 * 72 * pairs}
    peak = 197e12
    exact = [("window_attention_fwd.3", need["fwd"] / peak * 1e9), ("window_attention_bwd.1", need["bwd"] / peak * 1e9),
             ("window_attention_fwd", need["fwd"] / peak * 1e9)]
    assert roofline.share(exact, 1, 72, 8192, 128, 512, peak) == pytest.approx(100.0)
    slower = [(name, 2 * ns) for name, ns in exact] + [("causal_attention_fwd.2", 1.0), ("fusion.7", 5e6)]
    assert roofline.share(slower, 1, 72, 8192, 128, 512, peak) == pytest.approx(50.0)
    assert roofline.share([("causal_attention_bwd.4", 1e6)], 1, 72, 8192, 128, 512, peak) is None
    assert readers.python(types.SimpleNamespace(trace=None), "attn_window_mxu_roofline_pct_train") is None
    # a trace, but a configuration without a sliding layer: nothing to read
    ctx = types.SimpleNamespace(trace=types.SimpleNamespace(devices=[0]), config={"layer_types": ["mamba"]})
    assert roofline.read(ctx) is None


def test_the_cell_joins_the_accepted_metrics_of_a_token_cell_with_experts_and_no_kda_ssd_or_loop():
    per_layer = {m["name"]: m for m in manifest()["per_layer"]}
    for name in JOINED + [TIME, ROOFLINE]:
        assert per_layer[name]["workloads"][-1] == CELL, name
        assert per_layer[name]["moves"] == "train_images_per_s_per_chip"
    for name in NOT_JOINED:
        assert CELL not in per_layer[name]["workloads"], name
    assert [m["name"] for m in manifest()["per_layer"][-2:]] == [TIME, ROOFLINE]
    (throughput,) = [m for m in manifest()["end_to_end"] if m["name"] == "train_images_per_s_per_chip"]
    assert throughput["workloads"][-1] == CELL and throughput["bound"] == 0.01
    reported = {m["name"] for m in harness.metrics_of(manifest(), "per_layer", CELL)}
    assert not {n for n in reported if n.startswith("step.") and n not in JOINED}  # no CNN scope metric
    every_cell = {m["name"] for m in manifest()["per_layer"] if "workloads" not in m}
    assert reported == {*JOINED, TIME, ROOFLINE, *every_cell}


# -- the rehearsal run ---------------------------------------------------------


@pytest.fixture(scope="module")
def rehearsal():
    proc = subprocess.run([sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", str(2**31 + 41),
                           "--seconds", "1", "--trace", "0", "--rehearsal"], cwd=REPO, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_last_line_keys_and_checks(rehearsal):
    last = rehearsal[-1]
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert set(last["metrics"]) == {"train_images_per_s_per_chip", "setup_s"}
    run = next(ln["run"] for ln in rehearsal if "run" in ln)
    assert all(run["checks"].values()) and set(run["checks"]) >= {
        "losses_finite", "first_loss_near_its_initial_value", "loss_not_above_first", "no_assignment_dropped",
        "every_sliding_layer_is_counted", "every_attention_site_is_fused", "every_window_site_is_fused",
        "step_counter_advanced_by_attempted", "reference_saw_the_programs_initial_parameters",
        "first_step_agrees_with_the_float32_reference", "first_loss_agrees_with_the_reference_under_its_own_selection",
        "no_compile_in_window"}
    assert run["arch"] == "laguna"
    assert (run["attn_sites"], run["attn_window_sites"], run["attn_window_fused_sites"], run["moe_sites"]) == (
        5.0, 3.0, 0.0, 4.0)
    assert run["tokens_per_step"] == 32 and run["seq_len"] == 32 and run["moe_assignments_per_expert"] > 0
    assert run["macs_per_image"] > 0 and run["images_per_s_per_chip"] * 32 == pytest.approx(run["tokens_per_s"])
    assert run["reference"]["ok"] and set(run["reference"]["worst"]) == set(bench_ref.LIMITS)
    own = run["reference"]["own_selection"]["deviations"]
    assert set(own) == {"loss"} | {f"gnorm/layer_{i}/router" for i in range(1, 5)} and own["loss"] <= bench_ref.LIMITS["loss"]
    assert {"loss", "gnorm/embed", "gnorm/head", "gnorm/layer_0/mlp", "gnorm/layer_1/attn", "gnorm/layer_1/router",
            "gnorm/layer_4/experts", "change/layer_1/attn/gate", "change/layer_2/shared/sigmoid_gate"} <= set(
        run["reference"]["values"])
    notes = next(ln for ln in rehearsal if "setup_phases" in ln)
    assert notes["compile_window"]["compiles"] == 0 and notes["heavy_imports"] == []


# -- the reference ---------------------------------------------------------------


@pytest.fixture(scope="module")
def toy_step():
    """The toy model with weights that matter (init_std ten times the
    app's), a batch, and the package reference's scalars and gradients on
    them."""
    import jax

    from yet_another_mobilenet_series_tpu.models import lm_reference as package_ref

    net, lm = toy(32, **{"model.lm.init_std": 0.2})
    params, _ = net.init(jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, lm.seq_len + 2), 0, net.vocab)
    (loss, _), grads = jax.jit(lambda p: package_ref.laguna_loss_and_grads(p, tokens, package_ref.laguna_dims_of(lm)))(
        params)
    want = {"loss": loss, **bench_ref.group_norms(grads)}
    return net, lm, params, tokens, want, grads


ADAMW = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "clip": 1.0}


def reference_scalars(toy_step, operand_dtype=None, rows=8, chosen=None):
    net, lm, params, tokens, _, _ = toy_step
    sizes = types.SimpleNamespace(**{k: getattr(lm, k) for k in bench_ref.DIM_KEYS}, seq_len=lm.seq_len)
    return runner.reference_scalars(params, sizes, tokens, rows, ADAMW, chosen, operand_dtype)


def test_the_benchmarks_reference_is_the_packages_and_its_limits_catch_float8(toy_step):
    """In row blocks, a sequence at a time, under its own selection: the
    package reference's numbers (1e-4 of each); with float8_e4m3fn operands
    under the same selection it fails at least one limit."""
    import jax
    import jax.numpy as jnp

    net, lm, params, tokens, want, grads = toy_step
    own, shares = reference_scalars(toy_step)
    assert set(shares.values()) == {0.0} and len(shares) == 4  # its own selection: nothing differs
    same = bench_ref.compare(own, want)
    assert same["ok"] and max(same["deviations"].values()) < 1e-4, same
    assert {k for k in want if k.startswith("gnorm/")} == set(net.grad_scalars(grads))  # the step reports every compared group
    assert sum(k.startswith("change/") for k in own) == len(jax.tree.leaves(params))
    chosen = jax.jit(lambda p, ids: net.forward(p, {}, ids)[3])(params, tokens)
    low, low_shares = reference_scalars(toy_step, jnp.float8_e4m3fn, None, chosen)
    assert not runner.held_against(low, low_shares, own)["ok"]  # the nearest precision below bfloat16 fails a limit
    assert not bench_ref.compare({k: 0.0 for k in want}, want)["ok"]
    assert not bench_ref.compare({k: v for k, v in want.items() if k != "gnorm/layer_1/attn"}, want)["ok"]
    assert [bench_ref.kind_of(k) for k in ("loss", "gnorm/layer_1/attn", "gnorm/layer_1/router", "gnorm/layer_2/experts",
                                           "gnorm/layer_3/shared", "gnorm/head", "selection/layer_1", "change/embed")] == [
        "loss", "gnorm", "gnorm_router", "gnorm_experts", "gnorm", "gnorm", "selection", "change"]


def planted(fault: str, monkeypatch, net):
    """The program with one fault planted; returns the model to run."""
    import jax
    import jax.numpy as jnp

    from yet_another_mobilenet_series_tpu.config import RopeSpec
    from yet_another_mobilenet_series_tpu.ops import lm as ops

    lm = net.lm
    replace = lambda **kw: dataclasses.replace(net, lm=dataclasses.replace(lm, **kw))  # noqa: E731
    if fault == "no_window":  # the sliding layers attend causally over every position
        real = ops.causal_attention
        monkeypatch.setattr(ops, "causal_attention", lambda q, k, v, *, scale, block=None, window=None: real(
            q, k, v, scale=scale, block=block))
    elif fault == "the_window_one_position_short":
        return replace(sliding_window=lm.sliding_window - 1)
    elif fault == "no_gate":
        real = ops.mha_attention
        monkeypatch.setattr(ops, "mha_attention", lambda p, *a, **kw: real({k: v for k, v in p.items() if k != "gate"},
                                                                            *a, **kw))
    elif fault == "plain_rope_on_the_full_layers":
        rope = dataclasses.replace(lm.rope_parameters, full_attention=RopeSpec(rope_theta=500000.0))
        return replace(rope_parameters=rope)
    elif fault == "the_whole_head_turned_by_yarn":
        rope = dataclasses.replace(lm.rope_parameters, full_attention=dataclasses.replace(
            lm.rope_parameters.full_attention, partial_rotary_factor=1.0))
        return replace(rope_parameters=rope)
    elif fault == "no_attention_factor":
        rope = dataclasses.replace(lm.rope_parameters, full_attention=dataclasses.replace(
            lm.rope_parameters.full_attention, attention_factor=1.0))
        return replace(rope_parameters=rope)
    elif fault == "weights_not_renormalised":
        real = ops.route

        def route(router_w, bias, x, *, top_k, scaling, scoring="sigmoid_bias"):
            ids, weights, load = real(router_w, bias, x, top_k=top_k, scaling=scaling, scoring=scoring)
            scores = jax.nn.softmax(jnp.dot(x.astype(jnp.float32), router_w), axis=-1)
            return ids, jnp.take_along_axis(scores, ids, axis=-1) * scaling, load

        monkeypatch.setattr(ops, "route", route)
    elif fault == "no_routed_scaling":
        return replace(routed_scaling_factor=1.0)
    elif fault == "no_shared_gate":
        real = ops.gated_mlp
        monkeypatch.setattr(ops, "gated_mlp", lambda p, x: real({k: v for k, v in p.items() if k != "sigmoid_gate"}, x))
        net = dataclasses.replace(net)
        real_fed = type(net)._fed

        def fed(self, block, p, bias, x):
            if "shared" in p:
                p = {**p, "shared": {k: v for k, v in p["shared"].items() if k != "sigmoid_gate"}}
            return real_fed(self, block, p, bias, x)

        monkeypatch.setattr(type(net), "_fed", fed)
    elif fault == "half_the_tokens_in_the_loss":  # the mean over the batch's first half of tokens alone
        real_head_loss = type(net)._head_loss

        def head_loss(self, head_w, hidden, targets, per_token=False):
            half = hidden.shape[0] // 2
            return 2 * real_head_loss(self, head_w, hidden[:half], targets[:half], per_token)

        monkeypatch.setattr(type(net), "_head_loss", head_loss)
    elif fault == "query_head_i_reads_kv_head_i_mod_kv_heads":  # tiled, where each kv head serves a run of queries
        monkeypatch.setattr(ops, "jnp", types.SimpleNamespace(**{**vars(jnp), "repeat": lambda t, r, axis: jnp.concatenate(
            [t] * r, axis=axis)}))
    return net


FAULTS = [None, "no_window", "the_window_one_position_short", "no_gate", "plain_rope_on_the_full_layers",
          "the_whole_head_turned_by_yarn", "no_attention_factor", "weights_not_renormalised", "no_routed_scaling",
          "no_shared_gate", "half_the_tokens_in_the_loss", "query_head_i_reads_kv_head_i_mod_kv_heads"]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_faults_fail_the_comparison(toy_step, monkeypatch, fault):
    """The float32 program's first step against the benchmark's reference, as
    the runner compares them (the reference under the program's selection):
    sound, it passes every limit; each planted fault (the window dropped or
    one position short, the gates dropped, the full layers' YaRN replaced by
    the plain rotation, stretched over the whole head or without its
    attention factor, the routed weights not renormalised or not scaled, the
    shared expert's gate dropped, the loss taken over half the tokens, query
    heads mapped to the wrong key/value heads) fails at least one."""
    import jax

    net, lm, params, tokens, _, _ = toy_step
    faulty = planted(fault, monkeypatch, net)
    (loss, (_, scalars)), grads = jax.jit(jax.value_and_grad(
        lambda p: faulty.loss(p, {}, {"tokens": tokens}), has_aux=True))(params)
    chosen = jax.jit(lambda p, ids: faulty.forward(p, {}, ids)[3])(params, tokens)
    program = {"loss": loss, **scalars, **faulty.grad_scalars(grads)}
    reference, shares = reference_scalars(toy_step, chosen=chosen)
    program.update({k: v for k, v in reference.items() if k.startswith("change/")})  # the optimizer is not under test
    verdict = runner.held_against(program, shares, reference)
    assert verdict["ok"] is (fault is None), verdict["worst"]


def test_the_newest_cells_file_sees_the_whole_manifest_and_an_older_ones_its_own():
    """conftest.py of this directory cuts the manifest back to a file's own
    cell: for this file, the newest, that is the whole manifest; for the
    older `granite4hmicro_train_1x8k` everything appended with this cell is
    left out."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("cut", os.path.join(os.path.dirname(__file__), "conftest.py"))
    cut = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cut)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        whole = json.load(f)
    assert cut.cut_back_to(whole, CELL) == whole
    view = cut.cut_back_to(whole, "granite4hmicro_train_1x8k")
    assert [w["name"] for w in view["workloads"]][-1] == "granite4hmicro_train_1x8k" and len(view["workloads"]) == 7
    assert CONFIG not in [c["name"] for c in view["configs"]] and len(view["configs"]) == 6
    per_layer = {m["name"]: m for m in view["per_layer"]}
    assert not ({TIME, ROOFLINE} & set(per_layer))
    assert per_layer["lm.moe_experts_ms.train"]["workloads"] == ["glm47flash_train_2x8k", "kimilinear_train_1x16k"]
