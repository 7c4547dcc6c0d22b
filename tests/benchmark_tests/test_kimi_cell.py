"""The token training cell `kimilinear_train_1x16k` (CPU only, nothing timed):
its MAC count against the program's own `dot_general`s, the configuration
file against the catalog's published config and its arithmetic, every new
layer metric against its entry, file and reader, its `--rehearsal` run, the
benchmark's copy of the reference (benchmark/reference_kimi_linear.py) against
the package's (models/lm_reference.py), the comparison's limits against a
lower precision, and three planted faults that `compare` must see.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, macs_kimi_linear, readers, reference_kimi_linear as bench_ref  # noqa: E402
from benchmark.layer_metrics import step_scopes_kda, step_scopes_lm  # noqa: E402
from benchmark.runners import train_tokens_resident_kimi_linear as runner  # noqa: E402

CELL = "kimilinear_train_1x16k"
CONFIG = "kimi_linear_48b_ep32_share"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LAYER_DIR = os.path.join(REPO, "benchmark", "layer_metrics")
KDA_METRICS = sorted(step_scopes_kda.METRICS)
JOINED = ["host.dispatch_ms.train", "step.device_ms.train", "step.mfu.train", "coll.ms_per_step.train",
          "device.idle_share.train", "device.peak_hbm_gib.train", "moe.assignments_per_expert.train",
          "moe.load_max_over_mean.train", *step_scopes_lm.METRICS, step_scopes_lm.UNSCOPED_SHARE]


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
    `kda_core` scope: the recurrence counts at what the model requires
    (macs_kimi_linear.py), not at what the chunked form happens to multiply."""
    plain = ragged = 0
    for eqn in jaxpr.eqns:
        if "kda_core" in str(eqn.source_info.name_stack):
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


def test_macs_from_shapes_equal_the_programs_dot_generals(monkeypatch):
    """Everything but the routed experts and the recurrence: the program's
    forward over one sequence (latent attention in tiles of one row by one
    key, unrolled, so that exactly the causal pairs are `dot_general`s)
    against macs_kimi_linear.py's count from the configuration's keys."""
    import jax
    import jax.numpy as jnp

    from yet_another_mobilenet_series_tpu.ops import lm as ops

    def unrolled(lower, upper, body, carry):
        for i in range(lower, upper):
            carry = body(i, carry)
        return carry

    monkeypatch.setattr(ops, "ATTN_BLOCK", 1)
    monkeypatch.setattr(ops, "lax", types.SimpleNamespace(**{**vars(jax.lax), "fori_loop": unrolled}))
    net, lm = toy(12)
    params, state = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((1, lm.seq_len + 2), jnp.int32)
    plain, ragged = dot_macs(jax.make_jaxpr(lambda p, s, t: net.forward(p, s, t)[0])(params, state, tokens).jaxpr)
    la = lm.linear_attn_config
    keys = {k: getattr(lm, k) for k in (
        "hidden_size", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
        "first_k_dense_replace", "num_hidden_layers", "moe_intermediate_size", "intermediate_size",
        "num_experts_per_tok", "n_shared_experts")}
    keys.update(n_routed_experts=net.experts_held, vocab_size=net.vocab, linear_attn_config={
        "kda_layers": list(la.kda_layers), "head_dim": la.head_dim, "num_heads": la.num_heads})
    parts = macs_kimi_linear.parts(keys, lm.seq_len, lm.n_routed_experts)
    assert macs_kimi_linear.mixers(keys) == (net.kda_sites, 1) == (4, 1)
    routed, recurrence = parts.pop("routed_experts_expected"), parts.pop("kda_recurrence")
    assert plain == sum(parts.values())
    assert recurrence == net.kda_sites * lm.seq_len * la.num_heads * 3 * la.head_dim ** 2
    # the grouped matmuls are traced over every assignment's row; at the expected load 1 in 32 is held
    assert ragged == routed * lm.n_routed_experts // net.experts_held
    assert macs_kimi_linear.forward_macs(keys, lm.seq_len, lm.n_routed_experts) == plain + routed + recurrence


def test_the_configuration_file_is_the_published_config_and_its_arithmetic():
    """Every key of the catalog's `config` is in the file under the same name
    with the same value, but `num_hidden_layers` and `vocab_size` (in
    `reduced`, with `n_routed_experts`, the program's name for the experts
    HELD); nested groups whole."""
    config = config_file()
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f) if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
        assert config["source"] == row["source_url"]
        differing = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differing == {"num_hidden_layers", "vocab_size"}, differing
        assert row["config"]["num_hidden_layers"] == config["published"]["num_hidden_layers"] == 27
    assert config["published"]["n_routed_experts"] == config["num_experts"] == 256
    assert config["n_routed_experts"] * config["expert_shares"] == 256 and config["expert_shares"] == 32
    assert config["published"]["vocab_size"] == config["vocab_size"] * 8 == 163840
    assert (config["num_experts_per_tok"], config["n_shared_experts"]) == (
        config["num_experts_per_token"], config["num_shared_experts"]) == (8, 1)
    assert (config["hidden_size"], config["num_attention_heads"], config["q_lora_rank"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"], config["intermediate_size"],
            config["moe_intermediate_size"], config["routed_scaling_factor"], config["mla_use_nope"]) == (
        2304, 32, None, 512, 128, 64, 128, 9216, 1024, 2.446, True)
    la = config["linear_attn_config"]
    assert (la["head_dim"], la["num_heads"], la["short_conv_kernel_size"]) == (128, 32, 4)
    assert sorted(la["kda_layers"] + la["full_attn_layers"]) == list(range(1, 28))
    assert [n in la["kda_layers"] for n in range(1, 6)] == [True, True, True, False, True]  # the layers held
    parts = config["parameters_by_part"]
    assert parts["layer_1_kda_dense"] == parts["kda_mixer"] + parts["dense_mlp"] + parts["norms_a_layer"]
    expert_ffn = parts["router"] + parts["shared_expert"] + parts["routed_experts_held_a_layer"] + parts["norms_a_layer"]
    assert parts["expert_layer_with_kda_here"] == parts["kda_mixer"] + expert_ffn
    assert parts["expert_layer_with_mla_here"] == parts["mla_mixer"] + expert_ffn
    assert (parts["layer_1_kda_dense"] + 3 * parts["expert_layer_with_kda_here"] + parts["expert_layer_with_mla_here"]
            + parts["embedding_and_head"] + parts["final_norm"]) == config["parameters_here"] == 602_433_408
    assert (parts["layer_1_kda_dense"] + 19 * parts["published_expert_layer_with_kda"]
            + 7 * parts["published_expert_layer_with_mla"] + parts["published_embedding_head_final_norm"]
            ) == config["published"]["parameters"] == 49_122_675_072
    assert macs_kimi_linear.forward_macs(config, 16384, 256) == 6_975_916_081_152  # one sequence: the cell's macs_per_image
    shares = {k: v / 6_975_916_081_152 for k, v in macs_kimi_linear.parts(config, 16384, 256).items()}
    assert 0.37 < shares["kda_proj"] < 0.38 and 0.19 < shares["attn_core"] < 0.20 and 0.014 < shares["kda_recurrence"] < 0.016
    (entry,) = [c for c in manifest()["configs"] if c["name"] == config["name"]]
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_the_cell_is_the_issues_and_its_traffic_is_one_16k_document():
    (cell,) = [w for w in manifest()["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train_tokens_resident_1x16k", 1)
    assert len(cell["why"]) <= 200
    with open(os.path.join(REPO, "benchmark", "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["runner"], traffic["sequences_per_chip"], traffic["seq_len"], traffic["zipf_exponent"],
            traffic["warm_steps"], traffic["sync_every"], traffic["sync_lag"], traffic["trace_for_s"]) == (
        "train_tokens_resident_kimi_linear", 1, 16384, 1.0, 1, 1, 1, 3.0)
    assert config_file()["overrides"] == {"schedule.warmup_epochs": 0.0, "schedule.base_lr": 1e-6}
    assert sum(w["chips"] == 4 for w in manifest()["workloads"]) == 1  # the benchmark keeps its one four-chip cell


# -- the layer metrics -----------------------------------------------------------


@pytest.mark.parametrize("name", KDA_METRICS)
def test_each_new_metric_has_its_entry_its_file_and_its_reader(name):
    (entry,) = [m for m in manifest()["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "ms", "better": "lower", "source": "device_trace",
                     "layer": "compiled train step", "moves": "train_images_per_s_per_chip", "workloads": [CELL]}
    with open(os.path.join(LAYER_DIR, name + ".json")) as f:
        how = json.load(f)
    assert how["reader"] == "python" and os.path.exists(os.path.join(LAYER_DIR, how["module"] + ".py"))
    # nothing to read (no trace: a CPU rehearsal, or a parent without the family): None, never a raise
    ctx = types.SimpleNamespace(trace=None)
    assert readers.python(ctx, how["module"]) is None
    # a table without a kda row (a model, or a program, without KDA): None too
    ctx = types.SimpleNamespace(step_scopes_lm={"metrics": {}, "table": {"ms_per_step": {"attn_core.fwd": 3.0}}})
    assert readers.python(ctx, how["module"]) is None


def test_the_kda_metrics_sum_the_kda_rows_of_the_table_the_lm_reader_made():
    """No second compile, no second trace read: the rows of `ctx.step_scopes_lm`."""
    rows = {"kda_core.fwd": 5.0, "kda_core.bwd": 11.0, "kda_core.-": 1.0, "kda_proj.fwd": 2.0, "kda_proj.bwd": 4.0,
            "kda_conv.fwd": 0.5, "kda_gate.bwd": 0.25, "kda_norm.fwd": 0.125, "attn_core.fwd": 3.0, "mlp.bwd": 9.0}
    ctx = types.SimpleNamespace(step_scopes_lm={"metrics": {}, "table": {"ms_per_step": rows}})
    got = {name: step_scopes_kda.metric(ctx, name) for name in KDA_METRICS}
    assert got == {"lm.kda_core_ms.train": 17.0, "lm.kda_proj_ms.train": 6.0, "lm.kda_pointwise_ms.train": 0.875}
    from yet_another_mobilenet_series_tpu.obs import scopes

    assert {s for names in step_scopes_kda.METRICS.values() for s in names} == {s for s in scopes.SCOPES if s.startswith("kda_")}


def test_the_cell_joins_the_accepted_metrics():
    per_layer = {m["name"]: m for m in manifest()["per_layer"]}
    for name in JOINED + KDA_METRICS:
        assert CELL in per_layer[name]["workloads"], name
        assert per_layer[name]["moves"] == "train_images_per_s_per_chip"
    (throughput,) = [m for m in manifest()["end_to_end"] if m["name"] == "train_images_per_s_per_chip"]
    assert throughput["workloads"][-1] == CELL and throughput["bound"] == 0.01
    reported = {m["name"] for m in harness.metrics_of(manifest(), "per_layer", CELL)}
    assert not {n for n in reported if n.startswith("step.") and n not in JOINED}  # no CNN scope metric


# -- the rehearsal run ---------------------------------------------------------


@pytest.fixture(scope="module")
def rehearsal():
    proc = subprocess.run([sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", str(2**31 + 33),
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
        "step_counter_advanced_by_attempted", "reference_saw_the_programs_initial_parameters",
        "first_step_agrees_with_the_float32_reference", "no_compile_in_window"}
    assert run["arch"] == "kimi_linear" and run["kda_sites"] == 4 and run["first_ce_mtp"] is None
    assert run["moe_dropped"] == 0.0 and run["tokens_per_step"] == 32 and run["seq_len"] == 32
    assert run["kda_min_chunk_log_decay"] < 0.0
    assert run["macs_per_image"] > 0 and run["images_per_s_per_chip"] * 32 == pytest.approx(run["tokens_per_s"])
    assert run["reference"]["ok"] and set(run["reference"]["worst"]) == set(bench_ref.LIMITS)
    assert any(k.startswith("gnorm/") and k.endswith("/kda") for k in run["reference"]["values"])
    notes = next(ln for ln in rehearsal if "setup_phases" in ln)
    assert notes["compile_window"]["compiles"] == 0 and notes["heavy_imports"] == []


# -- the reference ---------------------------------------------------------------


@pytest.fixture(scope="module")
def toy_step():
    """The toy model, its seed's parameters, biases that matter, a batch, and
    the package reference's loss and gradients on them."""
    import jax

    from yet_another_mobilenet_series_tpu.models import lm_reference as package_ref

    # weights ten times the app's: at N(0, 0.02) and 64 channels every softmax is uniform, and no fault in what
    # feeds the scores could be seen
    net, lm = toy(32, **{"model.lm.init_std": 0.2})
    params, state = net.init(jax.random.PRNGKey(3))
    state = jax.tree.map(lambda b: 0.05 * jax.random.normal(jax.random.PRNGKey(5), b.shape), state)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, lm.seq_len + 2), 0, net.vocab)
    (_, aux), grads = jax.jit(lambda p: package_ref.loss_and_grads(p, state, tokens, package_ref.dims_of(lm)))(params)
    return net, lm, params, state, tokens, {"ce": aux["ce"], **bench_ref.group_norms(grads)}, grads


ADAMW = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "clip": 1.0}


def reference_scalars(toy_step, operand_dtype=None, rows=8, chosen=None):
    net, lm, params, state, tokens, _, _ = toy_step
    sizes = types.SimpleNamespace(**{k: getattr(lm, k) for k in bench_ref.DIM_KEYS}, seq_len=lm.seq_len)
    return runner.reference_scalars(params, state, sizes, tokens, rows, ADAMW, chosen, operand_dtype)


def test_the_benchmarks_reference_is_the_packages_and_its_limits_catch_float8(toy_step):
    import jax
    import jax.numpy as jnp

    net, lm, params, state, tokens, want, grads = toy_step
    own, shares = reference_scalars(toy_step)  # in row blocks, the recurrence as two scans: the same numbers
    same = bench_ref.compare(own, want)
    assert same["ok"] and max(same["deviations"].values()) < 1e-4, same
    assert set(want) == set(net.grad_scalars(grads)) | {"ce"}  # the step reports every compared group
    assert sum(k.endswith("/kda") for k in want) == 4 and sum(k.endswith("/attn") for k in want) == 1
    assert set(shares) == {f"selection/layer_{i}" for i in (1, 2, 3, 4)} and not any(shares.values())
    assert sum(k.startswith("change/") for k in own) == len(jax.tree.leaves(params))
    # the program's selection fed back: its own float32 forward chooses as the reference does
    chosen = jax.jit(lambda p: net.forward(p, state, tokens)[3])(params)
    fed, shares = reference_scalars(toy_step, chosen=chosen)
    assert runner.held_against(fed, shares, own)["ok"] and not any(shares.values())
    low = bench_ref.compare(reference_scalars(toy_step, jnp.float8_e4m3fn, None)[0], want)
    assert not low["ok"], low  # the nearest precision below bfloat16 fails at least one limit
    assert not bench_ref.compare({k: 0.0 for k in want}, want)["ok"]
    assert not bench_ref.compare({k: v for k, v in want.items() if k != "gnorm/layer_0/kda"}, want)["ok"]
    assert bench_ref.kind_of("gnorm/layer_2/kda") == "gnorm_kda" and bench_ref.kind_of("gnorm/layer_3/attn") == "gnorm"


def planted(fault: str, monkeypatch, net):
    """The program with one fault planted; returns the model to run."""
    import dataclasses

    import jax.numpy as jnp

    from yet_another_mobilenet_series_tpu.ops import lm_kda

    if fault == "a_clamped_decay":  # a log decay floored at -0.02 a position: what a kernel that feared e^-G would do
        real = lm_kda.kda_core
        monkeypatch.setattr(lm_kda, "kda_core", lambda q, k, v, g, beta: real(q, k, v, jnp.maximum(g, -0.02), beta))
    elif fault == "a_conv_that_peeks_one_position_ahead":
        real = lm_kda.short_conv
        monkeypatch.setattr(lm_kda, "short_conv", lambda z, w: real(jnp.roll(z, -1, axis=1).at[:, -1].set(0.0), w))
    elif fault == "rope_applied_in_the_mla_block":
        return dataclasses.replace(net, lm=dataclasses.replace(net.lm, mla_use_nope=False))
    return net


@pytest.mark.parametrize("fault", [None, "a_clamped_decay", "a_conv_that_peeks_one_position_ahead",
                                   "rope_applied_in_the_mla_block"])
def test_planted_faults_fail_the_comparison(toy_step, monkeypatch, fault):
    """The float32 program's first step against the benchmark's reference,
    as the runner compares them: sound, it passes every limit; a decay that
    is clamped, a short convolution that reads position t + 1, a rotation in
    the latent-attention block each fail at least one."""
    import jax

    net, lm, params, state, tokens, _, _ = toy_step
    faulty = planted(fault, monkeypatch, net)
    (_, (_, scalars)), grads = jax.jit(jax.value_and_grad(
        lambda p: faulty.loss(p, state, {"tokens": tokens}), has_aux=True))(params)
    program = {"ce": scalars["ce"], **faulty.grad_scalars(grads)}
    chosen = jax.jit(lambda p: faulty.forward(p, state, tokens)[3])(params)
    reference, shares = reference_scalars(toy_step, chosen=chosen)
    program.update({k: v for k, v in reference.items() if k.startswith("change/")})  # the optimizer is not under test
    verdict = runner.held_against(program, shares, reference)
    assert verdict["ok"] is (fault is None), verdict["worst"]


def test_an_older_cells_file_sees_the_manifest_cut_back_to_its_cell_and_nothing_less():
    """conftest.py of this directory: what PR 33 appended is left out of the
    view `test_glm_cell.py` checks (its cell is the last of four again, its
    metrics list it alone), what a PR put BEFORE or took away still shows."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("cut", os.path.join(os.path.dirname(__file__), "conftest.py"))
    cut = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cut)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        whole = json.load(f)
    view = cut.cut_back_to(whole, "glm47flash_train_2x8k")
    assert [w["name"] for w in view["workloads"]][-1] == "glm47flash_train_2x8k" and len(view["workloads"]) == 4
    assert CONFIG not in [c["name"] for c in view["configs"]]
    per_layer = {m["name"]: m for m in view["per_layer"]}
    assert not set(KDA_METRICS) & set(per_layer) and per_layer["lm.attn_core_ms.train"]["workloads"] == ["glm47flash_train_2x8k"]
    assert cut.cut_back_to(whole, CELL) == whole  # the newest cell's file sees everything
    # a cell put FIRST is not cut away, nor is a name put before the cell's in a list
    moved = {**whole, "workloads": [whole["workloads"][-1], *whole["workloads"][:-1]]}
    assert len(cut.cut_back_to(moved, "glm47flash_train_2x8k")["workloads"]) == 5
