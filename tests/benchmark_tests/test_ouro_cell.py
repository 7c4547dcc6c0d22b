"""The token training cell `ouro26b_train_1x8k` (CPU only, nothing timed): its
MAC count against the program's own `dot_general`s, the configuration file
against the catalog's published config and its arithmetic, every new layer
metric against its entry, file and reader, its `--rehearsal` run, the
benchmark's copy of the reference (benchmark/reference_ouro.py) against the
package's (models/lm_reference.py `ouro_*`), the comparison's limits against a
lower precision, and four planted faults that `compare` must see.
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

from benchmark import harness, macs_ouro, readers, reference_ouro as bench_ref  # noqa: E402
from benchmark.layer_metrics import step_scopes_lm, step_scopes_ouro  # noqa: E402
from benchmark.runners import train_tokens_resident_ouro as runner  # noqa: E402

CELL = "ouro26b_train_1x8k"
CONFIG = "ouro_2_6b_depth8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LAYER_DIR = os.path.join(REPO, "benchmark", "layer_metrics")
TIMES = sorted(step_scopes_ouro.METRICS)
COUNTERS = {"loop.layer_applications.train": ("count", "layer_applications"),
            "loop.expected_exit_step.train": ("steps", "expected_exit_step")}
JOINED = ["host.dispatch_ms.train", "step.device_ms.train", "step.mfu.train", "coll.ms_per_step.train",
          "device.idle_share.train", "device.peak_hbm_gib.train", "lm.attn_core_ms.train", "lm.dense_ms.train",
          "lm.head_loss_ms.train", "lm.optim_ms.train", step_scopes_lm.UNSCOPED_SHARE]
NOT_JOINED = ["lm.moe_experts_ms.train", "lm.moe_route_ms.train", "moe.assignments_per_expert.train",
              "moe.load_max_over_mean.train", "lm.kda_core_ms.train", "lm.kda_proj_ms.train", "lm.kda_pointwise_ms.train"]


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


def dot_macs(jaxpr, times: int = 1) -> int:
    """MACs of every dot_general in a jaxpr, through scans, remats and calls."""
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            total += times * math.prod(eqn.outvars[0].aval.shape) * math.prod(
                eqn.invars[0].aval.shape[i] for i in contract)
        inner = times * eqn.params.get("length", 1) if name == "scan" else times
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    total += dot_macs(sub, inner)
    return total


def test_macs_from_shapes_equal_the_programs_dot_generals(monkeypatch):
    """The program's forward over one sequence (attention in tiles of one row
    by one key, unrolled, so that exactly the causal pairs are `dot_general`s;
    all four loop steps, their heads and their gates) against macs_ouro.py's
    count from the configuration's keys."""
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
    counted = dot_macs(jax.make_jaxpr(lambda p, t: net.loss(p, state, {"tokens": t})[0])(params, tokens).jaxpr)
    keys = {k: getattr(lm, k) for k in ("hidden_size", "num_attention_heads", "head_dim", "intermediate_size",
                                        "num_hidden_layers", "total_ut_steps")}
    keys["vocab_size"] = net.vocab
    parts = macs_ouro.parts(keys, lm.seq_len)
    assert counted == sum(parts.values()) == macs_ouro.forward_macs(keys, lm.seq_len)
    assert parts["attn_core"] == net.layer_applications * (12 * 13 // 2) * lm.num_attention_heads * 2 * lm.head_dim
    assert parts["lm_head"] == 4 * 12 * lm.hidden_size * net.vocab and parts["exit_gate"] == 4 * 12 * lm.hidden_size


def test_the_configuration_file_is_the_published_config_and_its_arithmetic():
    """Every key of the catalog's `config` is in the file under the same name
    with the same value, but `num_hidden_layers` (in `reduced`); the nested
    `layer_types` whole."""
    config = config_file()
    assert config["reduced"] == ["num_hidden_layers"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B"]
        assert config["source"] == row["source_url"]
        differing = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differing == {"num_hidden_layers"}, differing
        assert row["config"]["num_hidden_layers"] == config["published"]["num_hidden_layers"] == 48
    assert (config["num_hidden_layers"], config["total_ut_steps"], config["early_exit_threshold"]) == (8, 4, 1)
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["vocab_size"], config["rope_theta"], config["rms_norm_eps"],
            config["model_type"], config["tie_word_embeddings"]) == (
        2048, 16, 16, 128, 5632, 49152, 1000000, 1e-6, "ouro", False)
    assert len(config["layer_types"]) == 48 and set(config["layer_types"]) == {"full_attention"}
    # the family's keys say "none": no latent, no expert layer, no MTP module
    assert (config["first_k_dense_replace"], config["n_routed_experts"], config["published"]["n_routed_experts"],
            config["num_experts_per_tok"], config["n_shared_experts"], config["moe_intermediate_size"],
            config["kv_lora_rank"], config["q_lora_rank"], config["num_nextn_predict_layers"], config["expert_shares"]) == (
        8, 0, 0, 0, 0, 0, 0, None, 0, 1)
    parts = config["parameters_by_part"]
    assert parts["layer"] == parts["attention_a_layer"] + parts["mlp_a_layer"] + parts["norms_a_layer"] == 51_388_416
    assert parts["layers_held"] == 8 * parts["layer"] == 411_107_328 and parts["embedding_and_head"] == 201_326_592
    assert (parts["layers_held"] + parts["embedding_and_head"] + parts["final_norm"] + parts["exit_gate"]
            ) == config["parameters_here"] == 612_438_017
    assert 48 * parts["layer"] + parts["embedding_and_head"] + parts["final_norm"] == config["published"]["parameters"]
    assert config["bytes"]["parameters_gradients_moments_gb"] == round(16 * 612_438_017 / 1e9, 2) == 9.8
    assert config["exit_entropy_weight"] == 0.1 and any("beta" in line for line in config["assumed"])
    assert macs_ouro.forward_macs(config, 8192) == 21_166_202_814_464  # one sequence: the cell's macs_per_image
    shares = {k: v / 21_166_202_814_464 for k, v in macs_ouro.parts(config, 8192).items()}
    assert 0.155 < shares["lm_head"] < 0.157 and 0.20 < shares["attn_core"] < 0.21 and 0.42 < shares["mlp"] < 0.43
    (entry,) = [c for c in manifest()["configs"] if c["name"] == config["name"]]
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_the_cell_is_the_issues_and_its_traffic_is_one_8k_document():
    (cell,) = [w for w in manifest()["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train_tokens_resident_ouro_1x8k", 1)
    assert len(cell["why"]) <= 200 and manifest()["workloads"][-1] == cell
    with open(os.path.join(REPO, "benchmark", "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["runner"], traffic["sequences_per_chip"], traffic["seq_len"], traffic["zipf_exponent"],
            traffic["warm_steps"], traffic["sync_every"], traffic["sync_lag"], traffic["trace_for_s"],
            traffic["reference_rows_at_once"], traffic["rehearsal"]["seq_len"]) == (
        "train_tokens_resident_ouro", 1, 8192, 1.0, 1, 1, 1, 4.0, 512, 32)
    assert config_file()["overrides"] == {"schedule.warmup_epochs": 0.0, "schedule.base_lr": 1e-6}
    assert sum(w["chips"] == 4 for w in manifest()["workloads"]) == 1  # the benchmark keeps its one four-chip cell
    assert len(manifest()["configs"]) == 5 and len(manifest()["workloads"]) == 6


# -- the layer metrics -----------------------------------------------------------


@pytest.mark.parametrize("name", TIMES)
def test_each_new_time_has_its_entry_its_file_and_its_reader(name):
    (entry,) = [m for m in manifest()["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "ms", "better": "lower", "source": "device_trace",
                     "layer": "compiled train step", "moves": "train_images_per_s_per_chip", "workloads": [CELL]}
    with open(os.path.join(LAYER_DIR, name + ".json")) as f:
        how = json.load(f)
    assert how["reader"] == "python" and os.path.exists(os.path.join(LAYER_DIR, how["module"] + ".py"))
    # nothing to read (no trace: a CPU rehearsal, or a parent without the family): None, never a raise
    assert readers.python(types.SimpleNamespace(trace=None), how["module"]) is None
    # a table without an exit_gate row (a model, or a program, without the loop): None too
    ctx = types.SimpleNamespace(step_scopes_lm={"metrics": {}, "table": {"ms_per_step": {"norm.fwd": 3.0}}})
    assert readers.python(ctx, how["module"]) is None


@pytest.mark.parametrize("name", sorted(COUNTERS))
def test_each_new_counter_has_its_entry_and_its_file_and_reads_a_fact_of_the_run(name):
    unit, key = COUNTERS[name]
    (entry,) = [m for m in manifest()["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": "higher", "source": "program_counter",
                     "layer": "compiled train step", "moves": "train_images_per_s_per_chip", "workloads": [CELL]}
    with open(os.path.join(LAYER_DIR, name + ".json")) as f:
        how = json.load(f)
    assert (how["reader"], how["key"]) == ("fact", key)
    assert readers.fact(types.SimpleNamespace(result={"facts": {key: 32.0}}), key) == 32.0
    assert readers.fact(types.SimpleNamespace(result={"facts": {}}), key) is None  # a program without it: left out


def test_the_two_times_sum_rows_of_the_table_the_lm_reader_made():
    """No second compile, no second trace read: the rows of `ctx.step_scopes_lm`."""
    rows = {"exit_gate.fwd": 0.5, "exit_gate.bwd": 0.25, "exit_gate.-": 0.125, "norm.fwd": 8.0, "norm.bwd": 16.0,
            "residual.fwd": 2.0, "residual.bwd": 1.0, "attn_core.fwd": 3.0, "mlp.bwd": 9.0, "rope.fwd": 4.0}
    ctx = types.SimpleNamespace(step_scopes_lm={"metrics": {}, "table": {"ms_per_step": rows}})
    got = {name: step_scopes_ouro.metric(ctx, name) for name in TIMES}
    assert got == {"lm.exit_gate_ms.train": 0.875, "lm.norm_residual_ms.train": 27.0}
    from yet_another_mobilenet_series_tpu.obs import scopes

    mine = {s for names in step_scopes_ouro.METRICS.values() for s in names}
    assert mine <= set(scopes.SCOPES) and not mine & {s for names in step_scopes_lm.METRICS.values() for s in names}


def test_the_cell_joins_the_accepted_metrics_of_a_token_cell_without_experts_or_kda():
    per_layer = {m["name"]: m for m in manifest()["per_layer"]}
    for name in JOINED + TIMES + sorted(COUNTERS):
        assert per_layer[name]["workloads"][-1] == CELL, name
        assert per_layer[name]["moves"] == "train_images_per_s_per_chip"
    for name in NOT_JOINED:
        assert CELL not in per_layer[name]["workloads"], name
    (throughput,) = [m for m in manifest()["end_to_end"] if m["name"] == "train_images_per_s_per_chip"]
    assert throughput["workloads"][-1] == CELL and throughput["bound"] == 0.01
    reported = {m["name"] for m in harness.metrics_of(manifest(), "per_layer", CELL)}
    assert not {n for n in reported if n.startswith("step.") and n not in JOINED}  # no CNN scope metric
    assert reported == {*JOINED, *TIMES, *COUNTERS, "entry.compile_s", "entry.cache_misses", "entry.program_compile_s",
                        "entry.program_compiles_in_window"}


# -- the rehearsal run ---------------------------------------------------------


@pytest.fixture(scope="module")
def rehearsal():
    proc = subprocess.run([sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", str(2**31 + 35),
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
        "losses_finite", "first_loss_near_its_initial_value", "loss_not_above_first", "every_layer_runs_every_loop_step",
        "every_attention_site_is_fused", "step_counter_advanced_by_attempted",
        "reference_saw_the_programs_initial_parameters", "first_step_agrees_with_the_float32_reference",
        "no_compile_in_window"}
    assert run["arch"] == "ouro" and len(run["first_ce_steps"]) == 4
    assert (run["loop_steps"], run["layer_applications"], run["attn_sites"]) == (4.0, 8.0, 2.0)
    assert run["tokens_per_step"] == 32 and run["seq_len"] == 32
    assert 1.8 < run["expected_exit_step"] < 1.95 and 0.1 < run["exit_p_last"] < 0.15  # a gate a few steps from zero
    assert run["macs_per_image"] > 0 and run["images_per_s_per_chip"] * 32 == pytest.approx(run["tokens_per_s"])
    assert run["reference"]["ok"] and set(run["reference"]["worst"]) == set(bench_ref.LIMITS)
    assert {"loss", "ce_step_4", "exit_entropy", "gnorm/exit_gate", "gnorm/layer_1/norms", "change/exit_gate/w",
            "change/layer_0/attn_out_norm"} <= set(run["reference"]["values"])
    notes = next(ln for ln in rehearsal if "setup_phases" in ln)
    assert notes["compile_window"]["compiles"] == 0 and notes["heavy_imports"] == []


# -- the reference ---------------------------------------------------------------


@pytest.fixture(scope="module")
def toy_step():
    """The toy model, its seed's parameters with a gate that matters, a batch,
    and the package reference's scalars and gradients on them."""
    import jax
    import jax.numpy as jnp

    from yet_another_mobilenet_series_tpu.models import lm_reference as package_ref

    # weights ten times the app's: at N(0, 0.02) and 64 channels every softmax is uniform, and no fault in what
    # feeds the scores could be seen
    net, lm = toy(32, **{"model.lm.init_std": 0.2})
    params, _ = net.init(jax.random.PRNGKey(3))
    params["exit_gate"] = {"w": 0.3 * jax.random.normal(jax.random.PRNGKey(5), (lm.hidden_size,)), "b": jnp.float32(0.2)}
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, lm.seq_len + 2), 0, net.vocab)
    (loss, aux), grads = jax.jit(lambda p: package_ref.ouro_loss_and_grads(p, tokens, package_ref.ouro_dims_of(lm)))(params)
    want = {"loss": loss, **{f"ce_step_{r + 1}": c for r, c in enumerate(aux["ce_step"])},
            **{k: aux[k] for k in runner.EXIT_SCALARS}, **bench_ref.group_norms(grads)}
    return net, lm, params, tokens, want, grads


ADAMW = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "clip": 1.0}


def reference_scalars(toy_step, operand_dtype=None, rows=8):
    net, lm, params, tokens, _, _ = toy_step
    sizes = types.SimpleNamespace(**{k: getattr(lm, k) for k in bench_ref.DIM_KEYS}, seq_len=lm.seq_len)
    return runner.reference_scalars(params, sizes, tokens, rows, ADAMW, operand_dtype)


def test_the_benchmarks_reference_is_the_packages_and_its_limits_catch_float8(toy_step):
    import jax
    import jax.numpy as jnp

    net, lm, params, tokens, want, grads = toy_step
    own = reference_scalars(toy_step)  # in row blocks, a sequence at a time: the same numbers
    same = bench_ref.compare(own, want)
    assert same["ok"] and max(same["deviations"].values()) < 1e-4, same
    assert {k for k in want if k.startswith("gnorm/")} == set(net.grad_scalars(grads))  # the step reports every compared group
    assert sum(k.startswith("change/") for k in own) == len(jax.tree.leaves(params)) == 2 * 11 + 5
    low = bench_ref.compare(reference_scalars(toy_step, jnp.float8_e4m3fn, None), want)
    assert not low["ok"], low  # the nearest precision below bfloat16 fails at least one limit
    assert not bench_ref.compare({k: 0.0 for k in want}, want)["ok"]
    assert not bench_ref.compare({k: v for k, v in want.items() if k != "gnorm/exit_gate"}, want)["ok"]
    assert [bench_ref.kind_of(k) for k in ("loss", "ce_step_3", "exit_p_last", "expected_exit_step", "gnorm/exit_gate",
                                           "gnorm/layer_1/attn", "change/exit_gate/b")] == [
        "loss", "ce_step", "exit", "exit", "gnorm_exit_gate", "gnorm", "change"]


def planted(fault: str, monkeypatch, net):
    """The program with one fault planted; returns the model to run."""
    import jax
    import jax.numpy as jnp

    from yet_another_mobilenet_series_tpu.models import lm as lm_module
    from yet_another_mobilenet_series_tpu.ops import lm as ops

    if fault == "a_loop_step_dropped":  # three runs of the layers, the fourth step's head on the third's state
        return dataclasses.replace(net, lm=dataclasses.replace(net.lm, total_ut_steps=3))
    if fault == "betas_sign":
        return dataclasses.replace(net, lm=dataclasses.replace(net.lm, exit_entropy_weight=-net.lm.exit_entropy_weight))
    if fault == "the_gates_gradient_cut":  # the exit distribution as constants: the gate never learns
        real = lm_module.TokenModel._expected_loss
        monkeypatch.setattr(lm_module.TokenModel, "_expected_loss",
                            lambda self, nll, logits: real(self, nll, jax.lax.stop_gradient(logits)))
    elif fault == "the_final_norm_outside_the_loop":  # step r + 1 reads the UN-normed state; the head still reads the normed one

        def looped(self, params, tokens, compute_dtype):
            c = self.lm
            seq = tokens.shape[1] - 2
            cos, sin = ops.rope_tables(seq, c.head_dim, c.rope_theta)
            y = params["embed"][tokens[:, :seq]].astype(compute_dtype)
            outs = []
            for _ in range(c.total_ut_steps):
                for name in self.block_names:
                    y = self._sandwich(params[name], y, cos, sin)
                flat = ops.rms_norm(y, params["final_norm"], c.rms_norm_eps).reshape(-1, c.hidden_size)
                totals, nll = self._head_loss(params["head"], flat, tokens[:, 1:seq + 1].reshape(-1), per_token=True)
                outs.append((totals, nll, flat @ params["exit_gate"]["w"] + params["exit_gate"]["b"]))
            return tuple(jnp.stack(o) for o in zip(*outs))

        monkeypatch.setattr(lm_module.TokenModel, "_looped", looped)
    return net


@pytest.mark.parametrize("fault", [None, "a_loop_step_dropped", "the_gates_gradient_cut",
                                   "the_final_norm_outside_the_loop", "betas_sign"])
def test_planted_faults_fail_the_comparison(toy_step, monkeypatch, fault):
    """The float32 program's first step against the benchmark's reference, as
    the runner compares them: sound, it passes every limit; a loop step
    dropped, a gate cut off from the loss, a final norm moved outside the loop
    and an entropy term of the wrong sign each fail at least one."""
    import jax

    net, lm, params, tokens, _, _ = toy_step
    faulty = planted(fault, monkeypatch, net)
    (loss, (_, scalars)), grads = jax.jit(jax.value_and_grad(
        lambda p: faulty.loss(p, {}, {"tokens": tokens}), has_aux=True))(params)
    program = {"loss": loss, **scalars, **faulty.grad_scalars(grads)}
    program.setdefault("ce_step_4", program["ce_step_3"])  # a program that ran three steps and reports four
    reference = reference_scalars(toy_step)
    program.update({k: v for k, v in reference.items() if k.startswith("change/")})  # the optimizer is not under test
    verdict = bench_ref.compare(program, reference)
    assert verdict["ok"] is (fault is None), verdict["worst"]


def test_the_newest_cells_file_sees_the_whole_manifest_and_an_older_ones_its_own():
    """conftest.py of this directory cuts the manifest back to a file's own
    cell: for this file, the newest, that is the whole manifest; for PR 33's
    (`kimilinear_train_1x16k`) everything this PR appended is left out."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("cut", os.path.join(os.path.dirname(__file__), "conftest.py"))
    cut = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cut)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        whole = json.load(f)
    assert cut.cut_back_to(whole, CELL) == whole
    view = cut.cut_back_to(whole, "kimilinear_train_1x16k")
    assert [w["name"] for w in view["workloads"]][-1] == "kimilinear_train_1x16k" and len(view["workloads"]) == 5
    assert CONFIG not in [c["name"] for c in view["configs"]] and len(view["configs"]) == 4
    per_layer = {m["name"]: m for m in view["per_layer"]}
    assert not (set(TIMES) | set(COUNTERS)) & set(per_layer)
    assert per_layer["lm.attn_core_ms.train"]["workloads"] == ["glm47flash_train_2x8k", "kimilinear_train_1x16k"]
