"""The token training cell `granite4hmicro_train_1x8k` (CPU only, nothing
timed): its MAC count against the program's own `dot_general`s, the
configuration file against the catalog's published config and its arithmetic,
every new layer metric against its entry, file and reader (the conv kernels'
roofline share under 100% by construction), its `--rehearsal` run, the
benchmark's copy of the reference (benchmark/reference_granite.py) against the
package's (models/lm_reference.py `granite_*`), the comparison's limits
against a lower precision, and the planted faults that `compare` must see.
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

from benchmark import harness, macs_granite, readers, reference_granite as bench_ref, roofline_ssd  # noqa: E402
from benchmark.layer_metrics import ssd_conv_hbm_roofline_pct_train as roofline  # noqa: E402
from benchmark.layer_metrics import step_scopes_lm, step_scopes_ssd  # noqa: E402
from benchmark.runners import train_tokens_resident_granite as runner  # noqa: E402

CELL = "granite4hmicro_train_1x8k"
CONFIG = "granite_4_0_h_micro_depth10"
LAYER_DIR = os.path.join(REPO, "benchmark", "layer_metrics")
TIMES = sorted(step_scopes_ssd.METRICS)
ROOFLINE = "ssd.conv_hbm_roofline_pct.train"
JOINED = ["host.dispatch_ms.train", "step.device_ms.train", "step.mfu.train", "coll.ms_per_step.train",
          "device.idle_share.train", "device.peak_hbm_gib.train", "lm.attn_core_ms.train", "lm.dense_ms.train",
          "lm.head_loss_ms.train", "lm.optim_ms.train", step_scopes_lm.UNSCOPED_SHARE, "host.gc_pause_ms.train",
          "host.gc_max_pause_ms.train"]
NOT_JOINED = ["lm.moe_experts_ms.train", "lm.moe_route_ms.train", "moe.assignments_per_expert.train",
              "moe.load_max_over_mean.train", "lm.kda_core_ms.train", "lm.kda_proj_ms.train", "lm.kda_pointwise_ms.train",
              "lm.exit_gate_ms.train", "lm.norm_residual_ms.train", "loop.layer_applications.train",
              "loop.expected_exit_step.train"]


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
    the SSD core stood in for by its input, so that what is left is the
    projections, attention, the MLPs and the tied head) against
    macs_granite.py's count from the configuration's keys, less the two kinds
    of work that are not the program's matmuls: the depthwise convolution and
    the recurrence, counted at 2 x heads x head_dim x state a token whatever
    form computes it."""
    import jax
    import jax.numpy as jnp

    from yet_another_mobilenet_series_tpu.ops import lm as ops
    from yet_another_mobilenet_series_tpu.ops import lm_mamba

    def unrolled(lower, upper, body, carry):
        for i in range(lower, upper):
            carry = body(i, carry)
        return carry

    monkeypatch.setattr(ops, "ATTN_BLOCK", 1)
    monkeypatch.setattr(ops, "lax", types.SimpleNamespace(**{**vars(jax.lax), "fori_loop": unrolled}))
    monkeypatch.setattr(lm_mamba, "ssd_core", lambda x, *rest: (x, jnp.float32(0.0)))
    net, lm = toy(12)
    params, _ = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((1, lm.seq_len + 2), jnp.int32)
    counted = dot_macs(jax.make_jaxpr(lambda p, t: net.loss(p, {}, {"tokens": t})[0])(params, tokens).jaxpr)
    keys = {k: getattr(lm, k) for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                                        "intermediate_size", "num_hidden_layers", "mamba_n_heads", "mamba_d_head",
                                        "mamba_d_state", "mamba_d_conv")}
    keys.update(layer_types=list(lm.layer_types), vocab_size=net.vocab)
    parts = macs_granite.parts(keys, lm.seq_len)
    assert counted == sum(parts.values()) - parts["ssd_conv"] - parts["ssd_recurrence"]
    assert parts["ssd_recurrence"] == 2 * 12 * 2 * lm.mamba_n_heads * lm.mamba_d_head * lm.mamba_d_state
    assert parts["attn_core"] == (12 * 13 // 2) * lm.num_attention_heads * 2 * lm.head_dim
    assert parts["lm_head"] == 12 * lm.hidden_size * net.vocab


def test_the_configuration_file_is_the_published_config_and_its_arithmetic():
    """The published config's keys as they are run, but `num_hidden_layers`,
    `layer_types` and `vocab_size` (in `reduced`): the first whole period of
    the pattern and an eighth of the vocabulary."""
    config = config_file()
    assert config["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"]
    assert config["source"] == "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
    assert (config["published"]["num_hidden_layers"], config["published"]["vocab_size"]) == (40, 100352)
    assert (config["model_type"], config["mamba_expand"], config["mamba_conv_bias"], config["mamba_proj_bias"],
            config["attention_bias"], config["rms_norm_eps"], config["num_local_experts"]) == (
        "granitemoehybrid", 2, True, False, False, 1e-05, 0)
    assert (config["num_hidden_layers"], config["vocab_size"], config["vocab_size"] * 8) == (10, 12544, 100352)
    assert config["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["shared_intermediate_size"], config["mamba_n_heads"],
            config["mamba_d_head"], config["mamba_d_state"], config["mamba_n_groups"], config["mamba_d_conv"],
            config["mamba_chunk_size"], config["position_embedding_type"], config["tie_word_embeddings"]) == (
        2048, 32, 8, 64, 8192, 8192, 64, 64, 128, 1, 4, 256, "nope", True)
    assert (config["attention_multiplier"], config["embedding_multiplier"], config["residual_multiplier"],
            config["logits_scaling"]) == (0.015625, 12, 0.22, 8)
    # the family's keys say "none": no latent, no expert layer, no MTP module
    assert (config["first_k_dense_replace"], config["n_routed_experts"], config["published"]["n_routed_experts"],
            config["num_experts_per_tok"], config["n_shared_experts"], config["moe_intermediate_size"],
            config["kv_lora_rank"], config["q_lora_rank"], config["num_nextn_predict_layers"], config["expert_shares"]) == (
        10, 0, 0, 0, 0, 0, 0, None, 0, 1)
    parts = config["parameters_by_part"]
    assert parts["mamba_mixer"] == 25_847_232 and parts["attention_mixer"] == 10_485_760
    assert parts["mamba_layer"] == parts["mamba_mixer"] + parts["mlp_a_layer"] + parts["norms_a_layer"] == 76_182_976
    assert parts["attention_layer"] == 60_821_504
    assert parts["layers_held"] == 9 * parts["mamba_layer"] + parts["attention_layer"] == 746_468_288
    assert parts["tied_vocabulary_held"] + parts["final_norm"] == 25_692_160
    assert parts["layers_held"] + parts["tied_vocabulary_held"] + parts["final_norm"] == config["parameters_here"] \
        == 772_160_448
    assert (36 * parts["mamba_layer"] + 4 * parts["attention_layer"] + 100352 * 2048 + 2048
            == config["published"]["parameters"] == 3_191_396_096)
    assert config["bytes"]["parameters_gradients_moments_gb"] == round(16 * 772_160_448 / 1e9, 2) == 12.35
    assert any("U(-1/2, 1/2)" in line for line in config["assumed"])
    assert macs_granite.forward_macs(config, 8192) == 6_539_314_200_576  # one sequence: the cell's macs_per_image
    shares = {k: v / 6_539_314_200_576 for k, v in macs_granite.parts(config, 8192).items()}
    assert 0.63 < shares["mlp"] < 0.64 and 0.29 < shares["ssd_proj"] < 0.30 and 0.011 < shares["ssd_recurrence"] < 0.012
    (entry,) = [c for c in manifest()["configs"] if c["name"] == config["name"]]
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_the_cell_and_its_traffic_are_one_8k_document():
    (cell,) = [w for w in manifest()["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train_tokens_resident_granite_1x8k", 1)
    assert len(cell["why"]) <= 200 and manifest()["workloads"][-1] == cell
    with open(os.path.join(REPO, "benchmark", "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["runner"], traffic["sequences_per_chip"], traffic["seq_len"], traffic["zipf_exponent"],
            traffic["warm_steps"], traffic["sync_every"], traffic["sync_lag"], traffic["trace_for_s"],
            traffic["reference_rows_at_once"], traffic["rehearsal"]["seq_len"]) == (
        "train_tokens_resident_granite", 1, 8192, 1.0, 1, 1, 1, 4.0, 512, 32)
    assert config_file()["overrides"] == {"schedule.warmup_epochs": 0.0, "schedule.base_lr": 1e-6}
    assert sum(w["chips"] == 4 for w in manifest()["workloads"]) == 1  # the benchmark keeps its one four-chip cell
    assert len(manifest()["configs"]) == 6 and len(manifest()["workloads"]) == 7


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
    # a table without an ssd_ row (a model, or a program, without Mamba-2): None too
    ctx = types.SimpleNamespace(step_scopes_lm={"metrics": {}, "table": {"ms_per_step": {"norm.fwd": 3.0}}})
    assert readers.python(ctx, how["module"]) is None


def test_the_three_times_sum_rows_of_the_table_the_lm_reader_made():
    """No second compile, no second trace read: the rows of `ctx.step_scopes_lm`."""
    rows = {"ssd_core.fwd": 50.0, "ssd_core.bwd": 90.0, "ssd_core.-": 0.5, "ssd_proj.fwd": 25.0, "ssd_proj.bwd": 70.0,
            "ssd_conv.fwd": 3.0, "ssd_conv.bwd": 9.0, "ssd_gate.fwd": 0.25, "ssd_norm.bwd": 2.0, "mlp.bwd": 125.0,
            "attn_core.fwd": 5.0}
    ctx = types.SimpleNamespace(step_scopes_lm={"metrics": {}, "table": {"ms_per_step": rows}})
    got = {name: step_scopes_ssd.metric(ctx, name) for name in TIMES}
    # the gated norm's fusions hold the SSD output's assembly, so ssd_norm is the core's
    assert got == {"lm.ssd_core_ms.train": 142.5, "lm.ssd_proj_ms.train": 95.0, "lm.ssd_pointwise_ms.train": 12.25}
    from yet_another_mobilenet_series_tpu.obs import scopes

    mine = {s for names in step_scopes_ssd.METRICS.values() for s in names}
    assert mine == {s for s in scopes.SCOPES if s.startswith("ssd_")}
    assert not mine & {s for names in step_scopes_lm.METRICS.values() for s in names}


def test_the_roofline_share_has_its_entry_and_file_and_cannot_pass_100():
    """The conv kernels' share of the HBM roofline: bytes from shapes
    (roofline_ssd.py: the least a call must move) over the calls' device time
    x 819 GB/s. Calls that took exactly bytes / bandwidth read 100; longer
    ones less; other kernels (KDA's, of the same module) are not read; no
    kernel, no trace: None."""
    (entry,) = [m for m in manifest()["per_layer"] if m["name"] == ROOFLINE]
    assert entry == {"name": ROOFLINE, "unit": "%", "better": "higher", "source": "device_trace",
                     "layer": "compiled train step", "moves": "train_images_per_s_per_chip", "workloads": [CELL]}
    with open(os.path.join(LAYER_DIR, ROOFLINE + ".json")) as f:
        how = json.load(f)
    assert (how["reader"], how["module"]) == ("python", "ssd_conv_hbm_roofline_pct_train")
    need = roofline_ssd.conv_bytes(1, 8192, 4352, 4)
    stream = 8192 * 4352 * 2
    assert need == {"fwd": 2 * stream + 5 * 4352 * 4, "bwd": 3 * stream + 10 * 4352 * 4}
    bandwidth = 819e9
    exact = [("ssd_conv_fwd.3", need["fwd"] / bandwidth * 1e9), ("ssd_conv_bwd.1", need["bwd"] / bandwidth * 1e9),
             ("ssd_conv_fwd", need["fwd"] / bandwidth * 1e9)]
    assert roofline.share(exact, 1, 8192, 4352, 4, bandwidth) == pytest.approx(100.0)
    slower = [(name, 2 * ns) for name, ns in exact] + [("kda_conv_fwd.2", 1.0), ("fusion.7", 5e6)]
    assert roofline.share(slower, 1, 8192, 4352, 4, bandwidth) == pytest.approx(50.0)
    assert roofline.share([("kda_conv_bwd.4", 1e6)], 1, 8192, 4352, 4, bandwidth) is None
    assert readers.python(types.SimpleNamespace(trace=None), "ssd_conv_hbm_roofline_pct_train") is None
    assert roofline.hbm_bytes_per_s("TPU v5 lite") == 819e9


def test_the_cell_joins_the_accepted_metrics_of_a_token_cell_without_experts_kda_or_loop():
    per_layer = {m["name"]: m for m in manifest()["per_layer"]}
    for name in JOINED + TIMES + [ROOFLINE]:
        assert per_layer[name]["workloads"][-1] == CELL, name
        assert per_layer[name]["moves"] == "train_images_per_s_per_chip"
    for name in NOT_JOINED:
        assert CELL not in per_layer[name]["workloads"], name
    (throughput,) = [m for m in manifest()["end_to_end"] if m["name"] == "train_images_per_s_per_chip"]
    assert throughput["workloads"][-1] == CELL and throughput["bound"] == 0.01
    reported = {m["name"] for m in harness.metrics_of(manifest(), "per_layer", CELL)}
    assert not {n for n in reported if n.startswith("step.") and n not in JOINED}  # no CNN scope metric
    every_cell = {m["name"] for m in manifest()["per_layer"] if "workloads" not in m}
    assert reported == {*JOINED, *TIMES, ROOFLINE, *every_cell}


# -- the rehearsal run ---------------------------------------------------------


@pytest.fixture(scope="module")
def rehearsal():
    proc = subprocess.run([sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", str(2**31 + 39),
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
        "losses_finite", "first_loss_near_its_initial_value", "loss_not_above_first", "every_mamba_layer_is_counted",
        "every_attention_site_is_fused", "every_xbc_convolution_is_fused", "step_counter_advanced_by_attempted",
        "reference_saw_the_programs_initial_parameters", "first_step_agrees_with_the_float32_reference",
        "no_compile_in_window"}
    assert run["arch"] == "granitemoehybrid"
    assert (run["ssd_sites"], run["ssd_kept_sites"], run["ssd_conv_fused_sites"], run["attn_sites"]) == (2.0, 2.0, 0.0, 1.0)
    assert run["tokens_per_step"] == 32 and run["seq_len"] == 32 and run["ssd_min_chunk_log_decay"] < 0
    assert run["macs_per_image"] > 0 and run["images_per_s_per_chip"] * 32 == pytest.approx(run["tokens_per_s"])
    assert run["reference"]["ok"] and set(run["reference"]["worst"]) == set(bench_ref.LIMITS)
    assert {"loss", "gnorm/embed", "gnorm/layer_0/mamba", "gnorm/layer_1/attn", "change/embed",
            "change/layer_0/mamba/conv_bias"} <= set(run["reference"]["values"])
    notes = next(ln for ln in rehearsal if "setup_phases" in ln)
    assert notes["compile_window"]["compiles"] == 0 and notes["heavy_imports"] == []


# -- the reference ---------------------------------------------------------------


@pytest.fixture(scope="module")
def toy_step():
    """The toy model with weights and scores that matter (init_std ten times
    the app's, scores times 1/4 where the app's 1/64 leaves a 16-channel toy
    head's softmax uniform), half its Mamba-2 heads decaying past float32's
    exp limit inside a chunk of 8, a batch, and the package reference's
    scalars and gradients on them."""
    import jax
    import jax.numpy as jnp

    from yet_another_mobilenet_series_tpu.models import lm_reference as package_ref

    net, lm = toy(32, **{"model.lm.init_std": 0.2, "model.lm.attention_multiplier": 0.25})
    params, _ = net.init(jax.random.PRNGKey(3))
    heads = lm.mamba_n_heads
    for name in net.blocks_mixing_by("mamba"):
        p = params[name]["mamba"]
        p["A_log"] = jnp.log(jnp.linspace(1.0, 16.0, heads))
        p["dt_bias"] = jnp.where(jnp.arange(heads) < heads // 2, -3.0, 1.0)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, lm.seq_len + 2), 0, net.vocab)
    (loss, _), grads = jax.jit(lambda p: package_ref.granite_loss_and_grads(p, tokens, package_ref.granite_dims_of(lm)))(
        params)
    want = {"loss": loss, **bench_ref.group_norms(grads)}
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
    assert sum(k.startswith("change/") for k in own) == len(jax.tree.leaves(params)) == 2 * 13 + 9 + 2
    low = bench_ref.compare(reference_scalars(toy_step, jnp.float8_e4m3fn, None), want)
    assert not low["ok"], low  # the nearest precision below bfloat16 fails at least one limit
    assert not bench_ref.compare({k: 0.0 for k in want}, want)["ok"]
    assert not bench_ref.compare({k: v for k, v in want.items() if k != "gnorm/layer_0/mamba"}, want)["ok"]
    assert [bench_ref.kind_of(k) for k in ("loss", "gnorm/layer_0/mamba", "gnorm/layer_1/attn", "gnorm/layer_2/mlp",
                                           "gnorm/embed", "gnorm/layer_1/norms", "gnorm/final_norm", "change/embed")] == [
        "loss", "gnorm_mamba", "gnorm_attn", "gnorm_mlp", "gnorm_embed", "gnorm_norms", "gnorm_norms", "change"]


def planted(fault: str, monkeypatch, net):
    """The program with one fault planted; returns the model to run."""
    import jax
    import jax.numpy as jnp

    from yet_another_mobilenet_series_tpu.ops import lm as ops
    from yet_another_mobilenet_series_tpu.ops import lm_kda, lm_mamba

    replace = lambda **kw: dataclasses.replace(net, lm=dataclasses.replace(net.lm, **kw))  # noqa: E731
    if fault in ("residual_multiplier", "embedding_multiplier", "logits_scaling"):  # one multiplier dropped
        return replace(**{fault: 1.0})
    if fault == "a_conv_that_peeks_ahead":
        real = lm_kda.short_conv
        monkeypatch.setattr(lm_kda, "short_conv", lambda z, w, bias=None, name="kda_conv": real(
            jnp.roll(z, -1, axis=1).at[:, -1].set(0.0), w, bias, name))
    elif fault == "the_decay_as_a_product_of_exponentials":  # e^{G_t} e^{-G_s}: overflows past -88

        def as_product(scores, cum, dx):
            rows = cum.shape[2]
            below = jnp.tril(jnp.ones((rows, rows), bool))[..., None]
            decays = jnp.where(below, jnp.exp(cum)[..., :, None, :] * jnp.exp(-cum)[..., None, :, :], 0.0)
            return jnp.einsum("bntsh,bnshp->bnthp", (scores[..., None] * decays).astype(dx.dtype), dx)

        monkeypatch.setattr(lm_mamba, "_in_chunk", as_product)
    elif fault == "the_gate_after_the_norm":

        def after(y, z, gain, eps):
            y32 = y.astype(jnp.float32)
            normed = y32 * jax.lax.rsqrt(jnp.mean(jnp.square(y32), axis=-1, keepdims=True) + eps) * gain
            return (normed * jax.nn.silu(z.astype(jnp.float32))).astype(y.dtype)

        monkeypatch.setattr(lm_mamba, "gated_norm", after)
    elif fault == "D_dropped":
        real = lm_mamba.ssd_core
        monkeypatch.setattr(lm_mamba, "ssd_core", lambda x, delta, a, b, c, d_skip, chunk: real(
            x, delta, a, b, c, jnp.zeros_like(d_skip), chunk))
    elif fault == "rope_applied":
        real = ops.mha_attention
        monkeypatch.setattr(ops, "mha_attention", lambda p, x, cos, sin, **kw: real(
            p, x, *ops.rope_tables(x.shape[1], kw["head_dim"], 1e4), **kw))
    elif fault == "query_head_i_reads_kv_head_i_mod_kv_heads":  # tiled, where each kv head serves a run of queries
        monkeypatch.setattr(ops, "jnp", types.SimpleNamespace(**{**vars(jnp), "repeat": lambda t, r, axis: jnp.concatenate(
            [t] * r, axis=axis)}))
    return net


FAULTS = [None, "a_conv_that_peeks_ahead", "the_decay_as_a_product_of_exponentials", "the_gate_after_the_norm",
          "D_dropped", "residual_multiplier", "embedding_multiplier", "logits_scaling", "rope_applied",
          "query_head_i_reads_kv_head_i_mod_kv_heads"]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_faults_fail_the_comparison(toy_step, monkeypatch, fault):
    """The float32 program's first step against the benchmark's reference, as
    the runner compares them: sound, it passes every limit; each planted
    fault (a convolution that reads the next position, the in-chunk decay
    written as a product that overflows, the gate after the norm, the D skip
    dropped, a multiplier dropped, a rotation applied, query heads mapped to
    the wrong key/value heads) fails at least one."""
    import jax

    net, lm, params, tokens, _, _ = toy_step
    faulty = planted(fault, monkeypatch, net)
    (loss, (_, scalars)), grads = jax.jit(jax.value_and_grad(
        lambda p: faulty.loss(p, {}, {"tokens": tokens}), has_aux=True))(params)
    program = {"loss": loss, **scalars, **faulty.grad_scalars(grads)}
    reference = reference_scalars(toy_step)
    program.update({k: v for k, v in reference.items() if k.startswith("change/")})  # the optimizer is not under test
    verdict = bench_ref.compare(program, reference)
    assert verdict["ok"] is (fault is None), verdict["worst"]
    if fault is None:  # the hazard is in the step: half the heads' in-chunk decays pass float32's exp limit
        assert float(scalars["ssd_min_chunk_log_decay"]) < -88.0


def test_the_newest_cells_file_sees_the_whole_manifest_and_an_older_ones_its_own():
    """conftest.py of this directory cuts the manifest back to a file's own
    cell: for this file, the newest, that is the whole manifest; for the
    older `ouro26b_train_1x8k` everything appended with this cell is left out."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("cut", os.path.join(os.path.dirname(__file__), "conftest.py"))
    cut = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cut)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        whole = json.load(f)
    assert cut.cut_back_to(whole, CELL) == whole
    view = cut.cut_back_to(whole, "ouro26b_train_1x8k")
    assert [w["name"] for w in view["workloads"]][-1] == "ouro26b_train_1x8k" and len(view["workloads"]) == 6
    assert CONFIG not in [c["name"] for c in view["configs"]] and len(view["configs"]) == 5
    per_layer = {m["name"]: m for m in view["per_layer"]}
    assert not ({*TIMES, ROOFLINE} & set(per_layer))
    assert per_layer["lm.attn_core_ms.train"]["workloads"] == ["glm47flash_train_2x8k", "kimilinear_train_1x16k",
                                                               "ouro26b_train_1x8k"]
