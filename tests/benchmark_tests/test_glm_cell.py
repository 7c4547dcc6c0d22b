"""The token training cell `glm47flash_train_2x8k` (CPU only, nothing timed):
the MAC count against the program's own `dot_general`s, the configuration
file's arithmetic, and every new layer metric against its entry, file and
reader. Its rehearsal run, and the benchmark's copy of the reference against
the package's, are in tests/test_lm_cell.py: they are the heavy ones, and a
file of this directory starts with the suite, beside timing-sensitive tests.
"""

from __future__ import annotations

import json
import math
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, macs_lm, readers  # noqa: E402
from benchmark.layer_metrics import step_scopes_lm  # noqa: E402

CELL = "glm47flash_train_2x8k"
LAYER_DIR = os.path.join(REPO, "benchmark", "layer_metrics")
LM_METRICS = sorted([*step_scopes_lm.METRICS, step_scopes_lm.UNSCOPED_SHARE])
MOE_METRICS = ["moe.assignments_per_expert.train", "moe.load_max_over_mean.train"]
JOINED = ["host.dispatch_ms.train", "step.device_ms.train", "step.mfu.train", "coll.ms_per_step.train",
          "device.idle_share.train", "device.peak_hbm_gib.train"]


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def config_file() -> dict:
    with open(os.path.join(REPO, "benchmark", "configs", "glm_4_7_flash_ep8_share.json")) as f:
        return json.load(f)


def toy(seq_len: int):
    """(TokenModel, LMConfig) at the configuration's own rehearsal sizes."""
    from yet_another_mobilenet_series_tpu.models import get_model

    config = harness.with_rehearsal(config_file(), True)
    cfg = harness.load_app_config(config["train_app"], {**config["overrides"], "model.lm.seq_len": seq_len})
    return get_model(cfg.model), cfg.model.lm


# -- the yardstick -------------------------------------------------------------


def dot_macs(jaxpr, times: int = 1) -> tuple[int, int]:
    """(MACs of every dot_general, MACs of every ragged_dot) in a jaxpr, through scans, remats and calls."""
    plain = ragged = 0
    for eqn in jaxpr.eqns:
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
    """Everything but the routed experts: the program's forward over one
    sequence, its attention in tiles of one row by one key so that exactly the
    causal pairs are formed, against macs_lm.py's count from the
    configuration's keys. The attention's loops run a number of times that
    depends on the query block, which a jaxpr does not say: here they are
    unrolled, so that every tile the program forms is a `dot_general` to count
    (12 tokens, 78 tiles a layer: this file starts with the suite, beside
    timing-sensitive tests, and a long trace would slow them)."""
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
    keys = {k: getattr(lm, k) for k in (
        "hidden_size", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "q_lora_rank",
        "kv_lora_rank", "first_k_dense_replace", "num_hidden_layers", "num_nextn_predict_layers",
        "moe_intermediate_size", "intermediate_size", "num_experts_per_tok", "n_shared_experts")}
    parts = macs_lm.parts({**keys, "n_routed_experts": net.experts_held, "vocab_size": net.vocab}, lm.seq_len,
                          lm.n_routed_experts)
    routed = parts.pop("routed_experts_expected")
    assert plain == sum(parts.values())
    # the grouped matmuls are traced over every assignment's row; at the expected load 1 in 8 is held
    assert ragged == routed * lm.n_routed_experts // net.experts_held
    assert macs_lm.forward_macs({**keys, "n_routed_experts": net.experts_held, "vocab_size": net.vocab},
                                lm.seq_len, lm.n_routed_experts) == plain + routed


def test_the_configuration_file_is_the_published_config_and_its_arithmetic():
    config = config_file()
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"]["n_routed_experts"] == config["n_routed_experts"] * config["expert_shares"] == 64
    assert config["published"]["vocab_size"] == config["vocab_size"] * 8 == 154880
    assert (config["hidden_size"], config["num_attention_heads"], config["q_lora_rank"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"], config["routed_scaling_factor"]) == (
        2048, 20, 768, 512, 192, 64, 256, 10240, 1536, 4, 1.8)
    parts = config["parameters_by_part"]
    assert (parts["dense_layer_0"] + 4 * parts["expert_layer_here"] + parts["mtp_module"]
            + parts["embedding_and_head"] + parts["final_norm"]) == config["parameters_here"] == 706_518_528
    assert macs_lm.forward_macs(config, 8192, 64) == 4_950_201_466_880  # one sequence: the cell's macs_per_image
    (entry,) = [c for c in manifest()["configs"] if c["name"] == config["name"]]
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]


# -- the layer metrics -----------------------------------------------------------


@pytest.mark.parametrize("name", LM_METRICS + MOE_METRICS)
def test_each_new_metric_has_its_entry_its_file_and_its_reader(name):
    (entry,) = [m for m in manifest()["per_layer"] if m["name"] == name]
    with open(os.path.join(LAYER_DIR, name + ".json")) as f:
        how = json.load(f)
    assert how["reader"] in readers.READERS
    assert entry["layer"] == "compiled train step" and entry["moves"] == "train_images_per_s_per_chip"
    assert entry["workloads"] == [CELL]
    if name.startswith("lm."):
        assert entry["source"] == "device_trace" and how["reader"] == "python"
        seen = []
        orig, step_scopes_lm.metric = step_scopes_lm.metric, lambda c, n: seen.append(n)
        try:
            readers.python(types.SimpleNamespace(trace=None), how["module"])
        finally:
            step_scopes_lm.metric = orig
        assert seen == [name]  # each metric's own module asks the shared one for exactly its name
    else:
        assert entry["source"] == "program_counter" and how["reader"] == "fact"
        ctx = types.SimpleNamespace(result={"facts": {how["key"]: 3.5}})
        assert readers.read_all(ctx, [{"name": name}]) == {name: 3.5}


def test_the_cell_joins_the_accepted_metrics_and_nothing_to_read_is_none():
    m = manifest()
    cells = [w["name"] for w in m["workloads"]]
    assert cells[-1] == CELL and len(cells) == 4 and sum(w["chips"] == 4 for w in m["workloads"]) == 1
    for metric in m["end_to_end"] + m["per_layer"]:
        if metric["name"] in JOINED + ["train_images_per_s_per_chip"]:
            assert metric["workloads"][-1] == CELL
        elif metric["name"].startswith("step."):
            assert CELL not in metric["workloads"]  # the CNN step's scope metrics stay the CNN cells'
    # a run without a device plane (a rehearsal), and a program without the family: None, never an error
    ctx = types.SimpleNamespace(trace=None)
    assert step_scopes_lm.metric(ctx, LM_METRICS[0]) is None
    assert readers.read_all(ctx, [{"name": n} for n in LM_METRICS]) == dict.fromkeys(LM_METRICS)


def test_scope_metrics_partition_hand_made_events(monkeypatch, capsys):
    from benchmark import trace_reduce

    hlo = """
%fc.10 (p: f32[8]) -> f32[8] {
  %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(shard_fn)/jvp(moe_combine)/mul"}
  %mul.2 = f32[8]{0} multiply(%mul.1, %p), metadata={op_name="jit(shard_fn)/jvp(moe_combine)/mul"}
  ROOT %reduce_sum.3 = f32[8]{0} add(%mul.2, %p)
}

ENTRY %main () -> f32[] {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kOutput, calls=%fc.1, metadata={op_name="jit(shard_fn)/jvp(attn_core)/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kOutput, calls=%fc.2, metadata={op_name="jit(shard_fn)/transpose(jvp(attn_proj))/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kOutput, calls=%fc.3, metadata={op_name="jit(shard_fn)/jvp(mlp)/dot_general"}
  %ragged-dot-none.4 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.5 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.5, metadata={op_name="jit(shard_fn)/jvp(moe_dispatch)/gather"}
  %fusion.6 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.6, metadata={op_name="jit(shard_fn)/jvp(lm_head)/dot_general"}
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.7, metadata={op_name="jit(shard_fn)/optim/mul"}
  %fusion.8 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.8, metadata={op_name="jit(shard_fn)/jvp(norm)/mul"}
  %copy.9 = f32[8]{0} copy(%a)
  %fusion.10 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.10
  %while.11 = (f32[8]{0}) while(%t), condition=%cond.11, body=%body.11, metadata={op_name="jit(shard_fn)/jvp(attn_core)/while"}
}
"""
    step = [("fusion.1", 400), ("fusion.2", 100), ("fusion.3", 100), ("ragged-dot-none.4", 50), ("fusion.5", 30),
            ("fusion.6", 120), ("fusion.7", 60), ("fusion.8", 10), ("copy.9", 20), ("fusion.10", 10)]
    ops, modules, t = [], [], 1000.0
    for start in (1000.0, 2000.0):
        modules.append(("jit_shard_fn(1)", start, 900.0))
        t = start
        # a loop's own event spans its body's (fusion.1 here): counted once, in the body
        ops.append(("%while.11 = (f32[8]{0}) while((f32[8]{0}) %t)", t, 400.0))
        for name, dur in step:
            ops.append((f"%{name} = f32[8]{{0}} op(f32[8]{{0}} %a)", t, float(dur)))
            t += dur
    trace = trace_reduce.Trace(devices={0: {"XLA Ops": ops, "XLA Modules": modules}},
                               host_spans=[(trace_reduce.WINDOW_SPAN, 500.0, 3000.0)])
    monkeypatch.setattr(step_scopes_lm, "compiled_step_text", lambda ctx: hlo)
    ctx = types.SimpleNamespace(trace=trace)
    values = {name: step_scopes_lm.metric(ctx, name) for name in LM_METRICS}
    assert values == pytest.approx({
        "lm.attn_core_ms.train": 400e-6, "lm.dense_ms.train": 200e-6, "lm.moe_experts_ms.train": 50e-6,
        # the grouped matmul the compiler named itself is moe_experts; the fusion rooted at a
        # compiler-made `reduce_sum` is its members' moe_combine: this reader's two rules
        "lm.moe_route_ms.train": 40e-6, "lm.head_loss_ms.train": 120e-6, "lm.optim_ms.train": 60e-6,
        "lm.unscoped_share.train": 100.0 * 20 / 900})
    table = json.loads(capsys.readouterr().out)["step_scopes_lm"]
    assert table["whole_steps"] == 2 and table["ms_per_step"]["norm.fwd"] == pytest.approx(10e-6)
    # ... and under obs.scopes' own rule, which the CNN cells' metrics keep, both stay unscoped
    assert table["unscoped_share_pct_plain_rule"] == pytest.approx(100.0 * (20 + 50 + 10) / 900)
