"""obs/scopes.py: the closed list of named scopes in the compiled train step,
and the rule that reads them back (docs/OBSERVABILITY.md "Named scopes").

On a two-block toy net's full train step compiled on the CPU: every listed
scope the step contains appears in the scope table, forward and backward are
told apart, BatchNorm resolves to bn_*, and the scopes are
metadata only (the compiled program is the same program without them).
"""

import collections
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

from yet_another_mobilenet_series_tpu.config import parse_cli
from yet_another_mobilenet_series_tpu.models import get_model
from yet_another_mobilenet_series_tpu.obs import scopes
from yet_another_mobilenet_series_tpu.ops.layers import BatchNorm
from yet_another_mobilenet_series_tpu.parallel import dp, mesh as mesh_lib
from yet_another_mobilenet_series_tpu.train import optim, schedules, steps

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
APP = os.path.join(REPO, "yet_another_mobilenet_series_tpu", "apps", "mobilenet_v3_large.yml")
# one plain block and one with SE, hswish and a residual; dropout and drop-connect on
TOY = ("model.block_specs=[{exp: 16, c: 16, n: 1, s: 2, k: 3, act: relu}, "
       "{exp: 48, c: 24, n: 2, s: 1, k: 5, act: hswish, se: 0.25}]")


TOKEN_SCOPES = {"embed", "norm", "rope", "attn_proj", "attn_core", "mlp", "moe_router", "moe_dispatch",
                "moe_experts", "moe_combine", "mtp_merge", "lm_head"}
KDA_SCOPES = {"kda_proj", "kda_conv", "kda_gate", "kda_core", "kda_norm"}
LOOP_SCOPES = {"exit_gate"}  # a looped model's (`ouro`)
SSD_SCOPES = {"ssd_proj", "ssd_conv", "ssd_gate", "ssd_core", "ssd_norm"}  # a Mamba-2 mixer's (`granitemoehybrid`)
WINDOW_SCOPES = {"attn_window", "attn_gate"}  # a sliding window's core and a per-head output gate (`laguna`)


def lowered_step(*overrides, chips: int = 1):
    cfg = parse_cli([f"app:{APP}", TOY, "model.num_classes=16", "model.drop_connect=0.2", "data.image_size=32",
                     f"train.batch_size={4 * chips}", f"dist.num_devices={chips}", *overrides])
    net = get_model(cfg.model, 32)
    mesh = mesh_lib.make_mesh(chips)
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 4 * chips, 10, 1)
    params_example, _ = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
    optimizer = optim.make_optimizer(cfg.optim, lr_fn, params_example)
    step = dp.make_dp_train_step(net, cfg, optimizer, lr_fn, mesh, params_example=params_example)
    ts = jax.eval_shape(lambda: steps.init_train_state(net, cfg, optimizer, jax.random.PRNGKey(0)))
    batch = {"image": jax.ShapeDtypeStruct((4 * chips, 32, 32, 3), jnp.float32),
             "label": jax.ShapeDtypeStruct((4 * chips,), jnp.int32)}
    return step.lower(ts, batch, jax.ShapeDtypeStruct((2,), jnp.uint32))


@pytest.fixture(scope="module")
def toy_text():
    return lowered_step().compile().as_text()


def _counts(text):
    return collections.Counter(scopes.scope_table(text).values())


# -- scope_of: the reading rule, on hand-written op_names -------------------


@pytest.mark.parametrize("op_name, expect", [
    ("jit(step)/jvp(bn_stats)/reduce_sum", ("bn_stats", "fwd")),
    ("jit(step)/transpose(jvp(bn_apply))/reduce_sum", ("bn_apply", "bwd")),
    # the innermost listed scope wins ...
    ("jit(step)/jvp(bn_stats)/syncbn/psum", ("syncbn", "fwd")),
    ("jit(step)/transpose(jvp(bn_stats))/syncbn/psum", ("syncbn", "bwd")),
    # ... except that everything inside se is se: its pool, its activations, its matmuls
    ("jit(step)/jvp(se)/pool/reduce_sum", ("se", "fwd")),
    ("jit(step)/transpose(jvp(se))/act/jit(clip)/mul", ("se", "bwd")),
    ("jit(step)/jvp(se)/dense/dot_general", ("se", "fwd")),
    # a nested jit of jax's own between the scope and the primitive
    ("jit(step)/jvp(act)/jit(clip)/max", ("act", "fwd")),
    ("jit(step)/jvp(drop)/jit(_bernoulli)/jit(_uniform)/max", ("drop", "fwd")),
    # outside autodiff: no phase
    ("jit(step)/optim/mul", ("optim", "-")),
    ("jit(step)/ema/add", ("ema", "-")),
    ("jit(step)/loss/div", ("loss", "-")),
    # SPMD and shard_map prefixes are seen through
    ("jit(shard_fn)/shard_map/jvp(conv_dw)/conv_general_dilated", ("conv_dw", "fwd")),
    ("jit(shard_fn)/jit(main)/shard_map/transpose(jvp(conv_pw))/dot_general", ("conv_pw", "bwd")),
    ("jit(shard_fn)/shard_map/grad_sync/psum", ("grad_sync", "-")),
    # under jax.checkpoint the recomputed forward is part of the backward pass
    ("jit(step)/transpose(jvp(checkpoint))/rematted_computation/bn_apply/mul", ("bn_apply", "bwd")),
    # a primitive that merely shares a scope's name is not a scope; neither is an unknown name
    ("jit(step)/jvp(my_layer)/add", (scopes.UNSCOPED, "fwd")),
    ("jit(step)/jit(_threefry_fold_in)/shift_right_logical", (scopes.UNSCOPED, "-")),
    ("jit(step)/transpose(jvp())/convert_element_type", (scopes.UNSCOPED, "bwd")),
    ("", (scopes.UNSCOPED, "-")),
])
def test_scope_of(op_name, expect):
    assert scopes.scope_of(op_name) == expect


def test_scope_rejects_a_name_off_the_list():
    with scopes.scope("bn_stats"):
        pass
    with pytest.raises(ValueError, match="not in obs.scopes.SCOPES"):
        scopes.scope("batchnorm")


# -- the table of a compiled step --------------------------------------------


def test_every_scope_the_toy_step_contains_is_in_its_table(toy_text):
    seen = {scope for scope, _ in scopes.scope_table(toy_text).values()}
    # everything on the list except the collectives (one chip), AtomNAS (no
    # masks, no penalty), the guard (off) and the token models' scopes
    expect = (set(scopes.SCOPES) - {"syncbn", "grad_sync", "nas_mask", "nas_penalty", "guard"} - TOKEN_SCOPES - KDA_SCOPES
              - LOOP_SCOPES - SSD_SCOPES - WINDOW_SCOPES)
    assert expect <= seen, f"missing: {sorted(expect - seen)}"
    assert seen <= set(scopes.SCOPES) | {scopes.UNSCOPED}


def test_forward_and_backward_are_told_apart(toy_text):
    counts = _counts(toy_text)
    for scope in ("conv_dw", "conv_pw", "conv_full", "dense", "bn_stats", "bn_apply", "act", "se"):
        assert counts[(scope, "fwd")] > 0 and counts[(scope, "bwd")] > 0, scope
    # the update is outside autodiff: no phase at all
    for scope in ("optim", "ema"):
        assert counts[(scope, "-")] > 0 and not counts[(scope, "fwd")] and not counts[(scope, "bwd")]


def test_collectives_are_named_on_a_mesh():
    text = lowered_step(chips=2).compile().as_text()
    counts = _counts(text)
    assert counts[("syncbn", "fwd")] > 0 and counts[("syncbn", "bwd")] > 0
    assert counts[("grad_sync", "-")] > 0
    # the all-reduces themselves resolve: none is left unscoped
    table = scopes.scope_table(text)
    reduces = [name for name in table if name.startswith("all-reduce")]
    assert reduces and all(table[name][0] in ("syncbn", "grad_sync", "loss") for name in reduces)


def test_batchnorm_lands_in_bn_scopes():
    """BatchNorm.apply alone under grad, lowered: every operation it traces,
    in the forward and in the backward, is under bn_stats or bn_apply."""
    bn = BatchNorm(8)
    params, state = bn.init()

    def loss(params, x):
        y, new_state = bn.apply(params, state, x, train=True)
        with scopes.scope("loss"):
            return jnp.sum(y.astype(jnp.float32) ** 2), new_state

    text = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).lower(
        params, jnp.ones((2, 4, 4, 8), jnp.bfloat16)).as_text(debug_info=True)
    # operations are located by their name-stack path, which starts at the jit
    by = collections.Counter(scopes.scope_of(m) for m in re.findall(r'loc\("(jit\([^"]*)"', text))
    assert {scope for scope, _ in by} == {"bn_stats", "bn_apply", "loss"}, by
    for scope in ("bn_stats", "bn_apply"):
        assert by[(scope, "fwd")] > 0 and by[(scope, "bwd")] > 0, by


def test_the_conv_bn_pair_lands_in_its_scopes():
    """ops/layers.py's conv + BN pair under grad on a mesh, lowered: every
    operation of its forward and of its custom backward is under conv_pw (the
    convolutions and contractions, the small matrices of the closed form
    too), bn_stats (the sums), bn_apply (the normalize and the per-channel
    coefficients) or syncbn (the psums), with the phase autodiff marks."""
    from jax.sharding import Mesh, PartitionSpec as P

    import numpy as np

    from yet_another_mobilenet_series_tpu.ops import layers

    conv, bn = layers.Conv2D(8, 24, 1), BatchNorm(24)
    conv_params = jax.eval_shape(lambda: conv.init(jax.random.PRNGKey(0)))
    bn_params, bn_state = bn.init()
    assert layers.conv_bn_pairs(conv, train=True)

    def loss(conv_params, bn_params, x):
        y, _ = layers.conv_bn(conv, bn, conv_params, bn_params, bn_state, x, train=True, axis_name="data",
                              compute_dtype=jnp.bfloat16)
        with scopes.scope("loss"):
            return jnp.sum(y.astype(jnp.float32) ** 2)

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    grads = jax.shard_map(jax.grad(loss, argnums=(0, 1, 2)), mesh=mesh, in_specs=(P(), P(), P("data")),
                          out_specs=(P(), P(), P("data")), check_vma=False)
    text = jax.jit(grads).lower(conv_params, bn_params, jax.ShapeDtypeStruct((4, 4, 4, 8), jnp.bfloat16)).as_text(
        debug_info=True)
    # inside a shard_map the name-stack paths start at the transform, not at a jit
    paths = re.findall(r'loc\("((?:jvp|transpose)\([^"]*)"', text)
    by = collections.Counter(scopes.scope_of(m) for m in paths)
    assert {scope for scope, _ in by} == {"conv_pw", "bn_stats", "bn_apply", "syncbn", "loss"}, by
    for scope in ("conv_pw", "bn_stats", "bn_apply", "syncbn"):
        assert by[(scope, "fwd")] > 0 and by[(scope, "bwd")] > 0, by


def test_time_by_scope_sums_and_reports_the_unresolved():
    table = {"fusion.1": ("bn_stats", "fwd"), "fusion.2": ("bn_stats", "bwd"), "convolution.3": ("conv_pw", "fwd"),
             "copy-done.4": (scopes.UNSCOPED, "-")}
    events = [("fusion.1", 10.0), ("fusion.1", 10.0), ("fusion.2", 5.0), ("convolution.3", 20.0),
              ("copy-done.4", 3.0), ("fusion.99", 2.0)]  # fusion.99: another compilation's name
    by = scopes.time_by_scope(events, table)
    assert by == {("bn_stats", "fwd"): 20.0, ("bn_stats", "bwd"): 5.0, ("conv_pw", "fwd"): 20.0,
                  (scopes.UNSCOPED, "-"): 5.0}
    assert scopes.unscoped_share(by) == pytest.approx(5.0 / 50.0)
    # a table from an executable without the names: everything is unresolved, and says so
    assert scopes.unscoped_share(scopes.time_by_scope(events, {})) == 1.0
    assert scopes.unscoped_share({}) is None


def test_scope_table_takes_a_fusion_without_a_name_from_its_root():
    text = """
%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(bn_apply)/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %fusion.8 = f32[8]{0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(act))/mul"}
  ROOT %copy.9 = f32[8]{0} copy(%fusion.8)
}
"""
    table = scopes.scope_table(text)
    assert table["fusion.7"] == ("bn_apply", "fwd")  # its fused computation's root's
    assert table["fusion.8"] == ("act", "bwd")  # its own
    assert "copy.9" not in table and "a" not in table  # nothing to resolve: unscoped when timed


def test_what_rides_in_a_convolutions_fusion():
    """XLA:TPU names a fusion after its convolution and fuses the BatchNorm
    sums around it in: scopes_inside lists the scopes of the reductions and
    contractions in a fusion (elementwise riders are not passes of their own),
    time_containing sums an op's time under each of them."""
    text = """
%inner.2 (p: f32[8,4]) -> f32[4] {
  %p = f32[8,4]{1,0} parameter(0)
  ROOT %reduce.5 = f32[4]{0} reduce(%p, %c), dimensions={0}, metadata={op_name="jit(f)/jvp(bn_stats)/reduce_sum"}
}

%fused_computation.1 (a: f32[8,4], /*index=1*/w: f32[4,4]) -> (f32[8,4], f32[4]) {
  %a = f32[8,4]{1,0} parameter(0)
  %convolution.3 = f32[8,4]{1,0} convolution(%a, %w), metadata={op_name="jit(f)/jvp(conv_pw)/conv_general_dilated"}
  %max.4 = f32[8,4]{1,0} maximum(%convolution.3, %z), metadata={op_name="jit(f)/jvp(act)/max"}
  %fusion.2 = f32[4]{0} fusion(%convolution.3), kind=kInput, calls=%inner.2
  ROOT %tuple.6 = (f32[8,4]{1,0}, f32[4]{0}) tuple(%max.4, %fusion.2)
}

ENTRY %main (a: f32[8,4]) -> f32[8,4] {
  %convert_reduce_fusion.1 = (f32[8,4]{1,0}, f32[4]{0}) fusion(%a, %w), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(f)/jvp(conv_pw)/conv_general_dilated"}
  %fusion.9 = f32[8,4]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1b, metadata={op_name="jit(f)/jvp(act)/max"}
}
"""
    table, inside = scopes.scope_table(text), scopes.scopes_inside(text)
    assert table["convert_reduce_fusion.1"] == ("conv_pw", "fwd")
    assert inside == {"convert_reduce_fusion.1": ("bn_stats", "conv_pw")}  # act rides along, no pass of its own
    events = [("convert_reduce_fusion.1", 10.0), ("fusion.9", 2.0), ("copy-done.3", 1.0)]
    assert scopes.time_containing(events, table, inside) == {
        "conv_pw": 10.0, "bn_stats": 10.0, "act": 2.0, scopes.UNSCOPED: 1.0}


def test_scope_table_round_trips_through_the_trace_directory(tmp_path, toy_text):
    path = scopes.write_scope_table(str(tmp_path / "trace"), toy_text)
    assert os.path.basename(path) == scopes.SCOPE_TABLE_FILE
    assert scopes.read_scope_table(str(tmp_path / "trace")) == (scopes.scope_table(toy_text),
                                                                scopes.scopes_inside(toy_text))
    assert scopes.read_scope_table(str(tmp_path / "nothing")) is None


# -- scopes are metadata: the same program without them ----------------------


def _strip(text: str) -> str:
    """Compiled HLO text without what names its source: the per-instruction
    metadata and the module's tables of files, functions and stack frames."""
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(.+\n)*", "", text, flags=re.M)
    return re.sub(r", metadata=\{[^}]*\}", "", text)


@pytest.mark.parametrize("overrides", [(), ("train.remat=true",)], ids=["plain", "remat"])
def test_scopes_add_nothing_to_the_compiled_step(monkeypatch, overrides):
    """The step compiled with the scopes, and with jax.named_scope patched to
    a no-op, metadata stripped: the same HLO, instruction for instruction.
    Under jax.checkpoint too."""
    with_scopes = lowered_step(*overrides).compile().as_text()
    assert "bn_stats" in with_scopes
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    without = lowered_step(*overrides).compile().as_text()
    assert "bn_stats" not in without and "conv_dw" not in without
    assert _strip(with_scopes) == _strip(without)


@pytest.mark.parametrize("overrides, digest", [((), "bddcadd2b0fb7542"), (("train.remat=true",), "44bade5e264b5f77")],
                         ids=["plain", "remat"])
def test_the_cnn_step_is_the_program_it_was_before_the_token_family(overrides, digest):
    r"""train/steps.py has ONE step skeleton for two families since PR 27. The
    toy CNN step's lowered module (StableHLO text: no locations, no machine in
    it) is pinned by digest. `plain` was bc356c6ecdd1f249 from 14d60ea to PR
    29; PR 30's deletion (here since PR 31) took Conv2D.apply's
    checkpoint_name landmark out, which lowers to nothing but was counted
    where jax numbers the module's private functions (`@clip_89` became
    `@clip_87`): the text is 7b6c8ef's but for those suffixes and the compiled
    HLO, under _strip, 7b6c8ef's byte for byte. Taking BatchNorm's mode switch out
    (PR 31) moved neither: the cells never took that branch. A change that means
    to alter the CNN step takes a new digest, and says so. To check the
    equality again, in each of two trees (`git archive 7b6c8ef | tar -x -C
    $P`, and this one):

        cd $TREE/tests && JAX_PLATFORMS=cpu PYTHONPATH=$TREE python -c "
        import hashlib, re, test_obs_scopes as t
        low = t.lowered_step()
        sha = lambda s: hashlib.sha256(s.encode()).hexdigest()[:16]
        print(sha(t._strip(low.compile().as_text())), sha(re.sub(r'(@\w+?)_\d+\b', r'\1', low.as_text())))"

    prints `a8494252204cf0c2 9d565d1460e09351` in both (jax 0.9.0, XLA:CPU);
    with `t.lowered_step('train.remat=true')` here and there (7b6c8ef's
    default remat policy, `full`, is the plain jax.checkpoint that stays),
    `d9f95915585aff34 9b4e5a03066168a7`."""
    import hashlib

    text = lowered_step(*overrides).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_token_model_scopes_resolve():
    """The token family's step on the CPU: every new scope is in its table,
    the hand-written attention backward's under `bwd`. (What the TPU compiler
    names itself, `ragged-dot-none.2`, is the benchmark reader's to resolve:
    tests/benchmark_tests/test_glm_cell.py.)"""
    from test_lm import LM, VOCAB

    from yet_another_mobilenet_series_tpu.config import ModelConfig, config_from_dict

    cfg = config_from_dict({"optim": {"optimizer": "adamw"}, "ema": {"enable": False},
                            "train": {"compute_dtype": "float32", "batch_size": 2}})
    net = get_model(ModelConfig(arch="glm4_moe_lite", num_classes=VOCAB, lm=LM))
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 2, 10, 1)
    params_example, _ = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
    optimizer = optim.make_optimizer(cfg.optim, lr_fn, params_example)
    step = dp.make_dp_train_step(net, cfg, optimizer, lr_fn, mesh_lib.make_mesh(1), params_example=params_example)
    ts = jax.eval_shape(lambda: steps.init_train_state(net, cfg, optimizer, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((2, LM.seq_len + 2), jnp.int32)}
    text = step.lower(ts, batch, jax.ShapeDtypeStruct((2,), jnp.uint32)).compile().as_text()
    seen = set(scopes.scope_table(text).values())
    assert TOKEN_SCOPES | {"loss", "optim", "residual"} <= {scope for scope, _ in seen}
    assert {("attn_core", "fwd"), ("attn_core", "bwd")} <= seen


def token_step(arch, lm, dtype="float32"):
    """The toy token step of tests/test_lm.py's sizes, lowered as every runner builds it."""
    from test_lm import VOCAB

    from yet_another_mobilenet_series_tpu.config import ModelConfig, config_from_dict

    cfg = config_from_dict({"optim": {"optimizer": "adamw"}, "ema": {"enable": False},
                            "train": {"compute_dtype": dtype, "batch_size": 2}})
    net = get_model(ModelConfig(arch=arch, num_classes=VOCAB, lm=lm))
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 2, 10, 1)
    params_example, _ = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
    optimizer = optim.make_optimizer(cfg.optim, lr_fn, params_example)
    step = dp.make_dp_train_step(net, cfg, optimizer, lr_fn, mesh_lib.make_mesh(1), params_example=params_example)
    ts = jax.eval_shape(lambda: steps.init_train_state(net, cfg, optimizer, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((2, lm.seq_len + 2), jnp.int32)}
    return step.lower(ts, batch, jax.ShapeDtypeStruct((2,), jnp.uint32))


@pytest.mark.parametrize("dtype, digest", [("float32", "f096f8949bb69b6a"), ("bfloat16", "9756a3708728f7be")])
def test_the_glm_step_is_the_program_it_was_before_kimi_linear(dtype, digest):
    """models/lm.py and ops/lm.py serve two archs since PR 33 (a mixer chosen
    by a pattern, `mla_attention` with two options, a block in two halves).
    The toy `glm4_moe_lite` step's lowered module (StableHLO text) is pinned
    by digest (taken with `token_step("glm4_moe_lite", test_lm.LM,
    dtype).as_text()`, the tile and loss blocks as shipped; jax 0.9.0). From
    15ed7fc, PR 33's parent, through PR 33 it was 793e81ba1884fd98 /
    9c566b9bf47247b4. PR 34 ALTERED IT ON PURPOSE: every expert layer is one
    `lax.cond` between a branch over `capacity_rows` rows and the full-length
    body, forward and backward (ops/lm.py `_held_experts`), and the step
    reports `moe_bounded_sites`. A change that means to alter GLM's step
    takes a new digest, and says so."""
    import hashlib

    from test_lm import LM

    text = token_step("glm4_moe_lite", LM, dtype).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


@pytest.mark.parametrize("arch, dtype, digest", [("kimi_linear", "float32", "7252fb0b8c1d3546"),
                                                 ("kimi_linear", "bfloat16", "533db32afde7f000"),
                                                 ("ouro", "float32", "fc4eb44195dfc711"),
                                                 ("ouro", "bfloat16", "e6a9088a2aa12e80")])
def test_the_kimi_and_ouro_steps_are_the_programs_they_were_before_granitemoehybrid(arch, dtype, digest):
    """models/lm.py and ops/lm.py serve a fourth arch, `granitemoehybrid` (a hybrid's
    mixer read from `layer_types`, `mha_attention` with grouped key/value
    heads and no rotation, v filled for the kernels, the multipliers and a
    tied head, the conv kernels with an optional bias). The toy `kimi_linear`
    and `ouro` steps' lowered modules (StableHLO text, as
    `test_the_glm_step_is_...` takes GLM's) are pinned by the digests their
    parent commit, 8550b86, gives: none of that reaches them. A change that
    means to alter one takes a new digest, and says so."""
    import hashlib

    from test_lm import KIMI
    from test_lm_ouro import OURO

    text = token_step(arch, {"kimi_linear": KIMI, "ouro": OURO}[arch], dtype).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_a_hybrid_models_scopes_resolve():
    """`granitemoehybrid`'s step on the CPU: every Mamba-2 scope is in its
    table, the SSD core and the convolution under both phases, beside the
    family's scopes this arch has (attention without `rope`; no expert layer,
    no `mtp_merge`, no KDA, no exit gate) and the residual adds that carry the
    multiplier."""
    from test_lm_granite import GRANITE

    text = token_step("granitemoehybrid", GRANITE).compile().as_text()
    seen = set(scopes.scope_table(text).values())
    names = {scope for scope, _ in seen}
    assert SSD_SCOPES | {"embed", "norm", "attn_proj", "attn_core", "mlp", "lm_head", "loss", "optim", "residual"} <= names
    assert not {"rope", "moe_router", "moe_dispatch", "moe_experts", "moe_combine", "mtp_merge", "exit_gate"} & names
    assert not KDA_SCOPES & names
    assert {(name, phase) for name in ("ssd_core", "ssd_conv", "ssd_proj", "attn_core") for phase in ("fwd", "bwd")} <= seen


def test_a_laguna_models_scopes_resolve():
    """`laguna`'s step on the CPU: the window's core and the per-head gate are
    in its table under both phases, beside the full layers' causal core, the
    rotary tables and rotation, and the expert layer's scopes (the softmax
    router among them); no latent, KDA, Mamba-2 or loop scope."""
    from test_lm_laguna import LAGUNA

    text = token_step("laguna", LAGUNA).compile().as_text()
    seen = set(scopes.scope_table(text).values())
    names = {scope for scope, _ in seen}
    assert WINDOW_SCOPES | (TOKEN_SCOPES - {"mtp_merge"}) | {"loss", "optim", "residual"} <= names
    assert not (KDA_SCOPES | SSD_SCOPES | LOOP_SCOPES | {"mtp_merge"}) & names
    assert {(name, phase) for name in ("attn_window", "attn_gate", "attn_core", "rope") for phase in ("fwd", "bwd")} <= seen


def test_kimi_linear_scopes_resolve():
    """`kimi_linear`'s step on the CPU: every KDA scope is in its table, the
    core's scan and its hand-written backward under both phases, beside the
    family's scopes that this arch has (no `rope`, no `mtp_merge`)."""
    from test_lm import KIMI

    text = token_step("kimi_linear", KIMI).compile().as_text()
    seen = set(scopes.scope_table(text).values())
    assert (TOKEN_SCOPES - {"rope", "mtp_merge"}) | KDA_SCOPES | {"loss", "optim", "residual"} <= {scope for scope, _ in seen}
    assert not {"rope", "mtp_merge"} & {scope for scope, _ in seen}
    assert {(name, phase) for name in ("kda_core", "kda_proj", "kda_conv", "attn_core") for phase in ("fwd", "bwd")} <= seen


def test_a_looped_models_scopes_resolve_and_the_exit_gate_in_both_phases():
    """`ouro`'s step on the CPU: the exit gate's scope is in its table forward
    AND backward (the gate learns through the exit distribution), beside the
    family's scopes that this arch has (`rope` on the whole head; no expert
    layer, no `mtp_merge`), the sandwich's norms and residual adds, and the
    attention core under both phases."""
    from test_lm_ouro import OURO

    text = token_step("ouro", OURO).compile().as_text()
    seen = set(scopes.scope_table(text).values())
    names = {scope for scope, _ in seen}
    assert {"embed", "norm", "rope", "attn_proj", "attn_core", "mlp", "lm_head", "exit_gate", "loss", "optim",
            "residual"} <= names
    assert not {"moe_router", "moe_dispatch", "moe_experts", "moe_combine", "mtp_merge"} & names and not KDA_SCOPES & names
    assert {(name, phase) for name in ("exit_gate", "attn_core", "norm", "residual") for phase in ("fwd", "bwd")} <= seen


def test_taxonomy_version_is_pinned_to_the_scope_sites():
    """The compile cache's key carries TAXONOMY_VERSION (utils/compile_cache.py)
    because JAX leaves metadata out of it: a scope added, renamed or moved
    without a bump would read yesterday's names from the cache. This is the
    reminder: the sites, file by file, as of the version below."""
    pkg = os.path.join(REPO, "yet_another_mobilenet_series_tpu")
    sites = {}
    for root, _, files in os.walk(pkg):
        for name in files:
            if name.endswith(".py") and name != "scopes.py":
                with open(os.path.join(root, name)) as f:
                    found = re.findall(r'\bscope\((?:(?:self|conv)\.scope_name|name|"(\w+)")\)', f.read())
                if found:
                    sites[os.path.relpath(os.path.join(root, name), pkg)] = sorted(found)
    assert (scopes.TAXONOMY_VERSION, sites) == (5, {
        # versions 3 and 4: the token-model family's scopes (PR 27; 3 was its first draft, whose
        # executables may still sit in a chip machine's cache)
        # PR 35 added `exit_gate` (two sites), two `residual` sites, an `embed` and a `rope` site in models/lm.py and
        # two `attn_proj` sites in ops/lm.py, all of them in the step of a NEW arch (`ouro`), whose cache key is new
        # anyway; no older step holds one of them: no bump (a bump would cost every cell one cold start)
        # the hybrid arch added an `ssd_gate` site (the step's lowest chunk decay) and joined two `residual` sites into one
        # (`_branch`, which carries the residual multiplier), and `ops/lm_mamba.py`'s five scopes, all of them
        # new names in the step of a NEW arch (`granitemoehybrid`); no older step holds one or lost one: no bump
        # `laguna` added a `rope` site (its tables by layer type) and an `mlp` site (the shared expert's gate), in the
        # step of a NEW arch; no older step holds one: no bump
        "models/lm.py": ["embed", "embed", "exit_gate", "exit_gate", "kda_gate", "lm_head", "loss", "loss", "mlp",
                         "moe_combine", "moe_router", "mtp_merge", "residual", "residual", "residual", "residual",
                         "rope", "rope", "rope", "ssd_gate"],
        "models/specs.py": ["drop"],
        "ops/activations.py": ["act"],
        "ops/blocks.py": ["drop", "nas_mask", "nas_mask", "residual", "se"],
        # version 2: the conv + BN pair's forward and custom backward (PR 26)
        # PR 34 added a `moe_combine` and a `moe_dispatch` site twice over (the expert layer's two branches) in a step
        # whose program changed with them, so its cache key moved anyway, and no other step holds them: no bump
        # an `attn_core` site for the grouped key/value heads' repeat (granitemoehybrid's step alone)
        # `laguna`'s sliding window added four `attn_window` sites (the core's, the key/value repeat's, the window's
        # custom_vjp forward and backward) and its per-head gate an `attn_gate` site: new names, reached only by the step
        # of a NEW arch (the others' lowered modules are pinned unchanged in tests/test_lm_laguna.py): no bump
        "ops/lm.py": ["attn_core", "attn_core", "attn_core", "attn_core", "attn_core", "attn_gate", "attn_proj", "attn_proj",
                      "attn_proj", "attn_proj", "attn_proj", "attn_window", "attn_window", "attn_window", "attn_window",
                      "mlp", "moe_combine",
                      "moe_combine", "moe_combine", "moe_dispatch", "moe_dispatch", "moe_dispatch", "moe_experts", "moe_router", "norm",
                      "rope"],
        # PR 31 took one `bn_apply` site out with the BatchNorm custom VJP no app or cell selected: no name
        # in a compiled cell moved, so no bump
        # version 5: Kimi Delta Attention's scopes (PR 33)
        # PR 36 added a second `kda_core` site (the fused in-chunk work's backward, a `custom_vjp`'s) in the one step whose
        # program changed with it, so its cache key moved anyway, and no other step holds it: no bump
        # the short convolutions' kernels added two `kda_conv` sites (the forward's and a `custom_vjp`'s backward; q's and
        # k's L2 norms move under `kda_conv` with them) in the one step whose program changed with them: no bump
        # the three `kda_conv` sites take the scope by name (`scope(name)`, "" here): `kda_conv` at every KDA
        # site as before, `ssd_conv` where a Mamba-2 mixer hands its xBC convolution in
        # the scan's kernels added a third `kda_core` site (their `custom_vjp`'s backward) in the one step whose program
        # changed with them, so its cache key moved anyway, and no other step holds it: no bump
        "ops/lm_kda.py": ["", "", "", "kda_core", "kda_core", "kda_core", "kda_gate", "kda_norm", "kda_norm", "kda_proj",
                          "kda_proj"],
        # the fused SSD kernels added two `ssd_core` sites (their `custom_vjp`s' backwards) in the one step whose program
        # changed with them, so its cache key moved anyway, and no other step holds them: no bump
        "ops/lm_mamba.py": ["ssd_core", "ssd_core", "ssd_core", "ssd_gate", "ssd_gate", "ssd_norm", "ssd_proj", "ssd_proj"],
        "ops/layers.py": ["", "", "bn_apply", "bn_apply", "bn_apply", "bn_stats", "bn_stats", "bn_stats",
                          "bn_stats", "dense", "drop", "pool", "syncbn", "syncbn"],
        "parallel/zero.py": ["grad_sync", "grad_sync", "optim", "optim"],
        "train/guard.py": ["guard"],
        "train/steps.py": ["ema", "grad_sync", "input", "loss", "loss", "nas_penalty", "optim", "syncbn"],
    }), "a scope site changed: bump obs.scopes.TAXONOMY_VERSION, then update this pin"


def test_compile_cache_key_carries_the_taxonomy_version(monkeypatch):
    """configure() hashes TAXONOMY_VERSION into the persistent cache's key
    through JAX's own hook; a jax without that hook gets the public flag that
    puts ALL metadata in the key instead (never stale, costlier to iterate on)."""
    from jax._src import cache_key

    from yet_another_mobilenet_series_tpu.utils import compile_cache

    monkeypatch.setattr(cache_key, "custom_hook", lambda: "")
    compile_cache.configure()
    assert cache_key.custom_hook() == f"yamt-scopes-{scopes.TAXONOMY_VERSION}"
    assert jax.config.jax_compilation_cache_include_metadata_in_key is False

    monkeypatch.delattr(cache_key, "custom_hook")
    try:
        compile_cache.configure()
        assert jax.config.jax_compilation_cache_include_metadata_in_key is True
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
