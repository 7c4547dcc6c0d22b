"""The looped arch of the token family (`ouro`: Ouro-2.6B; models/lm.py
`_looped`, `_sandwich`, `_expected_loss`; ops/lm.py `mha_attention`) against
its plain float32 reference (models/lm_reference.py `ouro_*`) at a toy size on
the CPU: hidden 64, 4 heads of 16, MLP 160, 2 layers run 4 times with the same
weights, a vocabulary of 32, 2 x 32 tokens. The exit gate is moved off its
zero start, so that the exit distribution differs from token to token.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_lm import as_lowered_for_a_tpu, attention_runs, worst_leaf

from yet_another_mobilenet_series_tpu.config import LMConfig, ModelConfig, config_from_dict
from yet_another_mobilenet_series_tpu.models import get_model, lm, lm_reference as ref
from yet_another_mobilenet_series_tpu.models.serialize import network_from_dict, network_to_dict
from yet_another_mobilenet_series_tpu.obs.registry import get_registry
from yet_another_mobilenet_series_tpu.ops import lm as ops
from yet_another_mobilenet_series_tpu.ops import lm_attention

OURO = LMConfig(hidden_size=64, num_hidden_layers=2, first_k_dense_replace=2, num_attention_heads=4,
                num_key_value_heads=4, head_dim=16, intermediate_size=160, num_nextn_predict_layers=0,
                n_routed_experts=0, expert_shares=1, rms_norm_eps=1e-6, total_ut_steps=4, seq_len=32, init_std=0.1)
VOCAB = 32
STEPS = [1, 2, 3, 4]


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    """32 tokens in tiles of 8 x 8 and the loss in blocks of 16, as tests/test_lm.py has them."""
    patch = pytest.MonkeyPatch()
    patch.setattr(ops, "ATTN_BLOCK", 8)
    patch.setattr(lm, "LOSS_BLOCK", 16)
    yield
    patch.undo()


def model(config=OURO):
    return get_model(ModelConfig(arch="ouro", num_classes=VOCAB, lm=config))


@functools.lru_cache(maxsize=None)
def setup(steps: int = 4):
    """(net, params with a gate that matters, tokens, the reference's loss, aux and gradients)."""
    config = dataclasses.replace(OURO, total_ut_steps=steps)
    net = model(config)
    params, state = net.init(jax.random.PRNGKey(0))
    assert state == {}
    params["exit_gate"] = {"w": 0.3 * jax.random.normal(jax.random.PRNGKey(7), (config.hidden_size,)),
                           "b": jnp.float32(-0.4)}
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, config.seq_len + 2), 0, VOCAB)
    (ref_loss, aux), ref_grads = jax.jit(lambda p, t: ref.ouro_loss_and_grads(p, t, ref.ouro_dims_of(config)))(
        params, tokens)
    return net, params, tokens, ref_loss, aux, ref_grads


@functools.partial(jax.jit, static_argnums=(0, 3))
def program(net, params, tokens, dtype=jnp.float32):
    return jax.value_and_grad(lambda p: net.loss(p, {}, {"tokens": tokens}, compute_dtype=dtype), has_aux=True)(params)


@pytest.mark.parametrize("steps", STEPS)
def test_loss_every_step_loss_and_every_gradient_leaf_equal_the_reference_in_float32(steps):
    net, params, tokens, ref_loss, aux, ref_grads = setup(steps)
    (loss, (new_state, scalars)), grads = program(net, params, tokens)
    assert new_state == {} and abs(float(loss) - float(ref_loss)) < 1e-5
    for r in range(steps):
        assert abs(float(scalars[f"ce_step_{r + 1}"]) - float(aux["ce_step"][r])) < 1e-5
    assert f"ce_step_{steps + 1}" not in scalars
    for name in ("exit_entropy", "exit_p_last", "expected_exit_step"):
        assert abs(float(scalars[name]) - float(aux[name])) < 1e-5, name
    assert abs(float(scalars["ce"]) - (float(ref_loss) + net.lm.exit_entropy_weight * float(aux["exit_entropy"]))) < 1e-5
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    assert worst_leaf(grads, ref_grads) < 2e-5
    # nothing is cut off from the loss but the gate of a model that never chooses (one step)
    dead = [k for k, g in jax.tree_util.tree_flatten_with_path(grads)[0] if float(jnp.max(jnp.abs(g))) == 0]
    assert len(dead) == (2 if steps == 1 else 0), dead


def test_the_groups_the_step_reports_are_the_gradients_norms():
    net, params, tokens, _, _, ref_grads = setup()
    got = net.grad_scalars(program(net, params, tokens)[1])
    assert set(got) == {"gnorm/embed", "gnorm/head", "gnorm/final_norm", "gnorm/exit_gate",
                        *(f"gnorm/layer_{i}/{g}" for i in range(2) for g in ("attn", "mlp", "norms"))}
    norm = lambda tree: float(jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree))))  # noqa: E731
    assert float(got["gnorm/exit_gate"]) == pytest.approx(norm(ref_grads["exit_gate"]), rel=1e-4)
    four = [ref_grads["layer_1"][k] for k in ("attn_norm", "attn_out_norm", "mlp_norm", "mlp_out_norm")]
    assert float(got["gnorm/layer_1/norms"]) == pytest.approx(norm(four), rel=1e-4)


def test_four_runs_of_shared_layers_equal_an_untied_model_of_copies_and_each_gradient_is_the_sum_of_its_copies():
    """R = 4 over L shared layers against an UNTIED 4L-layer model (the plain
    reference's blocks, one set of parameters an application, each a copy of
    the shared layer): the same loss, and every shared layer's gradient is the
    sum of its four copies'."""
    net, params, tokens, _, _, _ = setup()
    d = ref.ouro_dims_of(net.lm)
    names = list(net.block_names)
    copies = [[params[name] for name in names] for _ in range(d["total_ut_steps"])]
    rest = {k: v for k, v in params.items() if k not in names}

    def untied(copies, rest):
        with jax.default_matmul_precision("highest"):
            seq, total = net.lm.seq_len, 0.0
            for ids in tokens:
                x = rest["embed"][ids[:seq]]
                nll, gates = [], []
                for layers in copies:  # one set of layers a loop step: 4 x 2 = 8 different layers
                    for p in layers:
                        x = ref.ouro_block(p, x, d)
                    x = ref.rms_norm(x, rest["final_norm"], d["rms_norm_eps"])
                    z = x @ rest["head"]
                    nll.append(jax.nn.logsumexp(z, axis=-1) - z[jnp.arange(seq), ids[1:seq + 1]])
                    gates.append(jax.nn.sigmoid(x @ rest["exit_gate"]["w"] + rest["exit_gate"]["b"]))
                p = ref.ouro_exit_distribution(gates)
                h = -sum(q * jnp.log(q) for q in p)
                total = total + jnp.sum(sum(q * c for q, c in zip(p, nll)) - d["exit_entropy_weight"] * h)
            return total / (tokens.shape[0] * seq)

    want_loss, (by_copy, want_rest) = jax.jit(jax.value_and_grad(untied, (0, 1)))(copies, rest)
    (loss, _), grads = program(net, params, tokens)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    summed = {name: jax.tree.map(lambda *g: sum(g), *[by_copy[r][i] for r in range(len(copies))])
              for i, name in enumerate(names)}
    assert worst_leaf({k: grads[k] for k in names}, summed) < 2e-5
    assert worst_leaf({k: grads[k] for k in rest}, want_rest) < 2e-5
    # and no one copy's gradient is the whole: the four uses all count
    assert worst_leaf({k: grads[k] for k in names}, {name: by_copy[0][i] for i, name in enumerate(names)}) > 0.1


def test_one_loop_step_is_the_plain_model_p_is_one_the_entropy_zero_and_the_loss_the_cross_entropy():
    net, params, tokens, ref_loss, aux, _ = setup(1)
    (loss, (_, scalars)), grads = program(net, params, tokens)
    assert float(scalars["exit_p_last"]) == 1.0 and float(scalars["exit_entropy"]) == 0.0
    assert float(scalars["expected_exit_step"]) == 1.0
    assert float(loss) == pytest.approx(float(scalars["ce"]), rel=1e-6) == pytest.approx(float(scalars["ce_step_1"]), rel=1e-6)
    assert abs(float(loss) - float(aux["ce_step"][0])) < 1e-5
    assert not float(jnp.max(jnp.abs(grads["exit_gate"]["w"]))) and not float(grads["exit_gate"]["b"])


def test_the_exit_distribution_sums_to_one_and_the_loss_is_the_expectation_by_hand_on_three_tokens():
    """Three tokens, four steps: p by the products written out with numpy,
    sum_r p_r = 1, loss = mean(sum_r p_r CE_r - beta H), the last gate unread."""
    net = model()
    nll = np.array([[2.0, 3.0, 1.0], [1.5, 3.5, 0.5], [1.0, 4.0, 0.25], [0.5, 5.0, 0.125]], np.float32)
    logits = np.array([[0.0, 2.0, -3.0], [1.0, -1.0, 0.5], [-2.0, 0.3, 30.0], [9.0, -9.0, 0.0]], np.float32)
    g = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    p = np.stack([g[0], g[1] * (1 - g[0]), g[2] * (1 - g[0]) * (1 - g[1]), (1 - g[0]) * (1 - g[1]) * (1 - g[2])])
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-12)
    entropy = -(p * np.log(p)).sum(axis=0)
    want = np.mean((p * nll).sum(axis=0) - net.lm.exit_entropy_weight * entropy)
    expected_loss = jax.jit(net._expected_loss)
    loss, scalars = expected_loss(jnp.asarray(nll), jnp.asarray(logits))
    assert float(loss) == pytest.approx(want, rel=1e-5)
    assert float(scalars["exit_p_last"]) == pytest.approx(p[3].mean(), rel=1e-5)
    assert float(scalars["exit_entropy"]) == pytest.approx(entropy.mean(), rel=1e-5)
    assert float(scalars["expected_exit_step"]) == pytest.approx((p * np.arange(1, 5)[:, None]).sum(axis=0).mean(), rel=1e-5)
    moved, _ = expected_loss(jnp.asarray(nll), jnp.asarray(logits).at[3].set(-5.0))
    assert float(moved) == float(loss)  # g_R is not read


def test_a_fresh_gate_exits_with_a_half_a_quarter_an_eighth_and_an_eighth():
    net = model()
    params, _ = net.init(jax.random.PRNGKey(0))
    assert not float(jnp.max(jnp.abs(params["exit_gate"]["w"]))) and params["exit_gate"]["b"].shape == ()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, OURO.seq_len + 2), 0, VOCAB)
    (_, (_, scalars)), grads = program(net, params, tokens)
    assert float(scalars["expected_exit_step"]) == pytest.approx(0.5 + 2 * 0.25 + 3 * 0.125 + 4 * 0.125)  # 1.875
    assert float(scalars["exit_p_last"]) == pytest.approx(0.125)
    assert float(scalars["exit_entropy"]) == pytest.approx(0.5 * np.log(2) + 0.25 * np.log(4) + 0.25 * np.log(8), rel=1e-6)
    assert float(jnp.max(jnp.abs(grads["exit_gate"]["w"]))) > 0  # the gate learns from its first step


def test_mha_attention_equals_a_dense_masked_softmax_over_rotated_heads():
    net, params, tokens, _, _, _ = setup()
    c = net.lm
    x = jax.random.normal(jax.random.PRNGKey(3), (2, c.seq_len, c.hidden_size))
    cos, sin = ops.rope_tables(c.seq_len, c.head_dim, c.rope_theta)
    p = params["layer_1"]["attn"]
    got, grads = jax.jit(jax.value_and_grad(lambda p_, x_: jnp.sum(jnp.square(ops.mha_attention(
        p_, x_, cos, sin, heads=c.num_attention_heads, head_dim=c.head_dim))), (0, 1)))(p, x)
    d = ref.ouro_dims_of(c)

    def plain(p_, x_):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(jnp.square(jnp.stack([ref.ouro_attention(p_, row, d) for row in x_])))

    want, want_grads = jax.jit(jax.value_and_grad(plain, (0, 1)))(p, x)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want))
    assert worst_leaf(grads, want_grads) < 2e-5


def test_mha_attention_goes_through_the_fused_kernels_at_128_by_128(monkeypatch):
    """At the published head dims (128 / 128), bfloat16, a sequence of three
    256-row tiles: a TPU lowering takes the kernels (interpret mode here), and
    the layer's output stays as near the float32 reference as the loops' own
    bfloat16."""
    monkeypatch.setattr(ops, "ATTN_BLOCK", 256)
    seq, heads, width, h = 768, 2, 128, 64
    assert lm_attention.fuses(seq, 256, width, width, jnp.bfloat16)
    key = jax.random.PRNGKey(4)
    p = {n: 0.1 * jax.random.normal(jax.random.fold_in(key, i), (h, heads * width)) for i, n in enumerate("qkv")}
    p["o"] = 0.1 * jax.random.normal(jax.random.fold_in(key, 9), (heads * width, h))
    x = jax.random.normal(jax.random.fold_in(key, 5), (1, seq, h))
    cos, sin = ops.rope_tables(seq, width, 1e6)
    d = {"num_attention_heads": heads, "head_dim": width, "rope_theta": 1e6}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda: ref.ouro_attention(p, x[0], d))()

    def layer():
        return jax.jit(lambda: ops.mha_attention(p, x.astype(jnp.bfloat16), cos, sin, heads=heads, head_dim=width))()[0]

    loops = layer().astype(jnp.float32)
    as_lowered_for_a_tpu(monkeypatch)
    fused = layer().astype(jnp.float32)
    assert 1e-4 < worst_leaf(loops, want) < 3e-2
    assert worst_leaf(fused, want) <= 1.05 * worst_leaf(loops, want)
    assert float(jnp.max(jnp.abs(fused - loops))) > 0  # another lowering ran


@pytest.mark.parametrize("how, forwards_an_application", [("kept", 1), ("plain", 2)])
def test_the_gradient_runs_the_attention_forward_once_a_layer_application(monkeypatch, how, forwards_an_application):
    """PR 32's property times the loop: the layer checkpoint keeps attention's
    output and log-sum-exp by name in EVERY application, so the gradient's
    jaxpr holds layers x loop steps forwards and as many backwards, where a
    plain `jax.checkpoint` holds twice the forwards."""
    net, params, tokens, _, _, _ = setup()
    assert net.attention_sites(jnp.float32) == (2, 0) and (net.loop_steps, net.layer_applications) == (4, 8)
    if how == "plain":
        real = jax.checkpoint
        monkeypatch.setattr(jax, "checkpoint", lambda fn, **kw: real(fn))
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: net.loss(p, {}, {"tokens": tokens})[0]))(params)
    assert attention_runs(jaxpr.jaxpr) == {"fwd": forwards_an_application * 8, "bwd": 8}


def test_what_the_layer_checkpoint_keeps_changes_no_number(monkeypatch):
    net, params, tokens, _, _, _ = setup()
    (loss, _), grads = program(net, params, tokens)
    monkeypatch.setattr(jax, "checkpoint", lambda fn, **kw: fn)
    (want_loss, _), want = jax.jit(lambda p: jax.value_and_grad(
        lambda p_: net.loss(p_, {}, {"tokens": tokens}), has_aux=True)(p))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-6 and worst_leaf(grads, want) < 1e-5


def test_bfloat16_is_within_its_tolerance():
    net, params, tokens, ref_loss, aux, ref_grads = setup()
    (loss, (_, scalars)), grads = program(net, params, tokens, jnp.bfloat16)
    assert abs(float(loss) - float(ref_loss)) < 2e-2 * abs(float(ref_loss))
    assert all(g.dtype == jnp.float32 for g in jax.tree.leaves(grads))  # float32 weights take float32 gradients
    got, want = net.grad_scalars(grads), net.grad_scalars(ref_grads)
    assert max(abs(float(got[k]) - float(want[k])) / float(want[k]) for k in want) < 5e-2


def test_eval_reads_the_last_loop_steps_head():
    net, params, tokens, _, aux, _ = setup()
    counts = jax.jit(lambda p: net.eval_counts(p, {}, {"tokens": tokens}))(params)
    last = aux["logits"][:, -1]  # (B, S, V) of the last loop step
    targets = tokens[:, 1:net.lm.seq_len + 1]
    nll = jax.nn.logsumexp(last, axis=-1) - jnp.take_along_axis(last, targets[..., None], axis=-1)[..., 0]
    assert float(counts["loss_sum"]) == pytest.approx(float(jnp.sum(nll)), rel=1e-5)
    assert float(counts["top1"]) == float(jnp.sum(jnp.argmax(last, axis=-1) == targets))
    assert float(counts["n"]) == 64.0 and float(counts["top5"]) >= float(counts["top1"])


@pytest.mark.parametrize("change, complaint", [
    ({"num_key_value_heads": 2}, "grouped heads are not guessed"),
    ({"head_dim": 15}, "head_dim must be even"),
    ({"head_dim": None}, "head_dim must be even"),
    ({"first_k_dense_replace": 1}, "no expert layer"),
    ({"num_nextn_predict_layers": 1}, "no expert layer"),
    ({"total_ut_steps": 0}, "at least 1"),
    ({"first_k_dense_replace": 3}, "every layer of a model without expert layers"),
])
def test_validate_refuses_what_the_arch_would_have_to_guess(change, complaint):
    with pytest.raises(ValueError, match=complaint):
        model(dataclasses.replace(OURO, **change))


def test_num_key_value_heads_left_out_means_as_many_as_query_heads():
    assert model(dataclasses.replace(OURO, num_key_value_heads=None)).param_count() == model().param_count()


def test_the_published_widths_give_the_parameter_count_of_the_cut_and_of_the_source(monkeypatch):
    monkeypatch.setattr(ops, "ATTN_BLOCK", 512)  # as shipped: the fixture's 8-row tiles are no kernel's
    lm_config = LMConfig(hidden_size=2048, num_hidden_layers=8, first_k_dense_replace=8, num_attention_heads=16,
                         num_key_value_heads=16, head_dim=128, intermediate_size=5632, num_nextn_predict_layers=0,
                         total_ut_steps=4, seq_len=8192)
    net = get_model(ModelConfig(arch="ouro", num_classes=49152, lm=lm_config))
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert net.param_count() == 8 * layer + 2 * 49152 * 2048 + 2048 + 2049 == 612_438_017
    assert net.attention_sites(jnp.bfloat16) == (8, 8) and net.layer_applications == 32
    assert (net.expert_sites, net.kda_sites, net.expert_capacity_rows(1)) == (0, 0, 0)
    whole = dataclasses.replace(lm_config, num_hidden_layers=48, first_k_dense_replace=48)
    assert get_model(ModelConfig(arch="ouro", num_classes=49152, lm=whole)).param_count() == 48 * layer + 2 * 49152 * 2048 + 4097


def test_get_model_resolves_the_arch_and_the_spec_round_trips():
    net = model()
    assert net.arch == "ouro" and net.looped and network_from_dict(network_to_dict(net)) == net
    assert not get_model(ModelConfig(arch="glm4_moe_lite", num_classes=VOCAB)).looped


def test_the_train_step_reports_the_loop_its_gauges_and_its_scalars():
    """`train/steps.py` through `parallel/dp.py`, one step on the CPU: the
    gauges a looped model sets (and the expert gauges at 0), the step's
    scalars, a gate that moved."""
    from yet_another_mobilenet_series_tpu.parallel import dp, mesh as mesh_lib
    from yet_another_mobilenet_series_tpu.train import optim, schedules, steps

    cfg = config_from_dict({"optim": {"optimizer": "adamw", "weight_decay": 0.0}, "ema": {"enable": False},
                            "schedule": {"schedule": "constant", "base_lr": 1e-3, "warmup_epochs": 0.0,
                                         "scale_by_batch": False},
                            "train": {"compute_dtype": "float32", "batch_size": 2}})
    net = model()
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 2, 10, 1)
    params_example, _ = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
    optimizer = optim.make_optimizer(cfg.optim, lr_fn, params_example)
    step = dp.make_dp_train_step(net, cfg, optimizer, lr_fn, mesh_lib.make_mesh(1), params_example=params_example)
    ts = steps.init_train_state(net, cfg, optimizer, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, OURO.seq_len + 2), 0, VOCAB)
    new, metrics = step(ts, {"tokens": tokens}, jax.random.PRNGKey(2))
    snap = get_registry().snapshot()
    assert (snap["train.loop_steps"], snap["train.layer_applications"], snap["train.attn_sites"]) == (4.0, 8.0, 2.0)
    assert (snap["train.attn_kept_sites"], snap["train.kda_sites"], snap["train.moe_sites"],
            snap["train.moe_capacity_rows"], snap["train.attn_fused_sites"]) == (2.0, 0.0, 0.0, 0.0, 0.0)
    assert {"loss", "ce", "top1", "ce_step_1", "ce_step_2", "ce_step_3", "ce_step_4", "exit_p_last", "exit_entropy",
            "expected_exit_step", "gnorm/exit_gate", "gnorm/layer_0/attn", "gnorm/layer_1/norms", "grad_norm",
            "lr"} <= set(metrics)
    assert not [k for k in metrics if k.startswith("moe_")]
    assert float(metrics["expected_exit_step"]) == pytest.approx(1.875) and float(metrics["finite"]) == 1.0
    assert float(jnp.max(jnp.abs(new.params["exit_gate"]["w"]))) > 0 and new.state == {}
    glm = get_model(ModelConfig(arch="glm4_moe_lite", num_classes=VOCAB, lm=dataclasses.replace(
        OURO, first_k_dense_replace=1, head_dim=None, num_key_value_heads=None, n_routed_experts=16, expert_shares=8,
        kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, q_lora_rank=24,
        moe_intermediate_size=48, num_experts_per_tok=2, num_nextn_predict_layers=1)))
    assert (glm.loop_steps, glm.layer_applications) == (1, 3)  # whatever total_ut_steps says: the arch is not looped
