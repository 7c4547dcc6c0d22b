import numpy as np
import pytest

import jax
import jax.numpy as jnp

from yet_another_mobilenet_series_tpu.config import Config, EMAConfig, OptimConfig, ScheduleConfig, config_from_dict
from yet_another_mobilenet_series_tpu.train import ema as ema_lib
from yet_another_mobilenet_series_tpu.train import losses, optim, schedules, steps
from yet_another_mobilenet_series_tpu.models import get_model


def test_label_smoothing_matches_torch():
    import torch

    logits = np.random.RandomState(0).normal(size=(8, 10)).astype(np.float32)
    labels = np.random.RandomState(1).randint(0, 10, size=(8,))
    ours = losses.cross_entropy_label_smooth(jnp.asarray(logits), jnp.asarray(labels), 0.1)
    ref = torch.nn.functional.cross_entropy(torch.from_numpy(logits), torch.from_numpy(labels), label_smoothing=0.1)
    np.testing.assert_allclose(float(ours), float(ref), rtol=1e-5)
    # smoothing=0 degenerates to plain CE
    ours0 = losses.cross_entropy_label_smooth(jnp.asarray(logits), jnp.asarray(labels), 0.0)
    ref0 = torch.nn.functional.cross_entropy(torch.from_numpy(logits), torch.from_numpy(labels))
    np.testing.assert_allclose(float(ours0), float(ref0), rtol=1e-5)


def test_topk_correct():
    logits = jnp.asarray([[0.1, 0.9, 0.0, 0.0], [0.9, 0.1, 0.0, 0.0], [0.0, 0.1, 0.2, 0.7]])
    labels = jnp.asarray([1, 1, 0])
    out = losses.topk_correct(logits, labels, ks=(1, 3))
    assert float(out["top1"]) == 1.0  # only first row top-1 correct
    assert float(out["top3"]) == 2.0  # row2 label 0 is rank 3 (out of top-3... rank within top3)


def test_lr_exp_decay_staircase():
    cfg = ScheduleConfig(schedule="exp_decay", base_lr=0.1, scale_by_batch=False, warmup_epochs=2.0, decay_rate=0.9, decay_epochs=1.0)
    lr = schedules.make_lr_schedule(cfg, total_batch=256, steps_per_epoch=10, total_epochs=10)
    assert float(lr(0)) == 0.0
    np.testing.assert_allclose(float(lr(10)), 0.05, rtol=1e-6)  # mid-warmup (20 steps)
    np.testing.assert_allclose(float(lr(20)), 0.1, rtol=1e-6)  # warmup done
    np.testing.assert_allclose(float(lr(29)), 0.1, rtol=1e-6)  # staircase holds
    np.testing.assert_allclose(float(lr(30)), 0.09, rtol=1e-6)  # first decay
    np.testing.assert_allclose(float(lr(50)), 0.1 * 0.9**3, rtol=1e-6)


def test_lr_cosine_endpoints():
    cfg = ScheduleConfig(schedule="cosine", base_lr=0.2, scale_by_batch=False, warmup_epochs=0.0, final_lr_factor=0.0)
    lr = schedules.make_lr_schedule(cfg, total_batch=256, steps_per_epoch=100, total_epochs=10)
    np.testing.assert_allclose(float(lr(0)), 0.2, rtol=1e-6)
    np.testing.assert_allclose(float(lr(500)), 0.1, rtol=1e-5)
    assert float(lr(1000)) < 1e-8


def test_lr_batch_scaling():
    cfg = ScheduleConfig(schedule="constant", base_lr=0.064, scale_by_batch=True, warmup_epochs=0.0)
    lr = schedules.make_lr_schedule(cfg, total_batch=1024, steps_per_epoch=10, total_epochs=1)
    np.testing.assert_allclose(float(lr(5)), 0.064 * 4, rtol=1e-6)


def test_ema_algebra_and_warmup():
    cfg = EMAConfig(enable=True, decay=0.5, warmup=False)
    shadow = {"w": jnp.asarray(1.0)}
    val = {"w": jnp.asarray(3.0)}
    out = ema_lib.ema_update(cfg, shadow, val, step=0)
    np.testing.assert_allclose(float(out["w"]), 0.5 * 1 + 0.5 * 3)
    # warmup: at step 0 effective decay = min(0.9999, 1/10) = 0.1
    cfgw = EMAConfig(enable=True, decay=0.9999, warmup=True)
    outw = ema_lib.ema_update(cfgw, shadow, val, step=0)
    np.testing.assert_allclose(float(outw["w"]), 0.1 * 1 + 0.9 * 3, rtol=1e-6)


def test_wd_mask_exemptions():
    cfg = OptimConfig(wd_skip_bn=True, wd_skip_bias=True, wd_skip_depthwise=True)
    params = {
        "stem": {"conv": {"w": 0}, "bn": {"gamma": 0, "beta": 0}},
        "blocks": {"0": {"dw0_k3": {"w": 0}, "dw_bn": {"gamma": 0, "beta": 0}, "project": {"w": 0}}},
        "classifier": {"w": 0, "b": 0},
    }
    m = optim.wd_mask(params, cfg)
    assert m["stem"]["conv"]["w"] is True
    assert m["stem"]["bn"]["gamma"] is False
    assert m["blocks"]["0"]["dw0_k3"]["w"] is False  # depthwise exempt
    assert m["blocks"]["0"]["dw_bn"]["gamma"] is False
    assert m["blocks"]["0"]["project"]["w"] is True
    assert m["classifier"]["w"] is True and m["classifier"]["b"] is False
    # depthwise decayed when flag off
    m2 = optim.wd_mask(params, OptimConfig(wd_skip_depthwise=False))
    assert m2["blocks"]["0"]["dw0_k3"]["w"] is True


def test_rmsprop_tf_semantics_one_step():
    """Manual check: nu0=1 (TF initial_scale), eps inside sqrt, momentum after."""
    cfg = OptimConfig(optimizer="rmsprop", momentum=0.9, rmsprop_decay=0.9, rmsprop_eps=0.01, weight_decay=0.0)
    params = {"w": jnp.asarray(2.0)}
    opt = optim.make_optimizer(cfg, lambda s: 0.1, params)
    st = opt.init(params)
    g = {"w": jnp.asarray(0.5)}
    upd, _ = opt.update(g, st, params)
    nu = 0.9 * 1.0 + 0.1 * 0.5**2
    rms = 0.5 / np.sqrt(nu + 0.01)
    mom = 0.9 * 0.0 + rms
    np.testing.assert_allclose(float(upd["w"]), -0.1 * mom, rtol=1e-5)


def test_rmsprop_tf_momentum_order_across_lr_boundary():
    """TF ordering bakes each step's LR into the momentum buffer; compare the
    full optax chain against hand-computed TF-RMSProp across an LR decay
    (0.1 -> 0.01 at step 2), where the torch ordering diverges."""
    d, eps, m = 0.9, 0.01, 0.9
    lrs = [0.1, 0.1, 0.01, 0.01]
    grads = [0.5, -0.3, 0.2, 0.4]

    cfg = OptimConfig(optimizer="rmsprop", momentum=m, rmsprop_decay=d, rmsprop_eps=eps, weight_decay=0.0)
    params = {"w": jnp.asarray(2.0)}
    opt = optim.make_optimizer(cfg, lambda s: jnp.asarray(lrs)[s], params)
    st = opt.init(params)
    p_opt = params
    for g in grads:
        upd, st = opt.update({"w": jnp.asarray(g)}, st, p_opt)
        p_opt = {"w": p_opt["w"] + upd["w"]}

    # hand-computed TF RMSProp: nu0=1; mom = m*mom + lr_t*g/sqrt(nu+eps)
    nu, mom, p = 1.0, 0.0, 2.0
    for lr, g in zip(lrs, grads):
        nu = d * nu + (1 - d) * g * g
        mom = m * mom + lr * g / np.sqrt(nu + eps)
        p -= mom
    np.testing.assert_allclose(float(p_opt["w"]), p, rtol=1e-6)

    # torch ordering (switch off): mom accumulates unscaled rms, lr at apply
    cfg_t = OptimConfig(optimizer="rmsprop", momentum=m, rmsprop_decay=d, rmsprop_eps=eps,
                        weight_decay=0.0, rmsprop_tf_momentum_order=False)
    opt_t = optim.make_optimizer(cfg_t, lambda s: jnp.asarray(lrs)[s], params)
    st_t = opt_t.init(params)
    p_torch = params
    for g in grads:
        upd, st_t = opt_t.update({"w": jnp.asarray(g)}, st_t, p_torch)
        p_torch = {"w": p_torch["w"] + upd["w"]}
    nu, mom, p2 = 1.0, 0.0, 2.0
    for lr, g in zip(lrs, grads):
        nu = d * nu + (1 - d) * g * g
        mom = m * mom + g / np.sqrt(nu + eps)
        p2 -= lr * mom
    np.testing.assert_allclose(float(p_torch["w"]), p2, rtol=1e-6)
    # the two orderings genuinely differ once LR decays
    assert abs(p - p2) > 1e-4


def test_rmsprop_orderings_agree_at_constant_lr():
    grads = [0.5, -0.3, 0.2]
    params = {"w": jnp.asarray(2.0)}
    outs = []
    for tf_order in (True, False):
        cfg = OptimConfig(optimizer="rmsprop", momentum=0.9, rmsprop_decay=0.9,
                          rmsprop_eps=0.01, weight_decay=0.0, rmsprop_tf_momentum_order=tf_order)
        opt = optim.make_optimizer(cfg, lambda s: 0.1, params)
        st = opt.init(params)
        p = params
        for g in grads:
            upd, st = opt.update({"w": jnp.asarray(g)}, st, p)
            p = {"w": p["w"] + upd["w"]}
        outs.append(float(p["w"]))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6)


def test_weight_decay_coupled_before_rms():
    cfg = OptimConfig(optimizer="sgd", momentum=0.0, weight_decay=0.1)
    params = {"conv": {"w": jnp.asarray(2.0)}}
    opt = optim.make_optimizer(cfg, lambda s: 1.0, params)
    st = opt.init(params)
    upd, _ = opt.update({"conv": {"w": jnp.asarray(0.0)}}, st, params)
    # pure decay: grad 0 + wd*param = 0.2
    np.testing.assert_allclose(float(upd["conv"]["w"]), -0.2, rtol=1e-6)


def test_step_cadence_fires_every_boundary_exactly_once():
    from yet_another_mobilenet_series_tpu.utils.cadence import StepCadence

    # fractional-epoch chunks (spe=7, epochs=2.43): checks happen at chunk
    # ends 7, 14, 17 — boundaries 7 and 14 fire once each, 17 is no boundary
    cad = StepCadence(1.0, 7)
    assert [cad.due(s) for s in (7, 14, 17)] == [True, True, False]

    # no float drift over many epochs (the `epoch % every < 1e-6` failure)
    cad = StepCadence(1.0, 3)
    fired = sum(cad.due(s) for s in range(3, 301, 3))
    assert fired == 100

    # cadence coarser than a step-chunk: 2.5 epochs * 4 spe = every 10 steps
    cad = StepCadence(2.5, 4)
    fired_at = [s for s in range(1, 25) if cad.due(s)]
    assert fired_at == [10, 20]

    # a jump over several boundaries fires once, then resumes normally
    cad = StepCadence(1.0, 5)
    assert cad.due(17) is True  # crossed 5, 10, 15 -> one event
    assert cad.due(19) is False
    assert cad.due(20) is True

    # resume anchoring: boundaries at or before start_step already fired
    cad = StepCadence(1.0, 7, start_step=14)
    assert cad.due(14) is False
    assert cad.due(21) is True

    # disabled
    cad = StepCadence(0.0, 7)
    assert not any(cad.due(s) for s in range(100))

    # sub-step cadence clamps to every step, never to zero
    cad = StepCadence(0.25, 2)
    assert [cad.due(s) for s in (1, 2, 3)] == [True, True, True]


def _tiny_cfg(**over):
    d = {
        "model": {
            "arch": "mobilenet_v2",
            "num_classes": 4,
            "dropout": 0.0,
            "block_specs": [
                {"t": 2, "c": 8, "n": 1, "s": 2},
                {"t": 2, "c": 16, "n": 1, "s": 2, "k": [3, 5]},
            ],
        },
        "optim": {"optimizer": "rmsprop", "weight_decay": 1e-5},
        "schedule": {"schedule": "constant", "base_lr": 0.05, "scale_by_batch": False, "warmup_epochs": 0.0},
        "ema": {"enable": True, "decay": 0.9, "warmup": False},
        "train": {"compute_dtype": "float32"},
    }
    d.update(over)
    return config_from_dict(d)


@pytest.mark.parametrize("chips", [1, 4], ids=["one_device", "dp4_syncbn"])
def test_remat_step_equals_plain_step(chips):
    """train.remat must be a pure memory/recompute trade: the updated params
    after one step are BIT-IDENTICAL to the non-remat step's on CPU f32
    (jax.checkpoint changes scheduling, not math), the conv + BN pair
    recomputed under it included; on a mesh its psums are recomputed too
    (what __graft_entry__.py's second step runs on the chip)."""
    from yet_another_mobilenet_series_tpu.parallel import dp, mesh as mesh_lib

    batch = {
        "image": jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16, 3)),
        "label": jnp.arange(8) % 4,
    }
    rng = jax.random.PRNGKey(42)
    results = []
    for remat_over in ({}, {"remat": True}):
        cfg = _tiny_cfg(train={"compute_dtype": "float32", **remat_over})
        net = get_model(cfg.model, image_size=16)
        lr_fn = schedules.make_lr_schedule(cfg.schedule, 8, 1, 100)
        params, _ = net.init(jax.random.PRNGKey(0))
        opt = optim.make_optimizer(cfg.optim, lr_fn, params)
        ts = steps.init_train_state(net, cfg, opt, jax.random.PRNGKey(0))
        if chips == 1:
            step_fn, b = jax.jit(steps.make_train_step(net, cfg, opt, lr_fn)), batch
        else:
            mesh = mesh_lib.make_mesh(chips)
            step_fn, ts, b = dp.make_dp_train_step(net, cfg, opt, lr_fn, mesh), mesh_lib.replicate(ts, mesh), mesh_lib.shard_batch(batch, mesh)
        ts, metrics = step_fn(ts, b, rng)
        results.append((ts, metrics))
    (ts_plain, met_plain), (ts_remat, met_remat) = results
    assert float(met_plain["loss"]) == float(met_remat["loss"])
    assert float(met_plain["grad_norm"]) == float(met_remat["grad_norm"])
    for a, b in zip(jax.tree.leaves(ts_plain.params), jax.tree.leaves(ts_remat.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _loss_and_grads(net, params, state, batch, masks=None):
    def loss(p):
        logits, new_state = net.apply(p, state, batch["image"], train=True, masks=masks)
        return jnp.mean(losses.cross_entropy_label_smooth(logits, batch["label"], 0.1)), new_state

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)


def test_conv_bn_pair_leaves_loss_and_every_gradient_leaf_as_they_were(monkeypatch):
    """The two-block net at float32, its expand convs and head lowered through
    ops/layers.py's conv + BN pair (what a train step does) against the same
    net with the pair switched off in the test: same loss, same BN state,
    every gradient leaf equal to 1e-4 of its largest entry. The float32
    reference a change of reduction order has to bring (ROADMAP Queue 2 item 6)."""
    from yet_another_mobilenet_series_tpu.ops import layers

    cfg = _tiny_cfg()
    net = get_model(cfg.model, image_size=16)
    assert net.conv_bn_pair_sites() == (3, 5)  # two expands + the head; + two projects
    params, state = net.init(jax.random.PRNGKey(0))
    batch = {"image": jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16, 3)), "label": jnp.arange(8) % 4}
    (loss_pair, state_pair), grads_pair = _loss_and_grads(net, params, state, batch)
    monkeypatch.setattr(layers, "conv_bn_pairs", lambda *a, **kw: False)
    (loss_plain, state_plain), grads_plain = _loss_and_grads(net, params, state, batch)
    assert float(loss_pair) == float(loss_plain)
    for a, b in zip(jax.tree.leaves(state_pair), jax.tree.leaves(state_plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    flat_pair, flat_plain = (dict(jax.tree_util.tree_leaves_with_path(g)) for g in (grads_pair, grads_plain))
    assert flat_pair.keys() == flat_plain.keys()
    # BN's shift invariance makes some leaves (a bias-like direction ahead of a
    # BN) pure cancellation: those are held to the largest gradient in the net
    floor = 1e-6 * max(float(np.abs(np.asarray(g)).max()) for g in flat_plain.values())
    for path, want in flat_plain.items():
        want, got = np.asarray(want), np.asarray(flat_pair[path])
        assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max() + floor, jax.tree_util.keystr(path)


def test_train_step_reports_how_many_sites_the_pair_lowers():
    """make_train_step sets the two registry gauges from the network alone:
    train.conv_bn_pairs, train.conv_bn_pair_eligible."""
    from yet_another_mobilenet_series_tpu.obs.registry import get_registry

    for gauge in ("train.conv_bn_pairs", "train.conv_bn_pair_eligible"):
        get_registry().gauge(gauge).set(-1.0)
    cfg = _tiny_cfg(train={"compute_dtype": "float32"})
    net = get_model(cfg.model, image_size=16)
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 8, 1, 100)
    params, _ = net.init(jax.random.PRNGKey(0))
    steps.make_train_step(net, cfg, optim.make_optimizer(cfg.optim, lr_fn, params), lr_fn)
    assert get_registry().gauge("train.conv_bn_pairs").value == 3.0
    assert get_registry().gauge("train.conv_bn_pair_eligible").value == 5.0


@pytest.mark.parametrize("arch, expect", [
    ("mobilenet_v1", (0, 13)),  # 13 pointwise convs, none behind an expand
    ("mobilenet_v2", (17, 34)),
    ("mobilenet_v3_large", (15, 30)),
    ("mobilenet_v3_small", (11, 22)),
    ("mnasnet_a1", (16, 32)),
    ("atomnas_supernet", (17, 34)),
    ("atomnas_supernet_se", (17, 34)),
    ("efficientnet_b0", (16, 32)),
    ("efficientnet_lite0", (16, 32)),
])
def test_conv_bn_pair_sites_of_the_benchmarks_networks(arch, expect):
    """The benchmark's two (14 of MobileNetV3-Large's 15 blocks, the first
    having no expand, and its head; all 15 expanding blocks of
    EfficientNet-B0 and its head) and the rest of the zoo: every expand conv
    and head widens, every project conv is eligible and narrows."""
    from yet_another_mobilenet_series_tpu.config import ModelConfig
    from yet_another_mobilenet_series_tpu.models.zoo import ARCHS

    assert arch in ARCHS and len(ARCHS) == 9
    net = get_model(ModelConfig(arch=arch), 224)
    assert net.conv_bn_pair_sites() == expect


@pytest.mark.parametrize("kind, name, value", [
    ("key", "bn_mode", "exact"), ("key", "conv1x1_dot", "1"), ("key", "remat_policy", "full"),
    ("key", "steps_per_dispatch", "1"), ("key", "tuning_file", "1"),
    ("yaml", "bn_mode", "fused_vjp"), ("yaml", "tuning_file", "/path/to/BENCH_TUNING.json"),
])
def test_removed_step_options_are_refused_with_what_is_valid(tmp_path, kind, name, value):
    """PRs 30 and 31 took the step's forks out with no alias: a config that
    still names one, even with the value that was its default, fails where it
    is loaded (an override, or an app's YAML) with the section's valid keys."""
    from yet_another_mobilenet_series_tpu.config import parse_cli

    if kind == "yaml":
        app = tmp_path / "old_app.yml"
        app.write_text(f"train:\n  batch_size: 8\n  {name}: {value}\n")
        argv = [f"app:{app}"]
    else:
        argv = [f"train.{name}={value}"]
    with pytest.raises(KeyError, match=rf"unknown config key\(s\) \['{name}'\] in section 'train'; valid: .*'batch_size'.*'remat'") as e:
        parse_cli(argv)
    assert not any(f"'{gone}'" in str(e.value).split("valid:")[1]
                   for gone in ("bn_mode", "conv1x1_dot", "remat_policy", "steps_per_dispatch", "tuning_file"))


@pytest.mark.slow
def test_conv_bn_pair_converges_as_plain_autodiff_does(monkeypatch):
    """300 training steps with the conv + BN pair, as the step is built,
    track the loss trajectory of the same step with every site on plain
    autodiff (single device, f32) with bounded divergence: the forward is
    the same and the closed-form backward a re-association of the same sums.

    Raw losses cannot stay close for hundreds of steps: benign ~1e-7
    re-association differences compound chaotically through RMSProp's rsqrt
    (~0.5% rel by step 20, observed). The long-horizon guarantee is "same
    optimization", asserted as (a) both converge to the same overfit
    plateau band, and (b) end-state train-batch predictions match exactly."""
    from yet_another_mobilenet_series_tpu.ops import layers

    batch = {
        "image": jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16, 3)),
        "label": jnp.arange(8) % 4,
    }
    rng = jax.random.PRNGKey(42)
    n_steps, tail = 300, 50
    traces, end_preds = {}, {}
    for mode in ("paired", "unpaired"):
        if mode == "unpaired":  # the site decides where the step is traced, which is its first call
            monkeypatch.setattr(layers, "conv_bn_pairs", lambda *a, **kw: False)
        cfg = _tiny_cfg(train={"compute_dtype": "float32"})
        net = get_model(cfg.model, image_size=16)
        lr_fn = schedules.make_lr_schedule(cfg.schedule, 8, 1, 100)
        params, _ = net.init(jax.random.PRNGKey(0))
        opt = optim.make_optimizer(cfg.optim, lr_fn, params)
        ts = steps.init_train_state(net, cfg, opt, jax.random.PRNGKey(0))
        step_fn = jax.jit(steps.make_train_step(net, cfg, opt, lr_fn))
        losses = []
        for _ in range(n_steps):
            ts, metrics = step_fn(ts, batch, rng)
            losses.append(float(metrics["loss"]))
        traces[mode] = np.asarray(losses)
        logits, _ = net.apply(ts.params, ts.state, batch["image"], train=False)
        end_preds[mode] = np.asarray(jnp.argmax(logits, -1))
    mode = "paired"
    # short horizon: trajectories are still numerically locked
    np.testing.assert_allclose(traces[mode][:8], traces["unpaired"][:8], rtol=1e-3, atol=1e-4)
    # long horizon: same plateau (mean over the last `tail` steps) ...
    exact_tail = traces["unpaired"][-tail:].mean()
    mode_tail = traces[mode][-tail:].mean()
    assert abs(mode_tail - exact_tail) <= max(0.05, 0.15 * exact_tail), (
        mode, mode_tail, exact_tail)
    # ... and the same learned classification of the train batch
    np.testing.assert_array_equal(end_preds[mode], end_preds["unpaired"], err_msg=mode)
    # and training actually overfit on both paths (4 classes, 8 samples)
    assert all(t[-tail:].mean() < t[0] * 0.5 for t in traces.values())


def test_train_step_overfits_tiny_batch():
    # _tiny_cfg's lr=0.05 is chaotic for TF-RMSProp on this batch-8 toy net
    # (loss oscillates 0.42 -> 0.98 -> 5.6 over 30-60 steps, measured under
    # jax 0.4.37 — the step-30 reading was a coin flip). 0.02 converges
    # monotonically to ~0.25x the first loss; the 0.7 bar keeps real margin.
    cfg = _tiny_cfg(
        schedule={"schedule": "constant", "base_lr": 0.02, "scale_by_batch": False, "warmup_epochs": 0.0}
    )
    net = get_model(cfg.model, image_size=16)
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 8, 1, 100)
    params, _ = net.init(jax.random.PRNGKey(0))
    opt = optim.make_optimizer(cfg.optim, lr_fn, params)
    ts = steps.init_train_state(net, cfg, opt, jax.random.PRNGKey(0))
    step_fn = jax.jit(steps.make_train_step(net, cfg, opt, lr_fn))

    rng = jax.random.PRNGKey(42)
    batch = {
        "image": jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16, 3)),
        "label": jnp.arange(8) % 4,
    }
    first = None
    for i in range(30):
        ts, metrics = step_fn(ts, batch, rng)
        if first is None:
            first = float(metrics["loss"])
    assert int(ts.step) == 30
    assert float(metrics["finite"]) == 1.0
    assert float(metrics["loss"]) < first * 0.7, (first, float(metrics["loss"]))
    # EMA shadow differs from raw params but has same structure
    assert jax.tree.structure(ts.ema_params) == jax.tree.structure(ts.params)
    diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), ts.ema_params, ts.params)
    assert max(jax.tree.leaves(diffs)) > 0


def test_eval_step_counts_and_padding():
    cfg = _tiny_cfg()
    net = get_model(cfg.model, image_size=16)
    eval_fn = jax.jit(steps.make_eval_step(net, cfg))
    params, state = net.init(jax.random.PRNGKey(0))
    batch = {
        "image": jax.random.normal(jax.random.PRNGKey(1), (6, 16, 16, 3)),
        "label": jnp.asarray([0, 1, 2, 3, -1, -1]),  # 2 padded
    }
    m = eval_fn(params, state, batch, {})
    assert float(m["n"]) == 4.0
    assert 0 <= float(m["top1"]) <= float(m["top5"]) <= 4.0
    assert np.isfinite(float(m["loss_sum"]))


def test_batch_mixer_semantics():
    """In-step Mixup/CutMix (beyond reference parity, steps.make_batch_mixer):
    mixup is the exact convex combination, cutmix pastes a box whose ACTUAL
    clipped area defines lam, both deterministic per rng."""
    assert steps.make_batch_mixer(_tiny_cfg()) is None  # both alphas 0

    # mixup: per-batch convex combo preserves the batch mean exactly
    mix = steps.make_batch_mixer(_tiny_cfg(optim={"mixup_alpha": 0.4}))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8, 8, 3))
    y = jnp.arange(16) % 4
    xm, yb, lam = mix(jax.random.PRNGKey(1), x, y)
    xm2, yb2, lam2 = mix(jax.random.PRNGKey(1), x, y)
    np.testing.assert_array_equal(np.asarray(xm), np.asarray(xm2))  # deterministic
    assert float(lam) == float(lam2)
    np.testing.assert_allclose(np.asarray(xm.mean(0)), np.asarray(x.mean(0)), atol=1e-5)
    assert 0.0 <= float(lam) <= 1.0

    # cutmix: images constant at their SAMPLE INDEX (and labels = that
    # index), so pixel provenance is fully recoverable: pasted pixels must
    # carry exactly the value of the sample whose label came back in yb —
    # i.e. images and labels are permuted by the SAME permutation — and
    # lam == 1 - (pasted fraction)
    mix = steps.make_batch_mixer(_tiny_cfg(optim={"cutmix_alpha": 1.0}))
    yc = jnp.arange(16)
    xc = jnp.broadcast_to(jnp.arange(16, dtype=jnp.float32)[:, None, None, None], (16, 8, 8, 3))
    found = False
    for k in range(6):
        xm, yb, lam = mix(jax.random.PRNGKey(k), xc, yc)
        vals = np.asarray(xm[:, :, :, 0])
        yb = np.asarray(yb)
        per_sample = []
        for i in range(16):
            pasted = vals[i][vals[i] != i]
            if pasted.size:
                # every pasted pixel comes from ONE source: the sample whose
                # label is yb[i]
                assert set(np.unique(pasted)) == {float(yb[i])}, (i, np.unique(pasted), yb[i])
                per_sample.append(pasted.size / vals[i].size)
        if per_sample and max(per_sample) < 1.0:
            found = True
            np.testing.assert_allclose(per_sample, per_sample[0])  # same box everywhere
            np.testing.assert_allclose(1.0 - per_sample[0], float(lam), atol=1e-6)
    assert found


@pytest.mark.slow  # ~32 s: two jitted step builds (fast-gate budget, pytest.ini)
def test_train_step_with_mixup_cutmix_runs_and_differs():
    cfg_mix = _tiny_cfg(optim={"mixup_alpha": 0.2, "cutmix_alpha": 1.0, "weight_decay": 1e-5})
    cfg_off = _tiny_cfg()
    net = get_model(cfg_mix.model, image_size=16)
    lr_fn = schedules.make_lr_schedule(cfg_mix.schedule, 8, 1, 100)
    params, _ = net.init(jax.random.PRNGKey(0))
    batch = {
        "image": jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16, 3)),
        "label": jnp.arange(8) % 4,
    }
    rng = jax.random.PRNGKey(42)
    outs = {}
    for name, cfg in [("mix", cfg_mix), ("off", cfg_off)]:
        opt = optim.make_optimizer(cfg.optim, lr_fn, params)
        ts = steps.init_train_state(net, cfg, opt, jax.random.PRNGKey(0))
        step_fn = jax.jit(steps.make_train_step(net, cfg, opt, lr_fn))
        for _ in range(3):
            ts, metrics = step_fn(ts, batch, rng)
        assert float(metrics["finite"]) == 1.0
        outs[name] = jax.tree.leaves(ts.params)[0]
    # the mixed program actually trains on different inputs/targets
    assert float(jnp.abs(outs["mix"] - outs["off"]).max()) > 0
