"""AtomNAS machinery tests (SURVEY.md §4.1: penalty value on a toy net,
mask-prune -> rematerialize equivalence; §3.2 shrink semantics)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from yet_another_mobilenet_series_tpu.config import ModelConfig, PruneConfig
from yet_another_mobilenet_series_tpu.models import get_model
from yet_another_mobilenet_series_tpu.models.serialize import network_from_dict, network_to_dict
from yet_another_mobilenet_series_tpu.nas import masking, penalty, rematerialize
from yet_another_mobilenet_series_tpu.utils.profiling import masked_macs, profile_network


def _supernet(num_classes=4, image_size=32):
    cfg = ModelConfig(
        arch="atomnas_supernet",
        num_classes=num_classes,
        dropout=0.0,
        block_specs=(
            {"t": 1, "c": 16, "n": 1, "s": 1, "k": [3, 5, 7]},   # non-prunable (t=1)
            {"t": 6, "c": 16, "n": 2, "s": 2, "k": [3, 5, 7]},   # residual on 2nd
            {"t": 6, "c": 24, "n": 1, "s": 2, "k": [3, 5, 7], "se": 0.25},
        ),
    )
    return get_model(cfg, image_size=image_size)


def test_prunable_blocks_excludes_t1():
    net = _supernet()
    assert masking.prunable_blocks(net) == [1, 2, 3]
    masks = masking.init_masks(net)
    assert set(masks) == {"1", "2", "3"}
    assert masks["1"].shape == (net.blocks[1].expanded_channels,)


def test_penalty_value_hand_computed():
    net = _supernet()
    pcfg = PruneConfig(enable=True, rho=2.0, normalize_cost=False)
    params, _ = net.init(jax.random.PRNGKey(0))
    costs = penalty.atom_cost_table(net, pcfg)
    pen_fn = penalty.make_penalty_fn(net, pcfg)
    masks = masking.init_masks(net)
    # kill half of block 1's atoms: they must leave the penalty
    m1 = np.asarray(masks["1"]).copy()
    m1[::2] = 0.0
    masks["1"] = jnp.asarray(m1)
    expected = 0.0
    for k, cost in costs.items():
        gamma = np.abs(np.asarray(params["blocks"][k]["dw_bn"]["gamma"]))
        m = np.asarray(masks[k])
        expected += float(np.sum(cost * gamma * m))
    got = float(pen_fn(params, masks))
    np.testing.assert_allclose(got, 2.0 * expected, rtol=1e-5)


def test_rho_ramp_and_mult_scale_penalty():
    """ramp schedule: penalty scales linearly with step over rho_ramp_epochs;
    rho_mult multiplies on top (the adaptive controller's handle)."""
    net = _supernet()
    pcfg = PruneConfig(enable=True, rho=2.0, normalize_cost=False, rho_schedule="ramp", rho_ramp_epochs=1.0)
    params, _ = net.init(jax.random.PRNGKey(0))
    masks = masking.init_masks(net)
    pen_fn = penalty.make_penalty_fn(net, pcfg, steps_per_epoch=10)
    base_fn = penalty.make_penalty_fn(net, PruneConfig(enable=True, rho=2.0, normalize_cost=False))
    full = float(base_fn(params, masks))
    assert float(pen_fn(params, masks, step=jnp.asarray(0))) == 0.0
    np.testing.assert_allclose(float(pen_fn(params, masks, step=jnp.asarray(5))), 0.5 * full, rtol=1e-5)
    np.testing.assert_allclose(float(pen_fn(params, masks, step=jnp.asarray(10))), full, rtol=1e-5)
    np.testing.assert_allclose(float(pen_fn(params, masks, step=jnp.asarray(999))), full, rtol=1e-5)
    got = float(pen_fn(params, masks, rho_mult=jnp.asarray(3.0), step=jnp.asarray(10)))
    np.testing.assert_allclose(got, 3.0 * full, rtol=1e-5)
    # without a step the ramp is skipped, mult still applies
    np.testing.assert_allclose(float(pen_fn(params, masks, rho_mult=jnp.asarray(0.5))), 0.5 * full, rtol=1e-5)


def test_rho_schedule_validation():
    net = _supernet()
    with pytest.raises(ValueError, match="rho_schedule"):
        penalty.make_penalty_fn(net, PruneConfig(enable=True, rho_schedule="bogus"))
    with pytest.raises(ValueError, match="steps_per_epoch"):
        penalty.make_penalty_fn(net, PruneConfig(enable=True, rho_schedule="ramp", rho_ramp_epochs=1.0))
    # adaptive without a target would silently never engage — reject up front
    with pytest.raises(ValueError, match="target_flops"):
        penalty.make_penalty_fn(net, PruneConfig(enable=True, rho_schedule="adaptive"), steps_per_epoch=10)


def test_mask_update_thresholds_and_is_monotonic():
    net = _supernet()
    pcfg = PruneConfig(enable=True, gamma_threshold=0.5)
    params, _ = net.init(jax.random.PRNGKey(0))
    e1 = net.blocks[1].expanded_channels
    gamma = np.linspace(0, 1.2, e1).astype(np.float32)
    params["blocks"]["1"]["dw_bn"]["gamma"] = jnp.asarray(gamma)
    masks = masking.init_masks(net)
    update = jax.jit(masking.make_mask_update(net, pcfg))
    new = update(params, masks)
    np.testing.assert_array_equal(np.asarray(new["1"]), (np.abs(gamma) >= 0.5).astype(np.float32))
    # monotonic: resurrecting gamma doesn't resurrect the atom
    params["blocks"]["1"]["dw_bn"]["gamma"] = jnp.ones(e1)
    new2 = update(params, new)
    np.testing.assert_array_equal(np.asarray(new2["1"]), np.asarray(new["1"]))


def _random_masks(net, rng, kill_frac=0.5, kill_all_block=None, kill_branch=None):
    masks = {}
    for i in masking.prunable_blocks(net):
        b = net.blocks[i]
        m = (rng.uniform(size=b.expanded_channels) > kill_frac).astype(np.float32)
        if m.sum() == 0:
            m[0] = 1.0
        if kill_all_block == i:
            m[:] = 0.0
        if kill_branch is not None and kill_branch[0] == i:
            off = int(np.cumsum([0] + list(b.group_channels))[kill_branch[1]])
            m[off : off + b.group_channels[kill_branch[1]]] = 0.0
            if m.sum() == 0:
                m[-1] = 1.0  # keep the block itself alive via the last branch
        masks[str(i)] = jnp.asarray(m)
    return masks


def test_remat_exact_equivalence_with_branch_and_block_drop():
    """Masked supernet forward == rematerialized net forward, including a
    fully-dead residual block (dropped) and a fully-dead kernel branch."""
    net = _supernet()
    params, state = net.init(jax.random.PRNGKey(0))
    # make BN state non-trivial: one train pass
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    _, state = net.apply(params, state, x, train=True)

    rng = np.random.RandomState(0)
    masks = _random_masks(net, rng, kill_all_block=2, kill_branch=(3, 1))

    imasks = {int(k): v for k, v in masks.items()}
    y_masked, _ = net.apply(params, state, x, train=False, masks=imasks)

    new_net, new_params, new_state, new_masks, extras, report = rematerialize.rematerialize(
        net, params, state, masks
    )
    y_remat, _ = new_net.apply(new_params, new_state, x, train=False)
    np.testing.assert_allclose(np.asarray(y_masked), np.asarray(y_remat), rtol=1e-4, atol=1e-5)

    assert report.dropped_blocks == [2]  # residual block fully dead -> gone
    assert len(new_net.blocks) == len(net.blocks) - 1
    assert 5 in report.dropped_branches.get(3, [])  # k=5 branch killed
    # masks reset to all-ones on the new net
    assert all(float(m.min()) == 1.0 for m in new_masks.values())
    # effective macs(masked) == real macs(remat)
    np_masks = {int(k): np.asarray(v) for k, v in masks.items()}
    np.testing.assert_allclose(
        masked_macs(net, np_masks), profile_network(new_net).total_macs, rtol=1e-6
    )


def test_conv_bn_pair_on_the_masked_supernet_and_its_fallback_after_a_shrink(monkeypatch):
    """The supernet's parallel depthwise branches and masks sit after the
    expand BN, so the conv + BN pair (ops/layers.py) leaves loss and every
    gradient leaf of the masked train forward as they were at float32; and a
    block the shrink cut to its input width keeps its expand conv
    (force_expand) but falls back to the plain path by the shape rule."""
    from yet_another_mobilenet_series_tpu.ops import layers

    net = _supernet()
    params, state = net.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
    imasks = {int(k): v for k, v in _random_masks(net, np.random.RandomState(0)).items()}

    def grads():
        def loss(p):
            logits, _ = net.apply(p, state, x, train=True, masks=imasks)
            return jnp.mean(jnp.square(logits - 1.0))

        return jax.jit(jax.value_and_grad(loss))(params)

    assert net.conv_bn_pair_sites()[0] == 4  # blocks 1-3 and the head
    loss_pair, g_pair = grads()
    monkeypatch.setattr(layers, "conv_bn_pairs", lambda *a, **kw: False)
    loss_plain, g_plain = grads()
    assert float(loss_pair) == float(loss_plain)
    floor = 1e-6 * max(float(np.abs(np.asarray(g)).max()) for g in jax.tree.leaves(g_plain))
    for got, want in zip(jax.tree.leaves(g_pair), jax.tree.leaves(g_plain)):
        assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-4 * np.abs(np.asarray(want)).max() + floor
    monkeypatch.undo()

    import dataclasses

    blk = net.blocks[1]
    shrunk = dataclasses.replace(blk, expanded_channels=blk.in_channels, group_channels=(blk.in_channels,),
                                 kernel_sizes=(3,), force_expand=True)
    assert shrunk.has_expand
    assert blk.conv_bn_pair_sites() == (1, 2)
    assert shrunk.conv_bn_pair_sites() == (0, 2)


@pytest.mark.slow
def test_remat_slices_optimizer_and_ema_state():
    from yet_another_mobilenet_series_tpu.config import config_from_dict
    from yet_another_mobilenet_series_tpu.train import optim, schedules, steps

    net = _supernet()
    cfg = config_from_dict({
        "model": {"num_classes": 4},
        "optim": {"optimizer": "rmsprop"},
        "schedule": {"schedule": "constant", "base_lr": 0.01, "scale_by_batch": False, "warmup_epochs": 0.0},
        "ema": {"enable": True, "decay": 0.9, "warmup": False},
        "train": {"compute_dtype": "float32"},
        "prune": {"enable": True},
    })
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 8, 1, 10)
    params, _ = net.init(jax.random.PRNGKey(0))
    opt = optim.make_optimizer(cfg.optim, lr_fn, params)
    ts = steps.init_train_state(net, cfg, opt, jax.random.PRNGKey(0))
    ts = ts.replace(masks=masking.init_masks(net))
    step_fn = jax.jit(steps.make_train_step(net, cfg, opt, lr_fn, penalty_fn=penalty.make_penalty_fn(net, cfg.prune)))
    batch = {"image": jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3)), "label": jnp.arange(4) % 4}
    ts, _ = step_fn(ts, batch, jax.random.PRNGKey(2))

    masks = _random_masks(net, np.random.RandomState(1))
    new_net, new_params, new_state, new_masks, extras, _ = rematerialize.rematerialize(
        net, ts.params, ts.state, masks,
        opt_state=ts.opt_state, ema_params=ts.ema_params, ema_state=ts.ema_state,
    )
    # sliced optimizer state must initialize a further step without error
    new_opt = optim.make_optimizer(cfg.optim, lr_fn, new_params)
    ts2 = steps.TrainState(
        step=ts.step, params=new_params, state=new_state,
        opt_state=extras["opt_state"], ema_params=extras["ema_params"],
        ema_state=extras["ema_state"], masks=new_masks,
    )
    step2 = jax.jit(steps.make_train_step(new_net, cfg, new_opt, lr_fn, penalty_fn=penalty.make_penalty_fn(new_net, cfg.prune)))
    ts3, metrics = step2(ts2, batch, jax.random.PRNGKey(3))
    assert float(metrics["finite"]) == 1.0
    assert int(ts3.step) == 2
    # shapes really shrank
    assert profile_network(new_net).total_params < profile_network(net).total_params


def test_serialize_roundtrip_exact():
    net = _supernet()
    params, state = net.init(jax.random.PRNGKey(0))
    masks = _random_masks(net, np.random.RandomState(2))
    new_net, new_params, new_state, *_ = rematerialize.rematerialize(net, params, state, masks)
    d = network_to_dict(new_net)
    import json

    net2 = network_from_dict(json.loads(json.dumps(d)))
    assert net2 == new_net
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32, 3))
    y1, _ = new_net.apply(new_params, new_state, x, train=False)
    y2, _ = net2.apply(new_params, new_state, x, train=False)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


def test_mask_summary_reports_effective_macs():
    net = _supernet()
    masks = masking.init_masks(net)
    s = masking.mask_summary(net, masks)
    assert s["alive_atoms"] == s["total_atoms"]
    np.testing.assert_allclose(s["effective_macs"], profile_network(net).total_macs)
    dead = {k: jnp.zeros_like(v) for k, v in masks.items()}
    s2 = masking.mask_summary(net, dead)
    assert s2["alive_atoms"] == 0
    assert s2["effective_macs"] < s["effective_macs"]


def test_prune_event_matches_legacy_host_semantics():
    """make_prune_event == the round-4 host-side block: reached-target gate,
    adaptive-rho feedback direction/clamp, conditional monotone mask update,
    and the (step % interval)&(step <= stop) cadence — including the no-op
    at off-cadence steps."""
    net = _supernet()
    pcfg = PruneConfig(enable=True, rho=0.1, mask_interval=2, gamma_threshold=0.1,
                       target_flops=1.0, rho_schedule="adaptive", rho_adapt_rate=0.05)
    params, _ = net.init(jax.random.PRNGKey(0))
    masks = masking.init_masks(net)
    # push two of block 1's gammas below threshold so the event has deaths
    g = np.asarray(params["blocks"]["1"]["dw_bn"]["gamma"]).copy()
    g[:2] = 0.01
    params["blocks"]["1"]["dw_bn"]["gamma"] = jnp.asarray(g)
    event = jax.jit(masking.make_prune_event(net, pcfg, stop_step=100))
    rho = jnp.ones((), jnp.float32)

    # off-cadence step: everything unchanged
    m1, r1 = event(params, masks, rho, jnp.asarray(1))
    np.testing.assert_array_equal(np.asarray(m1["1"]), np.asarray(masks["1"]))
    assert float(r1) == 1.0
    # on-cadence: deaths applied, rho pushed up (target unreachable)
    m2, r2 = event(params, masks, rho, jnp.asarray(2))
    assert float(jnp.sum(m2["1"])) == float(jnp.sum(masks["1"])) - 2
    np.testing.assert_allclose(float(r2), 1.05, rtol=1e-6)
    # past stop_step: frozen
    m3, r3 = event(params, masks, rho, jnp.asarray(102))
    np.testing.assert_array_equal(np.asarray(m3["1"]), np.asarray(masks["1"]))
    assert float(r3) == 1.0
    # reached target (huge target_flops): rho anneals, masks frozen
    pcfg_hit = PruneConfig(enable=True, rho=0.1, mask_interval=2, gamma_threshold=0.1,
                           target_flops=1e18, rho_schedule="adaptive", rho_adapt_rate=0.05)
    event_hit = jax.jit(masking.make_prune_event(net, pcfg_hit, stop_step=100))
    m4, r4 = event_hit(params, masks, rho, jnp.asarray(2))
    np.testing.assert_array_equal(np.asarray(m4["1"]), np.asarray(masks["1"]))
    np.testing.assert_allclose(float(r4), 0.95, rtol=1e-6)
