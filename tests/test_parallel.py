"""Multi-chip DP correctness on 8 fake CPU devices (SURVEY.md §4.2): the
fake-backend tests covering acceptance configs #3-#5 logic without a pod."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from yet_another_mobilenet_series_tpu.config import config_from_dict
from yet_another_mobilenet_series_tpu.models import get_model
from yet_another_mobilenet_series_tpu.parallel import dp, mesh as mesh_lib
from yet_another_mobilenet_series_tpu.train import optim, schedules, steps


def _cfg():
    return config_from_dict({
        "model": {
            "arch": "mnasnet_a1",  # exercises SE + sepconv stem
            "num_classes": 8,
            "dropout": 0.0,
            "block_specs": [
                {"block": "ds", "c": 8, "n": 1, "s": 1, "k": 3},
                {"t": 3, "c": 16, "n": 1, "s": 2, "k": 5, "se": 0.25},
            ],
        },
        "optim": {"optimizer": "rmsprop", "weight_decay": 1e-5},
        "schedule": {"schedule": "constant", "base_lr": 0.02, "scale_by_batch": False, "warmup_epochs": 0.0},
        "ema": {"enable": True, "decay": 0.99, "warmup": False},
        "train": {"compute_dtype": "float32"},
        "dist": {"sync_bn": True},
    })


# function scope: dp steps donate their inputs, and on the fake-CPU-device
# platform replication can alias the source buffers — a donated ts must not
# be shared across tests.
@pytest.fixture()
def setup():
    cfg = _cfg()
    net = get_model(cfg.model, image_size=16)
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 16, 1, 100)
    params, _ = net.init(jax.random.PRNGKey(0))
    opt = optim.make_optimizer(cfg.optim, lr_fn, params)
    ts = steps.init_train_state(net, cfg, opt, jax.random.PRNGKey(0))
    batch = {
        "image": jax.random.normal(jax.random.PRNGKey(1), (16, 16, 16, 3)),
        "label": jnp.arange(16) % 8,
    }
    return cfg, net, lr_fn, opt, ts, batch


@pytest.mark.parametrize("sync_bn", [True, False], ids=["syncbn", "per_replica_bn"])
def test_dp_step_is_the_same_update_with_and_without_the_conv_bn_pair(setup, monkeypatch, sync_bn):
    """The conv + BN pair (ops/layers.py) must not change the training math:
    one 8-device DP step with it, as the step is built, and one with every
    site on plain autodiff produce the same updated params (within fp
    re-association) and the same grad_norm — the steps.py pmean seam that a
    psum'd custom backward would break with device_count× BN affine grads.
    With dist.sync_bn off the pair runs without an axis name inside the same
    shard_map: its sums are the replica's own."""
    import dataclasses as dc

    from yet_another_mobilenet_series_tpu.ops import layers

    cfg, net, lr_fn, opt, _, batch = setup
    cfg = dc.replace(cfg, dist=dc.replace(cfg.dist, sync_bn=sync_bn))
    assert net.conv_bn_pair_sites()[0] > 0
    m = mesh_lib.make_mesh(8)
    b = mesh_lib.shard_batch(batch, m)
    results = {}
    for name in ("paired", "unpaired"):
        if name == "unpaired":  # the site decides where the step is traced, which is its first call
            monkeypatch.setattr(layers, "conv_bn_pairs", lambda *a, **kw: False)
        ts = mesh_lib.replicate(steps.init_train_state(net, cfg, opt, jax.random.PRNGKey(0)), m)
        step = dp.make_dp_train_step(net, cfg, opt, lr_fn, m)
        ts, met = step(ts, b, jax.random.PRNGKey(7))
        results[name] = (jax.device_get(ts.params), float(met["grad_norm"]), float(met["loss"]))
    (p_ref, gn_ref, loss_ref), (p, gn, loss) = results["unpaired"], results["paired"]
    np.testing.assert_allclose(loss, loss_ref, rtol=1e-5)
    np.testing.assert_allclose(gn, gn_ref, rtol=1e-4)
    # post-RMSProp params: rsqrt(nu) amplifies reduction-order rounding
    # where grads are tiny, so the param bound is looser than the
    # grad-level contract test's (test_ops.py, rtol=1e-4 per device)
    for a, c in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-3, atol=1e-5)


def test_dp_step_equals_single_device_large_batch(setup):
    """psum grad allreduce + SyncBN == single-device full-batch step
    (SURVEY.md §4.2) — THE data-parallel correctness contract."""
    cfg, net, lr_fn, opt, ts, batch = setup
    m = mesh_lib.make_mesh(8)

    single = jax.jit(steps.make_train_step(net, cfg, opt, lr_fn))
    ts_s, met_s = single(ts, batch, jax.random.PRNGKey(7))

    dp_step = dp.make_dp_train_step(net, cfg, opt, lr_fn, m)
    ts_d, met_d = dp_step(mesh_lib.replicate(ts, m), mesh_lib.shard_batch(batch, m), jax.random.PRNGKey(7))

    # params identical up to f32 reduction-order noise (~1e-5 after the
    # RMSProp rsqrt; a missing psum or per-shard BN would show ~1e-2+)
    for pa, pb in zip(jax.tree.leaves(ts_s.params), jax.tree.leaves(ts_d.params)):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb), rtol=1e-3, atol=3e-5)
    # BN running stats identical (SyncBN == full-batch BN)
    for sa, sb in zip(jax.tree.leaves(ts_s.state), jax.tree.leaves(ts_d.state)):
        np.testing.assert_allclose(np.asarray(sa), np.asarray(sb), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(float(met_s["loss"]), float(met_d["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(met_s["top1"]), float(met_d["top1"]), rtol=1e-6)


def test_dp_determinism(setup):
    cfg, net, lr_fn, opt, ts, batch = setup
    m = mesh_lib.make_mesh(8)
    dp_step = dp.make_dp_train_step(net, cfg, opt, lr_fn, m)
    ts_d = mesh_lib.replicate(ts, m)
    b = mesh_lib.shard_batch(batch, m)
    # independent copies: the step donates its input state
    r1 = dp_step(jax.tree.map(jnp.copy, ts_d), b, jax.random.PRNGKey(3))
    r2 = dp_step(jax.tree.map(jnp.copy, ts_d), b, jax.random.PRNGKey(3))
    for a, b in zip(jax.tree.leaves(r1[0].params), jax.tree.leaves(r2[0].params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dp_multi_step_replicas_stay_in_sync(setup):
    cfg, net, lr_fn, opt, ts, batch = setup
    m = mesh_lib.make_mesh(8)
    dp_step = dp.make_dp_train_step(net, cfg, opt, lr_fn, m)
    check = dp.make_replica_sync_check(m)
    ts_d = mesh_lib.replicate(ts, m)
    b = mesh_lib.shard_batch(batch, m)
    for i in range(3):
        ts_d, met = dp_step(ts_d, b, jax.random.PRNGKey(11))
    assert float(check(ts_d.params)) == 0.0
    assert float(check(ts_d.state)) == 0.0
    assert float(met["finite"]) == 1.0
    assert int(ts_d.step) == 3


def test_dp_eval_counts_match_single(setup):
    cfg, net, lr_fn, opt, ts, batch = setup
    m = mesh_lib.make_mesh(8)
    params, state = ts.params, ts.state
    single_eval = jax.jit(steps.make_eval_step(net, cfg))
    dp_eval = dp.make_dp_eval_step(net, cfg, m)
    ms = single_eval(params, state, batch, {})
    md = dp_eval(mesh_lib.replicate(params, m), mesh_lib.replicate(state, m), mesh_lib.shard_batch(batch, m), {})
    for k in ms:
        np.testing.assert_allclose(float(ms[k]), float(md[k]), rtol=1e-5, err_msg=k)


@pytest.mark.slow
def test_sync_bn_off_gives_per_replica_stats(setup):
    """dist.sync_bn=false must actually disable the BN psum: running stats
    then differ from the full-batch (SyncBN) result while grads stay
    allreduced (params remain replica-identical)."""
    import dataclasses as dc

    cfg, net, lr_fn, opt, ts, batch = setup
    m = mesh_lib.make_mesh(8)
    b = mesh_lib.shard_batch(batch, m)

    cfg_off = dc.replace(cfg, dist=dc.replace(cfg.dist, sync_bn=False))
    step_on = dp.make_dp_train_step(net, cfg, opt, lr_fn, m)
    step_off = dp.make_dp_train_step(net, cfg_off, opt, lr_fn, m)
    ts_on, _ = step_on(mesh_lib.replicate(jax.tree.map(jnp.copy, ts), m), b, jax.random.PRNGKey(5))
    ts_off, _ = step_off(mesh_lib.replicate(jax.tree.map(jnp.copy, ts), m), b, jax.random.PRNGKey(5))

    # BN running stats must differ (per-replica vs global moments)...
    diffs = [
        float(jnp.abs(a - c).max())
        for a, c in zip(jax.tree.leaves(ts_on.state), jax.tree.leaves(ts_off.state))
    ]
    assert max(diffs) > 1e-6, diffs
    # ...but replicas stay in sync either way: grads are pmean'd and the
    # running stats are explicitly broadcast from device 0 (DDP rank-0
    # buffer semantics), so BOTH params and state remain replica-identical.
    check = dp.make_replica_sync_check(m)
    assert float(check(ts_off.params)) == 0.0
    assert float(check(ts_off.state)) == 0.0


def test_mesh_validation():
    with pytest.raises(ValueError):
        mesh_lib.make_mesh(999)
    m = mesh_lib.make_mesh(8)
    with pytest.raises(ValueError):
        mesh_lib.local_batch_slice(17, m)  # not divisible by 8 devices
    assert mesh_lib.local_batch_slice(64, m) == 64  # single host
    assert mesh_lib.is_coordinator()


def test_check_vma_contract():
    """Every production shard_map must pass check_vma=False explicitly
    (ADVICE r3 #2): the conv + BN pair's backward returns LOCAL partial
    dgamma/dbeta/dW by contract (ops/layers.py _bn_grad_sums), which is only
    the gradient autodiff produces under check_vma=False maps. Anyone flipping
    a site to check_vma=True (or dropping the kwarg, inheriting a future
    default) must revisit that VJP — this test makes the coupling fail
    loudly instead of silently rescaling BN affine grads."""
    import ast
    import inspect

    from yet_another_mobilenet_series_tpu.parallel import zero

    for module in (dp, zero):
        tree = ast.parse(inspect.getsource(module))
        sites = [
            node for node in ast.walk(tree)
            if isinstance(node, ast.Call)
            and (getattr(node.func, "id", None) == "shard_map"
                 or getattr(node.func, "attr", None) == "shard_map")
        ]
        assert sites, f"{module.__name__}: no shard_map call sites found"
        for call in sites:
            kw = {k.arg: k.value for k in call.keywords}
            assert "check_vma" in kw, (
                f"{module.__name__}:{call.lineno}: shard_map without an explicit "
                "check_vma kwarg (the conv + BN pair's grad contract requires False)")
            assert isinstance(kw["check_vma"], ast.Constant) and kw["check_vma"].value is False, (
                f"{module.__name__}:{call.lineno}: check_vma is not the literal False — "
                "revisit ops/layers.py _conv_bn_pair_bwd before changing this")
