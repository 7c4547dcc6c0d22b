import numpy as np
import pytest

import jax
import jax.numpy as jnp

from yet_another_mobilenet_series_tpu import ops


def test_activation_values():
    x = jnp.array([-4.0, -3.0, -1.0, 0.0, 1.0, 3.0, 10.0])
    np.testing.assert_allclose(ops.relu6(x), np.clip(x, 0, 6))
    # h-swish = x*relu6(x+3)/6 (MobileNetV3 paper exact form)
    np.testing.assert_allclose(ops.hswish(x), x * np.clip(x + 3, 0, 6) / 6, rtol=1e-5)
    np.testing.assert_allclose(ops.hsigmoid(x), np.clip(x + 3, 0, 6) / 6, rtol=1e-5)
    np.testing.assert_allclose(ops.swish(x), x / (1 + np.exp(-x)), rtol=1e-5)
    assert ops.hswish(jnp.array(-3.0)) == 0.0
    assert ops.hswish(jnp.array(10.0)) == 10.0
    with pytest.raises(ValueError):
        ops.get_activation("nope")


def test_make_divisible():
    # Reference semantics: round to nearest multiple of 8, never below 90%.
    assert ops.make_divisible(32) == 32
    assert ops.make_divisible(32 * 0.75) == 24
    assert ops.make_divisible(33) == 32
    assert ops.make_divisible(39) == 40
    assert ops.make_divisible(91) == 88  # 88 >= 0.9*91
    assert ops.make_divisible(8 * 0.35) == 8  # min_value clamp
    assert ops.make_divisible(16, divisor=8, min_value=16) == 16


def _torch_conv(x_nhwc, w_hwio, stride, groups, pad):
    import torch
    import torch.nn.functional as F

    xt = torch.from_numpy(np.asarray(x_nhwc).transpose(0, 3, 1, 2)).double()
    # HWIO -> OIHW
    wt = torch.from_numpy(np.asarray(w_hwio).transpose(3, 2, 0, 1)).double()
    y = F.conv2d(xt, wt, stride=stride, padding=pad, groups=groups)
    return y.numpy().transpose(0, 2, 3, 1)


@pytest.mark.parametrize("cin,cout,k,stride,groups", [
    (8, 16, 3, 1, 1),
    (8, 16, 1, 1, 1),
    (16, 16, 3, 2, 16),   # depthwise stride 2
    (16, 16, 5, 1, 16),   # depthwise k=5
    (12, 24, 7, 2, 1),
])
def test_conv2d_matches_torch(cin, cout, k, stride, groups):
    torch = pytest.importorskip("torch")  # noqa: F841
    key = jax.random.PRNGKey(0)
    spec = ops.Conv2D(cin, cout, k, stride, groups)
    params = spec.init(key)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 13, 13, cin))
    y = spec.apply(params, x)
    y_ref = _torch_conv(x, params["w"], stride, groups, k // 2)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-4, atol=1e-5)


def test_batchnorm_matches_torch_train_and_eval():
    import torch

    c = 6
    spec = ops.BatchNorm(c, momentum=0.1, eps=1e-5)
    params, state = spec.init()
    # random gamma/beta to make the test non-trivial
    params["gamma"] = jnp.asarray(np.random.RandomState(0).uniform(0.5, 1.5, c).astype(np.float32))
    params["beta"] = jnp.asarray(np.random.RandomState(1).uniform(-0.5, 0.5, c).astype(np.float32))
    x = np.random.RandomState(2).normal(size=(4, 5, 5, c)).astype(np.float32)

    bn = torch.nn.BatchNorm2d(c, momentum=0.1, eps=1e-5)
    bn.weight.data = torch.from_numpy(np.asarray(params["gamma"]))
    bn.bias.data = torch.from_numpy(np.asarray(params["beta"]))
    xt = torch.from_numpy(x.transpose(0, 3, 1, 2))

    # train step: normalized output + running-stat update semantics
    y, new_state = spec.apply(params, state, jnp.asarray(x), train=True)
    bn.train()
    yt = bn(xt).detach().numpy().transpose(0, 2, 3, 1)
    np.testing.assert_allclose(np.asarray(y), yt, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new_state["mean"]), bn.running_mean.numpy(), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_state["var"]), bn.running_var.numpy(), rtol=1e-5, atol=1e-6)

    # eval uses running stats
    y_eval, same_state = spec.apply(params, new_state, jnp.asarray(x), train=False)
    bn.eval()
    yt_eval = bn(xt).detach().numpy().transpose(0, 2, 3, 1)
    np.testing.assert_allclose(np.asarray(y_eval), yt_eval, rtol=1e-4, atol=1e-5)
    assert same_state is new_state


def _bn_closed_form_float64(x, dy, gamma, eps, shards):
    """BatchNorm's training backward written out in float64 NumPy, under the
    step's per-device contract (ops/layers.py _bn_grad_sums): moments and n
    over the WHOLE batch, dβ = Σ dy and dγ = Σ dy·x̂ per shard of the batch,
    dx = γ·inv·(dy − Σ_all dy/n − x̂·Σ_all dy·x̂/n)."""
    x, dy, gamma = (np.asarray(v, np.float64) for v in (x, dy, gamma))
    n = x.shape[0] * x.shape[1] * x.shape[2]
    mean = x.mean(axis=(0, 1, 2))
    inv = 1.0 / np.sqrt(x.var(axis=(0, 1, 2)) + eps)
    x_hat = (x - mean) * inv
    s1, s2 = dy.sum(axis=(0, 1, 2)), (dy * x_hat).sum(axis=(0, 1, 2))
    dx = gamma * inv * (dy - s1 / n - x_hat * (s2 / n))
    rows = x.shape[0] // shards
    part = lambda v: np.stack([v[i * rows:(i + 1) * rows].sum(axis=(0, 1, 2)) for i in range(shards)])
    return {"mean": mean, "inv": inv, "dbeta": part(dy), "dgamma": part(dy * x_hat), "s1": s1, "s2": s2, "dx": dx}


@pytest.mark.parametrize("shards", [1, 4], ids=["one_device", "syncbn_4"])
@pytest.mark.parametrize("shape", [(8, 7, 7, 12), (16, 3, 3, 4), (4, 5, 6, 24)], ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_batchnorm_backward_matches_closed_form_in_float64(dtype, shape, shards):
    """Autodiff through BatchNorm's batch moments gives dx, dγ and dβ of the
    closed form, and _bn_grad_sums (the conv + BN pair's half of it) gives its
    four sums: each against the mathematics in float64, not against each
    other. With 4 devices, under parallel/dp.py's shard_map (check_vma=False):
    dγ/dβ are one LOCAL partial a device, dx is complete."""
    from jax.sharding import Mesh, PartitionSpec as P

    from yet_another_mobilenet_series_tpu.ops import layers

    c = shape[-1]
    spec = ops.BatchNorm(c)
    params, state = spec.init()
    rs = np.random.RandomState(3)
    params["gamma"] = jnp.asarray(rs.uniform(0.5, 1.5, c).astype(np.float32))
    params["beta"] = jnp.asarray(rs.uniform(-0.5, 0.5, c).astype(np.float32))
    # x and the cotangent hold values the dtype represents, so the reference sees what the program sees
    x = jnp.asarray(rs.normal(1.0, 2.0, shape).astype(np.float32)).astype(dtype)
    dy = jnp.asarray(rs.normal(0, 1, shape).astype(np.float32)).astype(dtype)
    want = _bn_closed_form_float64(x.astype(jnp.float32), dy.astype(jnp.float32), params["gamma"], spec.eps, shards)
    axis_name = "data" if shards > 1 else None

    def body(p, xx, dd):
        def local_loss(p, xx):
            y, _ = spec.apply(p, state, xx, train=True, axis_name=axis_name)
            return jnp.sum(y.astype(jnp.float32) * dd.astype(jnp.float32))

        g, gx = jax.grad(local_loss, argnums=(0, 1))(p, xx)
        mean, inv = (jnp.asarray(want[k], jnp.float32) for k in ("mean", "inv"))
        dbeta, dgamma, s1, s2 = layers._bn_grad_sums(xx, dd, mean, inv, axis_name)
        # the raw per-device partials, laid out on the data axis
        return jax.tree.map(lambda v: v[None], (g, {"dbeta": dbeta, "dgamma": dgamma, "s1": s1, "s2": s2})), gx

    if shards > 1:
        mesh = Mesh(np.array(jax.devices()[:shards]), ("data",))
        body = jax.shard_map(body, mesh=mesh, in_specs=(P(), P("data"), P("data")),
                             out_specs=(P("data"), P("data")), check_vma=False)
    (g, sums), gx = jax.jit(body)(params, x, dy)

    def close(name, got, ref, tol):
        got, ref = np.asarray(got, np.float64), np.asarray(ref)
        assert got.shape == ref.shape, name
        assert np.abs(got - ref).max() <= tol * np.abs(ref).max(), name

    # f32 sums over at most 392 rows; dx comes back in x's dtype (one bf16 ulp of the largest entry is 2^-7)
    assert gx.dtype == dtype
    close("dx", gx, want["dx"], 1e-5 if dtype == jnp.float32 else 2.0 ** -7)
    for name, got in (("dgamma", g["gamma"]), ("dbeta", g["beta"]), ("dgamma", sums["dgamma"]), ("dbeta", sums["dbeta"])):
        close(name, got, want[name], 1e-5)
    for name in ("s1", "s2"):  # the psum'd pair: every device holds the global sum
        close(name, sums[name], np.broadcast_to(want[name], (shards, c)), 1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_batchnorm_eval_backward_matches_closed_form_in_float64(dtype):
    """In eval the running statistics are constants: y is affine in x, and
    autodiff gives dx = dy·γ·inv, dγ = Σ dy·x̂, dβ = Σ dy (float64 NumPy)."""
    c = 12
    spec = ops.BatchNorm(c)
    rs = np.random.RandomState(4)
    params = {"gamma": jnp.asarray(rs.uniform(0.5, 1.5, c).astype(np.float32)),
              "beta": jnp.asarray(rs.uniform(-0.5, 0.5, c).astype(np.float32))}
    state = {"mean": jnp.asarray(rs.normal(1.0, 0.3, c).astype(np.float32)),
             "var": jnp.asarray(rs.uniform(2.0, 5.0, c).astype(np.float32))}
    x = jnp.asarray(rs.normal(1.0, 2.0, (8, 7, 7, c)).astype(np.float32)).astype(dtype)
    dy = jnp.asarray(rs.normal(0, 1, (8, 7, 7, c)).astype(np.float32)).astype(dtype)

    def loss(p, xx):
        y, same = spec.apply(p, state, xx, train=False)
        assert same is state
        return jnp.sum(y.astype(jnp.float32) * dy.astype(jnp.float32))

    g, gx = jax.grad(loss, argnums=(0, 1))(params, x)
    x64, dy64 = np.asarray(x.astype(jnp.float32), np.float64), np.asarray(dy.astype(jnp.float32), np.float64)
    inv = 1.0 / np.sqrt(np.asarray(state["var"], np.float64) + spec.eps)
    x_hat = (x64 - np.asarray(state["mean"], np.float64)) * inv
    np.testing.assert_allclose(np.asarray(gx, np.float64), dy64 * np.asarray(params["gamma"], np.float64) * inv,
                               rtol=1e-5 if dtype == jnp.float32 else 2.0 ** -7, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g["gamma"]), (dy64 * x_hat).sum(axis=(0, 1, 2)), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(g["beta"]), dy64.sum(axis=(0, 1, 2)), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_syncbn_equals_full_batch_bn(dtype):
    """psum-of-moments SyncBN over 8 shards == BN over the unsharded batch
    (SURVEY.md §4.2) — the apex-SyncBatchNorm parity contract, in float32 and
    in the training dtype (the moments are f32 sums either way; y within one
    bf16 ulp)."""
    from jax.sharding import Mesh, PartitionSpec as P


    c = 4
    spec = ops.BatchNorm(c)
    params, state = spec.init()
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 3, 3, c)).astype(dtype)

    y_ref, st_ref = spec.apply(params, state, x, train=True)

    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))

    def shard_fn(p, s, xx):
        return spec.apply(p, s, xx, train=True, axis_name="data")

    y, st = jax.jit(
        jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(), P(), P("data")),
            out_specs=(P("data"), P()),
            # matches every production shard_map (parallel/dp.py)
            check_vma=False,
        )
    )(params, state, x)
    assert y.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_ref, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(st["mean"]), np.asarray(st_ref["mean"]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(st["var"]), np.asarray(st_ref["var"]), rtol=1e-5, atol=1e-6)


# -- the 1x1 conv + BatchNorm pair (ops/layers.py conv_bn) -------------------


def _conv_bn_case(cin, cexp, dtype, seed=0):
    from yet_another_mobilenet_series_tpu.ops import layers

    rs = np.random.RandomState(seed)
    conv, bn = ops.Conv2D(cin, cexp, 1), ops.BatchNorm(cexp)
    conv_params = conv.init(jax.random.PRNGKey(seed + 1))
    _, bn_state = bn.init()
    bn_params = {"gamma": jnp.asarray(rs.uniform(0.5, 1.5, cexp).astype(np.float32)),
                 "beta": jnp.asarray(rs.uniform(-0.5, 0.5, cexp).astype(np.float32))}
    x = jnp.asarray(rs.normal(0.3, 1.0, (8, 6, 6, cin)).astype(np.float32)).astype(dtype)
    ct = jnp.asarray(rs.normal(0, 1.0, (8, 6, 6, cexp)).astype(np.float32))

    def paired(cp, bp, xx, axis_name=None, ct=ct):
        y, st = layers.conv_bn(conv, bn, cp, bp, bn_state, xx, train=True, axis_name=axis_name,
                               compute_dtype=dtype)
        return jnp.sum(y.astype(jnp.float32) * ct), (y, st)

    def unpaired(cp, bp, xx, axis_name=None, ct=ct):
        e = conv.apply(cp, xx, compute_dtype=dtype)
        y, st = bn.apply(bp, bn_state, e, train=True, axis_name=axis_name)
        return jnp.sum(y.astype(jnp.float32) * ct), (y, st)

    return conv, bn, conv_params, bn_params, x, ct, paired, unpaired


# every (in, out) the pair meets in the two benchmark networks: the expand convs of
# apps/mobilenet_v3_large.yml and apps/efficientnet_b0.yml and their heads (160 -> 960, 320 -> 1280)
BENCHMARK_PAIR_SITES = [(16, 64), (24, 72), (40, 120), (40, 240), (80, 200), (80, 184), (80, 480), (112, 672),
                        (160, 960), (16, 96), (24, 144), (192, 1152), (320, 1280)]


@pytest.mark.parametrize("cin,cexp", BENCHMARK_PAIR_SITES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_conv_bn_pair_matches_autodiff(dtype, cin, cexp):
    """conv_bn() on a widening 1x1 conv in training is the custom-VJP pair
    whose backward never reads the conv's output: forward values and running
    stats bit-equal to Conv2D then BatchNorm; dX, dW, dgamma, dbeta equal to
    jax.vjp of plain Conv2D + BatchNorm, to 1e-5 of the largest entry in float32 and one bf16 ulp of it (2^-7) in
    bfloat16: the float32 reference of the changed arithmetic. At the widths
    the benchmark's networks have, on a small image."""
    from yet_another_mobilenet_series_tpu.ops import layers

    conv, _, conv_params, bn_params, x, _, paired, unpaired = _conv_bn_case(cin, cexp, dtype)
    assert layers.conv_bn_pairs(conv, train=True)
    grad = lambda f: jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(conv_params, bn_params, x)
    (_, (y, st)), (g_conv, g_bn, g_x) = grad(paired)
    (_, (y_same, st_same)), (r_conv, r_bn, r_x) = grad(unpaired)
    assert y.dtype == dtype
    np.testing.assert_array_equal(np.asarray(y, np.float32), np.asarray(y_same, np.float32))
    for k in ("mean", "var"):
        np.testing.assert_array_equal(np.asarray(st[k]), np.asarray(st_same[k]))

    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    for name, got, want in (("dX", g_x, r_x), ("dW", g_conv["w"], r_conv["w"]),
                            ("dgamma", g_bn["gamma"], r_bn["gamma"]), ("dbeta", g_bn["beta"], r_bn["beta"])):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert got.shape == want.shape
        assert np.abs(got - want).max() <= tol * np.abs(want).max(), name


def test_conv_bn_pair_in_bfloat16_is_no_further_from_float32_than_autodiff():
    """The pair's bf16 gradients against the float32 gradients of the same
    function: no worse than what autodiff through the bf16-rounded conv
    output gives (its rounding no longer enters the two products)."""
    cases = {dt: _conv_bn_case(40, 240, dt) for dt in (jnp.float32, jnp.bfloat16)}
    _, _, cp, bp, x32, _, _, ref = cases[jnp.float32]
    _, _, _, _, x16, _, paired, unpaired = cases[jnp.bfloat16]
    x32 = x16.astype(jnp.float32)  # the same input values on both sides
    grad = lambda f, xx: jax.grad(lambda *a: f(*a)[0], argnums=(0, 2))(cp, bp, xx)
    (w32, dx32), (wp, dxp), (wa, dxa) = grad(ref, x32), grad(paired, x16), grad(unpaired, x16)
    err = lambda a, b: float(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)).max()
                             / np.abs(np.asarray(b, np.float32)).max())
    assert err(wp["w"], w32["w"]) <= 1.5 * err(wa["w"], w32["w"]) + 1e-4
    assert err(dxp, dx32) <= 1.5 * err(dxa, dx32) + 1e-4


def test_conv_bn_pair_rejects_stat_cotangents():
    """The pair's closed-form backward DISCARDS the cotangents of its mean/var
    outputs by contract (they feed only the running statistics, which the
    training loss never differentiates). symbolic_zeros lets it see a real
    one: a loss term that reads the batch statistics fails LOUDLY where it is
    traced instead of training with zero stat-gradients, and works on the
    unpaired path, which is plain autodiff."""
    _, _, conv_params, bn_params, x, _, paired, unpaired = _conv_bn_case(8, 24, jnp.float32)
    stat_loss = lambda f: jax.grad(lambda cp: jnp.sum(f(cp, bn_params, x)[1][1]["mean"]))(conv_params)
    with pytest.raises(TypeError, match="conv \\+ BatchNorm pair.*cotangents"):
        stat_loss(paired)
    assert np.all(np.isfinite(np.asarray(stat_loss(unpaired)["w"])))


@pytest.mark.parametrize("conv, kw, expect", [
    (ops.Conv2D(16, 64, 1), {}, True),
    (ops.Conv2D(16, 64, 1), {"train": False}, False),  # eval, export, serving: the plain path
    (ops.Conv2D(64, 64, 1), {}, False),  # a pruned block shrunk to its input width: nothing to win
    (ops.Conv2D(64, 16, 1), {}, False),  # the project conv's shape
    (ops.Conv2D(3, 16, 3, 2), {}, False),  # the stem
    (ops.Conv2D(16, 64, 1, 2), {}, False),
    (ops.Conv2D(16, 64, 1, groups=2), {}, False),
    (ops.Conv2D(16, 64, 1, use_bias=True), {}, False),
])
def test_conv_bn_pair_engages_by_what_the_site_is(conv, kw, expect):
    from yet_another_mobilenet_series_tpu.ops import layers

    kw = {"train": True, **kw}
    assert layers.conv_bn_pairs(conv, **kw) is expect
    # and conv_bn() takes the custom-VJP pair exactly there
    bn = ops.BatchNorm(conv.out_channels)
    (bn_params, bn_state), conv_params = bn.init(), conv.init(jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(lambda x: layers.conv_bn(conv, bn, conv_params, bn_params, bn_state, x, **kw)[0])(
        jnp.ones((2, 8, 8, conv.in_channels)))
    assert ("_conv_bn_pair" in str(jaxpr)) is expect


def test_conv_bn_pair_sharded_grad_contract_matches_syncbn_autodiff():
    """The pair under shard_map on 4 devices (parallel/dp.py's contract,
    check_vma=False as there): per device, dgamma/dbeta/dW are LOCAL partials
    of the LOCAL loss and dX is complete, each equal to autodiff of Conv2D +
    SyncBN: the convention train/steps.py's grad pmean (and ZeRO's
    psum_scatter) assumes. A psum'd dgamma/dbeta inside the custom backward
    would pass a globally-normalised comparison and train the BN affine
    parameters at device_count x the gradient through the real step, so the
    seam is compared device by device. check_vma=False because, under the
    vma semantics, the cotangent of a replicated parameter is psum'd OUTSIDE
    a custom_vjp's view: the pair is contract-correct only where every
    production shard_map of this codebase already is."""
    from jax.sharding import Mesh, PartitionSpec as P

    _, _, conv_params, bn_params, x, ct, paired, unpaired = _conv_bn_case(8, 24, jnp.float32, seed=5)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))

    def per_device_grads(fn):
        def body(cp, bp, xx, cc):
            (g_conv, g_bn, g_x) = jax.grad(lambda *a: fn(*a, "data", cc)[0], argnums=(0, 1, 2))(cp, bp, xx)
            return jax.tree.map(lambda v: v[None], (g_conv, g_bn)), g_x

        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(), P(), P("data"), P("data")),
                                     out_specs=(P("data"), P("data")), check_vma=False))(conv_params, bn_params, x, ct)

    (gp, gxp), (gr, gxr) = per_device_grads(paired), per_device_grads(unpaired)
    assert gp[1]["gamma"].shape == (4, 24) and gp[0]["w"].shape == (4, 1, 1, 8, 24)  # one partial per device
    for got, want in zip(jax.tree.leaves((gp, gxp)), jax.tree.leaves((gr, gxr))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
    # the partials differ between devices: nothing was psum'd on the way out
    assert not np.allclose(np.asarray(gp[1]["gamma"][0]), np.asarray(gp[1]["gamma"][1]))


def test_inverted_residual_shapes_and_residual():
    spec = ops.InvertedResidual(
        in_channels=16, out_channels=16, expanded_channels=48, stride=1,
        kernel_sizes=(3, 5, 7), group_channels=(16, 16, 16), active_fn="hswish", se_channels=12,
    )
    params, state = spec.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 16))
    y, new_state = spec.apply(params, state, x, train=True)
    assert y.shape == (2, 8, 8, 16)
    assert spec.has_residual
    # stride-2 block: no residual, spatial halved
    spec2 = ops.InvertedResidual(16, 24, 96, stride=2, kernel_sizes=(3,))
    p2, s2 = spec2.init(jax.random.PRNGKey(2))
    y2, _ = spec2.apply(p2, s2, x, train=False)
    assert y2.shape == (2, 4, 4, 24)
    assert not spec2.has_residual


def test_inverted_residual_no_expand_when_t1():
    spec = ops.InvertedResidual(16, 16, 16, stride=1, kernel_sizes=(3,))
    params, _ = spec.init(jax.random.PRNGKey(0))
    assert "expand" not in params and not spec.has_expand


def test_inverted_residual_validation():
    with pytest.raises(ValueError):
        ops.InvertedResidual(16, 16, 48, kernel_sizes=(3, 5), group_channels=(16,))
    with pytest.raises(ValueError):
        ops.InvertedResidual(16, 16, 48, kernel_sizes=(3, 5), group_channels=(40, 9))


def test_mask_zeroes_atoms_exact_equivalence():
    """Masked supernet forward == physically shrunk net forward (the central
    AtomNAS-on-XLA claim, SURVEY.md §7 hard part 1). Includes SE to prove the
    zero channels are invisible to the squeeze FCs."""
    full = ops.InvertedResidual(8, 8, 24, stride=1, kernel_sizes=(3, 5), group_channels=(12, 12), se_channels=6)
    params, state = full.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 6, 8))

    # kill channels 3..11 of branch0 and 0..5 of branch1 -> keep (3, 6)
    keep0 = np.arange(0, 3)
    keep1 = np.arange(12 + 6, 24)
    keep = np.concatenate([keep0, keep1])
    mask = np.zeros(24, np.float32)
    mask[keep] = 1.0

    y_masked, _ = full.apply(params, state, x, train=False, mask=jnp.asarray(mask))

    shrunk = ops.InvertedResidual(8, 8, 9, stride=1, kernel_sizes=(3, 5), group_channels=(3, 6), se_channels=6)
    sp = {
        "expand": {"w": params["expand"]["w"][..., keep]},
        "expand_bn": {k: v[keep] for k, v in params["expand_bn"].items()},
        "dw0_k3": {"w": params["dw0_k3"]["w"][..., keep0]},
        "dw1_k5": {"w": params["dw1_k5"]["w"][..., keep1 - 12]},
        "dw_bn": {k: v[keep] for k, v in params["dw_bn"].items()},
        "se": {
            "reduce": {"w": params["se"]["reduce"]["w"][keep, :], "b": params["se"]["reduce"]["b"]},
            "expand": {"w": params["se"]["expand"]["w"][:, keep], "b": params["se"]["expand"]["b"][keep]},
        },
        "project": {"w": params["project"]["w"][..., keep, :]},
        "project_bn": params["project_bn"],
    }
    ss = {
        "expand_bn": {k: v[keep] for k, v in state["expand_bn"].items()},
        "dw_bn": {k: v[keep] for k, v in state["dw_bn"].items()},
        "project_bn": state["project_bn"],
    }
    y_shrunk, _ = shrunk.apply(sp, ss, x, train=False)
    np.testing.assert_allclose(np.asarray(y_masked), np.asarray(y_shrunk), rtol=1e-5, atol=1e-5)
