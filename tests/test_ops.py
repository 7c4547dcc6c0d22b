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


def test_conv1x1_as_dot_matches_conv_lowering():
    """as_dot (the round-3 weight-grad MXU experiment, train.conv1x1_dot)
    must be a pure lowering change: forward values and weight gradients
    match the conv_general_dilated path, including the stride>1 subsample
    case; k>1 and grouped convs ignore the flag entirely."""
    for cin, cout, stride in [(8, 16, 1), (8, 16, 2), (16, 5, 1)]:
        spec = ops.Conv2D(cin, cout, 1, stride)
        params = spec.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 9, cin))

        y_conv = spec.apply(params, x)
        y_dot = spec.apply(params, x, as_dot=True)
        np.testing.assert_allclose(np.asarray(y_dot), np.asarray(y_conv), rtol=1e-5, atol=1e-6)

        def loss(p, as_dot):
            return jnp.sum(jnp.square(spec.apply(p, x, as_dot=as_dot)))

        g_conv = jax.grad(loss)(params, False)["w"]
        g_dot = jax.grad(loss)(params, True)["w"]
        np.testing.assert_allclose(np.asarray(g_dot), np.asarray(g_conv), rtol=1e-4, atol=1e-5)

    # non-1x1 / grouped: flag is a no-op (same lowering, identical values)
    dw = ops.Conv2D(8, 8, 3, 1, groups=8)
    pdw = dw.init(jax.random.PRNGKey(2))
    xdw = jax.random.normal(jax.random.PRNGKey(3), (2, 7, 7, 8))
    np.testing.assert_array_equal(
        np.asarray(dw.apply(pdw, xdw, as_dot=True)), np.asarray(dw.apply(pdw, xdw))
    )


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


def test_batchnorm_modes_equivalent():
    """The bn_mode perf variants (ops/layers.py; the round-2 trace's 52%
    BN-reduction attack) must be semantics-preserving: statistics bit-exact
    in every mode; "folded" normalize within f32 re-association rounding of
    "exact"; "compute" within bf16 tolerance on bf16 inputs."""
    c = 12
    spec = ops.BatchNorm(c)
    params, state = spec.init()
    rs = np.random.RandomState(0)
    params["gamma"] = jnp.asarray(rs.uniform(0.5, 1.5, c).astype(np.float32))
    params["beta"] = jnp.asarray(rs.uniform(-0.5, 0.5, c).astype(np.float32))
    x = jnp.asarray(rs.normal(2.0, 3.0, (8, 7, 7, c)).astype(np.float32))

    for train in (True, False):
        y_exact, st_exact = spec.apply(params, state, x, train=train, mode="exact")
        y_folded, st_folded = spec.apply(params, state, x, train=train, mode="folded")
        y_compute, st_compute = spec.apply(params, state, x, train=train, mode="compute")
        for st in (st_folded, st_compute):
            for k in ("mean", "var"):
                np.testing.assert_array_equal(np.asarray(st[k]), np.asarray(st_exact[k]))
        np.testing.assert_allclose(np.asarray(y_folded), np.asarray(y_exact), rtol=2e-6, atol=2e-6)
        np.testing.assert_allclose(np.asarray(y_compute), np.asarray(y_exact), rtol=2e-2, atol=2e-2)

    # bf16 activations (the real training dtype): folded stays within one
    # bf16 ulp of exact after the output cast; gradients agree too.
    xb = x.astype(jnp.bfloat16)
    yb_exact, _ = spec.apply(params, state, xb, train=True, mode="exact")
    yb_folded, _ = spec.apply(params, state, xb, train=True, mode="folded")
    yb_compute, _ = spec.apply(params, state, xb, train=True, mode="compute")
    np.testing.assert_allclose(
        np.asarray(yb_folded, np.float32), np.asarray(yb_exact, np.float32), rtol=1e-2, atol=1e-2
    )
    np.testing.assert_allclose(
        np.asarray(yb_compute, np.float32), np.asarray(yb_exact, np.float32), rtol=4e-2, atol=4e-2
    )

    def loss(p, mode):
        y, _ = spec.apply(p, state, x, train=True, mode=mode)
        return jnp.sum(jnp.square(y) * jnp.cos(jnp.arange(y.size).reshape(y.shape)))

    g_exact = jax.grad(loss)(params, "exact")
    g_folded = jax.grad(loss)(params, "folded")
    for k in ("gamma", "beta"):
        np.testing.assert_allclose(np.asarray(g_folded[k]), np.asarray(g_exact[k]), rtol=1e-4, atol=1e-4)

    with pytest.raises(ValueError):
        spec.apply(params, state, x, train=True, mode="nope")


def test_batchnorm_fused_vjp_matches_autodiff():
    """mode='fused_vjp': forward values equal 'folded' bit-for-bit, running
    stats equal every other mode's, and the closed-form backward reproduces
    autodiff-through-the-moments gradients for x, gamma, AND beta."""
    c = 12
    spec = ops.BatchNorm(c)
    params, state = spec.init()
    rs = np.random.RandomState(3)
    params["gamma"] = jnp.asarray(rs.uniform(0.5, 1.5, c).astype(np.float32))
    params["beta"] = jnp.asarray(rs.uniform(-0.5, 0.5, c).astype(np.float32))
    x = jnp.asarray(rs.normal(1.0, 2.0, (8, 7, 7, c)).astype(np.float32))

    y_folded, st_folded = spec.apply(params, state, x, train=True, mode="folded")
    y_fused, st_fused = spec.apply(params, state, x, train=True, mode="fused_vjp")
    np.testing.assert_array_equal(np.asarray(y_fused), np.asarray(y_folded))
    for k in ("mean", "var"):
        np.testing.assert_allclose(np.asarray(st_fused[k]), np.asarray(st_folded[k]), rtol=1e-6)

    w = jnp.asarray(rs.normal(0, 1, (8, 7, 7, c)).astype(np.float32))

    def loss(p, xx, mode):
        y, _ = spec.apply(p, state, xx, train=True, mode=mode)
        return jnp.sum(y * w)  # non-trivial cotangent

    (g_exact, gx_exact) = jax.grad(loss, argnums=(0, 1))(params, x, "exact")
    (g_fused, gx_fused) = jax.grad(loss, argnums=(0, 1))(params, x, "fused_vjp")
    np.testing.assert_allclose(np.asarray(gx_fused), np.asarray(gx_exact), rtol=1e-4, atol=1e-5)
    for k in ("gamma", "beta"):
        np.testing.assert_allclose(np.asarray(g_fused[k]), np.asarray(g_exact[k]), rtol=1e-4, atol=1e-5)

    # eval mode falls back to the folded expression (no custom vjp needed)
    y_eval_fused, _ = spec.apply(params, st_fused, x, train=False, mode="fused_vjp")
    y_eval_folded, _ = spec.apply(params, st_folded, x, train=False, mode="folded")
    np.testing.assert_array_equal(np.asarray(y_eval_fused), np.asarray(y_eval_folded))


def test_batchnorm_fused_vjp_rejects_stat_cotangents():
    """ADVICE r3 #1: the closed-form backward DISCARDS the mean/var output
    cotangents by contract (they feed only the never-differentiated running
    stats). With symbolic_zeros enforcement, a loss term that reads the
    batch statistics must fail LOUDLY at trace time under fused_vjp rather
    than silently training with zero stat-gradients."""
    spec = ops.BatchNorm(4)
    params, state = spec.init()
    x = jnp.asarray(np.random.RandomState(0).normal(0, 1, (2, 3, 3, 4)).astype(np.float32))

    def stat_loss(p):
        _, st = spec.apply(p, state, x, train=True, mode="fused_vjp")
        return jnp.sum(st["mean"])  # differentiates the batch statistics

    with pytest.raises(TypeError, match="fused_vjp.*cotangents"):
        jax.grad(stat_loss)(params)

    # the same loss is fine under the autodiff modes
    def stat_loss_folded(p):
        _, st = spec.apply(p, state, x, train=True, mode="folded")
        return jnp.sum(st["mean"])

    g = jax.grad(stat_loss_folded)(params)
    assert all(np.all(np.isfinite(np.asarray(v))) for v in g.values())


def test_batchnorm_fused_vjp_sharded_grad_contract_matches_exact():
    """The per-device gradient CONTRACT under shard_map: fused_vjp's custom
    backward must produce the same per-device partial gradients of the LOCAL
    loss that autodiff of 'exact' produces (local dγ/dβ sums, global n) —
    the convention train/steps.py's grad pmean (and the ZeRO psum_scatter)
    assumes for every mode. A psum'd dγ/dβ inside the custom bwd would pass
    a globally-normalized comparison but train BN affine params at
    device_count× the gradient through the real step (caught by review in
    round 3; this test pins the seam per-device, no normalization games).

    check_vma=False deliberately matches parallel/dp.py's shard_maps: under
    the new vma semantics the cotangent of a replicated param is auto-psum'd
    OUTSIDE a custom_vjp's view, so fused_vjp is only contract-correct in
    check_vma=False contexts — which is what every production shard_map in
    this codebase uses (documented in ops/layers.py)."""
    from jax.sharding import Mesh, PartitionSpec as P


    c = 4
    spec = ops.BatchNorm(c)
    params, state = spec.init()
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 3, 3, c))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 3, 3, c))
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))

    def per_device_grads(mode):
        def local_loss(p, xx, ww):
            y, _ = spec.apply(p, state, xx, train=True, axis_name="data", mode=mode)
            return jnp.sum(y * ww)

        def body(p, xx, ww):
            g, gx = jax.grad(local_loss, argnums=(0, 1))(p, xx, ww)
            # return the RAW per-device partials, laid out on the data axis,
            # so the contract is compared device by device
            return jax.tree.map(lambda v: v[None], g), gx

        return jax.jit(
            jax.shard_map(body, mesh=mesh, in_specs=(P(), P("data"), P("data")),
                      out_specs=(P("data"), P("data")), check_vma=False)
        )(params, x, w)

    g_exact, gx_exact = per_device_grads("exact")
    g_fused, gx_fused = per_device_grads("fused_vjp")
    for k in ("gamma", "beta"):
        assert g_fused[k].shape == (8, c)  # one partial per device
        np.testing.assert_allclose(np.asarray(g_fused[k]), np.asarray(g_exact[k]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gx_fused), np.asarray(gx_exact), rtol=1e-4, atol=1e-5)


def test_batchnorm_sdot_stats_match_reduce():
    """mode='sdot' (MXU-dot batch statistics, the round-4 A/B candidate):
    values, gradients, and running stats must match 'folded' (identical
    normalize expression) within f32 accumulation-order rounding — the one
    mode whose statistics are NOT bit-identical to the reduce-based ones,
    by construction."""
    c = 12
    spec = ops.BatchNorm(c)
    params, state = spec.init()
    rs = np.random.RandomState(7)
    params["gamma"] = jnp.asarray(rs.uniform(0.5, 1.5, c).astype(np.float32))
    params["beta"] = jnp.asarray(rs.uniform(-0.5, 0.5, c).astype(np.float32))
    x = jnp.asarray(rs.normal(1.0, 2.0, (8, 7, 7, c)).astype(np.float32))

    y_ref, st_ref = spec.apply(params, state, x, train=True, mode="folded")
    y_dot, st_dot = spec.apply(params, state, x, train=True, mode="sdot")
    np.testing.assert_allclose(np.asarray(y_dot), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    for k in ("mean", "var"):
        np.testing.assert_allclose(np.asarray(st_dot[k]), np.asarray(st_ref[k]), rtol=1e-5, atol=1e-6)

    w = jnp.asarray(rs.normal(0, 1, (8, 7, 7, c)).astype(np.float32))

    def loss(p, xx, mode):
        y, _ = spec.apply(p, state, xx, train=True, mode=mode)
        return jnp.sum(y * w)

    (g_ref, gx_ref) = jax.grad(loss, argnums=(0, 1))(params, x, "folded")
    (g_dot, gx_dot) = jax.grad(loss, argnums=(0, 1))(params, x, "sdot")
    np.testing.assert_allclose(np.asarray(gx_dot), np.asarray(gx_ref), rtol=1e-4, atol=1e-5)
    for k in ("gamma", "beta"):
        np.testing.assert_allclose(np.asarray(g_dot[k]), np.asarray(g_ref[k]), rtol=1e-4, atol=1e-5)

    # bf16 activations (the real training dtype): the dot's bf16 products
    # are exact in the f32 accumulator, so stats stay at f32-rounding
    # distance even from bf16 inputs
    xb = x.astype(jnp.bfloat16)
    _, st_b16 = spec.apply(params, state, xb, train=True, mode="sdot")
    _, st_ref16 = spec.apply(params, state, xb, train=True, mode="folded")
    for k in ("mean", "var"):
        np.testing.assert_allclose(np.asarray(st_b16[k]), np.asarray(st_ref16[k]), rtol=1e-5, atol=1e-6)

    # eval mode uses running stats: sdot is folded exactly
    y_eval_dot, _ = spec.apply(params, st_dot, x, train=False, mode="sdot")
    y_eval_folded, _ = spec.apply(params, st_dot, x, train=False, mode="folded")
    np.testing.assert_array_equal(np.asarray(y_eval_dot), np.asarray(y_eval_folded))


@pytest.mark.parametrize("mode", ["exact", "folded", "compute", "fused_vjp", "sdot", "compute_sdot"])
def test_syncbn_equals_full_batch_bn(mode):
    """psum-of-moments SyncBN over 8 shards == BN over the unsharded batch
    (SURVEY.md §4.2) — the apex-SyncBatchNorm parity contract, in every
    bn_mode normalize variant."""
    from jax.sharding import Mesh, PartitionSpec as P


    c = 4
    spec = ops.BatchNorm(c)
    params, state = spec.init()
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 3, 3, c))

    y_ref, st_ref = spec.apply(params, state, x, train=True, mode=mode)

    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))

    def shard_fn(p, s, xx):
        return spec.apply(p, s, xx, train=True, axis_name="data", mode=mode)

    y, st = jax.jit(
        jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(), P(), P("data")),
            out_specs=(P("data"), P()),
            # matches every production shard_map (parallel/dp.py): the
            # fused_vjp custom backward has no replication rule, and old-jax
            # check_rep=True rejects it outright (NotImplementedError)
            check_vma=False,
        )
    )(params, state, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
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

    def paired(cp, bp, xx, mode, axis_name=None, ct=ct):
        y, st = layers.conv_bn(conv, bn, cp, bp, bn_state, xx, train=True, axis_name=axis_name,
                               compute_dtype=dtype, bn_mode=mode)
        return jnp.sum(y.astype(jnp.float32) * ct), (y, st)

    def unpaired(cp, bp, xx, mode, axis_name=None, ct=ct):
        e = conv.apply(cp, xx, compute_dtype=dtype)
        y, st = bn.apply(bp, bn_state, e, train=True, axis_name=axis_name, mode=mode)
        return jnp.sum(y.astype(jnp.float32) * ct), (y, st)

    return conv, bn, conv_params, bn_params, x, ct, paired, unpaired


@pytest.mark.parametrize("mode", ["exact", "folded", "fused_vjp"])
@pytest.mark.parametrize("cin,cexp", [(16, 64), (40, 240)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_conv_bn_pair_matches_autodiff(dtype, cin, cexp, mode):
    """conv_bn() on a widening 1x1 conv in training is the custom-VJP pair
    whose backward never reads the conv's output: forward values and running
    stats bit-equal to Conv2D then BatchNorm in the same bn_mode; dX, dW,
    dgamma, dbeta equal to jax.vjp of plain Conv2D + BatchNorm(mode="exact"),
    to 1e-5 of the largest entry in float32 and one bf16 ulp of it (2^-7) in
    bfloat16: the float32 reference of the changed arithmetic."""
    from yet_another_mobilenet_series_tpu.ops import layers

    conv, _, conv_params, bn_params, x, _, paired, unpaired = _conv_bn_case(cin, cexp, dtype)
    assert layers.conv_bn_pairs(conv, train=True, bn_mode=mode)
    grad = lambda f, m: jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(conv_params, bn_params, x, m)
    (_, (y, st)), (g_conv, g_bn, g_x) = grad(paired, mode)
    (_, (y_same, st_same)), _ = grad(unpaired, mode)
    assert y.dtype == dtype
    np.testing.assert_array_equal(np.asarray(y, np.float32), np.asarray(y_same, np.float32))
    for k in ("mean", "var"):
        np.testing.assert_array_equal(np.asarray(st[k]), np.asarray(st_same[k]))

    _, (r_conv, r_bn, r_x) = grad(unpaired, "exact")
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
    grad = lambda f, xx: jax.grad(lambda *a: f(*a, "exact")[0], argnums=(0, 2))(cp, bp, xx)
    (w32, dx32), (wp, dxp), (wa, dxa) = grad(ref, x32), grad(paired, x16), grad(unpaired, x16)
    err = lambda a, b: float(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)).max()
                             / np.abs(np.asarray(b, np.float32)).max())
    assert err(wp["w"], w32["w"]) <= 1.5 * err(wa["w"], w32["w"]) + 1e-4
    assert err(dxp, dx32) <= 1.5 * err(dxa, dx32) + 1e-4


def test_conv_bn_pair_rejects_stat_cotangents():
    """Like fused_vjp: a loss that differentiates the pair's batch statistics
    fails where it is traced, and works on the unpaired path."""
    _, _, conv_params, bn_params, x, _, paired, unpaired = _conv_bn_case(8, 24, jnp.float32)
    stat_loss = lambda f: jax.grad(lambda cp: jnp.sum(f(cp, bn_params, x, "exact")[1][1]["mean"]))(conv_params)
    with pytest.raises(TypeError, match="conv \\+ BatchNorm pair.*cotangents"):
        stat_loss(paired)
    assert np.all(np.isfinite(np.asarray(stat_loss(unpaired)["w"])))


@pytest.mark.parametrize("conv, kw, expect", [
    (ops.Conv2D(16, 64, 1), {}, True),
    (ops.Conv2D(16, 64, 1), {"bn_mode": "folded"}, True),
    (ops.Conv2D(16, 64, 1), {"bn_mode": "fused_vjp"}, True),
    (ops.Conv2D(16, 64, 1), {"train": False}, False),  # eval, export, serving: the plain path
    (ops.Conv2D(16, 64, 1), {"bn_mode": "compute"}, False),
    (ops.Conv2D(16, 64, 1), {"bn_mode": "sdot"}, False),
    (ops.Conv2D(16, 64, 1), {"bn_mode": "compute_sdot"}, False),
    (ops.Conv2D(16, 64, 1), {"conv1x1_dot": True}, False),
    (ops.Conv2D(64, 64, 1), {}, False),  # a pruned block shrunk to its input width: nothing to win
    (ops.Conv2D(64, 16, 1), {}, False),  # the project conv's shape
    (ops.Conv2D(3, 16, 3, 2), {}, False),  # the stem
    (ops.Conv2D(16, 64, 1, 2), {}, False),
    (ops.Conv2D(16, 64, 1, groups=2), {}, False),
    (ops.Conv2D(16, 64, 1, use_bias=True), {}, False),
])
def test_conv_bn_pair_engages_by_what_the_site_is(conv, kw, expect):
    from yet_another_mobilenet_series_tpu.ops import layers

    kw = {"train": True, "bn_mode": "exact", **kw}
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
    and dX is complete, each equal to autodiff of Conv2D + SyncBN 'exact'."""
    from jax.sharding import Mesh, PartitionSpec as P

    _, _, conv_params, bn_params, x, ct, paired, unpaired = _conv_bn_case(8, 24, jnp.float32, seed=5)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))

    def per_device_grads(fn):
        def body(cp, bp, xx, cc):
            (g_conv, g_bn, g_x) = jax.grad(lambda *a: fn(*a, "exact", "data", cc)[0], argnums=(0, 1, 2))(cp, bp, xx)
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
