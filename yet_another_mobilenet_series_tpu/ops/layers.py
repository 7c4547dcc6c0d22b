"""Pure-functional NN primitives: conv / batchnorm / dense / pooling.

Design (SURVEY.md §7 "design stance"): layers are *static specs* — frozen
dataclasses holding only hashable configuration — with ``init(key)`` returning
parameter/state pytrees (plain nested dicts) and ``apply(params, state, x, ...)``
as a pure function. No module objects, no global state; specs are safe to
close over in ``jit``/``shard_map``.

Conventions:
- NHWC activations, HWIO conv kernels (XLA/TPU-native layouts; channels last
  keeps the lane dimension dense on the VPU/MXU).
- Explicit symmetric padding k//2 matches the reference lineage's
  ``torch.nn.Conv2d(padding=k//2)`` (NOT TF 'SAME', which pads asymmetrically
  at stride 2 — a known top-1 parity hazard, SURVEY.md §7 hard part 2).
- Params are float32; matmul/conv compute may run in bfloat16 via
  ``compute_dtype`` while BN statistics stay float32.
- SyncBN: pass ``axis_name`` during training to psum batch moments across the
  data mesh axis — the apex SyncBatchNorm replacement (SURVEY.md §2 #12).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.scopes import scope

Array = jax.Array


# ---------------------------------------------------------------------------
# Initializers (torch-default-compatible: kaiming fan_out for convs, SURVEY.md §7)
# ---------------------------------------------------------------------------


def kaiming_normal_fan_out(key, shape, dtype=jnp.float32):
    """He-normal with fan_out = kh*kw*out_ch (torch's init for conv weights).

    For grouped/depthwise kernels (HWIO with I = in/groups) fan_out is still
    kh*kw*O per torch semantics.
    """
    kh, kw, _, o = shape
    fan_out = kh * kw * o
    std = math.sqrt(2.0 / fan_out)
    return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)


def normal_init(std):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)

    return init


# ---------------------------------------------------------------------------
# Conv2D
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Conv2D:
    """2-D convolution spec. groups=in_channels gives a depthwise conv, which
    XLA lowers via ``feature_group_count`` (the cuDNN-depthwise replacement,
    SURVEY.md §2 native table)."""

    in_channels: int
    out_channels: int
    kernel_size: int = 1
    stride: int = 1
    groups: int = 1
    use_bias: bool = False

    def __post_init__(self):
        if self.in_channels % self.groups or self.out_channels % self.groups:
            raise ValueError(f"channels ({self.in_channels}->{self.out_channels}) not divisible by groups={self.groups}")

    @property
    def scope_name(self) -> str:
        """Which obs/scopes.py scope this conv's work is timed under: the
        depthwise convs run on the VPU, the 1x1s are MXU matmuls, everything
        else (the stem, a dense or grouped k x k) is `conv_full`."""
        if self.groups > 1 and self.groups == self.in_channels:
            return "conv_dw"
        if self.kernel_size == 1 and self.groups == 1:
            return "conv_pw"
        return "conv_full"

    def init(self, key) -> dict:
        k = self.kernel_size
        shape = (k, k, self.in_channels // self.groups, self.out_channels)
        params = {"w": kaiming_normal_fan_out(key, shape)}
        if self.use_bias:
            params["b"] = jnp.zeros((self.out_channels,), jnp.float32)
        return params

    def apply(self, params: dict, x: Array, *, compute_dtype=jnp.float32) -> Array:
        with scope(self.scope_name):
            w = params["w"].astype(compute_dtype)
            x = x.astype(compute_dtype)
            pad = self.kernel_size // 2
            y = lax.conv_general_dilated(
                x,
                w,
                window_strides=(self.stride, self.stride),
                padding=((pad, pad), (pad, pad)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=self.groups,
            )
            if self.use_bias:
                y = y + params["b"].astype(compute_dtype)
            return y


# ---------------------------------------------------------------------------
# BatchNorm (with cross-replica sync)
# ---------------------------------------------------------------------------


def _bn_moments(x, axis_name):
    """Global (psum'd) f32 moments of x over N,H,W: (mean, var_biased, n).
    f32 accumulators reduce the input dtype directly — bit-equal to casting
    first, with no materialized f32 copy of the activation."""
    s1 = jnp.sum(x, axis=(0, 1, 2), dtype=jnp.float32)
    s2 = jnp.sum(jnp.square(x.astype(jnp.float32)), axis=(0, 1, 2))
    n = jnp.asarray(x.shape[0] * x.shape[1] * x.shape[2], jnp.float32)
    if axis_name is not None:
        with scope("syncbn"):
            s1 = lax.psum(s1, axis_name)
            s2 = lax.psum(s2, axis_name)
            n = lax.psum(n, axis_name)
    mean = s1 / n
    var = jnp.maximum(s2 / n - jnp.square(mean), 0.0)  # biased
    return mean, var, n


def _bn_normalize(x, mean, var, gamma, beta, eps):
    """(f32(x) - mean) * (gamma * rsqrt(var + eps)) + beta, in x's dtype."""
    scale = lax.rsqrt(var + eps) * gamma
    y = (x.astype(jnp.float32) - mean) * scale + beta
    return y.astype(x.dtype)


def _refuse_stat_cotangents(dmean_ct, dvar_ct):
    """The pair's closed-form backward discards the cotangents of its
    mean/var outputs by contract: they feed only the running-stat state,
    which the training loss never differentiates (train/steps.py returns
    new_state as aux). Anything but a symbolic zero there is refused where
    the step is traced, so a future loss term reading the batch statistics
    fails loudly instead of training with zero stat-gradients. Returns the
    SymbolicZero type for the caller's own check of dy."""
    zero = jax.custom_derivatives.SymbolicZero
    if not (isinstance(dmean_ct, zero) and isinstance(dvar_ct, zero)):
        raise TypeError(
            "the conv + BatchNorm pair received non-zero cotangents for the batch "
            "mean/var outputs; its closed-form backward discards them by "
            "contract. A loss term differentiating the batch statistics "
            "(e.g. a stat regularizer) must differentiate a plain BatchNorm "
            "(a site conv_bn_pairs() declines), or extend the closed form."
        )
    return zero


def _bn_grad_sums(x, dy, mean, inv, axis_name):
    """The two reductions of the closed-form BN backward, one pass over
    (x, dy): (dβ, dγ, psum dβ, psum dγ), with x̂ = (x − mean)·inv:

        dβ = Σ_local dy;  dγ = Σ_local dy·x̂;
        dx = γ·inv · (dy − psum(dβ)/n − x̂·psum(dγ)/n)    with n GLOBAL

    The asymmetry is the per-device gradient contract autodiff of a plain
    BatchNorm produces under the production shard_maps (parallel/dp.py,
    check_vma=False), pinned by tests/test_ops.py's sharded-contract test:

    - γ/β are REPLICATED params: each device returns its local partial sum
      and the training step's grad pmean (train/steps.py), or the ZeRO
      psum_scatter, combines them. A psum here would double-count.
    - x is SHARDED: each shard's cotangent must be complete immediately, and
      x affects every device's outputs through the psum'd moments, so the
      correction terms need the GLOBAL sums (the transpose of the forward
      psum).

    The var zero-clamp in _bn_moments is treated as inactive (it only engages
    when catastrophic cancellation makes var numerically negative). The sums
    are `bn_stats`, like the forward sums they are the gradients of."""
    with scope("bn_stats"):
        dyf = dy.astype(jnp.float32)
        x_hat = (x.astype(jnp.float32) - mean) * inv
        dbeta = jnp.sum(dyf, axis=(0, 1, 2))
        dgamma = jnp.sum(dyf * x_hat, axis=(0, 1, 2))
        s1, s2 = dbeta, dgamma
        if axis_name is not None:
            with scope("syncbn"):
                s1 = lax.psum(s1, axis_name)
                s2 = lax.psum(s2, axis_name)
    return dbeta, dgamma, s1, s2


# ---------------------------------------------------------------------------
# 1x1 conv + train-mode BatchNorm, differentiated as one pair
# ---------------------------------------------------------------------------

def is_conv1x1_bn_site(conv: Conv2D) -> bool:
    """A 1x1, stride-1, ungrouped, bias-free conv: with the BatchNorm that
    directly follows it, a site the pair below could lower."""
    return conv.kernel_size == 1 and conv.stride == 1 and conv.groups == 1 and not conv.use_bias


def conv_bn_pairs(conv: Conv2D, *, train: bool) -> bool:
    """Whether conv_bn() lowers this conv and its BatchNorm through the pair:
    decided from what the site is, never by an option. The output has to be
    WIDER than the input: the backward trades two passes over the conv's
    output for passes over its input, each `in/out` of a wide one, so at
    ratio 1 (a pruned supernet block shrunk to its input width) there is
    nothing to win."""
    return train and is_conv1x1_bn_site(conv) and conv.out_channels > conv.in_channels


def _conv_bn_pair(conv, eps, axis_name, x, w, gamma, beta):
    y, mean, var, _ = _conv_bn_pair_fwd_impl(conv, eps, axis_name, x, w, gamma, beta)
    return y, mean, var


def _conv_bn_pair_fwd_impl(conv, eps, axis_name, x, w, gamma, beta):
    # the unpaired path's expressions, unchanged: the forward fuses as before
    e = conv.apply({"w": w}, x, compute_dtype=x.dtype)
    with scope("bn_stats"):
        mean, var, n = _bn_moments(e, axis_name)
    with scope("bn_apply"):
        y = _bn_normalize(e, mean, var, gamma, beta, eps)
        inv = lax.rsqrt(var + eps)
    return y, mean, var, (e, inv, n)


def _conv_bn_pair_fwd(conv, eps, axis_name, x, w, gamma, beta):
    # symbolic_zeros=True (see defvjp below) wraps each differentiable
    # primal in a CustomVJPPrimal carrier: unwrap to the actual arrays
    x, w, gamma, beta = x.value, w.value, gamma.value, beta.value
    y, mean, var, (e, inv, n) = _conv_bn_pair_fwd_impl(conv, eps, axis_name, x, w, gamma, beta)
    # e is the buffer the consumer of y (the depthwise conv's backward) keeps
    # alive anyway; everything else is the narrow input or per-channel
    return (y, mean, var), (x, w, e, gamma, mean, inv, n)


def _conv_bn_pair_bwd(conv, eps, axis_name, res, cts):
    """The closed-form BN backward (_bn_grad_sums' contract: dγ/dβ local
    partials, dx complete, n GLOBAL, stat cotangents refused) with
    x̂ = (X W − mean)·inv substituted and the contractions re-associated, so
    that neither conv gradient reads the conv's output E. With D = dy,
    a = γ·inv, b = psum(Σ D)/n, c = psum(Σ D·x̂)/n, X flattened to (M, Cin):

        S = XᵀX,  r = Xᵀ1                                 (narrow input only)
        dW = (XᵀD)·a − r⊗(a·b) − (S W − r⊗mean)·(inv·a·c)
        K  = (W·(inv·a·c)) Wᵀ,   k = W (a·b − mean·inv·a·c)
        dX = D (W·a)ᵀ − X K − k

    S, r and XᵀD are LOCAL sums, so dW is the local partial the step's pmean
    (or ZeRO's psum_scatter) combines. Σ D·x̂ is the one place that still
    needs E; it costs no pass because both sums fuse into whatever produces
    D, which reads E for the activation's derivative (tests/test_tpu_aot.py
    pins that on the compiled block). W is the conv's weight as the forward
    used it (rounded to the compute dtype); the per-channel algebra is f32."""
    del eps  # static; the backward needs only the saved residuals
    x, w, e, gamma, mean, inv, n = res
    dy, dmean_ct, dvar_ct = cts
    zero = _refuse_stat_cotangents(dmean_ct, dvar_ct)
    if isinstance(dy, zero):
        return jnp.zeros_like(x), jnp.zeros_like(w), jnp.zeros_like(gamma), jnp.zeros_like(gamma)
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    dbeta, dgamma, s1, s2 = _bn_grad_sums(e, dy, mean, inv, axis_name)
    with scope("bn_apply"):
        a = gamma * inv
        ab = a * (s1 / n)
        iac = inv * a * (s2 / n)
    with scope(conv.scope_name):
        cin, cout = conv.in_channels, conv.out_channels
        w2 = w.astype(x.dtype).astype(f32).reshape(cin, cout)
        wide = jnp.concatenate([dy, x, jnp.ones(x.shape[:-1] + (1,), x.dtype)], axis=-1)
        sums = jnp.einsum("nhwi,nhwo->io", x, wide, preferred_element_type=f32)
        xtd, s, r = sums[:, :cout], sums[:, cout:cout + cin], sums[:, -1]
        dw = xtd * a - jnp.outer(r, ab) - (jnp.matmul(s, w2, precision=hi) - jnp.outer(r, mean)) * iac
        k_mat = jnp.matmul(w2 * iac, w2.T, precision=hi)
        k_vec = jnp.matmul(w2, ab - mean * iac, precision=hi)
        mat = jnp.concatenate([(w2 * a).T, -k_mat], axis=0).astype(x.dtype)
        dx = jnp.einsum("nhwo,oi->nhwi", wide[..., :cout + cin], mat, preferred_element_type=f32) - k_vec
        dx, dw = dx.astype(x.dtype), dw.reshape(w.shape).astype(w.dtype)
    return dx, dw, dgamma, dbeta


_conv_bn_pair = jax.custom_vjp(_conv_bn_pair, nondiff_argnums=(0, 1, 2))
# symbolic_zeros=True so the backward can DETECT (and reject) a real
# cotangent on the mean/var outputs rather than silently dropping it
_conv_bn_pair.defvjp(_conv_bn_pair_fwd, _conv_bn_pair_bwd, symbolic_zeros=True)


def conv_bn(conv: Conv2D, bn: "BatchNorm", conv_params: dict, bn_params: dict, bn_state: dict, x: Array, *,
            train: bool, axis_name: str | None = None, compute_dtype=jnp.float32) -> tuple[Array, dict]:
    """A conv directly followed by its BatchNorm: (y, new BN state). Where
    conv_bn_pairs() says so, the two are differentiated as one pair whose
    backward works from the gradient and the conv's INPUT alone; values are
    those of the two applied in turn either way."""
    if conv_bn_pairs(conv, train=train):
        y, mean, var = _conv_bn_pair(conv, bn.eps, axis_name, x.astype(compute_dtype),
                                     conv_params["w"], bn_params["gamma"], bn_params["beta"])
        return y, bn.running_after(bn_state, mean, var, y, axis_name)
    y = conv.apply(conv_params, x, compute_dtype=compute_dtype)
    return bn.apply(bn_params, bn_state, y, train=train, axis_name=axis_name)


@dataclass(frozen=True)
class BatchNorm:
    """BatchNorm over N,H,W with torch semantics:

    - normalization uses biased batch variance,
    - running stats update ``running = (1-m)*running + m*batch`` with
      momentum m (torch default 0.1) and *unbiased* batch variance,
    - when ``axis_name`` is given in training, batch moments are allreduced
      with ``lax.psum`` so statistics are exact global mean/var across
      replicas — matching apex SyncBatchNorm's two-pass moments
      (SURVEY.md §7 hard part 3).

    The scale vector ``gamma`` is the AtomNAS prune handle (SURVEY.md §3.2).
    """

    num_features: int
    momentum: float = 0.1
    eps: float = 1e-5

    def init(self, key=None) -> tuple[dict, dict]:
        params = {
            "gamma": jnp.ones((self.num_features,), jnp.float32),
            "beta": jnp.zeros((self.num_features,), jnp.float32),
        }
        state = {
            "mean": jnp.zeros((self.num_features,), jnp.float32),
            "var": jnp.ones((self.num_features,), jnp.float32),
        }
        return params, state

    def apply(self, params: dict, state: dict, x: Array, *, train: bool,
              axis_name: str | None = None) -> tuple[Array, dict]:
        """(f32(x) - mean) * (gamma*rsqrt(var+eps)) + beta under autodiff,
        with the batch moments in training and the running ones otherwise.
        The moments are f32 accumulations over the input dtype (equal to
        casting first, element for element, without an f32 copy of the
        activation). The work lands in one of two scopes (obs/scopes.py): the
        batch moments and the running-stat update are `bn_stats`, the
        normalize is `bn_apply`."""
        if train:
            with scope("bn_stats"):
                mean, var, n = _bn_moments(x, axis_name)
                new_state = self._running(state, mean, var, n)
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        with scope("bn_apply"):
            return _bn_normalize(x, mean, var, params["gamma"], params["beta"], self.eps), new_state

    def _running(self, state: dict, mean, var, n) -> dict:
        m = self.momentum
        unbiased = var * (n / jnp.maximum(n - 1.0, 1.0))
        return {
            "mean": (1.0 - m) * state["mean"] + m * mean,
            "var": (1.0 - m) * state["var"] + m * unbiased,
        }

    def running_after(self, state: dict, mean, var, x: Array, axis_name) -> dict:
        """The running-stat update for the conv + BN pair's forward, which
        hands back the batch moments of ``x`` but not their global count."""
        with scope("bn_stats"):
            # lax.psum of the literal 1 is constant-folded to the axis size
            n = jnp.asarray(x.shape[0] * x.shape[1] * x.shape[2], jnp.float32)
            if axis_name is not None:
                n = n * lax.psum(1, axis_name)
            return self._running(state, mean, var, n)


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Dense:
    in_features: int
    out_features: int
    use_bias: bool = True
    init_std: float = 0.01  # reference lineage: classifier ~ N(0, 0.01)

    def init(self, key) -> dict:
        w = normal_init(self.init_std)(key, (self.in_features, self.out_features))
        params = {"w": w}
        if self.use_bias:
            params["b"] = jnp.zeros((self.out_features,), jnp.float32)
        return params

    def apply(self, params: dict, x: Array, *, compute_dtype=jnp.float32) -> Array:
        with scope("dense"):
            y = x.astype(compute_dtype) @ params["w"].astype(compute_dtype)
            if self.use_bias:
                y = y + params["b"].astype(compute_dtype)
            return y


# ---------------------------------------------------------------------------
# Stateless helpers
# ---------------------------------------------------------------------------


def bn_scale_shift(gamma, beta, mean, var, eps: float = 1e-5):
    """Eval-mode BN collapsed to a per-channel affine: scale = gamma *
    rsqrt(var + eps), shift = beta - mean * scale — the single source of the
    fold used by the Pallas eval kernel (ops/pallas_kernels.fold_bn) and the
    serving weight transform (serve/export.py), so the two can never drift."""
    scale = gamma * lax.rsqrt(var + eps)
    return scale, beta - mean * scale


def global_avg_pool(x: Array, keepdims: bool = False) -> Array:
    """Mean over H,W. Computed in float32 (bf16 accumulation over 49+ terms
    loses precision that measurably hurts SE gates and the head)."""
    with scope("pool"):
        return jnp.mean(x.astype(jnp.float32), axis=(1, 2), keepdims=keepdims).astype(x.dtype)


def dropout(rng, x: Array, rate: float, train: bool) -> Array:
    if not train or rate == 0.0:
        return x
    keep = 1.0 - rate
    with scope("drop"):
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


def make_divisible(v: float, divisor: int = 8, min_value: int | None = None) -> int:
    """Channel rounding used throughout the MobileNet family (reference:
    mobilenet_base.make_divisible). Never rounds down by more than 10%."""
    if min_value is None:
        min_value = divisor
    new_v = max(min_value, int(v + divisor / 2) // divisor * divisor)
    if new_v < 0.9 * v:
        new_v += divisor
    return new_v
