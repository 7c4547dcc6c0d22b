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
from jax.ad_checkpoint import checkpoint_name

from ..obs.scopes import scope

Array = jax.Array

# the BatchNorm.apply normalize variants (single source of truth — the step
# builders and the A/B bench validate against this same tuple)
BN_MODES = ("exact", "folded", "compute", "fused_vjp", "sdot", "compute_sdot")


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

    def apply(self, params: dict, x: Array, *, compute_dtype=jnp.float32, as_dot: bool = False) -> Array:
        """as_dot lowers a 1x1 ungrouped conv as an explicit matmul
        (`(N,H,W,Cin) @ (Cin,Cout)`): forward is the same contraction XLA
        canonicalizes 1x1 convs to, but the WEIGHT GRADIENT of a dot is
        guaranteed to lower as another dot (MXU) — the pre-PR-1 trace showed
        25.3% of step time in `multiply_add_fusion` weight-grad reductions
        (ROADMAP.md's table), and this removes XLA's freedom to pick that lowering
        for the 1x1s. No-op for k>1 or grouped convs. Param layout is
        unchanged (HWIO, reshaped at apply), so checkpoints are identical."""
        with scope(self.scope_name):
            w = params["w"].astype(compute_dtype)
            x = x.astype(compute_dtype)
            if as_dot and self.kernel_size == 1 and self.groups == 1:
                if self.stride > 1:
                    # 1x1 stride-s conv == subsample then matmul (pad is 0)
                    x = x[:, :: self.stride, :: self.stride, :]
                y = x @ w.reshape(self.in_channels, self.out_channels)
            else:
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
        # remat landmark: train.remat_policy="save_conv" saves exactly these
        # (the MXU results) and recomputes the cheap BN/act elementwise chain
        # in backward, so normalized activations are never materialized
        # (train/steps.py; identity when no jax.checkpoint wraps the forward)
        return checkpoint_name(y, "conv_out")


# ---------------------------------------------------------------------------
# BatchNorm (with cross-replica sync)
# ---------------------------------------------------------------------------


def _finalize_moments(s1, s2, n_local, axis_name):
    """Shared psum + mean/biased-var tail of both stat paths — one copy, so
    a future change to the clamp or the psum structure cannot drift the
    modes apart below the parity tests' tolerance."""
    n = jnp.asarray(n_local, jnp.float32)
    if axis_name is not None:
        with scope("syncbn"):
            s1 = lax.psum(s1, axis_name)
            s2 = lax.psum(s2, axis_name)
            n = lax.psum(n, axis_name)
    mean = s1 / n
    var = jnp.maximum(s2 / n - jnp.square(mean), 0.0)  # biased
    return mean, var, n


def _bn_moments(x, axis_name):
    """Global (psum'd) f32 moments of x over N,H,W: (mean, var_biased, n).
    f32 accumulators reduce the input dtype directly — bit-equal to casting
    first, with no materialized f32 copy of the activation."""
    n_local = x.shape[0] * x.shape[1] * x.shape[2]
    s1 = jnp.sum(x, axis=(0, 1, 2), dtype=jnp.float32)
    s2 = jnp.sum(jnp.square(x.astype(jnp.float32)), axis=(0, 1, 2))
    return _finalize_moments(s1, s2, n_local, axis_name)


def _bn_moments_dot(x, axis_name):
    """Batch moments computed as MXU contractions instead of VPU reduces —
    the round-4 attack candidate on the trace's 51.8% convert_reduce_fusion
    share (ROADMAP.md's table): s1 = ones·x is a plain dot; s2 = Σ_nhw x² is a
    C-batched self-contraction (batch dim C, contract NHW), whose bf16
    products are EXACT in the f32 accumulator (8-bit mantissas double to 16
    < 24). Forcing dot lowerings also forces the BACKWARD companions of the
    stat reductions onto the MXU (autodiff transposes a dot to dots).
    Within f32 accumulation-order rounding (~1e-7 rel) of _bn_moments —
    NOT bit-identical, hence a separate opt-in mode. The exact-products
    argument above is for bf16 INPUTS; f32 inputs on the MXU would be
    silently truncated to bf16 under default precision (~1e-3 stat error,
    invisible to the CPU parity tests), so f32 requests HIGHEST precision —
    the bf16 training path keeps the fast default."""
    c = x.shape[-1]
    xt = x.reshape(-1, c)
    n_local = xt.shape[0]
    ones = jnp.ones((n_local,), x.dtype)
    prec = lax.Precision.HIGHEST if x.dtype == jnp.float32 else lax.Precision.DEFAULT
    s1 = lax.dot_general(ones, xt, (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32, precision=prec)
    s2 = lax.dot_general(xt, xt, (((0,), (0,)), ((1,), (1,))),
                         preferred_element_type=jnp.float32, precision=prec)
    return _finalize_moments(s1, s2, n_local, axis_name)


def _bn_train_fused(x, gamma, beta, eps, axis_name):
    y, mean, var, _ = _bn_train_fused_fwd_impl(x, gamma, beta, eps, axis_name)
    return y, mean, var


def _bn_train_fused_fwd_impl(x, gamma, beta, eps, axis_name):
    with scope("bn_stats"):
        mean, var, n = _bn_moments(x, axis_name)
    with scope("bn_apply"):
        inv = lax.rsqrt(var + eps)
        scale = gamma * inv
        bias = beta - mean * scale
        y = (x.astype(jnp.float32) * scale + bias).astype(x.dtype)
    return y, mean, var, (inv, n)


def _bn_train_fused_fwd(x, gamma, beta, eps, axis_name):
    # symbolic_zeros=True (see defvjp below) wraps each differentiable
    # primal in a CustomVJPPrimal carrier: unwrap to the actual arrays
    x, gamma, beta = x.value, gamma.value, beta.value
    y, mean, var, (inv, n) = _bn_train_fused_fwd_impl(x, gamma, beta, eps, axis_name)
    # residuals are the bf16 input + per-channel f32 stats — x_hat and any
    # f32 copy of the activation are recomputed, never stored
    return (y, mean, var), (x, gamma, mean, inv, n)


def _bn_train_fused_bwd(eps, axis_name, res, cts):
    """Closed-form BN backward through the batch statistics:

        dβ = Σ_local dy;  dγ = Σ_local dy·x̂;
        dx = γ·inv · (dy − psum(dβ)/n − x̂·psum(dγ)/n)    with n GLOBAL

    The asymmetry is the per-device gradient contract autodiff of the other
    bn_modes produces under the production shard_maps (parallel/dp.py,
    check_vma=False), pinned by tests/test_ops.py's sharded-contract test:

    - γ/β are REPLICATED params: each device returns its local partial sum
      and the training step's grad pmean (train/steps.py) — or the ZeRO
      psum_scatter — combines them. A psum here would double-count
      (device_count× BN affine grads; caught by review in round 3).
    - x is SHARDED: each shard's cotangent must be complete immediately,
      and x_e affects every device's outputs through the psum'd moments, so
      the correction terms need the GLOBAL sums (the transpose of the
      forward psum).

    The two local reductions fuse into ONE pass over (x, dy); dx is one
    more elementwise pass. Cotangents of the mean/var outputs must be
    symbolically zero: they feed only the running-stat state, which the
    training loss never differentiates (train/steps.py returns new_state as
    aux) — and that assumption is ENFORCED below (ADVICE r3 #1), so a
    future loss term reading the batch stats fails loudly at trace time
    instead of silently training with zero stat-gradients. The var
    zero-clamp in _bn_moments is treated as inactive (it only engages when
    catastrophic cancellation makes var numerically negative)."""
    del eps  # static; backward needs only the saved residuals
    x, gamma, mean, inv, n = res
    dy, dmean_ct, dvar_ct = cts
    zero = jax.custom_derivatives.SymbolicZero
    if not (isinstance(dmean_ct, zero) and isinstance(dvar_ct, zero)):
        raise TypeError(
            "bn_mode='fused_vjp' received non-zero cotangents for the batch "
            "mean/var outputs; its closed-form backward discards them by "
            "contract. A loss term differentiating the batch statistics "
            "(e.g. a stat regularizer) must use an autodiff bn_mode "
            "('exact'/'folded') or extend _bn_train_fused_bwd."
        )
    if isinstance(dy, zero):
        # nothing differentiates y either: all three gradients vanish
        return jnp.zeros_like(x), jnp.zeros_like(gamma), jnp.zeros_like(gamma)
    # the backward's two reductions are `bn_stats`, its elementwise pass
    # `bn_apply`, like the forward halves they are the gradients of
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
    with scope("bn_apply"):
        dx = (gamma * inv) * (dyf - s1 / n - x_hat * (s2 / n))
        dx = dx.astype(x.dtype)
    return dx, dgamma, dbeta


_bn_train_fused = jax.custom_vjp(_bn_train_fused, nondiff_argnums=(3, 4))
# symbolic_zeros=True so the backward can DETECT (and reject) a real
# cotangent on the mean/var outputs rather than silently dropping it
_bn_train_fused.defvjp(_bn_train_fused_fwd, _bn_train_fused_bwd, symbolic_zeros=True)


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

    def apply(
        self,
        params: dict,
        state: dict,
        x: Array,
        *,
        train: bool,
        axis_name: str | None = None,
        mode: str = "exact",
    ) -> tuple[Array, dict]:
        """mode selects the NORMALIZE expression only — batch statistics are
        bit-identical f32 accumulations in every mode (reducing the input
        dtype with an f32 accumulator equals casting first, element-for-
        element, and never materializes an f32 copy of the activation):

        - "exact"  — (f32(x) - mean) * (gamma*rsqrt(var+eps)) + beta. The
          round-2 TPU trace shows this step's 51.8% convert_reduce_fusion
          cost concentrated around BN (ROADMAP.md's table);
          the f32-upcast expression shared between the stat-reduce and the
          normalize is the suspected extra-HBM-traffic source.
        - "folded" — per-channel scale = gamma*rsqrt(var+eps) and
          bias = beta - mean*scale are precomputed (f32, C-sized, cheap);
          the tensor-wide work is a single FMA x*scale+bias with the f32
          convert inline in its own fusion. Differs from "exact" only by
          f32 rounding of the re-association (~1e-7 relative) — invisible
          under a bf16 output cast.
        - "compute" — like "folded" but scale/bias are cast to x.dtype and
          the FMA runs entirely in the compute dtype (bf16): halves the
          elementwise VPU width and drops both converts. Costs ~2-3 ulps of
          bf16 precision on y; opt-in for perf A/B.
        - "fused_vjp" — the "folded" forward under a custom VJP whose
          backward is the closed-form BN gradient: residuals are pinned to
          the bf16 input + per-channel f32 stats (x̂ and f32 activation
          copies are recomputed, never stored), and the dγ/dβ reductions
          fuse into one pass over (x, dy). Values equal "folded" exactly;
          gradients equal autodiff within reduction-order rounding.
        - "sdot" — the "folded" normalize, but batch statistics computed as
          MXU dots (_bn_moments_dot): the one family whose statistics are
          not bit-identical to the others (f32 accumulation order on the
          MXU; ~1e-7 rel). Opt-in for the hardware A/B against the VPU
          stat-reduce share of the trace.
        - "compute_sdot" — the "compute" (bf16 FMA) normalize over the
          MXU-dot statistics: the composite of the two independent levers,
          so the A/B can measure their combination directly instead of
          inferring additivity.
        """
        if mode not in BN_MODES:
            raise ValueError(f"unknown bn mode {mode!r}")
        out_dtype = x.dtype

        def running(mean, var, n):
            m = self.momentum
            unbiased = var * (n / jnp.maximum(n - 1.0, 1.0))
            return {
                "mean": (1.0 - m) * state["mean"] + m * mean,
                "var": (1.0 - m) * state["var"] + m * unbiased,
            }

        # every mode's work lands in one of two scopes (obs/scopes.py): the
        # batch moments and the running-stat update are `bn_stats`, the
        # normalize is `bn_apply`
        if train and mode == "fused_vjp":
            y, mean, var = _bn_train_fused(x, params["gamma"], params["beta"], self.eps, axis_name)
            with scope("bn_stats"):
                # lax.psum of the literal 1 is constant-folded to the axis size
                n = jnp.asarray(x.shape[0] * x.shape[1] * x.shape[2], jnp.float32)
                if axis_name is not None:
                    n = n * lax.psum(1, axis_name)
                return y, running(mean, var, n)
        if train:
            with scope("bn_stats"):
                moments = _bn_moments_dot if mode in ("sdot", "compute_sdot") else _bn_moments
                mean, var, n = moments(x, axis_name)
                new_state = running(mean, var, n)
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        with scope("bn_apply"):
            scale = lax.rsqrt(var + self.eps) * params["gamma"]
            if mode == "exact":
                y = (x.astype(jnp.float32) - mean) * scale + params["beta"]
            elif mode in ("compute", "compute_sdot"):
                bias = params["beta"] - mean * scale
                y = x * scale.astype(out_dtype) + bias.astype(out_dtype)
            else:  # "folded"/"sdot", and eval-mode "fused_vjp" (same expression)
                bias = params["beta"] - mean * scale
                y = x.astype(jnp.float32) * scale + bias
            return y.astype(out_dtype), new_state


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
