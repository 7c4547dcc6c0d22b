"""Composite blocks: Conv-BN-act, squeeze-excite, inverted residual.

Reference behavior being rebuilt (SURVEY.md §2 #3, §3.4): the MobileNet block
grammar, including the AtomNAS fine-grained inverted residual where the
expanded channels are split into parallel per-kernel-size depthwise branches
("atoms"), whose post-depthwise BatchNorm scales are the prune handles.

TPU-first choices:
- One shared 1x1 expand conv and one shared 1x1 project conv per block (big
  MXU matmuls); only the cheap depthwise convs are per-branch.
- The per-branch BNs of the reference collapse into a single per-channel BN
  over the concatenated branches (mathematically identical — BN is
  channel-wise) so the whole expanded space has one ``gamma`` prune handle.
- Channel pruning is a multiplicative ``mask`` over expanded channels applied
  after the depthwise BN+act. Because every downstream consumer (SE reduce,
  project conv) is linear in those channels, masking is exactly equivalent to
  physically removing them (tested in tests/test_nas.py) — this is how the
  reference's eager "rebuild the net with smaller tensors" becomes an
  XLA-static-shape program (SURVEY.md §3.2, §7 hard part 1).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..obs.scopes import scope
from .activations import get_activation
from .layers import Array, BatchNorm, Conv2D, Dense, conv_bn, conv_bn_pairs, global_avg_pool, is_conv1x1_bn_site


@dataclass(frozen=True)
class ConvBNAct:
    in_channels: int
    out_channels: int
    kernel_size: int = 3
    stride: int = 1
    groups: int = 1
    active_fn: str = "relu6"
    bn_momentum: float = 0.1
    bn_eps: float = 1e-5

    def __post_init__(self):
        get_activation(self.active_fn)  # fail at spec-build time, not in jit

    @property
    def conv(self) -> Conv2D:
        return Conv2D(self.in_channels, self.out_channels, self.kernel_size, self.stride, self.groups)

    @property
    def bn(self) -> BatchNorm:
        return BatchNorm(self.out_channels, self.bn_momentum, self.bn_eps)

    def conv_bn_pair_sites(self) -> tuple[int, int]:
        """(sites a train step lowers through the conv + BN pair, 1x1 conv + BN sites)."""
        return int(conv_bn_pairs(self.conv, train=True)), int(is_conv1x1_bn_site(self.conv))

    def init(self, key):
        params = {"conv": self.conv.init(key)}
        bn_p, bn_s = self.bn.init()
        params["bn"] = bn_p
        return params, {"bn": bn_s}

    def apply(self, params, state, x, *, train, axis_name=None, compute_dtype=jnp.float32):
        y, bn_s = conv_bn(self.conv, self.bn, params["conv"], params["bn"], state["bn"], x, train=train,
                          axis_name=axis_name, compute_dtype=compute_dtype)
        y = get_activation(self.active_fn)(y)
        return y, {"bn": bn_s}


@dataclass(frozen=True)
class SqueezeExcite:
    """SE over NHWC features: squeeze (global mean) -> reduce FC -> act ->
    expand FC -> gate. ``gate_fn`` is h-sigmoid for MobileNetV3-style nets and
    sigmoid for MNASNet-style (SURVEY.md §2 #3)."""

    channels: int
    se_channels: int
    inner_act: str = "relu"
    gate_fn: str = "hsigmoid"

    def init(self, key):
        k1, k2 = jax.random.split(key)
        # torch Conv2d-default init for the SE FCs: kaiming_uniform(a=sqrt(5))
        # over fan_in, i.e. U(-1/sqrt(fan_in), 1/sqrt(fan_in)).
        def ku(key, fan_in, shape):
            bound = 1.0 / math.sqrt(fan_in)
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)

        return {
            "reduce": {"w": ku(k1, self.channels, (self.channels, self.se_channels)), "b": jnp.zeros((self.se_channels,), jnp.float32)},
            "expand": {"w": ku(k2, self.se_channels, (self.se_channels, self.channels)), "b": jnp.zeros((self.channels,), jnp.float32)},
        }

    def apply(self, params, x, *, compute_dtype=jnp.float32):
        # Squeeze/gate in float32: tiny FLOPs, and bf16 pooled moments cost
        # accuracy in the gate. Everything in here is timed as `se`, the
        # pool and the activations too (obs/scopes.py's nesting rule).
        with scope("se"):
            s = global_avg_pool(x).astype(jnp.float32)  # (N, C)
            s = s @ params["reduce"]["w"] + params["reduce"]["b"]
            s = get_activation(self.inner_act)(s)
            s = s @ params["expand"]["w"] + params["expand"]["b"]
            gate = get_activation(self.gate_fn)(s).astype(x.dtype)
            return x * gate[:, None, None, :]


@dataclass(frozen=True)
class InvertedResidual:
    """MBConv / AtomNAS block.

    ``group_channels[i]`` expanded channels go through a depthwise conv of
    size ``kernel_sizes[i]``; a standard MBConv is the single-kernel case.
    ``sum(group_channels)`` is the expanded width. Residual iff stride==1 and
    in_channels==out_channels (reference semantics, SURVEY.md §3.4).
    """

    in_channels: int
    out_channels: int
    expanded_channels: int
    stride: int = 1
    kernel_sizes: tuple[int, ...] = (3,)
    group_channels: tuple[int, ...] = ()  # defaults to all channels on kernel_sizes[0]
    active_fn: str = "relu6"
    se_channels: int = 0  # 0 = no SE
    se_gate_fn: str = "hsigmoid"
    se_inner_act: str = "relu"
    bn_momentum: float = 0.1
    bn_eps: float = 1e-5
    # 'identity' = linear bottleneck (MBConv). MobileNetV1's depthwise-
    # separable block is this spec with expanded==in and a ReLU here.
    project_act: str = "identity"
    # V1/MNASNet-sepconv blocks never add a residual even when shapes allow.
    allow_residual: bool = True
    # Keep the 1x1 expand conv even when expanded==in (a pruned supernet
    # block can shrink to exactly in_channels; its expand conv must survive).
    force_expand: bool = False
    # Stochastic depth / drop-connect (arXiv:1603.09382; EfficientNet
    # arXiv:1905.11946): per-SAMPLE Bernoulli drop of the residual branch at
    # train time, inverse-scaled by the keep probability so eval needs no
    # rescale. Only meaningful on residual blocks; 0 = off (all non-
    # EfficientNet archs). In-jit: one (N,1,1,1) bernoulli, XLA fuses it.
    drop_path: float = 0.0

    def __post_init__(self):
        for name in (self.active_fn, self.project_act, self.se_gate_fn, self.se_inner_act):
            get_activation(name)  # fail at spec-build time, not in jit
        if not 0.0 <= self.drop_path < 1.0:
            # keep_prob <= 0 would inverse-scale by 1/0 -> NaN from step 0
            raise ValueError(f"drop_path must be in [0, 1), got {self.drop_path}")
        groups = self.group_channels or (self.expanded_channels,)
        object.__setattr__(self, "group_channels", tuple(groups))
        if len(self.group_channels) != len(self.kernel_sizes):
            raise ValueError(f"group_channels {self.group_channels} vs kernel_sizes {self.kernel_sizes}")
        if sum(self.group_channels) != self.expanded_channels:
            raise ValueError(f"group_channels {self.group_channels} must sum to expanded={self.expanded_channels}")
        if any(g <= 0 for g in self.group_channels):
            raise ValueError(f"empty atomic group in {self.group_channels}")

    # -- derived static structure ------------------------------------------
    @property
    def has_expand(self) -> bool:
        return self.force_expand or self.expanded_channels != self.in_channels

    @property
    def has_residual(self) -> bool:
        return self.allow_residual and self.stride == 1 and self.in_channels == self.out_channels

    def _bn(self, c):
        return BatchNorm(c, self.bn_momentum, self.bn_eps)

    @property
    def _expand_conv(self) -> Conv2D:
        return Conv2D(self.in_channels, self.expanded_channels, 1)

    @property
    def _project_conv(self) -> Conv2D:
        return Conv2D(self.expanded_channels, self.out_channels, 1)

    def conv_bn_pair_sites(self) -> tuple[int, int]:
        """(sites a train step lowers through the conv + BN pair, 1x1 conv + BN
        sites). Only the expand conv goes through conv_bn(): the project conv's
        INPUT is the wide tensor, so there the pair has nothing to shed."""
        expand = self.has_expand and conv_bn_pairs(self._expand_conv, train=True)
        return int(expand), int(self.has_expand) + 1

    def _branches(self):
        """Yields (branch_index, kernel_size, group_channels, offset) —
        single source of truth for the expanded-channel layout used by both
        the XLA and fused-kernel paths."""
        offset = 0
        for i, (k, g) in enumerate(zip(self.kernel_sizes, self.group_channels)):
            yield i, k, g, offset
            offset += g

    def init(self, key):
        keys = jax.random.split(key, 3 + len(self.kernel_sizes))
        params, state = {}, {}
        if self.has_expand:
            params["expand"] = self._expand_conv.init(keys[0])
            params["expand_bn"], state["expand_bn"] = self._bn(self.expanded_channels).init()
        for i, (k, g) in enumerate(zip(self.kernel_sizes, self.group_channels)):
            params[f"dw{i}_k{k}"] = Conv2D(g, g, k, self.stride, groups=g).init(keys[1 + i])
        # Single concatenated BN over all branches; its gamma is the per-atom
        # prune handle (SURVEY.md §3.2).
        params["dw_bn"], state["dw_bn"] = self._bn(self.expanded_channels).init()
        if self.se_channels:
            params["se"] = SqueezeExcite(
                self.expanded_channels, self.se_channels, self.se_inner_act, self.se_gate_fn
            ).init(keys[-2])
        params["project"] = self._project_conv.init(keys[-1])
        params["project_bn"], state["project_bn"] = self._bn(self.out_channels).init()
        return params, state

    def apply(
        self,
        params,
        state,
        x,
        *,
        train: bool,
        axis_name: str | None = None,
        compute_dtype=jnp.float32,
        mask: Array | None = None,
        rng: Array | None = None,
    ):
        """mask: optional (expanded_channels,) multiplier zeroing dead atoms.

        The depthwise chain is deliberately the plain XLA lowering: a Pallas
        fused dw+BN+act+mask eval kernel was built and A/B-measured on a real
        v5e before PR 1 and lost 10x end-to-end (ops/pallas_kernels.py keeps
        the kernel and the verdict; ROADMAP.md's table the number)."""
        act = get_activation(self.active_fn)
        new_state = {}
        h = x
        if self.has_expand:
            h, new_state["expand_bn"] = conv_bn(
                self._expand_conv, self._bn(self.expanded_channels), params["expand"], params["expand_bn"],
                state["expand_bn"], h, train=train, axis_name=axis_name, compute_dtype=compute_dtype,
            )
            h = act(h)
        branches = []
        for i, k, g, offset in self._branches():
            sl = h[..., offset : offset + g]
            branches.append(
                Conv2D(g, g, k, self.stride, groups=g).apply(params[f"dw{i}_k{k}"], sl, compute_dtype=compute_dtype)
            )
        h = branches[0] if len(branches) == 1 else jnp.concatenate(branches, axis=-1)
        h, new_state["dw_bn"] = self._bn(self.expanded_channels).apply(
            params["dw_bn"], state["dw_bn"], h, train=train, axis_name=axis_name
        )
        h = act(h)
        if mask is not None:
            with scope("nas_mask"):
                h = h * mask.astype(h.dtype)
        if self.se_channels:
            h = SqueezeExcite(self.expanded_channels, self.se_channels, self.se_inner_act, self.se_gate_fn).apply(
                params["se"], h, compute_dtype=compute_dtype
            )
        h = self._project_conv.apply(params["project"], h, compute_dtype=compute_dtype)
        h, new_state["project_bn"] = self._bn(self.out_channels).apply(
            params["project_bn"], state["project_bn"], h, train=train, axis_name=axis_name
        )
        h = get_activation(self.project_act)(h)
        if self.has_residual:
            if train and self.drop_path > 0 and rng is not None:
                with scope("drop"):
                    keep_prob = 1.0 - self.drop_path
                    keep = jax.random.bernoulli(rng, keep_prob, (h.shape[0], 1, 1, 1))
                    h = h * (keep.astype(h.dtype) / jnp.asarray(keep_prob, h.dtype))
            if mask is not None:
                # A fully-masked block must equal identity exactly — without
                # this gate the project BN's shift (beta - mean*scale) leaks
                # through zeroed inputs, and rematerialization (which drops
                # dead residual blocks, nas/rematerialize.py) would not be
                # equivalent to masking.
                with scope("nas_mask"):
                    any_alive = (jnp.max(mask) > 0).astype(h.dtype)
                    h = h * any_alive
            with scope("residual"):
                h = h + x.astype(h.dtype)
        return h, new_state
