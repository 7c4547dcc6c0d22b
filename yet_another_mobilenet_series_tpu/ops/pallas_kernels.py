"""Pallas TPU kernels for the depthwise hot path.

Depthwise convolution is the one MobileNet op that cannot use the MXU (no
contraction dimension: it is C independent k x k stencils), so it runs on
the VPU and is HBM-bandwidth-bound. The XLA lowering materializes the conv
output, then the BatchNorm affine, then the activation, then the AtomNAS
mask — up to four HBM round trips over the widest tensors in the network.
``fused_depthwise_inference`` does all of it in one VMEM residency:

    y = act((dw_conv(x, w)) * scale + shift) * mask

with the BN folded into per-channel scale/shift (eval semantics — training
BN needs batch stats of the conv output, which requires a second pass; the
train path keeps the XLA lowering, which the compiler already fuses well).

A ``jax.custom_vjp`` wrapper makes the fused forward safe to drop into
differentiated code: the backward pass recomputes with the reference XLA
ops. Everything is validated against the ``ops.layers`` reference in Pallas
interpret mode (tests/test_pallas.py), and chip_smoke.py compiles it with
Mosaic for the real device (``interpret=False``) at two MobileNetV3-Large
shapes on every run and compares it with ``_reference_fwd`` there: under jax
0.9.0 / libtpu 0.0.34 on a v5e it compiles unchanged and agrees with the
reference at highest precision (measured PR 22; at DEFAULT precision XLA's
own f32 convolution is the less exact side, by ~1e-2 on values of 6).

Status: NOT WIRED INTO THE MODEL — measured and rejected (VERDICT r1 #4
resolved "remove"). On a real v5e (before PR 1, 2026-07-29), after fixing
three compile-blocking issues the interpreter can't see (scoped-VMEM stack
OOM from whole-image tap unrolls; >2D gathers from strided slices; a Mosaic
crash on rank-5 blocked operands), the honest dependency-chained A/B showed
the fused MBV3-L eval step at 307 ms/step vs 31 ms/step for the plain XLA
lowering at batch 1024 — the kernel LOSES ~10x end-to-end. Root causes:
per-(image, channel-block) grid steps do microseconds of VPU work against
fixed Mosaic dispatch overhead, narrow early blocks (c=16..72) waste up to
8x of every lane-padded VMEM transfer, and the stride-2 phase split costs an
extra HBM round trip that XLA's native conv does not pay. SURVEY.md §2's
rule was "Pallas kernel only if profiling shows a gap" — profiling showed
the opposite, so the model path keeps the XLA lowering (ops/blocks.py) and
this module stays as the measured negative result + harness for future
chips (scripts/bench_pallas.py times it; ROADMAP.md's table keeps the
number, Queue 3 item 8 its fate).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .activations import get_activation


def _dw_kernel(*refs, k: int, stride: int, act: str, out_h: int, out_w: int, row_block: int):
    """One (image, channel-block) per grid step, computed in row slabs.

    Three real-hardware constraints shape this kernel (all invisible to the
    interpret-mode tests; all hit on a real v5e):

    - Mosaic stack-allocates every live unrolled temporary, and at 112x112
      spatial with the channel axis lane-padded to 128 a whole-image tap
      unroll needs ~32 MB of scoped VMEM (>16 MB limit). So accumulation
      happens per ``row_block`` output rows: slab temporaries are
      (row_block, out_w, C-block) regardless of image size.
    - Strided (stride>1) vector slices lower to an unsupported >2D gather.
      So the caller phase-splits the padded input into stride^2 planes and
      every tap read here is a *contiguous* slice: output row r needs input
      row r*s + i, which lives in plane i%s at row r + i//s (and likewise
      for columns).
    - A rank-5 blocked operand (phases stacked on one axis) crashes the
      Mosaic compiler outright, so the phase planes arrive as stride^2
      separate rank-4 refs instead.
    """
    s = stride
    x_refs, (w_ref, scale_ref, shift_ref, mask_ref, o_ref) = refs[: s * s], refs[s * s :]
    for r0 in range(0, out_h, row_block):
        rows = min(row_block, out_h - r0)
        acc = None
        for i in range(k):
            for j in range(k):
                ph = (i % s) * s + (j % s)
                sl = x_refs[ph][0, r0 + i // s : r0 + i // s + rows, j // s : j // s + out_w, :]
                term = sl * w_ref[i, j, :]
                acc = term if acc is None else acc + term
        y = acc * scale_ref[0, :] + shift_ref[0, :]
        y = get_activation(act)(y)
        o_ref[0, r0 : r0 + rows, :, :] = (y * mask_ref[0, :]).astype(o_ref.dtype)


# Channel tile: depthwise is channel-independent, so the channel axis blocks
# freely for ANY stride (no halo logic needed, unlike spatial tiling). 128 =
# one VPU lane register width; it bounds per-step VMEM at the widest blocks
# (112x112 spatial x 128ch f32 in+out ~ 13 MB < ~16 MB VMEM; bf16 half that)
# where the old one-image-per-step layout overflowed at real widths.
_C_BLOCK = 128


@functools.partial(jax.jit, static_argnames=("stride", "act", "interpret"))
def _fused_dw_fwd(x, w, scale, shift, mask, *, stride: int, act: str, interpret: bool = False):
    n, h, wd, c = x.shape
    k = w.shape[0]
    pad = k // 2
    s = stride
    # per-channel operands ride as rank-2 (1, C) f32: rank-1 vectors hit
    # two separate Mosaic/XLA layout walls on real v5e (bf16 rank-1 blocks
    # need 256-multiples; f32[240] gets an XLA T(256) layout Mosaic rejects),
    # while (1, C) blocks tile as (sublane=1, lane=C-block) cleanly
    scale = scale.astype(jnp.float32).reshape(1, c)
    shift = shift.astype(jnp.float32).reshape(1, c)
    mask = mask.astype(jnp.float32).reshape(1, c)
    out_h = (h - 1) // s + 1
    out_w = (wd - 1) // s + 1
    # pad to a multiple of s so the s^2 phase planes all have equal shape
    # (the extra zero rows/cols are beyond every tap's reach)
    eh = (-(h + 2 * pad)) % s
    ew = (-(wd + 2 * pad)) % s
    xp = jnp.pad(x, ((0, 0), (pad, pad + eh), (pad, pad + ew), (0, 0)))
    hs = (h + 2 * pad + eh) // s
    ws = (wd + 2 * pad + ew) // s
    # XLA-side phase split: strided slicing is free here but lowers to an
    # unsupported gather inside the kernel (see _dw_kernel docstring); s=1
    # is the identity (one plane, no data movement beyond the pad)
    phases = [xp[:, p::s, q::s, :] for p in range(s) for q in range(s)]

    cb = min(c, _C_BLOCK)
    # slab height: keep each unrolled temporary (row_block x out_w x cb,
    # lanes padded to 128) around ~0.5 MB so ~6 live temps stay well inside
    # the ~16 MB scoped-VMEM stack budget at every spatial size
    row_block = min(out_h, max(8, 2048 // max(out_w, 1)))
    kernel = functools.partial(
        _dw_kernel, k=k, stride=s, act=act, out_h=out_h, out_w=out_w, row_block=row_block
    )
    return pl.pallas_call(
        kernel,
        grid=(n, pl.cdiv(c, cb)),
        in_specs=[pl.BlockSpec((1, hs, ws, cb), lambda i, j: (i, 0, 0, j))] * (s * s)
        + [
            pl.BlockSpec((k, k, cb), lambda i, j: (0, 0, j)),
            pl.BlockSpec((1, cb), lambda i, j: (0, j)),
            pl.BlockSpec((1, cb), lambda i, j: (0, j)),
            pl.BlockSpec((1, cb), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, out_h, out_w, cb), lambda i, j: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, out_h, out_w, c), x.dtype),
        interpret=interpret,
    )(*phases, w, scale, shift, mask)


def _reference_fwd(x, w, scale, shift, mask, *, stride: int, act: str):
    """The XLA lowering the kernel replaces (also the VJP recompute path)."""
    from jax import lax

    k = w.shape[0]
    pad = k // 2
    c = x.shape[-1]
    y = lax.conv_general_dilated(
        x.astype(jnp.float32),
        w[:, :, None, :].astype(jnp.float32),  # (k,k,1,C) HWIO depthwise
        window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c,
    )
    y = y * scale + shift
    y = get_activation(act)(y)
    return (y * mask).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def fused_depthwise_inference(x, w, scale, shift, mask, stride: int = 1, act: str = "relu6", interpret: bool = False):
    """Fused dw-conv + folded-BN + activation + mask.

    Args:
      x: (N,H,W,C); w: (k,k,C) depthwise taps; scale/shift: (C,) folded BN
      (scale = gamma*rsqrt(var+eps), shift = beta - mean*scale);
      mask: (C,) AtomNAS atom mask (ones when unused).
      interpret: run the Pallas interpreter (CPU testing).
    """
    return _fused_dw_fwd(x, w, scale, shift, mask, stride=stride, act=act, interpret=interpret)


def _vjp_fwd(x, w, scale, shift, mask, stride, act, interpret):
    y = _fused_dw_fwd(x, w, scale, shift, mask, stride=stride, act=act, interpret=interpret)
    return y, (x, w, scale, shift, mask)


def _vjp_bwd(stride, act, interpret, res, g):
    x, w, scale, shift, mask = res
    # correctness-first backward: differentiate the reference lowering
    _, vjp = jax.vjp(lambda *a: _reference_fwd(*a, stride=stride, act=act), x, w, scale, shift, mask)
    return vjp(g)


fused_depthwise_inference.defvjp(_vjp_fwd, _vjp_bwd)


def fold_bn(gamma, beta, mean, var, eps: float = 1e-5):
    """BN eval affine folded to (scale, shift) for the fused kernel."""
    from .layers import bn_scale_shift

    return bn_scale_shift(gamma, beta, mean, var, eps)
