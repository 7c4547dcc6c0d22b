"""Causal attention as two fused TPU kernels (Pallas/Mosaic): what
`ops/lm.py:causal_attention` runs where the step is lowered for a TPU and the
shapes fit (`fuses`). The mathematics and the precision are those of the tile
loops there (`loops_fwd/loops_bwd`, which stay: the path of every other
platform, and these kernels' oracle in the tests): operands in the compute
dtype with float32 accumulation; scores, running maximum, `exp`, row sums and
log-sum-exp in float32; probabilities and `ds` cast to the compute dtype
before their matmuls; kept for the backward `q`, `k`, `v`, `out`, `lse`, never
a tile. What differs is where a tile of scores lives: in VMEM, from `QK^T` to
the last matmul that reads it.

**What a layer keeps ACROSS the step** is less than what the backward kernel
reads: `ops/lm.py` names `out` and `lse`, and the layer checkpoint of
`models/lm.py` saves those two names beside the layer's input. `q`, `k`, `v`
are made again in the backward's second run of the layer (the MLA projections,
RoPE and the joins, with the norms, the MLP and the expert layer); the forward
kernel is not run again, since nothing else of its results is wanted.

**This module imports no Pallas.** It holds what the dispatch and the gauges
need (the predicate, the layout, the two entry points); the kernels are
`ops/lm_attention_kernels.py`, imported INSIDE `attention_fwd/attention_bwd`,
so when the `tpu` branch of a fitting site is traced and at no other time.
`models/lm.py`, `train/steps.py` and every runner import this module; a
process whose step has no attention never pays the Pallas import (1.2-1.5 s
of `setup_s` on the chip's host: what PR 28 was refused for).

**Layout.** The kernels take their operands FEATURES-LEADING, (B, H * D, S):
a head is a band of D rows, the sequence lies in lanes. That is how XLA holds
the MLA projections' results in the token step (its own choice for those
matmuls), so `features_lead` is a bitcast there and no copy stands between a
projection and a kernel, in either direction (kernels whose OPERANDS were
(B, H, S, D) or (B, S, H * D) cost 60 relayout copies a step, by the
compiler's own count on a described v5e).
"""

from __future__ import annotations

import jax.numpy as jnp

# The largest whole-sequence operand (rows x head dim x itemsize) the kernels hold resident.
RESIDENT_BYTES = 8 * 2 ** 20


def fuses(seq: int, block: int, qk_dim: int, v_dim: int, dtype) -> bool:
    """Whether the kernels take an attention call of this shape, in tiles
    of `block` x `block` (the platform is the lowering's to decide, not this
    predicate's): the sequence a multiple of the block, the block a multiple
    of 256 rows, head dims multiples of the 128 lanes, a whole sequence of
    one head resident in VMEM, and bfloat16 operands. With float32 operands,
    or blocks of 128 rows, the backward kernel does not compile (an internal
    error of the TPU compiler's, libtpu 0.0.34, in its transposed matmul
    operands). SIZED FOR A v5e: `lm_attention_kernels.VMEM_LIMIT_BYTES` and
    `RESIDENT_BYTES` assume its 128 MiB of VMEM, and the platform alone picks
    the kernels, so a TPU with less fails to compile them instead of taking
    the loops."""
    return (seq % block == 0 and block % 256 == 0 and qk_dim % 128 == 0 and v_dim % 128 == 0
            and jnp.dtype(dtype) == jnp.bfloat16 and seq * max(qk_dim, v_dim) * 2 <= RESIDENT_BYTES)


def fitting_dims(seq: int, block: int, qk_dim: int, v_dim: int, dtype) -> tuple[int, int]:
    """(q/k head dim, v head dim) the kernels are handed: each filled with
    ZERO channels to the next multiple of the 128 lanes (192 / 128 -> 256 /
    128, 64 / 64 -> 128 / 128; a dim that fills them is left as it is) where
    that makes the kernels take the call, else both as they are (the loops,
    unfilled). Exact: a zero channel of q and k adds 0 to every score, one of
    v makes a zero channel of the output, which is cut off, and the filling
    gets no gradient."""
    filled_qk, filled_v = -(-qk_dim // 128) * 128, -(-v_dim // 128) * 128
    return (filled_qk, filled_v) if fuses(seq, block, filled_qk, filled_v, dtype) else (qk_dim, v_dim)


def features_lead(x):
    """(B, H, S, D) -> (B, H * D, S), how the kernels take their operands: a
    bitcast where XLA holds the projections' results with the sequence in
    lanes, which is how it holds them in the token step (its own choice for
    those matmuls; a copy where it does not)."""
    b, h, seq, d = x.shape
    return jnp.swapaxes(x, 2, 3).reshape(b, h * d, seq)


def heads_lead(x, heads: int):
    """(B, H * D, S) -> (B, H, S, D)."""
    b, features, seq = x.shape
    return jnp.swapaxes(x.reshape(b, heads, features // heads, seq), 2, 3)


def attention_fwd(q, k, v, scale: float, block: int, interpret: bool = False, window: int | None = None):
    """q, k (B, H, S, D), v (B, H, S, Dv) -> (out (B, H, S, Dv), lse (B, H, S)
    float32): the output, and what the backward keeps beside it. A `window`:
    within it (ops/lm.py `causal_attention`), the window's kernel."""
    from . import lm_attention_kernels as kernels  # Pallas comes in HERE and nowhere earlier (module docstring)

    b, h, seq, _ = q.shape
    out, lse = kernels.fwd_call(features_lead(q), features_lead(k), features_lead(v), h, scale, block, interpret,
                                window)
    return heads_lead(out, h), lse.reshape(b, h, seq)


def attention_bwd(q, k, v, out, lse, g, scale: float, block: int, interpret: bool = False, window: int | None = None):
    """(dq, dk, dv) in the operands' shapes and dtype, from what the forward
    kept and the output's cotangent g (B, H, S, Dv); within a `window`, the
    window's kernel."""
    from . import lm_attention_kernels as kernels  # as in attention_fwd

    b, h, seq, _ = q.shape
    # sum_k P dP of every row, which the softmax's backward subtracts: it is g . out
    inner = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    stats = (b, h, seq // block, 1, block)
    grads = kernels.bwd_call(features_lead(q), features_lead(k), features_lead(v), features_lead(g), lse.reshape(stats),
                             inner.reshape(stats), h, scale, block, interpret, window)
    return tuple(heads_lead(x, h) for x in grads)
