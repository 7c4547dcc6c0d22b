"""Mamba-2's mixer, the state-space layer of `granitemoehybrid` (models/lm.py):
one input projection into a gate `z`, the convolved stream `xBC` and a step
size `dt` a head; a short causal convolution with a bias and SiLU over `xBC`;
the selective state-space recurrence over `heads` heads of `head_dim` channels,
whose input and output maps `B` and `C` (`state` channels) are ONE group
shared by every head; a gated RMSNorm over all heads' channels; the output
projection. Plain `jax.numpy`/`lax`, but for the convolution, which goes
through the short-convolution kernels of ops/lm_kda.py where they take it,
and each chunk's output from its start state, which two fused TPU kernels
make where they take it (below).

The recurrence, a head at a time (state S: head_dim x state, S_0 = 0; Delta_t
and a_t = Delta_t A one number a head and position, A = -exp(A_log) < 0)::

    S_t = e^{a_t} S_{t-1} + Delta_t x_t B_t^T;   y_t = S_t C_t + D x_t

is what the reference runs token by token (models/lm_reference.py). Here it
runs in its CHUNKED form, the state-space duality ("SSD"), `chunk`
positions at a time (`mamba_chunk_size`). With G_t the in-chunk cumulative sum of a (float32) and
S_0 the state at the chunk's start:

    y_t = sum_{s<=t} e^{G_t - G_s} (C_t . B_s) Delta_s x_s + e^{G_t} S_0 C_t + D x_t
    S_C = e^{G_C} S_0 + sum_s e^{G_C - G_s} Delta_s x_s B_s^T

so what depends on a chunk alone is made for ALL chunks at once: the scores
`C B^T` ONCE a chunk for all heads (one group), each head's decays, the
in-chunk products and each chunk's own state contribution; one `lax.scan`
over the chunks (:func:`_state_pass`) carries the state through one
multiply-add a step.

**No decay is clamped.** Every in-chunk factor is `exp` of a DIFFERENCE of
one cumulative sum, `G_t - G_s` with `s <= t` (at most 0), masked above the
diagonal before the `exp`; written as `e^{G_t} e^{-G_s}` it overflows float32:
a fresh layer's per-token log decays reach -1.6 and a chunk of 256 sums past
-88. `e^{G_t}` alone, the state's way into the chunk, only ever underflows, to
the 0 its true value rounds to. `ssd_min_chunk_log_decay` (a step scalar) is
the most negative `G` a step met.

**Two lowerings of what depends on a chunk alone.** `_own` makes each
chunk's own contribution to the state at its end, and `_outputs` y of every
chunk from its start state, in plain `lax`: the state's way in, the in-chunk
products for all heads at once under one `jax.checkpoint` (its backward
makes them again: at 8,192 positions a float32 (chunk x chunk) matrix a head
and chunk is 512 MiB for 64 heads, which XLA never holds whole; eight heads
at a time declared 0.34 GiB MORE temporaries for the cell's step and stepped
2.5% slower, PERF.md), the D skip. That is every other platform's path, the
path of a shape the kernels do not take, and their oracle in the tests.
`_fused_own` and `_fused_outputs` are the same functions as Pallas/Mosaic
kernels (ops/lm_mamba_kernels.py), each under one `custom_vjp`: forward
kernels that read x, Delta, G, B (and C, the start states and D) as the
mixer holds them, the decays, weights and products in VMEM, and write the
own contributions as (N, B, H, P, K) and y once as (B, S, H * P), the layout
the gated norm reads; backward kernels that make them again and write every
argument's gradient. `ssd_core` takes them where `fuses` says the shapes fit
AND the step is lowered for a TPU (`lax.platform_dependent`); no option
chooses. The kernels' module comes in INSIDE `own_fwd` / `own_bwd` /
`chunk_fwd` / `chunk_bwd` only (every process imports this module through
models/lm.py, and the Pallas import costs a cell that runs none of its code
set-up time: PERF.md); `train.ssd_fused_sites` (train/steps.py) says how
many layers a step lowers that way. The chunk scan stays in `lax` in both.

**What the backward keeps.** The chunk scan is a `custom_vjp` whose output,
the state at every chunk's start (in the compute dtype, as the matmuls read
it), carries the name `SSD_STATES_NAME`: the layer checkpoint of models/lm.py
saves it, so the backward's second run of a layer makes the in-chunk matrices
again (in either lowering) and not the scan.

Precision: matmul operands in the compute dtype at the default precision (one
MXU pass), accumulated in float32; `Delta`, the decays, their cumulative sums,
every `exp`, the states, the norm in float32, whatever the compute dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.scopes import scope
from . import lm_kda

Array = jax.Array

# The name (jax.ad_checkpoint.checkpoint_name) of what the chunk scan's backward keeps: the state at each chunk's start.
SSD_STATES_NAME = "ssd_states"
# Lanes a band of the xBC convolution's kernels holds (no head norm there: any whole 128-lane band will do).
CONV_BAND = 128
# The SSD kernels (ops/lm_mamba_kernels.py): lanes of a band, the heads a program instance takes, and the VMEM a
# program instance may plan for (`fuses`; the kernels' compiler limit is twice it).
SSD_BAND = 128
SSD_HEAD_GROUP = 16
SSD_VMEM_BYTES = 32 * 2 ** 20
_F32 = {"preferred_element_type": jnp.float32}


def conv_fuses(seq: int, channels: int, taps: int, dtype) -> bool:
    """Whether the xBC convolution of a site of this shape goes through the
    short-convolution kernels (`lm_kda.conv_fuses`, the channels whole
    128-lane bands) where the lowering is a TPU's."""
    return channels % CONV_BAND == 0 and lm_kda.conv_fuses(seq, CONV_BAND, taps, dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _state_pass(contributions: Array, decay: Array, cd) -> Array:
    """The chunks in order: S_0 = 0, S_{n+1} = decay_n S_n + contributions_n.
    contributions (N, B, H, P, K) float32, decay (N, B, H) float32 -> the
    state at each chunk's START, (N, B, H, P, K) in `cd`. The backward walks
    the chunks in reverse from those states and the decays alone."""
    return _state_pass_fwd(contributions, decay, cd)[0]


def _state_pass_fwd(contributions, decay, cd):
    from jax.ad_checkpoint import checkpoint_name  # not an attribute of `jax`; an alias module of what `import jax` loaded

    def chunk(state, xs):
        own, gamma = xs
        # the state at the chunk's START, in the compute dtype the matmuls read it in (float32 is the CARRY's)
        return gamma[..., None, None] * state + own, state.astype(cd)

    _, starts = lax.scan(chunk, jnp.zeros(contributions.shape[1:], jnp.float32), (contributions, decay))
    starts = checkpoint_name(starts, SSD_STATES_NAME)
    return starts, (starts, decay)


def _state_pass_bwd(cd, kept, ct):
    starts, decay = kept

    def chunk(d_state, xs):  # d_state: the cotangent of the state at the chunk's END
        start, gamma, d_start = xs
        d_gamma = jnp.sum(d_state * start.astype(jnp.float32), axis=(-2, -1))
        return d_start.astype(jnp.float32) + gamma[..., None, None] * d_state, (d_state, d_gamma)

    _, grads = lax.scan(chunk, jnp.zeros(starts.shape[1:], jnp.float32), (starts, decay, ct), reverse=True)
    return grads


_state_pass.defvjp(_state_pass_fwd, _state_pass_bwd)


def _in_chunk(scores: Array, cum: Array, dx: Array) -> Array:
    """sum_{s<=t} e^{G_t - G_s} (C_t . B_s) dx_s for every head: scores (B,
    N, C, C) float32 (C B^T of every chunk), cum (B, N, C, H) float32, dx (B,
    N, C, H, P) in the compute dtype -> (B, N, C, H, P) float32."""
    rows = cum.shape[2]
    below = jnp.tril(jnp.ones((rows, rows), bool))
    gaps = cum[..., :, None, :] - cum[..., None, :, :]  # [t, s, h] = G_t - G_s
    decays = jnp.exp(jnp.where(below[..., None], gaps, -jnp.inf))  # at most 1: exponents <= 0, masked before the exp
    weights = (scores[..., None] * decays).astype(dx.dtype)  # (B, N, t, s, h)
    return jnp.einsum("bntsh,bnshp->bnthp", weights, dx, **_F32)


def _delta_x(x: Array, delta: Array) -> Array:
    """Delta_s x_s in x's dtype: x (..., H, P), delta (..., H) float32."""
    return (x.astype(jnp.float32) * delta[..., None]).astype(x.dtype)


def _own(x: Array, delta: Array, cum: Array, b: Array) -> Array:
    """Each chunk's own contribution to the state at its end, in plain `lax`:
    x (B, N, C, H, P) in the compute dtype; delta, cum (B, N, C, H) float32;
    b (B, N, C, K) in the compute dtype -> (N, B, H, P, K) float32,
    sum_s e^{G_C - G_s} Delta_s x_s B_s^T."""
    tail = jnp.exp(cum[:, :, -1:, :] - cum)  # <= 1
    return jnp.einsum("bnshp,bnsk->nbhpk", (_delta_x(x, delta).astype(jnp.float32) * tail[..., None]).astype(x.dtype), b,
                      **_F32)


def _outputs(x: Array, delta: Array, cum: Array, b: Array, c: Array, starts: Array, d_skip: Array) -> Array:
    """y of every chunk from its start state, in plain `lax`: x (B, N, C, H, P)
    in the compute dtype; delta, cum (B, N, C, H) float32; b, c (B, N, C, K)
    in the compute dtype; starts (N, B, H, P, K) in the compute dtype; d_skip
    (H,) -> y (B, N, C, H, P) in x's dtype: the state's way in, the in-chunk
    products (all heads at once, under one `jax.checkpoint`) and the D skip."""
    y = jnp.einsum("bntk,nbhpk->bnthp", c, starts, **_F32) * jnp.exp(cum)[..., None]
    scores = jnp.einsum("bntk,bnsk->bnts", c, b, **_F32)  # C B^T: once a chunk, for every head
    y = y + jax.checkpoint(_in_chunk)(scores, cum, _delta_x(x, delta))
    return (y + d_skip.astype(jnp.float32)[:, None] * x.astype(jnp.float32)).astype(x.dtype)


# ---- the kernels' lowering (module docstring): operands flat, (B, S, ...) as the mixer holds them, whole chunks ----
def _chunks(t: Array, n: int) -> Array:
    return t.reshape(t.shape[0], n, t.shape[1] // n, *t.shape[2:])


def _plain_own(x, delta, cum, b, chunk):
    """`_own` of flat operands: x (B, S, H * P), delta, cum (B, S, H), b (B, S, K)."""
    n = x.shape[1] // chunk
    return _own(_chunks(x.reshape(*x.shape[:2], delta.shape[-1], -1), n), _chunks(delta, n), _chunks(cum, n), _chunks(b, n))


def _plain_own_bwd(x, delta, cum, b, ct, chunk):
    return jax.vjp(functools.partial(_plain_own, chunk=chunk), x, delta, cum, b)[1](ct)


def _plain_outputs(x, delta, cum, b, c, starts, d_skip):
    """`_outputs` of flat operands (`_plain_own`'s, c like b), starts as
    `_state_pass` makes them -> y (B, S, H * P)."""
    n = starts.shape[0]
    y = _outputs(_chunks(x.reshape(*x.shape[:2], delta.shape[-1], -1), n), *(_chunks(t, n) for t in (delta, cum, b, c)),
                 starts, d_skip)
    return y.reshape(x.shape)


def _plain_outputs_bwd(x, delta, cum, b, c, starts, d_skip, ct):
    """`_plain_outputs`' own vjp (its in-chunk matrices made again)."""
    return jax.vjp(_plain_outputs, x, delta, cum, b, c, starts, d_skip)[1](ct)


def head_group(heads: int, head_dim: int) -> int:
    """The heads a program instance of the SSD kernels takes: `SSD_HEAD_GROUP`
    where it divides the heads and makes whole 128-lane bands, else all."""
    group = SSD_HEAD_GROUP
    return group if heads % group == 0 and group % 8 == 0 and group * head_dim % SSD_BAND == 0 else heads


def fuses(seq: int, chunk: int, heads: int, head_dim: int, state: int, dtype) -> bool:
    """Whether the SSD kernels of ops/lm_mamba_kernels.py take a site of this
    shape (the platform is the lowering's to decide): whole chunks of whole
    128-row tiles, a head a whole number of 16-row tiles that divides a
    128-lane band, all heads whole bands, a state of whole bands, bfloat16
    operands, and a program instance's blocks, scratch and (chunk x chunk)
    temporaries within the VMEM it may plan for."""
    if not (seq % chunk == 0 and chunk % 128 == 0 and head_dim % 16 == 0 and SSD_BAND % head_dim == 0
            and heads * head_dim % SSD_BAND == 0 and state % 128 == 0 and jnp.dtype(dtype) == jnp.bfloat16):
        return False
    lanes = head_group(heads, head_dim) * head_dim
    # double-buffered blocks (bfloat16 bands and states, C and B and their gradients, float32 (chunk x heads) blocks
    # padded to a band) beside the scratch and temporaries of (chunk x chunk) float32
    blocks = 2 * 2 * (4 * chunk * lanes + 2 * lanes * state + 4 * chunk * state + 2 * 5 * chunk * SSD_BAND)
    return blocks + 10 * 4 * chunk * chunk <= SSD_VMEM_BYTES


# Sites of one shape share one trace and one lowering of each form (the nine Mamba-2 layers of the cell are one kind).
@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def own_fwd(x, delta, cum, b, chunk, interpret: bool = False):
    """`_plain_own` as ONE fused kernel (ops/lm_mamba_kernels.py): Delta x and
    its decay to the chunk's end made in VMEM, its product with B on the MXU."""
    from . import lm_mamba_kernels as kernels  # Pallas comes in HERE and nowhere earlier (module docstring)

    heads = delta.shape[-1]
    return kernels.own_call(x, delta, cum, b, chunk, x.shape[-1] // heads, head_group(heads, x.shape[-1] // heads),
                            interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def own_bwd(x, delta, cum, b, ct, chunk, interpret: bool = False):
    """`_plain_own_bwd` from one kernel: dx, dDelta, dG, dB."""
    from . import lm_mamba_kernels as kernels  # as in own_fwd

    heads = delta.shape[-1]
    return tuple(kernels.own_bwd_call(x, delta, cum, b, ct, x.shape[-1] // heads, head_group(heads, x.shape[-1] // heads),
                                      interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def chunk_fwd(x, delta, cum, b, c, starts, d_skip, interpret: bool = False):
    """`_plain_outputs` as ONE fused kernel: the same arguments and result, the
    decays, weights and their products in VMEM."""
    from . import lm_mamba_kernels as kernels  # as in own_fwd

    heads = delta.shape[-1]
    return kernels.fwd_call(x, delta, cum, b, c, starts, d_skip, x.shape[-1] // heads,
                            head_group(heads, x.shape[-1] // heads), interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def chunk_bwd(x, delta, cum, b, c, starts, d_skip, ct, interpret: bool = False):
    """`_plain_outputs_bwd` from the second kernel, which makes the decays and
    weights again in VMEM; XLA adds dG's two layouts and sums D's lanes."""
    from . import lm_mamba_kernels as kernels  # as in own_fwd

    heads = delta.shape[-1]
    width = x.shape[-1] // heads
    dx, d_delta, d_cum, d_cum_rows, dc, db, d_starts, d_skip_lanes = kernels.bwd_call(
        x, delta, cum, b, c, starts, d_skip, ct, width, head_group(heads, width), interpret)
    d_d = jnp.sum(d_skip_lanes, axis=(0, 1, 2)).reshape(heads, width).sum(axis=1).astype(d_skip.dtype)
    return dx, d_delta, d_cum + jnp.swapaxes(d_cum_rows, 1, 2), db, dc, d_starts, d_d


# the other platforms' branches, shared by the sites as the kernels are
_shared_own = jax.jit(_plain_own, static_argnames=("chunk",))
_shared_own_bwd = jax.jit(_plain_own_bwd, static_argnames=("chunk",))
_shared_outputs = jax.jit(_plain_outputs)
_shared_outputs_bwd = jax.jit(_plain_outputs_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused_own(x, delta, cum, b, chunk):
    """`_plain_own` at a shape the kernels take (`fuses`): a kernel each way
    where the step is lowered for a TPU, else the plain form and its own vjp
    (`lax.platform_dependent` decides at lowering)."""
    return _fused_own_fwd(x, delta, cum, b, chunk)[0]


def _fused_own_fwd(x, delta, cum, b, chunk):
    made = lax.platform_dependent(x, delta, cum, b, tpu=functools.partial(own_fwd, chunk=chunk),
                                  default=functools.partial(_shared_own, chunk=chunk))
    return made, (x, delta, cum, b)


def _fused_own_bwd(chunk, kept, ct):
    with scope("ssd_core"):
        return lax.platform_dependent(*kept, ct, tpu=functools.partial(own_bwd, chunk=chunk),
                                      default=functools.partial(_shared_own_bwd, chunk=chunk))


_fused_own.defvjp(_fused_own_fwd, _fused_own_bwd)


@jax.custom_vjp
def _fused_outputs(x, delta, cum, b, c, starts, d_skip):
    """`_plain_outputs` at a shape the kernels take (`fuses`): the two kernels
    where the step is lowered for a TPU, else the plain form and its own vjp.
    What the backward keeps is its arguments."""
    return _fused_outputs_fwd(x, delta, cum, b, c, starts, d_skip)[0]


def _fused_outputs_fwd(*args):
    return lax.platform_dependent(*args, tpu=chunk_fwd, default=_shared_outputs), args


def _fused_outputs_bwd(kept, ct):
    with scope("ssd_core"):
        return lax.platform_dependent(*kept, ct, tpu=chunk_bwd, default=_shared_outputs_bwd)


_fused_outputs.defvjp(_fused_outputs_fwd, _fused_outputs_bwd)


def ssd_core(x: Array, delta: Array, log_decay: Array, b: Array, c: Array, d_skip: Array,
             chunk: int) -> tuple[Array, Array]:
    """The recurrence in its chunked form (module docstring). x (B, S, H, P)
    in the compute dtype; delta, log_decay (B, S, H) float32 (Delta and
    Delta A); b, c (B, S, K) in the compute dtype (ONE group, shared by the
    heads); d_skip (H,); `chunk` positions a chunk -> (y (B, S, H, P) in the compute dtype, the most
    negative in-chunk cumulative log decay: a float32 scalar, no gradient).
    Any length: the last chunk is filled with positions that neither decay
    nor write (Delta = 0, x = B = C = 0). What depends on a chunk alone (its
    own state contribution, its output from its start state) is made by the
    fused kernels where the shapes fit them and the lowering is a TPU's
    (`fuses`, `_fused_own`, `_fused_outputs`), else in plain `lax`; the chunk
    scan is `_state_pass` either way."""
    cd = x.dtype
    batch, seq, heads, width = x.shape
    chunk = min(chunk, seq)
    fill = -seq % chunk
    n = (seq + fill) // chunk

    def chunked(t):  # (B, S, ...) -> (B, N, C, ...)
        t = jnp.pad(t, [(0, 0), (0, fill)] + [(0, 0)] * (t.ndim - 2))
        return t.reshape(batch, n, chunk, *t.shape[2:])

    with scope("ssd_gate"):
        cum = jnp.cumsum(chunked(log_decay), axis=2)  # (B, N, C, H) float32, <= 0
        lowest = jnp.min(cum)
    with scope("ssd_core"):
        decay = jnp.moveaxis(jnp.exp(cum[:, :, -1, :]), 1, 0)  # (N, B, H): the state's decay across each chunk
        if fuses(seq, chunk, heads, width, b.shape[-1], cd):  # whole chunks: the operands stay flat, heads in lanes
            xf, cf = x.reshape(batch, seq, heads * width), cum.reshape(batch, seq, heads)
            starts = _state_pass(_fused_own(xf, delta, cf, b, chunk), decay, cd)
            return _fused_outputs(xf, delta, cf, b, c, starts, d_skip).reshape(x.shape), lax.stop_gradient(lowest)
        xc, bc, dc = chunked(x), chunked(b), chunked(delta)
        starts = _state_pass(_own(xc, dc, cum, bc), decay, cd)  # (N, B, H, P, K)
        y = _outputs(xc, dc, cum, bc, chunked(c), starts, d_skip)
        return y.reshape(batch, n * chunk, heads, width)[:, :seq], lax.stop_gradient(lowest)


def mamba_mixer(p: dict, x: Array, *, heads: int, head_dim: int, state: int, chunk: int,
                eps: float) -> tuple[Array, Array]:
    """One Mamba-2 mixer. x (B, S, h), the normed hidden state -> (y (B, S,
    h), the most negative in-chunk cumulative log decay). `p`: `in_proj` (h,
    2 H P + 2 K + H: z, then xBC = [x | B | C], then dt), `conv` (taps, H P +
    2 K) and `conv_bias` (H P + 2 K,), `A_log`, `D`, `dt_bias` (H,), `norm`
    (H P,), `out_proj` (H P, h). No bias on either projection; the gate is
    applied BEFORE the norm (y <- rms(y * silu(z)) * norm)."""
    cd = x.dtype
    batch, seq, _ = x.shape
    inner = heads * head_dim
    channels = inner + 2 * state
    with scope("ssd_proj"):
        w = p["in_proj"].astype(cd)
        z = x @ w[:, :inner]
        xbc = x @ w[:, inner:inner + channels]
        dt = x @ w[:, inner + channels:]
    taps = p["conv"].shape[0]
    if conv_fuses(seq, channels, taps, cd):
        xbc = lm_kda.conv_and_norm(xbc, p["conv"], CONV_BAND, bias=p["conv_bias"], name="ssd_conv")
    else:
        xbc = lm_kda.short_conv(xbc, p["conv"], p["conv_bias"], "ssd_conv")
    xs, b, c = xbc[..., :inner], xbc[..., inner:inner + state], xbc[..., inner + state:]
    with scope("ssd_gate"):
        delta = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))  # (B, S, H)
        log_decay = -jnp.exp(p["A_log"].astype(jnp.float32)) * delta
    y, lowest = ssd_core(xs.reshape(batch, seq, heads, head_dim), delta, log_decay, b, c, p["D"], chunk)
    normed = gated_norm(y.reshape(batch, seq, inner), z, p["norm"], eps)
    with scope("ssd_proj"):
        return normed @ p["out_proj"].astype(cd), lowest


def gated_norm(y: Array, z: Array, gain: Array, eps: float) -> Array:
    """RMSNorm(y * SiLU(z)) * gain over the last axis (all heads' channels: one
    group), the gate applied BEFORE the norm, in float32; in y's dtype."""
    with scope("ssd_norm"):
        gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        normed = gated * lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + eps)
        return (normed * gain.astype(jnp.float32)).astype(y.dtype)
