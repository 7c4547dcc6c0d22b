"""Mamba-2's mixer, the state-space layer of `granitemoehybrid` (models/lm.py):
one input projection into a gate `z`, the convolved stream `xBC` and a step
size `dt` a head; a short causal convolution with a bias and SiLU over `xBC`;
the selective state-space recurrence over `heads` heads of `head_dim` channels,
whose input and output maps `B` and `C` (`state` channels) are ONE group
shared by every head; a gated RMSNorm over all heads' channels; the output
projection. Plain `jax.numpy`/`lax`, but for the convolution, which goes
through the short-convolution kernels of ops/lm_kda.py where they take it.

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

**What the backward keeps.** The chunk scan is a `custom_vjp` whose output,
the state at every chunk's start (in the compute dtype, as the matmuls read
it), carries the name `SSD_STATES_NAME`: the layer checkpoint of models/lm.py
saves it, so the backward's second run of a layer makes the in-chunk matrices
again and not the scan. They are made for all heads at once, under one
`jax.checkpoint` (its backward makes them again): at 8,192 positions a float32
(chunk x chunk) matrix a head and chunk is 512 MiB for 64 heads, and XLA never
holds it whole. Eight heads at a time, as KDA's plain form goes, declared
0.34 GiB MORE temporaries for the cell's step compiled for a v5e and stepped
2.5% slower on the chip (PERF.md).

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


def ssd_core(x: Array, delta: Array, log_decay: Array, b: Array, c: Array, d_skip: Array,
             chunk: int) -> tuple[Array, Array]:
    """The recurrence in its chunked form (module docstring). x (B, S, H, P)
    in the compute dtype; delta, log_decay (B, S, H) float32 (Delta and
    Delta A); b, c (B, S, K) in the compute dtype (ONE group, shared by the
    heads); d_skip (H,); `chunk` positions a chunk -> (y (B, S, H, P) in the compute dtype, the most
    negative in-chunk cumulative log decay: a float32 scalar, no gradient).
    Any length: the last chunk is filled with positions that neither decay
    nor write (Delta = 0, x = B = C = 0)."""
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
        xc, bc, cc = chunked(x), chunked(b), chunked(c)
        dx = (xc.astype(jnp.float32) * chunked(delta)[..., None]).astype(cd)  # Delta_s x_s
        last = cum[:, :, -1:, :]
        # each chunk's own contribution to the state at its end, and the decay of the state across it
        tail = jnp.exp(last - cum)  # <= 1
        own = jnp.einsum("bnshp,bnsk->nbhpk", (dx.astype(jnp.float32) * tail[..., None]).astype(cd), bc, **_F32)
        starts = _state_pass(own, jnp.moveaxis(jnp.exp(last[:, :, 0, :]), 1, 0), cd)  # (N, B, H, P, K)
        y = jnp.einsum("bntk,nbhpk->bnthp", cc, starts, **_F32) * jnp.exp(cum)[..., None]
        scores = jnp.einsum("bntk,bnsk->bnts", cc, bc, **_F32)  # C B^T: once a chunk, for every head
        y = y + jax.checkpoint(_in_chunk)(scores, cum, dx) + d_skip.astype(jnp.float32)[:, None] * xc.astype(jnp.float32)
        y = y.reshape(batch, n * chunk, heads, width)[:, :seq].astype(cd)
        return y, lax.stop_gradient(lowest)


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
