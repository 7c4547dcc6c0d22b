"""Kimi Delta Attention (KDA), the linear-attention mixer of `kimi_linear`
(models/lm.py): short causal convolutions on q, k, v; L2-normalised q and k; a
decay gate of one value a KEY CHANNEL; a write strength a head; the gated
delta rule over a `head_dim x head_dim` state a head; a sigmoid-gated RMSNorm
on the way out. Plain `jax.numpy`/`lax` on every platform, but for what
depends on a chunk alone, the scan over the chunks and the short convolutions
with their norms, which fused TPU kernels make where the shapes fit them
(below).

The recurrence, a head at a time (state S, key x value, S_0 = 0)::

    S'_t = Diag(alpha_t) S_{t-1};  S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T;  o_t = S_t^T q_t

is what the reference runs token by token (models/lm_reference.py). Here it
runs in its CHUNKED form (:func:`kda_core`), `KDA_CHUNK` positions at a time.
With `G_r` the in-chunk cumulative log decay and `u_j = beta_j (v_j - S'_j^T k_j)`:

    S_r = Diag(e^{G_r}) S_0 + sum_{j<=r} Diag(e^{G_r - G_j}) k_j u_j^T
    (I + Diag(beta) A) U = Diag(beta) (V - (K e^G) S_0),  A_rj = sum_c k_rc k_jc e^{G_rc - G_jc}, j < r
    O = (Q e^G) S_0 + B U,                                 B_rj = sum_c q_rc k_jc e^{G_rc - G_jc}, j <= r
    S_C = Diag(e^{G_C}) S_0 + (K e^{G_C - G})^T U

so everything that depends on the chunk alone (A, B, the unit-triangular
solve) is computed for ALL chunks at once, and one scan over the chunks
carries the state through three small matmuls a step.

**No gate is clamped.** Every decayed product above has `r >= j`, so its
factor `e^{G_r - G_j}` is at most 1; but written as `(k_r e^{G_r}) . (k_j
e^{-G_j})`, a plain matmul, it overflows: `-G` passes 88, float32's `exp`
limit, inside one chunk of 64 for decays a fresh model already has (`exp(A_log)`
up to 16, `softplus` up to 0.1 and more). `_decayed_scores` forms the
products from DIFFERENCES: rows are cut into sub-blocks of `KDA_SUBCHUNK`; a
pair of rows in different sub-blocks meets through the later sub-block's first
row as the reference (both factors `e^{G_r - G_ref}`, `e^{G_ref - G_j}` are at
most 1, and their product is a matmul); a pair inside one sub-block is
multiplied out channel by channel with `e^{G_r - G_j}` itself. Both are exact
for any gate. `e^{G_r}` alone (the state's way into the chunk) only ever
underflows, to the 0 that its true value rounds to.

**Two lowerings of the in-chunk work** (PR 36). `_plain_operands` is the form
above in `lax`, `KDA_HEAD_GROUP` heads at a time: every other platform's path,
a short or ragged sequence's, and the kernels' oracle in the tests.
`_fused_operands` is the same function as two Pallas/Mosaic kernels
(ops/lm_kda_kernels.py) under one `custom_vjp`: a forward kernel that writes
the scan's six operands from q, k, v, g, beta as `kda_attention` holds them,
every intermediate in VMEM, and a backward kernel that makes A, B and the
inverse again in VMEM and returns dq, dk, dv, dg, dbeta. `kda_core` takes it
where `fuses` says the shapes fit (whole chunks, heads of whole 128-lane bands,
bfloat16) AND the step is lowered for a TPU (`lax.platform_dependent`, as
`ops/lm.py:_by_lowering` does for attention); no option chooses. There every
pair of rows meets through a reference row, by halving (that module's
docstring): exact for any gate, like the sub-block scheme here. This module
imports no Pallas: the kernels' module comes in INSIDE `operands_fwd` /
`operands_bwd`, so while the `tpu` branch of a fitting site is traced and at
no other time (`models/lm.py` imports this module at module level, and every
runner imports `train/steps.py`: PERF.md, PR 28). `train.kda_fused_sites`
(train/steps.py) says how many KDA layers a step lowers that way.

**The short convolutions: one kernel each way.** `conv_and_norm` makes q,
k and v from their projections: `short_conv` (causal, SiLU) and for q and k
`l2_normalise` over each head. Where `conv_fuses` takes the shapes (whole
16-row tiles, heads of whole 128-lane bands, bfloat16) and the lowering is a
TPU's, that is `_fused_conv`, a `custom_vjp` over two kernels of
ops/lm_kda_kernels.py: the forward reads z once (with `CONV_HALO` rows of
history behind each tile) and writes the result once, the float32 sum, SiLU
and norm in VMEM; the backward reads z and the cotangent once (with their
halos), makes the pre-activation and the norm's statistics again, and writes
dz once and a float32 dw a tile. The backward keeps z and the filter alone.
Its kernels come in inside `conv_fwd` / `conv_bwd`, as above;
`train.kda_conv_fused_sites` says how many KDA layers a step lowers that way.

**Two lowerings of the scan.** `_state_scan` is the scan in plain `lax`: a
`lax.scan` over the chunks, all heads together, its output chunk-leading (N,
B, H, C, D) and transposed to (B, S, H, D) after it: every other platform's
path, any shape the kernels do not take, and the kernels' oracle in the tests.
`_fused_scan` is the same function as two Pallas/Mosaic kernels
(ops/lm_kda_kernels.py `scan_fwd_call` / `scan_bwd_call`) under one
`custom_vjp`, taken at the shapes `fuses` takes (the in-chunk kernels' own)
where the lowering is a TPU's, as `_fused_operands` is. A program instance of
the forward walks a block of chunks of a few heads of one sequence in order,
each head's state a float32 (K, V) VMEM scratch carried from block to block
(zeroed at the sequence's first), and writes the output straight into (B, S,
H x D), a head a band of lanes: the layout `kda_attention` and the gated norm
hold, so no transpose is left either way. The backward walks the blocks in
reverse through its index maps, the state's cotangent carried alike, reads the
output's cotangent in that layout and writes the six operands' cotangents in
their own shapes and dtypes. Per chunk both do what `_state_scan_fwd` /
`_state_scan_bwd` do, in the same precisions. Their module comes in inside
`scan_fwd` / `scan_bwd`, as above; `train.kda_scan_fused_sites` says how many
KDA layers a step lowers that way.

**What the backward keeps.** Either scan is a `custom_vjp`: its backward
walks the chunks in reverse with the cotangent of the state, from the scan's
operands and the state at each chunk's start; nothing else of the forward is
kept. Output and states carry names (`KDA_OUT_NAME`, `KDA_STATES_NAME`): the
layer checkpoint of models/lm.py saves those two, so the backward's second
run of a layer makes the in-chunk matrices again (they are the backward's
operands) and not the scan.

Precision: matmul operands in the compute dtype with float32 accumulation;
the decay `g`, its cumulative sums, every `exp`, the solve, the pseudo-values
`u` and the state in float32, whatever the compute dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.scopes import scope

Array = jax.Array

# Positions of one chunk (a scan step), and of one sub-block of its rows (whose pairs are multiplied out
# channel by channel): module constants, as ops.lm.ATTN_BLOCK is, not options.
KDA_CHUNK = 64
KDA_SUBCHUNK = 16
# Heads whose in-chunk matrices are made at once (kda_core).
KDA_HEAD_GROUP = 8
# The names (jax.ad_checkpoint.checkpoint_name) of what the scan's backward keeps beside its operands.
KDA_OUT_NAME = "kda_out"
KDA_STATES_NAME = "kda_states"
L2_EPS = 1e-6
# Rows of a short-convolution kernel's halo (`conv_fuses`): one bfloat16 sublane tile of a neighbouring tile's rows.
CONV_HALO = 16


def short_conv(z: Array, w: Array, bias: Array | None = None, name: str = "kda_conv") -> Array:
    """SiLU of a causal depthwise convolution, one filter a channel, zero
    history before the first position, and a bias a channel where one is given
    (Mamba-2's xBC convolution, ops/lm_mamba.py): c_t = SiLU(sum_i w_i
    z_{t - (k-1) + i} + b). z (B, S, D), w (k, D), bias (D,) -> (B, S, D),
    under the scope `name`."""
    with scope(name):
        taps, seq = w.shape[0], z.shape[1]
        # padded in the operand's dtype, summed in float32: 1.1 ms forward and 4.1 with the backward at the cell's
        # shape, where a float32 padded copy reads 2.5 and 7.7 and ONE depthwise lax convolution 3.4 and 10.1
        # (scripts/bench_kda.py; PERF.md, PR 33)
        padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
        acc = sum(padded[:, i:i + seq].astype(jnp.float32) * w[i].astype(jnp.float32) for i in range(taps))
        if bias is not None:
            acc = acc + bias.astype(jnp.float32)
        return jax.nn.silu(acc).astype(z.dtype)


def l2_normalise(x: Array, scale: float = 1.0) -> Array:
    """x / sqrt(sum x^2 + eps) over the last axis (a head's channels), in float32."""
    with scope("kda_norm"):
        x32 = x.astype(jnp.float32)
        return (x32 * lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + L2_EPS) * scale).astype(x.dtype)


def _plain_conv(z: Array, w: Array, width: int, scale: float | None, bias: Array | None = None,
                name: str = "kda_conv") -> Array:
    """`short_conv`, then unless `scale` is None `l2_normalise` over each head
    of `width` channels: z (B, S, H * width) -> the same shape."""
    # without a bias, the call KDA's sites have always made (tests swap `short_conv` for a two-argument one)
    out = short_conv(z, w) if bias is None else short_conv(z, w, bias, name)
    if scale is None:
        return out
    return l2_normalise(out.reshape(*z.shape[:2], -1, width), scale).reshape(z.shape)


def _plain_conv_bwd(z, w, ct, width, scale, bias=None, name="kda_conv"):
    """`_plain_conv`'s own vjp: (dz, dw), and dbias where there is a bias."""
    if bias is None:
        return jax.vjp(functools.partial(_plain_conv, width=width, scale=scale), z, w)[1](ct)
    return jax.vjp(lambda z_, w_, b_: _plain_conv(z_, w_, width, scale, b_, name), z, w, bias)[1](ct)


def conv_fuses(seq: int, width: int, taps: int, dtype) -> bool:
    """Whether the short-convolution kernels of ops/lm_kda_kernels.py take a
    site of this shape (the platform is the lowering's to decide): a sequence
    of whole `CONV_HALO`-row tiles (bfloat16's sublane tile; a neighbour's
    halo is one such tile), a filter whose history fits one, a head a whole
    number of 128-lane bands, bfloat16 operands."""
    return (seq % CONV_HALO == 0 and 1 <= taps <= CONV_HALO + 1 and width % 128 == 0
            and jnp.dtype(dtype) == jnp.bfloat16)


# Sites of one shape share one trace and one lowering of each form (the twelve convolutions of a step are three kinds).
@functools.partial(jax.jit, static_argnames=("width", "scale", "interpret", "name"))
def conv_fwd(z, w, width, scale, interpret: bool = False, bias=None, name: str = "kda_conv"):
    """`_plain_conv` as ONE fused kernel, instruction `<name>_fwd`: z read once
    with a halo of the rows before each tile, the float32 sum (and bias), SiLU
    and norm in VMEM, the result written once."""
    from . import lm_kda_kernels as kernels  # Pallas comes in HERE and nowhere earlier (module docstring)

    return kernels.conv_fwd_call(z, w, width, scale, L2_EPS, interpret, bias=bias, name=name)


@functools.partial(jax.jit, static_argnames=("width", "scale", "interpret", "name"))
def conv_bwd(z, w, ct, width, scale, interpret: bool = False, bias=None, name: str = "kda_conv"):
    """(dz, dw), and dbias where there is a bias, from the forward's operands
    and the cotangent of its result: the second kernel remakes the
    pre-activation and the norm's statistics in VMEM; XLA sums its tiles'
    float32 dw (and db)."""
    from . import lm_kda_kernels as kernels  # as in conv_fwd

    dz, dw, *db = kernels.conv_bwd_call(z, w, ct, width, scale, L2_EPS, interpret, bias=bias, name=name)
    dw = jnp.sum(dw, axis=(0, 1)).astype(w.dtype)
    return (dz, dw) if bias is None else (dz, dw, jnp.sum(db[0], axis=(0, 1, 2)).astype(bias.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 5))
def _fused_conv(z: Array, w: Array, width: int, scale: float | None, bias: Array | None = None,
                name: str = "kda_conv") -> Array:
    """`_plain_conv` at a shape the kernels take (`conv_fuses`): the two
    kernels where the step is lowered for a TPU, else the plain form and its
    own vjp (`lax.platform_dependent` decides at lowering). What the backward
    keeps is z, w (and the bias) alone."""
    return _fused_conv_fwd(z, w, width, scale, bias, name)[0]


# the other platforms' branches, shared by the sites as the kernels' are
_shared_plain_conv = jax.jit(_plain_conv, static_argnames=("width", "scale", "name"))
_shared_plain_conv_bwd = jax.jit(_plain_conv_bwd, static_argnames=("width", "scale", "name"))


def _fused_conv_fwd(z, w, width, scale, bias, name):
    statics = {"width": width, "scale": scale, "name": name}
    made = lax.platform_dependent(z, w, bias, tpu=lambda *a: conv_fwd(*a[:2], bias=a[2], **statics),
                                  default=lambda *a: _shared_plain_conv(*a[:2], bias=a[2], **statics))
    return made, (z, w, bias)


def _fused_conv_bwd(width, scale, name, kept, ct):
    statics = {"width": width, "scale": scale, "name": name}
    z, w, bias = kept
    with scope(name):
        grads = lax.platform_dependent(z, w, bias, ct, tpu=lambda *a: conv_bwd(*a[:2], a[3], bias=a[2], **statics),
                                       default=lambda *a: _shared_plain_conv_bwd(*a[:2], a[3], bias=a[2], **statics))
    return grads if bias is not None else (*grads, None)


_fused_conv.defvjp(_fused_conv_fwd, _fused_conv_bwd)


def conv_and_norm(z: Array, w: Array, width: int, scale: float | None = None, bias: Array | None = None,
                  name: str = "kda_conv") -> Array:
    """q, k or v of a KDA mixer from its projection: `short_conv` (causal,
    SiLU) and, unless `scale` is None, `l2_normalise` over each head of
    `width` channels, times `scale`. z (B, S, H * width), w (taps, H *
    width) -> (B, S, H * width) in z's dtype. With a `bias` (H * width,) it
    is added before the SiLU: Mamba-2's xBC convolution (ops/lm_mamba.py),
    whose sites go under the scope `name` (`ssd_conv`) and whose kernels are
    instructions `<name>_fwd` / `_bwd`. Where `conv_fuses` takes the shapes
    and the lowering is a TPU's, one kernel each way (`_fused_conv`); else the
    plain form."""
    if conv_fuses(z.shape[1], width, w.shape[0], z.dtype):
        with scope(name):
            return _fused_conv(z, w, width, scale, bias, name)
    return _plain_conv(z, w, width, scale, bias, name)


def _pairs_decay(big_g: Array) -> Array:
    """E[r, s, c] = e^{G_rc - G_sc} where s <= r, else 0. (..., c, K) -> (..., c, c, K)."""
    rows = big_g.shape[-2]
    keep = jnp.tril(jnp.ones((rows, rows), bool))[..., None]
    return jnp.exp(jnp.where(keep, big_g[..., :, None, :] - big_g[..., None, :, :], -jnp.inf))


@jax.custom_vjp
def _scores_inside(q: Array, k: Array, big_g: Array) -> tuple[Array, Array]:
    """For the rows of ONE sub-block, multiplied out channel by channel with
    ONE array of decays for both: A[r, s] = sum_c k_rc k_sc e^{G_rc - G_sc} for
    s < r and B[r, s] = sum_c q_rc k_sc e^{G_rc - G_sc} for s <= r. q, k, G
    (..., c, K) float32 -> two (..., c, c). The backward makes the factors
    again (nothing of size c x c x K is kept)."""
    weighted = k[..., None, :, :] * _pairs_decay(big_g)  # [r, s, c] = k_sc E_rsc
    below = jnp.tril(jnp.ones(big_g.shape[-2:-1] * 2, jnp.float32), -1)
    return jnp.sum(k[..., :, None, :] * weighted, axis=-1) * below, jnp.sum(q[..., :, None, :] * weighted, axis=-1)


def _scores_inside_fwd(q, k, big_g):
    return _scores_inside(q, k, big_g), (q, k, big_g)


def _scores_inside_bwd(kept, cts):
    q, k, big_g = kept
    below = jnp.tril(jnp.ones(big_g.shape[-2:-1] * 2, jnp.float32), -1)
    ct_a, ct_b = cts[0] * below, cts[1]
    decay = _pairs_decay(big_g)
    by_row = ct_a[..., None] * k[..., :, None, :] + ct_b[..., None] * q[..., :, None, :]  # [r, s, c]: what meets k_sc E_rsc
    d_k_cols = jnp.sum(by_row * decay, axis=-3)  # k as the COLUMN's factor
    rows = decay * k[..., None, :, :]
    d_k_rows = jnp.sum(ct_a[..., None] * rows, axis=-2)  # k as the ROW's factor (A alone)
    d_q = jnp.sum(ct_b[..., None] * rows, axis=-2)
    # d/dG_r adds what row r's products got, d/dG_s takes away what column s's got
    return d_q, d_k_rows + d_k_cols, q * d_q + k * d_k_rows - k * d_k_cols


_scores_inside.defvjp(_scores_inside_fwd, _scores_inside_bwd)


def _decayed_scores(q: Array, k: Array, big_g: Array, sub: int, cd) -> tuple[Array, Array]:
    """(A, B) of the module docstring for every chunk: q, k, G (..., C, K)
    float32 -> two (..., C, C) float32, A strictly lower triangular (k against
    k), B lower triangular with its diagonal (q against k)."""
    chunk, width = k.shape[-2:]
    lead = k.shape[:-2]
    blocks = chunk // sub
    # pairs of rows in DIFFERENT sub-blocks: the later sub-block's first row is the reference
    rows_a, rows_b = [jnp.zeros((*lead, sub, chunk), jnp.float32)], [jnp.zeros((*lead, sub, chunk), jnp.float32)]
    for i in range(1, blocks):
        lo, hi = i * sub, (i + 1) * sub
        ref = big_g[..., lo:lo + 1, :]
        later = jnp.exp(big_g[..., lo:hi, :] - ref)  # rows at or after the reference: exponent <= 0
        both = jnp.concatenate([k[..., lo:hi, :] * later, q[..., lo:hi, :] * later], axis=-2).astype(cd)
        earlier = (k[..., :lo, :] * jnp.exp(ref - big_g[..., :lo, :])).astype(cd)  # rows before it: exponent <= 0
        block = jnp.einsum("...rc,...sc->...rs", both, earlier, preferred_element_type=jnp.float32)
        pad = [(0, 0)] * len(lead) + [(0, 0), (0, chunk - lo)]
        rows_a.append(jnp.pad(block[..., :sub, :], pad))
        rows_b.append(jnp.pad(block[..., sub:, :], pad))
    # pairs inside one sub-block, on the block diagonal
    cut = lambda x: x.reshape(*lead, blocks, sub, width)  # noqa: E731
    on_diagonal = jnp.eye(blocks, dtype=jnp.float32)[:, None, :, None]

    def placed(inside):  # (..., blocks, sub, sub) -> (..., C, C), block i on the diagonal
        return (inside[..., :, :, None, :] * on_diagonal).reshape(*lead, chunk, chunk)

    inside_a, inside_b = _scores_inside(cut(q), cut(k), cut(big_g))
    return jnp.concatenate(rows_a, axis=-2) + placed(inside_a), jnp.concatenate(rows_b, axis=-2) + placed(inside_b)


@jax.custom_vjp
def _state_scan(qg: Array, b: Array, w: Array, u0: Array, kh: Array, gamma: Array) -> Array:
    """The chunks in order, state carried: per chunk U = u0 - w S; O = qg S +
    b U; S <- gamma S + kh^T U. Operands chunk-leading, (N, B, H, ...): qg, w,
    kh (.., C, K) and b (.., C, C) in the compute dtype, u0 (.., C, V) and
    gamma (.., K) float32 -> O (N, B, H, C, V) in the compute dtype."""
    return _state_scan_fwd(qg, b, w, u0, kh, gamma)[0]


def _pseudo_values(w, u0, state):
    return u0 - jnp.einsum("bhck,bhkv->bhcv", w, state.astype(w.dtype), preferred_element_type=jnp.float32)


def _state_scan_fwd(qg, b, w, u0, kh, gamma):
    from jax.ad_checkpoint import checkpoint_name  # not an attribute of `jax`; an alias module of what `import jax` loaded

    cd = qg.dtype

    def chunk(state, xs):
        qg_n, b_n, w_n, u0_n, kh_n, gamma_n = xs
        u = _pseudo_values(w_n, u0_n, state).astype(cd)
        out = (jnp.einsum("bhck,bhkv->bhcv", qg_n, state.astype(cd), preferred_element_type=jnp.float32)
               + jnp.einsum("bhcs,bhsv->bhcv", b_n, u, preferred_element_type=jnp.float32))
        new = gamma_n[..., None] * state + jnp.einsum("bhck,bhcv->bhkv", kh_n, u, preferred_element_type=jnp.float32)
        # the state at the chunk's START is what the backward reads, and in the compute dtype, as this forward's
        # matmuls read it (float32 is the CARRY's precision; kept in it, the states of a layer are 0.5 GiB)
        return new, (out.astype(cd), state.astype(cd))

    _, batch, heads, _, width = qg.shape
    start = jnp.zeros((batch, heads, width, u0.shape[-1]), jnp.float32)
    _, (out, states) = lax.scan(chunk, start, (qg, b, w, u0, kh, gamma))
    out, states = checkpoint_name(out, KDA_OUT_NAME), checkpoint_name(states, KDA_STATES_NAME)
    return out, (qg, b, w, u0, kh, gamma, states)


def _state_scan_bwd(kept, ct):
    qg, b, w, u0, kh, gamma, states = kept
    cd = qg.dtype
    f32 = {"preferred_element_type": jnp.float32}

    def chunk(d_state, xs):  # d_state: the cotangent of the state at the chunk's END
        qg_n, b_n, w_n, u0_n, kh_n, gamma_n, state, d_out = xs
        s, ds, do = state, d_state.astype(cd), d_out.astype(cd)
        u = _pseudo_values(w_n, u0_n, s).astype(cd)
        du = jnp.einsum("bhcs,bhcv->bhsv", b_n, do, **f32) + jnp.einsum("bhck,bhkv->bhcv", kh_n, ds, **f32)
        duc = du.astype(cd)
        before = (gamma_n[..., None] * d_state + jnp.einsum("bhck,bhcv->bhkv", qg_n, do, **f32)
                  - jnp.einsum("bhck,bhcv->bhkv", w_n, duc, **f32))
        grads = (jnp.einsum("bhcv,bhkv->bhck", do, s, **f32).astype(cd),      # qg
                 jnp.einsum("bhcv,bhsv->bhcs", do, u, **f32).astype(cd),      # b
                 (-jnp.einsum("bhcv,bhkv->bhck", duc, s, **f32)).astype(cd),  # w
                 du,                                                          # u0
                 jnp.einsum("bhcv,bhkv->bhck", u, ds, **f32).astype(cd),      # kh
                 jnp.sum(s.astype(jnp.float32) * d_state, axis=-1))           # gamma
        return before, grads

    _, grads = lax.scan(chunk, jnp.zeros(states.shape[1:], jnp.float32), (qg, b, w, u0, kh, gamma, states, ct), reverse=True)
    return grads


_state_scan.defvjp(_state_scan_fwd, _state_scan_bwd)


def _chunk_operands(q: Array, k: Array, v: Array, g: Array, beta: Array):
    """What depends on a chunk alone, for every chunk of some heads at once:
    q, k, v (B, h, N, C, D) in the compute dtype, g alike in float32, beta (B,
    h, N, C, 1) float32 -> (`_state_scan`'s six operands, chunk axis still
    third; the most negative in-chunk cumulative log decay)."""
    cd = q.dtype
    chunk, width = k.shape[-2:]
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    big_g = jnp.cumsum(g, axis=-2)
    a, b = _decayed_scores(qf, kf, big_g, min(KDA_SUBCHUNK, chunk), cd)
    into = jnp.exp(big_g)  # the state's way into the chunk: underflows to the 0 its true value rounds to
    # XLA's own solve (a loop over the chunk's rows on the chip, 2.1 ms a head group a call). The same solve as
    # matmuls over 16 x 16 blocks, each inverted a row at a time, read 5.3 ms and doubled the backward: tiles of
    # 16 x 16 float32 waste seven eighths of every vector register (scripts/bench_kda.py; PERF.md, PR 33)
    solved = lax.linalg.triangular_solve(
        jnp.eye(chunk, dtype=jnp.float32) + beta * a, beta * jnp.concatenate([kf * into, vf], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    last = big_g[..., -1:, :]
    return ((qf * into).astype(cd), b.astype(cd), solved[..., :width].astype(cd), solved[..., width:],
            (kf * jnp.exp(last - big_g)).astype(cd), jnp.exp(last[..., 0, :])), jnp.min(big_g)


def _plain_operands(q: Array, k: Array, v: Array, g: Array, beta: Array):
    """`_chunk_operands` for a whole layer in plain `lax`: q, k, v (B, S, H, D),
    g alike in float32, beta (B, S, H) float32 -> (`_state_scan`'s six operands,
    chunk-leading (N, B, H, ...); the most negative in-chunk cumulative log decay).
    Any length: the last chunk is filled with positions that neither decay nor
    write (g = 0, beta = 0, k = 0).

    The work goes `KDA_HEAD_GROUP` heads at a time, each group a
    `jax.checkpoint` (its backward makes the group's matrices again): at
    16,384 positions the float32 intermediates of all 32 heads at once, and
    their cotangents, are several GiB."""
    batch, seq, heads, _ = q.shape
    chunk = min(KDA_CHUNK, seq)
    fill = -seq % chunk
    n = (seq + fill) // chunk
    at_once = KDA_HEAD_GROUP if heads % KDA_HEAD_GROUP == 0 else heads
    groups = heads // at_once

    def grouped(x):  # (B, S, H, ...) -> (groups, B, h, N, C, ...)
        x = jnp.pad(x, [(0, 0), (0, fill)] + [(0, 0)] * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(batch, n, chunk, groups, at_once, *x.shape[3:]), (3, 4), (0, 2))

    by_group = (grouped(q), grouped(k), grouped(v), grouped(g), grouped(beta[..., None]))
    if groups == 1:
        operands, lowest = jax.tree.map(lambda x: x[None], _chunk_operands(*(x[0] for x in by_group)))
    else:
        operands, lowest = lax.map(lambda xs: jax.checkpoint(_chunk_operands)(*xs), by_group)
    # (groups, B, h, N, ...) -> (N, B, H, ...)
    return tuple(jnp.moveaxis(x, (0, 3), (2, 0)).reshape(n, batch, heads, *x.shape[4:]) for x in operands), jnp.min(lowest)


def _plain_operands_bwd(q, k, v, g, beta, cts):
    """`_plain_operands`' own vjp (a head group at a time, its matrices made again)."""
    made, vjp = jax.vjp(_plain_operands, q, k, v, g, beta)
    return vjp((cts, jnp.zeros_like(made[1])))


def fuses(seq: int, chunk: int, width: int, dtype) -> bool:
    """Whether the kernels of ops/lm_kda_kernels.py take a KDA site of this
    shape (the platform is the lowering's to decide, not this predicate's):
    whole chunks (the sequence a multiple of the chunk), a chunk that halves
    down to one row and fills bfloat16's 16-row tiles, a head a whole number
    of 128-lane bands, bfloat16 operands (the kernels' tiles and their
    matmuls' operands are laid out for it)."""
    return (seq % chunk == 0 and chunk % 16 == 0 and chunk & (chunk - 1) == 0 and width % 128 == 0
            and jnp.dtype(dtype) == jnp.bfloat16)


def operands_fwd(q, k, v, g, beta, interpret: bool = False):
    """`_plain_operands` as ONE fused kernel (ops/lm_kda_kernels.py): the same
    arguments, the same results, every intermediate in VMEM. The operands go in
    as `kda_attention` holds them, (B, S, H * D): a reshape, no copy."""
    from . import lm_kda_kernels as kernels  # Pallas comes in HERE and nowhere earlier (module docstring)

    batch, seq, heads, width = q.shape
    flat = lambda x: x.reshape(batch, seq, heads * width)  # noqa: E731
    *operands, gamma, lowest = kernels.fwd_call(flat(q), flat(k), flat(v), flat(g), beta, heads, min(KDA_CHUNK, seq), interpret)
    return (*operands, gamma.reshape(*gamma.shape[:3], width)), jnp.min(lowest)


def operands_bwd(q, k, v, g, beta, cts, interpret: bool = False):
    """(dq, dk, dv, dg, dbeta) from the forward's arguments and the cotangents
    of its six operands: the second kernel, which makes the chunk's matrices
    again in VMEM."""
    from . import lm_kda_kernels as kernels  # as in operands_fwd

    batch, seq, heads, width = q.shape
    flat = lambda x: x.reshape(batch, seq, heads * width)  # noqa: E731
    *others, d_gamma = cts
    grads = kernels.bwd_call(flat(q), flat(k), flat(v), flat(g), beta, (*others, d_gamma[..., None, :]), heads,
                             min(KDA_CHUNK, seq), interpret)
    return (*(x.reshape(q.shape) for x in grads[:4]), grads[4])


@jax.custom_vjp
def _fused_operands(q: Array, k: Array, v: Array, g: Array, beta: Array):
    """`_plain_operands` at a shape the kernels take (`fuses`): the two fused
    kernels where the step is lowered for a TPU, else the plain form and its
    own vjp. `lax.platform_dependent` decides at lowering, so a compile for a
    described chip from a CPU process takes the kernels and a CPU the plain form."""
    return _fused_operands_fwd(q, k, v, g, beta)[0]


def _fused_operands_fwd(q, k, v, g, beta):
    return lax.platform_dependent(q, k, v, g, beta, tpu=operands_fwd, default=_plain_operands), (q, k, v, g, beta)


def _fused_operands_bwd(kept, cts):
    with scope("kda_core"):
        return lax.platform_dependent(*kept, cts[0], tpu=operands_bwd, default=_plain_operands_bwd)


_fused_operands.defvjp(_fused_operands_fwd, _fused_operands_bwd)


def _by_position(out: Array) -> Array:
    """(N, B, H, C, V) -> (B, N * C, H * V): a chunk-leading output as `kda_attention` holds it."""
    n, batch, heads, chunk, values = out.shape
    return jnp.moveaxis(out, (0, 2), (1, 3)).reshape(batch, n * chunk, heads * values)


def _plain_scan(qg, b, w, u0, kh, gamma):
    """`_state_scan_fwd` with its output laid out as the kernel writes it: (out (B, S, H * V), states)."""
    out, kept = _state_scan_fwd(qg, b, w, u0, kh, gamma)
    return _by_position(out), kept[-1]


def _plain_scan_bwd(qg, b, w, u0, kh, gamma, states, ct):
    """`_state_scan_bwd` from the cotangent of `_plain_scan`'s output."""
    n, batch, heads, chunk, values = u0.shape
    by_chunk = jnp.moveaxis(ct.reshape(batch, n, chunk, heads, values), (1, 3), (0, 2))
    return _state_scan_bwd((qg, b, w, u0, kh, gamma, states), by_chunk)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_fwd(qg, b, w, u0, kh, gamma, interpret: bool = False):
    """`_plain_scan` as ONE kernel (ops/lm_kda_kernels.py): the state carried
    in VMEM from chunk to chunk, the output written as (B, S, H * V)."""
    from . import lm_kda_kernels as kernels  # Pallas comes in HERE and nowhere earlier (module docstring)

    return kernels.scan_fwd_call(qg, b, w, u0, kh, gamma[..., None, :], interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_bwd(qg, b, w, u0, kh, gamma, states, ct, interpret: bool = False):
    """`_plain_scan_bwd` as ONE kernel: the chunks in reverse, the state's
    cotangent carried in VMEM; the six cotangents in the operands' shapes and dtypes."""
    from . import lm_kda_kernels as kernels  # as in scan_fwd

    *grads, d_gamma = kernels.scan_bwd_call(qg, b, w, u0, kh, gamma[..., None, :], states, ct, interpret)
    return (*grads, d_gamma.reshape(gamma.shape))


@jax.custom_vjp
def _fused_scan(qg: Array, b: Array, w: Array, u0: Array, kh: Array, gamma: Array) -> Array:
    """`_state_scan` at a shape the kernels take (`fuses`), its output as (B,
    S, H * V): the kernel pair where the step is lowered for a TPU, else the
    plain scan and its own backward (`lax.platform_dependent` decides at
    lowering). What the backward keeps is what `_state_scan`'s keeps, under
    the same names."""
    return _fused_scan_fwd(qg, b, w, u0, kh, gamma)[0]


def _fused_scan_fwd(qg, b, w, u0, kh, gamma):
    from jax.ad_checkpoint import checkpoint_name  # as in _state_scan_fwd

    operands = (qg, b, w, u0, kh, gamma)
    out, states = lax.platform_dependent(*operands, tpu=scan_fwd, default=_plain_scan)
    out, states = checkpoint_name(out, KDA_OUT_NAME), checkpoint_name(states, KDA_STATES_NAME)
    return out, (*operands, states)


def _fused_scan_bwd(kept, ct):
    with scope("kda_core"):
        return lax.platform_dependent(*kept, ct, tpu=scan_bwd, default=_plain_scan_bwd)


_fused_scan.defvjp(_fused_scan_fwd, _fused_scan_bwd)


def kda_core(q: Array, k: Array, v: Array, g: Array, beta: Array) -> tuple[Array, Array]:
    """The gated delta rule in its chunked form (module docstring). q (already
    scaled), k, v (B, S, H, D) in the compute dtype; g (B, S, H, D) float32,
    the LOG decay of each key channel (<= 0); beta (B, S, H) float32 ->
    (o (B, S, H, D), the most negative in-chunk cumulative log decay met: a
    float32 scalar, no gradient). Any length (`_plain_operands`).

    Where the shapes fit the kernels and the lowering is a TPU's (`fuses`),
    what depends on a chunk alone is made by the in-chunk kernels
    (`_fused_operands`) and the scan is the scan kernels (`_fused_scan`),
    whose output is already (B, S, H x D); else both are plain `lax`, the
    in-chunk work a head group at a time, the scan all heads together."""
    with scope("kda_core"):
        batch, seq, heads, width = q.shape
        chunk = min(KDA_CHUNK, seq)
        if chunk % min(KDA_SUBCHUNK, chunk):
            raise ValueError(f"a chunk of {chunk} positions is not a multiple of the sub-block {KDA_SUBCHUNK}")
        g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
        if fuses(seq, chunk, width, q.dtype):
            operands, lowest = _fused_operands(q, k, v, g, beta)
            return _fused_scan(*operands).reshape(q.shape), lax.stop_gradient(lowest)
        operands, lowest = _plain_operands(q, k, v, g, beta)
        out = _state_scan(*operands)  # (N, B, H, C, D)
        out = jnp.moveaxis(out, (0, 2), (1, 3)).reshape(batch, -1, heads, width)[:, :seq]
        return out, lax.stop_gradient(lowest)


def _banded_gated_norm(o: Array, gate: Array, gain: Array, heads: int, eps: float) -> Array:
    """The gated output norm of `kda_attention` on o, gate (B, S, H x D), a
    head a band of lanes, as the scan kernels write o: each head's mean square
    and its broadcast back to the band are products with the 0/1 (H x D, H)
    matrix of the bands, at float32 precision. Reshaped to (B, S, H, D) for a
    reduction over the last axis, the chip relays the whole float32 tensor out
    and back, forward and backward (PERF.md, section 6). -> float32 (B, S, H x D)."""
    width = o.shape[-1] // heads
    bands = jnp.repeat(jnp.eye(heads, dtype=jnp.float32), width, axis=0)
    o32 = o.astype(jnp.float32)
    mean_square = jnp.dot(jnp.square(o32), bands, precision=lax.Precision.HIGHEST) / width
    scale = jnp.dot(lax.rsqrt(mean_square + eps), bands.T, precision=lax.Precision.HIGHEST)
    return o32 * scale * jnp.tile(gain.astype(jnp.float32), heads) * jax.nn.sigmoid(gate.astype(jnp.float32))


def kda_attention(p: dict, x: Array, *, heads: int, head_dim: int, eps: float) -> tuple[Array, Array]:
    """One KDA mixer. x (B, S, h), the normed hidden state -> (y (B, S, h),
    the most negative in-chunk cumulative log decay). `p`: `q`, `k`, `v` (h,
    H D); `conv_q`, `conv_k`, `conv_v` (taps, H D); the decay gate `f_a` (h,
    D), `f_b` (D, H D), `A_log` (H,), `dt_bias` (H D,); the write strength `b`
    (h, H); the output gate `g_a` (h, D), `g_b` (D, H D); `o_norm` (D,), one
    gain shared by the heads; `o` (H D, h). No bias anywhere."""
    cd = x.dtype
    batch, seq, _ = x.shape
    with scope("kda_proj"):
        q, k, v = (x @ p[name].astype(cd) for name in ("q", "k", "v"))
        decay = (x @ p["f_a"].astype(cd)) @ p["f_b"].astype(cd)
        write = x @ p["b"].astype(cd)
        gate = (x @ p["g_a"].astype(cd)) @ p["g_b"].astype(cd)
    by_head = lambda z: z.reshape(batch, seq, heads, head_dim)  # noqa: E731
    q, k, v = (by_head(conv_and_norm(z, p["conv_" + name], head_dim, scale))
               for name, z, scale in (("q", q, head_dim ** -0.5), ("k", k, 1.0), ("v", v, None)))
    with scope("kda_gate"):
        g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
            by_head(decay.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)))
        beta = jax.nn.sigmoid(write.astype(jnp.float32))
    out, lowest = kda_core(q, k, v, g, beta)
    with scope("kda_norm"):
        if fuses(seq, min(KDA_CHUNK, seq), head_dim, cd):  # the scan kernels' output, (B, S, H x D) as it lies
            gated = _banded_gated_norm(out.reshape(gate.shape), gate, p["o_norm"], heads, eps).astype(cd)
        else:
            o32 = out.astype(jnp.float32)
            normed = o32 * lax.rsqrt(jnp.mean(jnp.square(o32), axis=-1, keepdims=True) + eps) * p["o_norm"].astype(jnp.float32)
            gated = (normed * jax.nn.sigmoid(by_head(gate.astype(jnp.float32)))).astype(cd)
    with scope("kda_proj"):
        return gated.reshape(batch, seq, heads * head_dim) @ p["o"].astype(cd), lowest
