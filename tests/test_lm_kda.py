"""Kimi Delta Attention's chunked form (ops/lm_kda.py) against the recurrence it
must equal, token by token, on the CPU at small sizes: 2 sequences, 2 heads of
8 channels, lengths of 1, 2.5 and 8 chunks' worth, with decays a fresh model
has and with decays whose in-chunk product underflows float32.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from yet_another_mobilenet_series_tpu.ops import lm_kda

HEADS, WIDTH = 2, 8


def recurrence(q, k, v, g, beta):
    """S'_t = Diag(e^{g_t}) S_{t-1}; S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T; o_t = S_t^T q_t."""
    def one(q, k, v, g, beta):
        def token(state, xs):
            q_t, k_t, v_t, g_t, b_t = xs
            state = jnp.exp(g_t)[:, :, None] * state
            read = jnp.einsum("hkv,hk->hv", state, k_t)
            state = state + b_t[:, None, None] * k_t[:, :, None] * (v_t - read)[:, None, :]
            return state, jnp.einsum("hkv,hk->hv", state, q_t)

        return jax.lax.scan(token, jnp.zeros((q.shape[1], q.shape[2], v.shape[2])), (q, k, v, g, beta))[1]

    with jax.default_matmul_precision("highest"):
        return jax.vmap(one)(q, k, v, g, beta)


def operands(seq: int, hard: bool, seed: int = 0, width: int = WIDTH, dtype=jnp.float32):
    """q (scaled), k L2-normalised, v (in `dtype`), log decays, write strengths.
    `hard`: decays up to 12 a position (a chunk of 64 passes -88 many times
    over) and `beta` exactly 0 at every third position, exactly 1 at every fifth."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (2, seq, HEADS, width)
    q, k, v = (jax.random.normal(key, shape) for key in ks[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * width ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.exp(jax.random.uniform(ks[3], shape, minval=math.log(1e-3), maxval=math.log(12.0 if hard else 0.5)))
    beta = jax.nn.sigmoid(2 * jax.random.normal(ks[4], shape[:3]))
    if hard:
        beta = beta.at[:, ::3].set(0.0).at[:, 1::5].set(1.0)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def value_and_grads(fn, args, ct):
    return jax.jit(jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * ct), argnums=(0, 1, 2, 3, 4), has_aux=False))(*args)


def worst(got, want):
    return max(float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)) for a, b in zip(got, want))


@pytest.mark.parametrize("hard", [False, True], ids=["fresh_gates", "gates_that_underflow"])
@pytest.mark.parametrize("seq", [64, 160, 512], ids=["1_chunk", "2.5_chunks", "8_chunks"])
def test_the_chunked_form_equals_the_recurrence_in_float32(seq, hard):
    """Output and every gradient (q, k, v, the log decay, beta), to float32
    rounding. With `hard` gates the in-chunk cumulative log decay passes -88
    (e^88 is float32's largest), where a product of `k e^G` and `k e^-G`
    overflows: the chunked form must stay finite AND exact, for `beta` at 0
    and at 1 too. (The cumulative sum itself rounds at ulp(160) = 1.5e-5,
    which the recurrence never forms: hence 2e-5 there, 2e-6 else.)"""
    assert lm_kda.KDA_CHUNK == 64 and lm_kda.KDA_SUBCHUNK == 16
    args = operands(seq, hard)
    ct = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    out, lowest = jax.jit(lm_kda.kda_core)(*args)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert (float(lowest) < -88.0) == hard
    want = recurrence(*args)
    limit = 2e-5 if hard else 2e-6
    assert worst([out], [want]) < limit
    _, got = value_and_grads(lambda *a: lm_kda.kda_core(*a)[0], args, ct)
    _, ref = value_and_grads(recurrence, args, ct)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in got)
    assert worst(got, ref) < limit


@pytest.mark.parametrize("chunk, sub, group", [(16, 4, 1), (32, 32, 2), (64, 8, 2)])
def test_chunk_sub_block_and_head_group_change_no_number(monkeypatch, chunk, sub, group):
    """The three module constants are how the work is cut, not what is computed."""
    args = operands(96, True, seed=3)
    want = recurrence(*args)
    monkeypatch.setattr(lm_kda, "KDA_CHUNK", chunk)
    monkeypatch.setattr(lm_kda, "KDA_SUBCHUNK", sub)
    monkeypatch.setattr(lm_kda, "KDA_HEAD_GROUP", group)
    out, _ = jax.jit(lambda *a: lm_kda.kda_core(*a))(*args)
    assert worst([out], [want]) < 2e-5


def test_bfloat16_is_within_its_tolerance_and_a_lower_precision_is_not():
    """bfloat16 operands (float32 decays, solve, state) against the float32
    recurrence: output and every gradient within 3% of the largest entry;
    operands rounded to float8_e4m3fn, the nearest precision below, are not."""
    args = operands(160, False, seed=1)
    ct = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    out_ref, ref = value_and_grads(recurrence, args, ct)

    def deviation(dtype):
        q, k, v, g, beta = args
        low = tuple(x.astype(dtype).astype(jnp.bfloat16) for x in (q, k, v))
        _, got = value_and_grads(lambda q_, k_, v_, g_, b_: lm_kda.kda_core(q_, k_, v_, g_, b_)[0].astype(jnp.float32),
                                 (*low, g, beta), ct.astype(jnp.float32))
        return worst([x.astype(jnp.float32) for x in got], ref)

    assert deviation(jnp.bfloat16) < 3e-2
    assert deviation(jnp.float8_e4m3fn) > 3e-2


def test_the_short_convolution_is_causal_with_zero_history():
    """Position t reads positions t-3..t and nothing after; before the
    document's first position it reads zeros; one filter a channel."""
    z = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    out = lm_kda.short_conv(z, w)
    np.testing.assert_allclose(out[0, 0], jax.nn.silu(w[3] * z[0, 0]), rtol=1e-6)
    np.testing.assert_allclose(out[0, 5], jax.nn.silu(sum(w[i] * z[0, 2 + i] for i in range(4))), rtol=1e-6)
    moved = lm_kda.short_conv(z.at[0, 7].add(1.0), w)
    assert bool(jnp.all(moved[0, :7] == out[0, :7]))  # nothing before 7 saw it
    assert bool(jnp.all(moved[0, 7:11] != out[0, 7:11])) and bool(jnp.all(moved[0, 11:] == out[0, 11:]))
    only = lm_kda.short_conv(z.at[0, :, 2].add(1.0), w)
    assert bool(jnp.all(only[..., [0, 1, 3, 4, 5]] == out[..., [0, 1, 3, 4, 5]]))  # depthwise


def test_the_mixer_equals_the_references_recurrence(monkeypatch):
    """ops.lm_kda.kda_attention against models/lm_reference.py `kda`, one
    sequence at a time: conv, L2 norms, gates, core, gated norm, projections;
    output and every parameter's gradient."""
    from yet_another_mobilenet_series_tpu.config import LinearAttnConfig, LMConfig, ModelConfig
    from yet_another_mobilenet_series_tpu.models import get_model, lm_reference as ref

    monkeypatch.setattr(lm_kda, "KDA_CHUNK", 8)
    monkeypatch.setattr(lm_kda, "KDA_SUBCHUNK", 4)
    lm = LMConfig(hidden_size=32, num_hidden_layers=1, q_lora_rank=None, num_nextn_predict_layers=0, init_std=0.2,
                  linear_attn_config=LinearAttnConfig(kda_layers=(1,), head_dim=WIDTH, num_heads=HEADS), seq_len=20)
    p = get_model(ModelConfig(arch="kimi_linear", num_classes=16, lm=lm)).init(jax.random.PRNGKey(2))[0]["layer_0"]["kda"]
    assert sum(x.size for x in jax.tree.leaves(p)) == (4 * 32 * 16 + 3 * 4 * 16 + 2 * (32 * 8 + 8 * 16) + HEADS + 16
                                                       + 32 * HEADS + WIDTH)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, 32))
    ct = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def program(p, x):
        return jnp.sum(lm_kda.kda_attention(p, x, heads=HEADS, head_dim=WIDTH, eps=1e-5)[0] * ct)

    def reference(p, x):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(jnp.stack([ref.kda(p, row, ref.dims_of(lm)) for row in x]) * ct)

    got, want = (jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(p, x) for f in (program, reference))
    assert abs(float(got[0]) - float(want[0])) < 1e-4 * abs(float(want[0]))
    assert worst(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])) < 2e-5
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree.leaves(got[1]))


# ---- the fused kernels (ops/lm_kda_kernels.py, PR 36) in Pallas interpret mode, against the plain form --------------
KERNEL_WIDTH = 128  # a head is a whole band of lanes, or the kernels do not take the site


def kernel_operands(seq: int, hard: bool, seed: int = 0):
    """`operands` at the kernels' shapes and dtypes: 2 sequences, 2 heads of 128, q, k, v in bfloat16."""
    return operands(seq, hard, seed, width=KERNEL_WIDTH, dtype=jnp.bfloat16)


def deviations(got, want):
    """Each result's largest deviation, as a share of the wanted one's largest entry (on the host: no op to compile)."""
    got, want = ([np.asarray(x).astype(np.float32) for x in xs] for xs in (got, want))
    return [float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30)) for a, b in zip(got, want)]


def cotangents(like):
    return tuple(jax.random.normal(jax.random.PRNGKey(7 + i), x.shape).astype(x.dtype) for i, x in enumerate(like))


# the largest deviation allowed, against each result's largest entry: an ulp of bfloat16 at the top of its range is 2^-8 for the
# results in the compute dtype (qg, b, w, kh; dq, dk, dv); the float32 ones (u0, gamma; dg, dbeta) inherit the bfloat16
# operands of A and B, whose pairs inside a 16-row sub-block the plain form multiplies out in float32
FWD_LIMITS = (1e-2, 1e-2, 1e-2, 2e-3, 1e-2, 1e-5)
BWD_LIMITS = (1.5e-2, 1.5e-2, 1.5e-2, 1e-2, 2e-3)
REGIMES = pytest.mark.parametrize("hard", [False, True], ids=["fresh_gates", "gates_that_underflow"])
LENGTHS = pytest.mark.parametrize("seq", [64, 512], ids=["1_chunk", "8_chunks"])


@REGIMES
@LENGTHS
def test_the_forward_kernel_makes_the_plain_forms_six_operands_and_its_lowest_decay(seq, hard):
    """`operands_fwd` (one fused kernel, every intermediate in VMEM) against
    `_plain_operands` (head groups, sub-blocks, XLA's triangular solve): qg, b,
    w, u0, kh, gamma in the scan's chunk-leading shapes, and the most negative
    in-chunk cumulative log decay, past -88 in the hard regime."""
    args = kernel_operands(seq, hard)
    assert lm_kda.fuses(seq, lm_kda.KDA_CHUNK, KERNEL_WIDTH, args[0].dtype)
    got, lowest = jax.jit(lambda *a: lm_kda.operands_fwd(*a, interpret=True))(*args)
    want, want_lowest = jax.jit(lm_kda._plain_operands)(*args)
    assert [x.shape for x in got] == [x.shape for x in want] and [x.dtype for x in got] == [x.dtype for x in want]
    assert all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32)))) for x in got)
    assert all(d < limit for d, limit in zip(deviations(got, want), FWD_LIMITS)), deviations(got, want)
    assert (float(lowest) < -88.0) == hard and abs(float(lowest) - float(want_lowest)) < 1e-4 * abs(float(want_lowest))


@REGIMES
@LENGTHS
def test_the_backward_kernel_equals_the_plain_forms_vjp(seq, hard):
    """`operands_bwd` (the second kernel: A, B and the inverse made again in
    VMEM) against `jax.vjp` of the plain form: dq, dk, dv, dg, dbeta from the
    same cotangents of the six operands, finite where the decays underflow."""
    args = kernel_operands(seq, hard, seed=1)
    cts = cotangents(jax.eval_shape(lm_kda._plain_operands, *args)[0])
    got = jax.jit(lambda *a: lm_kda.operands_bwd(*a, cts, interpret=True))(*args)
    want = jax.jit(lambda *a: lm_kda._plain_operands_bwd(*a, cts))(*args)
    assert [(x.shape, x.dtype) for x in got] == [(x.shape, x.dtype) for x in args]
    assert all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32)))) for x in got)
    assert all(d < limit for d, limit in zip(deviations(got, want), BWD_LIMITS)), deviations(got, want)


@REGIMES
def test_a_clamped_gate_in_the_kernel_path_is_caught_where_the_decays_underflow(monkeypatch, hard):
    """Plant what a form that multiplies `k e^G` by `k e^-G` would need: log
    decays held above -88 / chunk, so that no in-chunk sum passes float32's
    `exp`. At fresh gates (g >= -0.5) the clamp touches nothing and both
    comparisons stand; at gates that underflow both fail."""
    from yet_another_mobilenet_series_tpu.ops import lm_kda_kernels as kernels

    decay_sums = kernels._decay_sums
    monkeypatch.setattr(kernels, "_decay_sums", lambda sums_ref, pattern, g, chunk: decay_sums(
        sums_ref, pattern, jnp.maximum(g, -88.0 / lm_kda.KDA_CHUNK), chunk))
    args = kernel_operands(128, hard, seed=2)
    want, _ = jax.jit(lm_kda._plain_operands)(*args)
    cts = cotangents(want)
    got, _ = jax.jit(lambda *a: lm_kda.operands_fwd(*a, interpret=True))(*args)
    grads = jax.jit(lambda *a: lm_kda.operands_bwd(*a, cts, interpret=True))(*args)
    want_grads = jax.jit(lambda *a: lm_kda._plain_operands_bwd(*a, cts))(*args)
    fwd_holds = all(d < limit for d, limit in zip(deviations(got, want), FWD_LIMITS))
    bwd_holds = all(d < limit for d, limit in zip(deviations(grads, want_grads), BWD_LIMITS))
    assert (fwd_holds, bwd_holds) == (not hard, not hard), (deviations(got, want), deviations(grads, want_grads))


@pytest.mark.parametrize("seq, dtype, width, takes", [
    (128, jnp.bfloat16, 128, True), (96, jnp.bfloat16, 128, False), (128, jnp.float32, 128, False), (128, jnp.bfloat16, 64, False)],
    ids=["fits", "sequence_not_whole_chunks", "float32_operands", "head_dim_64"])
def test_the_dispatch_takes_the_kernels_form_by_the_shapes_alone(monkeypatch, seq, dtype, width, takes):
    """`kda_core` asks `fuses` (whole chunks, a head a whole number of 128-lane
    bands, bfloat16) and nothing else, for the in-chunk work and the scan
    alike; what does not fit takes the plain forms, head groups and all, with
    no `custom_vjp` and no platform switch around them."""
    assert lm_kda.fuses(seq, lm_kda.KDA_CHUNK, width, dtype) == takes
    asked = {"operands": [], "scan": []}
    fused, fused_scan = lm_kda._fused_operands, lm_kda._fused_scan
    monkeypatch.setattr(lm_kda, "_fused_operands", lambda *a: asked["operands"].append(a[0].shape) or fused(*a))
    monkeypatch.setattr(lm_kda, "_fused_scan", lambda *a: asked["scan"].append(a[0].shape) or fused_scan(*a))
    shape = (1, seq, HEADS, width)
    q, k, v = (jax.ShapeDtypeStruct(shape, dtype) for _ in range(3))
    jaxpr = jax.make_jaxpr(lm_kda.kda_core)(q, k, v, jax.ShapeDtypeStruct(shape, jnp.float32),
                                            jax.ShapeDtypeStruct(shape[:3], jnp.float32))
    assert bool(asked["operands"]) == bool(asked["scan"]) == takes
    assert str(jaxpr).count("platform_index") == (2 if takes else 0)


def test_on_a_cpu_the_fitting_shape_runs_the_plain_form_and_its_vjp(monkeypatch):
    """The kernels' shapes lowered for a CPU: `lax.platform_dependent` keeps
    the plain form and, in the backward, the plain form's own vjp, so output
    and every gradient equal those of a dispatch that refuses the shape."""
    args = kernel_operands(128, True, seed=4)
    ct = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    run = lambda: value_and_grads(lambda *a: lm_kda.kda_core(*a)[0].astype(jnp.float32), args, ct)  # noqa: E731
    assert "pallas_call" not in str(jax.jit(lm_kda.kda_core).lower(*args).compile().as_text())
    through_the_dispatch = run()
    monkeypatch.setattr(lm_kda, "fuses", lambda *a: False)
    plain = run()
    assert all(bool(jnp.all(a == b)) for a, b in zip(jax.tree.leaves(through_the_dispatch), jax.tree.leaves(plain)))


# ---- the scan's kernels (ops/lm_kda_kernels.py, `scan_*`) in Pallas interpret mode, against the plain scan ----------------
# 8 chunks of 64, two a program instance: the state crosses three grid blocks of each sequence; one head or both a program
SCAN_CUTS = pytest.mark.parametrize("hard, heads_at_once", [(False, 2), (True, 1)],
                                    ids=["fresh_gates_2_heads_a_program", "gates_that_underflow_1_head_a_program"])
# out, states; dqg, db, dw (the compute dtype: bfloat16's ulp at the top of its range is 2^-8), du0, dgamma (float32)
SCAN_FWD_LIMITS = (1e-2, 1e-2)
SCAN_BWD_LIMITS = (1e-2, 1e-2, 1e-2, 1e-4, 1e-2, 1e-4)


@functools.partial(jax.jit, static_argnums=(0, 1))
def scan_operands(hard: bool, seed: int):
    """The scan's six operands for 8 chunks of 64, 2 sequences, 2 heads of 128, in the in-chunk form's shapes and
    dtypes: qg, w, kh and a lower-triangular b in bfloat16, u0 and the chunks' decays gamma in float32. gamma in
    [0.9, 1) (a state that lasts), or `hard`: at most e^-20 and exactly 0 in every third chunk."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (8, 2, HEADS, lm_kda.KDA_CHUNK, KERNEL_WIDTH)
    qg, w, kh = (0.1 * jax.random.normal(key, shape) for key in ks[:3])
    b = jnp.tril(0.1 * jax.random.normal(ks[3], shape[:-1] + shape[-2:-1]))
    u0 = jax.random.normal(ks[4], shape)
    gamma = (jnp.exp(-jax.random.uniform(ks[5], shape[:3] + shape[-1:], minval=20.0, maxval=200.0)).at[::3].set(0.0) if hard
             else jax.random.uniform(ks[5], shape[:3] + shape[-1:], minval=0.9, maxval=1.0))
    return (*(x.astype(jnp.bfloat16) for x in (qg, b, w)), u0, kh.astype(jnp.bfloat16), gamma)


def scan_deviations(hard, seed=0):
    """(forward, backward) deviations of the scan kernels from `_state_scan_fwd` / `_bwd` (`_plain_scan`,
    `_plain_scan_bwd`: the same with the output and its cotangent laid out as (B, S, H x D)): `out`, `states`, and
    the six cotangents from one of `out`'s."""
    operands = scan_operands(hard, seed)
    want = jax.jit(lm_kda._plain_scan)(*operands)
    got = jax.jit(lambda *a: lm_kda.scan_fwd(*a, interpret=True))(*operands)
    assert [(x.shape, x.dtype) for x in got] == [(x.shape, x.dtype) for x in want]
    ct = cotangents(want[:1])[0]
    want_grads = jax.jit(lm_kda._plain_scan_bwd)(*operands, want[1], ct)
    grads = jax.jit(lambda *a: lm_kda.scan_bwd(*a, interpret=True))(*operands, want[1], ct)
    assert [(x.shape, x.dtype) for x in grads] == [(x.shape, x.dtype) for x in operands]
    assert all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32)))) for x in (*got, *grads))
    return deviations(got, want), deviations(grads, want_grads)


@SCAN_CUTS
def test_the_scan_kernels_equal_the_plain_scan_and_its_backward(conv_kernels, hard, heads_at_once):
    """`scan_fwd` (ONE kernel: the state carried in VMEM across the chunks, the
    output written as (B, S, H x D)) against `_state_scan_fwd`: the output,
    reshaped, and the states at each chunk's start; `scan_bwd` (the chunks in
    reverse, the state's cotangent carried) against `_state_scan_bwd`: the six
    cotangents in the operands' shapes and dtypes. With decays a fresh model
    has and with decays that underflow (gamma near 0)."""
    conv_kernels(CHUNKS_AT_ONCE=2, SCAN_HEADS=heads_at_once)
    fwd, bwd = scan_deviations(hard)
    assert all(d < limit for d, limit in zip(fwd, SCAN_FWD_LIMITS)), fwd
    assert all(d < limit for d, limit in zip(bwd, SCAN_BWD_LIMITS)), bwd


def test_the_banded_gated_norm_equals_the_norm_over_each_head():
    """`_banded_gated_norm` (each head's mean square and its broadcast back as
    products with the 0/1 band matrix, on (B, S, H x D) as the scan kernels
    write it) against the norm over the last axis of (B, S, H, D), times the
    shared gain and the sigmoid gate: to float32 rounding."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    shape = (2, 16, HEADS, KERNEL_WIDTH)
    o = (jax.random.normal(ks[0], shape) * jnp.array([0.01, 30.0])[:, None]).astype(jnp.bfloat16)  # heads far apart in scale
    gate = jax.random.normal(ks[1], shape).astype(jnp.bfloat16)
    gain = 1.0 + 0.1 * jax.random.normal(ks[2], (KERNEL_WIDTH,))
    o32 = o.astype(jnp.float32)
    want = (o32 * jax.lax.rsqrt(jnp.mean(jnp.square(o32), axis=-1, keepdims=True) + 1e-5) * gain
            * jax.nn.sigmoid(gate.astype(jnp.float32)))
    flat = lambda x: x.reshape(*shape[:2], -1)  # noqa: E731
    got = jax.jit(lambda *a: lm_kda._banded_gated_norm(*a, HEADS, 1e-5))(flat(o), flat(gate), gain)
    assert got.shape == flat(want).shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(flat(want)), rtol=2e-6, atol=1e-6)


def _planted_reverse_walk_forward(specs):
    return lambda seq, chunk, heads, reverse: specs(seq, chunk, heads, False)


@pytest.mark.parametrize("fault", ["carry_zeroed_every_block", "reverse_walk_taken_forward"])
def test_a_scan_kernel_that_drops_its_carry_or_walks_the_wrong_way_is_refused(conv_kernels, fault):
    """The comparison above refuses a pair whose carry is zeroed at every grid
    block (right inside each block of chunks, wrong across them), and a
    backward whose blocks come in forward order (its chunks inside a block
    still from the last)."""
    from yet_another_mobilenet_series_tpu.ops import lm_kda_kernels as kernels

    planted = ({"_starts_a_sequence": lambda: True} if fault == "carry_zeroed_every_block"
               else {"_scan_specs": _planted_reverse_walk_forward(kernels._scan_specs)})
    conv_kernels(CHUNKS_AT_ONCE=2, **planted)
    fwd, bwd = scan_deviations(False, seed=5)
    holds = (all(d < limit for d, limit in zip(fwd, SCAN_FWD_LIMITS)), all(d < limit for d, limit in zip(bwd, SCAN_BWD_LIMITS)))
    assert holds == ((False, False) if fault == "carry_zeroed_every_block" else (True, False)), (fwd, bwd)


# ---- the short convolutions' kernels (ops/lm_kda_kernels.py, `conv_*`) in Pallas interpret mode, against the plain form ----
CONV_KINDS = pytest.mark.parametrize("scale", [KERNEL_WIDTH ** -0.5, 1.0, None], ids=["q_conv_and_l2", "k_conv_and_l2", "v_conv"])
# (batch, positions, heads, head dim, rows of a tile, lanes of a tile): several row tiles, several lane blocks, a head of two
# bands, a sequence shorter than the module's tile (one tile of 48 rows)
CONV_SHAPES = pytest.mark.parametrize("batch, seq, heads, width, rows, lanes", [
    (2, 96, 2, 128, 32, 128), (2, 64, 3, 128, 16, 384), (1, 64, 2, 256, 32, 512), (2, 48, 2, 128, 512, 512)],
    ids=["3_row_tiles_2_lane_blocks", "4_row_tiles_of_16", "head_of_two_bands", "one_short_tile"])
CONV_LIMITS = (1e-2, 1.5e-2, 5e-3)  # out, dz (bfloat16: ulp 2^-8 of the largest entry), dw (float32 sums of bfloat16 operands)


@pytest.fixture
def conv_kernels(monkeypatch):
    """ops/lm_kda_kernels.py with some of its names set for one test: `conv_fwd` / `conv_bwd` are jitted, so a
    trace made under the module's own names is dropped first, and the test's own traces after it."""
    from yet_another_mobilenet_series_tpu.ops import lm_kda_kernels as kernels

    def setting(**names):
        for name, value in names.items():
            monkeypatch.setattr(kernels, name, value)
        jax.clear_caches()

    yield setting
    jax.clear_caches()


def conv_operands(batch, seq, heads, width, seed=0):
    """z and the incoming cotangent (B, S, H * D) in bfloat16, a filter of 4 taps in float32 (as `kda_attention` holds them)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    z = jax.random.normal(ks[0], (batch, seq, heads * width)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[1], (4, heads * width)) * 0.5
    return z, w, jax.random.normal(ks[2], z.shape).astype(jnp.bfloat16)


def conv_deviations(width, scale, z, w, ct):
    """(out, dz, dw) of the kernel pair against the plain form and its vjp, each as a share of the plain's largest entry."""
    got = (jax.jit(lambda *a: lm_kda.conv_fwd(*a, width, scale, interpret=True))(z, w),
           *jax.jit(lambda *a: lm_kda.conv_bwd(*a, width, scale, interpret=True))(z, w, ct))
    want = (jax.jit(lambda *a: lm_kda._plain_conv(*a, width, scale))(z, w),
            *jax.jit(lambda *a: lm_kda._plain_conv_bwd(*a, width, scale))(z, w, ct))
    assert [(x.shape, x.dtype) for x in got] == [(x.shape, x.dtype) for x in want]
    assert all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32)))) for x in got)
    return deviations(got, want)


@CONV_KINDS
@CONV_SHAPES
def test_the_conv_kernels_equal_the_plain_convolution_norm_and_vjp(conv_kernels, scale, batch, seq, heads, width, rows, lanes):
    """`conv_fwd` (ONE kernel: causal conv, SiLU and, for q and k, each head's
    L2 norm) against `short_conv` then `l2_normalise`; `conv_bwd` (the second
    kernel: dz and the tiles' float32 dw) against the plain form's vjp. Every
    tile boundary is crossed both ways (the forward's history behind a tile,
    the backward's cotangent ahead of it), and the plain form's zero history
    before position 0 is the oracle's. Whole tiles only: `conv_fuses` takes no
    sequence whose tiles are not all full."""
    from yet_another_mobilenet_series_tpu.ops import lm_kda_kernels as kernels

    conv_kernels(CONV_ROWS=rows, CONV_LANES=lanes)
    assert lm_kda.conv_fuses(seq, width, 4, jnp.bfloat16)
    assert kernels.conv_cut(seq, heads * width, width) == (min(rows, seq), min(lanes, heads * width))
    devs = conv_deviations(width, scale, *conv_operands(batch, seq, heads, width))
    assert all(d < limit for d, limit in zip(devs, CONV_LIMITS)), devs


def _reads_one_row_ahead(x, back, rows):
    from jax.experimental.pallas import tpu as pltpu

    from yet_another_mobilenet_series_tpu.ops import lm_kda_kernels as kernels

    return pltpu.roll(x, (back - 1) % x.shape[0], 0)[kernels.CONV_HALO:kernels.CONV_HALO + rows]


def _drops_the_halo(ref, band, first_row):
    x = jnp.zeros((ref.shape[0], band.stop - band.start), jnp.float32)
    return x, first_row + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)


@pytest.mark.parametrize("fault, planted", [("_behind", _reads_one_row_ahead), ("_history", _drops_the_halo)],
                         ids=["reads_one_row_ahead", "drops_the_halo"])
def test_a_conv_kernel_that_peeks_ahead_or_drops_its_halo_is_refused(conv_kernels, fault, planted):
    """The comparison above refuses a forward that reads the position after
    its own (not causal), and a pair that loses the rows a tile takes from
    its neighbours (right inside every tile, wrong at every boundary)."""
    conv_kernels(CONV_ROWS=32, CONV_LANES=128, **{fault: planted})
    devs = conv_deviations(KERNEL_WIDTH, KERNEL_WIDTH ** -0.5, *conv_operands(2, 96, 2, KERNEL_WIDTH, seed=5))
    assert not all(d < limit for d, limit in zip(devs, CONV_LIMITS)), devs


@pytest.mark.parametrize("seq, dtype, width, takes", [
    (128, jnp.bfloat16, 128, True), (120, jnp.bfloat16, 128, False), (128, jnp.float32, 128, False), (128, jnp.bfloat16, 64, False)],
    ids=["fits", "sequence_not_whole_16_row_tiles", "float32_operands", "head_dim_64"])
def test_the_conv_dispatch_takes_the_kernels_by_the_shapes_alone(monkeypatch, seq, dtype, width, takes):
    """`conv_and_norm` asks `conv_fuses` (whole 16-row tiles, a head a whole
    number of 128-lane bands, bfloat16, a filter whose history fits a halo)
    and nothing else; what does not fit takes the plain form, with no
    `custom_vjp` and no platform switch around it."""
    assert lm_kda.conv_fuses(seq, width, 4, dtype) == takes
    assert not lm_kda.conv_fuses(128, 128, lm_kda.CONV_HALO + 2, jnp.bfloat16)  # a history longer than one halo
    asked = []
    fused = lm_kda._fused_conv
    monkeypatch.setattr(lm_kda, "_fused_conv", lambda *a: asked.append(a[0].shape) or fused(*a))
    z = jax.ShapeDtypeStruct((1, seq, HEADS * width), dtype)
    jaxpr = jax.make_jaxpr(lambda z_, w_: lm_kda.conv_and_norm(z_, w_, width, 0.5))(z, jax.ShapeDtypeStruct((4, HEADS * width), jnp.float32))
    assert bool(asked) == takes
    assert ("platform_index" in str(jaxpr)) == takes


@pytest.mark.parametrize("scale", [0.5, None], ids=["q_or_k", "v"])
def test_on_a_cpu_the_fitting_conv_runs_the_plain_form_and_its_vjp(monkeypatch, scale):
    """The conv kernels' shapes lowered for a CPU: `lax.platform_dependent`
    keeps the plain form and its own vjp, so output, dz and dw equal those of
    a dispatch that refuses the shape, to the bit."""
    z, w, ct = conv_operands(2, 64, HEADS, KERNEL_WIDTH, seed=6)
    run = lambda: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda z_, w_: jnp.sum(lm_kda.conv_and_norm(z_, w_, KERNEL_WIDTH, scale).astype(jnp.float32) * ct), (0, 1)))(z, w)
    assert "pallas_call" not in jax.jit(lambda *a: lm_kda.conv_and_norm(*a, KERNEL_WIDTH, scale)).lower(z, w).compile().as_text()
    through_the_dispatch = run()
    monkeypatch.setattr(lm_kda, "conv_fuses", lambda *a: False)
    plain = run()
    assert all(bool(jnp.all(a == b)) for a, b in zip(jax.tree.leaves(through_the_dispatch), jax.tree.leaves(plain)))


def _step_gauges(app, overrides, platform):
    from yet_another_mobilenet_series_tpu.config import parse_cli
    from yet_another_mobilenet_series_tpu.models import get_model
    from yet_another_mobilenet_series_tpu.obs.registry import get_registry
    from yet_another_mobilenet_series_tpu.train import optim, schedules, steps

    cfg = parse_cli([f"app:{app}", *overrides])
    net = get_model(cfg.model)
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 2, 10, 1)
    params = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))[0]
    steps.make_train_step(net, cfg, optim.make_optimizer(cfg.optim, lr_fn, params), lr_fn, platform=platform)
    return tuple(get_registry().gauge(name).value
                 for name in ("train.kda_sites", "train.kda_kept_sites", "train.kda_fused_sites", "train.kda_conv_fused_sites",
                              "train.kda_scan_fused_sites"))


@pytest.mark.parametrize("family, toy, cell", [("kimi", (4.0, 4.0, 0.0, 0.0, 0.0), (4.0, 4.0, 4.0, 4.0, 4.0)),
                                               ("glm", (0.0, 0.0, 0.0, 0.0, 0.0), (0.0, 0.0, 0.0, 0.0, 0.0)),
                                               ("ouro", (0.0, 0.0, 0.0, 0.0, 0.0), (0.0, 0.0, 0.0, 0.0, 0.0))])
def test_train_step_reports_how_many_kda_layers_the_kernels_take(family, toy, cell):
    """`train.kda_fused_sites`, `train.kda_conv_fused_sites` and
    `train.kda_scan_fused_sites` beside `train.kda_sites` and
    `train.kda_kept_sites`, set where the step is built, as
    `train.attn_fused_sites` is: the KDA layers whose shapes `fuses` (the
    in-chunk work, and the scan over the chunks) / `conv_fuses` (the three
    short convolutions) take when the step is lowered for a TPU, else 0. The
    toys (heads of 8 channels, float32) take the plain forms anywhere; kimi's
    cell's model reads 4 / 4 / 4 / 4 / 4 for a TPU and 4 / 4 / 0 / 0 / 0 for a
    CPU; GLM and Ouro hold no KDA layer."""
    import test_lm_cli as cli

    app, overrides = {"kimi": (cli.KIMI_APP, cli.KIMI_TOY), "glm": (cli.APP, cli.TOY), "ouro": (cli.OURO_APP, cli.OURO_TOY)}[family]
    assert _step_gauges(app, overrides, None) == _step_gauges(app, overrides, "tpu") == toy
    assert _step_gauges(app, [], "tpu") == cell
    assert _step_gauges(app, [], "cpu") == (*cell[:2], 0.0, 0.0, 0.0)


_FRESH_PROCESS = """
import json, sys
import jax, jax.numpy as jnp
from yet_another_mobilenet_series_tpu.models import lm
from yet_another_mobilenet_series_tpu.ops import lm_kda
from yet_another_mobilenet_series_tpu.train import steps

def pallas():
    return sorted(m for m in sys.modules if m.startswith(("jax.experimental.pallas", "jax._src.pallas")))

shape = (1, 128, 2, 128)
q = jax.ShapeDtypeStruct(shape, jnp.bfloat16 if sys.argv[1].startswith("fitting") else jnp.float32)
before = pallas()
if sys.argv[1].endswith("conv_site"):  # the short convolution and q's L2 norm
    fits = lm_kda.conv_fuses(128, 128, 4, q.dtype)
    jax.eval_shape(lambda z, w: lm_kda.conv_and_norm(z, w, 128, 0.5), jax.ShapeDtypeStruct((1, 128, 256), q.dtype),
                   jax.ShapeDtypeStruct((4, 256), jnp.float32))
else:
    fits = lm_kda.fuses(128, lm_kda.KDA_CHUNK, 128, q.dtype)
    jax.eval_shape(lm_kda.kda_core, q, q, q, jax.ShapeDtypeStruct(shape, jnp.float32), jax.ShapeDtypeStruct(shape[:3], jnp.float32))
print(json.dumps({"fits": fits, "before": before, "pallas": pallas()}))
"""


@pytest.mark.parametrize("what, pays", [("plain_site", False), ("fitting_site", True), ("plain_conv_site", False),
                                        ("fitting_conv_site", True)])
def test_pallas_comes_in_where_a_fitting_kda_site_is_traced_and_nowhere_else(what, pays):
    """A fresh process that imports `ops.lm_kda`, `models.lm` and `train.steps`
    and asks the predicate has no `jax.experimental.pallas*` module, and none
    after tracing a KDA core or short convolution the kernels do not take; the
    `tpu` branch of a fitting site, once traced, brings it in (the import
    costs 1.2-1.5 s of `setup_s`, which a cell that runs none of its code
    must not pay)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", _FRESH_PROCESS, what], cwd=repo, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    said = json.loads(proc.stdout.strip().splitlines()[-1])
    assert said["fits"] == pays and not said["before"]
    assert {"jax.experimental.pallas", "jax.experimental.pallas.tpu"} <= set(said["pallas"]) if pays else not said["pallas"], said
