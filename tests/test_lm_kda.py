"""Kimi Delta Attention's chunked form (ops/lm_kda.py) against the recurrence it
must equal, token by token, on the CPU at small sizes: 2 sequences, 2 heads of
8 channels, lengths of 1, 2.5 and 8 chunks' worth, with decays a fresh model
has and with decays whose in-chunk product underflows float32.
"""

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


def operands(seq: int, hard: bool, seed: int = 0):
    """q (scaled), k L2-normalised, v, log decays, write strengths. `hard`:
    decays up to 12 a position (a chunk of 64 passes -88 many times over) and
    `beta` exactly 0 at every third position, exactly 1 at every fifth."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (2, seq, HEADS, WIDTH)
    q, k, v = (jax.random.normal(key, shape) for key in ks[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * WIDTH ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.exp(jax.random.uniform(ks[3], shape, minval=math.log(1e-3), maxval=math.log(12.0 if hard else 0.5)))
    beta = jax.nn.sigmoid(2 * jax.random.normal(ks[4], shape[:3]))
    if hard:
        beta = beta.at[:, ::3].set(0.0).at[:, 1::5].set(1.0)
    return q, k, v, g, beta


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
