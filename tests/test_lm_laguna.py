"""The token family's fifth arch, `laguna` (Laguna-S-2.1: models/lm.py,
ops/lm.py, ops/lm_attention*.py), against its plain float32 reference
(models/lm_reference.py `laguna_*`) at a toy size on the CPU: hidden 64, 5
layers in the published pattern (full, sliding x 3, full) with 4 or 6 query
heads over 2 key/value heads of 16, a window of 12 positions over tiles of 8
(so a query block meets two edge tiles and its own), YaRN on the full layers'
first half of each head, per-head gates, 32 experts in 4 shares of 8 under a
softmax router, top-4, a shared expert; 2 x 32 tokens. Beside it: the window
against a dense mask, the window's Pallas kernels in interpret mode against
the loops, the YaRN tables against their closed form, the expert shares
against the uncut layer, and the other archs' steps as they were.
"""

import dataclasses
import functools
import hashlib
import json
import math
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from yet_another_mobilenet_series_tpu.config import LMConfig, ModelConfig, RopeParameters, RopeSpec
from yet_another_mobilenet_series_tpu.models import get_model, lm_reference as ref
from yet_another_mobilenet_series_tpu.models.serialize import network_from_dict, network_to_dict
from yet_another_mobilenet_series_tpu.ops import lm as ops
from yet_another_mobilenet_series_tpu.ops import lm_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
APP = os.path.join(REPO, "yet_another_mobilenet_series_tpu", "apps", "laguna_s_2_1_ep32_share.yml")
VOCAB = 32
# the full layers' rotary embedding at the published numbers
YARN = RopeSpec(rope_type="yarn", rope_theta=500000.0, partial_rotary_factor=0.5, factor=128.0,
                original_max_position_embeddings=8192, beta_fast=32.0, beta_slow=1.0,
                attention_factor=1.4852030263919618)
LAGUNA = LMConfig(hidden_size=64, num_hidden_layers=5, first_k_dense_replace=1, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, num_attention_heads_per_layer=(4, 6, 6, 6, 4),
                  layer_types=("full_attention",) + ("sliding_attention",) * 3 + ("full_attention",),
                  sliding_window=12,
                  rope_parameters=RopeParameters(full_attention=YARN, sliding_attention=RopeSpec(rope_theta=10000.0)),
                  shared_expert_intermediate_size=48,
                  intermediate_size=160, moe_intermediate_size=48, n_routed_experts=32, num_experts_per_tok=4,
                  expert_shares=4, expert_share_index=1, routed_scaling_factor=2.5, num_nextn_predict_layers=0,
                  q_lora_rank=None, seq_len=32, init_std=0.1, rms_norm_eps=1e-6)


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    """32 tokens in tiles of 8 x 8 and the loss in blocks of 16, as tests/test_lm.py has them."""
    from yet_another_mobilenet_series_tpu.models import lm

    patch = pytest.MonkeyPatch()
    patch.setattr(ops, "ATTN_BLOCK", 8)
    patch.setattr(lm, "LOSS_BLOCK", 16)
    yield
    patch.undo()


def model(config=LAGUNA):
    return get_model(ModelConfig(arch="laguna", num_classes=VOCAB, lm=config))


@functools.lru_cache(maxsize=1)
def setup():
    """(net, params, tokens, the reference's loss, aux and gradients)."""
    net = model()
    params, state = net.init(jax.random.PRNGKey(0))
    assert state == {}  # a softmax router holds no state
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, LAGUNA.seq_len + 2), 0, VOCAB)
    (loss, aux), grads = jax.jit(lambda p: ref.laguna_loss_and_grads(p, tokens, ref.laguna_dims_of(LAGUNA)))(params)
    return net, params, tokens, loss, aux, grads


@functools.partial(jax.jit, static_argnums=(0, 3))
def program(net, params, tokens, dtype=jnp.float32):
    return jax.value_and_grad(lambda p: net.loss(p, {}, {"tokens": tokens}, compute_dtype=dtype), has_aux=True)(params)


def worst_leaf(got, want):
    """Largest |got - want| over a leaf's largest |want|, over all leaves."""
    return max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), got, want)))


# -- the model against the reference ------------------------------------------------


def test_loss_and_every_gradient_leaf_equal_the_reference_in_float32():
    """float32 against float32: the program's tiles, running softmax,
    grouped matmuls and layer checkpoints change only the order of sums
    (2e-5 of a leaf's largest entry, as tests/test_lm.py holds the other
    archs)."""
    net, params, tokens, ref_loss, aux, ref_grads = setup()
    (loss, (new_state, scalars)), grads = program(net, params, tokens)
    assert new_state == {} and abs(float(loss) - float(ref_loss)) < 1e-5
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    assert worst_leaf(grads, ref_grads) < 2e-5
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree.leaves(grads))  # the gates and the window included
    assert scalars["moe_dropped"] == 0.0 and scalars["moe_assignments_here"] > 0


def test_logits_equal_the_reference():
    """The program never holds the logits: its head on the hidden states it
    hands to its loss, against the reference's logits (float32: 1e-5 of
    their largest)."""
    net, params, tokens, _, aux, _ = setup()
    seen = []
    probe = dataclasses.replace(net)
    object.__setattr__(probe, "_head_loss", lambda w, hidden, t: (seen.append(hidden @ w), jnp.zeros(3))[1])
    jax.jit(lambda p: (probe.forward(p, {}, tokens), seen[-1])[1])(params)
    got = jax.jit(lambda p: (probe.forward(p, {}, tokens), seen[-1])[1])(params)
    want = aux["logits"].reshape(got.shape)
    assert float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))) < 1e-5


def test_the_groups_the_step_reports_are_the_gradients_norms():
    net, params, tokens, _, _, ref_grads = setup()
    got = net.grad_scalars(program(net, params, tokens)[1])
    blocks = {f"gnorm/layer_{i}/{g}" for i in range(5) for g in ("attn", "norms")}
    experts = {f"gnorm/layer_{i}/{g}" for i in range(1, 5) for g in ("router", "shared", "experts")}
    assert set(got) == {"gnorm/embed", "gnorm/head", "gnorm/final_norm", "gnorm/layer_0/mlp", *blocks, *experts}
    norm = lambda tree: float(jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree))))  # noqa: E731
    for name, tree in (("gnorm/layer_1/attn", ref_grads["layer_1"]["attn"]),
                       ("gnorm/layer_2/shared", ref_grads["layer_2"]["shared"])):
        assert float(got[name]) == pytest.approx(norm(tree), rel=1e-4), name


# -- the window ------------------------------------------------------------------


def dense_window(q, k, v, scale, window):
    """softmax(q k^T * scale) v under a dense mask: key k visible to query q where q - window < k <= q."""
    seq = q.shape[1]
    at, of = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where((of <= at) & (of > at - window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def operands(seq, heads, dims, key=2):
    key = jax.random.PRNGKey(key)
    return tuple(jax.random.normal(jax.random.fold_in(key, i), (2, seq, heads, d))
                 for i, d in enumerate((dims[0], dims[0], dims[1], dims[1])))


@pytest.mark.parametrize("window", [8, 3, 12, 20, 1, 40],
                         ids=["at-the-block", "under-a-block", "over-a-block", "a-whole-tile-inside", "itself-only",
                              "past-the-sequence"])
def test_windowed_loops_equal_a_dense_masked_softmax(window):
    """ops.causal_attention(window=W) through the loops (tiles of 8 over 32
    positions) against softmax over the dense window mask: the value and all
    three gradients, float32 (1e-5 of the largest). A window at the block
    (one edge tile and the diagonal), under it (the diagonal masked by the
    window too), over it (two edge tiles), wide enough for a whole tile
    between edge and diagonal, of one position (each query sees itself
    alone) and past the sequence (the causal result)."""
    q, k, v, w = operands(32, 4, (16, 24))

    def loss(attend):
        return lambda q, k, v: (jnp.sum(attend(q, k, v) * w), attend(q, k, v))

    got = jax.jit(jax.value_and_grad(loss(lambda q, k, v: ops.causal_attention(q, k, v, scale=0.25, block=8,
                                                                                window=window)), (0, 1, 2), has_aux=True))(q, k, v)
    want = jax.jit(jax.value_and_grad(loss(lambda q, k, v: dense_window(q, k, v, 0.25, window)), (0, 1, 2),
                                      has_aux=True))(q, k, v)
    # against the largest entry, or 1 where that is less: a window of one position makes q's and k's gradients zero
    assert max(jax.tree.leaves(jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b)) / max(float(jnp.max(jnp.abs(b))), 1.0)),
                                            (got[0][1], got[1]), (want[0][1], want[1])))) < 1e-5
    causal = ops.causal_attention(q, k, v, scale=0.25, block=8)
    assert (float(jnp.max(jnp.abs(got[0][1] - causal))) < 1e-5) is (window >= 32)  # the window is no causal pass


def as_lowered_for_a_tpu(patch):
    """The `tpu` branch of `lax.platform_dependent` on the CPU, the kernels in Pallas interpret mode."""
    patch.setattr(ops.lax, "platform_dependent", lambda *args, tpu, default: tpu(*args))
    for name in ("attention_fwd", "attention_bwd"):
        patch.setattr(lm_attention, name, functools.partial(getattr(lm_attention, name), interpret=True))


@pytest.mark.parametrize("window", [256, 100, 300, 600])
def test_the_windowed_kernels_equal_the_loops_forward_and_backward(window):
    """The window's two Pallas kernels (interpret mode) against the loops with
    the window's bounds, at lane-wide heads over three blocks of 256 rows,
    float32 (the kernel calls themselves: Mosaic takes only bfloat16, which
    tests/test_tpu_aot.py compiles): the output, the log-sum-exp and the three
    gradients within 1e-5 of the largest. A window at the block, under it,
    over it (one edge tile, then the diagonal) and over two blocks (an edge
    tile, a whole one, the diagonal)."""
    block, seq = 256, 768
    heads_lead = functools.partial(jnp.swapaxes, axis1=1, axis2=2)
    q, k, v, g = map(heads_lead, operands(seq, 2, (128, 128)))
    scale = 128 ** -0.5
    out, lse = lm_attention.attention_fwd(q, k, v, scale, block, interpret=True, window=window)
    want_out, want_lse = ops.loops_fwd(q, k, v, scale, block, window=window)
    assert worst_leaf((out, lse), (want_out, want_lse)) < 1e-5
    grads = lm_attention.attention_bwd(q, k, v, out, lse, g, scale, block, interpret=True, window=window)
    assert worst_leaf(grads, ops.loops_bwd(q, k, v, want_out, want_lse, g, scale, block, window=window)) < 1e-5


def test_a_tpu_lowering_takes_the_window_kernels_in_bfloat16(monkeypatch):
    """Through the dispatch as a TPU lowering takes it (the kernels in
    interpret mode), bfloat16 at the cell's window and tile over three
    blocks: no further from the float32 truth than the loops' own bfloat16
    (5% over, for the order of sums), and bfloat16 is visible in both."""
    q, k, v, w = operands(768, 2, (128, 128))

    def windowed(q, k, v):
        out = ops.causal_attention(*(x.astype(jnp.bfloat16) for x in (q, k, v)), scale=128 ** -0.5, block=256,
                                   window=256).astype(jnp.float32)
        return jnp.sum(out * w), out

    want = jax.jit(jax.value_and_grad(lambda q, k, v: (jnp.sum(dense_window(q, k, v, 128 ** -0.5, 256) * w),
                                                       dense_window(q, k, v, 128 ** -0.5, 256)), (0, 1, 2), has_aux=True))(q, k, v)
    assert lm_attention.fuses(768, 256, 128, 128, jnp.bfloat16)
    loops = jax.jit(jax.value_and_grad(windowed, (0, 1, 2), has_aux=True))(q, k, v)
    as_lowered_for_a_tpu(monkeypatch)
    got = jax.jit(jax.value_and_grad(windowed, (0, 1, 2), has_aux=True))(q, k, v)
    truth = (want[0][1], want[1])
    assert 1e-4 < worst_leaf((loops[0][1], loops[1]), truth) < 2e-2
    assert worst_leaf((got[0][1], got[1]), truth) <= 1.05 * worst_leaf((loops[0][1], loops[1]), truth)


def test_the_cells_window_meets_two_key_tiles_a_query_block(monkeypatch):
    """At the cell's window and tile (512 and 512) a query block meets its own
    key block and the one before, whose tile the window's edge crosses: the
    loops' forward (unrolled, its tiles counted) makes 2 tiles a query block
    past the first, where the causal bounds make i + 1 (8.5 a block on
    average at 8,192 positions); the kernels take the same bounds (no tile
    inside the window whole, one edge tile before the diagonal)."""
    from yet_another_mobilenet_series_tpu.ops import lm_attention_kernels as kernels

    assert ops.window_reach(512, 512) == 1 and kernels._window_bounds(512, 512) == (1, 0)
    assert kernels._window_bounds(1200, 512) == (ops.window_reach(1200, 512), 1) == (3, 1)

    def unrolled(lower, upper, body, carry):
        for i in range(int(lower), int(upper)):
            carry = body(i, carry)
        return carry

    counted = []
    real = ops._tile_scores
    monkeypatch.setattr(ops, "lax", types.SimpleNamespace(**{**vars(jax.lax), "fori_loop": unrolled}))
    monkeypatch.setattr(ops, "_tile_scores", lambda q, k, first_q, first_k, *rest: (
        counted.append((first_q, first_k)), real(q, k, first_q, first_k, *rest))[1])
    x = jnp.zeros((1, 1, 16 * 4, 8))  # 16 blocks of 4 rows, the window a block: the cell's 16 x 512 in miniature
    ops.loops_fwd(x, x, x, 1.0, 4, window=4)
    assert len(counted) == 1 + 2 * 15 and {q - k for q, k in counted} == {0, 4}
    counted.clear()
    ops.loops_fwd(x, x, x, 1.0, 4)
    assert len(counted) == 16 * 17 // 2


# -- the rotary tables -------------------------------------------------------------


def test_the_yarn_tables_are_their_closed_form_at_the_published_numbers():
    """The full layers' tables at 8,192 positions against YaRN written out in
    float64: the ramp between channels 9 and 18 of the 32 frequencies of 64
    rotated channels (ln(8192 / 2 pi beta) x 64 / 2 ln 500,000 at beta 32
    and 1: 9.04 and 17.49, rounded outwards), the fast channels kept, the
    slow ones divided by 128, both tables times 1.4852 (0.1 ln 128 + 1, as
    published). float32 angles of up to 8,192 radians are exact to 2.5e-4 of
    a radian (half an ulp), so within 1e-3 of the closed form."""
    cos, sin = ops.rope_tables_of(8192, 128, YARN)
    assert cos.shape == sin.shape == (8192, 32)
    r = 64
    turns = lambda beta: r * math.log(8192 / (2 * math.pi * beta)) / (2 * math.log(500000.0))  # noqa: E731
    assert (math.floor(turns(32.0)), math.ceil(turns(1.0))) == (9, 18)
    i = np.arange(32, dtype=np.float64)
    ramp = np.clip((i - 9) / 9, 0, 1)
    base = 500000.0 ** (-2 * i / r)
    freq = base * (1 - ramp) + base / 128 * ramp
    assert freq[8] == base[8] and freq[18] == base[18] / 128
    angle = np.arange(8192, dtype=np.float64)[:, None] * freq[None, :]
    assert YARN.attention_factor == pytest.approx(0.1 * math.log(128) + 1, rel=1e-12)
    assert np.max(np.abs(np.asarray(cos) - 1.4852030263919618 * np.cos(angle))) < 1e-3
    assert np.max(np.abs(np.asarray(sin) - 1.4852030263919618 * np.sin(angle))) < 1e-3
    np.testing.assert_allclose(np.asarray(ops.yarn_inv_freq(64, YARN)), ref.laguna_inv_freq(YARN, 128), rtol=1e-6)
    # the sliding layers' tables are the plain ones over the whole head
    plain = RopeSpec(rope_theta=10000.0)
    assert all(np.array_equal(a, b) for a, b in zip(ops.rope_tables_of(64, 128, plain), ops.rope_tables(64, 128, 1e4)))


def test_a_partial_rotation_turns_the_first_channels_and_passes_the_rest():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 2, 8))
    cos, sin = ops.rope_tables(16, 4, 100.0)
    out = ops.apply_rope(x, cos, sin)
    assert np.array_equal(out[..., 4:], x[..., 4:])
    assert np.allclose(out[..., :4], ops.apply_rope(x[..., :4], cos, sin))
    assert not np.allclose(out[..., :4], x[..., :4])


# -- the expert share ------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer_under_softmax_routing():
    """One expert layer of the toy at its uncut size (all 32 experts held,
    `expert_shares` 1) and cut in four shares of 8: the routed parts of the
    four shares add up to the uncut layer's and to the reference's, the
    router scoring all 32 in every share; the whole block's second half
    (shared expert counted once, with its gate) is the reference's."""
    uncut = model(dataclasses.replace(LAGUNA, expert_shares=1, expert_share_index=0))
    params, _ = uncut.init(jax.random.PRNGKey(3))
    p = params["layer_2"]
    y = jax.random.normal(jax.random.PRNGKey(4), (1, 32, 64))
    d = ref.laguna_dims_of(uncut.lm)
    route = dict(top_k=4, scaling=2.5, scoring="softmax")
    whole = ops.expert_layer(p, None, y, held=32, share_index=0, **route)[0]
    parts = sum(ops.expert_layer({**p, "experts": jax.tree.map(lambda e, s=s: e[8 * s:8 * s + 8], p["experts"])},
                                 None, y, held=8, share_index=s, **route)[0] for s in range(4))
    with jax.default_matmul_precision("highest"):
        want, load = ref.laguna_experts(p, y[0], d)
    assert float(jnp.max(jnp.abs(parts - whole))) < 1e-5 and float(jnp.max(jnp.abs(whole[0] - want))) < 1e-5
    assert float(jnp.sum(load)) == 32 * 4
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 32, 64))
    got = uncut._fed("layer_2", p, None, x)[0]
    with jax.default_matmul_precision("highest"):
        normed = ref.rms_norm(x[0], p["mlp_norm"], 1e-6)
        s = p["shared"]
        shared = jax.nn.sigmoid(normed @ s["sigmoid_gate"])[:, None] * ref.gated_mlp(s["gate"], s["up"], s["down"], normed)
        expect = x[0] + shared + ref.laguna_experts(p, normed, d)[0]
    assert float(jnp.max(jnp.abs(got[0] - expect))) < 1e-5


# -- the other archs --------------------------------------------------------------


@pytest.mark.parametrize("arch, dtype, digest", [("glm4_moe_lite", "float32", "f096f8949bb69b6a"),
                                                 ("kimi_linear", "float32", "7252fb0b8c1d3546"),
                                                 ("granitemoehybrid", "float32", "144e1750915ebc84"),
                                                 ("granitemoehybrid", "bfloat16", "824879eb828d442b")])
def test_no_window_and_the_sigmoid_router_leave_the_other_archs_steps_as_they_were(arch, dtype, digest):
    """`causal_attention` with `window` None, `mha_attention` without a gate,
    `route` with `sigmoid_bias`, `apply_rope` over a whole head and the
    stateful router of models/lm.py are what they were: the toy GLM, kimi and
    granite steps' lowered modules (StableHLO text, as tests/test_obs_scopes.py
    takes them, the tile and loss blocks as shipped) have the digests of the
    commit before `laguna` (GLM's and kimi's bfloat16 steps are pinned
    there)."""
    from test_lm import KIMI, LM
    from test_lm_granite import GRANITE
    from test_obs_scopes import token_step

    with pytest.MonkeyPatch.context() as patch:
        from yet_another_mobilenet_series_tpu.models import lm

        patch.setattr(ops, "ATTN_BLOCK", 512)
        patch.setattr(lm, "LOSS_BLOCK", 2048)
        text = token_step(arch, {"glm4_moe_lite": LM, "kimi_linear": KIMI, "granitemoehybrid": GRANITE}[arch],
                          dtype).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


@pytest.mark.parametrize("change, complaint", [
    ({"layer_types": ("full_attention",) * 4}, "layer_types"),
    ({"layer_types": ("full_attention", "mamba", "sliding_attention", "sliding_attention", "full_attention")},
     "layer_types"),
    ({"num_attention_heads_per_layer": (4, 6, 5, 6, 4)}, "divides"),
    ({"shared_expert_intermediate_size": 0}, "shared expert"),
    ({"sliding_window": None}, "sliding_window"),
    ({"rope_parameters": RopeParameters(full_attention=dataclasses.replace(YARN, factor=0.0))}, "yarn with a factor"),
    ({"rope_parameters": RopeParameters(full_attention=dataclasses.replace(YARN, partial_rotary_factor=0.3))},
     "partial_rotary_factor"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
], ids=["too-few-types", "a-mamba-layer", "heads-kv-does-not-divide", "no-shared-expert", "no-window",
        "a-yarn-without-factor", "an-odd-rotation", "an-mtp-module", "a-tied-head"])
def test_validate_refuses_what_laguna_does_not_run(change, complaint):
    with pytest.raises(ValueError, match=complaint):
        model(dataclasses.replace(LAGUNA, **change))


@pytest.mark.parametrize("change", [{"sliding_window": 512}, {"rope_parameters": RopeParameters(full_attention=YARN)},
                                    {"num_attention_heads_per_layer": (4, 4, 4)},
                                    {"shared_expert_intermediate_size": 48}],
                         ids=lambda c: next(iter(c)))
def test_the_other_archs_refuse_lagunas_keys(change):
    from test_lm import LM

    with pytest.raises(ValueError, match="laguna's"):
        get_model(ModelConfig(arch="glm4_moe_lite", num_classes=VOCAB, lm=dataclasses.replace(LM, **change)))


def test_the_arch_fixes_the_router_and_the_gate():
    """`laguna` routes by a softmax without router state and gates every
    layer's heads, with no option to say otherwise; the other archs keep the
    sigmoid-plus-bias router and its state."""
    from test_lm import LM

    net = model()
    params, state = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
    assert net.router_scoring == "softmax" and state == {}
    assert [params[b]["attn"]["gate"].shape for b in net.block_names] == [(64, net.heads_of(b)) for b in net.block_names]
    glm = get_model(ModelConfig(arch="glm4_moe_lite", num_classes=VOCAB, lm=LM))
    assert glm.router_scoring == "sigmoid_bias" and jax.eval_shape(lambda: glm.init(jax.random.PRNGKey(0)))[1]


# -- the published widths and the entry point ------------------------------------------


def test_the_published_widths_give_the_parameter_count_of_the_cut(monkeypatch):
    """The app at its published widths: 811,029,504 parameters (the
    configuration file's table has the parts), 3 windowed layers of 5
    attention layers, all five through the kernels where a TPU lowers the
    step (8,192 rows in tiles of 512, heads of 128, bfloat16)."""
    from yet_another_mobilenet_series_tpu.config import load_config

    monkeypatch.setattr(ops, "ATTN_BLOCK", 512)  # the tile as shipped, which this file's fixture shrinks
    net = get_model(load_config(APP).model)
    assert (net.arch, net.vocab, net.experts_held, net.expert_sites) == ("laguna", 12544, 8, 4)
    assert net.param_count() == 811_029_504
    assert [net.heads_of(b) for b in net.block_names] == [48, 72, 72, 72, 48]
    assert [net.window_of(b) for b in net.block_names] == [None, 512, 512, 512, None]
    assert net.attention_sites(jnp.bfloat16) == (5, 5)
    assert (net.window_sites, net.window_fitting_sites(jnp.bfloat16), net.window_fitting_sites(jnp.float32)) == (3, 3, 0)
    # a softmax router holds no state to spread the load: twice the sigmoid router's rows, four times the 2,560 expected
    assert net.expert_capacity_rows(1) == 2 * ops.capacity_rows(8192 * 10, 8, 256) == 4 * 8192 * 10 * 8 // 256 == 10240
    assert ops.site_capacity(8192 * 10, 8, 256, "sigmoid_bias") == ops.capacity_rows(8192 * 10, 8, 256) == 5120
    assert network_from_dict(json.loads(json.dumps(network_to_dict(net)))) == net


# two layers, full then sliding (the app's first two), the second with experts
LAGUNA_TOY = ["model.num_classes=256", "model.lm.hidden_size=64", "model.lm.num_attention_heads=4",
              "model.lm.num_hidden_layers=2", "model.lm.layer_types=[full_attention,sliding_attention]",
              "model.lm.num_attention_heads_per_layer=[4,6]", "model.lm.num_key_value_heads=2",
              "model.lm.head_dim=16", "model.lm.sliding_window=12", "model.lm.intermediate_size=160",
              "model.lm.moe_intermediate_size=48", "model.lm.shared_expert_intermediate_size=48",
              "model.lm.n_routed_experts=32", "model.lm.expert_shares=4", "model.lm.num_experts_per_tok=4",
              "model.lm.seq_len=32"]


def test_three_steps_through_cli_train(tmp_path, capsys):
    """The fifth arch through the normal entry point: apps/laguna_s_2_1_ep32_share.yml
    at a toy size, the window and gate gauges at the log boundary, no router
    state, eval, and a checkpoint that restores as the same TokenModel."""
    from yet_another_mobilenet_series_tpu.ckpt.manager import CheckpointManager
    from yet_another_mobilenet_series_tpu.cli import train as cli_train
    from yet_another_mobilenet_series_tpu.models import TokenModel

    log_dir = str(tmp_path / "log")
    final = cli_train.main([f"app:{APP}", *LAGUNA_TOY, "data.fake_train_size=3", "train.epochs=1",
                            "train.log_every=1", f"train.log_dir={log_dir}", "dist.num_devices=1"])
    assert final["epoch"] == 1.0 and final["eval_n"] == 2 * 32 and np.isfinite(final["eval_loss"])
    banner = [line for line in capsys.readouterr().out.splitlines() if "model laguna" in line]
    assert banner and "8 of 32 experts a layer" in banner[0]
    with open(os.path.join(log_dir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f if '"train/' in line]
    assert len(rows) == 3
    last = rows[-1]
    assert abs(last["train/ce"] - np.log(256)) < 0.2 and last["train/moe_dropped"] == 0.0
    assert "train/gnorm/layer_1/shared" in last and "train/gnorm/head" in last
    with open(os.path.join(log_dir, "obs_registry.json")) as f:
        registry = json.load(f)
    assert (registry["train.attn_sites"], registry["train.attn_window_sites"], registry["train.attn_window_fused_sites"],
            registry["train.moe_sites"]) == (2.0, 1.0, 0.0, 1.0)
    mgr = CheckpointManager(log_dir + "/ckpt")
    step, net, _ = mgr.restore_spec()
    mgr.close()
    assert step == 3 and isinstance(net, TokenModel) and net.arch == "laguna"
    assert net.lm.rope_parameters.full_attention == YARN and net.window_of("layer_1") == 12
