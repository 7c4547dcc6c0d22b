"""The token-model family (models/lm.py, ops/lm.py: GLM-4.7-Flash's
`glm4_moe_lite`) against its plain float32 reference (models/lm_reference.py)
at a toy size on the CPU: hidden 64, 4 heads, 16 experts in 8 shares of 2,
top-2, a vocabulary slice of 32, 1 dense + 2 expert layers + the MTP module,
2 x 32 tokens.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from yet_another_mobilenet_series_tpu.config import LMConfig, ModelConfig
from yet_another_mobilenet_series_tpu.models import get_model, lm_reference as ref
from yet_another_mobilenet_series_tpu.models.serialize import network_from_dict, network_to_dict
from yet_another_mobilenet_series_tpu.ops import lm as ops

LM = LMConfig(hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
              kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, intermediate_size=160,
              moe_intermediate_size=48, n_routed_experts=16, num_experts_per_tok=2, expert_shares=8,
              expert_share_index=1, seq_len=32, init_std=0.1)
VOCAB = 32


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    """32 tokens in tiles of 8 x 8 and the loss in blocks of 16, so that every
    test of this file goes through several tiles and blocks as 8,192 do."""
    from yet_another_mobilenet_series_tpu.models import lm

    patch = pytest.MonkeyPatch()
    patch.setattr(ops, "ATTN_BLOCK", 8)
    patch.setattr(lm, "LOSS_BLOCK", 16)
    yield
    patch.undo()


def model(lm=LM):
    return get_model(ModelConfig(arch="glm4_moe_lite", num_classes=VOCAB, lm=lm))


@pytest.fixture(scope="module")
def setup():
    net = model()
    params, state = net.init(jax.random.PRNGKey(0))
    # a router bias that matters: selection and weights must read different things
    state = jax.tree.map(lambda b: 0.3 * jax.random.normal(jax.random.PRNGKey(5), b.shape), state)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, LM.seq_len + 2), 0, VOCAB)
    (ref_loss, aux), ref_grads = jax.jit(lambda p, s, t: ref.loss_and_grads(p, s, t, ref.dims_of(LM)))(
        params, state, tokens)
    return net, params, state, tokens, ref_loss, aux, ref_grads


@functools.partial(jax.jit, static_argnums=(0, 4))
def program(net, params, state, tokens, dtype=jnp.float32):
    return jax.value_and_grad(lambda p: net.loss(p, state, {"tokens": tokens}, compute_dtype=dtype),
                              has_aux=True)(params)


def worst_leaf(got, want):
    """Largest |got - want| over a leaf's largest |want|, over all leaves."""
    return max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), got, want)))


def test_loss_and_every_gradient_leaf_equal_the_reference_in_float32(setup):
    net, params, state, tokens, ref_loss, aux, ref_grads = setup
    (loss, (new_state, scalars)), grads = program(net, params, state, tokens)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    assert abs(float(scalars["ce"]) - float(aux["ce"])) < 1e-5
    assert abs(float(scalars["ce_mtp"]) - float(aux["ce_mtp"])) < 1e-5
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    assert worst_leaf(grads, ref_grads) < 2e-5
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree.leaves(grads))  # nothing is cut off from the loss


def test_logits_equal_the_reference(setup):
    net, params, state, tokens, _, aux, _ = setup

    @jax.jit
    def main_logits(params, state):
        # the program never holds the logits: its head on the hidden states it hands to its loss
        seen = []
        probe = dataclasses.replace(net, lm=dataclasses.replace(LM, num_nextn_predict_layers=0))
        object.__setattr__(probe, "_head_loss", lambda w, hidden, t: (seen.append(hidden), jnp.zeros(3))[1])
        probe.forward(params, state, tokens)
        return (seen[0] @ params["head"]).reshape(2, LM.seq_len, VOCAB)

    logits = main_logits({k: v for k, v in params.items() if k != "mtp"},
                         {k: v for k, v in state.items() if k != "mtp"})
    np.testing.assert_allclose(logits, aux["logits"], atol=2e-5)


def test_bfloat16_is_within_its_tolerance_and_a_lower_precision_is_not(setup):
    """bfloat16 compute against the float32 reference: loss within 2e-3
    relative, gradient norms by group within 1%, and within 10% for the router
    and expert groups (of 64 tokens, one whose two best scores are a rounding
    apart goes to another expert). The same step with every weight rounded to
    float8_e4m3fn (the nearest precision below) must NOT pass."""
    net, params, state, tokens, ref_loss, _, ref_grads = setup
    want = {**net.grad_scalars(ref_grads), "loss": ref_loss}

    def limit(name):
        return 2e-3 if name == "loss" else 0.1 if name.endswith(("/router", "/experts")) else 1e-2

    def passes(p):
        (loss, _), grads = program(net, p, state, tokens, jnp.bfloat16)
        got = {**net.grad_scalars(grads), "loss": loss}
        return all(abs(float(got[k]) - float(want[k])) / float(want[k]) <= limit(k) for k in want)

    assert passes(params)
    assert not passes(jax.tree.map(lambda w: w.astype(jnp.float8_e4m3fn).astype(jnp.float32), params))


def test_the_parts_all_shares_compute_add_up_to_the_uncut_layer(setup):
    """One expert layer: the routed part of each of the 8 shares, summed, plus
    the shared expert counted ONCE, is the uncut reference layer."""
    net, params, state, _, _, _, _ = setup
    p, bias = params["layer_1"], state["layer_1"]["router_bias"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, LM.seq_len, LM.hidden_size))
    whole = jax.random.normal(jax.random.PRNGKey(4), (16, LM.hidden_size, LM.moe_intermediate_size)) * 0.1
    experts = {"gate": whole, "up": whole[::-1] * 0.5, "down": jnp.swapaxes(whole, 1, 2) * 0.7}
    routed = jnp.zeros_like(x)
    loads = []
    for share in range(8):
        held = {k: v[2 * share:2 * share + 2] for k, v in experts.items()}
        y, load, counters, _ = ops.expert_layer({**p, "experts": held}, bias, x, top_k=2, scaling=1.8, held=2,
                                                share_index=share)
        routed, loads = routed + y, loads + [counters["assignments_here"]]
        assert float(counters["dropped"]) == 0.0
    got = ops.gated_mlp(p["shared"], x) + routed
    uncut = {**ref.dims_of(LM), "expert_shares": 1, "expert_share_index": 0}
    for row in range(2):
        want_routed, want_load = ref.experts({**p, "experts": experts}, bias, x[row], uncut)
        want = ref.gated_mlp(p["shared"]["gate"], p["shared"]["up"], p["shared"]["down"], x[row]) + want_routed
        np.testing.assert_allclose(got[row], want, atol=2e-5)
    # every assignment lands in exactly one share
    assert sum(float(n) for n in loads) == 2 * LM.seq_len * 2 == float(jnp.sum(load))


def test_every_token_routed_to_one_held_expert_is_computed(setup):
    """The worst case for a capacity: a bias that sends EVERY token to the two
    held experts. Nothing is dropped, and the result is the reference's."""
    net, params, state, _, _, _, _ = setup
    p = params["layer_1"]
    bias = jnp.full((16,), -10.0).at[2:4].set(10.0)  # share 1 holds experts 2 and 3
    x = jax.random.normal(jax.random.PRNGKey(7), (2, LM.seq_len, LM.hidden_size))
    y, load, counters, _ = ops.expert_layer(p, bias, x, top_k=2, scaling=1.8, held=2, share_index=1)
    assert float(counters["assignments_here"]) == 2 * LM.seq_len * 2 and float(counters["dropped"]) == 0.0
    assert load.tolist() == [0, 0, 64, 64] + [0] * 12
    for row in range(2):
        want, _ = ref.experts(p, bias, x[row], ref.dims_of(LM))
        np.testing.assert_allclose(y[row], want, atol=2e-5)


def test_selection_reads_scores_plus_bias_weights_read_scores_and_the_bias_has_no_gradient(setup):
    net, params, state, tokens, _, aux, _ = setup
    w = params["layer_1"]["router"]
    x = jax.random.normal(jax.random.PRNGKey(8), (40, LM.hidden_size))
    scores = jax.nn.sigmoid(x @ w)
    bias = jnp.zeros((16,)).at[5].set(5.0)  # expert 5 is always selected ...
    ids, weights, load = ops.route(w, bias, x, top_k=2, scaling=1.8)
    assert bool(jnp.all(jnp.any(ids == 5, axis=-1))) and float(load[5]) == 40
    picked = jnp.take_along_axis(scores, ids, axis=-1)  # ... and weighted by its score, not score + 5
    np.testing.assert_allclose(weights, picked / picked.sum(-1, keepdims=True) * 1.8, rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 1.8, rtol=1e-5)
    grad = jax.grad(lambda b: jnp.sum(ops.route(w, b, x, top_k=2, scaling=1.8)[1] ** 2))(bias)
    assert float(jnp.max(jnp.abs(grad))) == 0.0
    # the sign rule, from the step's own counts over all 16 experts
    (_, (new_state, _)), _ = program(net, params, state, tokens)
    for name, loads in aux["loads"].items():
        want = state[name]["router_bias"] + LM.router_bias_rate * jnp.sign(jnp.mean(loads) - loads)
        np.testing.assert_allclose(new_state[name]["router_bias"], want, atol=1e-7)
        np.testing.assert_allclose(new_state[name]["router_bias"], aux["new_state"][name]["router_bias"], atol=1e-7)
    assert set(new_state) == {"layer_1", "layer_2", "mtp"}


def test_dropped_counts_the_rows_the_grouped_matmul_did_not_write(setup, monkeypatch):
    """`dropped` is read from what `lax.ragged_dot` wrote, not from the ids: a
    grouped matmul that leaves out the last rows of every group (a capacity,
    planted here) is counted, assignment by assignment."""
    net, params, _, _, _, _, _ = setup
    p = params["layer_1"]
    bias = jnp.full((16,), -10.0).at[2:4].set(10.0)  # every token to the two held experts: 64 rows a group
    x = jax.random.normal(jax.random.PRNGKey(7), (2, LM.seq_len, LM.hidden_size))
    real = jax.lax.ragged_dot

    def with_capacity(rows, weights, group_sizes, **kwargs):
        return real(rows, weights, jnp.minimum(group_sizes, 50), **kwargs)  # the groups no longer cover their rows

    layer = lambda: ops.expert_layer(p, bias, x, top_k=2, scaling=1.8, held=2, share_index=1)  # noqa: E731
    assert float(layer()[2]["dropped"]) == 0.0
    monkeypatch.setattr(ops.lax, "ragged_dot", with_capacity)
    counters = layer()[2]
    assert float(counters["assignments_here"]) == 128 and float(counters["dropped"]) > 0


@pytest.mark.parametrize("block", [1, 8, 16, 32])
def test_blocked_attention_and_its_backward_equal_plain_causal_attention(block):
    """ops.causal_attention (one loop body over the tiles on or below the
    diagonal, a hand-written backward) against softmax over a dense mask,
    value and all three gradients, for tiles from one row to the whole."""
    key = jax.random.PRNGKey(2)
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), (2, 32, 4, d)) for i, d in enumerate((16, 16, 24, 24)))

    def plain(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.25
        s = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), s, -jnp.inf)
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v) * w)

    def blocked(q, k, v):
        return jnp.sum(ops.causal_attention(q, k, v, scale=0.25, block=block) * w)

    got = jax.jit(jax.value_and_grad(blocked, (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(plain, (0, 1, 2)))(q, k, v)
    assert abs(float(got[0]) - float(want[0])) < 1e-4
    assert worst_leaf(got[1], want[1]) < 1e-5


def test_mla_equals_the_references_expanded_attention_with_the_shared_k_rope(setup):
    net, params, _, _, _, _, _ = setup
    p = params["layer_0"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(9), (2, LM.seq_len, LM.hidden_size))
    cos, sin = ops.rope_tables(LM.seq_len, LM.qk_rope_head_dim, LM.rope_theta)
    kwargs = dict(heads=4, nope=12, rope=4, v_dim=16, kv_rank=16, eps=LM.rms_norm_eps)
    got = ops.mla_attention(p, x, cos, sin, **kwargs)  # tiles of 8 x 8 (the fixture)
    for row in range(2):
        np.testing.assert_allclose(got[row], ref.mla(p, x[row], ref.dims_of(LM)), atol=2e-5)
    # the future does not leak into the past
    later = x.at[:, 20:].set(0.0)
    np.testing.assert_allclose(got[:, :20], ops.mla_attention(p, later, cos, sin, **kwargs)[:, :20], atol=2e-6)


def test_mtp_targets_and_shared_embedding_and_head(setup):
    """The MTP head predicts token i + 2 from h_i and Emb(t_{i+1}); embedding
    and head are the main model's, and both get gradient from BOTH heads."""
    net, params, state, tokens, _, _, _ = setup

    @jax.jit
    def head_losses(p, toks):
        _, (_, s) = net.loss(p, state, {"tokens": toks})
        return jnp.stack([s["ce"], s["ce_mtp"]])

    moved = tokens.at[:, -1].set((tokens[:, -1] + 1) % VOCAB)  # the last id is ONLY the MTP head's last target
    ce, ce_mtp = head_losses(params, tokens)
    ce2, ce_mtp2 = head_losses(params, moved)
    assert float(ce) == float(ce2) and float(ce_mtp) != float(ce_mtp2)
    of_head = jax.jit(jax.grad(lambda p, pick: jnp.dot(head_losses(p, tokens), pick)))
    for pick in ([1.0, 0.0], [0.0, 1.0]):
        grads = of_head(params, jnp.asarray(pick))
        assert float(jnp.max(jnp.abs(grads["embed"]))) > 0 and float(jnp.max(jnp.abs(grads["head"]))) > 0
    only_main = of_head(params, jnp.asarray([1.0, 0.0]))
    assert float(jnp.max(jnp.abs(only_main["mtp"]["eh_proj"]))) == 0.0  # the main head does not see the MTP module


def test_get_model_resolves_the_family_and_the_spec_round_trips():
    net = model()
    assert net.experts_held == 2 and net.block_names == ("layer_0", "layer_1", "layer_2", "mtp")
    assert network_from_dict(network_to_dict(net)) == net
    with pytest.raises(ValueError, match="do not divide"):
        model(dataclasses.replace(LM, expert_shares=3))
    shapes = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))[0]
    assert shapes["layer_1"]["experts"]["gate"].shape == (2, 64, 48)  # the experts HELD
    assert shapes["layer_1"]["router"].shape == (64, 16)  # the router's published width
    assert net.param_count() == sum(x.size for x in jax.tree.leaves(shapes))


def test_the_published_widths_give_the_parameter_count_of_the_cut():
    from yet_another_mobilenet_series_tpu.config import load_config
    import os

    app = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "yet_another_mobilenet_series_tpu", "apps", "glm_4_7_flash_ep8_share.yml")
    net = get_model(load_config(app).model)
    assert net.param_count() == 706_518_528 and net.experts_held == 8 and net.vocab == 19_360
