"""The token-model family (models/lm.py, ops/lm.py, ops/lm_kda.py) against its
plain float32 reference (models/lm_reference.py) at a toy size on the CPU.
GLM-4.7-Flash's `glm4_moe_lite`: hidden 64, 4 heads, 16 experts in 8 shares of
2, top-2, a vocabulary slice of 32, 1 dense + 2 expert layers + the MTP
module, 2 x 32 tokens. Kimi-Linear's `kimi_linear` (`KIMI`, the `kimi`
cases): 5 layers in its pattern (KDA, KDA, KDA, MLA, KDA: 4 heads of 8), MLA
without RoPE and without a low-rank q, 32 experts in 4 shares of 8, top-4, no
MTP.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from yet_another_mobilenet_series_tpu.config import LinearAttnConfig, LMConfig, ModelConfig
from yet_another_mobilenet_series_tpu.models import get_model, lm_reference as ref
from yet_another_mobilenet_series_tpu.models.serialize import network_from_dict, network_to_dict
from yet_another_mobilenet_series_tpu.ops import lm as ops
from yet_another_mobilenet_series_tpu.ops import lm_attention, lm_kda

LM = LMConfig(hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
              kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, intermediate_size=160,
              moe_intermediate_size=48, n_routed_experts=16, num_experts_per_tok=2, expert_shares=8,
              expert_share_index=1, seq_len=32, init_std=0.1)
VOCAB = 32
# published layers 1-5 of kimi_linear's pattern (the lists run on, as the source's do: a cut reads its first few)
KIMI = LMConfig(hidden_size=64, num_hidden_layers=5, first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=None,
                kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, intermediate_size=160,
                moe_intermediate_size=48, n_routed_experts=32, num_experts_per_tok=4, routed_scaling_factor=2.446,
                num_nextn_predict_layers=0, mla_use_nope=True, expert_shares=4, expert_share_index=1, seq_len=32,
                init_std=0.1, linear_attn_config=LinearAttnConfig(
                    kda_layers=(1, 2, 3, 5, 6, 7), full_attn_layers=(4, 8), head_dim=8, num_heads=4))
FAMILIES = ["glm", "kimi"]


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    """32 tokens in tiles of 8 x 8, the loss in blocks of 16 and KDA in chunks
    of 8 with sub-blocks of 4, so that every test of this file goes through
    several tiles, blocks and chunks as 8,192 and 16,384 do."""
    from yet_another_mobilenet_series_tpu.models import lm

    patch = pytest.MonkeyPatch()
    patch.setattr(ops, "ATTN_BLOCK", 8)
    patch.setattr(lm, "LOSS_BLOCK", 16)
    patch.setattr(lm_kda, "KDA_CHUNK", 8)
    patch.setattr(lm_kda, "KDA_SUBCHUNK", 4)
    yield
    patch.undo()


def model(lm=LM):
    arch = "kimi_linear" if lm.linear_attn_config.kda_layers else "glm4_moe_lite"
    return get_model(ModelConfig(arch=arch, num_classes=VOCAB, lm=lm))


def _setup(LM):
    net = model(LM)
    params, state = net.init(jax.random.PRNGKey(0))
    # a router bias that matters: selection and weights must read different things
    state = jax.tree.map(lambda b: 0.3 * jax.random.normal(jax.random.PRNGKey(5), b.shape), state)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, LM.seq_len + 2), 0, VOCAB)
    (ref_loss, aux), ref_grads = jax.jit(lambda p, s, t: ref.loss_and_grads(p, s, t, ref.dims_of(LM)))(
        params, state, tokens)
    return net, params, state, tokens, ref_loss, aux, ref_grads


@pytest.fixture(scope="module")
def setup():
    return _setup(LM)


@pytest.fixture(scope="module")
def kimi_setup():
    return _setup(KIMI)


def family_setup(request, family):
    return request.getfixturevalue("setup" if family == "glm" else "kimi_setup")


@functools.partial(jax.jit, static_argnums=(0, 4))
def program(net, params, state, tokens, dtype=jnp.float32):
    return jax.value_and_grad(lambda p: net.loss(p, state, {"tokens": tokens}, compute_dtype=dtype),
                              has_aux=True)(params)


def worst_leaf(got, want):
    """Largest |got - want| over a leaf's largest |want|, over all leaves."""
    return max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), got, want)))


@pytest.mark.parametrize("family", FAMILIES)
def test_loss_and_every_gradient_leaf_equal_the_reference_in_float32(request, family):
    net, params, state, tokens, ref_loss, aux, ref_grads = family_setup(request, family)
    (loss, (new_state, scalars)), grads = program(net, params, state, tokens)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    assert abs(float(scalars["ce"]) - float(aux["ce"])) < 1e-5
    assert abs(float(scalars.get("ce_mtp", 0.0)) - float(aux["ce_mtp"])) < 1e-5
    assert ("ce_mtp" in scalars) == (family == "glm") and ("kda_min_chunk_log_decay" in scalars) == (family == "kimi")
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


@pytest.mark.parametrize("family", FAMILIES)
def test_bfloat16_is_within_its_tolerance_and_a_lower_precision_is_not(request, family):
    """bfloat16 compute against the float32 reference: loss within 2e-3
    relative, gradient norms by group within 1% (a KDA mixer's group among
    them), and within 10% for the router and expert groups (of 64 tokens, one
    whose two best scores are a rounding apart goes to another expert). The
    same step with every weight rounded to float8_e4m3fn (the nearest
    precision below) must NOT pass."""
    net, params, state, tokens, ref_loss, _, ref_grads = family_setup(request, family)
    want = {**net.grad_scalars(ref_grads), "loss": ref_loss}

    # kimi's five layers are deeper than GLM's three, and a KDA layer's in-chunk solve hands bfloat16's rounding on:
    # its groups read up to 7% and 11% here (GLM's 0.6% and 6%), float8's 19% and 43%
    groups, routed = (1e-2, 0.1) if family == "glm" else (8e-2, 0.15)

    def limit(name):
        return 2e-3 if name == "loss" else routed if name.endswith(("/router", "/experts")) else groups

    def passes(p):
        (loss, _), grads = program(net, p, state, tokens, jnp.bfloat16)
        got = {**net.grad_scalars(grads), "loss": loss}
        return all(abs(float(got[k]) - float(want[k])) / float(want[k]) <= limit(k) for k in want)

    assert passes(params)
    assert not passes(jax.tree.map(lambda w: w.astype(jnp.float8_e4m3fn).astype(jnp.float32), params))


@pytest.mark.parametrize("family", FAMILIES)
def test_the_parts_all_shares_compute_add_up_to_the_uncut_layer(request, family):
    """One expert layer: the routed part of each of the shares (8 of 2 experts,
    top-2, for GLM; 4 of 8, top-4, for kimi), summed, plus the shared expert
    counted ONCE, is the uncut reference layer."""
    net, params, state, _, _, _, _ = family_setup(request, family)
    c = net.lm
    p, bias = params["layer_1"], state["layer_1"]["router_bias"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, c.seq_len, c.hidden_size))
    whole = jax.random.normal(jax.random.PRNGKey(4), (c.n_routed_experts, c.hidden_size, c.moe_intermediate_size)) * 0.1
    experts = {"gate": whole, "up": whole[::-1] * 0.5, "down": jnp.swapaxes(whole, 1, 2) * 0.7}
    routed = jnp.zeros_like(x)
    loads = []
    n = net.experts_held
    for share in range(c.expert_shares):
        held = {k: v[n * share:n * share + n] for k, v in experts.items()}
        y, load, counters, _ = ops.expert_layer({**p, "experts": held}, bias, x, top_k=c.num_experts_per_tok,
                                                scaling=c.routed_scaling_factor, held=n, share_index=share)
        routed, loads = routed + y, loads + [counters["assignments_here"]]
        assert float(counters["dropped"]) == 0.0
    got = ops.gated_mlp(p["shared"], x) + routed
    uncut = {**ref.dims_of(c), "expert_shares": 1, "expert_share_index": 0}
    for row in range(2):
        want_routed, want_load = ref.experts({**p, "experts": experts}, bias, x[row], uncut)
        want = ref.gated_mlp(p["shared"]["gate"], p["shared"]["up"], p["shared"]["down"], x[row]) + want_routed
        np.testing.assert_allclose(got[row], want, atol=2e-5)
    # every assignment lands in exactly one share
    assert sum(float(n) for n in loads) == 2 * c.seq_len * c.num_experts_per_tok == float(jnp.sum(load))


def test_all_shares_of_a_kimi_model_add_up_to_the_uncut_model(kimi_setup):
    """The WHOLE model, not one layer: with the other three shares' routed
    parts handed in where they would arrive (the shared expert and the mixers
    counted once, because each share runs them on the same tokens), layer by
    layer, the hidden state that leaves every block is the uncut reference
    block's."""
    net, params, state, tokens, _, _, _ = kimi_setup
    c = net.lm
    whole = {name: {k: jax.random.normal(jax.random.PRNGKey(7 + i), (c.n_routed_experts, *v.shape[1:])) * 0.1
                    for k, v in params[name]["experts"].items()}
             for i, name in enumerate(net.block_names) if name in state}
    uncut = {**ref.dims_of(c), "expert_shares": 1, "expert_share_index": 0}
    n = net.experts_held
    x = params["embed"][tokens[:, :c.seq_len]]
    for name in net.block_names:
        p = params[name]
        mixed, _ = net._mixed(p, x, None, None)
        y = ops.rms_norm(mixed, p["mlp_norm"], c.rms_norm_eps)
        if name in state:
            fed = mixed + ops.gated_mlp(p["shared"], y)
            for share in range(c.expert_shares):
                held = {k: v[n * share:n * share + n] for k, v in whole[name].items()}
                fed = fed + ops.expert_layer({**p, "experts": held}, state[name]["router_bias"], y,
                                             top_k=c.num_experts_per_tok, scaling=c.routed_scaling_factor, held=n,
                                             share_index=share)[0]
            want = jnp.stack([ref.block({**p, "experts": whole[name]}, state[name]["router_bias"], row, uncut, False)[0]
                              for row in x])
        else:
            fed = mixed + ops.gated_mlp(p["mlp"], y)
            want = jnp.stack([ref.block(p, None, row, uncut, True)[0] for row in x])
        np.testing.assert_allclose(fed, want, atol=5e-5)
        x = want


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


@pytest.mark.parametrize("branch, to_held, planted", [("fallback", 10.0, 50), ("bounded", 0.0, 3)])
def test_dropped_counts_the_rows_the_grouped_matmul_did_not_write(setup, monkeypatch, branch, to_held, planted):
    """`dropped` is read from what `lax.ragged_dot` wrote, not from the ids: a
    grouped matmul that leaves out the last rows of every group (a capacity,
    planted here) is counted, assignment by assignment, in the full-length
    branch (every token to the two held experts: 64 rows a group) as in the
    bounded one (a flat bias: 12 rows in the two groups)."""
    net, params, _, _, _, _, _ = setup
    p = params["layer_1"]
    bias = jnp.full((16,), -to_held).at[2:4].set(to_held)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, LM.seq_len, LM.hidden_size))
    real = jax.lax.ragged_dot

    def with_capacity(rows, weights, group_sizes, **kwargs):
        return real(rows, weights, jnp.minimum(group_sizes, planted), **kwargs)  # the groups no longer cover their rows

    layer = lambda: ops.expert_layer(p, bias, x, top_k=2, scaling=1.8, held=2, share_index=1)  # noqa: E731
    counters = layer()[2]
    assert float(counters["dropped"]) == 0.0 and float(counters["bounded"]) == (branch == "bounded")
    monkeypatch.setattr(ops.lax, "ragged_dot", with_capacity)
    jax.clear_caches()  # the layer's two halves are jitted: one trace serves every site of these shapes
    try:
        counters = layer()[2]
    finally:
        jax.clear_caches()  # and must not serve the planted capacity to the next test
    assert float(counters["assignments_here"]) == (128 if branch == "fallback" else 12) and float(counters["dropped"]) > 0


@pytest.mark.parametrize("assignments, held, n_experts, rows", [
    (16384 * 4, 8, 64, 16384),    # glm47flash_train_2x8k: a quarter of the assignments
    (16384 * 8, 8, 256, 8192),    # kimilinear_train_1x16k: a sixteenth
    (128, 2, 16, 64), (256, 8, 32, 128),  # this file's two models: 32 rows, up to a whole tile of 64; 128
    (24, 2, 16, 64), (48, 2, 64, 64),     # tests/benchmark_tests' 12-token models: not fewer rows than there are, so one body
    (128, 8, 16, 128), (128, 16, 16, 256),  # a share of half the experts, the uncut layer: the same
])
def test_the_capacity_is_twice_the_shares_expected_rows_read_from_the_shapes(assignments, held, n_experts, rows):
    assert ops.capacity_rows(assignments, held, n_experts) == rows
    # the branches a site traces: the bounded one only where it is the shorter
    assert len(ops._branches(2, rows, assignments)) == (2 if rows < assignments else 1)


@pytest.mark.parametrize("family", FAMILIES)
def test_the_bounded_branch_equals_the_full_length_one(request, monkeypatch, family):
    """One expert layer at the toy shapes (capacity 64 of 128 assignment rows
    for GLM's, 128 of 256 for kimi's): the branch over `capacity_rows` rows,
    which the layer takes here, against the same layer with only the
    full-length branch traced: the value and every gradient (x, router, held
    experts), and the counters."""
    net, params, state, _, _, _, _ = family_setup(request, family)
    c = net.lm
    p, bias = params["layer_1"], state["layer_1"]["router_bias"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, c.seq_len, c.hidden_size))
    ct = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def run():
        def fn(p_, x_):
            y, _, counters, _ = ops.expert_layer(p_, bias, x_, top_k=c.num_experts_per_tok, scaling=c.routed_scaling_factor,
                                                 held=net.experts_held, share_index=c.expert_share_index)
            return jnp.sum(y * ct), (y, counters)
        (_, (y, counters)), grads = jax.jit(jax.value_and_grad(fn, argnums=(0, 1), has_aux=True))(
            {k: p[k] for k in ("router", "experts")}, x)
        return y, grads, {k: float(v) for k, v in counters.items()}

    y, grads, counters = run()
    monkeypatch.setattr(ops, "capacity_rows", lambda assignments, held, n_experts: assignments)
    full_y, full_grads, full_counters = run()
    assert counters.pop("bounded") == 1.0 and full_counters.pop("bounded") == 0.0
    assert counters == full_counters and counters["dropped"] == 0.0 and 0 < counters["assignments_here"]
    np.testing.assert_allclose(y, full_y, atol=1e-6)
    assert worst_leaf(grads, full_grads) < 1e-5
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("held_rows, bounded", [(64, 1.0), (65, 0.0)])
def test_a_held_count_of_the_capacity_is_bounded_and_one_more_falls_back(setup, held_rows, bounded):
    """Capacity 64 (2 x 64 tokens, top-2: 256 assignments, 2 of 16 experts
    held). A router that sends exactly `held_rows` tokens to a held expert
    (and to one held elsewhere) and every other token to two experts held
    elsewhere: 64 rows take the bounded branch, 65 the full-length one; both
    drop nothing, give the reference's result and its gradients."""
    net, params, _, _, _, _, _ = setup
    assert ops.capacity_rows(2 * 64 * 2, 2, 16) == 64
    router = jnp.zeros((LM.hidden_size, 16)).at[0, 2].set(10.0).at[1, 7].set(10.0).at[2, 5].set(10.0).at[3, 6].set(10.0)
    p = {**params["layer_1"], "router": router}
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(7), (2, 64, LM.hidden_size)).at[..., :4].set(0.0)
    to_held = (jax.random.permutation(jax.random.PRNGKey(8), 2 * 64) < held_rows).reshape(2, 64)
    x = x.at[..., 0:2].set(jnp.where(to_held[..., None], 1.0, 0.0)).at[..., 2:4].set(jnp.where(to_held[..., None], 0.0, 1.0))
    bias = jnp.zeros((16,))

    def fn(experts, x_):
        y, load, counters, _ = ops.expert_layer({**p, "experts": experts}, bias, x_, top_k=2, scaling=1.8, held=2, share_index=1)
        return jnp.sum(jnp.square(y)), (y, load, counters)

    (_, (y, load, counters)), grads = jax.value_and_grad(fn, argnums=(0, 1), has_aux=True)(p["experts"], x)
    assert float(counters["bounded"]) == bounded and float(counters["dropped"]) == 0.0
    assert float(counters["assignments_here"]) == held_rows and load.tolist()[2:4] == [held_rows, 0]

    def reference(experts, x_):
        return jnp.stack([ref.experts({**p, "experts": experts}, bias, row, ref.dims_of(LM))[0] for row in x_])

    np.testing.assert_allclose(y, reference(p["experts"], x), atol=2e-5)
    want = jax.grad(lambda e, x_: jnp.sum(jnp.square(reference(e, x_))), argnums=(0, 1))(p["experts"], x)
    assert worst_leaf(grads, want) < 2e-5


def as_lowered_for_a_tpu(patch):
    """What `ops.causal_attention` takes where a step is lowered for a TPU, on
    the CPU: `lax.platform_dependent` picks its `tpu` branch and the kernels
    of ops/lm_attention.py run in Pallas interpret mode. Everything but Mosaic
    (tests/test_tpu_aot.py has that)."""
    patch.setattr(ops.lax, "platform_dependent", lambda *args, tpu, default: tpu(*args))
    for name in ("attention_fwd", "attention_bwd"):
        patch.setattr(lm_attention, name, functools.partial(getattr(lm_attention, name), interpret=True))


@pytest.mark.parametrize("how, block, dims, dtype", [
    *[("loops", block, (16, 24), jnp.float32) for block in (1, 8, 16, 32)],
    *[("fused", 256, dims, dtype) for dims in ((128, 128), (256, 256)) for dtype in (jnp.float32, jnp.bfloat16)],
], ids=lambda x: x if isinstance(x, (str, int)) else "x".join(map(str, x)) if isinstance(x, tuple) else x.__name__)
def test_blocked_attention_and_its_backward_equal_plain_causal_attention(monkeypatch, how, block, dims, dtype):
    """ops.causal_attention against softmax over a dense mask, value and all
    three gradients. `loops`: one loop body over the tiles on or below the
    diagonal and a hand-written backward, for tiles from one row to the whole.
    `fused`: the TPU kernels (interpret mode) at lane-wide head dims over three
    blocks of 256 rows (512 on the chip): in float32 as tight as the loops; in
    bfloat16 no further from the float32 truth than the loops' own bfloat16 at
    that block."""
    seq, heads = (32, 4) if how == "loops" else (3 * block, 2)
    key = jax.random.PRNGKey(2)
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), (2, seq, heads, d))
                  for i, d in enumerate((dims[0], dims[0], dims[1], dims[1])))
    scale = 0.25 if how == "loops" else dims[0] ** -0.5

    def plain(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -jnp.inf)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        return jnp.sum(out * w), out

    def blocked(q, k, v):
        out = ops.causal_attention(q.astype(dtype), k.astype(dtype), v.astype(dtype), scale=scale, block=block)
        out = out.astype(jnp.float32)
        return jnp.sum(out * w), out

    def through_the_dispatch():
        """((sum of the weighted output, the output), the sum's three gradients)."""
        return jax.jit(jax.value_and_grad(blocked, (0, 1, 2), has_aux=True))(q, k, v)

    want = jax.jit(jax.value_and_grad(plain, (0, 1, 2), has_aux=True))(q, k, v)
    if how == "loops":
        loops = through_the_dispatch()
        assert abs(float(loops[0][0]) - float(want[0][0])) < 1e-4
        assert worst_leaf(loops[1], want[1]) < 1e-5
        return
    # over 768 rows the weighted sum cancels to a thousandth of a term: the value held to is the output itself
    want = want[0][1], want[1]
    if dtype == jnp.bfloat16:  # what the dispatch takes: through causal_attention and its custom_vjp
        assert lm_attention.fuses(seq, block, *dims, dtype)
        loops = through_the_dispatch()  # a CPU lowering
        as_lowered_for_a_tpu(monkeypatch)
        got = through_the_dispatch()
        loops, got = (loops[0][1], loops[1]), (got[0][1], got[1])
        assert 1e-4 < worst_leaf(loops, want) < 2e-2  # bfloat16 is visible, and the loops are what they were
        assert worst_leaf(got, want) <= 1.05 * worst_leaf(loops, want)
    else:  # float32 is the interpreter's alone (Mosaic refuses the backward): the two kernel calls themselves
        assert not lm_attention.fuses(seq, block, *dims, dtype)
        as_lowered_for_a_tpu(monkeypatch)
        heads_lead = functools.partial(jnp.swapaxes, axis1=1, axis2=2)
        out, lse = lm_attention.attention_fwd(*map(heads_lead, (q, k, v)), scale, block)
        grads = lm_attention.attention_bwd(*map(heads_lead, (q, k, v)), out, lse, heads_lead(w), scale, block)
        got = heads_lead(out), tuple(map(heads_lead, grads))
        assert worst_leaf(got, want) < 1e-5
    assert all(g.dtype == jnp.float32 and g.shape == x.shape for g, x in zip(got[1], (q, k, v)))


def test_on_the_cpu_attention_lowers_to_the_loops_at_every_shape(setup):
    """The kernels are for a TPU lowering alone: at a shape they take, a CPU
    lowering holds the tile loops and no Mosaic call; and the toy token step's
    lowered module is pinned byte for byte, float32 and bfloat16 (digests taken
    at PR 34, whose expert layers are each one `lax.cond` between the rows held
    and every assignment's, forward and backward; b6f613dc73fcefba /
    933950a18e0716d0 from PR 32, whose layer checkpoint keeps attention's
    output and log-sum-exp, to PR 33: the module of 61078ea, the commit before
    the kernels, less the backward's second forward loops)."""
    import hashlib

    assert lm_attention.fuses(1024, 512, 128, 128, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((1, 1024, 1, 128), jnp.bfloat16)
    text = jax.jit(jax.grad(lambda q, k, v: jnp.sum(ops.causal_attention(q, k, v, scale=0.1, block=512).astype(jnp.float32)),
                            (0, 1, 2))).lower(x, x, x).as_text()
    assert "stablehlo.while" in text and "tpu_custom_call" not in text
    net, params, state, tokens, _, _, _ = setup
    for dtype, digest in ((jnp.float32, "dbc40fdaadfbcd5e"), (jnp.bfloat16, "002fcb076a545b6a")):
        text = program.lower(net, params, state, tokens, dtype).as_text()
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def layer_checkpoint(patch, how):
    """The token model's checkpoints as shipped (`kept`), with the policy taken
    off (`plain`: a layer keeps its input alone, as before PR 32), or gone
    (`none`: every intermediate is kept)."""
    real = jax.checkpoint
    if how != "kept":
        patch.setattr(jax, "checkpoint", (lambda fn, **kw: real(fn)) if how == "plain" else (lambda fn, **kw: fn))


def attention_runs(jaxpr, found=None):
    """How often the gradient's jaxpr holds each half of the tile loops: the
    forward's key loop is the one `while` with the row maximum's
    `optimization_barrier` in its body, the backward's the one without."""
    from jax._src.core import jaxprs_in_params as inner

    def holds(jaxpr, primitive):
        return any(e.primitive.name == primitive or any(holds(j, primitive) for j in inner(e.params)) for e in jaxpr.eqns)

    found = {"fwd": 0, "bwd": 0} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            found["fwd" if any(holds(j, "optimization_barrier") for j in inner(eqn.params)) else "bwd"] += 1
        else:
            for j in inner(eqn.params):
                attention_runs(j, found)
    return found


def kda_scans(jaxpr, found=None):
    """How often the gradient's jaxpr holds the KDA core's scan over chunks,
    forward and reverse: the `scan`s whose ONE carry is a state a head,
    (B, H, key, value). (The head loss's scans carry three sums or a weight's
    gradient; the in-chunk work's map over head groups carries nothing.)"""
    from jax._src.core import jaxprs_in_params as inner

    found = {"fwd": 0, "bwd": 0} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["num_carry"] == 1 and len(
                eqn.outvars[0].aval.shape) == 4 and eqn.outvars[0].aval.shape[-1] == eqn.outvars[0].aval.shape[-2]:
            found["bwd" if eqn.params["reverse"] else "fwd"] += 1
        else:
            for j in inner(eqn.params):
                kda_scans(j, found)
    return found


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("how, forwards_a_site", [("kept", 1), ("plain", 2), ("none", 1)])
def test_the_gradient_runs_each_mixers_forward_once_a_site(request, monkeypatch, family, how, forwards_a_site):
    """The layer checkpoint keeps attention's output and log-sum-exp by name
    (`ops.ATTN_OUT_NAME`, `ops.ATTN_LSE_NAME`) and the KDA scan's output and
    chunk-boundary states (`lm_kda.KDA_OUT_NAME`, `KDA_STATES_NAME`), so the
    backward's second run of a layer holds neither forward: one forward and
    one backward a site in the gradient's jaxpr, where a plain
    `jax.checkpoint` holds two forwards."""
    net, params, state, tokens, _, _, _ = family_setup(request, family)
    sites = net.attention_sites(jnp.float32)[0]
    assert (sites, net.kda_sites) == ((4, 0) if family == "glm" else (1, 4))
    layer_checkpoint(monkeypatch, how)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: net.loss(p, state, {"tokens": tokens})[0]))(params)
    assert attention_runs(jaxpr.jaxpr) == {"fwd": forwards_a_site * sites, "bwd": sites}
    assert kda_scans(jaxpr.jaxpr) == {"fwd": forwards_a_site * net.kda_sites, "bwd": net.kda_sites}


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("how", ["plain", "none"])
def test_what_the_layer_checkpoint_keeps_changes_no_number(request, monkeypatch, family, how):
    """Loss, scalars, new state and every gradient leaf of the step as shipped
    equal, to float rounding, those of the same loss with the checkpoint's
    policy taken off and with no checkpoint at all: the kept `out` and `lse`
    (and a KDA scan's output and states) are what the second run made."""
    net, params, state, tokens, _, _, _ = family_setup(request, family)

    def step(p):
        return jax.value_and_grad(lambda p_: net.loss(p_, state, {"tokens": tokens}), has_aux=True)(p)

    (loss, aux), grads = jax.jit(step)(params)
    layer_checkpoint(monkeypatch, how)
    (want_loss, want_aux), want = jax.jit(lambda p: step(p))(params)  # a new function: `step` itself is traced already
    assert abs(float(loss) - float(want_loss)) < 1e-6
    assert worst_leaf(aux, want_aux) < 1e-6
    # kimi's five layers, four of them with a triangular solve: XLA fuses the three variants' float32 sums
    # differently (1.5e-6 and 8.1e-6 read; program against reference reads 1.3e-5)
    assert worst_leaf(grads, want) < (1e-6 if family == "glm" else 2e-5)


@pytest.mark.parametrize("shape, dtype, expect", [
    ((8192, 512, 256, 256), jnp.bfloat16, True),  # the benchmark cell's: 2 x 8,192 tokens, 20 heads of 192 + 64 / 256
    ((8192, 512, 256, 256), jnp.float32, False),  # Mosaic refuses the backward kernel's float32 operands
    ((8192 + 256, 512, 256, 256), jnp.bfloat16, False),  # no multiple of the block
    ((32, 8, 16, 24), jnp.float32, False),  # this file's toy heads
    ((8192, 512, 192, 256), jnp.bfloat16, False),  # a head dim that does not fill the lanes
    ((8192, 512, 256, 256), jnp.float16, False),
    ((32768, 512, 256, 256), jnp.bfloat16, False),  # a sequence too long to hold resident
    ((8192, 128, 256, 256), jnp.bfloat16, False),  # Mosaic refuses the backward kernel's blocks of 128 rows
], ids=str)
def test_which_attention_calls_the_kernels_take(shape, dtype, expect):
    """`shape` is (sequence, block, qk head dim, v head dim)."""
    assert lm_attention.fuses(*shape, dtype) is expect


@pytest.mark.parametrize("shape, dtype, handed", [
    ((16384, 512, 192, 128), jnp.bfloat16, (256, 128)),  # kimi_linear's one latent-attention layer: 128 + 64 filled to 256
    ((8192, 512, 256, 256), jnp.bfloat16, (256, 256)),  # GLM's: fits as it is
    ((16384, 512, 192, 128), jnp.float32, (192, 128)),  # no filling makes float32 fit: the loops, unfilled
    ((32768, 512, 192, 128), jnp.bfloat16, (192, 128)),  # nor a sequence too long
    ((32, 8, 12, 16), jnp.float32, (12, 16)),  # this file's toy heads
], ids=str)
def test_q_and_k_are_filled_with_zero_channels_only_where_that_makes_the_kernels_fit(shape, dtype, handed):
    assert lm_attention.fitting_dims(*shape, dtype) == handed


def test_zero_filled_channels_change_no_number(monkeypatch):
    """`causal_attention` at shapes whose head dims it fills (q/k 192 -> 256
    with v 128 as it is; q/k and v 64 -> 128; one 256-row tile, bfloat16)
    against the same call with the filling switched off, so that the loops
    run at the dims as given, on the CPU: a zero channel adds 0.0 to every
    score, and one of v is a zero channel of the output, cut off. With q and
    k filled, output and the three gradients bit for bit; with v filled too,
    the output bit for bit and the gradients within one bfloat16 rounding
    (v's zero channels lengthen the float32 reduction that makes g v^T, so
    its order)."""
    monkeypatch.setattr(ops, "ATTN_BLOCK", 256)
    as_filled = lm_attention.fitting_dims

    def within_a_rounding(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return bool(jnp.all(jnp.abs(a - b) <= 2.0 ** -7 * jnp.maximum(jnp.abs(a), jnp.abs(b))))

    for qk_dim, v_dim, handed in ((192, 128, (256, 128)), (64, 64, (128, 128))):
        q, k = (jax.random.normal(jax.random.PRNGKey(i), (1, 256, 2, qk_dim), jnp.bfloat16) for i in (0, 1))
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 2, v_dim), jnp.bfloat16)
        assert as_filled(256, 256, qk_dim, v_dim, jnp.bfloat16) == handed

        def run():
            fn = lambda *a: ops.causal_attention(*a, scale=qk_dim ** -0.5)  # noqa: E731
            return jax.jit(lambda *a: (fn(*a), jax.grad(lambda *b: jnp.sum(fn(*b).astype(jnp.float32) ** 2),
                                                        (0, 1, 2))(*a)))(q, k, v)

        monkeypatch.setattr(lm_attention, "fitting_dims", as_filled)
        filled = run()
        handed_on = []
        monkeypatch.setattr(lm_attention, "fitting_dims",
                            lambda seq, block, qk, v_, dtype: handed_on.append((qk, v_)) or (qk, v_))
        plain = run()
        assert set(handed_on) == {(qk_dim, v_dim)}  # the unfilled side really reached the loops at the dims as given
        (out, grads), (want, want_grads) = filled, plain
        assert out.shape == want.shape and bool(jnp.all(out == want))
        same = (lambda a, b: bool(jnp.all(a == b))) if v_dim % 128 == 0 else within_a_rounding
        assert all(a.shape == b.shape and same(a, b) for a, b in zip(grads, want_grads))


def test_train_step_reports_how_many_attention_layers_the_kernels_take(monkeypatch):
    """make_train_step sets train.attn_sites / train.attn_fused_sites from the
    model's shapes and the platform the step is lowered for: 6 / 0 for the
    cell's model on the CPU, 6 / 6 for a TPU mesh, 4 / 0 for the toy model
    anywhere; and, third, train.attn_kept_sites, the layers whose attention
    output and log-sum-exp the layer checkpoint keeps: all, on every platform."""
    import os

    from yet_another_mobilenet_series_tpu.config import load_config
    from yet_another_mobilenet_series_tpu.obs.registry import get_registry
    from yet_another_mobilenet_series_tpu.train import optim, schedules, steps

    app = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "yet_another_mobilenet_series_tpu", "apps", "glm_4_7_flash_ep8_share.yml")
    cfg = load_config(app)
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 2, 10, 1)

    def gauges(net, **kw):
        params = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))[0]
        steps.make_train_step(net, cfg, optim.make_optimizer(cfg.optim, lr_fn, params), lr_fn, **kw)
        return tuple(get_registry().gauge(name).value
                     for name in ("train.attn_sites", "train.attn_fused_sites", "train.attn_kept_sites"))

    monkeypatch.setattr(ops, "ATTN_BLOCK", 512)  # the tile as shipped, which this file's fixture shrinks
    cell = get_model(cfg.model)
    assert cfg.train.compute_dtype == "bfloat16" and cell.attention_sites(jnp.bfloat16) == (6, 6)
    assert gauges(cell) == (6.0, 0.0, 6.0)
    assert gauges(cell, platform="cpu") == (6.0, 0.0, 6.0)
    assert gauges(cell, platform="tpu") == (6.0, 6.0, 6.0)
    assert gauges(model(), platform="tpu") == (4.0, 0.0, 4.0)
    assert tuple(get_registry().gauge(name).value for name in ("train.kda_sites", "train.kda_kept_sites")) == (0.0, 0.0)
    # kimi_linear's cell: ONE latent-attention layer, whose (192, 128) head dims the kernels take with q and k
    # filled to 256, and four KDA layers, each keeping its scan's output and states
    kimi = get_model(load_config(app.replace("glm_4_7_flash_ep8_share", "kimi_linear_48b_ep32_share")).model)
    assert kimi.attention_sites(jnp.bfloat16) == (1, 1) and kimi.kda_sites == 4
    assert gauges(kimi, platform="tpu") == (1.0, 1.0, 1.0) and gauges(kimi, platform="cpu") == (1.0, 0.0, 1.0)
    assert tuple(get_registry().gauge(name).value for name in ("train.kda_sites", "train.kda_kept_sites")) == (4.0, 4.0)


@pytest.mark.parametrize("family, sites, rows, cell, cell_sites, cell_rows", [
    ("glm", 3, 64, "glm_4_7_flash_ep8_share", 5, 16384), ("kimi", 4, 128, "kimi_linear_48b_ep32_share", 4, 8192)])
def test_train_step_reports_its_expert_sites_their_capacity_and_how_many_ran_bounded(request, family, sites, rows, cell,
                                                                                     cell_sites, cell_rows):
    """Beside `train.attn_sites`: `train.moe_sites` where the step is built
    (the toy GLM's two expert layers and MTP block, kimi's four; 5 and 4 for
    the cells' models), `train.moe_capacity_rows` where it is traced, from the
    batch one replica sees (64 of 128 assignment rows, 128 of 256; 16,384 of
    65,536 and 8,192 of 131,072 at the cells' batches), and the step scalar
    `moe_bounded_sites`: every site of a step whose held rows fit."""
    import os

    from yet_another_mobilenet_series_tpu.config import load_config
    from yet_another_mobilenet_series_tpu.obs.registry import get_registry
    from yet_another_mobilenet_series_tpu.train import optim, schedules, steps

    net, params, state, tokens, _, _, _ = family_setup(request, family)
    app = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "yet_another_mobilenet_series_tpu", "apps", cell + ".yml")
    cfg = load_config(app)
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 2, 10, 1)
    optimizer = optim.make_optimizer(cfg.optim, lr_fn, params)
    gauge = lambda name: get_registry().gauge(name).value  # noqa: E731
    get_registry().gauge("train.moe_capacity_rows").set(-1.0)
    step = steps.make_train_step(net, cfg, optimizer, lr_fn)
    assert (gauge("train.moe_sites"), gauge("train.moe_capacity_rows")) == (sites, -1.0)
    ts = jax.eval_shape(lambda: steps.init_train_state(net, cfg, optimizer, jax.random.PRNGKey(0)))
    _, metrics = jax.eval_shape(step, ts, {"tokens": tokens}, jax.random.PRNGKey(1))
    assert gauge("train.moe_capacity_rows") == rows == net.expert_capacity_rows(tokens.shape[0])
    assert "moe_bounded_sites" in metrics and "moe_dropped" in metrics
    (_, (_, scalars)), _ = program(net, params, state, tokens)
    assert float(scalars["moe_bounded_sites"]) == sites and float(scalars["moe_dropped"]) == 0.0
    published = get_model(cfg.model)
    steps.make_train_step(published, cfg, optimizer, lr_fn)
    batch = {"glm": 2, "kimi": 1}[family]  # the cells' sequences a step
    assert (gauge("train.moe_sites"), published.expert_capacity_rows(batch)) == (cell_sites, cell_rows)


_FRESH_PROCESS = """
import json, sys
import jax, jax.numpy as jnp
from yet_another_mobilenet_series_tpu.config import parse_cli
from yet_another_mobilenet_series_tpu.models import get_model, lm
from yet_another_mobilenet_series_tpu.ops import lm as ops, lm_attention
from yet_another_mobilenet_series_tpu.parallel import dp, mesh as mesh_lib
from yet_another_mobilenet_series_tpu.train import optim, schedules, steps

def pallas():
    return sorted(m for m in sys.modules if m.startswith(("jax.experimental.pallas", "jax._src.pallas")))

if sys.argv[1] == "cnn_step":  # one toy CNN train step, built as every runner builds it, and run
    cfg = parse_cli(["app:" + sys.argv[2], "model.block_specs=[{exp: 16, c: 16, n: 1, s: 2, k: 3, act: relu}]",
                     "model.num_classes=16", "data.image_size=32", "train.batch_size=4", "dist.num_devices=1"])
    net = get_model(cfg.model, 32)
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 4, 10, 1)
    optimizer = optim.make_optimizer(cfg.optim, lr_fn, jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))[0])
    step = dp.make_dp_train_step(net, cfg, optimizer, lr_fn, mesh_lib.make_mesh(1))
    ts = steps.init_train_state(net, cfg, optimizer, jax.random.PRNGKey(0))
    batch = {"image": jnp.ones((4, 32, 32, 3), jnp.float32), "label": jnp.arange(4, dtype=jnp.int32)}
    ts, metrics = step(ts, batch, jax.random.PRNGKey(1))
    ran = bool(jnp.isfinite(metrics["loss"])) and int(ts.step) == 1
elif sys.argv[1] == "kda_step":  # a toy kimi_linear step, KDA and the (unfused) latent attention traced
    cfg = parse_cli(["app:" + sys.argv[2], "model.num_classes=64", "model.lm.hidden_size=32", "model.lm.num_attention_heads=2",
                     "model.lm.kv_lora_rank=8", "model.lm.qk_nope_head_dim=8", "model.lm.qk_rope_head_dim=4",
                     "model.lm.v_head_dim=8", "model.lm.linear_attn_config.head_dim=8", "model.lm.linear_attn_config.num_heads=2",
                     "model.lm.intermediate_size=64", "model.lm.moe_intermediate_size=16", "model.lm.n_routed_experts=64",
                     "model.lm.seq_len=16", "train.batch_size=1", "dist.num_devices=1"])
    net = get_model(cfg.model)
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 1, 10, 1)
    optimizer = optim.make_optimizer(cfg.optim, lr_fn, jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))[0])
    step = dp.make_dp_train_step(net, cfg, optimizer, lr_fn, mesh_lib.make_mesh(1))
    ts = jax.eval_shape(lambda: steps.init_train_state(net, cfg, optimizer, jax.random.PRNGKey(0)))
    step.lower(ts, {"tokens": jax.ShapeDtypeStruct((1, 18), jnp.int32)}, jax.ShapeDtypeStruct((2,), jnp.uint32))
    ran = net.kda_sites == 4
else:  # the predicate and the gauges' count first, which must not need Pallas; then a fitting site traced
    cfg = parse_cli(["app:" + sys.argv[2]])
    fits = lm_attention.fuses(512, 256, 128, 128, jnp.bfloat16) and get_model(cfg.model).attention_sites(jnp.bfloat16) == (6, 6)
    before = pallas()
    x = jax.ShapeDtypeStruct((1, 512, 1, 128), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: ops.causal_attention(q, k, v, scale=0.1, block=256), x, x, x)
    ran = fits and not before
print(json.dumps({"ran": ran, "pallas": pallas()}))
"""


@pytest.mark.parametrize("what, app, pays", [("cnn_step", "mobilenet_v3_large.yml", False),
                                             ("fitting_site", "glm_4_7_flash_ep8_share.yml", True),
                                             ("kda_step", "kimi_linear_48b_ep32_share.yml", False)])
def test_pallas_is_imported_where_a_fused_attention_site_is_traced_and_nowhere_else(what, app, pays):
    """A fresh process that imports what every runner imports (train.steps,
    parallel.dp, models.lm, ops.lm, ops.lm_attention) and builds and runs a
    CNN train step has no `jax.experimental.pallas*` module: the import costs
    1.2-1.5 s of every cell's `setup_s` on the chip's host, which PR 28 was
    refused for. The predicate and the gauges' count need none either; the
    `tpu` branch of a fitting attention site, once traced, brings it in (the
    deferral engages: no dead import)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", _FRESH_PROCESS, what, os.path.join(repo, "yet_another_mobilenet_series_tpu", "apps", app)],
        cwd=repo, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    said = json.loads(proc.stdout.strip().splitlines()[-1])
    assert said["ran"]
    if pays:
        assert {"jax.experimental.pallas", "jax.experimental.pallas.tpu"} <= set(said["pallas"]), said
    else:
        assert not said["pallas"], said


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


def test_the_pattern_puts_latent_attention_exactly_where_full_attn_layers_says():
    """Layers are numbered from 1, as `linear_attn_config` numbers them; a
    cut reads the first `num_hidden_layers` entries of the published lists."""
    net = model(KIMI)
    assert [net.mixer(b) for b in net.block_names] == ["kda", "kda", "kda", "attn", "kda"]
    assert net.blocks_mixing_by("attn") == ("layer_3",) and net.kda_sites == 4
    shapes = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))[0]
    assert ["kda" in shapes[b] for b in net.block_names] == [True, True, True, False, True]
    assert set(shapes["layer_3"]["attn"]) == {"q", "kv_a", "kv_norm", "kv_b", "o"}  # one q projection, no q norm
    assert shapes["layer_3"]["attn"]["q"].shape == (64, 4 * 12)
    eight = model(dataclasses.replace(KIMI, num_hidden_layers=8))
    assert [eight.mixer(b) for b in eight.block_names] == ["kda"] * 3 + ["attn"] + ["kda"] * 3 + ["attn"]
    assert network_from_dict(network_to_dict(net)) == net
    # GLM: no pattern, every block (the MTP module's too) is latent attention
    assert [model().mixer(b) for b in model().block_names] == ["attn"] * 4


@pytest.mark.parametrize("change, complaint", [
    ({"kda_layers": (1, 2, 3, 4, 5)}, "in both"),
    ({"kda_layers": (1, 2, 5), "full_attn_layers": (4,)}, r"layers \[3\] are in neither"),
    ({"full_attn_layers": ()}, r"layers \[4\] are in neither"),
])
def test_validate_refuses_a_pattern_that_overlaps_or_leaves_a_layer_out(change, complaint):
    pattern = dataclasses.replace(KIMI.linear_attn_config, **change)
    with pytest.raises(ValueError, match=complaint):
        model(dataclasses.replace(KIMI, linear_attn_config=pattern))


def test_validate_refuses_an_odd_rope_dim_only_where_a_layer_mixes_by_latent_attention():
    with pytest.raises(ValueError, match="qk_rope_head_dim must be even.*layer_3"):
        model(dataclasses.replace(KIMI, qk_rope_head_dim=3))
    all_kda = LinearAttnConfig(kda_layers=(1, 2, 3, 4, 5), head_dim=8, num_heads=4)
    model(dataclasses.replace(KIMI, qk_rope_head_dim=3, linear_attn_config=all_kda))  # nothing reads it


def test_latent_attention_without_rope_and_without_a_low_rank_q_equals_the_reference(kimi_setup):
    """`mla_use_nope` + `q_lora_rank: null`: ops.mla_attention with `cos`
    None and `p["q"]` against the reference's expanded attention; and RoPE, if
    it were applied there, would be seen."""
    net, params, _, _, _, _, _ = kimi_setup
    x = jax.random.normal(jax.random.PRNGKey(2), (2, KIMI.seq_len, KIMI.hidden_size))
    kw = dict(heads=4, nope=8, rope=4, v_dim=8, kv_rank=16, eps=1e-5)
    got = ops.mla_attention(params["layer_3"]["attn"], x, None, None, **kw)
    for row in range(2):
        np.testing.assert_allclose(got[row], ref.mla(params["layer_3"]["attn"], x[row], ref.dims_of(KIMI)), atol=2e-5)
    cos, sin = ops.rope_tables(KIMI.seq_len, 4, 10000.0)
    turned = ops.mla_attention(params["layer_3"]["attn"], x, cos, sin, **kw)
    assert float(jnp.max(jnp.abs(turned - got))) > 1e-3


def test_the_published_widths_of_kimi_linear_give_the_parameter_count_of_the_cut_and_of_the_source():
    from yet_another_mobilenet_series_tpu.config import load_config
    import os

    app = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "yet_another_mobilenet_series_tpu", "apps", "kimi_linear_48b_ep32_share.yml")
    cfg = load_config(app).model
    net = get_model(cfg)
    assert net.param_count() == 602_433_408 and net.experts_held == 8 and net.vocab == 20_480
    shapes = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))[0]
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa: E731
    assert (count(shapes["layer_0"]["kda"]), count(shapes["layer_3"]["attn"])) == (39_514_272, 29_114_880)
    # uncut: all 27 layers, all 256 experts, all 163,840 vocabulary rows
    uncut = dataclasses.replace(cfg, num_classes=163_840, lm=dataclasses.replace(cfg.lm, num_hidden_layers=27, expert_shares=1))
    whole = get_model(uncut)
    assert len(whole.blocks_mixing_by("kda")) == 20 and len(whole.blocks_mixing_by("attn")) == 7
    assert whole.param_count() == 49_122_675_072
