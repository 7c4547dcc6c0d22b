"""The hybrid arch of the token family (`granitemoehybrid`: Granite 4.0-H;
models/lm.py, ops/lm_mamba.py, ops/lm.py `mha_attention` with grouped heads
and no rotation) against its plain float32 reference (models/lm_reference.py
`granite_*`) at a toy size on the CPU: hidden 64, three layers (Mamba-2,
attention, Mamba-2), Mamba-2 8 heads of 16 channels with a state of 16 in
chunks of 8, attention 4 query and 2 key/value heads of 16, MLP 96, a tied
vocabulary of 32, 2 x 32 tokens; the multipliers of the published config
(the attention's scaled for the toy head). And the pieces: the chunked SSD
against the token-by-token recurrence, the xBC convolution's kernels with a
bias, attention with v filled and key/value heads repeated, the gauges.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_lm import as_lowered_for_a_tpu, worst_leaf

from yet_another_mobilenet_series_tpu.config import LMConfig, ModelConfig
from yet_another_mobilenet_series_tpu.models import get_model, lm, lm_reference as ref
from yet_another_mobilenet_series_tpu.obs import scopes
from yet_another_mobilenet_series_tpu.obs.registry import get_registry
from yet_another_mobilenet_series_tpu.ops import lm as ops
from yet_another_mobilenet_series_tpu.ops import lm_attention, lm_kda, lm_mamba

GRANITE = LMConfig(hidden_size=64, num_hidden_layers=3, first_k_dense_replace=3, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16, intermediate_size=96, num_nextn_predict_layers=0,
                   n_routed_experts=0, expert_shares=1, layer_types=("mamba", "attention", "mamba"),
                   mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
                   mamba_chunk_size=8, attention_multiplier=0.125, embedding_multiplier=12.0,
                   residual_multiplier=0.22, logits_scaling=8.0, tie_word_embeddings=True, seq_len=32, init_std=0.1)
VOCAB = 32


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    """32 tokens in tiles of 8 x 8 and the loss in blocks of 16, as tests/test_lm.py has them."""
    patch = pytest.MonkeyPatch()
    patch.setattr(ops, "ATTN_BLOCK", 8)
    patch.setattr(lm, "LOSS_BLOCK", 16)
    yield
    patch.undo()


def model(config=GRANITE):
    return get_model(ModelConfig(arch="granitemoehybrid", num_classes=VOCAB, lm=config))


@functools.lru_cache(maxsize=None)
def setup():
    """(net, params, tokens, the reference's loss, aux and gradients)."""
    net = model()
    params, state = net.init(jax.random.PRNGKey(0))
    assert state == {} and "head" not in params
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, GRANITE.seq_len + 2), 0, VOCAB)
    (ref_loss, aux), ref_grads = jax.jit(lambda p, t: ref.granite_loss_and_grads(p, t, ref.granite_dims_of(GRANITE)))(
        params, tokens)
    return net, params, tokens, ref_loss, aux, ref_grads


@functools.partial(jax.jit, static_argnums=(0, 3))
def program(net, params, tokens, dtype=jnp.float32):
    return jax.value_and_grad(lambda p: net.loss(p, {}, {"tokens": tokens}, compute_dtype=dtype), has_aux=True)(params)


# -- the model against its reference -------------------------------------------------------------------------------


def test_loss_and_every_gradient_leaf_equal_the_reference_in_float32():
    net, params, tokens, ref_loss, aux, ref_grads = setup()
    (loss, (new_state, scalars)), grads = program(net, params, tokens)
    assert new_state == {} and abs(float(loss) - float(ref_loss)) < 1e-5
    assert abs(float(scalars["ce"]) - float(aux["ce"])) < 1e-5
    assert set(scalars) == {"ce", "top1", "ssd_min_chunk_log_decay"}
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    assert worst_leaf(grads, ref_grads) < 2e-5
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree.leaves(grads))  # nothing is cut off from the loss


def test_logits_equal_the_reference():
    """The program never holds the logits: its tied head (the embedding,
    read transposed, and the division by `logits_scaling`) on the hidden
    states it hands to its loss, against the reference's logits."""
    net, params, tokens, _, aux, _ = setup()

    @jax.jit
    def main_logits(params):
        seen = []
        probe = dataclasses.replace(net)
        object.__setattr__(probe, "_head_loss", lambda w, hidden, t: (seen.append((w, hidden)), jnp.zeros(3))[1])
        probe.forward(params, {}, tokens)
        head, hidden = seen[0]
        return (hidden @ head / GRANITE.logits_scaling).reshape(2, GRANITE.seq_len, VOCAB), head

    logits, head = main_logits(params)
    np.testing.assert_array_equal(head, params["embed"].T)
    np.testing.assert_allclose(logits, aux["logits"], atol=2e-5)


def test_the_tied_vocabularys_gradient_is_the_sum_of_the_embeddings_and_the_heads():
    """An UNTIED copy of the model (its own `head` = E^T) against the tied one:
    the same loss, and the tied embedding's gradient is the embedding's plus
    the head's, transposed."""
    net, params, tokens, _, _, _ = setup()
    untied = model(dataclasses.replace(GRANITE, tie_word_embeddings=False))
    copy = {**params, "head": params["embed"].T}
    (loss_tied, _), tied = program(net, params, tokens)
    (loss_untied, _), split = program(untied, copy, tokens)
    assert abs(float(loss_tied) - float(loss_untied)) < 1e-6
    np.testing.assert_allclose(tied["embed"], split["embed"] + split["head"].T, atol=1e-6)
    # both uses count: the sum is neither one alone
    assert worst_leaf(tied["embed"], split["embed"]) > 0.1 and worst_leaf(tied["embed"], split["head"].T) > 0.1


def test_the_groups_the_step_reports_are_the_gradients_norms():
    net, params, tokens, _, _, ref_grads = setup()
    got = net.grad_scalars(program(net, params, tokens)[1])
    assert set(got) == {"gnorm/embed", "gnorm/final_norm", "gnorm/layer_0/mamba", "gnorm/layer_1/attn",
                        "gnorm/layer_2/mamba", *(f"gnorm/layer_{i}/{g}" for i in range(3) for g in ("mlp", "norms"))}
    norm = lambda tree: float(jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree))))  # noqa: E731
    for name, tree in (("gnorm/layer_0/mamba", ref_grads["layer_0"]["mamba"]), ("gnorm/embed", ref_grads["embed"]),
                       ("gnorm/layer_1/norms", [ref_grads["layer_1"]["attn_norm"], ref_grads["layer_1"]["mlp_norm"]])):
        assert float(got[name]) == pytest.approx(norm(tree), rel=1e-4), name


def test_bfloat16_is_within_its_tolerance_and_a_lower_precision_is_not():
    """bfloat16 compute against the float32 reference: loss within 2e-3
    relative, every gradient group within 1% (bfloat16 reads at most 0.24%
    here, a layer's two norm gains). The same step with every weight rounded
    to float8_e4m3fn (the nearest precision below) must NOT pass (it reads
    up to 2.1%)."""
    net, params, tokens, ref_loss, _, ref_grads = setup()
    want = {**net.grad_scalars(ref_grads), "loss": ref_loss}

    def passes(p):
        (loss, _), grads = program(net, p, tokens, jnp.bfloat16)
        got = {**net.grad_scalars(grads), "loss": loss}
        return all(abs(float(got[k]) - float(want[k])) / float(want[k]) <= (2e-3 if k == "loss" else 1e-2) for k in want)

    assert passes(params)
    assert not passes(jax.tree.map(lambda w: w.astype(jnp.float8_e4m3fn).astype(jnp.float32), params))


def test_the_published_widths_give_the_parameter_count_of_the_cut(monkeypatch):
    import os

    from yet_another_mobilenet_series_tpu.config import load_config

    monkeypatch.setattr(ops, "ATTN_BLOCK", 512)  # the tile as shipped, which this file's fixture shrinks
    app = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "yet_another_mobilenet_series_tpu",
                       "apps", "granite_4_0_h_micro.yml")
    net = get_model(load_config(app).model)
    assert net.arch == "granitemoehybrid" and net.vocab == 12544 and net.lm.tie_word_embeddings
    assert net.param_count() == 772_160_448
    assert [net.mixer(b) for b in net.block_names] == ["mamba"] * 5 + ["attn"] + ["mamba"] * 4
    assert net.ssd_sites == 9 and net.attention_sites(jnp.bfloat16) == (1, 1) and net.expert_sites == 0


@pytest.mark.parametrize("change, complaint", [
    ({"layer_types": ("mamba", "attention")}, "layer_types"),
    ({"layer_types": ("mamba", "linear", "mamba")}, "layer_types"),
    ({"num_key_value_heads": 3}, "dividing"),
    ({"first_k_dense_replace": 1}, "no expert layer"),
    ({"num_nextn_predict_layers": 1}, "no MTP module"),
], ids=lambda x: x if isinstance(x, str) else "-".join(x))
def test_validate_refuses_what_the_arch_does_not_run(change, complaint):
    with pytest.raises(ValueError, match=complaint):
        model(dataclasses.replace(GRANITE, **change))


def test_the_other_archs_read_no_layer_types():
    from test_lm import LM

    with pytest.raises(ValueError, match="reads no layer_types"):
        get_model(ModelConfig(arch="glm4_moe_lite", num_classes=VOCAB, lm=dataclasses.replace(LM, layer_types=("mamba",))))


# -- the chunked SSD against the recurrence --------------------------------------------------------------------------


def recurrence(x, delta, log_decay, b, c, d_skip):
    """The SSD's recurrence a token at a time, float32: x (B, S, H, P), delta
    and log_decay (B, S, H), b and c (B, S, K), d_skip (H,) -> y (B, S, H, P)."""
    def token(state, inputs):  # state (B, H, P, K)
        x_t, dt_t, a_t, b_t, c_t = inputs
        state = jnp.exp(a_t)[..., None, None] * state + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, jnp.einsum("bhpk,bk->bhp", state, c_t) + d_skip[:, None] * x_t

    batch, _, heads, width = x.shape
    start = jnp.zeros((batch, heads, width, b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(token, start, tuple(jnp.moveaxis(t, 1, 0) for t in (x, delta, log_decay, b, c)))
    return jnp.moveaxis(y, 0, 1)


def ssd_operands(seq, heads=8, fast=1.0, seed=0):
    """Operands of `ssd_core` at seed `seed`: Delta in (0, 0.3), Delta A
    scaled by `fast` (16 makes a chunk of 8 sum past -88 in some heads)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (2, seq, heads, 16))
    delta = 0.3 * jax.nn.sigmoid(jax.random.normal(ks[1], (2, seq, heads)))
    rate = fast * jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=math.log(16.0)))
    b, c = (jax.random.normal(k, (2, seq, 16)) for k in ks[3:5])
    return x, delta, -rate * delta, b, c, jax.random.normal(ks[5], (heads,))


@pytest.mark.parametrize("chunk, seq, fast", [
    (8, 32, 1.0), (4, 32, 1.0), (16, 32, 1.0), (32, 32, 1.0), (2, 32, 1.0), (8, 30, 1.0),  # whole and ragged chunks
    (8, 32, 16.0), (16, 48, 16.0),  # decays whose in-chunk sum passes float32's exp limit
], ids=lambda x: str(x))
def test_the_chunked_ssd_and_its_gradient_equal_the_recurrence(chunk, seq, fast):
    """`lm_mamba.ssd_core` (chunks of `chunk`, the state carried by the chunk
    scan) against the SSD's recurrence token by token, output and every
    operand's gradient, float32. With `fast` 16 the in-chunk cumulative log
    decay passes -88: nothing is clamped, nothing overflows, and the reported
    minimum says so."""
    operands = ssd_operands(seq, fast=fast)
    w = jax.random.normal(jax.random.PRNGKey(9), operands[0].shape)

    def chunked(*a):
        y, lowest = lm_mamba.ssd_core(*a, chunk=chunk)
        return jnp.sum(y * w), (y, lowest)

    def plain(*a):
        y = recurrence(*a)
        return jnp.sum(y * w), y

    with jax.default_matmul_precision("highest"):
        (_, (got, lowest)), got_grads = jax.jit(jax.value_and_grad(chunked, range(6), has_aux=True))(*operands)
        (_, want), want_grads = jax.jit(jax.value_and_grad(plain, range(6), has_aux=True))(*operands)
    assert worst_leaf(got, want) < 2e-5
    assert worst_leaf(got_grads, want_grads) < 5e-5
    padded = jnp.pad(operands[2], ((0, 0), (0, -seq % chunk), (0, 0)))
    assert float(lowest) == pytest.approx(float(jnp.min(jnp.cumsum(padded.reshape(2, -1, chunk, 8), axis=2))), rel=1e-5)
    assert (float(lowest) < -88.0) is (fast > 1.0)


def test_the_decay_written_as_a_product_of_exponentials_overflows_where_the_differences_do_not(monkeypatch):
    """Why the in-chunk decays are `exp` of DIFFERENCES: `e^{G_t} e^{-G_s}`
    at decays past -88 gives inf x 0, and the output is not finite."""
    operands = ssd_operands(32, fast=16.0)

    def as_product(scores, cum, dx):
        rows = cum.shape[2]
        below = jnp.tril(jnp.ones((rows, rows), bool))[..., None]
        decays = jnp.where(below, jnp.exp(cum)[..., :, None, :] * jnp.exp(-cum)[..., None, :, :], 0.0)
        return jnp.einsum("bntsh,bnshp->bnthp", (scores[..., None] * decays).astype(dx.dtype), dx)

    assert bool(jnp.all(jnp.isfinite(lm_mamba.ssd_core(*operands, chunk=8)[0])))
    monkeypatch.setattr(lm_mamba, "_in_chunk", as_product)
    assert not bool(jnp.all(jnp.isfinite(lm_mamba.ssd_core(*operands, chunk=8)[0])))


def test_the_layer_checkpoint_keeps_the_chunk_states_and_the_backward_runs_no_second_chunk_scan():
    """Under the layer checkpoint the gradient's jaxpr holds the chunk scan's
    forward ONCE a Mamba-2 layer (its chunk-boundary states kept by name,
    `SSD_STATES_NAME`), not twice; the kept names include it."""
    from jax._src.core import jaxprs_in_params as inner

    assert lm_mamba.SSD_STATES_NAME in lm.KEPT_NAMES
    net, params, tokens, _, _, _ = setup()

    state = (2, GRANITE.mamba_n_heads, GRANITE.mamba_d_head, GRANITE.mamba_d_state)

    def forward_scans(jaxpr):  # scans that carry a (B, H, P, K) state forward over the chunks
        count = 0
        for e in jaxpr.eqns:
            if (e.primitive.name == "scan" and not e.params["reverse"] and e.params["length"] == GRANITE.seq_len // 8
                    and e.outvars[0].aval.shape == state):
                count += 1
            count += sum(forward_scans(j) for j in inner(e.params))
        return count

    jaxpr = jax.make_jaxpr(jax.grad(lambda p: net.loss(p, {}, {"tokens": tokens})[0]))(params).jaxpr
    assert forward_scans(jaxpr) == net.ssd_sites == 2


# -- the xBC convolution's kernels with a bias --------------------------------------------------------------------


@pytest.mark.parametrize("seq, channels, rows, lanes", [(96, 384, 32, 256), (64, 256, 16, 512), (48, 128, 48, 128)],
                         ids=str)
def test_the_conv_kernels_with_a_bias_equal_short_conv_plus_the_bias_both_ways(monkeypatch, seq, channels, rows, lanes):
    """`conv_fwd` / `conv_bwd` with a bias operand (Pallas interpret mode),
    against `short_conv` with the bias and its vjp: the output, dz, dw and
    db, every tile boundary crossed both ways; lanes that divide the channels
    (384 = 3 bands of 128: a tile of 128 lanes where 256 do not divide)."""
    from yet_another_mobilenet_series_tpu.ops import lm_kda_kernels as kernels

    monkeypatch.setattr(kernels, "CONV_ROWS", rows)
    monkeypatch.setattr(kernels, "CONV_LANES", lanes)
    jax.clear_caches()
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    z = jax.random.normal(ks[0], (2, seq, channels)).astype(jnp.bfloat16)
    w, bias = 0.5 * jax.random.normal(ks[1], (4, channels)), jax.random.normal(ks[2], (channels,))
    ct = jax.random.normal(ks[3], z.shape).astype(jnp.bfloat16)
    assert lm_mamba.conv_fuses(seq, channels, 4, jnp.bfloat16)
    assert kernels.conv_cut(seq, channels, lm_mamba.CONV_BAND)[1] in (128, 256, 512)
    kw = {"bias": bias, "name": "ssd_conv"}
    got = (jax.jit(lambda *a: lm_kda.conv_fwd(*a, lm_mamba.CONV_BAND, None, True, **kw))(z, w),
           *jax.jit(lambda *a: lm_kda.conv_bwd(*a, lm_mamba.CONV_BAND, None, True, **kw))(z, w, ct))
    want_out, pull = jax.vjp(lambda z_, w_, b_: lm_kda.short_conv(z_, w_, b_, "ssd_conv"), z, w, bias)
    want = (want_out, *pull(ct))
    jax.clear_caches()
    assert [(x.shape, x.dtype) for x in got] == [(x.shape, x.dtype) for x in want]
    devs = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))) / float(jnp.max(jnp.abs(b))))
            for a, b in zip(got, want)]
    assert all(d < limit for d, limit in zip(devs, (1e-2, 1.5e-2, 5e-3, 5e-3))), devs


def test_kimis_unbiased_conv_call_is_what_it_was():
    """A KDA site passes no bias: its kernels take the operands and make the
    instructions they always did (`kda_conv_fwd` / `_bwd`, no bias operand),
    and the plain form calls `short_conv(z, w)`."""
    import inspect

    z = jax.ShapeDtypeStruct((1, 64, 256), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((4, 256), jnp.float32)

    def kernel_calls(fn, *shapes):  # (name, operand count) of every pallas_call in the jaxpr
        found = []

        def walk(jaxpr):
            for e in jaxpr.eqns:
                if e.primitive.name == "pallas_call":
                    found.append((e.params["name"], len(e.invars)))
                for sub in inner(e.params):
                    walk(sub)

        walk(jax.make_jaxpr(fn)(*shapes).jaxpr)
        return found

    from jax._src.core import jaxprs_in_params as inner

    assert kernel_calls(lambda z_, w_: lm_kda.conv_fwd(z_, w_, 128, 0.5, True), z, w) == [("kda_conv_fwd", 3)]
    assert kernel_calls(lambda z_, w_, c_: lm_kda.conv_bwd(z_, w_, c_, 128, 0.5, True), z, w, z) == [("kda_conv_bwd", 6)]
    b = jax.ShapeDtypeStruct((256,), jnp.float32)
    assert kernel_calls(lambda z_, w_, b_: lm_kda.conv_fwd(z_, w_, 128, None, True, bias=b_, name="ssd_conv"),
                        z, w, b) == [("ssd_conv_fwd", 4)]
    assert "short_conv(z, w) if bias is None" in inspect.getsource(lm_kda._plain_conv)
    assert len(jax.jit(lambda *a: lm_kda.conv_bwd(*a, 128, 0.5, True)).eval_shape(z, w, z)) == 2


def test_on_the_cpu_the_xbc_convolution_is_the_plain_one_under_its_scope():
    """`mamba_mixer` at a shape the kernels take, lowered for the CPU: the
    plain `short_conv` with its bias (no Pallas call), under `ssd_conv`."""
    lm_cfg = dataclasses.replace(GRANITE, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=64, seq_len=64)
    net = model(lm_cfg)
    params, _ = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
    p = params["layer_0"]["mamba"]
    assert lm_mamba.conv_fuses(64, 128 + 128, 4, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((1, 64, 64), jnp.bfloat16)
    fn = functools.partial(lm_mamba.mamba_mixer, heads=8, head_dim=16, state=64, chunk=8, eps=1e-5)
    text = jax.jit(jax.grad(lambda p_, x_: jnp.sum(fn(p_, x_)[0].astype(jnp.float32)))).lower(p, x).compile().as_text()
    assert "tpu_custom_call" not in text and "ssd_conv_fwd" not in text
    seen = set(scopes.scope_table(text).values())
    assert {("ssd_conv", "fwd"), ("ssd_conv", "bwd"), ("ssd_core", "fwd"), ("ssd_core", "bwd")} <= seen
    assert {"ssd_proj", "ssd_gate", "ssd_norm"} <= {name for name, _ in seen}


# -- attention with v filled and the key/value heads repeated -------------------------------------------------------


@pytest.mark.parametrize("shape, dtype, handed", [
    ((8192, 512, 64, 64), jnp.bfloat16, (128, 128)),  # the hybrid's attention layer: both filled
    ((16384, 512, 192, 128), jnp.bfloat16, (256, 128)),  # kimi_linear's: q and k alone, as before
    ((8192, 512, 256, 256), jnp.bfloat16, (256, 256)),  # GLM's and ouro's kind: as they are
    ((8192, 512, 64, 64), jnp.float32, (64, 64)),  # nothing makes float32 fit: the loops, unfilled
], ids=str)
def test_v_is_filled_only_where_q_and_k_filled_are_not_enough(shape, dtype, handed):
    assert lm_attention.fitting_dims(*shape, dtype) == handed


def test_grouped_heads_with_v_filled_equal_plain_grouped_attention(monkeypatch):
    """`mha_attention` with 4 query heads on 2 key/value heads, no rotation,
    scale 1/64, at a shape whose 64-channel heads the kernels take with q, k
    AND v filled to 128 (256 rows, bfloat16): through the kernels (interpret
    mode) and through the loops, against softmax over a dense mask with query
    head i reading key/value head i // 2, output and every projection's
    gradient. The kernels are no further from the float32 truth than the loops."""
    monkeypatch.setattr(ops, "ATTN_BLOCK", 256)
    heads, kv, dim, h, seq = 4, 2, 64, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    p = {"q": jax.random.normal(ks[0], (h, heads * dim)) * 0.2, "k": jax.random.normal(ks[1], (h, kv * dim)) * 0.2,
         "v": jax.random.normal(ks[2], (h, kv * dim)) * 0.2, "o": jax.random.normal(ks[3], (heads * dim, h)) * 0.1}
    x = jax.random.normal(ks[4], (1, seq, h))
    assert lm_attention.fitting_dims(seq, 256, dim, dim, jnp.bfloat16) == (128, 128)

    def plain(p):
        q = (x @ p["q"]).reshape(1, seq, heads, dim)
        k, v = ((x @ p[n]).reshape(1, seq, kv, dim)[:, :, jnp.arange(heads) // (heads // kv)] for n in ("k", "v"))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 64.0
        s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -jnp.inf)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v).reshape(1, seq, heads * dim) @ p["o"]
        return jnp.sum(out * x), out

    def ours(p):
        out = ops.mha_attention(p, x.astype(jnp.bfloat16), None, None, heads=heads, head_dim=dim, kv_heads=kv,
                                scale=1 / 64).astype(jnp.float32)
        return jnp.sum(out * x), out

    with jax.default_matmul_precision("highest"):
        (_, want), want_g = jax.jit(jax.value_and_grad(plain, has_aux=True))(p)
    (_, loops), loops_g = jax.jit(jax.value_and_grad(ours, has_aux=True))(p)
    as_lowered_for_a_tpu(monkeypatch)
    (_, fused), fused_g = jax.jit(jax.value_and_grad(ours, has_aux=True))(p)
    for got, grads in ((loops, loops_g), (fused, fused_g)):
        assert worst_leaf((got, grads), (want, want_g)) < 3e-2
    assert worst_leaf((fused, fused_g), (want, want_g)) <= 1.05 * worst_leaf((loops, loops_g), (want, want_g))


# -- the gauges ---------------------------------------------------------------------------------------------------


def test_the_train_step_reports_its_ssd_and_attention_sites(monkeypatch):
    """make_train_step sets `train.ssd_sites` / `ssd_kept_sites` (the Mamba-2
    layers, all of whose chunk states the checkpoint keeps) and
    `train.ssd_conv_fused_sites` (those whose xBC convolution the conv kernels
    take: a prediction from the shapes and the lowering's platform) beside
    `train.attn_sites` / `attn_fused_sites`: 9 / 9 / 9 and 1 / 1 for the cell's
    model on a TPU, 9 / 9 / 0 and 1 / 0 on the CPU; 2 / 2 / 0 and 1 / 0 for the
    float32 toy anywhere; 0 for another arch."""
    import os

    from test_lm import LM

    from yet_another_mobilenet_series_tpu.config import load_config
    from yet_another_mobilenet_series_tpu.train import optim, schedules, steps

    app = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "yet_another_mobilenet_series_tpu",
                       "apps", "granite_4_0_h_micro.yml")
    cfg = load_config(app)
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 1, 10, 1)
    names = ("train.ssd_sites", "train.ssd_kept_sites", "train.ssd_conv_fused_sites", "train.attn_sites",
             "train.attn_fused_sites")

    def gauges(net, **kw):
        params = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))[0]
        steps.make_train_step(net, cfg, optim.make_optimizer(cfg.optim, lr_fn, params), lr_fn, **kw)
        return tuple(get_registry().gauge(name).value for name in names)

    monkeypatch.setattr(ops, "ATTN_BLOCK", 512)  # the tile as shipped, which this file's fixture shrinks
    cell = get_model(cfg.model)
    assert cell.ssd_conv_fitting_sites(jnp.bfloat16) == 9
    assert gauges(cell, platform="tpu") == (9.0, 9.0, 9.0, 1.0, 1.0)
    assert gauges(cell, platform="cpu") == (9.0, 9.0, 0.0, 1.0, 0.0)
    assert gauges(model(), platform="tpu") == (2.0, 2.0, 0.0, 1.0, 0.0)
    glm = get_model(ModelConfig(arch="glm4_moe_lite", num_classes=VOCAB, lm=LM))
    assert gauges(glm, platform="tpu")[:3] == (0.0, 0.0, 0.0)
