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


# -- the SSD kernels (ops/lm_mamba_kernels.py) in Pallas interpret mode, against the plain form ----------------------
# a shape the kernels take: 2 chunks of 128, 4 heads of 64 (two to a 128-lane band), a state of 128, bfloat16
KB, KS, KH, KP, KK, KC = 1, 256, 4, 64, 128, 128
# the largest deviation allowed against each result's largest entry: bfloat16's ulp at the top of its range is 2^-8 for
# y, dx, dB, dC and the start states; dDelta and dG are float32 sums of bfloat16 products; dD sums x dy in float32
OUTPUT_LIMIT = 1e-2
GRAD_LIMITS = (1.5e-2, 1e-2, 1e-2, 1e-2, 1e-2, 1e-2, 1e-4)  # x, delta, cum, b, c, starts, d_skip
OWN_LIMIT = 1e-5  # the own contributions: float32 sums of the same bfloat16 products
DECAYS = pytest.mark.parametrize("fast", [0.1, 4.0], ids=["fresh_decays", "decays_past_-88"])


@pytest.fixture
def ssd_kernels(monkeypatch):
    """The kernels' lowering on the CPU (`lax.platform_dependent` takes its `tpu` branch, the kernels run in Pallas
    interpret mode), with some of the kernels module's names set for one test: the kernels' calls are jitted,
    so traces made before are dropped first, and the test's own after it."""
    from yet_another_mobilenet_series_tpu.ops import lm_mamba_kernels as kernels

    monkeypatch.setattr(jax.lax, "platform_dependent", lambda *args, tpu, default: tpu(*args))
    for name in ("chunk_fwd", "chunk_bwd", "own_fwd", "own_bwd"):
        monkeypatch.setattr(lm_mamba, name, functools.partial(getattr(lm_mamba, name), interpret=True))

    def setting(**names):
        for name, value in names.items():
            monkeypatch.setattr(kernels, name, value)
        jax.clear_caches()

    setting()
    yield setting
    jax.clear_caches()


def outputs_operands(fast, seed=0):
    """The kernels' operands, flat as the mixer holds them: x, B, C in bfloat16, Delta in (0, 0.3), the in-chunk
    cumulative Delta A scaled by `fast` (0.1 keeps every in-chunk sum above -88, 4 takes some past it), start states
    as a chunk scan leaves them."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    delta = 0.3 * jax.nn.sigmoid(jax.random.normal(ks[1], (KB, KS, KH)))
    rate = fast * jnp.exp(jax.random.uniform(ks[2], (KH,), minval=0.0, maxval=math.log(16.0)))
    x = jax.random.normal(ks[0], (KB, KS, KH * KP)).astype(jnp.bfloat16)
    b, c = (jax.random.normal(k, (KB, KS, KK)).astype(jnp.bfloat16) for k in ks[3:5])
    starts = jax.random.normal(ks[5], (KS // KC, KB, KH, KP, KK)).astype(jnp.bfloat16)
    cum = jnp.cumsum((-rate * delta).reshape(KB, KS // KC, KC, KH), axis=2).reshape(KB, KS, KH)
    return x, delta, cum, b, c, starts, jax.random.normal(ks[6], (KH,))


def _vjp_deviations(fused, plain, args, ct):
    got, pull = jax.vjp(fused, *args)
    want, want_pull = jax.vjp(plain, *args)
    grads, want_grads = pull(ct), want_pull(ct)
    assert (got.shape, got.dtype) == (want.shape, want.dtype)
    assert [(g.shape, g.dtype) for g in grads] == [(a.shape, a.dtype) for a in args]
    return [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))) / float(jnp.max(jnp.abs(b.astype(jnp.float32)))))
            for a, b in zip((got, *grads), (want, *want_grads))]


def kernel_deviations(args):
    """The four kernels through their two custom_vjps against the plain forms and their vjps, from one cotangent
    each, as shares of the plain results' largest entries: (y's, y's arguments' gradients; each chunk's own state
    contribution's, its arguments' gradients)."""
    x, delta, cum, b, _, _, _ = args
    ct = jax.random.normal(jax.random.PRNGKey(11), x.shape).astype(jnp.bfloat16)
    outputs = _vjp_deviations(lm_mamba._fused_outputs, lm_mamba._plain_outputs, args, ct)
    own_ct = jax.random.normal(jax.random.PRNGKey(12), args[5].shape)
    own = _vjp_deviations(lambda *a: lm_mamba._fused_own(*a, KC), functools.partial(lm_mamba._plain_own, chunk=KC),
                          (x, delta, cum, b), own_ct)
    return outputs[0], outputs[1:], own[0], own[1:]


def holds(devs):
    output, grads, own, own_grads = devs
    return (output < OUTPUT_LIMIT and all(d < limit for d, limit in zip(grads, GRAD_LIMITS))
            and own < OWN_LIMIT and all(d < limit for d, limit in zip(own_grads, GRAD_LIMITS)))


@DECAYS
def test_the_ssd_kernels_output_and_every_gradient_equal_the_plain_form(ssd_kernels, fast):
    """`_fused_outputs` and `_fused_own` lowered as for a TPU (the forward
    kernels; their vjps the backward kernels, which make the decays and
    weights again in VMEM) against `_plain_outputs` (all heads' (t, s, h)
    matrices in `lax`), `_plain_own` and their vjps: y and the gradients of x,
    Delta, G, B, C, the start states and D; each chunk's own state
    contribution and the gradients of x, Delta, G and B; finite where the
    in-chunk sums pass -88."""
    args = outputs_operands(fast)
    assert lm_mamba.fuses(KS, KC, KH, KP, KK, jnp.bfloat16)
    assert (float(jnp.min(args[2])) < -88.0) is (fast > 1.0)
    devs = kernel_deviations(args)
    assert holds(devs), devs


def _product_of_exponentials(later, earlier, keep):
    return jnp.where(keep, jnp.exp(later) * jnp.exp(-earlier), 0.0)


def _masked_after_the_exp(later, earlier, keep):
    return jnp.exp(later - earlier) * jnp.where(keep, 1.0, 0.0)  # a 0/1 factor (`* keep` is a select in jax.numpy)


@pytest.mark.parametrize("planted", [_product_of_exponentials, _masked_after_the_exp],
                         ids=["decay_as_a_product_of_exponentials", "mask_after_the_exp"])
def test_a_decay_that_is_not_an_exp_of_a_masked_difference_is_refused_past_minus_88(ssd_kernels, planted):
    """Plant in the kernels a decay written as `e^{G_t} e^{-G_s}`, or masked
    by a product after the `exp`: the same numbers while every in-chunk sum
    stays above -88, so the comparison above passes there; past it inf x 0
    reaches the output and the gradients, and the comparison refuses both."""
    ssd_kernels(decays=planted)
    assert holds(kernel_deviations(outputs_operands(0.1, seed=3)))
    assert not holds(kernel_deviations(outputs_operands(4.0, seed=3)))


@pytest.mark.parametrize("seq, chunk, heads, width, state, dtype, takes", [
    (256, 128, 4, 64, 128, jnp.bfloat16, True), (8192, 256, 64, 64, 128, jnp.bfloat16, True),
    (320, 128, 4, 64, 128, jnp.bfloat16, False), (256, 64, 4, 64, 128, jnp.bfloat16, False),
    (256, 128, 4, 64, 128, jnp.float32, False), (256, 128, 4, 48, 128, jnp.bfloat16, False),
    (256, 128, 4, 64, 64, jnp.bfloat16, False), (256, 128, 1, 64, 128, jnp.bfloat16, False),
    (8192, 2048, 64, 64, 128, jnp.bfloat16, False),
], ids=["fits", "the_cells_shape", "sequence_not_whole_chunks", "chunk_of_64", "float32", "head_dim_48", "state_64",
        "half_a_band", "chunk_too_large_for_vmem"])
def test_the_ssd_dispatch_takes_the_kernels_by_the_shapes_alone(monkeypatch, seq, chunk, heads, width, state, dtype, takes):
    """`ssd_core` asks `fuses` (whole chunks of whole 128-row tiles, heads that
    fill whole 128-lane bands, a state of whole bands, bfloat16, what a program
    instance holds within the VMEM it may plan for) and nothing else; what does
    not fit takes the plain form, with no `custom_vjp` and no platform switch."""
    assert lm_mamba.fuses(seq, chunk, heads, width, state, dtype) == takes
    asked = []
    fused = lm_mamba._fused_outputs
    monkeypatch.setattr(lm_mamba, "_fused_outputs", lambda *a: asked.append(a[0].shape) or fused(*a))
    x = jax.ShapeDtypeStruct((1, seq, heads, width), dtype)
    per_head = jax.ShapeDtypeStruct((1, seq, heads), jnp.float32)
    bc = jax.ShapeDtypeStruct((1, seq, state), dtype)
    jaxpr = jax.make_jaxpr(lambda *a: lm_mamba.ssd_core(*a, chunk=chunk))(x, per_head, per_head, bc, bc,
                                                                         jax.ShapeDtypeStruct((heads,), jnp.float32))
    assert bool(asked) == takes
    assert ("platform_index" in str(jaxpr)) == takes


def test_on_a_cpu_the_fitting_ssd_runs_the_plain_form_and_its_vjp(monkeypatch):
    """The kernels' shape lowered for a CPU: `lax.platform_dependent` keeps the
    plain form and, in the backward, its own vjp, so y and every gradient of
    `ssd_core` equal those of a dispatch that refuses the shape, to the bit."""
    x, delta, log_decay, b, c, _, d_skip = outputs_operands(4.0, seed=5)
    args = (x.reshape(KB, KS, KH, KP), delta, log_decay, b, c, d_skip)
    ct = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def run():
        loss = lambda *a: jnp.sum(lm_mamba.ssd_core(*a, chunk=KC)[0].astype(jnp.float32) * ct)  # noqa: E731
        return jax.jit(jax.value_and_grad(loss, range(6)))(*args)

    text = jax.jit(lambda *a: lm_mamba.ssd_core(*a, chunk=KC)).lower(*args).compile().as_text()
    assert "pallas_call" not in text and "ssd_chunk_" not in text
    through_the_dispatch = run()
    monkeypatch.setattr(lm_mamba, "fuses", lambda *a: False)
    plain = run()
    assert all(bool(jnp.all(a == b)) for a, b in zip(jax.tree.leaves(through_the_dispatch), jax.tree.leaves(plain)))


_FRESH_PROCESS = """
import json, sys
import jax, jax.numpy as jnp
from yet_another_mobilenet_series_tpu.models import lm
from yet_another_mobilenet_series_tpu.ops import lm_mamba
from yet_another_mobilenet_series_tpu.train import steps

def pallas():
    return sorted(m for m in sys.modules if m.startswith(("jax.experimental.pallas", "jax._src.pallas")))

dtype = jnp.bfloat16 if sys.argv[1] == "fitting_site" else jnp.float32
before = pallas()
x = jax.ShapeDtypeStruct((1, 256, 4, 64), dtype)
per_head = jax.ShapeDtypeStruct((1, 256, 4), jnp.float32)
bc = jax.ShapeDtypeStruct((1, 256, 128), dtype)
jax.eval_shape(lambda *a: lm_mamba.ssd_core(*a, chunk=128), x, per_head, per_head, bc, bc, jax.ShapeDtypeStruct((4,), jnp.float32))
print(json.dumps({"fits": lm_mamba.fuses(256, 128, 4, 64, 128, dtype), "before": before, "pallas": pallas()}))
"""


@pytest.mark.parametrize("what, pays", [("plain_site", False), ("fitting_site", True)])
def test_pallas_comes_in_where_a_fitting_ssd_site_is_traced_and_nowhere_else(what, pays):
    """A fresh process that imports `ops.lm_mamba`, `models.lm` and
    `train.steps` has no `jax.experimental.pallas*` module, and none after
    tracing an SSD the kernels do not take; the `tpu` branch of a fitting
    site, once traced, brings it in (the import costs every cell that runs
    none of its code 1.2-1.5 s of `setup_s`)."""
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
    `train.ssd_conv_fused_sites` / `ssd_fused_sites` (those whose xBC
    convolution / in-chunk SSD work the kernels take: predictions from the
    shapes and the lowering's platform) beside `train.attn_sites` /
    `attn_fused_sites`: 9 / 9 / 9 / 9 and 1 / 1 for the cell's model on a
    TPU, 9 / 9 / 0 / 0 and 1 / 0 on the CPU; 2 / 2 / 0 / 0 and 1 / 0 for the
    float32 toy anywhere; 0 for another arch."""
    import os

    from test_lm import LM

    from yet_another_mobilenet_series_tpu.config import load_config
    from yet_another_mobilenet_series_tpu.train import optim, schedules, steps

    app = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "yet_another_mobilenet_series_tpu",
                       "apps", "granite_4_0_h_micro.yml")
    cfg = load_config(app)
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 1, 10, 1)
    names = ("train.ssd_sites", "train.ssd_kept_sites", "train.ssd_conv_fused_sites", "train.ssd_fused_sites",
             "train.attn_sites", "train.attn_fused_sites")

    def gauges(net, **kw):
        params = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))[0]
        steps.make_train_step(net, cfg, optim.make_optimizer(cfg.optim, lr_fn, params), lr_fn, **kw)
        return tuple(get_registry().gauge(name).value for name in names)

    monkeypatch.setattr(ops, "ATTN_BLOCK", 512)  # the tile as shipped, which this file's fixture shrinks
    cell = get_model(cfg.model)
    assert cell.ssd_conv_fitting_sites(jnp.bfloat16) == cell.ssd_fitting_sites(jnp.bfloat16) == 9
    assert gauges(cell, platform="tpu") == (9.0, 9.0, 9.0, 9.0, 1.0, 1.0)
    assert gauges(cell, platform="cpu") == (9.0, 9.0, 0.0, 0.0, 1.0, 0.0)
    assert gauges(model(), platform="tpu") == (2.0, 2.0, 0.0, 0.0, 1.0, 0.0)
    glm = get_model(ModelConfig(arch="glm4_moe_lite", num_classes=VOCAB, lm=LM))
    assert gauges(glm, platform="tpu")[:4] == (0.0, 0.0, 0.0, 0.0)
