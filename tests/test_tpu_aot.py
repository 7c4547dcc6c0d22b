"""Compiled for a DESCRIBED TPU v5e, never run: what the chip's compiler makes
of the main path at real widths (the `on-chip-measurement` guide, section 2,
third rehearsal). Nothing here gives a time; it gives operands, layouts and
fusions, at no chip time, on every later PR.

The topology is described inside a module fixture (only one process may hold
libtpu; nothing touches it at import or collection), and this is the one file
that does so: a second one could land on another xdist worker and skip.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from yet_another_mobilenet_series_tpu import ops
from yet_another_mobilenet_series_tpu.obs import scopes


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _entry_instructions(text):
    """ENTRY instructions of compiled HLO text: name -> (output shapes,
    opcode, operand names, op_name), and the get-tuple-elements as aliases
    name -> (source, index)."""
    entry = text[text.index("\nENTRY "):]
    instructions, alias = {}, {}
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|\S+)\s+([\w\-]+)\((.*)$", line)
        if m is None:
            continue
        name, out, opcode, rest = m.groups()
        operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
        if opcode == "get-tuple-element":
            index = int(re.search(r"index=(\d+)", rest).group(1))
            alias[name] = (operands[0], index)
            continue
        op_name = re.search(r'op_name="([^"]*)"', rest)
        instructions[name] = (re.findall(r"\w+\[[\d,]*\]", out), opcode, operands, op_name.group(1) if op_name else "")
    return instructions, alias


# MobileNetV3-Large's second block at the benchmark's batch: the step's four
# largest fusions all stream this block's expanded tensor (PERF.md section 5)
BATCH, SIZE, CIN, CEXP, COUT = 512, 112, 16, 64, 24
WIDE = f"bf16[{BATCH},{SIZE},{SIZE},{CEXP}]"


@pytest.fixture(scope="module")
def block_hlo(one_chip):
    """ENTRY instructions of the one-block gradient, get-tuple-elements seen through."""
    block = ops.InvertedResidual(CIN, COUT, CEXP, stride=2, kernel_sizes=(3,), active_fn="hswish")
    params, state = jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0)))
    state = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), state)

    def loss(params, x, ct):
        y, _ = block.apply(params, state, x, train=True, compute_dtype=jnp.bfloat16)
        return jnp.sum(y.astype(jnp.float32) * ct)

    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jax.tree.map(lambda a: on_chip(a.shape, a.dtype), params),
        on_chip((BATCH, SIZE, SIZE, CIN), jnp.bfloat16),
        on_chip((BATCH, SIZE // 2, SIZE // 2, COUT), jnp.float32)).compile()
    text = compiled.as_text()
    instructions, alias = _entry_instructions(text)

    def shape_of(operand):
        if operand in alias:
            source, index = alias[operand]
            return instructions[source][0][index]
        shapes = instructions.get(operand, ([],))[0]
        return shapes[0] if len(shapes) == 1 else None

    return instructions, alias, shape_of, text


def _wide_operands(block_hlo, name):
    instructions, _, shape_of, _ = block_hlo
    return [o for o in instructions[name][2] if shape_of(o) == WIDE]


def test_no_expand_gradient_reads_the_expanded_activation(block_hlo):
    """The conv + BN pair's point: of the fusions under transpose(jvp(conv_pw)),
    none takes the expand conv's output E as an operand (they take D, the
    gradient arriving at the BN's output, and the 16-channel input)."""
    instructions, alias, _, _ = block_hlo
    producer = [n for n, (out, opcode, _, op) in instructions.items()
                if opcode == "fusion" and WIDE in out and scopes.scope_of(op) == ("conv_pw", "fwd")]
    assert len(producer) == 1, producer
    e_names = {producer[0]} | {g for g, (source, _) in alias.items() if source == producer[0]}
    expand_grads = [n for n, (_, opcode, _, op) in instructions.items()
                    if opcode == "fusion" and scopes.scope_of(op) == ("conv_pw", "bwd")]
    assert expand_grads
    wide_readers = [n for n in expand_grads if _wide_operands(block_hlo, n)]
    assert len(wide_readers) == 2  # dX and the dW contraction, each reading D
    for n in expand_grads:
        assert not e_names & set(instructions[n][2]), f"{n} reads the expand conv's output"


def test_the_wide_tensors_are_read_five_times_and_never_copied(block_hlo):
    """Autodiff of conv then BN reads the two expanded-width tensors 7 times
    (E: depthwise forward, its two gradients, the expand conv's two; D: the
    expand conv's two); the pair leaves 5. And no relayout pays for it."""
    instructions, _, _, _ = block_hlo
    reads = sum(len(_wide_operands(block_hlo, n)) for n, (_, opcode, _, _) in instructions.items()
                if opcode == "fusion")
    writes = sum(out.count(WIDE) for out, opcode, _, _ in instructions.values() if opcode == "fusion")
    assert (reads, writes) == (5, 2)
    moved = [n for n, (out, opcode, _, _) in instructions.items()
             if WIDE in out and opcode in ("copy", "transpose", "copy-start", "copy-done")]
    assert not moved, moved


def test_the_bn_gradient_sums_ride_in_the_fusion_that_produces_d(block_hlo):
    """sum(D) and sum(D * x_hat) are the one place the pair's backward still
    needs E: they cost no pass because they are fused into the depthwise
    input-gradient fusion, which reads E for the activation's derivative."""
    instructions, _, _, text = block_hlo
    inside = scopes.scopes_inside(text)
    d_producer = [n for n, (out, opcode, _, op) in instructions.items()
                  if opcode == "fusion" and WIDE in out and scopes.scope_of(op)[1] == "bwd"]
    assert len(d_producer) == 1, d_producer
    assert scopes.scope_of(instructions[d_producer[0]][3]) == ("conv_dw", "bwd")
    assert "bn_stats" in inside.get(d_producer[0], ())
    # and nothing under bn_stats reads a wide tensor in a fusion of its own
    alone = [n for n, (_, opcode, _, op) in instructions.items()
             if opcode == "fusion" and scopes.scope_of(op)[0] == "bn_stats" and _wide_operands(block_hlo, n)]
    assert not alone, alone


# One attention layer of the token cell (glm47flash_train_2x8k): 2 x 8,192 tokens, 20 heads, head dims 256 / 256
ATTN = (2, 8192, 20, 256)


@pytest.fixture(scope="module")
def attention_hlo(one_chip):
    """`ops.lm.causal_attention`'s value and three gradients at the cell's
    shapes: a TPU lowering, so `lax.platform_dependent` takes the kernels of
    ops/lm_attention.py, and Mosaic compiles them (~3 s)."""
    from yet_another_mobilenet_series_tpu.ops import lm

    assert lm.lm_attention.fuses(ATTN[1], lm.ATTN_BLOCK, ATTN[3], ATTN[3], jnp.bfloat16)

    def loss(q, k, v, ct):
        return jnp.sum(lm.causal_attention(q, k, v, scale=ATTN[3] ** -0.5).astype(jnp.float32) * ct)

    operand = jax.ShapeDtypeStruct(ATTN, jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        operand, operand, operand, jax.ShapeDtypeStruct(ATTN, jnp.float32, sharding=one_chip)).compile()
    return compiled.as_text()


def test_attention_is_two_mosaic_kernels_under_its_scope(attention_hlo):
    """One custom call forward and one backward, each under `attn_core` with
    its phase, carrying the kernel's name; no loop is left."""
    instructions, _ = _entry_instructions(attention_hlo)
    kernels = {n: scopes.scope_of(op) for n, (_, opcode, _, op) in instructions.items() if opcode == "custom-call"}
    assert sorted(kernels.values()) == [("attn_core", "bwd"), ("attn_core", "fwd")], kernels
    assert sorted(n.split(".")[0] for n in kernels) == ["causal_attention_bwd", "causal_attention_fwd"]
    assert attention_hlo.count('custom_call_target="tpu_custom_call"') == 2
    assert not re.search(r"\bwhile\(", attention_hlo)


def test_a_sliding_layers_core_at_the_cells_shape_is_the_window_kernels_under_their_scope(one_chip):
    """A `laguna` sliding layer's core at the cell's shape (1 x 8,192 tokens,
    72 heads of 128, window 512, tiles of 512), value and three gradients,
    compiled for the described chip: one forward and one backward custom call,
    named `window_attention_fwd` / `_bwd` (not the causal pair) and under
    `attn_window` with their phases; no loop is left. The forward's bounds
    meet 2 key tiles a query block past the first (the diagonal and the edge
    tile before it), where the causal kernel meets i + 1 (8.5 on average over
    16 blocks)."""
    from yet_another_mobilenet_series_tpu.ops import lm, lm_attention_kernels

    shape = (1, 8192, 72, 128)
    assert lm.lm_attention.fuses(shape[1], lm.ATTN_BLOCK, 128, 128, jnp.bfloat16)
    reach, whole = lm_attention_kernels._window_bounds(512, lm.ATTN_BLOCK)
    blocks = shape[1] // lm.ATTN_BLOCK
    assert (reach, whole) == (1, 0) and sum(min(i, reach) + 1 for i in range(blocks)) == 2 * blocks - 1
    assert sum(i + 1 for i in range(blocks)) / blocks == 8.5

    def loss(q, k, v, ct):
        return jnp.sum(lm.causal_attention(q, k, v, scale=128 ** -0.5, window=512).astype(jnp.float32) * ct)

    operand = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        operand, operand, operand, jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)).compile().as_text()
    instructions, _ = _entry_instructions(text)
    kernels = {n: scopes.scope_of(op) for n, (_, opcode, _, op) in instructions.items() if opcode == "custom-call"}
    assert sorted(kernels.values()) == [("attn_window", "bwd"), ("attn_window", "fwd")], kernels
    assert sorted(n.split(".")[0] for n in kernels) == ["window_attention_bwd", "window_attention_fwd"]
    assert text.count('custom_call_target="tpu_custom_call"') == 2 and not re.search(r"\bwhile\(", text)


def test_the_192_channel_site_takes_the_kernels_with_q_and_k_filled_to_256(one_chip):
    """kimi_linear's latent-attention layer at the cell's shape (1 x 16,384
    tokens, 32 heads of 128 + 64 / 128): `causal_attention` fills q and k with
    zero channels to 256, and Mosaic compiles both kernels there: a whole
    sequence of one head is 8 MiB, exactly `RESIDENT_BYTES`."""
    from yet_another_mobilenet_series_tpu.ops import lm

    seq, heads = 16384, 32
    assert lm.lm_attention.fitting_dims(seq, lm.ATTN_BLOCK, 192, 128, jnp.bfloat16) == (256, 128)

    def loss(q, k, v, ct):
        return jnp.sum(lm.causal_attention(q, k, v, scale=192 ** -0.5).astype(jnp.float32) * ct)

    qk = jax.ShapeDtypeStruct((1, seq, heads, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, seq, heads, 128), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        qk, qk, v, jax.ShapeDtypeStruct(v.shape, jnp.float32, sharding=one_chip)).compile().as_text()
    instructions, _ = _entry_instructions(text)
    kernels = sorted(n.split(".")[0] for n, (_, opcode, _, _) in instructions.items() if opcode == "custom-call")
    assert kernels == ["causal_attention_bwd", "causal_attention_fwd"] and not re.search(r"\bwhile\(", text)
    (dq,) = [out for n, (out, _, _, _) in instructions.items() if n.startswith("causal_attention_bwd")]
    assert dq[0] == f"bf16[1,{heads * 256},{seq}]"  # the kernels' own width; the filling's gradient is cut off after


def test_a_kda_layer_at_the_cells_shape_is_two_mosaic_kernels_under_its_scope(one_chip, monkeypatch):
    """kimi_linear's Kimi Delta Attention core at the cell's shape (1 x 16,384
    tokens, 32 heads of 128, bfloat16), forward + backward, compiled for the
    described chip from this CPU process: `lax.platform_dependent` takes the
    kernels of ops/lm_kda_kernels.py and Mosaic compiles all four, the two of
    the in-chunk work and the two of the scan over the chunks; each custom call
    keeps `kda_core` and its phase in its `op_name` and carries the kernel's
    name (so `scope_table` resolves its device events as it does
    `causal_attention_fwd.N`); no loop is left to XLA (the scan's state is
    carried inside its kernels); and the declared temporaries are not above
    the plain form's (head groups, a `lax.map`, a solve loop and the scan's
    two loops)."""
    from yet_another_mobilenet_series_tpu.ops import lm_kda

    shape = (1, 16384, 32, 128)
    assert lm_kda.fuses(shape[1], lm_kda.KDA_CHUNK, shape[3], jnp.bfloat16)
    operand = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    rest = (jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip), jax.ShapeDtypeStruct(shape[:3], jnp.float32, sharding=one_chip))

    def compiled():
        loss = lambda *a: jnp.sum(lm_kda.kda_core(*a)[0].astype(jnp.float32))  # noqa: E731
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4))).lower(operand, operand, operand, *rest).compile()

    fused = compiled()
    text = fused.as_text()
    instructions, _ = _entry_instructions(text)
    kernels = {n.split(".")[0]: scopes.scope_of(op) for n, (_, opcode, _, op) in instructions.items()
               if opcode == "custom-call" and n.startswith("kda_")}
    assert kernels == {"kda_operands_fwd": ("kda_core", "fwd"), "kda_operands_bwd": ("kda_core", "bwd"),
                       "kda_scan_fwd": ("kda_core", "fwd"), "kda_scan_bwd": ("kda_core", "bwd")}, kernels
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert not re.search(r"\bwhile\(", text)  # no head groups, no solve loop, no scan loop
    monkeypatch.setattr(lm_kda, "fuses", lambda *a: False)
    plain = compiled()
    assert "tpu_custom_call" not in plain.as_text()
    assert fused.memory_analysis().temp_size_in_bytes <= plain.memory_analysis().temp_size_in_bytes


def test_a_kda_mixer_at_the_cells_shape_makes_q_k_v_in_the_conv_kernels_under_their_scope(one_chip, monkeypatch):
    """A whole KDA mixer of kimi_linear's cell (1 x 16,384 tokens, hidden 2,304,
    32 heads of 128; float32 weights, bfloat16 activations), its gradient
    compiled for the described chip: each of q, k, v is ONE `kda_conv_fwd`
    custom call under `("kda_conv", "fwd")` and one `kda_conv_bwd` under
    `("kda_conv", "bwd")`, beside the in-chunk kernels under `kda_core`; and
    the declared temporaries are not above those of the same mixer with the
    plain convolution and norms (4.01 against 4.52 GiB when written)."""
    from yet_another_mobilenet_series_tpu.ops import lm_kda

    hidden, heads, width, seq = 2304, 32, 128, 16384
    wide = heads * width
    shapes = {"q": (hidden, wide), "k": (hidden, wide), "v": (hidden, wide), "conv_q": (4, wide), "conv_k": (4, wide),
              "conv_v": (4, wide), "f_a": (hidden, width), "f_b": (width, wide), "A_log": (heads,), "dt_bias": (wide,),
              "b": (hidden, heads), "g_a": (hidden, width), "g_b": (width, wide), "o_norm": (width,), "o": (wide, hidden)}
    params = {name: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip) for name, shape in shapes.items()}
    x = jax.ShapeDtypeStruct((1, seq, hidden), jnp.bfloat16, sharding=one_chip)
    assert lm_kda.conv_fuses(seq, width, 4, jnp.bfloat16)

    def compiled():
        loss = lambda p, x_: jnp.sum(lm_kda.kda_attention(p, x_, heads=heads, head_dim=width, eps=1e-5)[0].astype(jnp.float32))  # noqa: E731
        return jax.jit(jax.grad(loss, (0, 1))).lower(params, x).compile()

    fused = compiled()
    instructions, _ = _entry_instructions(fused.as_text())
    kernels = sorted((n.split(".")[0], *scopes.scope_of(op)) for n, (_, opcode, _, op) in instructions.items()
                     if opcode == "custom-call" and n.startswith("kda_"))
    assert kernels == ([("kda_conv_bwd", "kda_conv", "bwd")] * 3 + [("kda_conv_fwd", "kda_conv", "fwd")] * 3
                       + [("kda_operands_bwd", "kda_core", "bwd"), ("kda_operands_fwd", "kda_core", "fwd"),
                          ("kda_scan_bwd", "kda_core", "bwd"), ("kda_scan_fwd", "kda_core", "fwd")]), kernels
    monkeypatch.setattr(lm_kda, "conv_fuses", lambda *a: False)
    plain = compiled()
    assert "kda_conv_" not in plain.as_text()
    assert fused.memory_analysis().temp_size_in_bytes <= plain.memory_analysis().temp_size_in_bytes


def test_a_mamba_mixer_at_the_cells_shape_convolves_xbc_in_the_conv_kernels_under_its_scope(one_chip, monkeypatch):
    """A whole Mamba-2 mixer of granitemoehybrid's cell (1 x 8,192 tokens,
    hidden 2,048, 64 heads of 64, state 128, chunks of 256; float32 weights,
    bfloat16 activations), its gradient compiled for the described chip: the
    xBC convolution (4,352 channels: lanes of 256, which divide them) is ONE
    `ssd_conv_fwd` custom call under `("ssd_conv", "fwd")` and one
    `ssd_conv_bwd` under `("ssd_conv", "bwd")`, each with the bias among its
    operands; the in-chunk SSD work is ONE `ssd_chunk_fwd` custom call under
    `("ssd_core", "fwd")` and one `ssd_chunk_bwd` under `("ssd_core", "bwd")`,
    and each chunk's own state contribution one `ssd_own_fwd` / `ssd_own_bwd`
    pair under the same scope; no KDA kernel; the chunk scan's two loops and
    no other; and the declared temporaries are not above those of the same
    mixer with the plain convolution and the plain SSD (0.41 against 0.93 GiB
    when written), whose lowering holds no SSD kernel."""
    from yet_another_mobilenet_series_tpu.ops import lm_mamba

    hidden, heads, width, state, seq = 2048, 64, 64, 128, 8192
    inner, channels = heads * width, heads * width + 2 * state
    shapes = {"in_proj": (hidden, inner + channels + heads), "conv": (4, channels), "conv_bias": (channels,),
              "A_log": (heads,), "D": (heads,), "dt_bias": (heads,), "norm": (inner,), "out_proj": (inner, hidden)}
    params = {name: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip) for name, shape in shapes.items()}
    x = jax.ShapeDtypeStruct((1, seq, hidden), jnp.bfloat16, sharding=one_chip)
    assert lm_mamba.conv_fuses(seq, channels, 4, jnp.bfloat16)

    def compiled():
        loss = lambda p, x_: jnp.sum(lm_mamba.mamba_mixer(p, x_, heads=heads, head_dim=width, state=state, chunk=256,  # noqa: E731
                                                          eps=1e-5)[0].astype(jnp.float32))
        return jax.jit(jax.grad(loss, (0, 1))).lower(params, x).compile()

    fused = compiled()
    text = fused.as_text()
    instructions, _ = _entry_instructions(text)
    kernels = sorted((n.split(".")[0], *scopes.scope_of(op), len(operands))
                     for n, (_, opcode, operands, op) in instructions.items()
                     if opcode == "custom-call" and n.startswith(("ssd_", "kda_")))
    # operands: the conv's filter, bias, z (and its halo's block), the backward's the cotangent twice more; the SSD's
    # outputs C, B, G by columns and by rows, Delta, x, D, the start states, the backward's the cotangent; its chunks'
    # own states G, Delta, x, B, the backward's their cotangent
    assert kernels == [("ssd_chunk_bwd", "ssd_core", "bwd", 9), ("ssd_chunk_fwd", "ssd_core", "fwd", 8),
                       ("ssd_conv_bwd", "ssd_conv", "bwd", 7), ("ssd_conv_fwd", "ssd_conv", "fwd", 4),
                       ("ssd_own_bwd", "ssd_core", "bwd", 5), ("ssd_own_fwd", "ssd_core", "fwd", 4)], kernels
    assert len(re.findall(r"\bwhile\(", text)) == 2  # the chunk scan, forward and backward
    monkeypatch.setattr(lm_mamba, "conv_fuses", lambda *a: False)
    monkeypatch.setattr(lm_mamba, "fuses", lambda *a: False)
    plain = compiled()
    assert not re.search(r"ssd_(conv|chunk|own)_", plain.as_text())
    assert fused.memory_analysis().temp_size_in_bytes <= plain.memory_analysis().temp_size_in_bytes


def test_no_tile_of_scores_and_no_float32_dq_reaches_hbm(attention_hlo):
    """What the tile loops paid for: no buffer of a tile's shape in any
    dtype (`f32[2,20,512,512]`, or the kernel's own 512 x 512 block), no
    dynamic-update-slice (the loops added dQ's rows into a float32 buffer
    tile by tile), and the kernels hand back dQ in the operands' dtype."""
    from yet_another_mobilenet_series_tpu.ops import lm

    block = lm.ATTN_BLOCK
    assert not re.search(rf"\[(\d+,)*{block},{block}\]", attention_hlo)
    assert "dynamic-update-slice" not in attention_hlo
    instructions, _ = _entry_instructions(attention_hlo)
    (outs,) = [out for n, (out, opcode, _, _) in instructions.items() if n.startswith("causal_attention_bwd")]
    assert outs == [f"bf16[{ATTN[0]},{ATTN[2] * ATTN[3]},{ATTN[1]}]"] * 3  # dq, dk, dv, features leading
    whole = ATTN[0] * ATTN[1] * ATTN[2] * ATTN[3]

    def elements(shape):
        return int(np.prod([int(n) for n in re.findall(r"\d+", shape.split("[")[1])]))

    float32_whole = [n for n, (out, _, _, op) in instructions.items() if scopes.scope_of(op)[0] == "attn_core"
                     and any(o.startswith("f32[") and elements(o) >= whole for o in out)]
    assert not float32_whole, float32_whole


@pytest.fixture(scope="module")
def token_step_hlo(one_chip):
    """(attention sites, compiled text) of the gradient of `TokenModel.loss`
    for a SMALL token model whose attention the kernels take (bfloat16, one
    512-row tile a sequence, head dims 128 / 128): 1 dense + 1 expert layer +
    the MTP module, each under its layer checkpoint (~10 s)."""
    from yet_another_mobilenet_series_tpu.config import LMConfig, ModelConfig
    from yet_another_mobilenet_series_tpu.models import get_model

    lm = LMConfig(hidden_size=256, num_hidden_layers=2, first_k_dense_replace=1, num_attention_heads=2, q_lora_rank=64,
                  kv_lora_rank=64, qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=128, intermediate_size=512,
                  moe_intermediate_size=128, n_routed_experts=16, num_experts_per_tok=2, expert_shares=8, seq_len=512)
    net = get_model(ModelConfig(arch="glm4_moe_lite", num_classes=512, lm=lm))
    sites, fitting = net.attention_sites(jnp.bfloat16)
    assert sites == fitting == 3
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params, state = jax.tree.map(on_chip, jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0))))
    tokens = jax.ShapeDtypeStruct((2, lm.seq_len + 2), jnp.int32, sharding=one_chip)
    compiled = jax.jit(jax.grad(lambda p, s, t: net.loss(p, s, {"tokens": t}, compute_dtype=jnp.bfloat16)[0])).lower(
        params, state, tokens).compile()
    return sites, compiled.as_text()


def test_the_token_step_runs_the_attention_forward_kernel_once_a_layer(token_step_hlo):
    """The compiled gradient of a token model's loss holds exactly
    `attention_sites` forward kernels and as many backward kernels, all under
    `attn_core`, every forward in the forward pass: the layer checkpoint keeps
    attention's output and log-sum-exp by name (models/lm.py, PR 32), so the
    backward's second run of a layer makes q, k, v again and not the forward.
    Under the parent's plain `jax.checkpoint` the same step holds TWICE the
    forward count (3 under `jvp`, 3 more under `transpose(jvp)`)."""
    sites, text = token_step_hlo
    instructions, _ = _entry_instructions(text)
    kernels = sorted((n.split(".")[0], *scopes.scope_of(op)) for n, (_, opcode, _, op) in instructions.items()
                     if opcode == "custom-call" and n.startswith("causal_attention"))
    assert kernels == ([("causal_attention_bwd", "attn_core", "bwd")] * sites
                       + [("causal_attention_fwd", "attn_core", "fwd")] * sites)
    assert len(re.findall(r"^\s*%?causal_attention_[\w.]+ = .* custom-call\(", text, re.M)) == 2 * sites  # none outside ENTRY


def _computations(text):
    """Compiled HLO text -> {computation: its instruction lines}."""
    found, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$", line)
        if head is not None:
            name = head.group(1)
            found[name] = []
        elif name is not None:
            found[name].append(line)
    return found


def test_an_expert_site_is_one_conditional_each_way_and_its_bounded_branch_holds_one_gather_of_all_rows(token_step_hlo):
    """The small step's two expert sites (an expert layer and the MTP block;
    2 x 512 tokens, top-2 of 16 experts, 2 held: 2,048 assignment rows of
    width 256, capacity 512) compile to ONE `conditional` a site in the
    forward pass and one in the backward (the layer checkpoint's second run
    needs neither branch: the backward's own `cond` differentiates the branch
    it takes), on `held rows <= capacity`. The bounded branch holds exactly
    one tensor of every assignment's row by the width, the gather that sums
    the held rows into token rows (forward: the experts' output; backward: the
    held rows' gradient), where the full-length branch holds five or more."""
    _, text = token_step_hlo
    computations = _computations(text)
    assignments, width = 2 * 512 * 2, 256
    sites = re.findall(r"\sconditional\(.*branch_computations=\{%?([\w.\-]+), %?([\w.\-]+)\}", text)
    assert len(sites) == 2 * 2

    def full_length(computation):
        """Instructions of the branch's own computation whose output is a whole (assignments, width), reshapes apart."""
        found = []
        for line in computations[computation]:
            m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|\S+)\s+([\w\-]+)\(", line)
            if m is not None and m.group(3) not in ("reshape", "bitcast", "get-tuple-element"):
                found += [m.group(1) for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(2))
                          if np.prod([int(d) for d in dims.split(",")]) >= assignments * width]
        return found

    for every_row, held_rows in sites:  # index 0 is the predicate's False
        assert len(full_length(held_rows)) == 1, (held_rows, full_length(held_rows))
        assert len(full_length(every_row)) >= 5, (every_row, full_length(every_row))


# The looped arch at its cell's size (ouro26b_train_1x8k): apps/ouro_2_6b_depth8.yml as shipped, 1 x 8,192 tokens
OURO_APP = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "yet_another_mobilenet_series_tpu",
                        "apps", "ouro_2_6b_depth8.yml")
HBM_GIB = 15.75  # what a v5e chip's 16 GB leave a program


@pytest.fixture(scope="module")
def ouro_step(one_chip):
    """(model, compiled train step) of `ouro_2_6b_depth8` at the PUBLISHED widths
    for the described chip, built as every runner builds it (`parallel/dp.py`
    on a one-device mesh): 612 M parameters, 8 layers run 4 times (~1 min)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from yet_another_mobilenet_series_tpu.config import parse_cli
    from yet_another_mobilenet_series_tpu.models import get_model
    from yet_another_mobilenet_series_tpu.parallel import dp, mesh as mesh_lib
    from yet_another_mobilenet_series_tpu.train import optim, schedules, steps

    cfg = parse_cli([f"app:{OURO_APP}", "dist.num_devices=1"])
    net = get_model(cfg.model)
    mesh = Mesh(np.asarray([next(iter(one_chip.device_set))]), (mesh_lib.DATA_AXIS,))
    lr_fn = schedules.make_lr_schedule(cfg.schedule, 1, 1000, cfg.train.epochs)
    params_example, _ = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
    optimizer = optim.make_optimizer(cfg.optim, lr_fn, params_example)
    step = dp.make_dp_train_step(net, cfg, optimizer, lr_fn, mesh, params_example=params_example)
    replicated = NamedSharding(mesh, P())
    ts = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated),
                      jax.eval_shape(lambda: steps.init_train_state(net, cfg, optimizer, jax.random.PRNGKey(0))))
    batch = {"tokens": jax.ShapeDtypeStruct((1, cfg.model.lm.seq_len + 2), jnp.int32,
                                            sharding=NamedSharding(mesh, P(mesh_lib.DATA_AXIS)))}
    return net, step.lower(ts, batch, jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=replicated)).compile()


def test_the_looped_step_fuses_every_attention_site_and_runs_its_forward_once_an_application(ouro_step):
    """All 8 layers' attention (head dims 128 / 128, 8,192 rows in tiles of
    512) take the fused kernels, and the compiled step holds ONE forward kernel
    and one backward kernel a layer APPLICATION (8 layers x 4 loop steps): the
    layer checkpoint keeps `attn_out` and `attn_lse` in every application."""
    net, compiled = ouro_step
    assert net.param_count() == 612_438_017
    assert net.attention_sites(jnp.bfloat16) == (8, 8) and net.layer_applications == 32
    text = compiled.as_text()
    for half in ("fwd", "bwd"):
        assert len(re.findall(rf"^\s*%?causal_attention_{half}[\w.]* = .* custom-call\(", text, re.M)) == 32, half


def test_the_looped_step_fits_the_chip_at_eight_layers(ouro_step):
    """Arguments (weights and both Adam moments: 6.84 GiB; the step donates
    them) plus the program's temporaries (gradients, 32 applications' kept
    tensors, one loss block) stay under a v5e's 15.75 GiB."""
    m = ouro_step[1].memory_analysis()
    peak = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
    assert 6.8 < m.argument_size_in_bytes / 2**30 < 6.9
    assert 12.0 < peak / 2**30 < HBM_GIB - 0.5, peak / 2**30
