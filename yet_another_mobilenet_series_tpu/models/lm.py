"""The token-model family beside `specs.Network` (token family:
`glm4_moe_lite`, `kimi_linear`, `ouro`): token embedding, dense blocks then
expert blocks, each behind a token MIXER, an untied head, cross-entropy over
the vocabulary slice held here. `glm4_moe_lite` (GLM-4.7-Flash) mixes by
multi-head latent attention in every block and has one
multi-token-prediction module; `kimi_linear` (Kimi-Linear-48B-A3B) mixes by
Kimi Delta Attention (ops/lm_kda.py) in the layers its
`linear_attn_config.kda_layers` names and by latent attention without
rotation and without a low-rank q in its `full_attn_layers`, and has no MTP.
What a block of those two holds is read from `config.LMConfig` alone.

`ouro` (Ouro-2.6B, a LOOPED model) is the arch whose name says more than its
sizes: every block mixes by plain multi-head attention with the whole head
rotated and a dense MLP, each sub-layer between TWO RMSNorms (one before, one
on its output, before the residual add); the stack of `num_hidden_layers`
blocks runs `total_ut_steps` times a step with the SAME weights, the final
norm inside the loop; head, cross-entropy and an exit gate follow every run,
and the loss is the cross-entropy's expectation over a learned exit
distribution less `exit_entropy_weight` times that distribution's entropy
(`TokenModel._looped`, `_expected_loss`). Not on this path: the early exit at
inference (`early_exit_threshold`), a key/value cache a (loop step, layer),
and training the gate alone against a frozen model.

`laguna` (Laguna-S-2.1) mixes by grouped-query attention in every layer, of
the kind its `layer_types` entry names: `full_attention` (causal, YaRN rotary
embedding on the first half of each head's channels) or `sliding_attention`
(a window of `sliding_window` positions, the plain rotary embedding on the
whole head), each layer with its own `num_attention_heads_per_layer` query
heads over `num_key_value_heads` key/value heads and each head's output times
sigmoid(x W_g) before `o` (a per-head gate); its expert layers route by a
softmax over every expert (`router_scoring`; no router state) beside ONE
shared expert scaled by sigmoid(x . w_s). Its first `first_k_dense_replace`
layers' MLPs are dense (the published `mlp_only_layers`).

`granitemoehybrid` (Granite 4.0-H) is a HYBRID: each layer's mixer is named by
`layer_types`, a Mamba-2 state-space mixer (ops/lm_mamba.py) or grouped-query
attention without rotation (the published "nope": query head i
reads key/value head i // (heads / kv heads), scores times
`attention_multiplier`); every MLP dense; the embedding's rows scaled by
`embedding_multiplier`, each branch by `residual_multiplier` before its
residual add, the logits divided by `logits_scaling`; the head IS the
embedding (`tie_word_embeddings`: its gradient is the sum of both uses').

A `TokenModel` with expert layers is one SHARE of an expert-parallel
deployment (config.LMConfig): the mixers are whole, each expert layer holds
`experts_held` of the `n_routed_experts` the router scores, embedding and head
hold `vocab` rows. On one chip the share runs without an exchange, and
computes exactly its own part: ops/lm.py `expert_layer`. `ouro` has no expert
layer: what is held of it is whole.

The train step takes the family through three methods where it takes a
`Network` through `apply` (train/steps.py): `init`, `loss` (tokens in,
`(loss, (new_state, scalars))` out) and `eval_counts`. The only state is the
router's selection bias of each expert layer, which the forward moves once a
step from the step's own counts: non-gradient state, carried where a CNN
carries its BatchNorm statistics (`ouro` has none: `{}`). The plain float32 reference of the same
equations is models/lm_reference.py, which shares no function with this file.
"""

from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ..config import LMConfig, ModelConfig, RopeParameters
from ..obs.scopes import scope
from ..ops import lm as ops
from ..ops import lm_attention, lm_kda, lm_mamba

LM_ARCHS = ("glm4_moe_lite", "kimi_linear", "ouro", "granitemoehybrid", "laguna")
# What `laguna` reads and the other archs do not: each key's value where it means nothing (`TokenModel.validate`
# refuses any other on another arch)
LAGUNA_KEYS = {"num_attention_heads_per_layer": (), "sliding_window": None, "rope_parameters": RopeParameters(),
               "shared_expert_intermediate_size": 0}
# ... and what the other archs read that `laguna` does not, at the values that say so
NOT_LAGUNAS = {"num_nextn_predict_layers": 0, "mla_use_nope": False, "total_ut_steps": 1, "attention_multiplier": None,
               "embedding_multiplier": 1.0, "residual_multiplier": 1.0, "logits_scaling": 1.0,
               "tie_word_embeddings": False, "n_shared_experts": 1}
# What a layer's checkpoint keeps, by name, beside its input: attention's output and row log-sum-exp, the
# KDA scan's output and chunk-boundary states, the SSD scan's chunk-boundary states: what the cores' backwards
# read, so no forward of theirs runs twice.
KEPT_NAMES = (ops.ATTN_OUT_NAME, ops.ATTN_LSE_NAME, lm_kda.KDA_OUT_NAME, lm_kda.KDA_STATES_NAME,
              lm_mamba.SSD_STATES_NAME)
# Tokens whose logits over the vocabulary slice are held at once (each such block is a jax.checkpoint).
LOSS_BLOCK = 2048


def _key(name: str) -> int:
    """A fold_in constant per parameter name, so that a tensor's draw does not
    depend on which other tensors exist (the reference's init reads the same
    tree, not the same stream)."""
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


@dataclasses.dataclass(frozen=True)
class TokenModel:
    arch: str
    vocab: int  # rows of the vocabulary held here
    lm: LMConfig

    # ---- shapes -----------------------------------------------------------

    @property
    def experts_held(self) -> int:
        return self.lm.n_routed_experts // self.lm.expert_shares

    @property
    def block_names(self) -> tuple[str, ...]:
        """Main blocks in order, then the MTP module's block."""
        names = tuple(f"layer_{i}" for i in range(self.lm.num_hidden_layers))
        return names + (("mtp",) if self.lm.num_nextn_predict_layers else ())

    @property
    def looped(self) -> bool:
        return self.arch == "ouro"

    @property
    def hybrid(self) -> bool:
        return self.arch == "granitemoehybrid"

    @property
    def laguna(self) -> bool:
        return self.arch == "laguna"

    @property
    def router_scoring(self) -> str:
        """How the expert layers score (ops/lm.py `route`): `softmax` for `laguna`, `sigmoid_bias` for the others."""
        return "softmax" if self.laguna else "sigmoid_bias"

    def attention_kind(self, block: str) -> str:
        """A `laguna` layer's `layer_types` entry: `full_attention` or `sliding_attention`."""
        return self.lm.layer_types[int(block.split("_")[1])]

    def heads_of(self, block: str) -> int:
        """Query heads of a block's attention: its `num_attention_heads_per_layer` entry where the list is given."""
        per_layer = self.lm.num_attention_heads_per_layer
        return per_layer[int(block.split("_")[1])] if per_layer else self.lm.num_attention_heads

    def window_of(self, block: str) -> int | None:
        """The sliding window of a `laguna` block's attention, None for a full (causal) one and every other arch's."""
        return self.lm.sliding_window if self.laguna and self.attention_kind(block) == "sliding_attention" else None

    @property
    def loop_steps(self) -> int:
        """How often a step runs the layer stack (`train.loop_steps`): `total_ut_steps` of a looped model, else 1."""
        return self.lm.total_ut_steps if self.looped else 1

    @property
    def layer_applications(self) -> int:
        """Blocks run a step (`train.layer_applications`): what the layer checkpoints' kept tensors are counted by."""
        return len(self.block_names) * self.loop_steps

    def is_dense(self, block: str) -> bool:
        return block != "mtp" and int(block.split("_")[1]) < self.lm.first_k_dense_replace

    def mixer(self, block: str) -> str:
        """`kda` where `linear_attn_config.kda_layers` names the block's
        layer (numbered from 1, as the source numbers them), `mamba` where a
        hybrid's `layer_types` says "mamba" (numbered from 0), else `attn`."""
        if self.hybrid:
            return "mamba" if self.lm.layer_types[int(block.split("_")[1])] == "mamba" else "attn"
        kda_layers = self.lm.linear_attn_config.kda_layers
        return "kda" if block != "mtp" and int(block.split("_")[1]) + 1 in kda_layers else "attn"

    def blocks_mixing_by(self, kind: str) -> tuple[str, ...]:
        return tuple(b for b in self.block_names if self.mixer(b) == kind)

    def validate(self) -> None:
        c = self.lm
        kda, full = set(c.linear_attn_config.kda_layers), set(c.linear_attn_config.full_attn_layers)
        if kda & full:
            raise ValueError(f"layers {sorted(kda & full)} are in both kda_layers and full_attn_layers")
        unassigned = set(range(1, c.num_hidden_layers + 1)) - kda - full
        if (kda or full) and unassigned:
            raise ValueError(f"layers {sorted(unassigned)} are in neither kda_layers nor full_attn_layers")
        if c.n_routed_experts % c.expert_shares or not 0 <= c.expert_share_index < c.expert_shares:
            raise ValueError(f"{c.n_routed_experts} experts do not divide into {c.expert_shares} shares "
                             f"with a share of index {c.expert_share_index}")
        if c.num_nextn_predict_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers is 0 or 1")
        if c.qk_rope_head_dim % 2 and self.blocks_mixing_by("attn"):
            raise ValueError(f"qk_rope_head_dim must be even where a layer mixes by latent attention "
                             f"({', '.join(self.blocks_mixing_by('attn'))})")
        if not 0 < c.first_k_dense_replace <= c.num_hidden_layers:
            raise ValueError("first_k_dense_replace (the leading layers whose MLP is dense: every layer of a model "
                             "without expert layers) must be in [1, num_hidden_layers]")
        if self.looped:
            if c.num_key_value_heads not in (None, c.num_attention_heads):
                raise ValueError(f"arch ouro is plain multi-head attention: num_key_value_heads {c.num_key_value_heads} "
                                 f"is not num_attention_heads {c.num_attention_heads}, and grouped heads are not guessed")
            if not c.head_dim or c.head_dim % 2:
                raise ValueError(f"arch ouro rotates the whole head: head_dim must be even, not {c.head_dim}")
            if c.first_k_dense_replace != c.num_hidden_layers or c.num_nextn_predict_layers or kda or full:
                raise ValueError("arch ouro has no expert layer, no MTP module and no layer pattern: "
                                 "first_k_dense_replace = num_hidden_layers, num_nextn_predict_layers = 0, "
                                 "linear_attn_config empty")
            if c.total_ut_steps < 1:
                raise ValueError("total_ut_steps must be at least 1")
        if self.hybrid:
            self._validate_hybrid()
        elif self.laguna:
            self._validate_laguna()
        elif c.layer_types:
            raise ValueError(f"arch {self.arch} reads no layer_types (granitemoehybrid's and laguna's)")
        if not self.laguna:
            theirs = sorted(k for k, none in LAGUNA_KEYS.items() if getattr(c, k) != none)
            if theirs:
                raise ValueError(f"arch {self.arch} reads none of {theirs} (laguna's)")

    def _validate_laguna(self) -> None:
        c = self.lm
        n = c.num_hidden_layers
        if len(c.layer_types) != n or set(c.layer_types) - {"full_attention", "sliding_attention"}:
            raise ValueError(f"layer_types must name each of the {n} layers 'full_attention' or 'sliding_attention', "
                             f"not {list(c.layer_types)}")
        kv = c.num_key_value_heads
        heads = c.num_attention_heads_per_layer
        if len(heads) != n or not kv or not c.head_dim or any(h % kv for h in heads):
            raise ValueError(f"num_attention_heads_per_layer must give each of the {n} layers a head count that "
                             f"num_key_value_heads ({kv}) divides, with a head_dim; not {list(heads)}")
        if "sliding_attention" in c.layer_types and not (c.sliding_window or 0) >= 1:
            raise ValueError("a sliding_attention layer needs a sliding_window of at least one position")
        for kind in set(c.layer_types):
            spec = getattr(c.rope_parameters, kind)
            rotated = c.head_dim * spec.partial_rotary_factor
            if rotated != int(rotated) or int(rotated) % 2 or not 0 < rotated <= c.head_dim:
                raise ValueError(f"rope_parameters.{kind}: partial_rotary_factor {spec.partial_rotary_factor} must "
                                 f"rotate an even number of head_dim's {c.head_dim} channels")
            if spec.rope_type not in ("default", "yarn") or (
                    spec.rope_type == "yarn" and (spec.factor <= 0 or spec.original_max_position_embeddings < 1)):
                raise ValueError(f"rope_parameters.{kind}: rope_type default, or yarn with a factor and "
                                 f"original_max_position_embeddings, not {spec}")
        if c.first_k_dense_replace < n and not c.shared_expert_intermediate_size:
            raise ValueError("arch laguna's expert layers have a shared expert of shared_expert_intermediate_size")
        if c.linear_attn_config.kda_layers or c.linear_attn_config.full_attn_layers:
            raise ValueError("arch laguna has no KDA layer: linear_attn_config empty")
        others = sorted(k for k, none in NOT_LAGUNAS.items() if getattr(c, k) != none)
        if others:
            raise ValueError(f"arch laguna reads none of {others}; they must say so: "
                             f"{ {k: NOT_LAGUNAS[k] for k in others} }")

    def _validate_hybrid(self) -> None:
        c = self.lm
        if len(c.layer_types) != c.num_hidden_layers or set(c.layer_types) - {"mamba", "attention"}:
            raise ValueError(f"layer_types must name each of the {c.num_hidden_layers} layers 'mamba' or 'attention', "
                             f"not {list(c.layer_types)}")
        if c.first_k_dense_replace != c.num_hidden_layers or c.num_nextn_predict_layers or (
                c.linear_attn_config.kda_layers or c.linear_attn_config.full_attn_layers):
            raise ValueError("arch granitemoehybrid has no expert layer, no MTP module and no KDA layer: "
                             "first_k_dense_replace = num_hidden_layers, num_nextn_predict_layers = 0, "
                             "linear_attn_config empty")
        kv = c.num_key_value_heads or c.num_attention_heads
        if not c.head_dim or c.num_attention_heads % kv:
            raise ValueError(f"grouped-query attention needs a head_dim and num_key_value_heads ({kv}) dividing "
                             f"num_attention_heads ({c.num_attention_heads})")

    def attention_sites(self, compute_dtype) -> tuple[int, int]:
        """(layers that mix by softmax attention, latent or `ouro`'s plain
        multi-head, those whose shapes ops/lm_attention.py's fused kernels
        take): what `train.attn_sites` reports and, where the step is lowered
        for a TPU, `train.attn_fused_sites` (train/steps.py). The predicate is
        the one `ops.causal_attention` dispatches on. A site is a LAYER: a
        looped model calls each `loop_steps` times a step
        (`layer_applications`). KDA layers are `kda_sites`."""
        c = self.lm
        sites = len(self.blocks_mixing_by("attn"))
        block = min(ops.ATTN_BLOCK, c.seq_len)
        plain = self.looped or self.hybrid or self.laguna
        qk_dim, v_dim = (c.head_dim, c.head_dim) if plain else (c.qk_nope_head_dim + c.qk_rope_head_dim, c.v_head_dim)
        # as `ops.causal_attention` hands q, k and v on
        fits = lm_attention.fuses(c.seq_len, block, *lm_attention.fitting_dims(c.seq_len, block, qk_dim, v_dim,
                                                                                compute_dtype), compute_dtype)
        return sites, sites if fits else 0

    @property
    def window_sites(self) -> int:
        """The attention layers within a sliding window (`train.attn_window_sites`): among `attention_sites`'."""
        return sum(self.window_of(block) is not None for block in self.blocks_mixing_by("attn"))

    def window_fitting_sites(self, compute_dtype) -> int:
        """The windowed layers whose shapes the window's kernels of
        ops/lm_attention_kernels.py take (the causal kernels' predicate, which
        `ops.causal_attention` dispatches on for both): what
        `train.attn_window_fused_sites` reports where the step is lowered for a
        TPU (train/steps.py)."""
        return self.window_sites if self.attention_sites(compute_dtype)[1] else 0

    @property
    def kda_sites(self) -> int:
        return len(self.blocks_mixing_by("kda"))

    def kda_fitting_sites(self, compute_dtype) -> int:
        """The KDA layers whose shapes ops/lm_kda_kernels.py's fused kernels
        take, by the predicate `lm_kda.kda_core` dispatches on: what
        `train.kda_fused_sites` reports where the step is lowered for a TPU
        (train/steps.py), and `train.kda_scan_fused_sites` (the scan's kernels
        take the same sites)."""
        la = self.lm.linear_attn_config
        fits = lm_kda.fuses(self.lm.seq_len, min(lm_kda.KDA_CHUNK, self.lm.seq_len), la.head_dim, compute_dtype)
        return self.kda_sites if fits else 0

    def kda_conv_fitting_sites(self, compute_dtype) -> int:
        """The KDA layers whose three short convolutions (and q's and k's L2
        norms) the conv kernels of ops/lm_kda_kernels.py take, by the predicate
        `lm_kda.conv_and_norm` dispatches on: what `train.kda_conv_fused_sites`
        reports where the step is lowered for a TPU (train/steps.py)."""
        la = self.lm.linear_attn_config
        fits = lm_kda.conv_fuses(self.lm.seq_len, la.head_dim, la.short_conv_kernel_size, compute_dtype)
        return self.kda_sites if fits else 0

    @property
    def ssd_sites(self) -> int:
        return len(self.blocks_mixing_by("mamba"))

    def ssd_conv_fitting_sites(self, compute_dtype) -> int:
        """The Mamba-2 layers whose xBC convolution the conv kernels of
        ops/lm_kda_kernels.py take, by the predicate `lm_mamba.mamba_mixer`
        dispatches on: what `train.ssd_conv_fused_sites` reports where the
        step is lowered for a TPU (train/steps.py)."""
        c = self.lm
        channels = c.mamba_n_heads * c.mamba_d_head + 2 * c.mamba_d_state
        return self.ssd_sites if lm_mamba.conv_fuses(c.seq_len, channels, c.mamba_d_conv, compute_dtype) else 0

    def ssd_fitting_sites(self, compute_dtype) -> int:
        """The Mamba-2 layers whose in-chunk SSD work the kernels of
        ops/lm_mamba_kernels.py take, by the predicate `lm_mamba.ssd_core`
        dispatches on: what `train.ssd_fused_sites` reports where the step is
        lowered for a TPU (train/steps.py)."""
        c = self.lm
        chunk = min(c.mamba_chunk_size, c.seq_len)
        fits = lm_mamba.fuses(c.seq_len, chunk, c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state, compute_dtype)
        return self.ssd_sites if fits else 0

    @property
    def expert_sites(self) -> int:
        """The expert layers (`train.moe_sites`): what `moe_bounded_sites` reads on a
        step whose every site's held assignments fit `expert_capacity_rows`."""
        return sum(not self.is_dense(block) for block in self.block_names)

    def expert_capacity_rows(self, sequences: int) -> int:
        """`ops.site_capacity` of every expert layer for a batch of `sequences` on one replica."""
        c = self.lm
        if not self.expert_sites:
            return 0
        return ops.site_capacity(sequences * c.seq_len * c.num_experts_per_tok, self.experts_held, c.n_routed_experts,
                                 self.router_scoring)

    def param_count(self) -> int:
        shapes = jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0))[0])
        return sum(int(x.size) for x in jax.tree.leaves(shapes))

    # ---- parameters and state --------------------------------------------

    def _init_block(self, key, block: str) -> dict:
        c = self.lm
        h, heads = c.hidden_size, c.num_attention_heads

        def w(name, *shape):
            return c.init_std * jax.random.normal(jax.random.fold_in(key, _key(name)), shape, jnp.float32)

        def mlp(name, width, *lead):
            return {"gate": w(name + "g", *lead, h, width), "up": w(name + "u", *lead, h, width),
                    "down": w(name + "d", *lead, width, h)}

        p = {"attn_norm": jnp.ones((h,), jnp.float32), "mlp_norm": jnp.ones((h,), jnp.float32)}
        if self.laguna:
            return self._init_laguna(block, p, w, mlp)
        if self.hybrid:  # a Mamba-2 or a grouped-query attention mixer, and a dense MLP
            kv = (c.num_key_value_heads or heads) * c.head_dim
            p["mlp"] = mlp("mlp", c.intermediate_size)
            if self.mixer(block) == "mamba":
                p["mamba"] = self._init_mamba(key, w)
            else:
                p["attn"] = {"q": w("q", h, heads * c.head_dim), "k": w("k", h, kv), "v": w("v", h, kv),
                             "o": w("o", heads * c.head_dim, h)}
            return p
        if self.looped:  # a sandwich block: a second gain a sub-layer, on its output; q, k, v, o whole
            wide = heads * c.head_dim
            p.update(attn_out_norm=jnp.ones((h,), jnp.float32), mlp_out_norm=jnp.ones((h,), jnp.float32),
                     attn={"q": w("q", h, wide), "k": w("k", h, wide), "v": w("v", h, wide), "o": w("o", wide, h)},
                     mlp=mlp("mlp", c.intermediate_size))
            return p
        if self.mixer(block) == "kda":
            p["kda"] = self._init_kda(key, w)
        else:
            q_width = heads * (c.qk_nope_head_dim + c.qk_rope_head_dim)
            q = ({"q": w("q", h, q_width)} if c.q_lora_rank is None else
                 {"q_a": w("q_a", h, c.q_lora_rank), "q_norm": jnp.ones((c.q_lora_rank,), jnp.float32),
                  "q_b": w("q_b", c.q_lora_rank, q_width)})
            p["attn"] = {
                **q,
                "kv_a": w("kv_a", h, c.kv_lora_rank + c.qk_rope_head_dim),
                "kv_norm": jnp.ones((c.kv_lora_rank,), jnp.float32),
                "kv_b": w("kv_b", c.kv_lora_rank, heads * (c.qk_nope_head_dim + c.v_head_dim)),
                "o": w("o", heads * c.v_head_dim, h),
            }
        if self.is_dense(block):
            p["mlp"] = mlp("mlp", c.intermediate_size)
        else:
            p["router"] = w("rout", h, c.n_routed_experts)
            p["shared"] = mlp("shar", c.moe_intermediate_size * c.n_shared_experts)
            p["experts"] = mlp("exp", c.moe_intermediate_size, self.experts_held)
        return p

    def _init_laguna(self, block: str, p: dict, w, mlp) -> dict:
        """A `laguna` block: grouped-query attention of the block's own head
        count, with its per-head output gate `gate` (h, heads); then a dense
        MLP, or the router over every expert, the held experts and the shared
        expert with its gate `shared.sigmoid_gate` (h,): every tensor
        N(0, init_std)."""
        c = self.lm
        h, heads, kv = c.hidden_size, self.heads_of(block), c.num_key_value_heads * c.head_dim
        p["attn"] = {"q": w("q", h, heads * c.head_dim), "k": w("k", h, kv), "v": w("v", h, kv),
                     "o": w("o", heads * c.head_dim, h), "gate": w("attn_gate", h, heads)}
        if self.is_dense(block):
            p["mlp"] = mlp("mlp", c.intermediate_size)
        else:
            p["router"] = w("rout", h, c.n_routed_experts)
            p["shared"] = {**mlp("shar", c.shared_expert_intermediate_size), "sigmoid_gate": w("shar_gate", h)}
            p["experts"] = mlp("exp", c.moe_intermediate_size, self.experts_held)
        return p

    def _init_kda(self, key, w) -> dict:
        """One KDA mixer's parameters (ops/lm_kda.py `kda_attention`). Beside
        the N(0, init_std) matrices and filters: `A_log` = ln(U[1, 16]) a head,
        `dt_bias` = softplus^-1(dt) with dt log-uniform in [1e-3, 1e-1] a
        channel (the decay a fresh layer starts from: e^{-A dt} a position)."""
        c = self.lm
        la = c.linear_attn_config
        h, wide = c.hidden_size, la.num_heads * la.head_dim
        rate = jax.random.uniform(jax.random.fold_in(key, _key("A_log")), (la.num_heads,), jnp.float32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(jax.random.fold_in(key, _key("dt_bias")), (wide,), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return {
            "q": w("kda_q", h, wide), "k": w("kda_k", h, wide), "v": w("kda_v", h, wide),
            **{"conv_" + n: w("conv_" + n, la.short_conv_kernel_size, wide) for n in ("q", "k", "v")},
            "f_a": w("f_a", h, la.head_dim), "f_b": w("f_b", la.head_dim, wide),
            "A_log": jnp.log(rate), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "b": w("kda_b", h, la.num_heads),
            "g_a": w("g_a", h, la.head_dim), "g_b": w("g_b", la.head_dim, wide),
            "o_norm": jnp.ones((la.head_dim,), jnp.float32), "o": w("kda_o", wide, h),
        }

    def _init_mamba(self, key, w) -> dict:
        """One Mamba-2 mixer's parameters (ops/lm_mamba.py `mamba_mixer`).
        Beside the N(0, init_std) projections: the depthwise filter and its
        bias U(-1/sqrt(taps), 1/sqrt(taps)), a Conv1d's own initialisation;
        `A_log` = ln(U[1, 16]) and `dt_bias` = softplus^-1(dt) with dt
        log-uniform in [1e-3, 1e-1], a head each; `D` and the gated norm's
        gain 1."""
        c = self.lm
        h, heads = c.hidden_size, c.mamba_n_heads
        inner = heads * c.mamba_d_head
        channels = inner + 2 * c.mamba_d_state
        bound = c.mamba_d_conv ** -0.5
        draw = lambda name, shape, lo, hi: jax.random.uniform(  # noqa: E731
            jax.random.fold_in(key, _key(name)), shape, jnp.float32, lo, hi)
        dt = jnp.exp(draw("ssd_dt_bias", (heads,), math.log(1e-3), math.log(1e-1)))
        return {
            "in_proj": w("in_proj", h, inner + channels + heads),
            "conv": draw("ssd_conv", (c.mamba_d_conv, channels), -bound, bound),
            "conv_bias": draw("ssd_conv_bias", (channels,), -bound, bound),
            "A_log": jnp.log(draw("ssd_A_log", (heads,), 1.0, 16.0)),
            "D": jnp.ones((heads,), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm": jnp.ones((inner,), jnp.float32),
            "out_proj": w("out_proj", inner, h),
        }

    def init(self, key) -> tuple[dict, dict]:
        """(params, state): float32 weights ~ N(0, init_std), norm gains 1 (a
        KDA mixer's decay parameters: `_init_kda`; a looped model's exit gate:
        zeros, so that a fresh gate exits with probability 1/2 after every
        step); state = each expert block's router bias, zeros."""
        self.validate()
        c = self.lm
        h = c.hidden_size
        params: dict[str, Any] = {
            "embed": c.init_std * jax.random.normal(jax.random.fold_in(key, _key("embed")), (self.vocab, h)),
            "head": c.init_std * jax.random.normal(jax.random.fold_in(key, _key("head")), (h, self.vocab)),
            "final_norm": jnp.ones((h,), jnp.float32),
        }
        if c.tie_word_embeddings:  # the head is the embedding, read transposed
            del params["head"]
        for i, block in enumerate(self.block_names):
            params[block] = self._init_block(jax.random.fold_in(key, 1000 + i), block)
        if self.looped:
            params["exit_gate"] = {"w": jnp.zeros((h,), jnp.float32), "b": jnp.zeros((), jnp.float32)}
        if c.num_nextn_predict_layers:
            params["mtp"].update(
                eh_proj=c.init_std * jax.random.normal(jax.random.fold_in(key, _key("eh")), (2 * h, h)),
                h_norm=jnp.ones((h,), jnp.float32), e_norm=jnp.ones((h,), jnp.float32),
                final_norm=jnp.ones((h,), jnp.float32))
        state = {block: {"router_bias": jnp.zeros((c.n_routed_experts,), jnp.float32)}
                 for block in self.block_names if not self.is_dense(block) and self.router_scoring == "sigmoid_bias"}
        return params, state

    # ---- forward ----------------------------------------------------------

    def _branch(self, x, out):
        """x + out, the branch scaled by `residual_multiplier` where it is not 1."""
        m = self.lm.residual_multiplier
        with scope("residual"):
            return x + (out if m == 1.0 else out * m)

    def _mixed(self, p: dict, x, cos, sin, window=None):
        """A block's first half, x + Mixer(norm(x)): (x, its KDA or Mamba-2
        mixer's most negative in-chunk log decay or None). A `laguna` block's
        mixer reads the tables of its layer type, and its `window`."""
        c = self.lm
        normed = ops.rms_norm(x, p["attn_norm"], c.rms_norm_eps)
        lowest = None
        if self.laguna:
            a = ops.mha_attention(p["attn"], normed, cos, sin, heads=p["attn"]["q"].shape[1] // c.head_dim,
                                  head_dim=c.head_dim, kv_heads=c.num_key_value_heads, window=window)
        elif "mamba" in p:
            a, lowest = lm_mamba.mamba_mixer(p["mamba"], normed, heads=c.mamba_n_heads, head_dim=c.mamba_d_head,
                                             state=c.mamba_d_state, chunk=c.mamba_chunk_size, eps=c.rms_norm_eps)
        elif self.hybrid:
            a = ops.mha_attention(p["attn"], normed, None, None, heads=c.num_attention_heads, head_dim=c.head_dim,
                                  kv_heads=c.num_key_value_heads, scale=c.attention_multiplier)
        elif "kda" in p:
            la = c.linear_attn_config
            a, lowest = lm_kda.kda_attention(p["kda"], normed, heads=la.num_heads, head_dim=la.head_dim,
                                             eps=c.rms_norm_eps)
        else:
            a = ops.mla_attention(
                p["attn"], normed, cos, sin,
                heads=c.num_attention_heads, nope=c.qk_nope_head_dim, rope=c.qk_rope_head_dim,
                v_dim=c.v_head_dim, kv_rank=c.kv_lora_rank, eps=c.rms_norm_eps)
        return self._branch(x, a), lowest

    def _fed(self, block: str, p: dict, bias, x):
        """A block's second half, x + FFN(norm(x)): (x, what its expert layer reports or None)."""
        c = self.lm
        y = ops.rms_norm(x, p["mlp_norm"], c.rms_norm_eps)
        if self.is_dense(block):
            return self._branch(x, ops.gated_mlp(p["mlp"], y)), None
        routed, load, counters, ids = ops.expert_layer(
            p, bias, y, top_k=c.num_experts_per_tok, scaling=c.routed_scaling_factor,
            held=self.experts_held, share_index=c.expert_share_index, scoring=self.router_scoring)
        shared = ops.gated_mlp(p["shared"], y)
        if "sigmoid_gate" in p["shared"]:  # the shared expert times sigmoid(y . w_s), a scalar a token
            with scope("mlp"):
                gate = jnp.dot(y, p["shared"]["sigmoid_gate"].astype(y.dtype), preferred_element_type=jnp.float32)
                shared = shared * jax.nn.sigmoid(gate)[..., None].astype(shared.dtype)
        with scope("residual"):
            return x + shared + routed, (load, counters, ids)

    def _seq_of(self, tokens) -> int:
        if tokens.shape[1] != self.lm.seq_len + 2:
            raise ValueError(f"a batch row holds {tokens.shape[1]} ids, model.lm.seq_len + 2 = {self.lm.seq_len + 2} expected")
        return self.lm.seq_len

    def _head_of(self, params):
        """The head (h, vocabulary rows): the embedding itself where it is tied."""
        return params["embed"].T if self.lm.tie_word_embeddings else params["head"]

    def _head_loss(self, head_w, hidden, targets, per_token: bool = False):
        """Summed cross-entropy, and how many targets rank first and among the
        first five, over a (tokens, h) block: float32 logits over the slice
        (divided by `logits_scaling` where it is not 1), never more than
        `LOSS_BLOCK` tokens of them at once. `per_token`: and every token's
        cross-entropy, (tokens,), which a looped model weights by its exit
        distribution."""
        scaling = self.lm.logits_scaling
        tokens = hidden.shape[0]
        block = min(LOSS_BLOCK, tokens)
        if tokens % block:
            raise ValueError(f"{tokens} tokens are not a multiple of the loss block {block}")

        def chunk(carry, xs):
            hid, tgt = xs
            with scope("lm_head"):
                logits = jnp.dot(hid, head_w.astype(hid.dtype), preferred_element_type=jnp.float32)
                if scaling != 1.0:
                    logits = logits / scaling
            with scope("loss"):
                own = jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0]
                nll = jax.nn.logsumexp(logits, axis=-1) - own
                above = jnp.sum(lax.stop_gradient(logits) > lax.stop_gradient(own)[:, None], axis=-1)
                out = jnp.stack([jnp.sum(nll), jnp.sum(above < 1).astype(jnp.float32),
                                 jnp.sum(above < 5).astype(jnp.float32)])
            return carry + out, (nll if per_token else None)

        chunk = jax.checkpoint(chunk)
        xs = (hidden.reshape(tokens // block, block, -1), targets.reshape(tokens // block, block))
        totals, nll = lax.scan(chunk, jnp.zeros((3,), jnp.float32), xs)
        return (totals, nll.reshape(tokens)) if per_token else totals

    def _sandwich(self, p: dict, x, cos, sin):
        """A looped model's block: y = x + N(Attn(N(x))), then y + N(MLP(N(y))), four gains."""
        c = self.lm
        a = ops.mha_attention(p["attn"], ops.rms_norm(x, p["attn_norm"], c.rms_norm_eps), cos, sin,
                              heads=c.num_attention_heads, head_dim=c.head_dim)
        a = ops.rms_norm(a, p["attn_out_norm"], c.rms_norm_eps)
        with scope("residual"):
            x = x + a
        m = ops.rms_norm(ops.gated_mlp(p["mlp"], ops.rms_norm(x, p["mlp_norm"], c.rms_norm_eps)),
                         p["mlp_out_norm"], c.rms_norm_eps)
        with scope("residual"):
            return x + m

    def _looped(self, params, tokens, compute_dtype):
        """A looped model's forward: tokens (B, seq_len + 2; the last id is not
        read) -> (head totals (R, 3), every token's cross-entropy (R, B * S),
        every token's exit-gate logit (R, B * S)), one row a loop step. Step r
        reads step r - 1's output AFTER the final norm, which is inside the
        loop; positions, and so the rotation, are the same in every step. The
        loop is written out, R x layers blocks in one program: as ONE
        `lax.scan` body it compiles in 20 s where this takes 51, steps 0.13%
        slower and holds 1.5 GiB more (the weight gradients' sums ride in the
        scan's carry), which at the cell's size passes the chip's memory as
        the benchmark sums it (PERF.md, PR 35)."""
        c = self.lm
        seq = self._seq_of(tokens)
        with scope("rope"):
            cos, sin = ops.rope_tables(seq, c.head_dim, c.rope_theta)
        with scope("embed"):
            x = params["embed"][tokens[:, :seq]].astype(compute_dtype)
        targets = tokens[:, 1:seq + 1].reshape(-1)
        # a layer's checkpoint keeps its input and, by name, attention's output and row log-sum-exp (`forward`
        # says why): here once an APPLICATION, `loop_steps` times a layer
        block = jax.checkpoint(lambda x_, p_: self._sandwich(p_, x_, cos, sin),
                               policy=jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES))
        gate = params["exit_gate"]

        by_step = []
        for _ in range(c.total_ut_steps):
            for name in self.block_names:
                x = block(x, params[name])
            x = ops.rms_norm(x, params["final_norm"], c.rms_norm_eps)
            flat = x.reshape(-1, c.hidden_size)
            totals, nll = self._head_loss(params["head"], flat, targets, per_token=True)
            with scope("exit_gate"):  # the last step's is computed and not read (`_expected_loss`)
                logit = jnp.dot(flat.astype(jnp.float32), gate["w"], precision=lax.Precision.HIGHEST) + gate["b"]
            by_step.append((totals, nll, logit))
        return tuple(jnp.stack(rows) for rows in zip(*by_step))

    def _expected_loss(self, nll, logits):
        """(loss a token's mean, scalars) from every step's cross-entropy and
        gate logit, (R, tokens) each, in float32: the exit distribution p_1 =
        g_1, p_r = g_r prod_{j<r}(1 - g_j), p_R = prod_{j<R}(1 - g_j) (g =
        sigmoid(logit); the last step's gate is not read), the loss sum_r p_r
        CE_r - exit_entropy_weight * H(p)."""
        with scope("exit_gate"):
            stay = jnp.cumsum(jax.nn.log_sigmoid(-logits[:-1]), axis=0)  # ln prod_{j<=r}(1 - g_j), r < R
            reached = jnp.concatenate([jnp.zeros_like(logits[:1]), stay])  # ln P(step r is reached), r = 1..R
            log_p = jnp.concatenate([jax.nn.log_sigmoid(logits[:-1]) + reached[:-1], reached[-1:]])
            p = jnp.exp(log_p)
            entropy = -jnp.sum(p * log_p, axis=0)
            steps = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)[:, None]
            expected = jnp.sum(p * nll, axis=0)
            loss = jnp.mean(expected - self.lm.exit_entropy_weight * entropy)
            scalars = {"ce": jnp.mean(expected), "exit_p_last": jnp.mean(p[-1]), "exit_entropy": jnp.mean(entropy),
                       "expected_exit_step": jnp.mean(jnp.sum(steps * p, axis=0)),
                       **{f"ce_step_{r + 1}": jnp.mean(nll[r]) for r in range(p.shape[0])}}
        return loss, scalars

    def forward(self, params, state, tokens, *, compute_dtype=jnp.float32, axis_name: str | None = None):
        """tokens (B, seq_len + 2) -> (per-head totals {head: [nll sum, top-1,
        top-5]}, new_state, counters, selected). Head `main` predicts token
        i + 1 from tokens[..i], head `mtp` token i + 2 from the main model's
        last hidden state at i and the embedding of token i + 1. new_state:
        each router bias after the sign rule's one move. selected: each
        expert block's chosen expert ids, (B * seq_len, top_k)."""
        c = self.lm
        seq = self._seq_of(tokens)
        if self.looped:  # the LAST loop step's head: every step runs (`early_exit_threshold` 1)
            return {"main": self._looped(params, tokens, compute_dtype)[0][-1]}, {}, {}, {}
        cos = sin = None
        tables = {}  # `laguna`'s: (cos, sin) by layer type, each made once a step
        if self.laguna:
            with scope("rope"):
                tables = {kind: ops.rope_tables_of(seq, c.head_dim, getattr(c.rope_parameters, kind))
                          for kind in sorted(set(c.layer_types))}
        elif not (c.mla_use_nope or self.hybrid):  # a hybrid's attention rotates nothing
            with scope("rope"):
                cos, sin = ops.rope_tables(seq, c.qk_rope_head_dim, c.rope_theta)
        with scope("embed"):
            # one gather for both heads' inputs: positions 0..seq of every row
            # (float32 rows, then the cast: a frequent token's gradient is summed in float32)
            emb = params["embed"][tokens[:, :seq + 1]]
            if c.embedding_multiplier != 1.0:
                emb = emb * c.embedding_multiplier
            emb = emb.astype(compute_dtype)

        # Across the step a layer keeps its input and, by name, its attention's output and row log-sum-exp
        # (B, H, S, Dv in the compute dtype; B, H, S float32). The backward runs the rest of the layer again
        # (norms, MLA projections, RoPE, the q/k joins, MLP, the expert layer), but not the attention forward:
        # its custom_vjp needs from that second run q, k, v alone. A KDA layer likewise keeps its scan's output
        # and chunk-boundary states, and its second run makes the in-chunk matrices again, not the scan.
        kept = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)

        def run(block, x, p, bias):
            turn = tables[self.attention_kind(block)] if self.laguna else (cos, sin)
            window = self.window_of(block)

            def fn(x_, p_, b_):
                x_, lowest = self._mixed(p_, x_, *turn, window)
                return (*self._fed(block, p_, b_, x_), lowest)

            return jax.checkpoint(fn, policy=kept)(x, p, bias)

        new_state, selected, per_block, lowest_by_block = {}, {}, [], []

        def through(block, x):
            bias = state[block]["router_bias"] if block in state else None
            x, routed, lowest = run(block, x, params[block], bias)
            if lowest is not None:
                lowest_by_block.append(lowest)
            if routed is not None:
                load, counters, selected[block] = routed
                if bias is not None:  # a softmax router (`laguna`) holds no state
                    with scope("moe_router"):
                        if axis_name is not None:
                            load = lax.psum(load, axis_name)
                        new_state[block] = {"router_bias": bias + c.router_bias_rate * jnp.sign(jnp.mean(load) - load)}
                per_block.append(counters)
            return x

        x = emb[:, :seq]
        for block in self.block_names:
            if block != "mtp":
                x = through(block, x)
        heads = {}
        hidden = ops.rms_norm(x, params["final_norm"], c.rms_norm_eps)
        heads["main"] = self._head_loss(self._head_of(params), hidden.reshape(-1, c.hidden_size),
                                        tokens[:, 1:seq + 1].reshape(-1))
        if c.num_nextn_predict_layers:
            m = params["mtp"]
            with scope("mtp_merge"):
                merged = jnp.concatenate([ops.rms_norm(x, m["h_norm"], c.rms_norm_eps),
                                          ops.rms_norm(emb[:, 1:], m["e_norm"], c.rms_norm_eps)], axis=-1)
                y = merged @ m["eh_proj"].astype(compute_dtype)
            y = through("mtp", y)
            hidden = ops.rms_norm(y, m["final_norm"], c.rms_norm_eps)
            heads["mtp"] = self._head_loss(params["head"], hidden.reshape(-1, c.hidden_size),
                                           tokens[:, 2:seq + 2].reshape(-1))
        with scope("moe_combine"):
            counters = {
                "moe_assignments_here": sum(b["assignments_here"] for b in per_block),
                "moe_dropped": sum(b["dropped"] for b in per_block),
                "moe_bounded_sites": sum(b["bounded"] for b in per_block),
                "moe_load_max_over_mean": jnp.max(jnp.stack([b["load_max_over_mean"] for b in per_block])),
            } if per_block else {}
        if lowest_by_block:
            name = "ssd_min_chunk_log_decay" if self.hybrid else "kda_min_chunk_log_decay"
            with scope("ssd_gate") if self.hybrid else scope("kda_gate"):
                lowest = jnp.min(jnp.stack(lowest_by_block))
                counters[name] = lowest if axis_name is None else lax.pmin(lowest, axis_name)
        return heads, new_state, counters, selected

    def loss(self, params, state, batch, *, compute_dtype=jnp.float32, axis_name: str | None = None):
        """The train step's loss: `(loss, (new_state, scalars))`, loss =
        CE_main + mtp_loss_weight * CE_mtp, each the mean over this shard's
        tokens; a looped model's: `_expected_loss`, with `ce` the expected
        cross-entropy, `ce_step_<r>` each step's and `top1` the last step's."""
        tokens = batch["tokens"]
        if self.looped:
            totals, nll, logits = self._looped(params, tokens, compute_dtype)
            loss, scalars = self._expected_loss(nll, logits)
            return loss, ({}, {**scalars, "top1": totals[-1, 1] / nll.shape[1]})
        heads, new_state, counters, _ = self.forward(params, state, tokens, compute_dtype=compute_dtype,
                                                     axis_name=axis_name)
        with scope("loss"):
            n = jnp.asarray(tokens.shape[0] * self.lm.seq_len, jnp.float32)
            ce = heads["main"][0] / n
            scalars = {"ce": ce, "top1": heads["main"][1] / n, **counters}
            loss = ce
            if "mtp" in heads:
                scalars["ce_mtp"] = heads["mtp"][0] / n
                loss = ce + self.lm.mtp_loss_weight * scalars["ce_mtp"]
        return loss, (new_state, scalars)

    def eval_counts(self, params, state, batch, *, compute_dtype=jnp.float32) -> dict:
        """The main head's summed counts over a batch, in the form the eval
        loop adds up: top1, top5, n, loss_sum."""
        heads = self.forward(params, state, batch["tokens"], compute_dtype=compute_dtype)[0]
        nll, top1, top5 = heads["main"]
        n = jnp.asarray(batch["tokens"].shape[0] * self.lm.seq_len, jnp.float32)
        return {"top1": top1, "top5": top5, "n": n, "loss_sum": nll}

    def grad_scalars(self, grads: dict) -> dict:
        """Gradient norms by group, as step scalars: embedding, head, `W_eh`,
        a looped model's exit gate, and per block its mixer (`attn`, `kda` or
        `mamba`), router, held experts, shared or dense MLP and norm gains (a
        sandwich block's four). A tied vocabulary has no `head`: `embed` is the
        sum of both uses' gradients. What the benchmark holds against the
        reference."""
        def norm(tree):
            return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree)))

        out = {"gnorm/embed": norm(grads["embed"])}
        if "head" in grads:
            out["gnorm/head"] = norm(grads["head"])
        out["gnorm/final_norm"] = norm(grads["final_norm"])
        if "exit_gate" in grads:
            out["gnorm/exit_gate"] = norm(grads["exit_gate"])
        for block in self.block_names:
            g = grads[block]
            for name in ("attn", "kda", "mamba", "mlp", "router", "shared", "experts", "eh_proj"):
                if name in g:
                    out[f"gnorm/{block}/{name}"] = norm(g[name])
            out[f"gnorm/{block}/norms"] = norm([v for k, v in g.items() if k.endswith("norm")])
        return out


def token_model(cfg: ModelConfig) -> TokenModel:
    model = TokenModel(arch=cfg.arch, vocab=cfg.num_classes, lm=cfg.lm)
    model.validate()
    return model
