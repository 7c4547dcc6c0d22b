"""Three steps of the token family through the normal entry point, cli/train.py
on apps/glm_4_7_flash_ep8_share.yml at a toy size (CPU): the seeded token
source, the one step skeleton under parallel/dp.py, the log-boundary gauges,
eval on the held-out stream, and a checkpoint that restores as a TokenModel.
"""

import json
import os

import numpy as np

from yet_another_mobilenet_series_tpu.ckpt.manager import CheckpointManager
from yet_another_mobilenet_series_tpu.cli import train as cli_train
from yet_another_mobilenet_series_tpu.config import DataConfig
from yet_another_mobilenet_series_tpu.data import pipeline
from yet_another_mobilenet_series_tpu.models import TokenModel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
APP = os.path.join(REPO, "yet_another_mobilenet_series_tpu", "apps", "glm_4_7_flash_ep8_share.yml")
TOY = ["model.num_classes=256", "model.lm.hidden_size=64", "model.lm.num_hidden_layers=3",
       "model.lm.num_attention_heads=4", "model.lm.q_lora_rank=24", "model.lm.kv_lora_rank=16",
       "model.lm.qk_nope_head_dim=12", "model.lm.qk_rope_head_dim=4", "model.lm.v_head_dim=16",
       "model.lm.intermediate_size=160", "model.lm.moe_intermediate_size=48", "model.lm.n_routed_experts=16",
       "model.lm.num_experts_per_tok=2", "model.lm.seq_len=32"]


def test_three_steps_through_cli_train(tmp_path):
    log_dir = str(tmp_path / "log")
    final = cli_train.main([f"app:{APP}", *TOY, "data.fake_train_size=6", "train.epochs=1", "train.log_every=1",
                            f"train.log_dir={log_dir}", "dist.num_devices=1"])
    assert final["epoch"] == 1.0 and final["eval_n"] == 2 * 2 * 32 and np.isfinite(final["eval_loss"])
    with open(os.path.join(log_dir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f if '"train/' in line]
    assert len(rows) == 3
    last = rows[-1]
    assert last["train/moe_dropped"] == 0.0 and last["train/moe_assignments_here"] > 0
    assert [row["train/moe_bounded_sites"] for row in rows] == [3.0] * 3  # every expert site, every step: the held rows fit
    assert abs(last["train/ce"] - np.log(256)) < 0.2 and abs(last["train/ce_mtp"] - np.log(256)) < 0.2
    assert last["train/loss"] == last["train/ce"] + 0.3 * last["train/ce_mtp"] or abs(
        last["train/loss"] - last["train/ce"] - 0.3 * last["train/ce_mtp"]) < 1e-5
    assert "train/gnorm/layer_1/experts" in last and "train/gnorm/mtp/eh_proj" in last
    with open(os.path.join(log_dir, "obs_registry.json")) as f:
        registry = json.load(f)
    assert registry["train.moe_dropped"] == 0.0 and registry["train.moe_load_max_over_mean"] >= 1.0
    assert registry["train.tokens_per_s"] > 0 and registry["train.moe_assignments_here"] > 0
    assert (registry["train.moe_bounded_sites"], registry["train.moe_sites"], registry["train.moe_capacity_rows"]) == (3.0, 3.0, 64.0)
    mgr = CheckpointManager(log_dir + "/ckpt")
    step, net, _ = mgr.restore_spec()
    mgr.close()
    assert step == 3 and isinstance(net, TokenModel) and net.vocab == 256 and net.experts_held == 2


def test_token_batches_are_seeded_zipf_and_resume_where_they_left():
    cfg = DataConfig(dataset="fake", loader="tokens", seq_len=30)
    a = [b["tokens"] for _, b in zip(range(4), pipeline.token_batches(cfg, 8, 500, seed=3))]
    b = [b["tokens"] for _, b in zip(range(2), pipeline.token_batches(cfg, 8, 500, seed=3, start_step=2))]
    assert a[0].shape == (8, 32) and a[0].dtype == np.int32 and not np.array_equal(a[0], a[1])
    assert np.array_equal(a[2], b[0]) and np.array_equal(a[3], b[1])  # batch i is a function of (seed, i)
    ids = np.concatenate([x["tokens"].ravel() for x in pipeline.token_batches(cfg, 64, 500, seed=1, num_batches=20)])
    counts = np.bincount(ids, minlength=500)
    assert ids.min() >= 0 and ids.max() < 500
    assert 1.7 < counts[0] / counts[1] < 2.3 and counts[0] > 5 * counts[9]  # p(id) ~ 1 / (id + 1)
    assert len(list(pipeline.token_batches(cfg, 8, 500, seed=0, num_batches=3))) == 3


KIMI_APP = os.path.join(REPO, "yet_another_mobilenet_series_tpu", "apps", "kimi_linear_48b_ep32_share.yml")
KIMI_TOY = ["model.num_classes=256", "model.lm.hidden_size=64", "model.lm.num_attention_heads=4",
            "model.lm.kv_lora_rank=16", "model.lm.qk_nope_head_dim=8", "model.lm.qk_rope_head_dim=4",
            "model.lm.v_head_dim=8", "model.lm.linear_attn_config.head_dim=8", "model.lm.linear_attn_config.num_heads=4",
            "model.lm.intermediate_size=160", "model.lm.moe_intermediate_size=48", "model.lm.n_routed_experts=64",
            "model.lm.num_experts_per_tok=4", "model.lm.seq_len=32"]


def test_three_steps_of_kimi_linear_through_cli_train(tmp_path):
    """The second arch through the same entry point: apps/kimi_linear_48b_ep32_share.yml
    at a toy size, its five layers in the published pattern (KDA, KDA, KDA,
    MLA, KDA), one head, the KDA gauge at the log boundary, a checkpoint that
    restores as the same TokenModel (its `linear_attn_config` with it)."""
    log_dir = str(tmp_path / "log")
    final = cli_train.main([f"app:{KIMI_APP}", *KIMI_TOY, "data.fake_train_size=3", "train.epochs=1",
                            "train.log_every=1", f"train.log_dir={log_dir}", "dist.num_devices=1"])
    assert final["epoch"] == 1.0 and final["eval_n"] == 2 * 32 and np.isfinite(final["eval_loss"])
    with open(os.path.join(log_dir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f if '"train/' in line]
    assert len(rows) == 3
    last = rows[-1]
    assert last["train/moe_dropped"] == 0.0 and abs(last["train/ce"] - np.log(256)) < 0.2
    assert "train/ce_mtp" not in last and abs(last["train/loss"] - last["train/ce"]) < 1e-6
    assert last["train/kda_min_chunk_log_decay"] < 0.0
    assert {"train/gnorm/layer_0/kda", "train/gnorm/layer_3/attn", "train/gnorm/layer_4/kda"} <= set(last)
    assert "train/gnorm/layer_3/kda" not in last and "train/gnorm/layer_0/attn" not in last
    with open(os.path.join(log_dir, "obs_registry.json")) as f:
        registry = json.load(f)
    assert (registry["train.kda_sites"], registry["train.kda_kept_sites"], registry["train.attn_sites"]) == (4.0, 4.0, 1.0)
    assert registry["train.kda_min_chunk_log_decay"] < 0.0 and registry["train.tokens_per_s"] > 0
    mgr = CheckpointManager(log_dir + "/ckpt")
    step, net, _ = mgr.restore_spec()
    mgr.close()
    assert step == 3 and isinstance(net, TokenModel) and net.arch == "kimi_linear" and net.experts_held == 2
    assert [net.mixer(b) for b in net.block_names] == ["kda", "kda", "kda", "attn", "kda"]


OURO_APP = os.path.join(REPO, "yet_another_mobilenet_series_tpu", "apps", "ouro_2_6b_depth8.yml")
OURO_TOY = ["model.num_classes=256", "model.lm.hidden_size=64", "model.lm.num_hidden_layers=2",
            "model.lm.first_k_dense_replace=2", "model.lm.num_attention_heads=4", "model.lm.num_key_value_heads=4",
            "model.lm.head_dim=16", "model.lm.intermediate_size=160", "model.lm.seq_len=32"]


def test_three_steps_of_the_looped_arch_through_cli_train(tmp_path, capsys):
    """The third arch through the same entry point: apps/ouro_2_6b_depth8.yml
    at a toy size, 2 layers run 4 times a step, the banner of a model without
    an expert layer, the loop's gauges and exit statistics at the log boundary,
    eval on the last loop step's head, a checkpoint that restores as the same
    TokenModel."""
    log_dir = str(tmp_path / "log")
    final = cli_train.main([f"app:{OURO_APP}", *OURO_TOY, "data.fake_train_size=3", "train.epochs=1",
                            "train.log_every=1", f"train.log_dir={log_dir}", "dist.num_devices=1"])
    assert final["epoch"] == 1.0 and final["eval_n"] == 2 * 32 and np.isfinite(final["eval_loss"])
    banner = [line for line in capsys.readouterr().out.splitlines() if "model ouro" in line]
    assert banner and "no expert layer; 2 layers run 4 times a step; 256 vocabulary rows" in banner[0]
    assert "experts a layer" not in banner[0]
    with open(os.path.join(log_dir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f if '"train/' in line]
    assert len(rows) == 3
    last = rows[-1]
    assert all(abs(last[f"train/ce_step_{r}"] - np.log(256)) < 0.2 for r in (1, 2, 3, 4))
    assert abs(last["train/loss"] - (last["train/ce"] - 0.1 * last["train/exit_entropy"])) < 1e-5
    assert 1.8 < last["train/expected_exit_step"] < 1.95 and "train/gnorm/exit_gate" in last
    assert not [k for k in last if k.startswith("train/moe_") or k.endswith("ce_mtp")]
    with open(os.path.join(log_dir, "obs_registry.json")) as f:
        registry = json.load(f)
    assert (registry["train.loop_steps"], registry["train.layer_applications"], registry["train.attn_sites"],
            registry["train.moe_sites"], registry["train.moe_capacity_rows"]) == (4.0, 8.0, 2.0, 0.0, 0.0)
    assert registry["train.expected_exit_step"] == last["train/expected_exit_step"] and registry["train.tokens_per_s"] > 0
    assert 0.1 < registry["train.exit_p_last"] < 0.15 and 1.15 < registry["train.exit_entropy"] < 1.25
    mgr = CheckpointManager(log_dir + "/ckpt")
    step, net, _ = mgr.restore_spec()
    mgr.close()
    assert step == 3 and isinstance(net, TokenModel) and net.arch == "ouro" and net.loop_steps == 4


GRANITE_APP = os.path.join(REPO, "yet_another_mobilenet_series_tpu", "apps", "granite_4_0_h_micro.yml")
GRANITE_TOY = ["model.num_classes=256", "model.lm.hidden_size=64", "model.lm.num_hidden_layers=3",
               "model.lm.layer_types=[mamba,attention,mamba]", "model.lm.first_k_dense_replace=3",
               "model.lm.num_attention_heads=4", "model.lm.num_key_value_heads=2", "model.lm.head_dim=16",
               "model.lm.intermediate_size=96", "model.lm.mamba_n_heads=8", "model.lm.mamba_d_head=16",
               "model.lm.mamba_d_state=16", "model.lm.mamba_chunk_size=8", "model.lm.seq_len=32"]


def test_three_steps_of_the_hybrid_arch_through_cli_train(tmp_path, capsys):
    """The fourth arch through the same entry point: apps/granite_4_0_h_micro.yml
    at a toy size (Mamba-2, attention, Mamba-2; a tied vocabulary of 256),
    the banner of a model without an expert layer, the Mamba-2 gauges and the
    step's lowest chunk decay at the log boundary, eval, a checkpoint that
    restores as the same TokenModel."""
    log_dir = str(tmp_path / "log")
    final = cli_train.main([f"app:{GRANITE_APP}", *GRANITE_TOY, "data.fake_train_size=3", "train.epochs=1",
                            "train.log_every=1", f"train.log_dir={log_dir}", "dist.num_devices=1"])
    assert final["epoch"] == 1.0 and final["eval_n"] == 2 * 32 and np.isfinite(final["eval_loss"])
    banner = [line for line in capsys.readouterr().out.splitlines() if "model granitemoehybrid" in line]
    assert banner and "no expert layer; 3 layers run 1 times a step; 256 vocabulary rows" in banner[0]
    with open(os.path.join(log_dir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f if '"train/' in line]
    assert len(rows) == 3
    last = rows[-1]
    # a tied head over unit-rms states, logits / 8: ln(vocabulary) and a little
    assert abs(last["train/ce"] - np.log(256)) < 0.2 and last["train/loss"] == last["train/ce"]
    assert last["train/ssd_min_chunk_log_decay"] < 0 and "train/gnorm/layer_0/mamba" in last
    assert "train/gnorm/head" not in last and not [k for k in last if k.startswith("train/moe_")]
    with open(os.path.join(log_dir, "obs_registry.json")) as f:
        registry = json.load(f)
    assert (registry["train.ssd_sites"], registry["train.ssd_kept_sites"], registry["train.ssd_conv_fused_sites"],
            registry["train.ssd_fused_sites"], registry["train.attn_sites"], registry["train.moe_sites"]) == (
                2.0, 2.0, 0.0, 0.0, 1.0, 0.0)
    assert registry["train.ssd_min_chunk_log_decay"] == last["train/ssd_min_chunk_log_decay"]
    mgr = CheckpointManager(log_dir + "/ckpt")
    step, net, _ = mgr.restore_spec()
    mgr.close()
    assert step == 3 and isinstance(net, TokenModel) and net.arch == "granitemoehybrid"
    assert [net.mixer(b) for b in net.block_names] == ["mamba", "attn", "mamba"] and net.lm.tie_word_embeddings
