"""The token family through ``raft_tpu.train.train()`` and the CLI:
the real loop with the routing counters on its spans, a save and a
resume that continues the loss, and ``train.py --help`` naming the
family."""

import dataclasses
import json

import pytest

from raft_tpu.config import LMConfig, TrainConfig

# the small size of tests/test_lfm2.py
S = 128
SMALL = LMConfig(
    hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
    num_hidden_layers=3, layer_types=("conv", "full_attention", "conv"),
    num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
    num_experts=8, num_experts_per_tok=2, vocab_size=256,
    experts_held=2, expert_offset=2, vocab_held=64,
    mixed_precision=False)

# ------------------------------------------------------------------- train()

def _loss_rows(log_dir):
    with open(log_dir / "scalars.jsonl") as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if "loss" in r]


def test_train_loop_counters_and_resume(tmp_path):
    """``train()`` with ``model_family="lfm2_moe"`` through the real
    loop (8 sequences over the tests' 8-device data mesh): the routing
    counters ride every ``train.step`` span and the scalar stream; a
    second run resumed from the first's step-3 checkpoint continues its
    losses exactly (state, optimizer and the loader's cursor all came
    back)."""
    import shutil

    from raft_tpu.data.tokens import TokenLoader
    from raft_tpu.train import train
    from raft_tpu.utils.logger import TrainLogger
    from raft_tpu.utils.profiling import host_timer

    cfg = dataclasses.replace(SMALL, mixed_precision=True)
    tcfg = TrainConfig(name="lm", model_family="lfm2_moe", lr=3e-4,
                       wdecay=0.1, num_steps=6, batch_size=8, seq_len=S,
                       val_freq=3, sum_freq=1)

    def run(name, **kw):
        return train(
            tcfg, cfg, ckpt_dir=str(tmp_path / name),
            dataloader=TokenLoader(8, S, cfg.vocab, seed=3),
            logger=TrainLogger(str(tmp_path / name / "logs"), sum_freq=1,
                               tensorboard=False), **kw)

    state = run("whole")
    assert int(state.step) == 6
    spans = [s for s in host_timer().spans()
             if s.name == "train.step" and s.args.get("complete")][-6:]
    assert [s.unit for s in spans] == [1, 2, 3, 4, 5, 6]
    for span in spans:
        assert 8 * S - 160 < span.args["tokens"] < 8 * S
        assert span.args["dropped"] == 0
        # 2 expert layers, top 2 of 8 experts, 2 held: a quarter falls here
        assert 0 < span.args["routed_here"] <= 8 * S * 2 * 2
        assert span.args["expert_load_max"] * 2 * 2 >= \
            span.args["routed_here"]
    whole = _loss_rows(tmp_path / "whole" / "logs")
    assert len(whole) == 6
    assert all(k in whole[-1] for k in
               ("tokens", "routed_here", "expert_load_max", "dropped"))

    shutil.copytree(tmp_path / "whole" / "lm" / "3",
                    tmp_path / "cut" / "lm" / "3")
    state = run("cut", resume=True)
    assert int(state.step) == 6
    cut = _loss_rows(tmp_path / "cut" / "logs")
    assert [r["loss"] for r in cut] == [r["loss"] for r in whole[3:]]


def test_train_cli_names_the_family(capsys):
    from raft_tpu.train import lm_config_from_json, main
    with pytest.raises(SystemExit):
        main(["--help"])
    text = capsys.readouterr().out
    assert "lfm2_moe" in text and "--lm_config" in text \
        and "--seq_len" in text
    cfg = lm_config_from_json("benchmark/configs/lfm2_24b_a2b.json")
    assert (cfg.hidden_size, cfg.held, cfg.vocab, cfg.num_hidden_layers,
            cfg.num_dense_layers) == (2048, 8, 8192, 5, 1)
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv")
    with pytest.raises(SystemExit):
        main(["--model_family", "raft", "--lm_config", "x.json"])
