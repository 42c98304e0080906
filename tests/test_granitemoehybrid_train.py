"""The state-space hybrid through ``raft_tpu.train.train()`` and the
CLI: three steps of the real loop against the reference's three steps,
the family's counters on its spans and in the scalar stream, a save and
a resume that continues the loss, and ``train.py --help`` naming the
family."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.config import GraniteHybridConfig, TrainConfig

# the small size, the reference's keys and the gap by leaf
from test_granitemoehybrid import S, SMALL, ref_cfg, rel


def _loss_rows(log_dir):
    with open(log_dir / "scalars.jsonl") as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if "loss" in r]


def _document_starts(batch):
    return int((np.diff(batch["segment_ids"], axis=1) != 0).sum())


def test_train_loop_follows_the_reference_counts_and_resumes(tmp_path):
    """``train()`` with ``model_family="granitemoehybrid"`` through the
    real loop (8 sequences over the tests' 8-device data mesh), its
    state begun from the benchmark's seeded weights: the first three
    losses are the reference's three steps' (float32 against float32,
    1e-5 relative) and so are the parameters after them; ``tokens``,
    ``ssm_resets`` and ``ssd_chunks`` ride every ``train.step`` span and
    the scalar stream, the resets equal to the document starts of the
    batch trained on; a second run resumed from the first's step-3
    checkpoint continues its losses exactly."""
    import shutil

    from benchmark.drivers.ssm_train_steps import seeded_variables
    from benchmark.drivers.train_steps import Observed, observed
    from benchmark.reference import granitemoehybrid as reference
    from raft_tpu.data.tokens import TokenLoader
    from raft_tpu.train import train
    from raft_tpu.utils.logger import TrainLogger
    from raft_tpu.utils.profiling import host_timer

    cfg = SMALL
    tcfg = TrainConfig(name="ssm", model_family="granitemoehybrid", lr=3e-4,
                       wdecay=0.1, num_steps=6, batch_size=8, seq_len=S,
                       val_freq=3, sum_freq=1)
    variables = seeded_variables(cfg, 11)

    def run(name, **kw):
        return train(
            tcfg, cfg, ckpt_dir=str(tmp_path / name),
            dataloader=TokenLoader(8, S, cfg.vocab, seed=3),
            logger=TrainLogger(str(tmp_path / name / "logs"), sum_freq=1,
                               tensorboard=False), **kw)

    record = Observed(3)
    with observed(variables, record):
        state = run("whole")
    assert int(state.step) == 6
    batches = [b for _, b in zip(range(6), TokenLoader(8, S, cfg.vocab,
                                                       seed=3))]

    spans = [s for s in host_timer().spans()
             if s.name == "train.step" and s.args.get("complete")][-6:]
    assert [s.unit for s in spans] == [1, 2, 3, 4, 5, 6]
    for span, batch in zip(spans, batches):
        assert 8 * S - 160 < span.args["tokens"] < 8 * S
        assert span.args["ssm_resets"] == _document_starts(batch)
        assert span.args["ssd_chunks"] == 8 * S // 16
    # documents of median 700 in sequences of 128: few boundaries, some
    assert sum(s.args["ssm_resets"] for s in spans) > 0
    whole = _loss_rows(tmp_path / "whole" / "logs")
    assert len(whole) == 6
    assert all(k in whole[-1] for k in ("tokens", "ssm_resets",
                                        "ssd_chunks"))

    ref_step = jax.jit(lambda p, o, b, n: reference.train_step(
        p, o, b, n, cfg=ref_cfg(cfg), lr=tcfg.lr,
        total_steps=tcfg.num_steps + 100, wdecay=tcfg.wdecay,
        eps=tcfg.epsilon, clip=tcfg.clip))
    params = jax.tree.map(jnp.asarray, variables["params"])
    opt = {"mu": jax.tree.map(jnp.zeros_like, params),
           "nu": jax.tree.map(jnp.zeros_like, params)}
    for n in range(3):
        batch = {k: jnp.asarray(batches[n][k])
                 for k in ("tokens", "segment_ids")}
        params, opt, ref_loss, _ = ref_step(params, opt, batch, n)
        assert abs(whole[n]["loss"] - float(ref_loss)) \
            < 1e-5 * float(ref_loss)
    change = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                          record.params[-1], variables["params"])
    ref_change = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                              params, variables["params"])
    assert max(jax.tree.leaves(jax.tree.map(rel, change, ref_change))) \
        < 2e-3

    shutil.copytree(tmp_path / "whole" / "ssm" / "3",
                    tmp_path / "cut" / "ssm" / "3")
    state = run("cut", resume=True)
    assert int(state.step) == 6
    cut = _loss_rows(tmp_path / "cut" / "logs")
    assert [r["loss"] for r in cut] == [r["loss"] for r in whole[3:]]


def test_train_cli_names_the_family(capsys):
    from raft_tpu.train import lm_config_from_json, main
    with pytest.raises(SystemExit):
        main(["--help"])
    text = capsys.readouterr().out
    assert "granitemoehybrid" in text and "--lm_config" in text \
        and "granite_4_0_h_micro.json" in text
    cfg = lm_config_from_json(
        "benchmark/configs/granite_4_0_h_micro.json", "granitemoehybrid")
    assert (cfg.hidden_size, cfg.vocab, cfg.num_hidden_layers,
            cfg.mamba_n_heads) == (2048, 12544, 10, 64)
    assert type(lm_config_from_json(None, "granitemoehybrid")) \
        is GraniteHybridConfig
    with pytest.raises(SystemExit):
        main(["--model_family", "sparse", "--lm_config", "x.json"])
