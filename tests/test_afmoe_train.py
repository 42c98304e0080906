"""The sliding-window family through ``raft_tpu.train.train()`` and the
CLI: three steps of the real loop against the reference's three steps,
the family's counters on its spans and in the scalar stream, a save and
a resume that continues the loss, and ``train.py --help`` naming the
family."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.config import AfmoeConfig, TrainConfig

# the small size, the reference's keys and the gap by leaf
from test_afmoe import S, SMALL, ref_cfg, rel


def _loss_rows(log_dir):
    with open(log_dir / "scalars.jsonl") as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if "loss" in r]


def test_train_loop_follows_the_reference_counts_and_resumes(tmp_path):
    """``train()`` with ``model_family="afmoe"`` through the real loop
    (8 sequences over the tests' 8-device data mesh), its state begun
    from the benchmark's seeded weights: the first three losses are the
    reference's three steps' and so are the parameters after them;
    ``tokens``, the routing counters, ``window_pairs`` and
    ``causal_pairs`` ride every ``train.step`` span and the scalar
    stream, the pairs equal to the driver's own count from the batch
    trained on; a second run resumed from the first's step-3 checkpoint
    continues its losses exactly."""
    import shutil

    from benchmark.drivers.swa_train_steps import (allowed_pairs,
                                                   seeded_variables)
    from benchmark.drivers.train_steps import Observed, observed
    from benchmark.reference import afmoe as reference
    from raft_tpu.data.tokens import TokenLoader
    from raft_tpu.train import train
    from raft_tpu.utils.logger import TrainLogger
    from raft_tpu.utils.profiling import host_timer

    cfg = SMALL
    tcfg = TrainConfig(name="swa", model_family="afmoe", lr=3e-4,
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
        assert span.args["dropped"] == 0 < span.args["routed_here"]
        assert span.args["routed_here"] >= span.args["expert_load_max"] > 0
        mine = allowed_pairs(batch, cfg.sliding_window)
        assert span.args["window_pairs"] == mine["window_pairs"]
        assert span.args["causal_pairs"] == mine["causal_pairs"]
        assert mine["window_pairs"] < mine["causal_pairs"]
    whole = _loss_rows(tmp_path / "whole" / "logs")
    assert len(whole) == 6
    assert all(k in whole[-1] for k in (
        "tokens", "routed_here", "expert_load_max", "dropped",
        "window_pairs", "causal_pairs"))

    ref_step = jax.jit(lambda p, o, b, n: reference.train_step(
        p, o, b, n, cfg=ref_cfg(cfg), lr=tcfg.lr,
        total_steps=tcfg.num_steps + 100, wdecay=tcfg.wdecay,
        eps=tcfg.epsilon, clip=tcfg.clip))
    params = jax.tree.map(jnp.asarray, variables["params"])
    opt = {"mu": jax.tree.map(jnp.zeros_like, params),
           "nu": jax.tree.map(jnp.zeros_like, params)}
    for n in range(3):
        batch = {k: jnp.asarray(batches[n][k])
                 for k in ("tokens", "segment_ids", "positions")}
        params, opt, ref_loss, _ = ref_step(params, opt, batch, n)
        assert abs(whole[n]["loss"] - float(ref_loss)) \
            < 1e-4 * float(ref_loss)
    change = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                          record.params[-1], variables["params"])
    ref_change = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                              params, variables["params"])
    gaps = jax.tree.map(rel, change, ref_change)
    for layer in ("layers_1", "layers_2"):
        gaps[layer]["mlp"].pop("expert_bias")       # it does not move
    # Adam divides by sqrt(v): where a gradient element is nought to
    # rounding the quotient is not, so a few elements of a leaf move
    # differently (the largest leaf gap here is 0.9 %)
    assert max(jax.tree.leaves(gaps)) < 2e-2

    shutil.copytree(tmp_path / "whole" / "swa" / "3",
                    tmp_path / "cut" / "swa" / "3")
    state = run("cut", resume=True)
    assert int(state.step) == 6
    cut = _loss_rows(tmp_path / "cut" / "logs")
    assert [r["loss"] for r in cut] == [r["loss"] for r in whole[3:]]


def test_train_cli_names_the_family(capsys):
    from raft_tpu.train import lm_config_from_json, main
    with pytest.raises(SystemExit):
        main(["--help"])
    text = capsys.readouterr().out
    assert "afmoe" in text and "--lm_config" in text \
        and "trinity_mini.json" in text
    cfg = lm_config_from_json("benchmark/configs/trinity_mini.json",
                              "afmoe")
    assert (cfg.hidden_size, cfg.vocab, cfg.num_hidden_layers, cfg.held,
            cfg.sliding_window) == (2048, 25024, 5, 16, 2048)
    assert type(lm_config_from_json(None, "afmoe")) is AfmoeConfig
    with pytest.raises(SystemExit):
        main(["--model_family", "sparse", "--lm_config", "x.json"])
    assert "afmoe" in capsys.readouterr().err
