"""Readers that turn the window's counts into shares of the chip's peak."""

from __future__ import annotations

from benchmark import flops, peaks


def forward_mfu(ctx):
    """Required operations of the pairs completed, over the window and
    the chip's bf16 peak."""
    traffic, config = ctx["cell"]["traffic"], ctx["cell"]["config"]
    run = ctx["run"]
    if not run["pairs"]:
        return None
    h = -(-traffic["height"] // 8) * 8
    w = -(-traffic["width"] // 8) * 8
    per_pair = flops.forward_flops(h, w, config["shapes"]["small"],
                                   traffic["iters"])["total"]
    peak = peaks.peaks_of(ctx["device"]["kind"])["bf16_flops_per_s"]
    return (100.0 * run["pairs"] * per_pair / run["window_s"]
            / (peak * ctx["device"]["count"]))


def train_step_mfu(ctx):
    """Required forward and backward operations of the samples whose
    step completed, over the window and the chip's bf16 peak."""
    traffic, config = ctx["cell"]["traffic"], ctx["cell"]["config"]
    run = ctx["run"]
    if not run["samples"]:
        return None
    per_sample = flops.train_step_flops(
        traffic["height"], traffic["width"], config["shapes"]["small"],
        traffic["iters"])["total"]
    peak = peaks.peaks_of(ctx["device"]["kind"])["bf16_flops_per_s"]
    return (100.0 * run["samples"] * per_sample / run["window_s"]
            / (peak * ctx["device"]["count"]))


def loader_wait_ms_per_step(ctx):
    run = ctx["run"]
    if not run["steps"]:
        return None
    return 1e3 * run["loader_wait_s"] / run["steps"]
