"""Readers of the state-space hybrid's cell. Each takes the run's
context and returns a number, or ``None`` where it finds nothing to read
(a program without the family, a run without the counts or without the
compiled step's stages)."""

from __future__ import annotations

from benchmark import peaks, ssm_flops


def _model(ctx):
    """The configuration's ``model`` object where it is this family's,
    else ``None``."""
    model = ctx["cell"]["config"].get("model") or {}
    return model if "mamba_n_heads" in model else None


def train_step_mfu(ctx):
    """Required forward and backward operations of the steps completed
    (projections, convolution, the scan's four products a chunk,
    attention by the documents' causal area, MLPs, head; recomputation
    not counted) over the window and the chips' bf16 peak: the share of
    the whole step."""
    run, cfg = ctx["run"], _model(ctx)
    counts = run.get("ssm_counts")
    if not cfg or not counts or not counts.get("chunks") \
            or not run.get("steps"):
        return None
    required = ssm_flops.train_step_flops(
        cfg, counts["tokens"], counts["chunks"],
        counts["causal_pairs"])["total"]
    peak = peaks.peaks_of(ctx["device"]["kind"])["bf16_flops_per_s"]
    return (100.0 * required / run["window_s"]
            / (peak * ctx["device"]["count"]))


def _stage_seconds(ctx, stages):
    """Device self time of the traced window's events whose instruction
    the compiled step puts under one of ``stages``; ``None`` where the
    run has no map or the trace no such event."""
    by_stage = ctx["run"].get("stage_ops") or {}
    names = {name for stage in stages for name in by_stage.get(stage, ())}
    seconds = sum(s for name, s in ctx["trace"]["ops"].items()
                  if name in names)
    return seconds if seconds > 0 else None


def stage_time_pct(ctx, stages):
    """Share of device-busy time in instructions under the named
    stages' scopes, forward, recomputed and backward."""
    seconds = _stage_seconds(ctx, stages)
    if seconds is None:
        return None
    return 100.0 * seconds / ctx["trace"]["busy_s"]


def ssd_roofline(ctx, stages):
    """The least time the chip could take for the traced steps' scans
    (the larger of operations over peak and bytes over bandwidth, from
    shapes and the chunks the program counted) over the device time
    under the scan's scope: the same required work whatever implements
    it."""
    cfg, counts = _model(ctx), ctx["run"].get("ssm_traced_counts")
    seconds = _stage_seconds(ctx, stages)
    if not cfg or not counts or not counts.get("chunks") or seconds is None:
        return None
    need = ssm_flops.ssd_step(cfg, counts["tokens"], counts["chunks"])
    peak = peaks.peaks_of(ctx["device"]["kind"])
    least = max(need["flops"] / peak["bf16_flops_per_s"],
                need["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
