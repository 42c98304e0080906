"""Readers of the sliding-window family's cell. Each takes the run's
context and returns a number, or ``None`` where it finds nothing to read
(a program without the family or its counters, a run without the
compiled step's scopes).

The counts come from the program's own counters on its ``train.step``
spans (``window_pairs``, ``causal_pairs``, ``routed_here``), read here
because the run's facts are put together by the state-space kind's
``run_steps``, which does not know them.
"""

from __future__ import annotations

from benchmark import afmoe_flops, peaks

ROOT_SPAN = "train.step"
COUNTERS = ("routed_here", "window_pairs", "causal_pairs")


def _model(ctx):
    """The configuration's ``model`` object where it is this family's,
    else ``None``."""
    model = ctx["cell"]["config"].get("model") or {}
    return model if "sliding_window" in model else None


def _counted(ctx, steps):
    """The program's counters summed over the last ``steps`` complete
    steps, with the tokens those steps trained on; ``None`` where the
    program has no such spans or counters."""
    try:
        from raft_tpu.utils import profiling
    except ImportError:
        return None
    host_timer = getattr(profiling, "host_timer", None)
    steps = int(steps or 0)
    if host_timer is None or steps < 1:
        return None
    spans = [s for s in host_timer().spans()
             if s.name == ROOT_SPAN and s.args.get("complete")][-steps:]
    if len(spans) < steps or any(k not in s.args for s in spans
                                 for k in COUNTERS):
        return None
    traffic = ctx["cell"]["traffic"]
    out = {k: sum(int(s.args[k]) for s in spans) for k in COUNTERS}
    out["tokens"] = steps * traffic["sequences"] * traffic["seq_len"]
    return out


def _traced(ctx):
    return _counted(ctx, (ctx["run"].get("ssm_traced_counts")
                          or {}).get("steps"))


def train_step_mfu(ctx):
    """Required forward and backward operations of the steps completed
    (experts by the rows routed here, sliding layers by the pairs the
    window allows, the full layer by the documents' causal area,
    recomputation not counted) over the window and the chips' bf16
    peak: the share of the whole step."""
    run, cfg = ctx["run"], _model(ctx)
    counts = _counted(ctx, run.get("steps")) if cfg else None
    if not counts:
        return None
    required = afmoe_flops.train_step_flops(
        cfg, counts["tokens"], counts["routed_here"],
        counts["window_pairs"], counts["causal_pairs"])["total"]
    peak = peaks.peaks_of(ctx["device"]["kind"])["bf16_flops_per_s"]
    return (100.0 * required / run["window_s"]
            / (peak * ctx["device"]["count"]))


def _scope_seconds(ctx, scopes):
    """Device self time of the traced window's events whose instruction
    the compiled step puts under one of ``scopes``; ``None`` where the
    run has no map or the trace no such event."""
    by_scope = ctx["run"].get("stage_ops") or {}
    names = {name for scope in scopes for name in by_scope.get(scope, ())}
    seconds = sum(s for name, s in ctx["trace"]["ops"].items()
                  if name in names)
    return seconds if seconds > 0 else None


def scope_time_pct(ctx, scopes):
    """Share of device-busy time in instructions under the named
    scopes, forward, recomputed and backward."""
    seconds = _scope_seconds(ctx, scopes)
    if seconds is None:
        return None
    return 100.0 * seconds / ctx["trace"]["busy_s"]


def _roofline(ctx, need, seconds):
    peak = peaks.peaks_of(ctx["device"]["kind"])
    least = max(need["flops"] / peak["bf16_flops_per_s"],
                need["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def attn_window_roofline(ctx, scopes):
    """The least time the chip could take for the traced steps' windowed
    attention (the larger of operations over peak and bytes over
    bandwidth, over the pairs the program counted as allowed) over the
    device time under the windowed kernel's scope."""
    cfg, seconds = _model(ctx), _scope_seconds(ctx, scopes)
    counts = _traced(ctx) if cfg and seconds else None
    if not counts:
        return None
    return _roofline(ctx, afmoe_flops.attn_window_step(
        cfg, counts["tokens"], counts["window_pairs"]), seconds)


def gmm_roofline(ctx, kernels):
    """``readers/lm.py::gmm_roofline`` from this kind's counts: the
    least time for the traced steps' grouped products over the device
    time of the events named by ``kernels``."""
    cfg = _model(ctx)
    seconds = sum(s for name, s in ctx["trace"]["ops"].items()
                  if any(k in name for k in kernels))
    counts = _traced(ctx) if cfg and seconds > 0 else None
    if not counts:
        return None
    return _roofline(ctx, afmoe_flops.expert_gmm_step(
        cfg, 0, counts["routed_here"]), seconds)
