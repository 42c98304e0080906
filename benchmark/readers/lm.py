"""Readers of the token family's cells. Each takes the run's context
and returns a number, or ``None`` where it finds nothing to read (a
program without the family, a run without the counts)."""

from __future__ import annotations

from benchmark import lm_flops, peaks


def _model(ctx):
    """The configuration's ``model`` object where it is the token
    family's, else ``None``."""
    model = ctx["cell"]["config"].get("model") or {}
    return model if "experts_held" in model else None


def train_step_mfu(ctx):
    """Required forward and backward operations of the steps completed
    (experts by the rows routed here, attention by the documents' causal
    area, recomputation not counted) over the window and the chips'
    bf16 peak: the share of the whole step."""
    run, cfg = ctx["run"], _model(ctx)
    counts = run.get("lm_counts")
    if not cfg or not counts or not run.get("steps"):
        return None
    required = lm_flops.train_step_flops(
        cfg, counts["tokens"], counts["routed_here"],
        counts["causal_pairs"])["total"]
    peak = peaks.peaks_of(ctx["device"]["kind"])["bf16_flops_per_s"]
    return (100.0 * required / run["window_s"]
            / (peak * ctx["device"]["count"]))


def gmm_roofline(ctx, kernels):
    """The least time the chip could take for the traced steps' grouped
    products (the larger of operations over peak and bytes over
    bandwidth, from shapes and the routed counts) over the device time
    of the events named by ``kernels``."""
    run, cfg = ctx["run"], _model(ctx)
    counts = run.get("lm_traced_counts")
    if not cfg or not counts:
        return None
    seconds = sum(s for name, s in ctx["trace"]["ops"].items()
                  if any(k in name for k in kernels))
    if seconds <= 0:
        return None
    need = lm_flops.expert_gmm_step(cfg, counts["buffer_rows"],
                                    counts["routed_here"])
    peak = peaks.peaks_of(ctx["device"]["kind"])
    least = max(need["flops"] / peak["bf16_flops_per_s"],
                need["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def expert_load(ctx, root):
    """Mean over the window's steps of the fullest held expert's rows in
    one layer over the mean rows of a held expert in a layer, from the
    counters on the program's ``root`` spans."""
    cfg = _model(ctx)
    try:
        from raft_tpu.utils import profiling
    except ImportError:
        return None
    host_timer = getattr(profiling, "host_timer", None)
    steps = int(ctx["run"].get("steps") or 0)
    if host_timer is None or not cfg or steps < 1:
        return None
    spans = [s for s in host_timer().spans()
             if s.name == root and s.args.get("complete")][-steps:]
    slots = cfg["experts_held"] * lm_flops.expert_layers(cfg)
    ratios = [s.args["expert_load_max"] * slots / s.args["routed_here"]
              for s in spans
              if s.args.get("routed_here") and "expert_load_max" in s.args]
    if len(ratios) < steps:
        return None
    return sum(ratios) / len(ratios)
