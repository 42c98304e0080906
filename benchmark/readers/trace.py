"""Readers of the device trace. Each takes the run's context
(``trace``: the reduced trace, ``run``: the window's facts, ``cell``,
``device``) and returns a number, or ``None`` where it finds nothing to
read."""

from __future__ import annotations

from benchmark import flops, peaks


def device_idle_pct(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def host_ms_per_batch(ctx):
    """Wall time of the window that no device operation covers, for
    each batch."""
    t = ctx["trace"]
    return 1e3 * (t["window_s"] - t["busy_s"]) / ctx["run"]["batches"]


def device_ms_per_batch(ctx):
    return 1e3 * ctx["trace"]["busy_s"] / ctx["run"]["batches"]


def _kernel_seconds(ctx, needle):
    return sum(s for name, s in ctx["trace"]["ops"].items()
               if needle in name)


def kernel_time_pct(ctx, kernels):
    """Share of device-busy time in events of the named kernels."""
    seconds = sum(_kernel_seconds(ctx, k) for k in kernels)
    if seconds <= 0:
        return None
    return 100.0 * seconds / ctx["trace"]["busy_s"]


def corr_lookup_roofline(ctx, kernel):
    """The least time the chip could take for the window's lookups (the
    larger of operations over peak and bytes over bandwidth, both from
    shapes) over the kernel's device time."""
    seconds = _kernel_seconds(ctx, kernel)
    if seconds <= 0:
        return None
    traffic, config = ctx["cell"]["traffic"], ctx["cell"]["config"]
    shape = config["shapes"]
    h8 = -(-traffic["height"] // 8)
    w8 = -(-traffic["width"] // 8)
    call = flops.corr_lookup_call(
        traffic["batch_size"], h8, w8, shape["feature_dim"],
        shape["corr_radius"], traffic["iters"])
    peak = peaks.peaks_of(ctx["device"]["kind"])
    least = max(call["flops"] / peak["bf16_flops_per_s"],
                call["bytes"] / peak["hbm_bytes_per_s"])
    calls = ctx["run"]["batches"] * traffic["iters"]
    return 100.0 * calls * least / seconds
