"""Input padding to stride-8-compatible shapes.

Reference semantics: ``core/utils/utils.py:7-24`` — replicate-pad to the next
multiple of 8; 'sintel' mode centers vertically, every other mode (kitti)
puts all vertical padding at the bottom (torch ``F.pad`` order is
left/right/top/bottom and the reference passes ``[l, r, 0, pad_ht]``). On
TPU static shapes matter, so the padder is a host-side helper: pick a
resolution bucket once, pad numpy arrays before ``device_put``, and crop
after.
"""

from __future__ import annotations

import numpy as np


class InputPadder:
    """Pads NHWC (or HWC) arrays so H and W are divisible by ``factor``."""

    def __init__(self, dims, mode: str = "sintel", factor: int = 8):
        self.ht, self.wd = dims[-3], dims[-2]
        pad_ht = (((self.ht // factor) + 1) * factor - self.ht) % factor
        pad_wd = (((self.wd // factor) + 1) * factor - self.wd) % factor
        if mode == "sintel":
            self._pad = [pad_wd // 2, pad_wd - pad_wd // 2,
                         pad_ht // 2, pad_ht - pad_ht // 2]
        else:  # kitti: all vertical padding at the bottom
            self._pad = [pad_wd // 2, pad_wd - pad_wd // 2, 0, pad_ht]

    @property
    def padded_shape(self):
        return (self.ht + self._pad[2] + self._pad[3],
                self.wd + self._pad[0] + self._pad[1])

    def pad(self, *inputs):
        l, r, t, b = self._pad
        out = []
        for x in inputs:
            widths = [(0, 0)] * x.ndim
            widths[-3] = (t, b)
            widths[-2] = (l, r)
            out.append(np.pad(x, widths, mode="edge"))
        return out if len(out) > 1 else out[0]

    def pad_into(self, dst, x):
        """Write ``pad(x)`` into ``dst`` (a batch slot of a staging
        buffer) without building it first: one copy of ``x`` into the
        interior, then the edge columns and rows replicated in place.
        Bit-identical to :meth:`pad`; every element of ``dst`` is
        rewritten, so what it held before does not matter."""
        l, r, t, b = self._pad
        h, w = x.shape[-3], x.shape[-2]
        want = x.shape[:-3] + (t + h + b, l + w + r, x.shape[-1])
        if dst.shape != want or dst.dtype != x.dtype:
            raise ValueError(
                f"pad_into: a {x.dtype} frame of shape {x.shape} pads to "
                f"{want}, got a {dst.dtype} destination of {dst.shape}")
        dst[..., t:t + h, l:l + w, :] = x
        rows = dst[..., t:t + h, :, :]
        if l:
            rows[..., :l, :] = rows[..., l:l + 1, :]
        if r:
            rows[..., l + w:, :] = rows[..., l + w - 1:l + w, :]
        if t:
            dst[..., :t, :, :] = dst[..., t:t + 1, :, :]
        if b:
            dst[..., t + h:, :, :] = dst[..., t + h - 1:t + h, :, :]
        return dst

    def unpad(self, x):
        l, r, t, b = self._pad
        ht, wd = x.shape[-3], x.shape[-2]
        return x[..., t:ht - b if b else ht, l:wd - r if r else wd, :]
