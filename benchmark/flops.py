"""Operations and bytes that canonical RAFT requires, from shapes alone.

Every count is of the mathematics, whatever implements it: a fused
kernel, a chain of XLA convolutions and a recomputing backward all
"require" the same number. Only contractions are counted (convolutions,
the correlation, the windowed lookup's interpolation); normalisations,
activations, the GRU's gate arithmetic and the convex upsampling's
softmax are left out, which understates the work by under 1 % and so can
only lower a share of the peak, never push one over 100 %. One
multiply-add is two operations. The derivation is in ``PERF.md``.
"""

from __future__ import annotations

LEVELS = 4


def conv_flops(h_out: int, w_out: int, kh: int, kw: int, c_in: int,
               c_out: int) -> int:
    """A convolution producing ``h_out x w_out x c_out`` from ``c_in``
    channels through a ``kh x kw`` window."""
    return 2 * h_out * w_out * kh * kw * c_in * c_out


def _half(n: int) -> int:
    """Output length of a stride-2 convolution with 'same' padding."""
    return (n + 1) // 2


def encoder_flops(h: int, w: int, small: bool, out_dim: int) -> int:
    """``BasicEncoder`` / ``SmallEncoder`` over one ``h x w`` image."""
    h2, w2 = _half(h), _half(w)
    h4, w4 = _half(h2), _half(w2)
    h8, w8 = _half(h4), _half(w4)
    if small:
        total = conv_flops(h2, w2, 7, 7, 3, 32)
        c_in, (hi, wi) = 32, (h2, w2)
        for planes, (hh, ww) in ((32, (h2, w2)), (64, (h4, w4)),
                                 (96, (h8, w8))):
            q = planes // 4
            # block 0: 1x1 at the input's resolution, 3x3 (strided from
            # the second stage on), 1x1 back up, 1x1 strided shortcut
            total += conv_flops(hi, wi, 1, 1, c_in, q)
            total += conv_flops(hh, ww, 3, 3, q, q)
            total += conv_flops(hh, ww, 1, 1, q, planes)
            if (hh, ww) != (hi, wi):
                total += conv_flops(hh, ww, 1, 1, c_in, planes)
            # block 1
            total += conv_flops(hh, ww, 1, 1, planes, q)
            total += conv_flops(hh, ww, 3, 3, q, q)
            total += conv_flops(hh, ww, 1, 1, q, planes)
            c_in, (hi, wi) = planes, (hh, ww)
        return total + conv_flops(h8, w8, 1, 1, 96, out_dim)
    total = conv_flops(h2, w2, 7, 7, 3, 64)
    c_in = 64
    for planes, (hh, ww), strided in ((64, (h2, w2), False),
                                      (96, (h4, w4), True),
                                      (128, (h8, w8), True)):
        total += conv_flops(hh, ww, 3, 3, c_in, planes)      # block 0
        total += conv_flops(hh, ww, 3, 3, planes, planes)
        if strided:
            total += conv_flops(hh, ww, 1, 1, c_in, planes)  # downsample
        total += 2 * conv_flops(hh, ww, 3, 3, planes, planes)  # block 1
        c_in = planes
    return total + conv_flops(h8, w8, 1, 1, 128, out_dim)


def pyramid_cells(h8: int, w8: int, levels: int = LEVELS) -> int:
    """Target positions over all levels of a 2x2-pooled pyramid."""
    total = 0
    for _ in range(levels):
        total += h8 * w8
        h8, w8 = h8 // 2, w8 // 2
    return total


def allpairs_flops(h8: int, w8: int, c: int, levels: int = LEVELS) -> int:
    """The all-pairs volume built once, and its pooling (one add for
    each cell pooled)."""
    n = h8 * w8
    return 2 * n * n * c + n * (pyramid_cells(h8, w8, levels) - n) * 4


def lookup_from_volume_flops(h8: int, w8: int, radius: int,
                             levels: int = LEVELS) -> int:
    """One iteration's windows read from a built volume: a bilinear
    blend (4 multiplies, 3 adds) at each of the ``(2r+1)^2`` points."""
    return h8 * w8 * levels * (2 * radius + 1) ** 2 * 7


def windowed_lookup_flops(h8: int, w8: int, c: int, radius: int,
                          levels: int = LEVELS) -> int:
    """One iteration's windows computed on demand: all points of a
    window share one fractional offset, so ``(2r+2)^2`` dot products of
    length ``c`` on the integer grid, then the same bilinear blend."""
    return h8 * w8 * levels * ((2 * radius + 2) ** 2 * 2 * c
                               + (2 * radius + 1) ** 2 * 7)


def correlation_flops(h8: int, w8: int, c: int, radius: int, iters: int,
                      levels: int = LEVELS) -> dict:
    """Both ways of getting ``iters`` lookups, and the cheaper."""
    built = (allpairs_flops(h8, w8, c, levels)
             + iters * lookup_from_volume_flops(h8, w8, radius, levels))
    demand = iters * windowed_lookup_flops(h8, w8, c, radius, levels)
    return {"allpairs": built, "windowed": demand,
            "cheaper": "allpairs" if built <= demand else "windowed",
            "flops": min(built, demand)}


def update_flops(h8: int, w8: int, small: bool, radius: int,
                 levels: int = LEVELS) -> dict:
    """One refinement iteration's convolutions, and the mask head that
    only an iteration whose flow is upsampled needs (RAFT-large)."""
    cc = levels * (2 * radius + 1) ** 2

    def f(kh, kw, c_in, c_out):
        return conv_flops(h8, w8, kh, kw, c_in, c_out)

    if small:
        motion = (f(1, 1, cc, 96) + f(7, 7, 2, 64) + f(3, 3, 64, 32)
                  + f(3, 3, 128, 80))
        gru = 3 * f(3, 3, 96 + 64 + 82, 96)
        head = f(3, 3, 96, 128) + f(3, 3, 128, 2)
        return {"iteration": motion + gru + head, "mask": 0}
    motion = (f(1, 1, cc, 256) + f(3, 3, 256, 192) + f(7, 7, 2, 128)
              + f(3, 3, 128, 64) + f(3, 3, 256, 126))
    gru = 3 * f(1, 5, 384, 128) + 3 * f(5, 1, 384, 128)
    head = f(3, 3, 128, 256) + f(3, 3, 256, 2)
    mask = f(3, 3, 128, 256) + f(1, 1, 256, 576)
    return {"iteration": motion + gru + head, "mask": mask}


def upsample_flops(h8: int, w8: int, small: bool) -> int:
    """Convex combination of 9 neighbours (large) or a bilinear blend
    (small), for 2 components of each full-resolution pixel."""
    per_pixel = 2 * 7 if small else 2 * 2 * 9
    return 64 * h8 * w8 * per_pixel


def forward_flops(h: int, w: int, small: bool, iters: int) -> dict:
    """One pair's test-mode forward at the padded size ``h x w``."""
    h8, w8 = h // 8, w // 8
    fdim, hidden, context, radius = ((128, 96, 64, 3) if small
                                     else (256, 128, 128, 4))
    parts = {
        "fnet": 2 * encoder_flops(h, w, small, fdim),
        "cnet": encoder_flops(h, w, small, hidden + context),
        "correlation": correlation_flops(h8, w8, fdim, radius,
                                         iters)["flops"],
        "update": iters * update_flops(h8, w8, small, radius)["iteration"],
        "mask": update_flops(h8, w8, small, radius)["mask"],
        "upsample": upsample_flops(h8, w8, small),
    }
    parts["total"] = sum(parts.values())
    return parts


def corr_lookup_call(batch: int, h8: int, w8: int, c: int, radius: int,
                     iters: int, feature_bytes: int = 2,
                     out_bytes: int = 2, levels: int = LEVELS) -> dict:
    """What one call of a windowed-lookup kernel over ``batch`` pairs
    requires. Operations: one iteration's share of the cheaper way of
    getting ``iters`` lookups. Bytes: the query features and the pooled
    target pyramid read once, the coordinates read, the windows written."""
    per_iter = correlation_flops(h8, w8, c, radius, iters, levels)["flops"] \
        / iters
    n = h8 * w8
    bytes_ = (n * c * feature_bytes
              + pyramid_cells(h8, w8, levels) * c * feature_bytes
              + n * 2 * 4
              + n * levels * (2 * radius + 1) ** 2 * out_bytes)
    return {"flops": batch * per_iter, "bytes": batch * bytes_}


def train_step_flops(h: int, w: int, small: bool, iters: int) -> dict:
    """One sample's training step at ``h x w``: the training-mode
    forward (every iteration's flow upsampled, so the mask head and the
    upsampling run ``iters`` times) and its backward, counted as twice
    the forward (one pass for the inputs' gradients, one for the
    weights'). Recomputation in a backward pass is not counted."""
    h8, w8 = h // 8, w // 8
    fdim, hidden, context, radius = ((128, 96, 64, 3) if small
                                     else (256, 128, 128, 4))
    update = update_flops(h8, w8, small, radius)
    forward = (2 * encoder_flops(h, w, small, fdim)
               + encoder_flops(h, w, small, hidden + context)
               + correlation_flops(h8, w8, fdim, radius, iters)["flops"]
               + iters * (update["iteration"] + update["mask"]
                          + upsample_flops(h8, w8, small)))
    return {"forward": forward, "total": 3 * forward}
