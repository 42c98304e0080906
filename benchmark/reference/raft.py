"""Plain reference of canonical RAFT (Teed & Deng, ECCV 2020), both sizes.

The forward pass of princeton-vl/RAFT ``core/raft.py`` (``RAFT`` and
``--small``) written out in straightforward ``jax.numpy`` float32: no
kernels, no scan, no batching tricks, every contraction at
``Precision.HIGHEST``. It imports nothing of the program under test. It
reads the parameters by the names the published checkpoints use
(``fnet.layer1.0.conv1`` is ``params["fnet"]["layer1_0"]["conv1"]``), in
NHWC / HWIO layout.

``operand`` is applied to both operands of every convolution and of the
all-pairs correlation: the identity for the reference, a rounding to a
lower precision for the control (see ``fp8_operand``).

Departures from the published code, none of which changes the result:
the windowed lookup is written with dense bilinear weights
(``relu(1 - |t - x|)``, zero outside the map, as ``grid_sample`` with
``align_corners=True, padding_mode="zeros"`` gives) and two contractions
instead of four gathers, and only the last iteration computes the
upsampling mask (``test_mode`` returns the last flow only).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def identity(x):
    return x


def fp8_operand(x):
    """Round ``x`` to float8 (e4m3) with one scale per tensor, back in
    float32: the precision below bfloat16 that a later PR could be
    tempted by. Accumulation stays float32."""
    x = x.astype(jnp.float32)
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def conv(x, p, operand, stride=1, pad=None):
    kh, kw = p["kernel"].shape[:2]
    if pad is None:
        pad = (kh // 2, kw // 2)
    y = jax.lax.conv_general_dilated(
        operand(x), operand(p["kernel"]), (stride, stride),
        ((pad[0], pad[0]), (pad[1], pad[1])),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return y + p["bias"]


def make_norm(kind, params, stats):
    """``norm(name, x)`` for one encoder: instance (per sample and
    channel over H, W, no affine), batch in evaluation mode (running
    statistics, affine) or none."""
    if kind == "batch_train":
        return make_batch_norm(params)

    def norm(name, x):
        if kind == "none":
            return x
        if kind == "instance":
            mean = jnp.mean(x, axis=(1, 2), keepdims=True)
            var = jnp.mean((x - mean) ** 2, axis=(1, 2), keepdims=True)
            return (x - mean) / jnp.sqrt(var + 1e-5)
        p, s = params[name]["n"], stats[name]["n"]
        return ((x - s["mean"]) / jnp.sqrt(s["var"] + 1e-5) * p["scale"]
                + p["bias"])
    return norm


def residual_block(x, p, stats, kind, stride, operand):
    norm = make_norm(kind, p, stats)
    y = jax.nn.relu(norm("norm1", conv(x, p["conv1"], operand, stride)))
    y = jax.nn.relu(norm("norm2", conv(y, p["conv2"], operand)))
    if stride != 1:
        x = norm("norm3", conv(x, p["downsample"], operand, stride))
    return jax.nn.relu(x + y)


def bottleneck_block(x, p, stats, kind, stride, operand):
    norm = make_norm(kind, p, stats)
    y = jax.nn.relu(norm("norm1", conv(x, p["conv1"], operand)))
    y = jax.nn.relu(norm("norm2", conv(y, p["conv2"], operand, stride)))
    y = jax.nn.relu(norm("norm3", conv(y, p["conv3"], operand)))
    if stride != 1:
        x = norm("norm4", conv(x, p["downsample"], operand, stride))
    return jax.nn.relu(x + y)


def encoder(x, p, stats, kind, small, operand):
    """``BasicEncoder`` / ``SmallEncoder`` of ``core/extractor.py``:
    7x7 stride-2 stem, three stages at strides 1, 2, 2, 1x1 projection."""
    block = bottleneck_block if small else residual_block
    norm = make_norm(kind, p, stats)
    x = jax.nn.relu(norm("norm1", conv(x, p["conv1"], operand, 2)))
    for stage, stride in (("layer1", 1), ("layer2", 2), ("layer3", 2)):
        for i, s in ((0, stride), (1, 1)):
            name = f"{stage}_{i}"
            x = block(x, p[name], (stats or {}).get(name, {}), kind, s,
                      operand)
    return conv(x, p["conv2"], operand)


def correlation_pyramid(fmap1, fmap2, levels, operand):
    """``CorrBlock.__init__``: the all-pairs volume over ``sqrt(C)``,
    average-pooled 2x2 over the target's axes. Level ``l`` is
    ``(B, H*W, H >> l, W >> l)``."""
    b, h, w, c = fmap1.shape
    f1 = operand(fmap1.reshape(b, h * w, c))
    f2 = operand(fmap2.reshape(b, h * w, c))
    corr = jnp.einsum("bnc,bmc->bnm", f1, f2, precision=HIGHEST)
    corr = (corr / jnp.sqrt(jnp.float32(c))).reshape(b, h * w, h, w)
    pyramid = [corr]
    for _ in range(levels - 1):
        hh, ww = corr.shape[2] // 2, corr.shape[3] // 2
        corr = corr[:, :, :2 * hh, :2 * ww].reshape(
            b, h * w, hh, 2, ww, 2).mean(axis=(3, 5))
        pyramid.append(corr)
    return pyramid


def bilinear_weights(t, n):
    """Weights that a bilinear sample at ``t`` puts on source indices
    ``0..n-1``; samples beyond the edge blend toward zero."""
    return jnp.maximum(0.0, 1.0 - jnp.abs(
        t[..., None] - jnp.arange(n, dtype=jnp.float32)))


def correlation_lookup(pyramid, coords, radius):
    """``CorrBlock.__call__``: a ``(2r+1)^2`` window of every level
    around ``coords / 2^level``; window index ``(i, j)`` samples
    ``(x + i - r, y + j - r)``, flattened row-major, levels concatenated."""
    b, h, w, _ = coords.shape
    off = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    flat = coords.reshape(b, h * w, 2)
    out = []
    for level, corr in enumerate(pyramid):
        cx = flat[..., 0] / 2 ** level
        cy = flat[..., 1] / 2 ** level
        wx = bilinear_weights(cx[..., None] + off, corr.shape[3])
        wy = bilinear_weights(cy[..., None] + off, corr.shape[2])
        tmp = jnp.einsum("bqyx,bqix->bqiy", corr, wx, precision=HIGHEST)
        win = jnp.einsum("bqiy,bqjy->bqij", tmp, wy, precision=HIGHEST)
        out.append(win.reshape(b, h, w, -1))
    return jnp.concatenate(out, axis=-1)


def motion_encoder(flow, corr, p, small, operand):
    cor = jax.nn.relu(conv(corr, p["convc1"], operand))
    if not small:
        cor = jax.nn.relu(conv(cor, p["convc2"], operand))
    flo = jax.nn.relu(conv(flow, p["convf1"], operand))
    flo = jax.nn.relu(conv(flo, p["convf2"], operand))
    out = jax.nn.relu(conv(jnp.concatenate([cor, flo], -1), p["conv"],
                           operand))
    return jnp.concatenate([out, flow], -1)


def gru_step(h, x, z_p, r_p, q_p, operand):
    hx = jnp.concatenate([h, x], -1)
    z = jax.nn.sigmoid(conv(hx, z_p, operand))
    r = jax.nn.sigmoid(conv(hx, r_p, operand))
    q = jnp.tanh(conv(jnp.concatenate([r * h, x], -1), q_p, operand))
    return (1 - z) * h + z * q


def gru(h, x, p, small, operand):
    """``ConvGRU`` (small) or ``SepConvGRU``: a (1, 5) step, then a
    (5, 1) step."""
    if small:
        return gru_step(h, x, p["convz"], p["convr"], p["convq"], operand)
    h = gru_step(h, x, p["convz1"], p["convr1"], p["convq1"], operand)
    return gru_step(h, x, p["convz2"], p["convr2"], p["convq2"], operand)


def convex_upsample(flow, mask):
    """``RAFT.upsample_flow``: each fine pixel is a softmax-weighted
    combination of the 3x3 coarse neighbourhood of ``8 * flow``; the
    mask's 576 channels split as (9 neighbours, 8 rows, 8 columns)."""
    b, h, w, _ = flow.shape
    mask = jax.nn.softmax(mask.reshape(b, h, w, 9, 8, 8), axis=3)
    padded = jnp.pad(8.0 * flow, ((0, 0), (1, 1), (1, 1), (0, 0)))
    nb = jnp.stack([padded[:, dy:dy + h, dx:dx + w]
                    for dy in range(3) for dx in range(3)], axis=3)
    up = jnp.einsum("bhwkyx,bhwkc->bhywxc", mask, nb, precision=HIGHEST)
    return up.reshape(b, 8 * h, 8 * w, 2)


def upflow8(flow):
    """``upflow8``: 8x bilinear, ``align_corners=True``, values times 8."""
    b, h, w, _ = flow.shape
    ty = jnp.arange(8 * h, dtype=jnp.float32) * ((h - 1) / (8 * h - 1))
    tx = jnp.arange(8 * w, dtype=jnp.float32) * ((w - 1) / (8 * w - 1))
    out = jnp.einsum("oh,bhwc->bowc", bilinear_weights(ty, h), flow,
                     precision=HIGHEST)
    return 8.0 * jnp.einsum("pw,bowc->bopc", bilinear_weights(tx, w), out,
                            precision=HIGHEST)


def forward(variables, image1, image2, *, small, iters, levels=4,
            operand=identity):
    """Images ``(B, H, W, 3)`` in [0, 255], H and W multiples of 8, to
    the full-resolution flow ``(B, H, W, 2)`` after ``iters`` updates."""
    params = variables["params"]
    stats = variables.get("batch_stats", {}).get("cnet", {})
    hidden, radius = (96, 3) if small else (128, 4)
    image1 = 2.0 * (image1.astype(jnp.float32) / 255.0) - 1.0
    image2 = 2.0 * (image2.astype(jnp.float32) / 255.0) - 1.0
    fmap1 = encoder(image1, params["fnet"], {}, "instance", small, operand)
    fmap2 = encoder(image2, params["fnet"], {}, "instance", small, operand)
    pyramid = correlation_pyramid(fmap1, fmap2, levels, operand)
    cnet = encoder(image1, params["cnet"], stats,
                   "none" if small else "batch", small, operand)
    net = jnp.tanh(cnet[..., :hidden])
    inp = jax.nn.relu(cnet[..., hidden:])
    b, h, w, _ = fmap1.shape
    yy, xx = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    coords0 = jnp.broadcast_to(jnp.stack([xx, yy], -1), (b, h, w, 2))
    p = params["update"]["update_block"]

    def update(carry, _):
        net, coords1 = carry
        corr = correlation_lookup(pyramid, coords1, radius)
        motion = motion_encoder(coords1 - coords0, corr, p["encoder"],
                                small, operand)
        net = gru(net, jnp.concatenate([inp, motion], -1), p["gru"], small,
                  operand)
        hid = jax.nn.relu(conv(net, p["flow_head"]["conv1"], operand))
        delta = conv(hid, p["flow_head"]["conv2"], operand)
        return (net, coords1 + delta), None

    (net, coords1), _ = jax.lax.scan(update, (net, coords0), None,
                                     length=iters)
    flow = coords1 - coords0
    if small:
        return upflow8(flow)
    mask = conv(jax.nn.relu(conv(net, p["mask_conv1"], operand)),
                p["mask_conv2"], operand)
    return convex_upsample(flow, 0.25 * mask)


# ---------------------------------------------------------------- training
#
# princeton-vl/RAFT ``train.py``: the forward pass in training mode (cnet's
# BatchNorm on batch statistics, every iteration's flow upsampled, the
# carried coordinates detached at the top of each iteration), the
# sequence loss, global-norm clipping, AdamW and the one-cycle schedule.
# BatchNorm's running statistics are not followed: in training mode
# nothing reads them, so neither the loss nor a gradient depends on them.

def make_batch_norm(params):
    """BatchNorm in training mode: the batch's own mean and (biased)
    variance over N, H, W."""
    def norm(name, x):
        mean = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
        var = jnp.mean((x - mean) ** 2, axis=(0, 1, 2), keepdims=True)
        p = params[name]["n"]
        return (x - mean) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]
    return norm


def train_forward(params, image1, image2, *, small, iters, levels=4,
                  operand=identity):
    """All ``iters`` upsampled flows, ``(iters, B, H, W, 2)``."""
    hidden, radius = (96, 3) if small else (128, 4)
    image1 = 2.0 * (image1.astype(jnp.float32) / 255.0) - 1.0
    image2 = 2.0 * (image2.astype(jnp.float32) / 255.0) - 1.0
    # jax.checkpoint, here and on the scan below: the backward pass
    # recomputes one encoder, or one iteration, at a time instead of
    # keeping every activation of all of them. The numbers are the same;
    # at 368x496, batch 8, float32 the step would not fit one chip
    # otherwise (15.8 GB against 6.5 GB, XLA's figures for a v5e).
    def encode(image, p, kind):
        return encoder(image, p, None, kind, small, operand)

    encode = jax.checkpoint(encode, static_argnums=(2,))
    fmap1 = encode(image1, params["fnet"], "instance")
    fmap2 = encode(image2, params["fnet"], "instance")
    pyramid = correlation_pyramid(fmap1, fmap2, levels, operand)
    cnet = encode(image1, params["cnet"],
                  "none" if small else "batch_train")
    net = jnp.tanh(cnet[..., :hidden])
    inp = jax.nn.relu(cnet[..., hidden:])
    b, h, w, _ = fmap1.shape
    yy, xx = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    coords0 = jnp.broadcast_to(jnp.stack([xx, yy], -1), (b, h, w, 2))
    p = params["update"]["update_block"]

    def update(carry, _):
        net, coords1 = carry
        coords1 = jax.lax.stop_gradient(coords1)
        corr = correlation_lookup(pyramid, coords1, radius)
        motion = motion_encoder(coords1 - coords0, corr, p["encoder"],
                                small, operand)
        net = gru(net, jnp.concatenate([inp, motion], -1), p["gru"], small,
                  operand)
        hid = jax.nn.relu(conv(net, p["flow_head"]["conv1"], operand))
        coords1 = coords1 + conv(hid, p["flow_head"]["conv2"], operand)
        flow = coords1 - coords0
        if small:
            return (net, coords1), upflow8(flow)
        mask = conv(jax.nn.relu(conv(net, p["mask_conv1"], operand)),
                    p["mask_conv2"], operand)
        return (net, coords1), convex_upsample(flow, 0.25 * mask)

    _, flows = jax.lax.scan(jax.checkpoint(update), (net, coords0), None,
                            length=iters)
    return flows


def sequence_loss(flows, flow_gt, valid, gamma=0.8, max_flow=400.0):
    """``train.py::sequence_loss``: L1 over every iteration's flow,
    weighted ``gamma**(n - i - 1)``, invalid pixels (and ground truth
    over ``max_flow``) zeroed, the mean taken over all pixels."""
    n = flows.shape[0]
    mag = jnp.sqrt(jnp.sum(flow_gt ** 2, axis=-1))
    v = (valid >= 0.5) & (mag < max_flow)
    loss = 0.0
    for i in range(n):
        i_loss = jnp.abs(flows[i] - flow_gt)
        loss = loss + gamma ** (n - i - 1) * jnp.mean(
            v[..., None] * i_loss)
    return loss


def one_cycle_lr(step, lr, total_steps, pct_start=0.05):
    """``OneCycleLR(max_lr, total_steps, pct_start=0.05,
    anneal_strategy="linear", cycle_momentum=False)`` at ``step``: from
    ``lr/25`` up to ``lr`` over the first 5 %, then down to ``lr/25e4``."""
    warm = max(int(total_steps * pct_start), 1)
    up = lr / 25.0 + (lr - lr / 25.0) * jnp.minimum(step / warm, 1.0)
    frac = jnp.clip((step - warm) / (total_steps - warm), 0.0, 1.0)
    down = lr + (lr / 25.0 / 1e4 - lr) * frac
    return jnp.where(step < warm, up, down)


def train_step(params, opt, batch, step, *, small, iters, lr, total_steps,
               wdecay=1e-4, eps=1e-8, clip=1.0, gamma=0.8, b1=0.9,
               b2=0.999, operand=identity):
    """One step of ``train.py``'s loop on ``batch`` (``image1``,
    ``image2``, ``flow``, ``valid``). ``opt`` holds Adam's ``mu`` and
    ``nu``. Returns the new parameters and moments, the loss and the
    clipped gradient the optimizer got."""
    def loss_fn(p):
        flows = train_forward(p, batch["image1"], batch["image2"],
                              small=small, iters=iters, operand=operand)
        return sequence_loss(flows, batch["flow"], batch["valid"], gamma)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    norm = jnp.sqrt(sum(jnp.sum(g ** 2) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-30))
    grads = jax.tree.map(lambda g: g * scale, grads)
    t = step + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["nu"],
                      grads)
    rate = one_cycle_lr(step, lr, total_steps)

    def update(p, m, v):
        m_hat = m / (1 - b1 ** t)
        v_hat = v / (1 - b2 ** t)
        return p - rate * (m_hat / (jnp.sqrt(v_hat) + eps) + wdecay * p)

    new_params = jax.tree.map(update, params, mu, nu)
    return new_params, {"mu": mu, "nu": nu}, loss, grads
