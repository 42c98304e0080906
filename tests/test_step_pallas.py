"""Fused one-launch refine-iteration kernel suite (round-10 tentpole).

CPU interpret-mode parity for ``ops/step_pallas.py`` — the single
Pallas launch chaining motion encoder → SepConvGRU (→ flow head) — at
three levels:

* **vs the two-launch chain** (``motion_pallas.motion_encoder`` →
  ``gru_pallas.sepconv_gru``): BIT-exact at every row tile, both
  fusion depths. Same shifted-matmul taps, same masks, same cast
  points — fusing the handoff, and walking an image's row tiles in
  order with every stage's trailing rows carried in VMEM, must not
  move a single bit.
* **vs the conv path** (``BasicUpdateBlock`` with all kernels off):
  within the ISSUE acceptance bounds (f32 forward ≤1e-5, grads ≤2e-4),
  forward and gradients, through the custom VJP and all three weight
  packers.
* **dispatch contract** (``RAFT_STEP_PALLAS``): '0' byte-identical,
  '1' forced (raises on TPU when inadmissible), auto fuses only on TPU
  with a LOUD logged fallback; plus the pinned VMEM admission table
  (Mosaic's own figures for the streamed body: every rung fits at
  Sintel in either dtype, and the rung is the one that streams least).
* **the pass census**: from the traced program, that no product with a
  contraction or an output of 2 is left, that every product streams
  exactly a grid step's ``th`` rows, and what a Sintel image streams.
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.ops import gru_pallas, motion_pallas, step_pallas, vmem

# Interpret-mode kernel parity suite — one selectable group across the
# corr/gru/msda/motion/step kernels (registered in conftest.py).
pytestmark = pytest.mark.pallas_interpret

B, H, W, CC = 2, 9, 7, 12
C = 128    # hidden/context channels
CO = 126   # motion fusing-conv width; handoff is [out(126) ‖ flow(2)]


def _pairs(params, *names):
    return tuple((params[n]["kernel"], params[n]["bias"]) for n in names)


def _packers(params):
    """(mmats, gmats, fmats) from a BasicUpdateBlock param tree — the
    same packers the fused dispatch path uses."""
    enc = params["encoder"]
    mmats = motion_pallas.pack_weights(*_pairs(
        enc, "convc1", "convc2", "convf1", "convf2", "conv"))
    gru = params["gru"]
    gmats = gru_pallas.pack_weights(
        _pairs(gru, "convz1", "convr1", "convq1"),
        _pairs(gru, "convz2", "convr2", "convq2"), C)
    fmats = step_pallas.pack_flow_head(*_pairs(
        params["flow_head"], "conv1", "conv2"))
    return mmats, gmats, fmats


def _inputs(w=W, seed=1):
    """(net, inp, corr, flow) for a batch of B images of H x ``w``."""
    rng = np.random.default_rng(seed)
    net = jnp.asarray(np.tanh(rng.standard_normal((B, H, w, C))),
                      jnp.float32)
    inp = jnp.asarray(rng.standard_normal((B, H, w, C)), jnp.float32)
    corr = jnp.asarray(rng.standard_normal((B, H, w, CC)), jnp.float32)
    flow = jnp.asarray(3.0 * rng.standard_normal((B, H, w, 2)),
                       jnp.float32)
    return net, inp, corr, flow


@pytest.fixture(scope="module")
def update_setup():
    """Full BasicUpdateBlock + inputs at a deliberately awkward shape
    (odd W, H not a row-tile multiple, so every carry and the
    padded-row masks are live through the 9/11-row receptive field)."""
    from raft_tpu.models.update import BasicUpdateBlock

    model = BasicUpdateBlock()
    net, inp, corr, flow = _inputs()
    vs = model.init(jax.random.PRNGKey(1), net, inp, corr, flow)
    return model, vs, net, inp, corr, flow


def _tile_span(rng, th, ti, halo, chans, dtype=jnp.float32):
    """A kernel tile's working span as ``halo_assemble`` leaves it at
    image shape (H, W): ``th + 2*halo`` rows whose rows outside the image
    hold garbage (what a clamped neighbour block brings), with the
    span's ``col`` and global-row vectors. ``ti`` picks the tile, so the
    first, an inner and the last one put every edge of the masks to
    work."""
    rows = (th + 2 * halo) * W
    ri = jnp.arange(rows, dtype=jnp.int32)[:, None]
    col = ri % W
    grow = ti * th - halo + ri // W
    v = jnp.asarray(3.0 * rng.standard_normal((rows, chans)), dtype)
    return v, col, grow


def _dot_generals(jaxpr):
    """Every ``dot_general`` under ``jaxpr`` (kernel bodies included),
    as ``(rows, K, N)``."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, rc), _ = eqn.params["dimension_numbers"]
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            assert len(lhs) == len(rhs) == 2 and lc == (1,) and rc == (0,)
            out.append((lhs[0], lhs[1], rhs[1]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _dot_generals(sub)
    return out


def _real_width_mats():
    """The three packers' outputs at RAFT-large widths (324 corr
    channels), as shapes only."""
    def z(*shape):
        return jnp.zeros(shape, jnp.float32)

    def build():
        mm = motion_pallas.pack_weights(
            (z(1, 1, 324, 256), z(256)), (z(3, 3, 256, 192), z(192)),
            (z(7, 7, 2, 128), z(128)), (z(3, 3, 128, 64), z(64)),
            (z(3, 3, 256, CO), z(CO)))
        gm = gru_pallas.pack_weights(
            tuple((z(1, 5, 3 * C, C), z(C)) for _ in range(3)),
            tuple((z(5, 1, 3 * C, C), z(C)) for _ in range(3)), C)
        fm = step_pallas.pack_flow_head((z(3, 3, C, 256), z(256)),
                                        (z(3, 3, 256, 2), z(2)))
        return mm, gm, fm
    return jax.eval_shape(build)


class TestForwardParity:
    @pytest.mark.parametrize("w,th,fh,foreign", [
        # H = 9 is no multiple of any of these tiles.
        (W, 8, False, False), (W, 8, True, False),    # 2 tiles + 2 closing
        # th under the outputs' lag (9 / 11 rows): 3 tiles and 3 closing
        # steps, stages that keep more rows than a step computes
        (W, 4, False, False), (W, 4, True, False),
        # a one-tile image, of a width that needs no padding
        (16, 16, True, False),
        # the image before it in the batch leaves nothing behind
        (W, 8, True, True), (W, 4, False, True)])
    def test_fused_is_bitexact_vs_chained_kernels(self, update_setup,
                                                  w, th, fh, foreign):
        """The whole point of the fusion: identical arithmetic to the
        two-launch motion→GRU chain, with the handoff buffer gone. h2
        must not move a bit at ANY row tile, although the streamed body
        computes each row of each stage once, in a product of ``th``
        rows, where the chained kernels compute it with its tile's
        halo. (An image row takes 16 flattened rows in the fused
        kernel, so its products have 64 rows or more; under 51 XLA's
        CPU dot sums a contraction of 256 in another order, for any
        kernel alike.) ``foreign``: the first image of the
        batch is filled with 1e4, and the second must come out, bit for
        bit, as it does alone — the carries are cut at an image's first
        step."""
        _, vs, *_ = update_setup
        net, inp, corr, flow = args = _inputs(w)
        mmats, gmats, fmats = _packers(vs["params"])

        def fused(net, inp, corr, flow):
            return step_pallas.fused_step(net, inp, corr, flow, mmats,
                                          gmats, fmats if fh else None,
                                          interpret=True, th=th)

        if foreign:
            got = fused(*(a.at[0].set(1e4) for a in args))
            want = fused(*(a[1:] for a in args))
            for a, b in zip(got if fh else (got,),
                            want if fh else (want,)):
                assert np.isfinite(np.asarray(b)).all()
                np.testing.assert_array_equal(np.asarray(a[1:]),
                                              np.asarray(b))
            return
        mot = motion_pallas.motion_encoder(flow, corr, mmats,
                                           interpret=True, th=th)
        want_h2 = gru_pallas.sepconv_gru(net, (inp, mot), gmats,
                                         interpret=True, th=th)
        out = fused(*args)
        got_h2 = out[0] if fh else out
        np.testing.assert_array_equal(np.asarray(got_h2),
                                      np.asarray(want_h2))

    @pytest.mark.parametrize("th,ti", [(4, 0), (4, 1), (4, 2), (8, 1)])
    def test_packed_convf1_matches_the_49_tap_sum(self, th, ti):
        """``convf1`` as one contraction over its 98 tap-channels vs the
        49 shifted-masked K = 2 products it replaces, at float32 on one
        tile's span (odd W, H not a tile multiple, first / inner / last
        tile, out-of-image rows holding garbage). The patch operand is
        the 49 masked copies side by side, value for value; the product
        differs only by the order of 98 float32 partial sums (measured
        over these four cases and two more tiles at PR 34: at most
        6.7e-6 on outputs of magnitude up to 18, four parts in ten
        million; asserted at three times that)."""
        rng = np.random.default_rng(10 * th + ti)
        hm = step_pallas.halos(True)[1]
        fac, col, grow = _tile_span(rng, th, ti, hm, 2)
        wf1 = jnp.asarray(rng.standard_normal((98, 128)) / 7.0,
                          jnp.float32)
        bf1 = jnp.asarray(rng.standard_normal((1, 128)), jnp.float32)

        def valid(dy, dx):
            return motion_pallas.tap_valid(col, grow, W, H, dy, dx)

        patches = motion_pallas.flow_patches(fac, col, grow, W, H)
        copies = [motion_pallas._shift_rows(fac, dy * W + dx)
                  * valid(dy, dx).astype(jnp.float32)
                  for dy in range(-3, 4) for dx in range(-3, 4)]
        np.testing.assert_array_equal(
            np.asarray(patches),
            np.asarray(jnp.concatenate(
                copies + [jnp.zeros((fac.shape[0], 30))], axis=1)))
        want = motion_pallas.conv_taps(valid, [(fac, wf1)], bf1, 7, W)
        got = motion_pallas.flow_conv7(
            fac, jnp.pad(wf1, ((0, 30), (0, 0))), bf1, col, grow, W, H)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=0)

    @pytest.mark.parametrize("th,ti", [(4, 0), (4, 1), (4, 2), (8, 1)])
    def test_folded_head_conv_is_the_9_tap_sum(self, th, ti):
        """The flow head's 3x3 ``256 -> 2`` conv as one product and nine
        shifted adds of its float32 columns vs the nine N = 2 products
        it replaces: the same terms in the same order, so not a bit
        moves. The streamed body zeroes ``fh1``'s rows outside the image
        where it produces them and hands the product over the output
        rows and one image row each side; the nine-tap sum masks each
        tap on a span whose outside rows hold garbage."""
        rng = np.random.default_rng(20 * th + ti)
        hg = step_pallas.halos(True)[0]
        fh1, col, grow = _tile_span(rng, th, ti, hg, 256)
        wfh2 = jnp.asarray(rng.standard_normal((9 * 256, 2)) / 48.0,
                           jnp.float32)
        bfh2 = jnp.asarray(rng.standard_normal((1, 2)), jnp.float32)

        def valid(dy, dx):
            return motion_pallas.tap_valid(col, grow, W, H, dy, dx)

        want = motion_pallas.conv_taps(valid, [(fh1, wfh2)], bfh2, 3, W)
        taps = jnp.dot(jnp.where((grow >= 0) & (grow < H), fh1, 0.0),
                       step_pallas.fold_head_taps(wfh2),
                       preferred_element_type=jnp.float32)
        got = step_pallas.folded_head_conv(
            taps, bfh2, W, W, fh1.shape[0] - 2 * W, jnp.float32)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want[W:-W]))

    def test_mgf_delta_matches_conv_flow_head(self, update_setup):
        """The in-kernel flow head vs the flax FlowHead on the SAME h2
        (tap decomposition changes only the reduction order)."""
        from raft_tpu.models.update import FlowHead

        _, vs, net, inp, corr, flow = update_setup
        mmats, gmats, fmats = _packers(vs["params"])
        h2, delta = step_pallas.fused_step(net, inp, corr, flow, mmats,
                                           gmats, fmats, interpret=True)
        want = FlowHead(256).apply(
            {"params": vs["params"]["flow_head"]}, h2)
        np.testing.assert_allclose(np.asarray(delta), np.asarray(want),
                                   atol=1e-5, rtol=0)

    def test_reference_twin_matches_kernel(self, update_setup):
        """The pure-jnp twin (the VJP backward) reproduces the fused
        kernel — identical tap order/masks/cast points."""
        _, vs, net, inp, corr, flow = update_setup
        mmats, gmats, fmats = _packers(vs["params"])
        h2, delta = step_pallas.fused_step(net, inp, corr, flow, mmats,
                                           gmats, fmats, interpret=True)
        gm = gru_pallas.split_x_weights(gmats, (C, CO + 2))
        ref_h2, ref_delta = step_pallas.reference_step(
            (W, H), net.reshape(B, H * W, C), inp.reshape(B, H * W, C),
            flow.reshape(B, H * W, 2), corr.reshape(B, H * W, CC),
            mmats, gm, fmats)
        np.testing.assert_allclose(
            np.asarray(h2), np.asarray(ref_h2.reshape(B, H, W, C)),
            atol=1e-5, rtol=0)
        np.testing.assert_allclose(
            np.asarray(delta), np.asarray(ref_delta.reshape(B, H, W, 2)),
            atol=1e-5, rtol=0)

    @pytest.mark.parametrize("compute_mask", [True, None])
    def test_forced_matches_conv_path(self, update_setup, monkeypatch,
                                      compute_mask):
        """'1' through BasicUpdateBlock vs the all-conv path, both mask
        regimes: compute_mask=True runs the 'mg' depth (mask/flow heads
        stay XLA), None runs 'mgf' (delta in-kernel). f32 acceptance
        bound ≤1e-5."""
        model, vs, net, inp, corr, flow = update_setup
        for f in ("RAFT_MOTION_PALLAS", "RAFT_GRU_PALLAS"):
            monkeypatch.delenv(f, raising=False)
        monkeypatch.setenv("RAFT_STEP_PALLAS", "0")
        want = model.apply(vs, net, inp, corr, flow,
                           compute_mask=compute_mask)
        monkeypatch.setenv("RAFT_STEP_PALLAS", "1")
        got = model.apply(vs, net, inp, corr, flow,
                          compute_mask=compute_mask)
        for a, b in zip(got, want):
            if a is None and b is None:
                continue
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=0)

    def test_bf16_matches_conv_path(self, update_setup, monkeypatch):
        """bf16 compute dtype (the mixed-precision policy): both paths
        share the f32-accumulate → bf16-bias-add contract; the chain is
        ~11 convs deep, so allow a few bf16 ulp of the feature scale."""
        from raft_tpu.models.update import BasicUpdateBlock

        _, vs, net, inp, corr, flow = update_setup
        model16 = BasicUpdateBlock(dtype=jnp.bfloat16)
        args16 = tuple(a.astype(jnp.bfloat16)
                       for a in (net, inp, corr, flow))
        monkeypatch.setenv("RAFT_STEP_PALLAS", "0")
        monkeypatch.setenv("RAFT_MOTION_PALLAS", "0")
        monkeypatch.setenv("RAFT_GRU_PALLAS", "0")
        want = model16.apply(vs, *args16, compute_mask=None)
        monkeypatch.setenv("RAFT_STEP_PALLAS", "1")
        got = model16.apply(vs, *args16, compute_mask=None)
        for a, b in zip(got, want):
            if a is None and b is None:
                continue
            a32 = np.asarray(a, np.float32)
            b32 = np.asarray(b, np.float32)
            scale = float(np.max(np.abs(b32)))
            tol = 8 * float(jnp.finfo(jnp.bfloat16).eps) * max(scale, 1.0)
            np.testing.assert_allclose(a32, b32, atol=tol, rtol=0)


class TestGradParity:
    def test_input_grads_match_conv_path(self, update_setup,
                                         monkeypatch):
        """d(sum(h2)+sum(delta))/d{net, inp, corr, flow} through the
        custom VJP (recompute via the jnp twin) vs the conv path's
        autodiff — the ISSUE acceptance bound ≤2e-4."""
        model, vs, net, inp, corr, flow = update_setup

        def loss(n, i, c, f):
            h2, _, delta = model.apply(vs, n, i, c, f,
                                       compute_mask=None)
            return jnp.sum(h2) + jnp.sum(delta)

        for f in ("RAFT_MOTION_PALLAS", "RAFT_GRU_PALLAS"):
            monkeypatch.delenv(f, raising=False)
        # each compiled as one program (the path is read as it traces)
        monkeypatch.setenv("RAFT_STEP_PALLAS", "0")
        g_conv = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(
            net, inp, corr, flow)
        monkeypatch.setenv("RAFT_STEP_PALLAS", "1")
        g_fused = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(
            net, inp, corr, flow)
        for a, b in zip(g_conv, g_fused):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=0)

    def test_param_grads_flow_through_packers(self, update_setup,
                                              monkeypatch):
        """Gradients reach the flax param tree through all three weight
        packers (motion / GRU / flow head) — what training with the
        fused scan body relies on."""
        model, vs, net, inp, corr, flow = update_setup

        def loss(params):
            h2, _, delta = model.apply({"params": params}, net, inp,
                                       corr, flow, compute_mask=None)
            return jnp.sum(h2) + jnp.sum(delta)

        for f in ("RAFT_MOTION_PALLAS", "RAFT_GRU_PALLAS"):
            monkeypatch.delenv(f, raising=False)
        monkeypatch.setenv("RAFT_STEP_PALLAS", "0")
        g_conv = jax.jit(jax.grad(loss))(vs["params"])
        monkeypatch.setenv("RAFT_STEP_PALLAS", "1")
        g_fused = jax.jit(jax.grad(loss))(vs["params"])
        for a, b in zip(jax.tree_util.tree_leaves(g_conv),
                        jax.tree_util.tree_leaves(g_fused)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=0)


class TestPassCensus:
    """The mechanism's counter, read from the program itself: what the
    kernel streams through the MXU, in pass units of (row of a product)
    x (128-wide slice of the contraction) x (128-wide slice of the
    output), at RAFT-large widths."""

    MOTION_UNITS = 79     # 6 + 36 + 1 (convf1: 49 before) + 9 + 27
    GRU_UNITS = 90
    HEAD_UNITS = 20       # 18 + 2 (conv2: 18 before)

    @staticmethod
    def _units(dots, rows):
        return sum(-(-k // 128) * -(-n // 128)
                   for r, k, n in dots if r == rows)

    @pytest.mark.parametrize("th", [4, 8])
    @pytest.mark.parametrize("fh", [False, True])
    def test_no_two_channel_tap_gets_a_pass_of_its_own(self, fh, th):
        mm, gm, fm = _real_width_mats()

        def sds(c):
            return jax.ShapeDtypeStruct((1, H, W, c), jnp.bfloat16)

        jaxpr = jax.make_jaxpr(
            lambda n, i, c, f, mm, gm, fm: step_pallas.fused_step(
                n, i, c, f, mm, gm, fm if fh else None,
                dtype=jnp.bfloat16, interpret=True, th=th))(
            sds(C), sds(C), sds(324), sds(2), mm, gm, fm)
        dots = _dot_generals(jaxpr.jaxpr)
        assert all(k > 2 and n > 2 for _, k, n in dots), dots
        # 38 motion + 60 GRU (+ 10 flow head); 86 + 60 (+ 18) before
        assert len(dots) == (108 if fh else 98)
        # Every product streams exactly the grid step's th rows (an image
        # row padded to whole sublane tiles: 7 -> 16 here, 62 -> 64 at
        # chairs, 128 as it is at Sintel): no stage computes a row for a
        # neighbouring tile's sake (the motion products ran over
        # th + 2*hm rows and the GRU's over th + 2*hg).
        assert {r for r, _, _ in dots} == {th * 16}
        assert self._units(dots, th * 16) == (
            self.MOTION_UNITS + self.GRU_UNITS
            + (self.HEAD_UNITS if fh else 0))

    def test_a_sintel_image_streams_its_rows_and_the_lag(self):
        """The number ISSUE 36's prediction rests on: what a 55-row
        Sintel feature map streams at the rung ``choose_rows`` picks.
        Self-contained tiles streamed 4 x (79 x 38 + 110 x 28) = 24,328
        units at TH 16 (442 a kept row); a stage now streams the
        image's tiles and the closing steps its outputs lag by, and
        189 a kept row is the floor."""
        th = step_pallas.choose_rows(55, 128, 324, 2, flow_head=True)
        per_row = self.MOTION_UNITS + self.GRU_UNITS + self.HEAD_UNITS
        streamed = {t: step_pallas.grid_steps(55, t, True) * t * per_row
                    for t in (16, 8)}
        assert streamed == {16: 5 * 16 * 189, 8: 9 * 8 * 189}
        assert (th, streamed[th]) == (8, 13608)         # 247 a kept row

    def test_chained_motion_kernel_shares_the_packing(self):
        mm, _, _ = _real_width_mats()
        jaxpr = jax.make_jaxpr(
            lambda f, c, mm: motion_pallas.motion_encoder(
                f, c, mm, dtype=jnp.bfloat16, interpret=True, th=4))(
            jax.ShapeDtypeStruct((1, H, W, 2), jnp.float32),
            jax.ShapeDtypeStruct((1, H, W, 324), jnp.bfloat16), mm)
        dots = _dot_generals(jaxpr.jaxpr)
        assert all(k > 2 and n > 2 for _, k, n in dots), dots
        assert len(dots) == 38
        assert self._units(dots, (4 + 10) * W) == self.MOTION_UNITS


class TestDispatch:
    def test_flag_off_is_bitexact(self, update_setup, monkeypatch):
        """RAFT_STEP_PALLAS=0 and unset-on-CPU (auto) both take the
        existing path through BasicUpdateBlock — bit-for-bit identical
        (the acceptance pin; the golden-EPE variant lives in
        test_golden.py)."""
        model, vs, net, inp, corr, flow = update_setup
        for f in ("RAFT_STEP_PALLAS", "RAFT_MOTION_PALLAS",
                  "RAFT_GRU_PALLAS"):
            monkeypatch.delenv(f, raising=False)
        auto = model.apply(vs, net, inp, corr, flow)
        monkeypatch.setenv("RAFT_STEP_PALLAS", "0")
        off = model.apply(vs, net, inp, corr, flow)
        for a, b in zip(auto, off):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_plan_fusion_modes(self, update_setup, monkeypatch):
        _, _, net, inp, corr, flow = update_setup
        plan = step_pallas.plan_fusion
        assert plan(net, inp, corr, flow, True, mode="0") is None
        # forced off-TPU: interpret-mode parity tooling, depth by need
        assert plan(net, inp, corr, flow, True, mode="1") == "mgf"
        assert plan(net, inp, corr, flow, False, mode="1") == "mg"
        # auto off-TPU: keep the XLA/chained path
        monkeypatch.delenv("RAFT_STEP_PALLAS", raising=False)
        assert plan(net, inp, corr, flow, True) is None

    def test_auto_on_tpu_fuses_the_depth_that_is_wanted(self, monkeypatch):
        """KITTI f32 (48x156 features) on a (faked) TPU backend: while
        every tile recomputed its halo the flow-head depth fitted no
        tile there and auto stepped down to 'mg'; the streamed body's
        working rows do not grow with the depth, so a shape that admits
        a rung admits it at the depth that is wanted."""
        monkeypatch.delenv("RAFT_STEP_PALLAS", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

        def sds(hw, dtype):
            return tuple(jax.ShapeDtypeStruct((1, *hw, c), dtype)
                         for c in (C, C, 324, 2))

        kitti = sds((48, 156), jnp.float32)
        assert step_pallas.plan_fusion(*kitti, True) == "mgf"
        assert step_pallas.plan_fusion(*kitti, False) == "mg"
        for dtype in (jnp.float32, jnp.bfloat16):
            assert step_pallas.plan_fusion(*sds((55, 128), dtype),
                                           True) == "mgf"

    def test_forced_bad_shape_raises(self, update_setup):
        _, _, net, inp, corr, _ = update_setup
        bad_flow = jnp.zeros((B, H, W, 3), jnp.float32)
        with pytest.raises(ValueError, match="RAFT_STEP_PALLAS=1"):
            step_pallas.plan_fusion(net, inp, corr, bad_flow, True,
                                    mode="1")

    def test_forced_inadmissible_on_tpu_raises(self, monkeypatch):
        """'1' on a TPU backend must never silently degrade: when no
        tile fits (an 8K frame's float32 feature map; 1080p fits since
        the body streams), the forced arm dies loudly at trace time."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

        def sds(c):
            return jax.ShapeDtypeStruct((1, 540, 960, c), jnp.float32)

        with pytest.raises(ValueError, match="admits no row tile"):
            step_pallas.plan_fusion(sds(C), sds(C), sds(324),
                                    sds(2), False, mode="1")

    def test_auto_fallback_is_logged_step(self, monkeypatch, caplog):
        """The satellite contract carried to the fused step: when auto
        on a TPU backend rejects a shape on the VMEM envelope, one loud
        structured warning names the flag, shape and budget — never a
        silent two-launch fallback."""
        monkeypatch.delenv("RAFT_STEP_PALLAS", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

        def sds(c):     # an 8K frame's feature map: too wide for any tile
            return jax.ShapeDtypeStruct((1, 540, 960, c), jnp.float32)

        with caplog.at_level(logging.WARNING,
                             logger="raft_tpu.ops.vmem"):
            assert step_pallas.plan_fusion(sds(C), sds(C), sds(324),
                                           sds(2), False) is None
        assert "RAFT_STEP_PALLAS=auto" in caplog.text
        assert "falling back to the XLA path" in caplog.text
        assert "H=540, W=960" in caplog.text
        assert "admission budget" in caplog.text

    @pytest.mark.multidevice
    @pytest.mark.parametrize("kernel", ["step", "motion", "gru", "msda"])
    def test_mesh_keeps_the_xla_path(self, kernel, monkeypatch, caplog):
        """GSPMD cannot partition a Mosaic kernel and the scan-body
        kernels have no shard_map wrapper: on a (faked) TPU backend
        under a kernel mesh of more than one device, auto keeps the XLA
        path and says so; a forced '1' raises; a one-device mesh and no
        mesh are unaffected."""
        from raft_tpu.parallel import make_mesh
        from raft_tpu.parallel.spatial import spatial_kernel_mesh
        for flag in ("RAFT_STEP_PALLAS", "RAFT_MOTION_PALLAS",
                     "RAFT_GRU_PALLAS"):
            monkeypatch.delenv(flag, raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

        def sds(c):
            return jax.ShapeDtypeStruct((2, 46, 62, c), jnp.bfloat16)

        def decide(mode=None):
            if kernel == "msda":
                from raft_tpu.ops import msda, msda_pallas
                if mode == "1":     # only auto consults the rule
                    raise ValueError("no shard_map wrapper")
                monkeypatch.setattr(msda_pallas, "ms_deform_attn_pallas",
                                    lambda *a, **k: "kernel")
                out = msda.ms_deform_attn(
                    jnp.zeros((1, 44 * 60, 8, 16)), ((44, 60),),
                    jnp.zeros((1, 256, 8, 1, 4, 2)),
                    jnp.zeros((1, 256, 8, 1, 4)))
                return isinstance(out, str)
            if kernel == "step":
                return step_pallas.plan_fusion(
                    sds(C), sds(C), sds(324), sds(2), True, mode=mode)
            if kernel == "motion":
                return motion_pallas.should_fuse(sds(2), sds(324),
                                                 mode=mode)
            return gru_pallas.should_fuse(sds(C), sds(2 * C), C, mode=mode)

        assert decide()                         # no mesh: the kernel
        with spatial_kernel_mesh(make_mesh(devices=jax.devices()[:1])):
            assert decide()                     # one device: the kernel
        with spatial_kernel_mesh(make_mesh(devices=jax.devices()[:2])):
            with caplog.at_level(logging.WARNING,
                                 logger="raft_tpu.parallel.spatial"):
                assert not decide()
            assert "no shard_map wrapper" in caplog.text
            with pytest.raises(ValueError, match="shard_map wrapper"):
                decide(mode="1")

    def test_bad_env_value_fails_loudly(self, monkeypatch):
        monkeypatch.setenv("RAFT_STEP_PALLAS", "on")
        with pytest.raises(ValueError, match="RAFT_STEP_PALLAS"):
            step_pallas.resolve_mode()


class TestEligibility:
    def test_halos_compose_across_the_chain(self):
        """GRU ±4 (+flow head ±2) of valid x; motion inputs another ±5
        beyond wherever its output must be valid. No tile assembles
        these rows any more: the outputs run ``hm`` rows behind the
        input blocks, which costs an image ``ceil(hm / th)`` closing
        grid steps, and the stages keep in VMEM what their readers
        need above a step's rows."""
        assert step_pallas.halos(False) == (4, 9)
        assert step_pallas.halos(True) == (6, 11)
        assert [step_pallas.grid_steps(55, th, True)
                for th in (16, 8, 4)] == [4 + 1, 7 + 2, 14 + 3]
        assert [step_pallas.grid_steps(46, th, False)
                for th in (16, 8, 4)] == [3 + 1, 6 + 2, 12 + 3]
        rows = step_pallas._carry_rows(16, True)
        assert (rows["flow"], rows["cor1"], rows["inp"], rows["h1"],
                rows["h2"], rows["delta"]) == (6, 5, 11, 4, 7, 5)
        # 63 image rows in all, most of them 128 lanes wide: ~2.4 MB at
        # W = 128 in bfloat16
        assert sum(rows.values()) == 63

    def test_sintel_admission_table(self):
        """The pinned envelope under the explicit 100 MiB scoped limit
        and Mosaic's figures for the streamed body. Every rung fits at
        Sintel-eval feature shapes (H=55, W=128, Ccorr=4*81=324) in
        either dtype (bf16 'mgf' TH=16 took 75.2 MiB while every tile
        recomputed its halo and takes 40.3; f32 TH=16 took 102.4 and
        takes 81.6), so the rung is the one whose grid streams least:
        TH=8 for Sintel's 55 rows (72 rows a stage against 80 at
        TH=16), TH=16 for chairs' 46 and KITTI's 48 (64 rows either
        way), TH=8 for 1080p's 135, which is admitted now (f32 too, up
        to TH=8). The ladder has no TH=4: that rung reads wrong on the
        chip, and nothing that fits a rung needs it."""
        assert step_pallas._ROW_LADDER == (16, 8)
        assert step_pallas.choose_rows(55, 128, 324, 2) == 8
        assert step_pallas.choose_rows(55, 128, 324, 2,
                                       flow_head=True) == 8
        assert step_pallas.choose_rows(55, 128, 324, 4) == 8
        assert step_pallas.choose_rows(55, 128, 324, 4,
                                       flow_head=True) == 8
        assert step_pallas.choose_rows(46, 62, 324, 2) == 16
        assert step_pallas.choose_rows(48, 156, 324, 2,
                                       flow_head=True) == 16
        assert step_pallas.choose_rows(48, 156, 324, 4,
                                       flow_head=True) == 8
        assert step_pallas.choose_rows(135, 240, 324, 2) == 8
        assert step_pallas.choose_rows(135, 240, 324, 4,
                                       flow_head=True) == 8
        assert not vmem.fits(step_pallas.step_vmem_parts(240, 16, 4),
                             vmem.SCAN_LIMIT_BYTES)
        assert step_pallas.choose_rows(540, 960, 324, 4) is None

    @pytest.mark.parametrize("w,th,dtype_bytes,flow_head,mosaic_mib", [
        (128, 4, 2, True, 16.19), (128, 8, 2, True, 24.34),
        (128, 16, 2, False, 35.68), (128, 16, 2, True, 40.34),
        (128, 8, 4, True, 49.09), (128, 16, 4, True, 81.59),
        (62, 16, 2, True, 22.48), (156, 8, 2, True, 29.05),
        (240, 16, 2, True, 70.80), (240, 8, 4, True, 82.41),
        (240, 16, 4, True, None)])
    def test_estimate_covers_what_mosaic_reported(self, w, th, dtype_bytes,
                                                  flow_head, mosaic_mib):
        """The phase-peak estimate admitted Sintel bf16 'mg' TH=4 at
        12.8 MiB under a 13 MiB budget where Mosaic needed 45.4 MiB.
        The calibrated estimate is at least what the compiler reported
        (for the present body, compiled for a described v5e under a
        1 GiB limit) at every probed tile and width, and inside the
        limit there: the tiles the cells ride (Sintel bf16 TH=8, chairs
        TH=16) are admitted. A tile the compiler could not place at all
        (f32 'mgf' TH=16 at 1080p: RESOURCE_EXHAUSTED) is refused."""
        est = vmem.total_bytes(step_pallas.step_vmem_parts(
            w, th, dtype_bytes, flow_head=flow_head))
        if mosaic_mib is None:
            assert est > vmem.SCAN_LIMIT_BYTES
        else:
            assert mosaic_mib * 2**20 <= est <= vmem.SCAN_LIMIT_BYTES

    def test_small_shapes_admit_deeper_fusion(self):
        """Smaller operating points ride the top rung at the 'mgf'
        depth — the serving brownout ladder's shapes stay fused."""
        assert step_pallas.choose_rows(30, 64, 324, 2) == 16
        assert step_pallas.choose_rows(30, 64, 324, 2,
                                       flow_head=True) == 16

    def test_fused_step_preflights_real_launches(self, update_setup):
        """fused_step(interpret=False) trips the itemized VMEM
        preflight before any pallas_call for an over-budget shape (a
        map as wide as an 8K frame's: no rung fits)."""
        _, vs, *_ = update_setup
        mmats, gmats, fmats = _packers(vs["params"])
        rng = np.random.default_rng(2)
        net = jnp.asarray(rng.standard_normal((1, 8, 960, C)),
                          jnp.float32)
        inp = jnp.asarray(rng.standard_normal((1, 8, 960, C)),
                          jnp.float32)
        corr = jnp.asarray(rng.standard_normal((1, 8, 960, CC)),
                           jnp.float32)
        flow = jnp.asarray(rng.standard_normal((1, 8, 960, 2)),
                           jnp.float32)
        with pytest.raises(ValueError, match="VMEM"):
            step_pallas.fused_step(net, inp, corr, flow, mmats, gmats,
                                   fmats, interpret=False)

    def test_generic_ladder_alignment_and_budget(self):
        """vmem.choose_rows (shared by motion/gru/step): misaligned
        (th*w) % 8 rungs are skipped even when they'd fit; every
        aligned rung over budget → None."""
        huge, tiny = {"x": 1 << 40}, {"x": 1 << 10}
        assert vmem.choose_rows(
            (16, 8, 4), 2,
            lambda th: tiny if th == 4 else huge) == 4
        assert vmem.choose_rows((16, 8, 4), 2, lambda th: huge) is None
        assert vmem.choose_rows((4,), 1, lambda th: tiny) is None


class TestPackFlowHead:
    def test_shapes(self, update_setup):
        _, vs, *_ = update_setup
        _, _, fmats = _packers(vs["params"])
        assert [m.shape for m in fmats] == [
            (9 * C, 256), (1, 256), (9 * 256, 2), (1, 2)]

    def test_rejects_wrong_geometry(self):
        k1 = jnp.zeros((3, 3, C, 256))
        b1 = jnp.zeros((256,))
        k2 = jnp.zeros((3, 3, 256, 2))
        b2 = jnp.zeros((2,))
        with pytest.raises(ValueError, match="HWIO"):
            step_pallas.pack_flow_head(
                (jnp.zeros((1, 5, C, 256)), b1), (k2, b2))
        with pytest.raises(ValueError, match="chain mismatch"):
            step_pallas.pack_flow_head(
                (k1, b1), (jnp.zeros((3, 3, 128, 2)), b2))
        with pytest.raises(ValueError, match="chain mismatch"):
            step_pallas.pack_flow_head(
                (k1, b1), (jnp.zeros((3, 3, 256, 3)), jnp.zeros((3,))))
