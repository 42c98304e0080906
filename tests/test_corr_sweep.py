"""The corr kernel's diagonal y-sweep: the forward kernel folds a target
row into a y-offset accumulator only where the tile's hat weight can be
nonzero. Held bit for bit against the dense sweep it replaced, kept here
as an oracle, and its pair counts (``sweep_stats``) against an
enumeration. A file of its own, beside ``test_corr_pallas.py``, so that
``--dist loadfile`` can hand the two to different workers.

On CPU the kernel runs in Pallas interpreter mode."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.models.corr import build_feature_pyramid, windowed_correlation

# Interpret-mode kernel parity suite — one selectable group across the
# corr/gru/msda/motion kernels (registered in conftest.py).
pytestmark = pytest.mark.pallas_interpret


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _dense_fwd_kernel(cx_ref, cy_ref, f1_ref, *refs, radius, scale, levels,
                      mxu_dtype, band, rescale, tout=False, band_rows=0):
    """The forward kernel as it swept before the diagonal rule, kept as
    the oracle: every row of every 8-row chunk product is folded into all
    ``2r+1`` y-offset accumulators in order of ``y`` (no band, no
    skipping), then the same x-side contraction and store. Run in the
    kernel's place under the same interpreter, so equality is bit for
    bit: same terms, same order, same float32 expressions."""
    from jax.experimental import pallas as pl
    from raft_tpu.ops import corr_pallas as cp
    from raft_tpu.ops import layout as klayout
    nl = len(levels)
    f2_refs, out_ref, t1_ref = refs[:nl], refs[nl], refs[nl + 1]
    win = 2 * radius + 1
    f1 = f1_ref[0].astype(cp._mxu(mxu_dtype))
    tq, c = f1.shape
    cx0 = cx_ref[0].astype(jnp.float32)
    cy0 = cy_ref[0].astype(jnp.float32)
    level_rows = []
    for l, (_, h2lp, w2pl) in enumerate(levels):
        lscale = (1.0 / 2 ** l) if rescale else 1.0
        cx, cy = cx0 * lscale, cy0 * lscale
        t1_ref[0:win * w2pl, :] = jnp.zeros((win * w2pl, tq), jnp.float32)

        def body(yc, carry, l=l, w2pl=w2pl, cy=cy):
            f2c = f2_refs[l][0, pl.ds(yc * (8 * w2pl), 8 * w2pl), :]
            corr = jax.lax.dot_general(
                f2c.astype(f1.dtype), f1, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            y0f = (yc * 8).astype(jnp.float32)
            for r_i in range(8):
                row = corr[r_i * w2pl:(r_i + 1) * w2pl, :]
                for i in range(win):
                    wy = cp._hat(y0f + r_i - (cy + (i - radius)))
                    t1_ref[i * w2pl:(i + 1) * w2pl, :] += wy * row
            return carry

        jax.lax.fori_loop(0, h2lp // 8, body, 0)
        xi = cp._x_iota(w2pl, tq)
        for a in range(win):
            vx = cp._hat(xi - (cx + (a - radius)))
            for b in range(win):
                t1_b = t1_ref[b * w2pl:(b + 1) * w2pl, :]
                level_rows.append(jnp.sum(t1_b * vx, axis=0, keepdims=True))
    out = jnp.concatenate(level_rows, axis=0)
    if scale:
        out = out * (1.0 / (c ** 0.5))
    klayout.boundary_store(out_ref, out, transpose=tout)


@functools.lru_cache(maxsize=None)
def _fused_interpret(radius, band, rescale, which="kernel"):
    """The fused lookup in interpret mode under ``jax.jit``, one compile
    per (radius, band, rescale) shared by the cases below. ``which`` only
    keys the cache: the oracle's entry is traced while ``_fwd_kernel`` is
    patched, the scratch-limited one while ``_BAND_ROWS`` is."""
    from raft_tpu.ops.corr_pallas import windowed_correlation_pallas_fused
    return jax.jit(lambda f1, pyr, coords: windowed_correlation_pallas_fused(
        f1, pyr, coords, radius, interpret=True, band=band, rescale=rescale))


def _sweep_case(rng, name):
    """(f1, pyramid, coords, radius, rescale) of one named case: a 24 x 64
    grid (a query tile is four raster rows) over levels of 24 and 12
    rows; the second is not a multiple of the 8-row chunk."""
    B, C, H, W, L = 1, 8, 24, 64, 2
    radius = 3 if name.endswith("r3") else 4
    f1 = _rand(rng, B, H, W, C)
    pyr = build_feature_pyramid(_rand(rng, B, H, W, C), L)
    ys, xs = np.meshgrid(np.arange(H, dtype=np.float32),
                         np.arange(W, dtype=np.float32), indexing="ij")
    grid = np.stack([xs, ys], -1)[None]
    kind = name.rsplit("_", 1)[0]
    if kind in ("smooth", "no_rescale", "small_scratch"):
        flow = np.stack([1.7 * np.sin(xs / 5.0), 0.8 * np.cos(ys / 3.0)], -1)
    elif kind == "wild":        # every diagonal of every level is live
        flow = rng.uniform(-H, H, (B, H, W, 2))
    elif kind == "integer_cy":  # the neighbour diagonals' weight is exactly 0
        flow = np.stack([0.3 + 0 * xs, np.round(2 * np.sin(xs / 4.0))], -1)
    else:                       # above / below / straddling the image
        dy = {"above": -3.0 * H, "below": 3.0 * H, "straddle_top": -H + 1.5,
              "straddle_bottom": H - 2.5}[kind]
        flow = np.stack([0.4 * np.sin(ys), dy + 0.6 * np.cos(xs / 2.0)], -1)
    coords = jnp.asarray(grid + flow, jnp.float32)
    return f1, pyr, coords, radius, kind != "no_rescale"


@pytest.mark.parametrize("name", [
    "smooth_r4", "smooth_r3", "wild_r4", "wild_r3", "integer_cy_r4",
    "above_r3", "below_r4", "straddle_top_r4", "straddle_bottom_r3",
    "no_rescale_r3", "small_scratch_r4"])
def test_diagonal_sweep_bitexact_vs_dense_oracle(rng, name, monkeypatch):
    # The forward kernel leaves out exactly the (row, offset) pairs whose
    # hat weight is 0 for the whole query tile, so every accumulator gets
    # the same nonzero terms in the same order: bit-identical to the
    # dense sweep, in all three band modes ("off" sweeps every diagonal).
    # ``small_scratch``: a band scratch of 16 rows, so that the tiles in
    # the middle of the 24-row level take the dense sweep and the others
    # the diagonal one, inside one launch.
    from raft_tpu.ops import corr_pallas
    f1, pyr, coords, radius, rescale = _sweep_case(rng, name)
    with monkeypatch.context() as m:
        m.setattr(corr_pallas, "_fwd_kernel", _dense_fwd_kernel)
        want = np.asarray(_fused_interpret(radius, "off", rescale, "oracle")(
            f1, pyr, coords))
    which = "kernel"
    levels = corr_pallas._level_geometry([f2.shape[1:3] for f2 in pyr])
    if name.startswith("small_scratch"):
        monkeypatch.setattr(corr_pallas, "_BAND_ROWS", 16)
        which = "small_scratch"
        chunks = [corr_pallas._band_chunks(cy, radius, 24, 3) for cy in
                  coords[0, ..., 1].reshape(-1, 256)]
        fits = [int(hi - lo) <= 2 for lo, hi in chunks]
        assert any(fits) and not all(fits)
    else:           # the whole 24-row level is parked: no dense sweep
        assert corr_pallas._band_scratch_rows(levels, radius) == 24
    for band in ("dynamic", "static", "off"):
        got = _fused_interpret(radius, band, rescale, which)(f1, pyr, coords)
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=band)
    if "above" in name or "below" in name:
        assert not want.any()       # no row of the image is in reach
    else:
        ref = jnp.concatenate([
            windowed_correlation(f1, f2, coords / (2 ** l if rescale else 1),
                                 radius) for l, f2 in enumerate(pyr)], -1)
        np.testing.assert_allclose(want, np.asarray(ref), rtol=1e-5,
                                   atol=1e-5)
        assert np.abs(want).max() > 0.1


def _brute_force_pairs(cx_tile, cy_tile, h2l, w2pl, radius, window):
    """What the forward kernel folds for one tile at one level, by
    enumeration: ``dense`` is every offset of every row of the chunks
    ``_band_chunks`` gives; ``live`` the pairs among them that can carry
    a nonzero weight (a row of the image with ``floor(min cy) <= y - off
    <= ceil(max cy)``); ``diagonal`` every offset of every diagonal of
    the blocks the diagonal sweep steps through, or the dense count
    where the kernel keeps the dense sweep (band taller than the chunks
    of scratch holds, a level too short to hold one block of diagonals,
    or no fewer pairs by diagonals; a window always takes the diagonal
    sweep). The columns: those where
    some query's x-side weight, evaluated in float32 as the kernel does,
    is nonzero; the window of ``window = (xb, xw)`` from the block of the
    first of them holds them all, or the tile reads the level's width a
    window at a time; ``products``, ``xside`` and the folds follow."""
    from raft_tpu.ops import corr_pallas as cp
    xb, xw = window
    xs = np.arange(w2pl, dtype=np.float32)[:, None, None]
    offs = np.arange(-radius, radius + 1, dtype=np.float32)[None, :, None]
    reach = np.abs(xs - (cx_tile[None, None, :] + offs)) < 1
    cols = np.flatnonzero(reach.any(axis=(1, 2)))
    fits, windows = False, 1
    held = min(32, -(-h2l // 8) * 8) // 8
    if xw < w2pl:
        jb0 = min(cols[0] // xb, (w2pl - xw) // xb) if cols.size else 0
        fits = not cols.size or cols[-1] < jb0 * xb + xw
        windows = 1 if fits else -(-w2pl // xw)
    c_lo, c_hi = (int(v) for v in cp._band_chunks(
        jnp.asarray(cy_tile), radius, h2l, -(-h2l // 8)))
    rows = [y for c in range(c_lo, c_hi) for y in range(c * 8, c * 8 + 8)]
    offs = range(-radius, radius + 1)
    dense = len(rows) * len(offs)
    d_lo = max(int(np.floor(cy_tile.min())), -radius)
    d_hi = min(int(np.ceil(cy_tile.max())), h2l - 1 + radius)
    live = sum(1 for y in range(h2l) for off in offs
               if rows and d_lo <= y - off <= d_hi)
    stepped = [d for d0 in range(d_lo, d_hi + 1, cp._DIAG_BLOCK)
               for d in range(d0, d0 + cp._DIAG_BLOCK)]
    by_diagonals = len(stepped) * len(offs)
    takes = (c_hi - c_lo <= held and by_diagonals < dense
             and held * 8 >= len(offs) + cp._DIAG_BLOCK)
    if xw < w2pl:               # a window folds by diagonals, always
        takes = bool(rows)
    diagonal = by_diagonals if takes else dense
    return {"diagonal": diagonal * windows, "dense": dense * windows,
            "live": live, "tiles_diagonal": int(takes),
            "products": len(rows) * xw * windows, "xside": xw * windows,
            "tiles_windowed": int(fits)}


def _tiles(coords, tile):
    """The query tiles' coordinates (tiles, queries, 2), edge-padded:
    raster runs of ``tile`` queries, or ``(th, tw)`` rectangles."""
    b, h, w, _ = coords.shape
    if isinstance(tile, int):
        flat = coords.reshape(b, h * w, 2)
        flat = np.pad(flat, ((0, 0), (0, -(h * w) % tile), (0, 0)),
                      mode="edge")
        return flat.reshape(-1, tile, 2)
    th, tw = tile
    grid = np.pad(coords, ((0, 0), (0, -h % th), (0, -w % tw), (0, 0)),
                  mode="edge")
    return np.stack([grid[0, i:i + th, j:j + tw].reshape(-1, 2)
                     for i in range(0, grid.shape[1], th)
                     for j in range(0, grid.shape[2], tw)])


@pytest.mark.parametrize("case", ["two_rows", "spread", "outside", "r3",
                                  "tiled", "tiled_wild", "tiled_r3",
                                  "sintel"])
def test_sweep_stats_matches_enumeration(rng, case):
    from raft_tpu.ops import corr_pallas as cp
    from raft_tpu.ops.corr_pallas import sweep_stats
    radius = 3 if case.endswith("r3") else 4
    H, W, tile = 16, 64, 128                # a raster tile is two rows
    shapes = [(40, 64), (20, 32), (10, 16)]  # 40 > the 32 rows of scratch
    if case.startswith("tiled"):            # windows at two of 3 levels
        tile = cp._Tiling(8, 16, ((8, 40), (8, 24), (16, 16)))
    if case == "sintel":                    # the pass cells' grid
        H, W, tile = 55, 128, None
        shapes = [(55 >> l, 128 >> l) for l in range(4)]
    ys, xs = np.meshgrid(np.arange(H, dtype=np.float32),
                         np.arange(W, dtype=np.float32), indexing="ij")
    dy = {"two_rows": 0.25 + 0 * xs, "r3": 0.5 * np.sin(xs / 7.0),
          "spread": np.where(ys < 8, rng.uniform(-2, 2, (H, W)),
                             rng.uniform(-6, 26, (H, W))),
          "outside": 90.0 + np.cos(xs), "tiled": 1.5 * np.sin(xs / 9.0),
          "tiled_wild": np.where(xs < 32, np.cos(ys), rng.uniform(
              -20, 40, (H, W))), "tiled_r3": 0.7 * np.cos(ys / 4.0),
          "sintel": 2.0 * np.sin(xs / 11.0) + np.cos(ys / 7.0)}[case]
    dx = {"tiled": 1.3 * np.cos(ys / 5.0), "tiled_r3": -2.5 + 0 * xs,
          "sintel": 3.0 * np.cos(xs / 13.0)}.get(case, 0 * xs)
    if case == "tiled_wild":
        dx = np.where(xs < 32, 0.5, rng.uniform(-30, 30, (H, W)))
    coords = np.stack([xs + dx, ys + dy], -1)[None].astype(np.float32)
    got = sweep_stats(coords, shapes, radius, tile, channels=256)
    levels = cp._level_geometry(shapes)
    if tile is None:
        # the pass cells' 55 x 128 grid takes a tile of 8 x 32 queries,
        # whose level-0 products are under half a raster tile's
        tile = cp.choose_query_tile(H, W, levels, radius, 256)
        assert got["tile"] == [tile.th, tile.tw] == [8, 32]
        raster = sweep_stats(coords, shapes, radius, 256)
        assert (2 * got["levels"][0]["products"] / got["tiles"]
                < raster["levels"][0]["products"] / raster["tiles"])
        assert (got["levels"][0]["xside"] / got["tiles"]
                < raster["levels"][0]["xside"] / raster["tiles"] / 2)
    shape = tile if isinstance(tile, int) else (tile.th, tile.tw)
    tiles = _tiles(coords, shape)
    assert got["tiles"] == len(tiles) and got["tq"] == tiles.shape[1]
    windows = (tile.windows if not isinstance(tile, int)
               else [(w2pl, w2pl) for (_, _, w2pl) in levels])
    for l, ((h2l, _, w2pl), window) in enumerate(zip(levels, windows)):
        s = np.float32(1 / 2 ** l)
        want = [_brute_force_pairs(t[:, 0] * s, t[:, 1] * s, h2l, w2pl,
                                   radius, window)
                for t in tiles]
        assert got["levels"][l] == {
            key: sum(w[key] for w in want) for key in want[0]}
        assert got["levels"][l]["live"] <= got["levels"][l]["diagonal"]
        if window[1] == w2pl:   # a whole width's diagonals never cost more
            assert got["levels"][l]["diagonal"] <= got["levels"][l]["dense"]
    for key in ("diagonal", "dense", "live", "products", "xside"):
        assert got[key] == sum(v[key] for v in got["levels"])
    if case == "two_rows":
        # by hand, level 0: cy spans [y + .25, y + 1.25], so diagonals
        # y .. y + 2: two blocks of 2, 4 x 9 pairs a tile of which 3 x 9
        # are live (less the rows above the image: 4 + 3 + 2 at the
        # first tile, 2 + 1 at the second), where the dense sweep folds
        # 2 or 3 chunks: 144 or 216
        assert got["levels"][0]["diagonal"] == 8 * 36
        assert got["levels"][0]["live"] == 8 * 27 - (4 + 3 + 2) - (2 + 1)
        assert got["levels"][0]["tiles_diagonal"] == 8
    if case == "spread":        # the lower tiles' bands pass the scratch
        assert got["levels"][0]["tiles_diagonal"] == 4
    if case == "outside":       # below every level: nothing to fold
        assert got["diagonal"] == 0 and got["live"] == 0
    if case == "tiled_wild":    # the wild half reads whole widths
        assert 0 < got["levels"][0]["tiles_windowed"] < got["tiles"]
    elif case.startswith("tiled"):
        assert got["levels"][0]["tiles_windowed"] == got["tiles"]
