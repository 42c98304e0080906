"""Compile the main path's Pallas kernels for a *described* TPU v5e.

Every other test runs the kernels in interpret mode on the CPU, which
cannot see what Mosaic refuses: a bf16 gate activation that fails
verification, or a tile that needs more scoped VMEM than the launch may
use. Here the installed TPU compiler lowers each kernel, called with
``interpret=False`` at RAFT-large Sintel/chairs shapes, for a chip that
is described and not attached. Nothing runs, so a pass says the kernel
compiles — not that it is right or fast; ``chip_smoke.py`` runs them.

This is the only file that describes the chip, and it does so inside a
module-scoped fixture: only the worker that is given this file loads the
TPU library, and only once a test of it has started.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from raft_tpu.ops import (corr_pallas, gru_pallas, motion_pallas,
                          msda_pallas, step_pallas, vmem)
from raft_tpu.ops.layout import kernel_census

SINTEL = (55, 128)      # 440x1024 / 8
CHAIRS = (46, 62)       # 368x496 / 8
HD1080 = (135, 240)     # 1080x1920 / 8
CC = 324                # 4 levels x (2*4+1)^2 corr channels
C = 128                 # hidden = context width
FNET = 256              # feature-encoder width


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-topology compile can be written to the persistent cache
    # but not read back; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _z(*shape):
    return jnp.zeros(shape, jnp.float32)


def _motion_mats():
    c1, c2, f1, f2, co = 256, 192, 128, 64, 126
    return motion_pallas.pack_weights(
        (_z(1, 1, CC, c1), _z(c1)), (_z(3, 3, c1, c2), _z(c2)),
        (_z(7, 7, 2, f1), _z(f1)), (_z(3, 3, f1, f2), _z(f2)),
        (_z(3, 3, c2 + f2, co), _z(co)))


def _gru_mats():
    return gru_pallas.pack_weights(
        tuple((_z(1, 5, 3 * C, C), _z(C)) for _ in range(3)),
        tuple((_z(5, 1, 3 * C, C), _z(C)) for _ in range(3)), C)


def _flow_head_mats():
    return step_pallas.pack_flow_head((_z(3, 3, C, 256), _z(256)),
                                      (_z(3, 3, 256, 2), _z(2)))


def _corr(dtype, grad, hw, batch, fnet=FNET, radius=4, features=jnp.float32):
    """The fused banded lookup (dynamic band, the default), forward or
    forward+backward, over a 4-level pooled pyramid. With bfloat16
    ``features`` (what mixed-precision inference hands it: the two pass
    cells' launches, RAFT-large's and RAFT-small's at ``fnet`` 128,
    radius 3) the forward holds the diagonal sweep's band scratch; over
    float32 features at Sintel there is no room for it and the launch
    keeps the dense sweep."""
    h, w = hw
    pyramid = tuple((batch, max(h >> l, 1), max(w >> l, 1), fnet)
                    for l in range(4))

    def fwd(f1, coords, *pyr):
        return corr_pallas.windowed_correlation_pallas_fused(
            f1, pyr, coords, radius, interpret=False, band="dynamic",
            mxu_dtype=jnp.dtype(dtype).name,
            out_dtype=dtype)

    def loss(f1, coords, *pyr):
        return jnp.sum(fwd(f1, coords, *pyr).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 2)) if grad else fwd
    shapes = [((batch, h, w, fnet), features),
              ((batch, h, w, 2), jnp.float32)]
    shapes += [(p, features) for p in pyramid]
    return fn, shapes, None


def _gru(dtype, hw, th=None):
    h, w = hw

    def fn(net, inp, motion, mats):
        return gru_pallas.sepconv_gru(net, (inp, motion), mats, dtype=dtype,
                                      interpret=False, th=th)

    shapes = [((2, h, w, C), dtype), ((2, h, w, C), dtype),
              ((2, h, w, C), jnp.float32), _gru_mats]
    d = jnp.dtype(dtype).itemsize
    return fn, shapes, lambda: gru_pallas.gru_vmem_parts(
        h, w, C, 2 * C, th or gru_pallas.choose_rows(h, w, C, 2 * C, d), d)


def _motion(dtype, hw):
    h, w = hw

    def fn(flow, corr, mats):
        return motion_pallas.motion_encoder(flow, corr, mats, dtype=dtype,
                                            interpret=False)

    shapes = [((2, h, w, 2), jnp.float32), ((2, h, w, CC), dtype),
              _motion_mats]
    d = jnp.dtype(dtype).itemsize
    return fn, shapes, lambda: motion_pallas.motion_vmem_parts(
        h, w, CC, motion_pallas.choose_rows(h, w, CC, d), d)


def _step(dtype, hw, flow_head, th=None):
    h, w = hw

    def fn(net, inp, corr, flow, mmats, gmats, *fmats):
        return step_pallas.fused_step(
            net, inp, corr, flow, mmats, gmats,
            fmats[0] if flow_head else None, dtype=dtype, interpret=False,
            th=th)

    shapes = [((2, h, w, C), dtype), ((2, h, w, C), dtype),
              ((2, h, w, CC), dtype), ((2, h, w, 2), dtype),
              _motion_mats, _gru_mats]
    if flow_head:
        shapes.append(_flow_head_mats)
    d = jnp.dtype(dtype).itemsize
    return fn, shapes, lambda: step_pallas.step_vmem_parts(
        w, th or step_pallas.choose_rows(h, w, CC, d, flow_head=flow_head),
        d, flow_head=flow_head)


def _msda_dense():
    """SparseRAFT's dense-query encoder at its 352x480 training
    resolution: every stride-8 token queries the 44x60 value map."""
    h, w, m, d, p = 44, 60, 8, 16, 4
    s = h * w

    def fn(value, loc, wts):
        return msda_pallas.ms_deform_attn_pallas(value, ((h, w),), loc, wts,
                                                 interpret=False)

    shapes = [((1, s, m, d), jnp.float32), ((1, s, m, 1, p, 2), jnp.float32),
              ((1, s, m, 1, p), jnp.float32)]
    return fn, shapes, None


def _expert_gmm(rows, tiling=None):
    """The expert layer's grouped products at LFM2-24B-A2B's widths
    (hidden 2048, expert width 1536), 8 of 64 experts held, over the
    sorted buffer of a 32768-token step's assignments: forward and both
    backward products."""
    from raft_tpu.ops import gmm

    def fn(lhs, rhs, sizes):
        def loss(lhs, rhs):
            kw = {} if tiling is None else {"tiling": tiling}
            out = gmm.expert_gmm(lhs, rhs, sizes, 8, impl="pallas",
                                 interpret=False, **kw)
            return out.astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1))(lhs, rhs)

    shapes = [((rows, 2048), jnp.bfloat16), ((8, 2048, 1536), jnp.bfloat16),
              ((64,), jnp.int32)]
    return fn, shapes, None


def _attn(seq, block=None):
    """Blocked causal attention at LFM2-24B-A2B's heads (32 query, 8
    key-value, 64 wide), two packed sequences: forward and backward."""
    from raft_tpu.ops import attention

    def fn(q, k, v, seg):
        def loss(q, k, v):
            kw = {} if block is None else {"block": block}
            out = attention.causal_attention(q, k, v, seg, scale=0.125,
                                             impl="pallas", **kw)
            return out.astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    shapes = [((2, 32, seq, 64), jnp.bfloat16),
              ((2, 8, seq, 64), jnp.bfloat16),
              ((2, 8, seq, 64), jnp.bfloat16), ((2, seq), jnp.int32)]
    return fn, shapes, None


BF16, F32 = jnp.bfloat16, jnp.float32

# (build, kernel expected in the compiled text, or ValueError if the
# repo's own preflight must refuse the launch before Mosaic sees it)
CASES = {
    "corr_fwd_bf16_sintel": (lambda: _corr(BF16, False, SINTEL, 2),
                             "corr_fwd"),
    "corr_fwd_bf16_features_sintel": (
        lambda: _corr(BF16, False, SINTEL, 32, features=BF16), "corr_fwd"),
    "corr_fwd_bf16_features_sintel_small": (
        lambda: _corr(BF16, False, SINTEL, 32, fnet=128, radius=3,
                      features=BF16), "corr_fwd"),
    "corr_fwd_f32_sintel": (lambda: _corr(F32, False, SINTEL, 2),
                            "corr_fwd"),
    "corr_bwd_f32_chairs_b8": (lambda: _corr(F32, True, CHAIRS, 8),
                               "corr_bwd"),
    "gru_bf16_sintel": (lambda: _gru(BF16, SINTEL), "gru"),
    "gru_f32_sintel": (lambda: _gru(F32, SINTEL), "gru"),
    "motion_bf16_sintel": (lambda: _motion(BF16, SINTEL), "motion"),
    "step_mg_bf16_chairs": (lambda: _step(BF16, CHAIRS, False), "step"),
    "step_mgf_bf16_sintel": (lambda: _step(BF16, SINTEL, True), "step"),
    "step_mgf_bf16_th16_sintel": (lambda: _step(BF16, SINTEL, True, th=16),
                                  "step"),
    "step_mgf_f32_sintel": (lambda: _step(F32, SINTEL, True), "step"),
    "msda_dense_352x480": (_msda_dense, "msda_fwd"),
    "expert_gmm_bf16_131072_rows": (lambda: _expert_gmm(131072),
                                    "expert_gmm"),
    "attn_bf16_8192": (lambda: _attn(8192), "attn"),
    # refused before Mosaic: tiles whose double buffers pass 13 MiB
    "refuse_expert_gmm_tiles_of_2048": (
        lambda: _expert_gmm(131072, (2048, 2048, 1536)), ValueError),
    "refuse_attn_blocks_of_2048": (lambda: _attn(8192, 2048), ValueError),
    # Refused by the static admission rule, before Mosaic: tiles Mosaic
    # was seen to take more than the limit for (f32 GRU TH=16 at 1080p:
    # 104.7 MiB) or could not place at all (the fused step's f32 'mgf'
    # TH=16 at 1080p: RESOURCE_EXHAUSTED under a 1 GiB limit. Its
    # streamed body fits both rungs at Sintel and, in bf16, at 1080p:
    # 40.8 / 70.8 MiB at TH 8 / 16).
    "refuse_gru_f32_th16_1080p": (lambda: _gru(F32, HD1080, th=16),
                                  ValueError),
    "refuse_step_mgf_f32_th16_1080p": (
        lambda: _step(F32, HD1080, True, th=16), ValueError),
}


def _abstract(shapes, sharding):
    out = []
    for item in shapes:
        if callable(item):      # a weight packer: float32 flax params
            out.append(jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sharding),
                jax.eval_shape(item)))
        else:
            shape, dtype = item
            out.append(jax.ShapeDtypeStruct(shape, dtype, sharding=sharding))
    return out


def _used_scoped_bytes(text: str) -> int:
    """What Mosaic put in scoped VMEM for the kernel, from the compiled
    text's ``used_scoped_memory_configs``."""
    sizes = [int(s) for line in text.splitlines()
             if "tpu_custom_call" in line
             for s in re.findall(
                 r'"used_scoped_memory_configs":\[\{"memory_space":"1",'
                 r'"offset":"0","size":"(\d+)"', line)]
    return max(sizes, default=0)


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    build, expect = CASES[case]
    fn, shapes, launched_parts = build()
    args = _abstract(shapes, one_chip)
    if expect is ValueError:
        with pytest.raises(ValueError, match="admission budget"):
            jax.jit(fn).lower(*args)
        return
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert kernel_census(text).get(expect), kernel_census(text)
    if launched_parts is not None:
        # The estimate the tile was admitted on covers what the compiler
        # really used, and that is inside the limit the launch carries.
        used = _used_scoped_bytes(text)
        est = vmem.total_bytes(launched_parts())
        assert 0 < used <= est <= vmem.SCAN_LIMIT_BYTES, (used, est)
