"""Shared layout contract for Pallas custom-call and scan boundaries.

Why this module exists
----------------------
Round-5 profiling (BASELINE.md) showed that the *boundaries* of a custom
kernel can cost as much as its body: an XLA ``transpose``/``convert`` copy
at the custom-call edge measured ~12 ms/step (copy.257) until the corr
kernel learned to emit each output tile already in the consumer's axis
order and dtype (``RAFT_CORR_TOUT``).  That logic lived as ad-hoc branches
inside ``corr_pallas.py``; this module extracts it so every kernel —
corr, the fused GRU cell, and whatever comes next — inherits the win
instead of re-deriving it.

The contract (invariants for kernel authors)
--------------------------------------------
1. **Emit the consumer's dtype in the final store.**  Accumulate in
   float32 inside the kernel, then cast *once* in the store
   (``boundary_store``).  This is bit-identical to casting the float32
   result outside the kernel (one rounding either way —
   ``test_out_dtype_bitexact_vs_external_cast``) but deletes the XLA
   ``convert``+copy at the custom-call boundary.
2. **Emit the consumer's axis order in the final store.**  If the next op
   wants ``(..., N, F)`` and the kernel naturally produces ``(F, N)``
   tiles, transpose *in VMEM, per tile* (``boundary_store(...,
   transpose=True)``) rather than letting XLA materialize a full-array
   transpose in HBM.  Value-level transposes of VMEM-resident tiles are
   cheap; HBM relayouts are not.
3. **Tile the output over the axis the consumer iterates.**  Output
   BlockSpecs index the *tiled* axis with the grid's tile index and pin
   every other axis to 0 (``query_tiled_out``), so each block is written
   exactly once and XLA can alias the buffer straight into the consumer.
4. **Scan carries keep one layout for the whole scan.**  Arrays carried
   through ``lax.scan`` (the RAFT refinement loop: hidden state, flow,
   coords) must enter and leave a fused kernel in the *same* axis order
   and dtype — ``(B, H, W, C)``, channel-minor, the carry's dtype —
   otherwise XLA inserts a relayout copy on every iteration, which is
   precisely the HBM round-trip the kernel exists to delete.  A kernel
   that wants a different internal layout must reshape *inside* (VMEM),
   not at the boundary (HBM).
5. **Gradients are float32 at the boundary.**  ``out_dtype`` shapes only
   the forward value; custom-VJP backward outputs are emitted float32 and
   cast to the primal dtype by the wrapper (the corr kernel's contract).
6. **Producer→consumer handoff between chained kernels.**  When one
   kernel's output is the next kernel's input inside the same scan body
   (motion encoder → GRU), the producer must emit the exact tensor the
   consumer's input BlockSpec will window: the consumer's dtype
   (invariant 1), the consumer's axis order (invariant 2), tiled over
   the axis the consumer's grid iterates (invariant 3), with the packed
   channel layout the consumer's weight slices expect (the motion
   kernel's ``[out‖flow]`` concat is the GRU's x-part channel order).
   Declared with ``handoff_tiled_out`` so the intent is visible at the
   producer's ``out_specs``; the payoff is that the buffer between the
   two custom calls is a plain HBM array XLA can alias — zero
   relayout/convert ops at either boundary — and, for the fused
   single-launch step kernel (``step_pallas.py``), that the SAME packed
   value can stay VMEM-resident and never touch HBM at all: a handoff
   that honors this invariant is *fusable by construction*.

``corr_pallas.py`` (RAFT_CORR_TOUT), ``gru_pallas.py``,
``motion_pallas.py`` and ``step_pallas.py`` all build on these helpers;
the VMEM-budget side of kernel admission lives in ``raft_tpu.ops.vmem``.
The motion kernel is the reason invariant 4 grew into invariant 6: it
emits ``[out‖flow]`` in the layout and dtype the fused GRU consumes as
an x part, so no concat/relayout sits between the two custom calls
inside the scan body — and the round-10 fused step kernel collapses
that handoff into VMEM entirely.
"""

from __future__ import annotations

import re

import jax
from jax.experimental import pallas as pl

#: Stable ``pallas_call`` names, one per kernel. A name shows up in the
#: compiled program's text (in the ``op_name`` of its
#: ``tpu_custom_call``) and in profiler traces, so tools can tell which
#: kernels a program really lowered (``kernel_census``) without parsing
#: kernel bodies. A census takes the first name an ``op_name`` holds, so
#: ``attn_window`` stands before ``attn``, which its name begins with.
KERNEL_NAMES = {key: f"raft_{key}" for key in (
    "corr_fwd", "corr_bwd", "gru", "motion", "step", "msda_fwd", "msda_bwd",
    "expert_gmm", "attn_window", "attn")}


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = ")


def hlo_instructions(compiled_text: str):
    """The lines of a compiled program's text, an instruction whole on
    each: a kernel that hands Mosaic metadata of its own (the shipped
    block-sparse attention does) is printed over several lines, its
    ``op_name`` on the last; they are joined until the braces the
    instruction opened are closed."""
    held = None
    for line in compiled_text.splitlines():
        if held is not None:
            held += " " + line.strip()
        elif _INSTRUCTION.match(line):
            held = line
        else:
            yield line
            continue
        if held.count("{") <= held.count("}"):
            yield held
            held = None
    if held is not None:
        yield held


def kernel_census(compiled_text: str, names: dict = KERNEL_NAMES) -> dict:
    """Count the Mosaic kernels in a compiled program's text
    (``compiled.as_text()``): ``{kernel: n}`` over the keys of ``names``
    (``KERNEL_NAMES``), plus ``"unnamed"`` for any other
    ``tpu_custom_call``. Kernels that did not lower are absent — an
    interpret-mode or XLA-path program returns ``{}``."""
    counts: dict = {}
    for line in hlo_instructions(compiled_text):
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        # op_name is ".../<name>/pallas_call" in a forward program and
        # ".../transpose(jvp(<name>))/pallas_call" in a backward one.
        op_name = line.partition('op_name="')[2].partition('"')[0]
        key = next((k for k, name in names.items()
                    if name in op_name), "unnamed")
        counts[key] = counts.get(key, 0) + 1
    return counts


def boundary_store(out_ref, value, *, transpose: bool = False) -> None:
    """The canonical final store of a kernel output block.

    Casts ``value`` (typically a float32 accumulator) to the output ref's
    dtype — invariant 1 — and optionally transposes the last two axes in
    VMEM first — invariant 2.  ``out_ref`` is expected to be a
    ``(1, rows, cols)`` block ref (the leading 1 is the grid's batch
    axis); ``value`` is the 2-D tile value.
    """
    if transpose:
        value = value.T
    out_ref[0] = value.astype(out_ref.dtype)


def query_tiled_out(b: int, n: int, feat: int, tile: int, dtype, *,
                    consumer_major: bool = True):
    """Output BlockSpec + ShapeDtypeStruct for a kernel whose grid is
    ``(batch, n // tile)`` and whose per-tile result is ``tile`` rows of
    ``feat`` features (invariant 3).

    ``consumer_major=True`` (the contract default) lays the array out as
    ``(B, N, F)`` — the tiled axis major, features minor — which is what
    channel-minor NHWC consumers read without a relayout; the kernel pairs
    it with ``boundary_store(..., transpose=...)`` as needed.
    ``consumer_major=False`` is the legacy query-minor order ``(B, F, N)``
    (``RAFT_CORR_TOUT=0``), kept so the bit-exactness of the transposed
    store stays testable against it.

    Returns ``(block_spec, shape_struct)``.
    """
    if consumer_major:
        spec = pl.BlockSpec((1, tile, feat), lambda bi, ti: (bi, ti, 0))
        shape = jax.ShapeDtypeStruct((b, n, feat), dtype)
    else:
        spec = pl.BlockSpec((1, feat, tile), lambda bi, ti: (bi, 0, ti))
        shape = jax.ShapeDtypeStruct((b, feat, n), dtype)
    return spec, shape


def handoff_tiled_out(b: int, n: int, feat: int, tile: int, dtype):
    """Invariant 6's producer-side declaration: the out-spec of a kernel
    whose output IS the next kernel's input inside the same scan body
    (motion encoder → GRU).

    Mechanically this is ``query_tiled_out(..., consumer_major=True)``
    — the consumer-major order is not optional for a handoff — but the
    distinct name makes the producer→consumer contract greppable at the
    producer's ``out_specs``: dtype, axis order, tiling axis and packed
    channel layout all match what the consumer's input BlockSpec will
    window, so the interposed buffer is alias-able (two-launch chain)
    or elidable entirely (the fused ``step_pallas`` kernel).

    Returns ``(block_spec, shape_struct)``.
    """
    return query_tiled_out(b, n, feat, tile, dtype, consumer_major=True)
