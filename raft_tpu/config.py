"""Model / training configuration.

The reference keeps hyperparameters hard-coded inside module ``__init__``s and
mutates an argparse namespace as a grab-bag (reference ``core/raft.py:31-47``).
Here everything is an explicit, hashable dataclass so configs can be closed
over by ``jit`` without retracing surprises.
"""

from __future__ import annotations

import dataclasses
import os as _os
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class RAFTConfig:
    """Canonical RAFT hyperparameters.

    Mirrors reference ``core/raft.py:31-41``: the large model uses
    hidden/context dims 128/128, 4 correlation levels, radius 4; the small
    model 96/64, 4 levels, radius 3.
    """

    small: bool = False
    hidden_dim: int = 128
    context_dim: int = 128
    corr_levels: int = 4
    corr_radius: int = 4
    feature_dim: int = 256          # fnet output channels (reference raft.py:56)
    dropout: float = 0.0
    alternate_corr: bool = False    # on-demand (Pallas) correlation lookup
    # The fork added a 1/sqrt(dim) scale inside CorrBlock (reference
    # core/corr.py:61); canonical RAFT applies the same scale in its
    # all-pairs matmul. Kept switchable for exactness experiments.
    corr_scale: bool = True
    # Fork drift: the fork's coords_grid normalizes to [0,1] (reference
    # core/utils/utils.py:74-77) to serve the sigmoid-space "ours" family.
    # Canonical RAFT needs pixel coordinates. Pixel is the default.
    normalized_coords: bool = False
    # Mixed precision: run encoders/update block in bfloat16, keep the
    # correlation volume and flow arithmetic in float32.
    mixed_precision: bool = False
    # Storage dtype of the materialized correlation pyramid. The volume and
    # its avg-pools are always *computed* in float32 (the reference exempts
    # the volume from autocast, core/raft.py:100-103); this controls only
    # how the pyramid is stored between refinement iterations. "bfloat16"
    # halves the HBM footprint and read traffic of the framework's
    # dominant memory object. The default "auto" = bfloat16 iff
    # mixed_precision AND inference (test_mode): measured flow delta at
    # Sintel resolution is mean 0.0026 px / max 0.0093 px (BASELINE.md,
    # round 3) — far inside the 0.02 parity band — while *training* keeps
    # the reference's autocast-exempt f32 volume so gradient numerics
    # match train_mixed.sh semantics exactly. "float32" forces the old
    # default everywhere.
    corr_dtype: str = "auto"        # auto | float32 | bfloat16
    # Operand dtype of the on-demand (alternate_corr) Pallas kernel's
    # correlation matmuls. Accumulation is always float32; "bfloat16"
    # operands quadruple MXU throughput. The reference casts features to
    # f32 before EITHER correlation path (core/raft.py:103-104), so
    # "auto" mirrors corr_dtype's boundary exactly: bfloat16 iff
    # mixed_precision AND inference (test_mode). Training matmuls stay
    # f32 unless bfloat16 is explicitly requested, preserving reference
    # training numerics. No effect on the materialized all-pairs path.
    corr_mxu_dtype: str = "auto"    # float32 | bfloat16 | auto
    # Number of refinement iterations (train default 12; eval uses 24/32 —
    # reference train.py:445, evaluate.py:75,102,251).
    iters: int = 12

    def __post_init__(self):
        if self.corr_dtype not in ("auto", "float32", "bfloat16"):
            raise ValueError(
                f"corr_dtype must be 'auto', 'float32' or 'bfloat16', "
                f"got {self.corr_dtype!r}")
        if self.corr_mxu_dtype not in ("auto", "float32", "bfloat16"):
            raise ValueError(
                f"corr_mxu_dtype must be 'auto', 'float32' or 'bfloat16', "
                f"got {self.corr_mxu_dtype!r}")
        if self.alternate_corr and self.corr_dtype == "bfloat16":
            # The on-demand path never materializes a volume pyramid, so an
            # explicit bfloat16 request would be a silent no-op.
            raise ValueError(
                "corr_dtype='bfloat16' has no effect with alternate_corr "
                "(the on-demand path stores no correlation pyramid)")
        if not self.alternate_corr and self.corr_mxu_dtype == "bfloat16":
            # Mirror of the check above: the MXU-operand dtype only exists
            # on the on-demand kernel's matmuls.
            raise ValueError(
                "corr_mxu_dtype='bfloat16' has no effect without "
                "alternate_corr (the materialized path controls volume "
                "precision via corr_dtype)")

    @property
    def fnet_dim(self) -> int:
        return 128 if self.small else self.feature_dim

    @property
    def hdim(self) -> int:
        return 96 if self.small else self.hidden_dim

    @property
    def cdim(self) -> int:
        return 64 if self.small else self.context_dim

    @property
    def radius(self) -> int:
        return 3 if self.small else self.corr_radius

    def corr_storage(self, inference: bool):
        import jax.numpy as jnp
        if self.corr_dtype == "auto":
            return (jnp.bfloat16 if (self.mixed_precision and inference)
                    else jnp.float32)
        return jnp.dtype(self.corr_dtype)

    def corr_mxu(self, inference: bool) -> str:
        """Resolved MXU-operand dtype for the on-demand kernel's matmuls.
        Mirrors ``corr_storage``: "auto" is a bf16 *inference* lever only."""
        if self.corr_mxu_dtype == "auto":
            return ("bfloat16" if (self.mixed_precision and inference)
                    else "float32")
        return self.corr_mxu_dtype

    @staticmethod
    def large(**kw) -> "RAFTConfig":
        return RAFTConfig(small=False, **kw)

    @staticmethod
    def tiny(**kw) -> "RAFTConfig":
        """A miniature config for fast tests (not part of the reference)."""
        return RAFTConfig(small=True, **kw)


@dataclasses.dataclass(frozen=True)
class OursConfig:
    """The sparse-keypoint ("ours") model family hyperparameters.

    Mirrors the hard-coded values in reference ``core/ours.py:49-123``:
    d_model 128, 3 feature levels (strides 8/16/32), 6 outer iterations of a
    deformable decoder over 100 learned keypoint queries, fork-drifted
    2-level correlation inputs with radius 4.
    """

    base_channel: int = 64
    d_model: int = 128
    num_feature_levels: int = 3
    outer_iterations: int = 6
    num_keypoints: int = 100
    n_heads: int = 8
    n_points: int = 4
    dropout: float = 0.1
    corr_levels: int = 2            # fork default (reference core/corr.py:13)
    corr_radius: int = 4
    # On-demand correlation for the one-shot center-grid lookups: computes
    # each query's (2r+1)^2 window directly from (pooled) features instead
    # of materializing the all-pairs volume + avg-pool chain — the chain
    # the round-4 sparse_b8 profile measured at ~17% of the train step
    # (pure HBM bandwidth). Numerically identical (linearity; contract
    # tested incl. the fork's rescale=False drift). Default ON since the
    # round-4 on-chip A/B: train step 108.6 → 89.8 ms at b4 (+21%) and
    # 202.4 → 154.5 ms at b8 (+31%), stable over reps (TPU_EXTRAS
    # sparse_train alt arms + the recheck recorded in BASELINE.md);
    # device-time profile confirms the pool chain gone (85.0 → 62.3 ms
    # at b4). False restores the materialized volume path; the
    # RAFT_SPARSE_CORR=materialized env var does the same on every CLI
    # entry point without a source edit (--alternate_corr stays a
    # raft-family-only flag) — applied by the entry points via
    # sparse_corr_from_env(), NOT here: a frozen config's default must
    # be deterministic (equality, hashing, jit static-arg identity
    # must not depend on the environment — ADVICE r4 low-3).
    alternate_corr: bool = True
    mixed_precision: bool = False
    # >0 enables the ours_07 lineage: that many deformable-encoder layers
    # refine the motion and context token sets (separate stacks) before
    # the decoder loop (reference core/ours_07.py:97-109, :541-543).
    # 0 = the live ours.py, which carries the stacks commented out.
    encoder_iterations: int = 0

    @property
    def up_dim(self) -> int:
        return round(self.base_channel * 1.5)

    @property
    def level_channels(self):
        """Channels of the pyramid levels fed to the decoder (reference
        ``core/ours.py:57``: ``[96, 128, 192, 256][4 - levels:]``)."""
        c = self.base_channel
        return [round(c * 1.5), c * 2, round(c * 3), c * 4][
            4 - self.num_feature_levels:]


def sparse_corr_from_env() -> bool:
    """Entry-point-layer default for ``OursConfig.alternate_corr``:
    ``RAFT_SPARSE_CORR=materialized`` restores the materialized volume
    path on any CLI without a source edit. Read here — at the CLI layer,
    like ``RAFT_CORR_BAND`` — rather than in the frozen dataclass's
    default, so constructed configs stay deterministic (ADVICE r4
    low-3: env-dependent defaults break config equality/hash/jit
    static-arg identity across processes and checkpoint reloads)."""
    return _os.environ.get("RAFT_SPARSE_CORR", "ondemand") != "materialized"


#: The published layer pattern of LFM2-24B-A2B: attention at every
#: fourth layer from layer 2 on, gated short convolutions elsewhere.
_LFM2_LAYER_TYPES = tuple(
    "full_attention" if i % 4 == 2 else "conv" for i in range(40))


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """The ``lfm2_moe`` token family (LiquidAI LFM2-24B-A2B's
    ``config.json`` keys, defaults as published) plus this chip's share
    of an expert- and vocabulary-parallel deployment: the router always
    scores all ``num_experts``; ``experts_held`` of them, from
    ``expert_offset`` on, live here and only their terms are added up;
    ``vocab_held`` rows of the (tied) embedding live here and the
    logits and the loss are over them."""

    hidden_size: int = 2048
    intermediate_size: int = 11776        # dense SwiGLU (leading layers)
    moe_intermediate_size: int = 1536     # each expert's SwiGLU
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = _LFM2_LAYER_TYPES
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    vocab_size: int = 65536
    # the chip's share (all of it by default)
    experts_held: Optional[int] = None
    expert_offset: int = 0
    vocab_held: Optional[int] = None
    # bfloat16 operands with float32 accumulation; parameters, router
    # scores, norm statistics, softmax and loss stay float32
    mixed_precision: bool = True

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        bad = set(self.layer_types) - {"conv", "full_attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.num_attention_heads % self.num_key_value_heads or \
                self.hidden_size % self.num_attention_heads:
            raise ValueError("heads must divide hidden_size and be a "
                             "multiple of num_key_value_heads")
        if not 0 < self.held <= self.num_experts - self.expert_offset:
            raise ValueError(
                f"experts {self.expert_offset}.."
                f"{self.expert_offset + self.held} are not among the "
                f"router's {self.num_experts}")
        if not 0 < self.vocab <= self.vocab_size:
            raise ValueError(f"vocab_held {self.vocab} of {self.vocab_size}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def held(self) -> int:
        return (self.num_experts if self.experts_held is None
                else self.experts_held)

    @property
    def vocab(self) -> int:
        return self.vocab_size if self.vocab_held is None else self.vocab_held


#: The published layer pattern of granite-4.0-h-micro: attention at
#: layers 5, 15, 25 and 35, Mamba-2 elsewhere (a period of 10, 9:1).
_GRANITE_LAYER_TYPES = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40))


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The ``granitemoehybrid`` token family (ibm-granite
    granite-4.0-h-micro's ``config.json`` keys, defaults as published):
    Mamba-2 state-space mixers and grouped-query attention without
    positions, a dense SwiGLU in every layer (``num_local_experts`` is 0
    in the published model: no routed experts), four scalars on the
    residual path. ``vocab_held`` rows of the (tied) embedding live
    here; logits and loss are over them."""

    hidden_size: int = 2048
    shared_intermediate_size: int = 8192   # the dense SwiGLU
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = _GRANITE_LAYER_TYPES
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    vocab_size: int = 100352
    # the chip's share (all of it by default)
    vocab_held: Optional[int] = None
    # bfloat16 operands with float32 accumulation; parameters, norm
    # statistics, softmax, dt, the log-decays and the carried state
    # stay float32
    mixed_precision: bool = True

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.num_attention_heads % self.num_key_value_heads or \
                self.hidden_size % self.num_attention_heads:
            raise ValueError("heads must divide hidden_size and be a "
                             "multiple of num_key_value_heads")
        if self.mamba_n_heads * self.mamba_d_head != self.d_inner:
            raise ValueError(
                f"{self.mamba_n_heads} heads of {self.mamba_d_head} are "
                f"not mamba_expand x hidden_size = {self.d_inner}")
        if self.mamba_n_groups != 1:
            raise ValueError("one group of B and C serves all heads "
                             "(mamba_n_groups 1) in this family's scan")
        if not 0 < self.vocab <= self.vocab_size:
            raise ValueError(f"vocab_held {self.vocab} of {self.vocab_size}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def vocab(self) -> int:
        return self.vocab_size if self.vocab_held is None else self.vocab_held


#: The published layer pattern of Trinity-Mini: a global layer after
#: every three sliding-window ones (``global_attn_every_n_layers`` 4).
_AFMOE_LAYER_TYPES = tuple(
    "full_attention" if i % 4 == 3 else "sliding_attention"
    for i in range(32))


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """The ``afmoe`` token family (arcee-ai Trinity-Mini's
    ``config.json`` keys, defaults as published) plus this chip's share
    of an expert- and vocabulary-parallel deployment: sliding-window
    (rotary) and full (position-free) attention layers with a sigmoid
    gate on the heads' output, a dense SwiGLU in the leading layers,
    then ``num_experts`` routed experts beside ``num_shared_experts``
    shared ones. The router always scores all ``num_experts``;
    ``experts_held`` of them, from ``expert_offset`` on, live here and
    only their terms are added up (the shared expert's whole);
    ``vocab_held`` rows of the embedding and of the untied head live
    here and the logits and the loss are over them."""

    hidden_size: int = 2048
    intermediate_size: int = 6144         # dense SwiGLU (leading layers)
    moe_intermediate_size: int = 1024     # each expert's SwiGLU
    num_hidden_layers: int = 32
    layer_types: Tuple[str, ...] = _AFMOE_LAYER_TYPES
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    vocab_size: int = 200192
    # the chip's share (all of it by default)
    experts_held: Optional[int] = None
    expert_offset: int = 0
    vocab_held: Optional[int] = None
    # bfloat16 operands with float32 accumulation; parameters, router
    # scores, norm statistics, softmax, the attention gate's sigmoid,
    # residual stream, logits and loss stay float32
    mixed_precision: bool = True

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        bad = set(self.layer_types) - {"sliding_attention",
                                       "full_attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of "
                             "num_key_value_heads")
        if self.num_shared_experts not in (0, 1):
            raise ValueError("one shared expert, or none")
        if not 0 < self.held <= self.num_experts - self.expert_offset:
            raise ValueError(
                f"experts {self.expert_offset}.."
                f"{self.expert_offset + self.held} are not among the "
                f"router's {self.num_experts}")
        if not 0 < self.vocab <= self.vocab_size:
            raise ValueError(f"vocab_held {self.vocab} of {self.vocab_size}")

    @property
    def held(self) -> int:
        return (self.num_experts if self.experts_held is None
                else self.experts_held)

    @property
    def vocab(self) -> int:
        return self.vocab_size if self.vocab_held is None else self.vocab_held


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Training hyperparameters (reference ``train.py:431-452`` flags and
    ``train_mixed.sh`` / ``train_standard.sh`` schedules)."""

    name: str = "raft"
    stage: str = "chairs"
    # a row of raft_tpu/families.py: "raft" (canonical), "sparse" (the
    # fork's active "ours" trainer, reference train.py:19 → core/ours.py),
    # "lfm2_moe", "granitemoehybrid" or "afmoe" (packed token sequences)
    model_family: str = "raft"
    lr: float = 4e-4
    num_steps: int = 100000
    batch_size: int = 8
    image_size: Tuple[int, int] = (368, 496)
    # tokens a sequence; read by the token families only, which have no
    # ``image_size``
    seq_len: int = 8192
    wdecay: float = 1e-4
    epsilon: float = 1e-8
    clip: float = 1.0
    gamma: float = 0.8              # loss decay weight (train.py gamma flag)
    # "all" = reference loss semantics, .mean() over all pixels with
    # invalid zeroed (train.py:70); "valid" = divide by valid-pixel count
    # (density-independent opt-in; different dynamics on sparse KITTI/HD1K)
    loss_normalization: str = "all"
    add_noise: bool = False
    iters: int = 12
    val_freq: int = 5000            # reference train.py VAL_FREQ
    sum_freq: int = 100             # reference train.py SUM_FREQ
    scheduler: str = "onecycle"     # onecycle | step | cosine_warmup
    seed: int = 2022                # reference train.py:454-455
    # Auxiliary sparse-keypoint loss weight for the "ours" family, active
    # for the first 20k steps (reference train.py:379-383).
    sparse_lambda: float = 0.0
    sparse_lambda_steps: int = 20000
    # Non-finite step guard: a batch with NaN/Inf loss or grads has its
    # update suppressed in-graph (params unchanged, skipped_steps
    # counted); after this many CONSECUTIVE skips the run checkpoints
    # its (still finite) state and aborts — persistent divergence is an
    # operator problem, not something to grind through. 0 disables the
    # abort (skipping still applies).
    max_consecutive_skips: int = 20
    # Async (non-blocking) checkpointing: saves dispatch the orbax write
    # and the loop keeps stepping; the write is finalized, cross-host
    # vote-committed and only then made restore-visible at the next
    # barrier (next save point / preemption / divergence-abort / exit).
    # Hides multi-second save latency on big models. Off by default:
    # synchronous saves keep bit-identical pre-async on-disk behavior.
    async_checkpointing: bool = False
