"""raft_tpu — a TPU-native (JAX / XLA / Pallas) optical-flow framework.

Re-designed from scratch with the capabilities of the reference RAFT fork
(damien911224/RAFT): the canonical RAFT recurrent-refinement optical flow
model (ECCV 2020), a sparse-keypoint deformable-attention flow model family,
the FlyingChairs/FlyingThings/Sintel/KITTI/HD1K data stack, training /
evaluation / submission tooling, and memory-efficient on-demand correlation.

Design principles (TPU-first, not a port):
  * NHWC layouts everywhere; bfloat16 matmul policy with fp32 correlation.
  * The iterative refinement loop is a single ``lax.scan`` under ``jit``.
  * All-pairs correlation is one MXU einsum; the memory-efficient variant is
    a fused Pallas gather-dot kernel (the ``alt_cuda_corr`` equivalent).
  * Scaling is expressed with ``jax.sharding.Mesh`` + ``shard_map``: data
    parallelism across chips, spatial (context-parallel) sharding of the
    correlation volume for high-resolution inputs.
"""

__version__ = "0.1.0"

from raft_tpu.config import RAFTConfig  # noqa: F401
