from raft_tpu.models.raft import RAFT  # noqa: F401
from raft_tpu.models.ours import SparseRAFT  # noqa: F401
from raft_tpu.models.backbone import (  # noqa: F401
    Backbone, FrozenBatchNorm, Joiner, PositionEmbeddingLearned,
    PositionEmbeddingSine, ResNet50, build_backbone)
from raft_tpu.models.deformable import (  # noqa: F401
    DeformableTransformer, DeformableTransformerDecoder,
    DeformableTransformerDecoderLayer, DeformableTransformerEncoder,
    DeformableTransformerEncoderLayer, MSDeformAttn)
from raft_tpu.models.relative import (  # noqa: F401
    MultiHeadAttentionLayer, RelativePosition,
    RelativeTransformerDecoderLayer)
