"""Neural-net ops: normalization and losses.

TPU-native equivalents of the kernels the reference borrows from
torch/cuDNN (see SURVEY.md §2.2): cross-replica batch norm replaces
``torch.nn.SyncBatchNorm`` (reference ``main.py:43``), the loss replaces
``nn.CrossEntropyLoss`` (reference ``main.py:48``).
"""

from .batch_norm import SyncBatchNorm
from .losses import cross_entropy_loss
from .moe import (MoEMlp, dropless_experts, route_sigmoid_topk,
                  shard_expert_params)

__all__ = ["SyncBatchNorm", "cross_entropy_loss", "MoEMlp",
           "dropless_experts", "route_sigmoid_topk", "shard_expert_params"]
