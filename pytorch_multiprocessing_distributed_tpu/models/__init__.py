"""Model zoo.

Flax re-designs of the reference's model layer (``model/resnet.py``).
The registry is the seam where the families the reference's CLI
advertises but never implemented (``--model dense|vgg``, reference
``main.py:24`` — selecting them raises ``UnboundLocalError`` at
``main.py:39-40``) and the scale-out families from BASELINE.md
(ViT, ConvNeXt) plug in as they land.

All models are NHWC (TPU-native layout), take a ``train`` flag, and carry
their BatchNorm cross-replica axis name so the same module is correct on
1 chip or a full pod.
"""

from .resnet import (
    BasicBlock,
    Bottleneck,
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from .registry import get_model, LM_MODELS, MODEL_REGISTRY
# importing the zoo modules also registers their CLI names
from .vgg import VGG, VGG11, VGG13, VGG16, VGG19
from .densenet import DenseNet, DenseNet121, DenseNetBC100
from .vit import ViT, ViT_B16, ViT_S16, ViT_Tiny
from .convnext import ConvNeXt, ConvNeXt_T, ConvNeXt_S, ConvNeXt_B, ConvNeXt_L
from .gpt import GPT, GPT_Small, GPT_Medium, GPT_Tiny
from .xing4 import Xing4, Xing4_29B_A4B, Xing4_Tiny
from .pangu_ultra_moe import (PanguUltraMoE, PanguUltraMoE_718B,
                              PanguUltraMoE_Tiny)
from .afmoe import Afmoe, Afmoe_Tiny, Trinity_Large_Preview
from .mimo_v2 import MiMoV2, MiMo_V2_5, MiMo_V2_Tiny
from .lfm2_moe import Lfm2Moe, LFM2_8B_A1B, Lfm2Moe_Tiny

__all__ = [
    "BasicBlock",
    "Bottleneck",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
    "get_model",
    "MODEL_REGISTRY",
    "VGG", "VGG11", "VGG13", "VGG16", "VGG19",
    "DenseNet", "DenseNet121", "DenseNetBC100",
    "ViT", "ViT_B16", "ViT_S16", "ViT_Tiny",
    "ConvNeXt", "ConvNeXt_T", "ConvNeXt_S", "ConvNeXt_B", "ConvNeXt_L",
    "GPT", "GPT_Small", "GPT_Medium", "GPT_Tiny", "LM_MODELS",
    "Xing4", "Xing4_29B_A4B", "Xing4_Tiny",
    "PanguUltraMoE", "PanguUltraMoE_718B", "PanguUltraMoE_Tiny",
    "Afmoe", "Afmoe_Tiny", "Trinity_Large_Preview",
    "MiMoV2", "MiMo_V2_5", "MiMo_V2_Tiny",
    "Lfm2Moe", "LFM2_8B_A1B", "Lfm2Moe_Tiny",
]

