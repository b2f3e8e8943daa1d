"""Model zoo covering the BASELINE.md config milestones.

1. LeNet-5 (MNIST, static-graph milestone) — lenet.py
2. ResNet-50 (ImageNet, dygraph milestone) — resnet.py
3. Transformer (WMT14 seq2seq milestone) — transformer.py
4. BERT/ERNIE-base pretrain (flagship, north-star metric) — bert.py

All models are dygraph Layers that also build static Programs (the layer
stack dispatches per mode), so one definition serves both executors.
"""

from .bert import BertConfig, BertForPretraining, BertModel  # noqa: F401
from .moe import MoEFFN  # noqa: F401
from .moe_decoder import (  # noqa: F401
    GatedExperts,
    MoEDecoderConfig,
    MoEDecoderLM,
)
from .lenet import LeNet5  # noqa: F401
from .mobilenet import MobileNetV1, mobilenet_v1  # noqa: F401
from .resnet import ResNet, resnet18, resnet34, resnet50, resnet101  # noqa: F401
from .transformer import Transformer, TransformerConfig  # noqa: F401
from .transformer_lm import (  # noqa: F401
    TransformerLM,
    TransformerLMBlock,
    TransformerLMConfig,
)
from .vgg import VGG, vgg16, vgg19  # noqa: F401
