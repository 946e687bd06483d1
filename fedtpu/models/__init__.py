"""fedtpu model zoo — flax.linen rebuilds of the reference CIFAR zoo
(``src/models/__init__.py:1-18``) plus the BASELINE parity models.

Constructor names mirror the reference exports so users of the reference find
the same surface: ``MobileNet()``, ``ResNet18()``, ``VGG('VGG19')``,
``ShuffleNetV2(1)``, ... Every architecture is also reachable by registry
string via :func:`create`.
"""

from fedtpu.models.registry import available, create, register

from fedtpu.models.mlp import MLP
from fedtpu.models.smallcnn import SmallCNN
from fedtpu.models.lenet import LeNet
from fedtpu.models.mobilenet import MobileNet
from fedtpu.models.mobilenetv2 import MobileNetV2
from fedtpu.models.resnet import ResNet18, ResNet34, ResNet50, ResNet101, ResNet152
from fedtpu.models.preact_resnet import (
    PreActResNet18,
    PreActResNet34,
    PreActResNet50,
    PreActResNet101,
    PreActResNet152,
)
from fedtpu.models.vgg import VGG
from fedtpu.models.googlenet import GoogLeNet
from fedtpu.models.densenet import (
    DenseNet121,
    DenseNet161,
    DenseNet169,
    DenseNet201,
    densenet_cifar,
)
from fedtpu.models.resnext import (
    ResNeXt29_2x64d,
    ResNeXt29_4x64d,
    ResNeXt29_8x64d,
    ResNeXt29_32x4d,
)
from fedtpu.models.senet import SENet18
from fedtpu.models.dpn import DPN26, DPN92
from fedtpu.models.shufflenet import ShuffleNetG2, ShuffleNetG3
from fedtpu.models.shufflenetv2 import ShuffleNetV2
from fedtpu.models.efficientnet import EfficientNetB0
from fedtpu.models.regnet import RegNetX_200MF, RegNetX_400MF, RegNetY_400MF
from fedtpu.models.pnasnet import PNASNetA, PNASNetB
from fedtpu.models.dla import DLA
from fedtpu.models.dla_simple import SimpleDLA
from fedtpu.models.joyai_llm_flash import JoyAILLMFlash
from fedtpu.models.qwen3_next import Qwen3Next
from fedtpu.models.lfm2_moe import Lfm2Moe
from fedtpu.models.laguna import Laguna
from fedtpu.models.nemotron_h import NemotronH
from fedtpu.models.granite_hybrid import GraniteHybrid

__all__ = [
    "available",
    "create",
    "register",
    "MLP",
    "SmallCNN",
    "LeNet",
    "MobileNet",
    "MobileNetV2",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
    "PreActResNet18",
    "PreActResNet34",
    "PreActResNet50",
    "PreActResNet101",
    "PreActResNet152",
    "VGG",
    "GoogLeNet",
    "DenseNet121",
    "DenseNet161",
    "DenseNet169",
    "DenseNet201",
    "densenet_cifar",
    "ResNeXt29_2x64d",
    "ResNeXt29_4x64d",
    "ResNeXt29_8x64d",
    "ResNeXt29_32x4d",
    "SENet18",
    "DPN26",
    "DPN92",
    "ShuffleNetG2",
    "ShuffleNetG3",
    "ShuffleNetV2",
    "EfficientNetB0",
    "RegNetX_200MF",
    "RegNetX_400MF",
    "RegNetY_400MF",
    "PNASNetA",
    "PNASNetB",
    "DLA",
    "SimpleDLA",
    "JoyAILLMFlash",
    "Qwen3Next",
    "Lfm2Moe",
    "Laguna",
    "NemotronH",
    "GraniteHybrid",
]
